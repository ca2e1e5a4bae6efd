"""Tiled matmul Pallas kernel — TPU adaptation of the paper's OpenCL kernel.

The 2012 kernel stages 16x16 work-group tiles of A and B through 16 KB of
local (scratchpad) memory, accumulates in registers, and sweeps tile sizes
{4x4 ... 16x16}. The TPU translation (DESIGN.md §3):

  * work-group tile        -> BlockSpec tile, MXU-aligned (multiples of 128),
                              staged HBM->VMEM by the pallas_call pipeline
  * local-memory staging   -> automatic double-buffered DMA per grid step
  * register accumulator   -> fp32 VMEM scratch accumulator across the K grid
  * barriers               -> grid sequencing: K is an "arbitrary"
                              (sequential) dimension, M/N are "parallel"
  * float4 vectorization   -> (8,128) lane alignment of the block shapes
  * tile-size sweep        -> block_m/n/k are runtime-selectable; the sweep
                              lives in benchmarks/kernel_sweep.py

The kernel computes C[M,N] = A[M,K] @ B[K,N] with fp32 accumulation for
f32/bf16 inputs. Shapes must be block-divisible — ``ops.matmul`` pads and
un-pads arbitrary shapes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["matmul_kernel", "matmul_pallas", "square_kernel",
           "square_panel_kernel", "square_pallas", "square_tier",
           "panel_vmem_footprint",
           "DEFAULT_BLOCK", "SQUARE_VMEM_LIMIT", "SQUARE_PANEL_LIMIT"]

# Default tile: 512x512 output tile, K panels of 512. VMEM footprint
# (bf16 in, f32 acc): 2*512*512*2 + 512*512*4 = 2.0 MiB << ~16 MiB VMEM,
# leaving room for double buffering. All dims multiples of the 128-wide MXU.
DEFAULT_BLOCK = (512, 512, 512)


def matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int, out_dtype):
    """Grid point (i, j, k): accumulate A[i,k]-tile @ B[k,j]-tile into acc."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # MXU matmul on the VMEM-resident tiles; accumulate at fp32.
    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def _accum_dtype(dtype) -> jnp.dtype:
    d = jnp.dtype(dtype)
    if d in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16),
             jnp.dtype(jnp.float32)):
        return jnp.dtype(jnp.float32)
    return d


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret", "out_dtype"),
)
def matmul_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    block_m: int = DEFAULT_BLOCK[0],
    block_n: int = DEFAULT_BLOCK[1],
    block_k: int = DEFAULT_BLOCK[2],
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Block-divisible tiled matmul. See ``ops.matmul`` for arbitrary shapes."""
    if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
        raise ValueError(f"bad matmul shapes {a.shape} @ {b.shape}")
    m, k = a.shape
    _, n = b.shape
    if m % block_m or n % block_n or k % block_k:
        raise ValueError(
            f"shapes ({m},{k})x({k},{n}) not divisible by blocks "
            f"({block_m},{block_n},{block_k}); use ops.matmul")
    out_dtype = out_dtype or a.dtype
    n_k = k // block_k

    grid = (m // block_m, n // block_n, n_k)

    return pl.pallas_call(
        functools.partial(matmul_kernel, n_k=n_k, out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        # fp32 accumulator tile in VMEM (paper: per-work-group register tile).
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        # M/N tiles are independent; K must run sequentially (accumulator).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)


# Largest whole-operand footprint the single-ref square kernel will stage in
# VMEM. Above this, square_pallas moves to the panel-resident kernel.
SQUARE_VMEM_LIMIT = 8 * 1024 * 1024

# Largest operand the panel-resident square kernel covers: above this the
# row/column K-panels themselves stop fitting comfortably in VMEM and
# square_pallas falls back to the generic two-operand streaming kernel.
# Both thresholds are tunable cache entries — see autotune.square_tiers.
SQUARE_PANEL_LIMIT = 64 * 1024 * 1024


def panel_vmem_footprint(p: int, block_m: int, block_n: int,
                         itemsize: int = 2) -> int:
    """Working-set bytes of one panel-tier grid step: the double-buffered
    (block_m, P) row and (P, block_n) column panels plus the output tile.
    The panel tier is only usable when this fits VMEM — ``square_pallas``
    demotes to the two-operand streaming kernel otherwise."""
    return 2 * (block_m * p + p * block_n) * itemsize + block_m * block_n * 4


def square_tier(operand_bytes: int, vmem_limit: int = SQUARE_VMEM_LIMIT,
                panel_limit: int = SQUARE_PANEL_LIMIT) -> str:
    """Memory-tier policy for C = A @ A: which kernel serves this operand.

    ``"whole"``       — A fits ``vmem_limit``: stage the entire operand once
                        for both sides of the dot (``square_kernel``).
    ``"panel"``       — A fits ``panel_limit``: stage the K row-panel once
                        per row of output tiles (``square_panel_kernel``).
    ``"two_operand"`` — stream tiles of A twice through ``matmul_kernel``.

    Boundaries are inclusive: an operand exactly at a limit takes the more
    VMEM-resident tier.
    """
    if operand_bytes <= vmem_limit:
        return "whole"
    if operand_bytes <= panel_limit:
        return "panel"
    return "two_operand"


def square_kernel(a_ref, o_ref, *, block_m: int, block_n: int, out_dtype):
    """Grid point (i, j): C tile (i, j) of A @ A from ONE staged copy of A.

    The generic kernel streams two operand tiles per grid step; for the
    squaring chain both operands are the same matrix, so we stage the whole
    operand once (the index map is grid-invariant — the pipeline fetches it
    from HBM a single time) and slice the row/column panels for each output
    tile out of that one VMEM-resident ref. HBM traffic for the operand drops
    from 2 tile-reads per grid step to one read of A total.
    """
    i = pl.program_id(0)
    j = pl.program_id(1)
    row = a_ref[pl.ds(i * block_m, block_m), :]
    col = a_ref[:, pl.ds(j * block_n, block_n)]
    o_ref[...] = jnp.dot(
        row, col, preferred_element_type=jnp.float32
    ).astype(out_dtype)


def square_panel_kernel(row_ref, col_ref, o_ref, *, out_dtype):
    """Grid point (i, j): C tile (i, j) of A @ A from VMEM-resident K-panels.

    The middle memory tier between the whole-operand ``square_kernel`` and
    the fully streaming ``matmul_kernel``: both refs view the SAME matrix A,
    sliced as the (block_m, P) row panel and the (P, block_n) column panel
    of the output tile. The row panel's index map depends only on ``i`` and
    ``j`` is the innermost (sequential) grid dimension, so the pipeline
    stages each row panel HBM->VMEM once per row of output tiles — the
    paper's local-memory staging applied at panel granularity. Operand HBM
    traffic drops from 2 tile-reads per grid step to one panel-read per
    output tile plus one panel-read per output row.
    """
    o_ref[...] = jnp.dot(
        row_ref[...], col_ref[...], preferred_element_type=jnp.float32
    ).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret", "out_dtype",
                     "vmem_limit", "panel_limit"),
)
def square_pallas(
    a: jax.Array,
    *,
    block_m: int = DEFAULT_BLOCK[0],
    block_n: int = DEFAULT_BLOCK[1],
    block_k: int = DEFAULT_BLOCK[2],
    interpret: bool = False,
    out_dtype=None,
    vmem_limit: int = SQUARE_VMEM_LIMIT,
    panel_limit: int = SQUARE_PANEL_LIMIT,
) -> jax.Array:
    """C = A @ A for a block-divisible square A — the squaring-chain step.

    Kernel choice follows the ``square_tier`` memory policy on the operand's
    byte size: the whole-operand single-ref kernel below ``vmem_limit``, the
    panel-resident kernel (K-panels staged once per row of output tiles) up
    to ``panel_limit``, and the generic two-operand ``matmul_pallas`` above
    that. Both thresholds are static arguments so tuned tier entries from
    ``autotune.square_tiers`` flow through ``ops.square`` / ``MatmulChain``.

    Block-size constraints: the whole-operand and panel tiers need the shape
    divisible by ``block_m`` and ``block_n``; the two-operand tier needs
    ``block_k`` to divide too (checked by ``matmul_pallas``). A non-divisible
    shape raises ``ValueError`` — ``ops.square`` / ``ops.MatmulChain`` pad
    arbitrary shapes before calling in here.
    """
    if a.ndim != 2 or a.shape[0] != a.shape[1]:
        raise ValueError(f"square_pallas needs a square 2-D matrix, got {a.shape}")
    p = a.shape[0]
    out_dtype = out_dtype or a.dtype
    tier = square_tier(p * p * a.dtype.itemsize, vmem_limit, panel_limit)
    if tier == "panel" and panel_vmem_footprint(
            p, block_m, block_n, a.dtype.itemsize) > 2 * SQUARE_VMEM_LIMIT:
        # The operand qualifies for the panel tier but these block shapes
        # make the panels themselves bust VMEM — stream like the old path.
        tier = "two_operand"
    if tier == "two_operand":
        return matmul_pallas(a, a, block_m=block_m, block_n=block_n,
                             block_k=block_k, interpret=interpret,
                             out_dtype=out_dtype)
    if p % block_m or p % block_n:
        raise ValueError(
            f"shape ({p},{p}) not divisible by blocks ({block_m},{block_n}); "
            "use ops.MatmulChain / ops.matmul for arbitrary shapes")

    # whole tier: both grid dims independent. panel tier: j must run
    # sequentially innermost so each row panel is staged exactly once.
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel") if tier == "whole"
        else ("parallel", "arbitrary"))

    grid = (p // block_m, p // block_n)
    out_spec = pl.BlockSpec((block_m, block_n), lambda i, j: (i, j))
    out_shape = jax.ShapeDtypeStruct((p, p), out_dtype)

    if tier == "whole":
        return pl.pallas_call(
            functools.partial(square_kernel, block_m=block_m, block_n=block_n,
                              out_dtype=out_dtype),
            grid=grid,
            in_specs=[pl.BlockSpec((p, p), lambda i, j: (0, 0))],
            out_specs=out_spec,
            out_shape=out_shape,
            compiler_params=params,
            interpret=interpret,
        )(a)

    # Panel tier: the same array twice, viewed as row and column K-panels.
    return pl.pallas_call(
        functools.partial(square_panel_kernel, out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, p), lambda i, j: (i, 0)),
            pl.BlockSpec((p, block_n), lambda i, j: (0, j)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        compiler_params=params,
        interpret=interpret,
    )(a, a)
