"""Public jit'd wrappers around the Pallas kernels.

``matmul``      — arbitrary-shape tiled matmul: pads to block multiples,
                  strips the padding, vmaps over leading batch dims, and picks
                  block shapes that fit VMEM. On non-TPU backends it
                  takes the XLA dot (the Pallas TPU pipeline only lowers on
                  TPU; ``interpret=True`` forces the kernel body on CPU for
                  validation — used throughout tests/). See
                  ``pallas_supported``.
``square``      — C = A @ A through the single-ref squaring kernel, same
                  pad/dispatch contract as ``matmul``.
``MatmulChain`` — fused chain executor for repeated-multiply workloads
                  (matpow, expm): pads ONCE at entry, runs every multiply /
                  squaring on the block-divisible padded buffer (no per-call
                  pad/unpad/block-pick), un-pads once at exit, and donates the
                  squaring input so eager chains reuse HBM buffers in place.
``attention``   — flash attention wrapper with the same dispatch contract.
``dense_matmul``— the model-layer (..., K) @ (K, N) projection routed through
                  the tuned tiled kernel (``models.layers.dense`` calls it).
``pick_blocks`` — matmul tile selection: persistent autotune cache first
                  (``repro.kernels.autotune``), VMEM heuristic fallback.
``pick_attn_blocks``
                — the flash-attention (block_q, block_k) face of the same
                  tuning subsystem (``attention`` cache namespace).
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.matmul import (matmul_pallas, square_pallas, DEFAULT_BLOCK,
                                  SQUARE_VMEM_LIMIT)

__all__ = ["matmul", "square", "attention", "dense_matmul",
           "dense_routing_active", "pick_blocks", "pick_attn_blocks",
           "pad_to_blocks", "PaddedChain", "MatmulChain", "pallas_supported"]


def pallas_supported() -> bool:
    """True when the default backend can lower a TPU Pallas pipeline.

    Where it is False (the CPU runs of the test suite) ``matmul``,
    ``square``, ``MatmulChain`` and the Strassen leaves take the XLA dot
    instead, unless ``interpret=True`` asks for the kernel bodies in the
    Pallas interpreter. On a TPU this is True and that CPU route is never
    taken: ``chip_smoke.py`` at the repo root fails unless the executables
    the serving engine ran on the chip contain the Pallas kernels.
    """
    return jax.default_backend() == "tpu"


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def pick_blocks(m: int, n: int, k: int,
                vmem_budget_bytes=None,
                dtype=None, use_cache: bool = True):
    """Choose (block_m, block_n, block_k) for an (m, k) x (k, n) problem.

    Consults the persistent autotune cache first (the paper's measured tile
    sweep, see ``repro.kernels.autotune``); on a miss falls back to the
    static heuristic: largest 128-multiples <= the dim (capped at the
    defaults) whose working set fits the VMEM budget — the paper's "an
    appropriate TILE size is used based on the problem and local memory
    available" with 16 KB of OpenCL local memory replaced by VMEM. Both the
    budget and the footprint model are shared with the autotuner's scorer.
    """
    from repro.kernels import autotune
    if vmem_budget_bytes is None:
        vmem_budget_bytes = autotune.VMEM_BUDGET
    if use_cache:
        tuned = autotune.lookup(m, n, k, dtype=dtype)
        # A cache entry must still satisfy the kernel's hard invariants: MXU
        # 128-alignment and a working set that can exist in VMEM at all. The
        # footprint bound is 2x the modeled budget — measured-on-TPU winners
        # may legitimately exceed the conservative model, but a stale or
        # hand-edited entry that cannot compile must fall to the heuristic.
        itemsize = jnp.dtype(dtype).itemsize if dtype is not None else 2
        if tuned is not None and all(x % 128 == 0 for x in tuned) \
                and autotune.vmem_footprint(tuned, itemsize=itemsize) \
                <= 2 * vmem_budget_bytes:
            return tuned

    bm = min(DEFAULT_BLOCK[0], _round_up(m, 128))
    bn = min(DEFAULT_BLOCK[1], _round_up(n, 128))
    bk = min(DEFAULT_BLOCK[2], _round_up(k, 128))

    def footprint(bm, bn, bk):  # bf16 in, f32 acc, x2 double buffering on in
        return autotune.vmem_footprint((bm, bn, bk), itemsize=2)

    # Shrink K first (accumulator unaffected), then N, then M.
    while footprint(bm, bn, bk) > vmem_budget_bytes and bk > 128:
        bk //= 2
    while footprint(bm, bn, bk) > vmem_budget_bytes and bn > 128:
        bn //= 2
    while footprint(bm, bn, bk) > vmem_budget_bytes and bm > 128:
        bm //= 2
    return bm, bn, bk


def pick_attn_blocks(sq: int, skv: int, d: int,
                     vmem_budget_bytes=None,
                     dtype=None, use_cache: bool = True):
    """Choose (block_q, block_k) for a flash-attention (sq, skv, d) problem.

    The attention face of the tuning subsystem: consults the persistent
    cache's ``attention`` namespace first, then falls back to a heuristic
    mirroring the kernel's historical defaults (256/256) shrunk to divide
    the sequence lengths and fit the VMEM budget.

    Cache entries are re-validated against the kernel's hard invariants
    before being trusted (the same discipline as ``pick_blocks``): both
    blocks MXU 128-aligned, each dividing its (clamped) sequence length —
    ``flash_attention`` raises ``ValueError`` otherwise — and an
    ``attn_vmem_footprint`` within 2x the modeled budget (measured-on-TPU
    winners may exceed the conservative model; an uncompilable entry must
    not). Invalid entries fall through to the heuristic, never raise.

    For ragged lengths the heuristic uses the largest divisor <= 256; when
    only a degenerate divisor exists (near-prime lengths) it takes the whole
    axis as one tile if that fits 2x the budget and raises ``ValueError``
    (pad the sequence) otherwise — a sliver tile would fail Mosaic lowering
    on real TPUs anyway.
    """
    from repro.kernels import autotune
    if vmem_budget_bytes is None:
        vmem_budget_bytes = autotune.VMEM_BUDGET
    itemsize = jnp.dtype(dtype).itemsize if dtype is not None else 2
    if use_cache:
        tuned = autotune.lookup(sq, skv, d, dtype=dtype, kernel="attention")
        if (tuned is not None and len(tuned) == 2
                and all(x % 128 == 0 for x in tuned)
                and sq % min(tuned[0], sq) == 0
                and skv % min(tuned[1], skv) == 0
                and autotune.attn_vmem_footprint(
                    min(tuned[0], sq), min(tuned[1], skv), d,
                    itemsize=itemsize) <= 2 * vmem_budget_bytes):
            return tuned

    def footprint(bq, bk):
        return autotune.attn_vmem_footprint(bq, bk, d, itemsize=itemsize)

    def seq_block(s):
        b = min(256, s)
        if s % b == 0:
            return b
        # Ragged length: largest divisor <= 256 (trace-time only, s is
        # static), e.g. 333 -> 111. Degenerate divisors (near-prime s) take
        # the whole axis as one tile when that can exist in VMEM at all.
        b = max(x for x in range(1, min(256, s) + 1) if s % x == 0)
        return s if b < 16 < s else b

    bq, bk = seq_block(sq), seq_block(skv)
    # Shrink the KV tile first (more sequential steps but smaller score
    # tile), then the query tile — only along divisibility-preserving steps.
    while footprint(bq, bk) > vmem_budget_bytes and bk > 128 and skv % (bk // 2) == 0:
        bk //= 2
    while footprint(bq, bk) > vmem_budget_bytes and bq > 128 and sq % (bq // 2) == 0:
        bq //= 2
    if footprint(bq, bk) > 2 * vmem_budget_bytes:
        raise ValueError(
            f"no usable attention tiling for seq lens ({sq},{skv}) at "
            f"d={d}: the smallest divisor tiles bust VMEM; pad the "
            f"sequence to a multiple of 128")
    return bq, bk


def _square_blocks(n: int, dtype, blocks=None):
    """(blocks, padded_n) for an (n, n) squaring-chain problem.

    The padded size must divide by all three block dims (the output of one
    multiply feeds the next, so M = N = K). A pathological mixed tiling from
    the CACHE (e.g. 384s + 512s -> lcm 1536) would blow the padding up, so
    cache-sourced tiles fall back to the uncached heuristic in that case.
    Explicitly supplied ``blocks`` are always honored — a caller asking for
    a specific tiling (benchmarks, tests) must get that tiling.
    """
    if blocks is not None:
        bm, bn, bk = blocks
        return (bm, bn, bk), _round_up(n, math.lcm(bm, bn, bk))
    bm, bn, bk = pick_blocks(n, n, n, dtype=dtype)
    step = math.lcm(bm, bn, bk)
    if step > 2 * _round_up(n, 128):
        bm, bn, bk = pick_blocks(n, n, n, dtype=dtype, use_cache=False)
        step = math.lcm(bm, bn, bk)
    return (bm, bn, bk), _round_up(n, step)


def pad_to_blocks(a: jax.Array, block_m: int, block_n: int) -> jax.Array:
    """Zero-pad the trailing two dims of ``a`` up to block multiples.

    No-op (returns ``a`` unchanged) when already divisible. The chain
    executor calls this exactly once per chain; ``matmul`` once per operand.
    """
    m, n = a.shape[-2], a.shape[-1]
    mp, np_ = _round_up(m, block_m), _round_up(n, block_n)
    if (mp, np_) == (m, n):
        return a
    pad = [(0, 0)] * (a.ndim - 2) + [(0, mp - m), (0, np_ - n)]
    return jnp.pad(a, pad)


def matmul(a: jax.Array, b: jax.Array, *, interpret: bool = False,
           blocks=None, out_dtype=None, precision=None) -> jax.Array:
    """C = A @ B via the tiled Pallas kernel; arbitrary shapes and batching.

    a: (..., M, K), b: (..., K, N) (leading dims broadcast like jnp.matmul
    as long as they match exactly or are absent on one side).
    ``precision`` is each product's (``matmul.tile_dot``; XLA's on the
    portable path).
    """
    out_dtype = out_dtype or a.dtype
    if not (interpret or pallas_supported()):
        # Portable path: identical math (fp32 accumulation) via XLA.
        return _ref.matmul_ref(a, b, out_dtype=out_dtype,
                               precision=precision)

    # Normalize batching: strip matching leading dims via vmap.
    if a.ndim > 2 or b.ndim > 2:
        again = functools.partial(matmul, interpret=interpret, blocks=blocks,
                                  out_dtype=out_dtype, precision=precision)
        if a.ndim == b.ndim:
            return jax.vmap(again)(a, b)
        if a.ndim > 2 and b.ndim == 2:
            return jax.vmap(lambda x: again(x, b))(a)
        if b.ndim > 2 and a.ndim == 2:
            return jax.vmap(lambda y: again(a, y), out_axes=0)(b)
        raise ValueError(f"unsupported batch ranks {a.shape} @ {b.shape}")

    m, k = a.shape
    k2, n = b.shape
    bm, bn, bk = blocks or pick_blocks(m, n, k, dtype=a.dtype)

    a = pad_to_blocks(a, bm, bk)
    b = pad_to_blocks(b, bk, bn)

    out = matmul_pallas(a, b, block_m=bm, block_n=bn, block_k=bk,
                        interpret=interpret, out_dtype=out_dtype,
                        precision=precision)
    if out.shape != (m, n):
        out = out[:m, :n]
    return out


def _square_tiers(dtype):
    """Tier thresholds for this dtype — tuned cache entry or the defaults.

    Resolved OUTSIDE the jitted kernels (they take the limits as static
    arguments) so a cache update takes effect on the next call instead of
    being baked into a stale jit cache entry.
    """
    from repro.kernels import autotune
    return autotune.square_tiers(dtype=dtype)


def square(a: jax.Array, *, interpret: bool = False, blocks=None,
           out_dtype=None) -> jax.Array:
    """C = A @ A via the tiered squaring kernels; arbitrary square shapes.

    Kernel choice (whole-operand-resident / panel-resident / two-operand)
    follows the ``square_tier`` VMEM policy with thresholds resolved through
    the tuning cache (``autotune.square_tiers``).
    """
    out_dtype = out_dtype or a.dtype
    if not (interpret or pallas_supported()):
        return _ref.matmul_ref(a, a, out_dtype=out_dtype)
    if a.ndim > 2:
        return jax.vmap(lambda x: square(
            x, interpret=interpret, blocks=blocks, out_dtype=out_dtype))(a)
    n = a.shape[-1]
    (bm, bn, bk), padded_n = _square_blocks(n, a.dtype, blocks)
    vmem_limit, panel_limit = _square_tiers(a.dtype)
    padded = pad_to_blocks(a, padded_n, padded_n)
    out = square_pallas(padded, block_m=bm, block_n=bn, block_k=bk,
                        interpret=interpret, out_dtype=out_dtype,
                        vmem_limit=vmem_limit, panel_limit=panel_limit)
    if out.shape != a.shape:
        out = out[:n, :n]
    return out


# Donated squaring steps: called eagerly (one dispatch per squaring in a
# python-level chain), XLA reuses the operand's HBM buffer for the output.
# Inside an outer trace (fori/while loops, user jit) donation is inert and
# XLA's own buffer reuse applies. Callers must treat the argument as
# consumed — see MatmulChain.square.
@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret", "out_dtype",
                     "vmem_limit", "panel_limit", "precision"),
    donate_argnums=(0,),
)
def _square_step(a, *, block_m, block_n, block_k, interpret, out_dtype,
                 vmem_limit, panel_limit, precision):
    return square_pallas(a, block_m=block_m, block_n=block_n, block_k=block_k,
                         interpret=interpret, out_dtype=out_dtype,
                         vmem_limit=vmem_limit, panel_limit=panel_limit,
                         precision=precision)


@functools.partial(jax.jit, static_argnames=("precision",),
                   donate_argnums=(0,))
def _square_step_ref(a, *, precision):
    return _ref.matmul_ref(a, a, precision=precision)


# Donated Strassen squaring step (the chain's fast=True path, eager calls
# only — same donation story as _square_step). The whole recursion jits into
# ONE executable per (shape, config): the 7 sub-products and the combine
# adds fuse instead of dispatching per leaf.
@functools.partial(
    jax.jit,
    static_argnames=("levels", "crossover", "leaf_blocks", "interpret",
                     "out_dtype", "precision"),
    donate_argnums=(0,),
)
def _fast_square_step(a, *, levels, crossover, leaf_blocks, interpret,
                      out_dtype, precision):
    from repro.kernels import fastmm as _fastmm
    return _fastmm.strassen_square(a, levels=levels, crossover=crossover,
                                   leaf_blocks=leaf_blocks,
                                   interpret=interpret, out_dtype=out_dtype,
                                   precision=precision)


class PaddedChain:
    """Pad-once / unpad-once plumbing shared by the chain executors.

    A chain of k same-shape square multiplies needs exactly ONE pad at entry
    and ONE un-pad at exit — zero-padding is closed under multiplication
    ([[A,0],[0,0]]^2 = [[A^2,0],[0,0]]) — so every chain executor (the
    single-device ``MatmulChain`` here, the mesh-sharded
    ``core.distributed.ShardedMatmulChain``) shares this boundary contract:

        x = chain.pad(a)            # once: (..., n, n) -> (..., P, P)
        x = chain.square(x)         # k times on the padded buffer
        out = chain.unpad(result)   # once: strip back to (..., n, n)

    Subclasses set ``self.padded_n`` (the chain-invariant padded size P) in
    their ``__init__`` and implement ``square``/``mm``. ``donate`` records
    whether eager squarings consume their operand's buffer; ``pad`` honors it
    by never handing the caller's own buffer into the chain.
    """

    def __init__(self, n: int, dtype, *, donate: bool = True):
        self.n = int(n)
        if self.n < 1:
            # A 0-size chain would "work" — every pad/square/unpad is an
            # empty-array no-op — and hand back identity-shaped garbage.
            # Reject it here so every chain executor (single-device, batched,
            # sharded) fails loudly at construction.
            raise ValueError(f"chain matrices must have n >= 1, got n={n!r}")
        self.dtype = jnp.dtype(dtype)
        self.donate = bool(donate)
        self.padded_n = self.n

    # -- chain boundary ----------------------------------------------------
    def pad(self, a: jax.Array) -> jax.Array:
        """Zero-pad (..., n, n) -> (..., P, P). Called once per chain.

        When padding is a no-op (already divisible, or inactive chain) and
        donation is on, an EAGER caller gets a copy instead of its own array
        back: ``square`` consumes its operand, and the chain must never
        consume the caller's buffer. Under a trace the copy is elided by XLA.
        """
        if self.padded_n != self.n:
            return pad_to_blocks(a, self.padded_n, self.padded_n)
        if self.donate and not isinstance(a, jax.core.Tracer):
            return jnp.copy(a)
        return a

    def unpad(self, c: jax.Array) -> jax.Array:
        """Strip back to (..., n, n). Called once per chain."""
        if self.padded_n == self.n:
            return c
        return c[..., : self.n, : self.n]


class MatmulChain(PaddedChain):
    """Fused executor for a chain of same-shape square multiplies.

    The seed implementation paid ``ops.matmul``'s full entry cost on every
    multiply of a squaring chain: re-pick blocks, re-pad both operands,
    re-strip the padding, re-dispatch vmap. This object hoists all of that
    to the chain boundary (see :class:`PaddedChain`):

        chain = MatmulChain(a.shape[-1], a.dtype, interpret=...)
        x = chain.pad(a)            # once
        x = chain.square(x)         # k times, block-divisible fast path,
        ...                         #   donated buffers, single-ref kernel
        out = chain.unpad(result)   # once

    Off-TPU without ``interpret`` the Pallas pipeline cannot lower, so the
    chain degrades to the XLA dot with NO padding at all (``pad``/``unpad``
    are identity) — strictly no worse than the seed path there either.

    ``square(x)`` may donate ``x``'s buffer when called eagerly: treat the
    argument as consumed (copy first if you hold another reference to it).

    ``fast`` selects the Strassen route (``kernels.fastmm``): every
    ``square``/``mm`` recurses per the autotuned ``fastmm`` config
    (crossover, depth cap, leaf tiles) with the tuned dense kernels as
    leaves. ``fast=None`` auto-enables it exactly when the chain size
    exceeds the crossover; the default ``False`` keeps the dense routes'
    bit-exact contract — Strassen results are tolerance-bounded, not
    bit-identical (~1 bit per recursion level; see
    ``fastmm.error_budget``).

    ``precision`` is every multiply's, Strassen's leaves included
    (``matmul.tile_dot``; XLA's where the chain degrades to the XLA dot).
    None is JAX's default: one bf16 pass per f32 product on a TPU v5e.
    """

    def __init__(self, n: int, dtype, *, interpret: bool = False,
                 blocks=None, donate: bool = True, fast=False,
                 precision=None):
        super().__init__(n, dtype, donate=donate)
        self.interpret = bool(interpret)
        self.precision = precision
        self.active = self.interpret or pallas_supported()
        if self.active:
            self.blocks, self.padded_n = _square_blocks(self.n, self.dtype,
                                                        blocks)
            # VMEM tier thresholds fixed once per chain (tuned cache entry
            # or the defaults) — every squaring uses the same kernel tier.
            self.tiers = _square_tiers(self.dtype)
        else:
            self.blocks = None
            self.tiers = None
        # Strassen config resolved ONCE per chain (like blocks/tiers): the
        # whole chain recurses identically, so its error budget is a
        # function of one (crossover, levels) pair.
        if fast is not False:
            from repro.kernels import autotune
            self.fast_config = autotune.fastmm_config(self.dtype)
            if fast is None:          # auto: only where recursion can win
                fast = self.padded_n > self.fast_config[0]
        if fast is False:
            self.fast_config = None
        self.fast = bool(fast)

    @property
    def fast_levels(self) -> int:
        """Strassen levels each multiply of this chain actually recurses
        (0 for dense chains) — the ``levels`` input to
        ``fastmm.error_budget``."""
        if not self.fast:
            return 0
        from repro.kernels import fastmm as _fastmm
        crossover, levels, _ = self.fast_config
        return _fastmm.plan_levels(self.padded_n, levels, crossover)

    def _strassen_mm(self, x: jax.Array, y: jax.Array) -> jax.Array:
        from repro.kernels import fastmm as _fastmm
        crossover, levels, leaf_blocks = self.fast_config
        return _fastmm.strassen_matmul(x, y, levels=levels,
                                       crossover=crossover,
                                       leaf_blocks=leaf_blocks,
                                       interpret=self.interpret,
                                       out_dtype=self.dtype,
                                       precision=self.precision)

    # -- chain body (operands already padded) ------------------------------
    def mm(self, x: jax.Array, y: jax.Array) -> jax.Array:
        """x @ y on padded buffers — no pad/unpad, blocks fixed per chain."""
        if self.fast:
            return self._strassen_mm(x, y)
        if not self.active:
            return _ref.matmul_ref(x, y, out_dtype=self.dtype,
                                   precision=self.precision)
        if x.ndim > 2 or y.ndim > 2:
            return jax.vmap(self.mm)(x, y)
        bm, bn, bk = self.blocks
        return matmul_pallas(x, y, block_m=bm, block_n=bn, block_k=bk,
                             interpret=self.interpret, out_dtype=self.dtype,
                             precision=self.precision)

    def square(self, x: jax.Array) -> jax.Array:
        """x @ x via the single-ref kernel; CONSUMES x (buffer donation).

        The donated jit step only wraps EAGER calls — that is where donation
        frees the operand's HBM buffer for the output. Under an outer trace
        donation is inert and the extra pjit boundary would only block XLA
        fusion/inlining, so traced calls go straight to the kernel.
        """
        eager = not isinstance(x, jax.core.Tracer)
        if self.fast:
            if self.donate and eager:
                crossover, levels, leaf_blocks = self.fast_config
                return _fast_square_step(x, levels=levels,
                                         crossover=crossover,
                                         leaf_blocks=leaf_blocks,
                                         interpret=self.interpret,
                                         out_dtype=self.dtype,
                                         precision=self.precision)
            return self._strassen_mm(x, x)
        if not self.active:
            if self.donate and eager:
                return _square_step_ref(x, precision=self.precision)
            return _ref.matmul_ref(x, x, out_dtype=self.dtype,
                                   precision=self.precision)
        if x.ndim > 2:
            return jax.vmap(self.square)(x)
        bm, bn, bk = self.blocks
        vmem_limit, panel_limit = self.tiers
        if self.donate and eager:
            return _square_step(x, block_m=bm, block_n=bn, block_k=bk,
                                interpret=self.interpret, out_dtype=self.dtype,
                                vmem_limit=vmem_limit,
                                panel_limit=panel_limit,
                                precision=self.precision)
        return square_pallas(x, block_m=bm, block_n=bn, block_k=bk,
                             interpret=self.interpret, out_dtype=self.dtype,
                             vmem_limit=vmem_limit, panel_limit=panel_limit,
                             precision=self.precision)


def attention(q, k, v, *, causal: bool = True, window=None, scale=None,
              interpret: bool = False, block_q=None, block_k=None):
    """Flash attention (q:(Sq,D), k/v:(Skv,D)) with XLA fallback off-TPU.

    ``block_q``/``block_k`` default to ``None`` — auto-tuned through
    ``pick_attn_blocks`` (cache entry first, heuristic on a miss). Explicit
    ints are honored exactly and must divide the sequence lengths after
    clamping (``flash_attention`` raises ``ValueError`` otherwise).
    """
    if not (interpret or pallas_supported()):
        return _ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                        scale=scale)
    from repro.kernels.attention import flash_attention
    return flash_attention(q, k, v, causal=causal, window=window, scale=scale,
                           interpret=interpret, block_q=block_q,
                           block_k=block_k)


# ---------------------------------------------------------------------------
# Dense-layer routing: model serving inherits tuned tiles for free
# ---------------------------------------------------------------------------

def _dense_mode() -> str:
    """How ``dense_matmul`` dispatches: ``auto`` (Pallas when the backend
    lowers it, XLA einsum otherwise), ``interpret`` (force the kernel body
    on CPU — tests/validation), or ``off`` (always einsum)."""
    return os.environ.get("REPRO_DENSE_PALLAS", "auto")


def dense_routing_active() -> bool:
    """True when ``dense_matmul`` would route through the tiled kernel.

    ``auto`` mode requires a TPU backend AND a single device: GSPMD has no
    partitioning rule for the pallas_call, so on a multi-device mesh the
    tuned-kernel route would gather/replicate what the einsum partitions.
    Exposed so multi-matmul callers (``models.layers.moe_block``'s expert
    einsums) can keep their single fused einsum whenever the projection
    path would keep its einsum too, instead of splitting into per-expert
    matmuls that then each fall back anyway.
    """
    mode = _dense_mode()
    return (mode == "interpret"
            or (mode == "auto" and pallas_supported()
                and jax.device_count() == 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _dense_2d(x2, w, blocks, interpret):
    return matmul(x2, w, interpret=interpret, blocks=blocks)


def _dense_2d_fwd(x2, w, blocks, interpret):
    return _dense_2d(x2, w, blocks, interpret), (x2, w)


def _dense_2d_bwd(blocks, interpret, res, g):
    # Cotangents through the same tiled kernel; the transposed problems
    # re-pick their own (cached or heuristic) tiles.
    x2, w = res
    dx = matmul(g, w.T, interpret=interpret)
    dw = matmul(x2.T, g, interpret=interpret)
    return dx.astype(x2.dtype), dw.astype(w.dtype)


_dense_2d.defvjp(_dense_2d_fwd, _dense_2d_bwd)


def dense_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """y = x @ w for (..., K) activations against a (K, N) weight.

    The model-layer projection path (``models.layers.dense``): consults
    ``pick_blocks`` for the flattened (M, N, K) problem so serving inherits
    tuned tiles from the same cache the matpow kernels populate, then runs
    the tiled Pallas kernel (differentiable — cotangents route through the
    kernel too). Off-TPU (or with ``REPRO_DENSE_PALLAS=off``) this is
    exactly the XLA einsum the layer always used.

    ``auto`` mode additionally requires a single device: GSPMD has no
    partitioning rule for the pallas_call, so on a multi-device mesh the
    tuned-kernel route would gather/replicate what the einsum partitions —
    sharded training/serving keeps the einsum.
    """
    m = math.prod(x.shape[:-1])
    k = x.shape[-1]
    n = w.shape[-1]
    if not dense_routing_active() or m == 0:
        return jnp.einsum("...d,df->...f", x, w)
    blocks = pick_blocks(m, n, k, dtype=x.dtype)
    y = _dense_2d(x.reshape(m, k), w, tuple(blocks),
                  _dense_mode() == "interpret")
    return y.reshape(*x.shape[:-1], n)
