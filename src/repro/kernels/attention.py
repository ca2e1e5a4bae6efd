"""Flash attention Pallas kernel (causal + sliding-window).

The LM substrate's second compute hot-spot after the paper's matmul: prefill
attention at 32k context. Online-softmax over KV tiles so the Sq x Skv score
matrix never exists in HBM — the same VMEM-tiling discipline the paper
applies to matmul, applied to attention (FlashAttention restructured for the
TPU memory hierarchy: KV tiles stream HBM->VMEM along a sequential grid
dimension, running (max, denom, acc) live in VMEM scratch).

Layout: q (Sq, D), k/v (Skv, D) — one (batch, head) slice; the ops-level
wrapper vmaps over batch/heads. Sliding-window masking prunes KV tiles that
are entirely outside the window (the index map still visits them, but the
mask zeroes their contribution; tile-skip via scalar prefetch is a TPU-only
optimization noted in EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

_NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 n_kv: int, causal: bool, window, scale: float,
                 block_q: int, block_k: int, sq: int, skv: int):
    qi = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32) * scale
    k = k_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    # Positions: queries are right-aligned against the KV axis (decode-style
    # alignment also covers prefill where sq == skv).
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
        + (skv - sq)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones(s.shape, dtype=bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]            # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)         # (bq, bk)
    correction = jnp.exp(m_prev - m_new)
    l_new = correction * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)

    v = v_ref[...].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == n_kv - 1)
    def _flush():
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)  # fully-masked query rows -> 0
        o_ref[...] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window=None, scale=None,
                    interpret: bool = False, block_q=None, block_k=None):
    """Flash attention over one (batch, head) slice. q: (Sq, D), k/v: (Skv, D).

    Tile selection — the attention face of the kernel-wide tuning subsystem:

      * ``block_q``/``block_k`` of ``None`` (the default) consult
        ``ops.pick_attn_blocks``, which returns a tuned pair from the
        persistent cache's ``attention`` namespace when one exists (and
        still satisfies the kernel invariants below), or a divisibility- and
        VMEM-safe heuristic otherwise. Resolution happens OUTSIDE the jitted
        kernel so a cache update is picked up on the next call rather than
        being baked into a stale jit entry.
      * Explicit ints are honored exactly: each block is clamped to its
        sequence length, and the clamped block must then divide that length
        — ``ValueError`` otherwise (the Pallas grid cannot cover a ragged
        remainder tile; route through ``ops.attention`` padding-free only
        with divisible shapes).

    VMEM working set per grid step is ``autotune.attn_vmem_footprint(block_q,
    block_k, d)``: double-buffered q/k/v tiles, the fp32 score tile, and the
    fp32 running (max, denom, acc) scratch. Blocks should be multiples of
    128 (MXU lane width) on real TPU hardware.
    """
    if block_q is None or block_k is None:
        from repro.kernels import ops
        auto_q, auto_k = ops.pick_attn_blocks(q.shape[0], k.shape[0],
                                              q.shape[1], dtype=q.dtype)
        block_q = auto_q if block_q is None else block_q
        block_k = auto_k if block_k is None else block_k
    return _flash_attention(q, k, v, causal=causal, window=window,
                            scale=scale, interpret=interpret,
                            block_q=int(block_q), block_k=int(block_k))


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "interpret", "block_q", "block_k"))
def _flash_attention(q, k, v, *, causal, window, scale, interpret, block_q,
                     block_k):
    sq, d = q.shape
    skv, dk = k.shape
    if dk != d or v.shape != (skv, d):
        raise ValueError(f"bad attention shapes q{q.shape} k{k.shape} v{v.shape}")
    scale = float(scale) if scale is not None else d ** -0.5

    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    if sq % block_q or skv % block_k:
        raise ValueError(f"seq lens ({sq},{skv}) not divisible by blocks "
                         f"({block_q},{block_k})")
    n_kv = skv // block_k
    grid = (sq // block_q, n_kv)

    kern = functools.partial(
        _attn_kernel, n_kv=n_kv, causal=causal, window=window, scale=scale,
        block_q=block_q, block_k=block_k, sq=sq, skv=skv)

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda qi, ki: (qi, 0)),
            pl.BlockSpec((block_k, d), lambda qi, ki: (ki, 0)),
            pl.BlockSpec((block_k, d), lambda qi, ki: (ki, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, d), lambda qi, ki: (qi, 0)),
        out_shape=jax.ShapeDtypeStruct((sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
