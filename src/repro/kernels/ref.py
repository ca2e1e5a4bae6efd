"""Pure-jnp oracles for every kernel in this package.

These are the correctness references the Pallas kernels are swept against in
``tests/test_kernels.py`` (shape x dtype grid, assert_allclose), mirroring
the paper's own "strictly compared with the sequential code results for any
precision problems" methodology.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["matmul_ref", "matmul_naive_ref", "flash_attention_ref"]


def matmul_ref(a: jax.Array, b: jax.Array, out_dtype=None,
               precision=None) -> jax.Array:
    """fp32-accumulating matmul oracle (the paper's sequential reference),
    at XLA's ``precision`` (None: JAX's default)."""
    out_dtype = out_dtype or a.dtype
    acc = jnp.dtype(jnp.float32) if jnp.dtype(a.dtype) != jnp.float64 else a.dtype
    return jnp.matmul(a, b, preferred_element_type=acc,
                      precision=precision).astype(out_dtype)


def matmul_naive_ref(a, b):
    """The paper's naive CPU triple loop, vectorized one level for sanity:
    row i of C computed as sum_k a[i,k] * b[k,:]. Used only in tiny tests —
    O(n^3) python-free but deliberately un-blocked."""
    def row(ai):
        return jnp.sum(ai[:, None] * b, axis=0)
    return jax.vmap(row)(a).astype(a.dtype)


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        window: int | None = None, scale: float | None = None):
    """Naive full-materialization attention oracle.

    q: (Sq, D), k/v: (Skv, D). fp32 softmax. Sliding window keeps keys with
    q_pos - window < k_pos <= q_pos (assuming aligned ends for prefill).
    """
    sq, d = q.shape
    skv = k.shape[0]
    scale = scale if scale is not None else d ** -0.5
    scores = jnp.einsum("qd,kd->qk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    q_pos = jnp.arange(sq)[:, None] + (skv - sq)  # right-aligned positions
    k_pos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), dtype=bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)  # fully-masked rows
    return jnp.einsum("qk,kd->qd", probs, v.astype(jnp.float32)).astype(q.dtype)
