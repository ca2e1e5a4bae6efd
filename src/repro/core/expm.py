"""Matrix exponential e^A by scaling-and-squaring — the scientific application.

The paper motivates A^n with "highly critical flight, CAD simulations to
financial, statistical applications"; the workhorse in those domains is the
matrix *exponential* e^A, whose standard algorithm (Higham 2005) is built on
exactly the paper's squaring chain: approximate e^{A/2^s} with a Pade
rational, then square s times. This module supplies it as a first-class user
of ``repro.core.matpow``'s squaring machinery.

Pure JAX (jit/vmap/grad-safe); fp32 or fp64.
"""

from __future__ import annotations

import functools
import operator
import types

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import matpow

__all__ = ["expm"]

# Pade-13 coefficients (Higham, "The Scaling and Squaring Method for the
# Matrix Exponential Revisited", SIAM J. Matrix Anal. 2005).
_PADE13 = (
    64764752532480000.0, 32382376266240000.0, 7771770303897600.0,
    1187353796428800.0, 129060195264000.0, 10559470521600.0, 670442572800.0,
    33522128640.0, 1323241920.0, 40840800.0, 960960.0, 16380.0, 182.0, 1.0,
)
_THETA13 = 5.371920351148152  # 1-norm threshold for Pade-13


def _pade13(a: jax.Array, ident: jax.Array, mm=operator.matmul):
    """Numerator and denominator terms of Pade-13 at ``a``; ``mm`` is the
    route's product (``a @ b`` at JAX's default precision unless given)."""
    b = _PADE13
    a2 = mm(a, a)
    a4 = mm(a2, a2)
    a6 = mm(a2, a4)
    u = mm(a, mm(a6, b[13] * a6 + b[11] * a4 + b[9] * a2)
           + b[7] * a6 + b[5] * a4 + b[3] * a2 + b[1] * ident)
    v = (mm(a6, b[12] * a6 + b[10] * a4 + b[8] * a2)
         + b[6] * a6 + b[4] * a4 + b[2] * a2 + b[0] * ident)
    return u, v


def expm(a: jax.Array, *, max_squarings: int = 32,
         backend: str = "xla") -> jax.Array:
    """Matrix exponential via Pade-13 + the paper's repeated-squaring chain.

    Supports batched stacks (..., n, n). The number of squarings is data
    dependent, so the squaring chain runs as a ``lax.fori_loop`` over
    ``max_squarings`` with a mask (keeps one compiled program; each masked
    squaring is a select, each live one a matmul — the log-depth structure
    of matpow_binary with data-dependent depth).

    ``backend`` selects the squaring-chain multiply route, same names as
    :func:`repro.core.matpow.matmul_backend`; ``"pallas_chain"`` pads the
    Pade result once, squares on the padded buffer through the single-ref
    kernel, and un-pads once at the end. The small fixed Pade polynomial
    (6 matmuls + one solve) stays on XLA — it is not a chain — at the
    backend's precision (``matpow.precision_for``): JAX's default for
    ``xla``, the chain's precision for the chain backends.
    """
    if a.ndim < 2 or a.shape[-1] != a.shape[-2]:
        raise ValueError(f"expm needs square matrices, got {a.shape}")
    if a.shape[-1] < 1:
        raise ValueError(f"expm needs matrices with n >= 1, got {a.shape}")
    return _scaling_and_squaring(
        a, max_squarings,
        functools.partial(jnp.matmul, precision=matpow.precision_for(backend)),
        functools.partial(_squaring_chain, backend=backend))


def _squaring_chain(r: jax.Array, backend: str):
    """The chain that squares ``r`` on one device: ``backend``'s fused
    chain, else its per-call product on the unpadded buffer."""
    # Squarings run inside the fori_loop (always traced) — donation never
    # fires, so skip the donate-enabled chain's defensive pad-time copy.
    chain = matpow.chain_for(r, backend, donate=False)
    if chain is None:
        mm = operator.matmul if backend == "xla" \
            else matpow.matmul_backend(backend)
        chain = types.SimpleNamespace(pad=lambda x: x, unpad=lambda x: x,
                                      square=lambda x: mm(x, x))
    return chain


def _scaling_and_squaring(a: jax.Array, max_squarings: int, mm,
                          squaring_chain) -> jax.Array:
    """e^a by scaling and squaring, for :func:`expm` and
    :func:`repro.core.distributed.expm_sharded` alike: scale by the
    1-norm, Pade-13 through ``mm``, one solve, then the masked squarings
    on ``squaring_chain(r)`` (an object with ``pad``, ``square`` and
    ``unpad``) between one pad and one unpad."""
    dtype = a.dtype
    compute = a.astype(jnp.float64 if dtype == jnp.float64 else jnp.float32)

    # Each phase runs under a named scope: the scope reaches every compiled
    # instruction's op_name metadata, so a device trace can split an
    # answer's time by phase whatever ops implement it. Scopes change
    # metadata only, never the compiled program.
    with jax.named_scope("expm.scale"):
        norm = jnp.linalg.norm(compute, ord=1, axis=(-2, -1), keepdims=True)
        # s = max(0, ceil(log2(norm / theta))) squarings, clipped to
        # max_squarings.
        s = jnp.maximum(0.0, jnp.ceil(jnp.log2(norm / _THETA13)))
        s = jnp.minimum(s, float(max_squarings)).astype(jnp.int32)
        scaled = compute / (2.0 ** s.astype(compute.dtype))

    ident = jnp.broadcast_to(jnp.eye(a.shape[-1], dtype=compute.dtype), compute.shape)
    with jax.named_scope("expm.pade"):
        u, v = _pade13(scaled, ident, mm)
    with jax.named_scope("expm.solve"):
        # r = (v - u)^-1 (v + u)
        r = jnp.linalg.solve(v - u, v + u)

    chain = squaring_chain(r)
    r = chain.pad(r)
    s_scalar = jnp.max(s)  # batched: square to the max, masking finished ones

    def body(i, r_cur):
        sq = chain.square(r_cur)
        # jnp.where, NOT multiply-masking: a finished member's wasted extra
        # squaring can overflow to inf in fp32, and 0 * inf = NaN would
        # corrupt its already-correct result. (i < s) broadcasts (..., 1, 1).
        return jnp.where(i < s, sq, r_cur)

    with jax.named_scope("expm.square"):
        r = lax.fori_loop(0, s_scalar, body, r)
    return chain.unpad(r).astype(dtype)
