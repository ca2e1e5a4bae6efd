"""Stochastic/Markov-chain workloads on the squaring engine.

The paper motivates A^n with "financial, statistical applications"; the
canonical such workload is the Markov chain, and its two production query
shapes are NOT plain fixed-power matpow:

  * ``steady_state`` — the horizon is *unknown*: you square until the chain
    stops moving. A ``lax.while_loop`` squaring chain with a between-squaring
    residual test (``max_i sum_j |P^{2^k} - P^{2^{k-1}}|`` — the induced
    infinity norm) stops a well-mixed chain after ~6 squarings where a fixed
    p = 2^20 policy pays 20. Each live iteration is exactly one squaring on
    :class:`repro.kernels.ops.MatmulChain`'s padded buffer, so at equal
    squaring counts the result is bit-identical to
    ``matpow_binary(p, 2**k, backend=...)``.
  * ``evolve_distributions`` — B start distributions share ONE transition
    matrix over a known horizon. Evolving the (B, n) stack directly by the
    binary decomposition of the horizon replaces every O(n^3) *combine*
    multiply of the matpow route with an O(B n^2) vector–matrix product
    (the squarings stay, but only bit_length-1 of them, and the big-B
    regime falls back to the dense route via an autotuned threshold).

``validate_stochastic`` is the host-side admission gate for both (row sums,
non-negativity, optional renormalization).

Pure JAX below the validation gate; fp32 or fp64.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import matpow

__all__ = [
    "validate_stochastic",
    "markov_power",
    "steady_state",
    "evolve_distributions",
    "SteadyStateResult",
]


def _is_traced(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def validate_stochastic(p: jax.Array, *, tol: float = 1e-5,
                        renormalize: bool = False) -> jax.Array:
    """Check (or repair) a row-stochastic transition matrix, host-side.

    Accepts (..., n, n) stacks. Entries must be >= -tol and every row must
    sum to 1 within ``tol``; violations raise ``ValueError``. With
    ``renormalize=True`` the row-sum check is replaced by a repair: tiny
    negatives (within tol) are clipped to zero and each row is divided by
    its sum — rows whose sum is not strictly positive still raise, since no
    scaling can make them stochastic.

    This is an eager gate (it must concretize the checks): calling it on a
    traced array raises ``TypeError``. Inside jit, validate before tracing
    — the serving engine leaves this gate to its admission edge (a
    device-sync per submit would stall the daemon), so gate inputs here
    before ``submit("markov", ...)``.
    """
    p = jnp.asarray(p)
    if p.ndim < 2 or p.shape[-1] != p.shape[-2] or p.shape[-1] < 1:
        raise ValueError(f"transition matrices must be square with n >= 1, "
                         f"got shape {p.shape}")
    if _is_traced(p):
        raise TypeError("validate_stochastic is a host-side gate and cannot "
                        "run on traced values; validate before jit (the "
                        "serving engine validates at submit time)")
    min_entry = float(jnp.min(p))
    if min_entry < -tol:
        raise ValueError(f"stochastic matrix entries must be non-negative, "
                         f"found {min_entry:.3g} (< -tol = {-tol:g})")
    if renormalize:
        clipped = jnp.maximum(p, 0.0).astype(p.dtype)
        rows = jnp.sum(clipped, axis=-1, keepdims=True)
        min_row = float(jnp.min(rows))
        if min_row <= 0.0:
            raise ValueError(f"cannot renormalize: a row sums to "
                             f"{min_row:.3g} (must be > 0)")
        return (clipped / rows).astype(p.dtype)
    row_err = float(jnp.max(jnp.abs(jnp.sum(p, axis=-1) - 1.0)))
    if row_err > tol:
        raise ValueError(f"rows must sum to 1: max |row_sum - 1| = "
                         f"{row_err:.3g} > tol = {tol:g} (pass "
                         f"renormalize=True to repair)")
    return p


def markov_power(p: jax.Array, steps: int, *, backend: str = "xla",
                 validate: bool = True, validate_tol: float = 1e-5,
                 renormalize: bool = False) -> jax.Array:
    """P^steps for a validated transition matrix — fixed-horizon queries.

    ``validate_stochastic`` then :func:`repro.core.matpow.matpow_binary`
    on the requested backend. For unknown horizons use
    :func:`steady_state`; for batches of start distributions use
    :func:`evolve_distributions`.
    """
    p = jnp.asarray(p)
    if validate and not _is_traced(p):
        p = validate_stochastic(p, tol=validate_tol, renormalize=renormalize)
    return matpow.matpow_binary(p, steps, backend=backend)


class SteadyStateResult(NamedTuple):
    """:func:`steady_state`'s outputs.

    ``pi``         (n,) stationary distribution (row-mean of ``matrix``,
                   renormalized to sum exactly to 1 in its dtype).
    ``matrix``     (n, n) ``P^(2^squarings)`` — all rows ~= ``pi`` at
                   convergence; bit-identical to
                   ``matpow_binary(p, 2**squarings)`` on the same backend.
    ``squarings``  int32 — squarings actually paid (the early-exit win vs a
                   fixed policy; CI gates this < 20 on a well-mixed chain).
    ``residual``   infinity-norm of the last between-squaring delta — at or
                   below ``tol`` iff the loop exited by convergence rather
                   than by the ``max_squarings`` cap.
    """

    pi: jax.Array
    matrix: jax.Array
    squarings: jax.Array
    residual: jax.Array


def steady_state(p: jax.Array, *, tol: float = 1e-6,
                 max_squarings: int = 20, backend: str = "xla",
                 validate: bool = True, validate_tol: float = 1e-5,
                 renormalize: bool = False,
                 chain=None) -> SteadyStateResult:
    """Stationary distribution by convergence-aware repeated squaring.

    Squares P inside a ``lax.while_loop`` until the between-squaring
    residual ``‖P^{2^k} − P^{2^{k-1}}‖∞`` (max row-sum of absolute deltas)
    drops to ``tol`` or ``max_squarings`` is hit. The chain machinery is
    the same pad-once buffer :func:`repro.core.matpow.matpow_binary` uses
    (``chain_for(p, backend, donate=False)`` — donation is inert inside
    ``lax`` control flow), so zero rows of the padded buffer contribute 0
    to the residual and the padded-buffer test is exact.

    ``chain`` overrides the backend-derived chain with a caller-built
    executor sharing the pad/square/unpad contract — the serving engine
    passes a :class:`repro.core.distributed.ShardedMatmulChain` here to run
    the loop mesh-resident. Build overrides with ``donate=False``.

    Jit-safe below the validation gate (pass ``validate=False`` or eager
    input). Single matrix only — the engine maps batches per-member so each
    member keeps its own squaring count.
    """
    p = jnp.asarray(p)
    if p.ndim != 2 or p.shape[-1] != p.shape[-2] or p.shape[-1] < 1:
        raise ValueError(f"steady_state takes one (n, n) matrix with "
                         f"n >= 1, got shape {p.shape}; batches are served "
                         f"per-member (see serve.matfn op='markov')")
    if max_squarings < 1:
        raise ValueError(f"max_squarings must be >= 1, got {max_squarings}")
    if validate and not _is_traced(p):
        p = validate_stochastic(p, tol=validate_tol, renormalize=renormalize)

    if chain is None:
        chain = matpow.chain_for(p, backend, donate=False)
    if chain is not None:
        square = chain.square
        x0 = chain.pad(p)
    else:
        mm = matpow.matmul_backend(backend)
        square = lambda x: mm(x, x)
        x0 = p

    rdtype = jnp.float64 if p.dtype == jnp.float64 else jnp.float32

    def residual(nxt, cur):
        # Induced infinity norm of the delta. Padded rows are identically
        # zero in both buffers, so they contribute 0 — exact on the padded
        # buffer.
        delta = (nxt - cur).astype(rdtype)
        return jnp.max(jnp.sum(jnp.abs(delta), axis=-1))

    def cond(state):
        k, _, resid = state
        return jnp.logical_and(k < max_squarings, resid > tol)

    def body(state):
        k, x, _ = state
        nxt = square(x)
        return (k + 1, nxt, residual(nxt, x))

    k0 = jnp.asarray(0, jnp.int32)
    r0 = jnp.asarray(jnp.inf, rdtype)
    # Named for the device trace (metadata only; the program is unchanged).
    with jax.named_scope("markov.square"):
        k, x, resid = lax.while_loop(cond, body, (k0, x0, r0))

    m = chain.unpad(x) if chain is not None else x
    pi = jnp.mean(m, axis=0)
    pi = pi / jnp.sum(pi)
    return SteadyStateResult(pi=pi, matrix=m, squarings=k, residual=resid)


def evolve_distributions(dists: jax.Array, p: jax.Array, steps: int, *,
                         backend: str = "xla", validate: bool = True,
                         validate_tol: float = 1e-5,
                         renormalize: bool = False,
                         dense_threshold: Optional[float] = None) -> jax.Array:
    """Evolve B start distributions ``steps`` transitions under one P.

    Binary decomposition of the horizon applied to the (B, n) stack:
    LSB-first, each set bit costs one (B, n) x (n, n) vector–matrix product
    through the tuned ``dense_matmul`` tiles (O(B n^2)), and each remaining
    bit one P-squaring on the chain (O(n^3), ``bit_length(steps) - 1`` of
    them). Versus routing through ``matpow_binary`` + one final apply, the
    ``popcount - 1`` O(n^3) *combine* multiplies become O(B n^2) products —
    the win the `evolve` serving route exists for.

    When B grows past ``dense_threshold * n`` the extra vecmats outweigh the
    saved combines and the dense route (one ``markov_power``, one apply) is
    used instead. ``dense_threshold=None`` consults the autotune cache's
    ``markov`` namespace (``kernels.autotune.markov_evolve_threshold``,
    modeled default 1.0 — evolve while B <= n).

    ``dists`` is (n,) or (B, n); rows need not be validated (any
    non-negative weights evolve linearly), only ``p`` is gated. ``steps``
    must be a static python int >= 0. Returns the evolved stack in the
    promoted dtype of ``dists`` and ``p``.
    """
    d = jnp.asarray(dists)
    p = jnp.asarray(p)
    if not isinstance(steps, int) or isinstance(steps, bool):
        raise TypeError(f"steps must be a static python int, "
                        f"got {type(steps).__name__}")
    if steps < 0:
        raise ValueError(f"steps must be >= 0, got {steps}")
    single = d.ndim == 1
    if single:
        d = d[None, :]
    if d.ndim != 2:
        raise ValueError(f"dists must be (n,) or (B, n), got shape "
                         f"{jnp.asarray(dists).shape}")
    if p.ndim != 2 or p.shape[-1] != p.shape[-2] or p.shape[-1] < 1:
        raise ValueError(f"transition matrix must be (n, n) with n >= 1, "
                         f"got shape {p.shape}")
    n = p.shape[-1]
    if d.shape[-1] != n:
        raise ValueError(f"dists feature dim {d.shape[-1]} != matrix "
                         f"n = {n}")
    if validate and not _is_traced(p):
        p = validate_stochastic(p, tol=validate_tol, renormalize=renormalize)

    dtype = jnp.promote_types(d.dtype, p.dtype)
    d = d.astype(dtype)
    p = p.astype(dtype)
    if steps == 0:
        out = d
        return out[0] if single else out

    from repro.kernels import ops as kops

    b = d.shape[0]
    if dense_threshold is None:
        from repro.kernels import autotune
        dense_threshold = autotune.markov_evolve_threshold(dtype)
    if b > dense_threshold * n:
        # Big-B regime: combines are cheaper than B-row vecmats — take the
        # plain matpow route and apply once.
        m = markov_power(p, steps, backend=backend, validate=False)
        out = kops.dense_matmul(d, m)
        out = out[0] if single else out
        return out.astype(dtype)

    # Eager python loop over the bits of ``steps``: squarings donate their
    # buffer when the chain route is active (the loop is not traced here —
    # jit callers trace it, where donation is inert and XLA reuses buffers).
    chain = matpow.chain_for(p, backend)
    if chain is not None:
        base = chain.pad(p)
        pn = chain.padded_n
        if pn != n:
            d = jnp.pad(d, ((0, 0), (0, pn - n)))
        square = chain.square
    else:
        base = p
        pn = n
        mm = matpow.matmul_backend(backend)
        square = lambda x: mm(x, x)

    acc = d
    t = steps
    while True:
        if t & 1:
            # Row-vector step: d' = d @ P^(2^bit), tuned dense tiles.
            acc = kops.dense_matmul(acc, base)
        t >>= 1
        if t == 0:
            break
        base = square(base)

    if pn != n:
        acc = acc[:, :n]
    out = acc[0] if single else acc
    return out.astype(dtype)
