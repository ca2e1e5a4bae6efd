"""Matrix exponentiation A^n — the paper's core contribution.

Implements, faithfully:
  * ``matpow_naive``   — the paper's "Naive GPU" baseline: n-1 sequential full
    matrix multiplications (one kernel launch per multiply in the 2012 OpenCL
    version; here one fused XLA loop body per multiply).
  * ``matpow_binary``  — the paper's "Our Approach": exponentiation by
    squaring, ceil(log2 n) squarings + popcount(n)-1 combines. Static ``n``
    unrolls at trace time (exactly log2(n) dots in the HLO).
  * ``matpow_binary_traced`` — same algorithm with a *traced* n via
    ``lax.while_loop`` so a single compiled program serves every power.

Beyond the paper:
  * everything stays on-device in ONE XLA program — the 2012 implementation
    still paid log2(n) kernel launches and host round-trips; here the host
    launches once.
  * ``backend="pallas"`` routes every multiply through the tiled Pallas TPU
    kernel (``repro.kernels``), the TPU adaptation of the paper's tiled
    OpenCL kernel.
  * ``backend="pallas_chain"`` runs the whole squaring/combine chain fused
    (``repro.kernels.ops.MatmulChain``): the operand is padded to block
    multiples ONCE at entry, every multiply runs block-divisible on the
    padded buffer (squarings through the single-ref ``square_pallas`` kernel
    with HBM buffer donation), and the result is un-padded once at exit —
    vs one pad/unpad/block-pick per multiply on the plain ``pallas`` route.
    ``"pallas_chain_interpret"`` is its CPU-validation twin.
  * ``matpow_sharded`` (see ``repro.core.distributed``) runs each squaring as
    a SUMMA collective matmul over a device mesh.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "matpow_naive",
    "matpow_binary",
    "matpow_binary_traced",
    "matmul_backend",
    "chain_for",
    "precision_for",
]


# backend name -> interpret flag for the fused chain-execution route.
# The ``fastmm`` pair runs the same chain with Strassen recursion per
# multiply (``kernels.fastmm``; tolerance-bounded, NOT bit-identical to the
# dense pair — see ``fastmm.error_budget``).
_CHAIN_BACKENDS = {"pallas_chain": False, "pallas_chain_interpret": True,
                   "pallas_fastmm": False, "pallas_fastmm_interpret": True}

#: Chain backends whose multiplies take the Strassen route.
_FAST_BACKENDS = frozenset({"pallas_fastmm", "pallas_fastmm_interpret"})

#: The precision of every product a chain backend computes: expm's Pade
#: products, each squaring and combine, and Strassen's leaves. On a TPU
#: v5e JAX's default runs an f32 product as one bf16 pass (8 significant
#: bits per input), and a chain of s squarings multiplies what each
#: product carries by about 2**s: the 2016-state tandem CTMC's e^{10 Q}
#: came out no better than bfloat16 (row-L1 error 0.66 to 11). ``HIGH`` is
#: three bf16 passes (XLA's bf16_3x; ``kernels.matmul.tile_dot`` in the
#: Pallas kernels): 1e-3 to 6e-3 there, at 8.2 ms of device time an
#: answer against 11.3 ms at ``HIGHEST`` (Mosaic's f32 contract
#: precision, 9e-5). The ``xla`` and per-call ``pallas`` backends keep
#: JAX's default, so their programs are unchanged. The Pallas kernels
#: apply it to f32 operands only (``kernels.matmul.kernel_precision``).
CHAIN_PRECISION = lax.Precision.HIGH


def precision_for(backend: str):
    """The dot precision ``backend`` computes its products at: None (JAX's
    default) or :data:`CHAIN_PRECISION` for the fused chain backends."""
    return CHAIN_PRECISION if backend in _CHAIN_BACKENDS else None


def matmul_backend(backend: str = "xla", precision=None) -> Callable:
    """Return a (a, b) -> a @ b callable for the requested backend.

    backend:
      * ``"xla"``    — jnp.matmul with fp32 accumulation (CPU/GPU/TPU).
      * ``"pallas"`` — the tiled Pallas TPU kernel (repro.kernels.ops.matmul).
      * ``"pallas_interpret"`` — same kernel, interpret mode (CPU validation).
      * ``"pallas_chain"`` / ``"pallas_chain_interpret"`` — the fused chain
        route. The matpow/expm entry points recognize these and hoist
        padding to the chain boundary via :func:`chain_for`; as a bare
        (a, b) callable this behaves like the matching per-call kernel.
      * ``"pallas_fastmm"`` / ``"pallas_fastmm_interpret"`` — the fused
        chain with Strassen recursion per multiply (above the autotuned
        crossover); as a bare callable this is ``fastmm.strassen_matmul``.

    The chain backends compute at :func:`precision_for` (``precision`` is
    the ``xla`` backend's alone).
    """
    if backend == "xla":
        def mm(a, b):
            return jnp.matmul(a, b, preferred_element_type=_accum_dtype(a.dtype),
                              precision=precision).astype(a.dtype)
        return mm
    if backend in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops
        return functools.partial(kops.matmul, interpret=(backend == "pallas_interpret"))
    if backend in _FAST_BACKENDS:
        from repro.kernels import fastmm as _fastmm
        return functools.partial(_fastmm.strassen_matmul,
                                 interpret=_CHAIN_BACKENDS[backend],
                                 precision=precision_for(backend))
    if backend in _CHAIN_BACKENDS:
        from repro.kernels import ops as kops
        return functools.partial(kops.matmul, interpret=_CHAIN_BACKENDS[backend],
                                 precision=precision_for(backend))
    raise ValueError(f"unknown matmul backend: {backend!r}")


def chain_for(a: jax.Array, backend: str, donate: bool = True):
    """A ``MatmulChain`` for ``a``'s shape when ``backend`` requests the
    fused route, else None (callers fall back to the per-multiply path).

    Pass ``donate=False`` when every squaring runs inside lax control flow
    (fori/while loops): donation only fires on eager calls, and a
    donate-enabled chain pays a defensive pad-time copy to protect the
    caller's buffer that traced-only chains do not need.
    """
    if backend not in _CHAIN_BACKENDS:
        return None
    from repro.kernels import ops as kops
    return kops.MatmulChain(a.shape[-1], a.dtype,
                            interpret=_CHAIN_BACKENDS[backend],
                            donate=donate,
                            fast=backend in _FAST_BACKENDS,
                            precision=precision_for(backend))


def _accum_dtype(dtype) -> jnp.dtype:
    d = jnp.dtype(dtype)
    if d in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16), jnp.dtype(jnp.float32)):
        return jnp.dtype(jnp.float32)
    return d  # f64 stays f64; ints stay ints


def _check_square(a: jax.Array) -> int:
    if a.ndim < 2 or a.shape[-1] != a.shape[-2]:
        raise ValueError(f"matpow needs square matrices, got shape {a.shape}")
    if a.shape[-1] < 1:
        # Every op on a 0-size matrix is an empty-array no-op, so the chain
        # would silently return identity-shaped garbage; fail loudly instead.
        raise ValueError(f"matpow needs matrices with n >= 1, got shape "
                         f"{a.shape}")
    return a.shape[-1]


def _eye_like(a: jax.Array) -> jax.Array:
    n = a.shape[-1]
    eye = jnp.eye(n, dtype=a.dtype)
    return jnp.broadcast_to(eye, a.shape)


def matpow_naive(a: jax.Array, n: int, *, backend: str = "xla") -> jax.Array:
    """A^n with n-1 sequential multiplies — the paper's Naive GPU baseline.

    Kept deliberately dumb (a fori_loop of full matmuls) so benchmarks compare
    the paper's two algorithms on equal kernel footing. ``n`` must be a static
    Python int >= 0. Supports batched stacks (..., m, m).
    """
    if not isinstance(n, int):
        raise TypeError("matpow_naive requires a static python int n")
    if n < 0:
        raise ValueError("negative powers not supported (matrix may be singular)")
    _check_square(a)
    if n == 0:
        return _eye_like(a)
    chain = chain_for(a, backend, donate=False)  # multiplies are all traced
    if chain is not None:
        ap = chain.pad(a)
        out = lax.fori_loop(0, n - 1, lambda _, acc: chain.mm(acc, ap), ap)
        return chain.unpad(out)
    mm = matmul_backend(backend)
    # lax.fori_loop keeps HLO O(1) in n, matching "launch the kernel N times".
    return lax.fori_loop(0, n - 1, lambda _, acc: mm(acc, a), a)


def matpow_binary(a: jax.Array, n: int, *, backend: str = "xla") -> jax.Array:
    """A^n by exponentiation-by-squaring — the paper's "Our Approach".

    Static ``n``: the squaring chain unrolls at trace time into exactly
    floor(log2 n) squarings plus popcount(n)-1 combines, each one matmul.
    Supports batched stacks (..., m, m).
    """
    if not isinstance(n, int):
        raise TypeError("matpow_binary requires a static python int n; "
                        "use matpow_binary_traced for traced n")
    if n < 0:
        raise ValueError("negative powers not supported")
    _check_square(a)
    if n == 0:
        return _eye_like(a)
    chain = chain_for(a, backend)
    if chain is not None:
        # chain.pad guarantees the returned buffer is the chain's own (copy
        # on identity-pad), so donated squarings never touch the caller's.
        return chain.unpad(_binary_chain_body(chain.pad(a), n, chain))
    mm = matmul_backend(backend)
    result = None
    base = a
    while True:
        if n & 1:
            result = base if result is None else mm(result, base)
        n >>= 1
        if n == 0:
            break
        base = mm(base, base)
    return result


def _binary_chain_body(base: jax.Array, n: int, chain) -> jax.Array:
    """Squaring/combine loop on the padded buffer. ``chain.square`` donates
    its input, so when ``result`` first aliases ``base`` (and squarings
    remain) it takes a cheap O(n^2) copy instead of sharing the buffer."""
    result = None
    while True:
        if n & 1:
            if result is None:
                result = base if n == 1 else jnp.copy(base)
            else:
                result = chain.mm(result, base)
        n >>= 1
        if n == 0:
            return result
        base = chain.square(base)


def matpow_binary_traced(a: jax.Array, n: jax.Array, *, backend: str = "xla",
                         max_bits: int = 32) -> jax.Array:
    """A^n with a *traced* integer n — one compiled program for every power.

    Uses ``lax.while_loop``s over the binary digits of ``n``; identical math
    to :func:`matpow_binary`. The result is seeded from the FIRST set bit
    (squaring past any trailing zeros first) rather than from the identity,
    so no call pays the identity @ base combine: exactly bit_length(n)-1
    squarings + popcount(n)-1 combines. ``max_bits`` only bounds loop trip
    count checks (the loops exit as soon as n reaches 0).
    """
    _check_square(a)
    # Squarings run inside while_loops (always traced) — donation never fires.
    chain = chain_for(a, backend, donate=False)
    if chain is not None:
        mm, square = chain.mm, chain.square
        ap = chain.pad(a)
    else:
        mm = matmul_backend(backend)
        square = lambda x: mm(x, x)
        ap = a
    # Clamp negative n to 0 (-> identity): the static siblings raise for
    # n < 0, but a traced value can't, and falling through the loops would
    # silently return A^1.
    n = jnp.maximum(jnp.asarray(n, dtype=jnp.int32), 0)

    # Phase 1: square through the trailing zero bits of n.
    def strip_cond(state):
        k, _ = state
        return jnp.logical_and(k > 0, (k & 1) == 0)

    def strip_body(state):
        k, base = state
        return (k >> 1, square(base))

    k, base = lax.while_loop(strip_cond, strip_body, (n, ap))
    # base now holds the first set bit's power A^(2^t) — the result seed.

    def cond(state):
        k, _, _ = state
        return k > 0

    def body(state):
        k, base, result = state
        base = square(base)
        result = lax.cond(k & 1, lambda: mm(result, base), lambda: result)
        return (k >> 1, base, result)

    _, _, result = lax.while_loop(cond, body, (k >> 1, base, base))
    result = jnp.where(n == 0, _eye_like(ap), result)
    return chain.unpad(result) if chain is not None else result
