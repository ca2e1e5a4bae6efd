"""Shared tolerance gate for the accuracy contract of every compute route.

The repo's accuracy contract (docs/serving.md) has exactly two classes:

  * BIT-EXACT routes — ``xla``, ``chain``, ``sharded`` (and every matpow
    entry point running the same squaring/combine sequence). Same math,
    same bits: asserted with ``assert_bit_identical``, never a tolerance.
  * TOLERANCE-BOUNDED routes — ``fastmm`` (Strassen recursion). Each
    Strassen level costs ~1 bit of accuracy, so the budget SCALES with the
    recursion depth: ``kernels.fastmm.error_budget`` takes the dense
    per-dtype floor (the same rtol/atol this suite has always used for
    dense-vs-f64 comparisons) and multiplies by ``2**levels``, with an
    eps·sqrt(n)·mults term so huge operands and long chains widen it.

Every test that compares a fast-route answer against a reference goes
through :func:`assert_within_budget` so the budget lives in ONE place
(``fastmm.DENSE_BUDGET`` + ``fastmm.error_budget``) instead of sprinkled
rtol literals; bit-exact assertions go through :func:`assert_bit_identical`
so a route silently drifting into "merely close" fails loudly.

Those floors are the CPU's, where every f32 product is exact to f32. On a
TPU v5e a product is off by its route's precision: one bf16 pass on the
``xla`` route, three on ``chain`` and ``fastmm``
(``fastmm.CHIP_PRODUCT_RTOL``, the per-product error a chip reads).
"""

import numpy as np
import jax.numpy as jnp

from repro.kernels import fastmm

#: Routes whose bucket answers must be bit-identical to per-matrix calls.
BIT_EXACT_ROUTES = ("xla", "chain", "sharded")

#: Routes bounded by ``fastmm.error_budget`` instead of bit-identity.
TOLERANCE_ROUTES = ("fastmm",)


def dense_budget(dtype):
    """(rtol, atol) for a dense (level-0) route vs an f64 reference —
    the suite's long-standing per-dtype floors, read from the single
    source of truth in ``kernels.fastmm.DENSE_BUDGET``."""
    return fastmm.error_budget(dtype, levels=0)


def strassen_budget(dtype, *, levels, n=1, mults=1):
    """(rtol, atol) for a Strassen answer: dense floor x 2**levels with
    the eps-scaled size/chain-length term. ``mults`` is the number of
    multiplies in the chain (log2 p squarings + combines for matpow)."""
    return fastmm.error_budget(dtype, levels=levels, n=n, mults=mults)


def assert_bit_identical(got, want, err_msg=""):
    """Same math must mean same bits (the dense-route contract).

    bf16 arrays go through f32 so numpy can compare them; the cast is
    exact, so equality is still bit-equality.
    """
    got, want = np.asarray(got), np.asarray(want)
    if got.dtype == jnp.bfloat16 or want.dtype == jnp.bfloat16:
        got, want = np.float32(got), np.float32(want)
    np.testing.assert_array_equal(got, want, err_msg=err_msg)


def assert_within_budget(got, ref, dtype=None, *, levels=0, n=None, mults=1,
                         err_msg=""):
    """Assert ``got`` matches ``ref`` within the route's error budget.

    ``levels=0`` is the dense gate (the floors every dense-vs-f64 check in
    this suite has always used); ``levels>0`` widens it per Strassen level.
    ``n`` defaults to the operand's trailing dimension; ``dtype`` to
    ``got``'s dtype.
    """
    got = np.asarray(got)
    if dtype is None:
        dtype = got.dtype
    if n is None:
        n = got.shape[-1] if got.ndim else 1
    rtol, atol = fastmm.error_budget(dtype, levels=levels, n=n, mults=mults)
    if np.asarray(got).dtype == jnp.bfloat16:
        got = np.float32(got)
    np.testing.assert_allclose(got, np.asarray(ref, np.float64),
                               rtol=rtol, atol=atol, err_msg=err_msg)


def matpow_mults(p):
    """Multiply count of the binary-exponentiation chain for power p."""
    if p <= 1:
        return 1
    return max(p.bit_length() - 1, 0) + max(bin(p).count("1") - 1, 0)
