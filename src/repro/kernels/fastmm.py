"""Strassen fast matmul over the tuned dense kernels.

The paper squeezes its speedups out of the dense multiply inside the
exponentiation chain; D'Alberto's heterogeneous fast-matmul work (PAPERS.md,
arXiv 1205.2927) shows the next multiplier is algorithmic: above a
hardware-dependent crossover size, one Strassen level trades 8 half-size
multiplies for 7 (12.5% of the FLOPs per level) at the price of O(n^2)
add/subtract traffic and ~1 bit of accuracy per level.

``strassen_matmul`` / ``strassen_square`` recurse at the JAX level:

  * leaves are the existing tuned dense kernels — ``ops.matmul`` routes to
    ``matmul_pallas`` with cached tiles on TPU (or in interpret mode) and to
    the fp32-accumulating XLA dot everywhere else, so the recursion composes
    with the whole tuning subsystem for free;
  * odd sub-problems pad to the next EVEN size per level (one zero row/col,
    sliced back after the combine) — the quadrant split needs nothing more,
    and the chain's pad-once buffer stays the only full-size padding;
  * recursion stops at the autotuned crossover (``fastmm`` cache namespace,
    ``autotune.fastmm_config``) or the depth cap, whichever comes first, and
    falls through to the dense leaf.

Accuracy contract: dense routes are bit-exact re-orderings of the same
kernel math; Strassen is NOT — its combine adds grow the forward-error
constant by roughly one bit per recursion level. ``error_budget`` is the
single source of truth for the resulting tolerance (consumed by
``tests/_tolerance.py`` and the CI gates in ``benchmarks/fastmm_bench.py``):
the suite's long-standing dense-vs-f64 floors scaled by ``2**levels``.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels import ref as _ref

__all__ = ["strassen_matmul", "strassen_square", "plan_levels",
           "error_budget", "DENSE_BUDGET", "CHIP_PRODUCT_RTOL"]

#: The dense routes' empirical vs-f64 tolerance floors (rtol, atol) per
#: dtype name — the same values ``tests/test_chains_property.py`` has gated
#: the chain with since PR 4. ``error_budget`` scales these by the Strassen
#: level count; dense comparisons use them as-is (levels=0).
DENSE_BUDGET = {
    "float64": (1e-12, 1e-14),
    "float32": (2e-3, 1e-5),
    "bfloat16": (0.15, 0.05),
}

#: What one f32 product is off by on a TPU v5e, per engine route: the
#: largest entry error of a 1024 x 1024 product of uniform [0, 1)
#: operands over the largest entry of the f64 product, read on the chip.
#: The ``xla`` route runs at JAX's default, one bf16 pass per product
#: (3.88e-4, XLA's dot and the Pallas kernel alike); ``chain`` and
#: ``fastmm`` at ``core.matpow.CHAIN_PRECISION``, three passes: 7.4e-7 in
#: the Pallas kernels, 7.7e-6 in Pade's XLA dots (bf16_3x), the larger
#: stated. The f32 floor above holds where every product is exact to f32
#: (the CPU, the Pallas interpreter at the default precision); a chip
#: answer's error starts from its route's entry here and grows with the
#: products it went through.
CHIP_PRODUCT_RTOL = {"xla": 3.9e-4, "chain": 7.7e-6, "fastmm": 7.7e-6}


def error_budget(dtype, *, levels: int = 0, n: int = 1,
                 mults: int = 1) -> tuple:
    """(rtol, atol) error budget vs an f64 reference for one route.

    ``levels=0`` is the dense budget (the suite's long-standing floors, with
    an eps*sqrt(n)*mults forward-error term for problems large or deep
    enough to exceed them); each Strassen level doubles both bounds — the
    documented ~1-bit-per-level loss. ``mults`` is the number of chained
    multiplies the result went through (a p-th power via binary powering
    does about ``log2(p)`` squarings plus the popcount-1 combines).
    """
    dt = jnp.dtype(dtype)
    eps = float(jnp.finfo(dt).eps)
    rtol0, atol0 = DENSE_BUDGET.get(dt.name, (2e-3, 1e-5))
    mults = max(int(mults), 1)
    growth = 2.0 ** max(int(levels), 0)
    rtol = max(rtol0, 16.0 * eps * math.sqrt(max(int(n), 1)) * mults) * growth
    atol = max(atol0, 16.0 * eps * mults) * growth
    return rtol, atol


def _resolve(dtype, levels, crossover, leaf_blocks):
    """Fill ``None`` knobs from the autotune ``fastmm`` namespace."""
    if levels is None or crossover is None or leaf_blocks is None:
        c_cfg, l_cfg, b_cfg = autotune.fastmm_config(dtype)
        levels = l_cfg if levels is None else levels
        crossover = c_cfg if crossover is None else crossover
        leaf_blocks = b_cfg if leaf_blocks is None else leaf_blocks
    return int(levels), int(crossover), leaf_blocks


def plan_levels(n: int, levels: Optional[int] = None,
                crossover: Optional[int] = None, dtype=None) -> int:
    """Recursion depth ``strassen_matmul`` will actually use for size n.

    Mirrors the recursion's stopping rule exactly (depth cap, crossover
    fall-through, n < 2 degenerate) so tests and benchmarks can compute the
    matching ``error_budget`` without re-deriving it.
    """
    levels, crossover, _ = _resolve(dtype, levels, crossover, ())
    n, used = int(n), 0
    while used < levels and n > crossover and n >= 2:
        n = (n + 1) // 2
        used += 1
    return used


def _strassen(a, b, levels: int, crossover: int, leaf: Callable):
    n = a.shape[-1]
    if levels <= 0 or n <= crossover or n < 2:
        return leaf(a, b)
    m = n + (n % 2)
    if m != n:                      # pad to the next even size, this level only
        pad = [(0, 0)] * (a.ndim - 2) + [(0, 1), (0, 1)]
        a = jnp.pad(a, pad)
        b = jnp.pad(b, pad)
    h = m // 2
    a11, a12 = a[..., :h, :h], a[..., :h, h:]
    a21, a22 = a[..., h:, :h], a[..., h:, h:]
    b11, b12 = b[..., :h, :h], b[..., :h, h:]
    b21, b22 = b[..., h:, :h], b[..., h:, h:]
    rec = lambda x, y: _strassen(x, y, levels - 1, crossover, leaf)
    m1 = rec(a11 + a22, b11 + b22)
    m2 = rec(a21 + a22, b11)
    m3 = rec(a11, b12 - b22)
    m4 = rec(a22, b21 - b11)
    m5 = rec(a11 + a12, b22)
    m6 = rec(a21 - a11, b11 + b12)
    m7 = rec(a12 - a22, b21 + b22)
    c = jnp.concatenate(
        [jnp.concatenate([m1 + m4 - m5 + m7, m3 + m5], axis=-1),
         jnp.concatenate([m2 + m4, m1 - m2 + m3 + m6], axis=-1)], axis=-2)
    if m != n:
        c = c[..., :n, :n]
    return c


def _default_leaf(interpret: bool, leaf_blocks, out_dtype,
                  precision) -> Callable:
    # ops.matmul is the whole dispatch story in one call: tuned Pallas tiles
    # on TPU / interpret, fp32-accumulating XLA dot everywhere else, vmap
    # over leading batch dims. Lazy import: ops lazily imports this module
    # for the chain's fast path.
    from repro.kernels import ops as kops
    return functools.partial(kops.matmul, interpret=interpret,
                             blocks=leaf_blocks, out_dtype=out_dtype,
                             precision=precision)


def strassen_matmul(a: jax.Array, b: jax.Array, *,
                    levels: Optional[int] = None,
                    crossover: Optional[int] = None,
                    leaf_blocks=None, interpret: bool = False,
                    out_dtype=None, leaf: Optional[Callable] = None,
                    precision=None):
    """C = A @ B via Strassen recursion over the tuned dense leaves.

    Operands must be square with identical shapes (the squaring-chain
    use case); leading batch dims are carried through the quadrant slicing
    and handled by the leaf. ``levels`` / ``crossover`` / ``leaf_blocks``
    default to the autotuned ``fastmm`` config for ``a.dtype``
    (``levels=0`` or ``crossover >= n`` degenerate to one dense leaf call).
    ``leaf`` overrides the dense leaf entirely (chain executors pass their
    fixed-block ``mm``); ``precision`` is the default leaf's
    (``matmul.tile_dot``).
    """
    if a.shape != b.shape or a.ndim < 2 or a.shape[-1] != a.shape[-2]:
        raise ValueError(f"strassen_matmul needs same-shape square "
                         f"operands, got {a.shape} @ {b.shape}")
    out_dtype = out_dtype or a.dtype
    levels, crossover, leaf_blocks = _resolve(a.dtype, levels, crossover,
                                              leaf_blocks)
    if leaf is None:
        leaf = _default_leaf(interpret, leaf_blocks, out_dtype, precision)
    return _strassen(a, b, levels, crossover, leaf)


def strassen_square(a: jax.Array, **kwargs):
    """C = A @ A via ``strassen_matmul`` (the squaring-chain face)."""
    return strassen_matmul(a, a, **kwargs)
