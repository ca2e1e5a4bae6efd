"""Strassen fast-matmul bench: crossover sweep + dense-vs-fastmm squaring.

    PYTHONPATH=src python -m benchmarks.fastmm_bench [--quick]

Three measurements:

  * crossover sweep — ``autotune.sweep_fastmm`` for the active backend
    (measured candidate probing on TPU, modeled defaults recorded with
    ``measured: false`` elsewhere), so the run leaves a documented
    ``fastmm`` cache entry behind exactly like the other namespaces;
  * dense vs Strassen squaring at sizes bracketing the crossover — one
    donable jitted square per route, min-of-reps, at the depth
    ``fastmm.plan_levels`` actually picks for each size. Sizes are
    deliberately NON-powers-of-two at the top (1536, 2560): power-of-two
    dense dots get disproportionately fast XLA code paths on CPU, which
    would gate the size, not the algorithm;
  * accuracy vs the f64 reference at every size, compared against
    ``fastmm.error_budget`` for the depth used — the tolerance-aware gate
    CI enforces (speedup >= 1.0x AND error <= budget at the largest quick
    size).

Writes ``BENCH_fastmm.json`` at the repo root (tracked by
``benchmarks/compare.py`` SPECS for trajectory). ``--quick`` drops the
largest full-run size and lowers reps (<90 s on CPU).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import autotune, fastmm
from repro.kernels import ops as kops

ROOT = Path(__file__).resolve().parent.parent

#: Bench sizes. The largest quick size (the CI gate point) is 1536:
#: comfortably above the modeled crossover (1024) so depth 1 engages, and
#: non-power-of-two (see module docstring). The full run adds 2560.
QUICK_SIZES = (512, 1024, 1536)
FULL_SIZES = QUICK_SIZES + (2560,)


def _best_us(fn, a, reps: int) -> float:
    jfn = jax.jit(fn)
    jax.block_until_ready(jfn(a))          # compile outside the timing
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(jfn(a))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def bench_size(n: int, crossover: int, max_levels: int, reps: int,
               dtype=jnp.float32) -> dict:
    """One dense-vs-Strassen squaring row at size n."""
    rng = np.random.default_rng(n)
    a = jnp.asarray(rng.standard_normal((n, n)) / np.sqrt(n), dtype)
    levels = fastmm.plan_levels(n, levels=max_levels, crossover=crossover)
    dense_us = _best_us(lambda x: kops.square(x), a, reps)
    fast_us = _best_us(
        lambda x: fastmm.strassen_square(x, levels=max_levels,
                                         crossover=crossover), a, reps)
    got = np.asarray(fastmm.strassen_square(a, levels=max_levels,
                                            crossover=crossover), np.float64)
    ref = np.asarray(a, np.float64)
    ref = ref @ ref
    rtol, atol = fastmm.error_budget(dtype, levels=levels, n=n)
    maxerr = float(np.max(np.abs(got - ref)))
    err_bound = float(rtol * np.max(np.abs(ref)) + atol)
    return {
        "dense_us": round(dense_us, 1),
        "fastmm_us": round(fast_us, 1),
        "speedup": round(dense_us / fast_us, 3),
        "levels": levels,
        "maxerr": maxerr,
        "err_bound": err_bound,
        "within_budget": maxerr <= err_bound,
    }


def main(rows=None, quick: bool = False) -> list:
    """Run the fastmm bench; follows the benchmarks/run.py rows convention
    (standalone: prints CSV itself). Writes BENCH_fastmm.json either way."""
    own = rows is None
    rows = [] if own else rows

    # Crossover sweep first: measured on TPU, modeled elsewhere — the
    # bench's subsequent sizes then run against the recorded policy.
    crossover, max_levels = autotune.sweep_fastmm(jnp.float32)
    reps = 3 if quick else 5
    sizes = QUICK_SIZES if quick else FULL_SIZES

    data = {
        "backend": jax.default_backend(),
        "crossover": crossover,
        "max_levels": max_levels,
        "rows": {},
    }
    for n in sizes:
        row = bench_size(n, crossover, max_levels, reps)
        data["rows"][f"n{n}"] = row
        rows.append({
            "name": f"fastmm_square_{n}",
            "us_per_call": row["fastmm_us"],
            "derived": (f"dense_us={row['dense_us']};"
                        f"speedup={row['speedup']};levels={row['levels']};"
                        f"maxerr={row['maxerr']:.2e}"),
        })

    # The CI gate point: the largest QUICK size even on full runs, so the
    # gated metric is measured identically in both configurations.
    gate_n = max(QUICK_SIZES)
    gate_row = data["rows"][f"n{gate_n}"]
    data["gate"] = {
        "n": gate_n,
        "speedup": gate_row["speedup"],
        "levels": gate_row["levels"],
        "within_budget": gate_row["within_budget"],
    }

    out_path = ROOT / "BENCH_fastmm.json"
    out_path.write_text(json.dumps(data, indent=2, sort_keys=True))
    print(f"# wrote {out_path}", file=sys.stderr)
    if own:
        for r in rows:
            print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="quick sizes + lower reps (<90 s CPU)")
    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(quick=args.quick)
