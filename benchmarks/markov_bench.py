"""Markov workload bench: convergence-aware steady state + evolve route.

    PYTHONPATH=src python -m benchmarks.markov_bench [--quick]

Two measurements, each against the policy it replaces:

  * early exit — ``steady_state`` on a well-mixed n=256 chain vs the
    fixed ``matpow_binary(p, 2**20)`` policy the pre-markov code paid for
    every steady-state query. The win is structural (squarings actually
    paid, CI gates < 20) and temporal (min-of-reps wall clock for the
    whole query). Both run the same squaring kernels, so the speedup is
    the squaring-count ratio up to while-loop + residual overhead — n is
    sized so an O(n^3) squaring dwarfs the O(n^2) residual check (at
    n=64 the two are close enough that the timing gate flaked on a
    shared CPU box).
  * evolve — ``evolve_distributions`` on a (B, n) stack over a 1023-step
    horizon vs the dense route (``markov_power`` then one apply). The
    binary decomposition turns every O(n^3) combine multiply into an
    O(B n^2) vector-matrix product; at B=8, n=256, steps=1023 the modeled
    compute ratio is ~1.9x and CI gates the measured speedup >= 1.0x.

Writes ``BENCH_markov.json`` at the repo root (tracked by
``benchmarks/compare.py`` SPECS for trajectory). ``--quick`` lowers reps
only — both sections are already CPU-cheap, and the gate metrics must be
measured identically in both configurations.
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

from repro.core.markov import (evolve_distributions, markov_power,
                               steady_state)
from repro.core.matpow import matpow_binary
from repro.kernels import ops as kops

ROOT = Path(__file__).resolve().parent.parent

STEADY_N = 256
EVOLVE_N = 256
EVOLVE_B = 8
EVOLVE_STEPS = 1023      # 10 set bits: the worst case for combine count


def _stochastic(n, seed, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    m = rng.random((n, n)) + 0.05        # strictly positive: well-mixed
    return jnp.asarray(m / m.sum(axis=1, keepdims=True), dtype)


def _best_us(jfn, *args, reps: int) -> float:
    jax.block_until_ready(jfn(*args))    # compile outside the timing
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(jfn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def bench_early_exit(reps: int) -> dict:
    p = _stochastic(STEADY_N, 0)
    res = steady_state(p, validate=False)
    steady_us = _best_us(
        jax.jit(lambda x: steady_state(x, validate=False)), p, reps=reps)
    fixed_us = _best_us(
        jax.jit(lambda x: matpow_binary(x, 1 << 20)), p, reps=reps)
    pi = np.asarray(res.pi, np.float64)
    drift = float(np.abs(pi @ np.asarray(p, np.float64) - pi).max())
    return {
        "n": STEADY_N,
        "squarings": int(res.squarings),
        "max_squarings": 20,
        "residual": float(res.residual),
        "pi_drift": drift,
        "steady_us": round(steady_us, 1),
        "fixed_us": round(fixed_us, 1),
        "speedup": round(fixed_us / steady_us, 3),
    }


def bench_evolve(reps: int) -> dict:
    p = _stochastic(EVOLVE_N, 1)
    rng = np.random.default_rng(2)
    d = rng.random((EVOLVE_B, EVOLVE_N)).astype(np.float32)
    d = jnp.asarray(d / d.sum(axis=1, keepdims=True))

    evolve_us = _best_us(
        jax.jit(lambda dd, pp: evolve_distributions(
            dd, pp, EVOLVE_STEPS, validate=False, dense_threshold=1e9)),
        d, p, reps=reps)
    dense_us = _best_us(
        jax.jit(lambda dd, pp: kops.dense_matmul(
            dd, markov_power(pp, EVOLVE_STEPS, validate=False))),
        d, p, reps=reps)

    got = np.asarray(evolve_distributions(d, p, EVOLVE_STEPS,
                                          validate=False,
                                          dense_threshold=1e9), np.float64)
    ref = np.asarray(kops.dense_matmul(
        d, markov_power(p, EVOLVE_STEPS, validate=False)), np.float64)
    maxerr = float(np.abs(got - ref).max())
    return {
        "n": EVOLVE_N,
        "batch": EVOLVE_B,
        "steps": EVOLVE_STEPS,
        "evolve_us": round(evolve_us, 1),
        "dense_us": round(dense_us, 1),
        "speedup": round(dense_us / evolve_us, 3),
        "maxerr_vs_dense": maxerr,
        # Same kernels, different multiply schedule: fp32 noise only.
        "agrees": maxerr < 1e-4,
    }


def main(rows=None, quick: bool = False) -> list:
    """Run the markov bench; follows the benchmarks/run.py rows convention
    (standalone: prints CSV itself). Writes BENCH_markov.json either way."""
    own = rows is None
    rows = [] if own else rows
    reps = 3 if quick else 7

    early = bench_early_exit(reps)
    evolve = bench_evolve(reps)
    data = {
        "backend": jax.default_backend(),
        "early_exit": early,
        "evolve": evolve,
    }
    rows.append({
        "name": f"markov_steady_{STEADY_N}",
        "us_per_call": early["steady_us"],
        "derived": (f"fixed_us={early['fixed_us']};"
                    f"squarings={early['squarings']}/20;"
                    f"speedup={early['speedup']}"),
    })
    rows.append({
        "name": f"markov_evolve_{EVOLVE_N}x{EVOLVE_B}",
        "us_per_call": evolve["evolve_us"],
        "derived": (f"dense_us={evolve['dense_us']};"
                    f"speedup={evolve['speedup']};"
                    f"maxerr={evolve['maxerr_vs_dense']:.2e}"),
    })

    out_path = ROOT / "BENCH_markov.json"
    out_path.write_text(json.dumps(data, indent=2, sort_keys=True))
    print(f"# wrote {out_path}", file=sys.stderr)
    if own:
        for r in rows:
            print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="lower reps (<60 s CPU)")
    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(quick=args.quick)
