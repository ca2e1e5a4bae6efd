"""Benchmark harness — one module per paper table/figure + system benches.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--json BENCH_matpow.json]

Prints ``name,us_per_call,derived`` CSV (one row per measurement) and ALWAYS
writes a machine-readable ``BENCH_matpow.json`` mapping name -> us_per_call,
so the perf trajectory is tracked across PRs:

  * paper_tables       — Tables 2-5 of the paper (size x power grid),
                         naive vs binary exponentiation + TPU projections
  * chain_bench        — the fused chain-execution path (pad once, donated
                         squarings) vs the seed per-multiply ops.matmul path
  * autotune           — populates / reuses the persistent tuning cache
                         across all kernel namespaces (matmul, attention,
                         square_panel tiers, the fastmm Strassen
                         crossover) — ~/.cache/repro/autotune.json,
                         REPRO_AUTOTUNE_CACHE to override; delete the file
                         to force a re-sweep
  * kernel_sweep       — the paper's tile-size sweep on the Pallas kernels:
                         matmul blocks, attention (block_q, block_k), and
                         the square_pallas memory tiers (records winners
                         into the cache)
  * distributed_bench  — chained (ShardedMatmulChain) vs per-call sharded
                         squaring + Cannon vs gather schedules (4-dev CPU);
                         also writes BENCH_distributed.json
  * roofline_bench     — per (arch x shape x mesh) dominant term from the
                         dry-run artifacts

``--quick`` bounds the run to <60 s on CPU: the small paper tables plus
chain_bench and autotune only. Run twice to see the autotuner cache being
populated (first run) and reused (second run, ``cache_hit=True``).
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import jax.numpy as jnp

from repro.runtime.compile_cache import enable_compile_cache


def chain_bench(rows, sizes=(256, 512), power=64, reps=60):
    """Fused chain path vs the seed per-multiply path, same kernels.

    Rounds are interleaved (seed then chain, back to back) and the speedup is
    the ratio of min-over-rounds — the estimator most robust to the heavy
    scheduler noise of shared CPU runners. Off-TPU both paths lower to the
    same XLA dots (the chain's pad-once/donation advantages only exist where
    the Pallas pipeline lowers), so the bench ALSO proves no-regression
    structurally: ``identical_hlo_vs_seed`` compares the optimized HLO of the
    two programs modulo value numbering. Wall-clock ratios on a contended
    CPU runner jitter around 1.00; the HLO check is the deterministic
    ground truth there. The chain's win shows up in the pad/dispatch counts
    (tests/test_chain.py) and on real TPU hardware.
    """
    import re

    from repro.core import matpow_binary

    def _norm_hlo(text):
        # Strip SSA value numbering (names start with a letter: dot.12,
        # %fusion.3) WITHOUT touching float literals like 0.30000001, so
        # constant differences between the programs still show up.
        text = re.sub(r"%?\b[A-Za-z_][\w\-]*(?:\.\d+)+", "X", text)
        return re.sub(r"metadata=\{[^}]*\}", "", text)

    for size in sizes:
        key = jax.random.PRNGKey(size)
        a = jax.random.normal(key, (size, size), jnp.float32)
        a = a / (jnp.linalg.norm(a, 2) * 1.02)

        seed_fn = jax.jit(lambda x: matpow_binary(x, power, backend="pallas"))
        chain_fn = jax.jit(lambda x: matpow_binary(x, power,
                                                   backend="pallas_chain"))
        for fn in (seed_fn, chain_fn):  # compile + warm
            for _ in range(3):
                jax.block_until_ready(fn(a))
        t_seed = t_chain = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(seed_fn(a))
            t_seed = min(t_seed, time.perf_counter() - t0)
            t0 = time.perf_counter()
            jax.block_until_ready(chain_fn(a))
            t_chain = min(t_chain, time.perf_counter() - t0)
        err = float(jnp.max(jnp.abs(chain_fn(a) - seed_fn(a))))
        same_hlo = (_norm_hlo(seed_fn.lower(a).compile().as_text())
                    == _norm_hlo(chain_fn.lower(a).compile().as_text()))
        rows.append({
            "name": f"matpow_chain_{size}_p{power}",
            "us_per_call": t_chain * 1e6,
            "derived": (f"seed_us={t_seed*1e6:.0f};"
                        f"speedup_vs_seed={t_seed/t_chain:.2f};"
                        f"identical_hlo_vs_seed={same_hlo};"
                        f"maxerr_vs_seed={err:.1e}"),
        })


def autotune_bench(rows, sizes=(256, 512), attn=(1024, 1024, 128)):
    """Populate the persistent tuning cache (first run) / reuse it (later).

    Seeds all three kernel namespaces: matmul tilings for the benched matpow
    sizes, an attention (block_q, block_k) entry for a 1k-prefill slice, and
    the square_pallas tier thresholds. Modeled scoring off-TPU is pure
    python, so this keeps ``--quick`` well inside its 60 s budget.
    """
    from repro.kernels import autotune

    for size in sizes:
        blocks = autotune.lookup(size, size, size, dtype=jnp.float32)
        hit = blocks is not None
        if not hit:
            blocks, _ = autotune.sweep(size, size, size, dtype=jnp.float32)
        rows.append({
            "name": f"autotune_{size}x{size}x{size}",
            "us_per_call": 0.0,
            "derived": (f"blocks={'x'.join(map(str, blocks))};"
                        f"cache_hit={hit};path={autotune.cache_path()}"),
        })

    sq, skv, d = attn
    blocks = autotune.lookup(sq, skv, d, dtype=jnp.float32,
                             kernel="attention")
    hit = blocks is not None
    if not hit:
        blocks, _ = autotune.sweep_attention(sq, skv, d, dtype=jnp.float32)
    rows.append({
        "name": f"autotune_attn_{sq}x{skv}x{d}",
        "us_per_call": 0.0,
        "derived": (f"blocks={'x'.join(map(str, blocks))};"
                    f"cache_hit={hit};path={autotune.cache_path()}"),
    })

    whole, panel = autotune.square_tiers(dtype=jnp.float32)
    rows.append({
        "name": "autotune_square_tiers",
        "us_per_call": 0.0,
        "derived": f"whole_limit={whole};panel_limit={panel}",
    })

    crossover, levels, _ = autotune.fastmm_config(dtype=jnp.float32)
    rows.append({
        "name": "autotune_fastmm",
        "us_per_call": 0.0,
        "derived": f"crossover={crossover};levels={levels}",
    })


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small paper tables + chain/autotune only (<60 s CPU)")
    ap.add_argument("--json", default="BENCH_matpow.json",
                    help="machine-readable output path (name -> us_per_call)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    from benchmarks import paper_tables

    rows = []
    paper_tables.main(rows, quick=args.quick)
    chain_bench(rows)
    autotune_bench(rows)
    if not args.quick:
        from benchmarks import distributed_bench, kernel_sweep, roofline_bench
        kernel_sweep.main(rows)
        distributed_bench.main(rows)
        roofline_bench.main(rows)

    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")

    # Perf-trajectory artifact: REAL timings only. Structural rows (modeled
    # kernel-sweep metrics, autotune markers) report 0.0 us and would read
    # as measurements to anything diffing this file across PRs.
    timed = {r["name"]: round(r["us_per_call"], 1)
             for r in rows if r["us_per_call"] > 0}
    out = Path(args.json)
    out.write_text(json.dumps(timed, indent=2, sort_keys=True))
    print(f"# wrote {out} ({len(timed)} timed entries, "
          f"{len(rows)} rows total)", file=sys.stderr)


if __name__ == '__main__':
    main()
