"""Distributed benches on a 2x2 device mesh.

    PYTHONPATH=src python -m benchmarks.distributed_bench [--quick]

Where JAX runs on a TPU the benches run in this process on four of its
chips: a chip belongs to one process, so a child could not reach them.
Anywhere else they run in a child process on a forced four-device CPU
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``, ``JAX_PLATFORMS=cpu``)
so the main process keeps one device. Two measurements:

  * schedule comparison — Cannon vs 2D-gather collective matmul, plus the
    one-jit-program ``matpow_sharded`` (the PR-1-era row, kept for
    trajectory).
  * chained vs per-call squaring — the ``ShardedMatmulChain`` story: a
    squaring chain on a NON-mesh-divisible operand through (a) the chain
    (pad + commit the 2-D sharding once, donated collective squarings,
    unpad once) and (b) the per-call path the code forced before the chain
    existed (every squaring re-pads, re-places, multiplies, and re-slices —
    the operand is re-materialized each step). Reported as us per squaring,
    min over rounds.

Writes ``BENCH_distributed.json`` (name -> us) at the repo root so the
distributed perf trajectory is tracked across PRs; a standalone run exits
non-zero if the bench fails (inside ``benchmarks.run`` the failure
degrades to a ``failed:`` CSV row instead). ``--quick`` only lowers the
rep counts (same measurements, <60 s on CPU).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from pathlib import Path

SRC = str(Path(__file__).resolve().parent.parent / "src")
ROOT = Path(__file__).resolve().parent.parent

_RESULT_PREFIX = "BENCHJSON:"


def measure(mesh, reps: int) -> dict:
    """Both measurements on a 2x2 ``("data", "model")`` mesh."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import matmul_2d_gather, matmul_cannon, matpow_sharded
    from repro.core.distributed import ShardedMatmulChain, sharded_matmul

    sh = NamedSharding(mesh, P("data", "model"))
    out = {}

    # --- schedule comparison (divisible size, one jit program) -----------
    n = 512
    a = jax.device_put(jax.random.normal(jax.random.PRNGKey(0), (n, n)) * 0.1,
                       sh)
    b = jax.device_put(jax.random.normal(jax.random.PRNGKey(1), (n, n)) * 0.1,
                       sh)

    def bench(fn, *args, reps=max(reps // 4, 3)):
        jfn = jax.jit(fn)
        jax.block_until_ready(jfn(*args))
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(jfn(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    out["sharded_gather_512_us"] = bench(
        lambda x, y: matmul_2d_gather(x, y, mesh), a, b) * 1e6
    out["sharded_cannon_512_us"] = bench(
        lambda x, y: matmul_cannon(x, y, mesh), a, b) * 1e6
    out["sharded_matpow64_512_us"] = bench(
        lambda x: matpow_sharded(x, 64, mesh), a) * 1e6

    # --- chained vs per-call squaring (non-divisible size) ---------------
    # n = 509 (prime): shard_map needs even shards, so pre-chain code had to
    # pad around EVERY call; the chain pads + commits the sharding once.
    n, squarings = 509, 6
    chain = ShardedMatmulChain(n, jnp.float32, mesh)
    pad_n = chain.padded_n
    a = jax.random.normal(jax.random.PRNGKey(2), (n, n)) * (0.5 / np.sqrt(n))

    @jax.jit
    def percall_square(x):   # pad -> place -> collective matmul -> slice
        xp = jnp.zeros((pad_n, pad_n), x.dtype).at[:n, :n].set(x)
        xp = jax.lax.with_sharding_constraint(xp, sh)
        return sharded_matmul(xp, xp, mesh)[:n, :n]

    def run_percall(x):
        for _ in range(squarings):
            x = percall_square(x)
        return x

    def run_chained(x):
        xp = chain.pad(x)            # once
        for _ in range(squarings):
            xp = chain.square(xp)    # donated collective steps
        return chain.unpad(xp)       # once

    # warm both (compile)
    jax.block_until_ready(run_percall(a))
    jax.block_until_ready(run_chained(a))
    t_per = t_chain = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(run_percall(a))
        t_per = min(t_per, time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(run_chained(a))
        t_chain = min(t_chain, time.perf_counter() - t0)

    # numerics cross-check while we are here
    err = float(jnp.max(jnp.abs(run_percall(a) - run_chained(a))))
    out["sharded_percall_us_per_square"] = t_per * 1e6 / squarings
    out["sharded_chain_us_per_square"] = t_chain * 1e6 / squarings
    out["chain_speedup_vs_percall"] = t_per / t_chain
    out["chain_maxerr_vs_percall"] = err
    out["platform"] = jax.devices()[0].platform
    return out


def _mesh(devices):
    import jax
    return jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2,
                         devices=devices[:4])


def child_main(reps: int) -> None:
    """Entry point of the forced four-device CPU child."""
    import jax
    print(_RESULT_PREFIX + json.dumps(measure(_mesh(jax.devices()), reps)))


def _run_child(reps: int) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT), SRC])
    code = (f"from benchmarks.distributed_bench import child_main; "
            f"child_main({int(reps)})")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    if out.returncode != 0:
        raise RuntimeError(f"distributed bench child failed:\n{out.stderr[-2000:]}")
    line = [l for l in out.stdout.splitlines()
            if l.startswith(_RESULT_PREFIX)][-1]
    return json.loads(line[len(_RESULT_PREFIX):])


def _run(reps: int) -> dict:
    """In-process on a TPU's own chips; in a forced-CPU child elsewhere."""
    import jax
    if jax.default_backend() != "tpu":
        return _run_child(reps)
    devices = jax.devices()
    if len(devices) < 4:
        raise RuntimeError(f"the 2x2 mesh needs 4 TPU chips, found "
                           f"{len(devices)}")
    return measure(_mesh(devices), reps)


def main(rows=None, quick: bool = False):
    """Run the distributed benches; append CSV rows; write the JSON artifact.

    ``rows`` follows the benchmarks/run.py convention (list of dicts with
    name / us_per_call / derived); called standalone it prints the CSV
    itself. ``BENCH_distributed.json`` is written either way.
    """
    own = rows is None
    rows = [] if own else rows
    try:
        data = _run(reps=8 if quick else 40)
        derived = (f"speedup_vs_percall={data['chain_speedup_vs_percall']:.2f};"
                   f"percall_us_per_square="
                   f"{data['sharded_percall_us_per_square']:.0f};"
                   f"maxerr_vs_percall={data['chain_maxerr_vs_percall']:.1e}")
        rows.append({"name": "sharded_chain_509_p64",
                     "us_per_call": data["sharded_chain_us_per_square"],
                     "derived": derived})
        for key in ("sharded_gather_512_us", "sharded_cannon_512_us",
                    "sharded_matpow64_512_us"):
            rows.append({"name": key.rsplit("_us", 1)[0],
                         "us_per_call": data[key],
                         "derived": f"schedule_comparison_2x2"
                                    f"{data['platform']}"})
        out_path = ROOT / "BENCH_distributed.json"
        # round timings for stable diffs, but keep the numerics cross-check
        # at full precision (a ~1e-6 maxerr must not be recorded as 0.0)
        out_path.write_text(json.dumps(
            {k: (v if k in ("chain_maxerr_vs_percall", "platform")
                 else round(v, 2))
             for k, v in data.items()}, indent=2, sort_keys=True))
        print(f"# wrote {out_path}", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 — a failed bench must not kill run.py
        rows.append({"name": "sharded_chain_509_p64", "us_per_call": 0.0,
                     "derived": f"failed: {e}"})
        if own:
            # standalone run: surface the failure (non-zero exit) instead of
            # printing a failed row and leaving no JSON artifact behind
            for r in rows:
                print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
            raise
    if own:
        for r in rows:
            print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="lower rep counts (same measurements, <60 s CPU)")
    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(quick=args.quick)
