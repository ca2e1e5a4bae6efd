"""Find the highest rate an open-loop cell sustains: one whole run of
the cell (``harness.run_cell``, set-up included) at each offered rate.

    python3 bench/sweep.py --workload phylo_codon61.mcmc --seed 1 \
        --seconds 10 --rates 800,1000,1200

For each offered rate (bursts per second) it prints one JSON line: the
answers completed per second, p50 and p95 of latency from due, how late
the generator ran, whether the answers were correct, and p95 over the
first and the last third of the window (a backlog that grows shows as a
last third far above the first). The rate a cell is offered is fixed in
its traffic file, at about four fifths of the highest rate found here.
"""

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from mfbench import harness, loops  # noqa: E402


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    try:
        harness.check_devices(harness.load_cell(args.workload).chips)
    except harness.NoChip as exc:
        print(f"[bench] {exc}", file=sys.stderr)
        return 2
    harness.configure_jax()
    for rate in map(float, args.rates.split(",")):
        cell = harness.load_cell(args.workload)
        cell.traffic["bursts_per_s"] = rate
        reqs = []
        result = harness.run_cell(cell, args.seed, args.seconds, False,
                                  loops.clock(), requests_out=reqs)
        t0 = min(r.due for r in reqs if r.in_window)
        t1 = t0 + args.seconds
        third = args.seconds / 3
        s = loops.summarize(reqs, t0, t1)
        first = loops.summarize(reqs, t0, t0 + third)
        last = loops.summarize(reqs, t1 - third, t1)
        print(json.dumps({
            "bursts_per_s": rate,
            "offered_per_s": s.attempted / args.seconds,
            "answers_per_s": s.answers_per_s, "failed": s.failed,
            "correct": result["correct"], "p50_ms": s.p50_ms,
            "p95_ms": s.p95_ms, "late_p50_ms": s.late_p50_ms,
            "late_max_ms": s.late_max_ms,
            "p95_first_third_ms": first.p95_ms,
            "p95_last_third_ms": last.p95_ms}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
