"""Readings of the control, the upper ends of the limits in
``bench/cells/<cell>.json``.

    python3 bench/control_readings.py --workload <cell> --seeds 1,2,3

For each seed it builds the cell's operand pool as a run does, takes as
many requests as a run compares (the hardest operand and a sample drawn
from the seed), answers each with the control (``mfbench.control``) in
the program's place on the chip, and prints the compared numbers beside
the cell's limits, one JSON line per seed.
"""

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from mfbench import harness  # noqa: E402


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        harness.check_devices(cell.chips)
    except harness.NoChip as exc:
        print(f"[bench] {exc}", file=sys.stderr)
        return 2
    for seed in map(int, args.seeds.split(",")):
        checks = harness.control_checks(cell, seed)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
