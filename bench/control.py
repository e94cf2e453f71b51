"""Readings that set a cell's correctness limits, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed, in one process: a run of the cell as ``bench/run.py``
makes it (set-up, a short window at the cell's own load), whose check
judges the control in the program's place: the plain reference
computed in float8, whose first choice at each served position is
read against the float32 reference like a served token. Prints one
JSON line per seed with the control's verdict and gaps (the upper
readings) and the program's own gaps on the same sample (the lower
ones). Exits 1 if the control came out correct on any seed. The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import cell
    passed = []
    for seed in args.seeds:
        t0 = time.time()
        try:
            r = cell.run(args.workload, seed, args.seconds, False,
                         t_start=t0, control=True)
        except cell.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 3
        c = r["checked"]
        if r["correct"]:
            passed.append(seed)
        print(json.dumps({
            "seed": seed, "correct": r["correct"],
            "control_gap_probe": r["checks"]["gap_probe"]["value"],
            "control_gap_member": r["checks"]["gap_member"]["value"],
            "gap_probe": c["program_gap_probe"],
            "gap_member": c["program_gap_member"],
            "tokens": c["tokens"], "lanes": c["lanes"],
            "checks": {k: v["value"] for k, v in r["checks"].items()},
            "seconds": time.time() - t0}), flush=True)
    if passed:
        print(f"control: came out correct on seeds {passed}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
