"""Chip benchmark of the ACAR serving path, one cell per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout, on a machine that holds the chips the
cell asks for; exits non-zero, with no result, when JAX finds no TPU or
too few chips. Set-up makes the fleet's weights from the seed, builds
one engine and serves the cell's shapes once; then calls into
``BatchedACAREngine.run_stepped`` go back to back until ``--seconds``
have passed (the call in progress is finished). ``--trace 1`` runs the
same window with part of it under the profiler and reports the
per-layer metrics instead of the end-to-end ones.

The last line of standard output is the result as JSON; the last lines
of standard error give each number the correctness check compared,
beside its limit.
"""
from __future__ import annotations

import time

T_IMPORT = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def process_start() -> float:
    """Wall-clock time this process started (``/proc``), else the
    time this file began to run."""
    try:
        stat = Path("/proc/self/stat").read_text()
        ticks = int(stat.rsplit(")", 1)[1].split()[19])
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        return time.time() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return T_IMPORT


def main(argv=None) -> int:
    t_start = min(process_start(), T_IMPORT)
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    from bench import cell
    try:
        result = cell.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start)
    except cell.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
