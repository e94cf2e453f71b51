"""Benchmark driver — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run

Prints ``name,us_per_call,derived`` CSV (plus detailed per-benchmark
sections) and writes JSON artifacts under experiments/bench/.
"""
from __future__ import annotations

import sys
import time
import traceback


def run_sections(sections) -> int:
    """Run every ``(title, module)`` section, printing each one's
    report; a section that raises is reported and the rest still run.
    Returns the exit code: 1 when any section failed."""
    csv_lines = []
    failed = []
    for title, mod in sections:
        print(f"\n== {title} ==")
        try:
            t0 = time.perf_counter()
            mod.run(verbose=True)
            csv_lines.append(mod.main())
            print(f"  [{time.perf_counter() - t0:.1f}s]")
        except Exception as e:  # noqa: BLE001 — report every section
            traceback.print_exc()
            csv_lines.append(f"{title.split()[0]},0.0,ERROR:{e}")
            failed.append(title.split()[0])

    print("\n# name,us_per_call,derived")
    for line in csv_lines:
        print(line)
    if failed:
        print(f"FAILED sections: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


def main() -> int:
    from benchmarks import (
        ablation_probe, attribution_bench, figures, kernels_micro,
        roofline, scheduler_bench, serving_bench, table1_overall,
        table2_retrieval)

    # sharding_bench needs four devices and forces the host device
    # count itself, so it runs standalone (python -m
    # benchmarks.sharding_bench), not in this process
    return run_sections([
        ("table1_overall (paper Table 1, Figs 2/3)", table1_overall),
        ("table2_retrieval (paper Table 2, §6.1)", table2_retrieval),
        ("figures (paper Figs 1/5/6/7/8/9)", figures),
        ("attribution (paper §6.3)", attribution_bench),
        ("roofline (deliverable g — reads experiments/dryrun)",
         roofline),
        ("kernels_micro", kernels_micro),
        ("ablation_probe (beyond-paper: N and probe choice)",
         ablation_probe),
        ("serving_bench (batched ACAR engine over JAX zoo)",
         serving_bench),
        ("scheduler_bench (continuous batching vs sequential)",
         scheduler_bench),
    ])


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
