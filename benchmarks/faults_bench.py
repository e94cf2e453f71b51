"""Fault-tolerance benchmark: injection-hook overhead and journal
recovery speed.

Two measurements over the single-device step loop (real micro models,
duplicate-bearing long-prompt stream — the crash-recovery harness
regime):

* **hook overhead** — the fault-injection hooks are attribute checks
  that must cost nothing when no fault fires. Serve the same stream
  with ``faults=None`` and with an *armed but never-firing* plan (one
  spec at a far-future tick, so the injector and every per-group gate
  run on the hot path); min-of-``--repeats`` wall clock each. Gate:
  the armed run is within 2% of the plain run.
* **recovery speed** — journal a run, kill it at 90% of its ticks,
  then time ``BatchedACAREngine.recover()`` against a full journaled
  re-run (both on a warm jit cache). Recovery restores retired rows
  verbatim and re-executes only the tail, so it must be >= 5x faster
  than re-serving the whole stream.

Gates persist via ``persist_bench`` to ``BENCH_faults.json`` +
``experiments/bench/faults.json`` (uploaded nightly by CI).

    PYTHONPATH=src:tests python -m benchmarks.faults_bench [--smoke]
        [--repeats 3]
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks.common import csv_line, persist_bench
from repro.configs.acar import ACARConfig
from repro.serving import BatchedACAREngine, MicroBatchPolicy
from repro.serving.faults import FaultPlan, FaultSpec, SimulatedCrash


def _zoo():
    from harness.simulate import paged_zoo
    return paged_zoo(seed=0)


def _engine(zoo, max_new_tokens):
    probe, ensemble = zoo
    return BatchedACAREngine(ACARConfig(probe_temperature=0.9, seed=0),
                             probe, ensemble,
                             max_new_tokens=max_new_tokens)


def _serve(zoo, tasks, policy, *, max_new_tokens, chunk_tokens,
           **kw):
    eng = _engine(zoo, max_new_tokens)
    t0 = time.perf_counter()
    if "recover" in kw:
        res = eng.recover(tasks, policy, journal_path=kw["recover"],
                          chunk_tokens=chunk_tokens)
    else:
        res = eng.run_stepped(tasks, policy,
                              chunk_tokens=chunk_tokens, **kw)
    return res, time.perf_counter() - t0


def run(n_tasks: int = 32, batch_size: int = 8,
        prompt_chars: int = 24, max_new_tokens: int = 4,
        chunk_tokens: int = 8, repeats: int = 3, seed: int = 0,
        verbose: bool = True) -> dict:
    import tempfile
    from pathlib import Path

    from harness.simulate import long_prompt_workload

    tasks = long_prompt_workload(n_tasks, prompt_chars, seed=seed,
                                 duplicate_rate=0.15)
    zoo = _zoo()
    policy = MicroBatchPolicy(max_batch_size=batch_size,
                              max_batch_tokens=1 << 20)
    kw = dict(max_new_tokens=max_new_tokens,
              chunk_tokens=chunk_tokens)
    # an armed plan that never fires: the injector and every
    # per-tick / per-group fault gate run, but no fault path executes
    armed = FaultPlan(specs=(
        FaultSpec(tick=1 << 30, site="admit_alloc"),))

    base_res, _ = _serve(zoo, tasks, policy, **kw)   # warmup (jit)
    plain_wall = min(_serve(zoo, tasks, policy, **kw)[1]
                     for _ in range(repeats))
    armed_wall = min(_serve(zoo, tasks, policy, faults=armed, **kw)[1]
                     for _ in range(repeats))

    workdir = Path(tempfile.mkdtemp(prefix="acar-faults-bench-"))
    jp = workdir / "journal.jsonl"
    crash_tick = max(1, base_res.step.ticks * 9 // 10)
    try:
        _serve(zoo, tasks, policy,
               faults=FaultPlan.crash_at(crash_tick),
               journal_path=jp, **kw)
        raise RuntimeError("crash fault never fired")
    except SimulatedCrash:
        pass
    rec_res, rec_wall = _serve(zoo, tasks, policy, recover=jp, **kw)
    full_wall = min(
        _serve(zoo, tasks, policy,
               journal_path=workdir / f"full-{i}.jsonl", **kw)[1]
        for i in range(repeats))
    if rec_res.final_answers != base_res.final_answers:
        raise RuntimeError("recovered run diverged from baseline")

    out = {
        "n_tasks": n_tasks,
        "repeats": repeats,
        "ticks": base_res.step.ticks,
        "crash_tick": crash_tick,
        "plain_wall_s": plain_wall,
        "armed_wall_s": armed_wall,
        "hook_overhead": armed_wall / plain_wall,
        "restored_rows": rec_res.restored_rows,
        "recover_wall_s": rec_wall,
        "full_rerun_wall_s": full_wall,
        "recovery_speedup": full_wall / rec_wall,
    }
    persist_bench("faults", out)
    if verbose:
        for k, v in out.items():
            print(f"  {k}: {v}")
    return out


def check(out: dict) -> list:
    """Perf gates: never-firing fault hooks within 2% of the
    hook-free run; journal recovery >= 5x faster than a full
    re-serve of the stream."""
    failures = []
    if out["hook_overhead"] > 1.02:
        failures.append(
            f"armed-but-idle fault hooks cost "
            f"{(out['hook_overhead'] - 1) * 100:.2f}% > 2% gate")
    if out["recovery_speedup"] < 5.0:
        failures.append(
            f"journal recovery only {out['recovery_speedup']:.2f}x "
            f"faster than a full re-run (< 5x gate)")
    if out["restored_rows"] <= 0:
        failures.append("recovery restored no rows from the journal")
    return failures


def main() -> str:
    t = run(verbose=False)
    us = t["recover_wall_s"] * 1e6 / t["n_tasks"]
    return csv_line(
        "faults_bench", us,
        f"overhead={(t['hook_overhead'] - 1) * 100:.2f}%;"
        f"recovery={t['recovery_speedup']:.1f}x")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="smaller stream for CI")
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args()
    out = run(n_tasks=12 if args.smoke else 32,
              repeats=args.repeats, verbose=True)
    failures = check(out)
    for f in failures:
        print(f"GATE FAILED: {f}", file=sys.stderr)
    sys.exit(1 if failures else 0)
