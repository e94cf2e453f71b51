"""Observability benchmark: armed-tracer overhead and lineage-walk
verification over a full serve.

Two measurements over the single-device step loop (real micro models,
duplicate-bearing long-prompt stream — the obs harness regime):

* **tracer overhead** — span instrumentation must be effectively
  free. Serve the same stream untraced (``tracer=None`` — every hook
  is one attribute check) and with an armed ``SpanTracer`` recording
  the full lifecycle plus on-capacity leave-one-out attribution;
  min-of-``--repeats`` wall clock each. Gate: the armed run is within
  3% of the untraced run (the span chain hashes in memory and flushes
  once — no fsync ever enters the serving loop).
* **lineage verification** — over the traced run, build the PROV
  graph and walk the lineage of every distinct task, re-verifying the
  content hash of every span each walk touches, and audit the flushed
  span JSONL with the ArtifactStore verifier. Gate: every hash
  verifies (zero failures) and the file audit is clean.

Gates persist via ``persist_bench`` to ``BENCH_obs.json`` +
``experiments/bench/obs.json`` (uploaded nightly by CI).

    PYTHONPATH=src:tests python -m benchmarks.obs_bench [--smoke]
        [--repeats 3]
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks.common import csv_line, persist_bench
from repro.configs.acar import ACARConfig
from repro.serving import BatchedACAREngine, MicroBatchPolicy
from repro.serving.tracing import SpanTracer


def _zoo():
    from harness.simulate import paged_zoo
    return paged_zoo(seed=0)


def _engine(zoo, max_new_tokens):
    probe, ensemble = zoo
    return BatchedACAREngine(ACARConfig(probe_temperature=0.9, seed=0),
                             probe, ensemble,
                             max_new_tokens=max_new_tokens)


def _serve(zoo, tasks, policy, *, max_new_tokens, chunk_tokens,
           tracer=None):
    eng = _engine(zoo, max_new_tokens)
    t0 = time.perf_counter()
    res = eng.run_stepped(tasks, policy, chunk_tokens=chunk_tokens,
                          tracer=tracer)
    return res, time.perf_counter() - t0


def run(n_tasks: int = 200, batch_size: int = 8,
        prompt_chars: int = 24, max_new_tokens: int = 4,
        chunk_tokens: int = 8, repeats: int = 3, seed: int = 0,
        verbose: bool = True) -> dict:
    import tempfile
    from pathlib import Path

    from harness.simulate import long_prompt_workload
    from repro.teamllm.prov import lineage, verify_span_file

    tasks = long_prompt_workload(n_tasks, prompt_chars, seed=seed,
                                 duplicate_rate=0.15)
    zoo = _zoo()
    policy = MicroBatchPolicy(max_batch_size=batch_size,
                              max_batch_tokens=1 << 20)
    kw = dict(max_new_tokens=max_new_tokens,
              chunk_tokens=chunk_tokens)

    base_res, _ = _serve(zoo, tasks, policy, **kw)   # warmup (jit)
    plain_wall = min(_serve(zoo, tasks, policy, **kw)[1]
                     for _ in range(repeats))
    workdir = Path(tempfile.mkdtemp(prefix="acar-obs-bench-"))
    span_path = workdir / "spans.jsonl"
    traced_res = None
    armed_wall = float("inf")
    for i in range(repeats):
        res, wall = _serve(
            zoo, tasks, policy,
            tracer=SpanTracer(span_path if i == 0 else None), **kw)
        if i == 0:
            traced_res = res
        armed_wall = min(armed_wall, wall)
    if traced_res.final_answers != base_res.final_answers:
        raise RuntimeError("traced run diverged from baseline")

    t0 = time.perf_counter()
    audit = verify_span_file(span_path)
    walked = 0
    verified = 0
    failures = []
    for tid in sorted({t.task_id for t in tasks}):
        lin = lineage(traced_res.spans, tid)
        walked += 1
        verified += lin["verified"]
        failures.extend(f"{tid}: {f}" for f in lin["hash_failures"])
    lineage_wall = time.perf_counter() - t0

    out = {
        "n_tasks": n_tasks,
        "repeats": repeats,
        "ticks": base_res.step.ticks,
        "plain_wall_s": plain_wall,
        "armed_wall_s": armed_wall,
        "tracer_overhead": armed_wall / plain_wall,
        "span_records": len(traced_res.spans),
        "span_file_ok": bool(audit["ok"]),
        "span_head": traced_res.span_head,
        "lineage_tasks": walked,
        "lineage_hashes_verified": verified,
        "lineage_failures": len(failures),
        "lineage_wall_s": lineage_wall,
    }
    persist_bench("obs", out)
    if verbose:
        for k, v in out.items():
            print(f"  {k}: {v}")
        for f in failures[:10]:
            print(f"  lineage failure: {f}")
    return out


def check(out: dict) -> list:
    """Perf + integrity gates: armed tracer within 3% of the untraced
    run; the flushed span chain audits clean; every span hash on
    every task's lineage walk verifies."""
    failures = []
    if out["tracer_overhead"] > 1.03:
        failures.append(
            f"armed tracer costs "
            f"{(out['tracer_overhead'] - 1) * 100:.2f}% > 3% gate")
    if not out["span_file_ok"]:
        failures.append("flushed span chain failed ArtifactStore "
                        "audit")
    if out["lineage_failures"]:
        failures.append(
            f"{out['lineage_failures']} lineage hash verifications "
            f"failed")
    if out["lineage_hashes_verified"] <= 0:
        failures.append("lineage walk verified no span hashes")
    return failures


def main() -> str:
    t = run(verbose=False)
    us = t["armed_wall_s"] * 1e6 / t["n_tasks"]
    return csv_line(
        "obs_bench", us,
        f"overhead={(t['tracer_overhead'] - 1) * 100:.2f}%;"
        f"lineage={t['lineage_hashes_verified']}hashes")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="smaller stream for CI")
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args()
    out = run(n_tasks=24 if args.smoke else 200,
              repeats=args.repeats, verbose=True)
    failures = check(out)
    for f in failures:
        print(f"GATE FAILED: {f}", file=sys.stderr)
    sys.exit(1 if failures else 0)
