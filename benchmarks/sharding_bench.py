"""Sharded-serving benchmark: virtual-clock throughput and aggregate
KV page capacity, mesh-sharded step loop (data=N) vs single device.

Drives a saturated (all arrivals at tick 0), duplicate-bearing stream
of uniform long prompts through the step-level loop twice — once on a
single device and once on a ``ServingMesh`` with ``--shards`` data
shards — with routing forced to the paper's published 45.8% escalation
rate and the *same per-shard resources* (``active_rows`` is the
per-shard admission cap on both sides, so the sharded run serves
N x the concurrent rows out of N independent per-shard page pools).

The virtual clock is the step loop's own (device-program launches,
max over independent per-server executors per tick — see
serving/step_loop.py). A tick's group structure is identical on both
sides (groups key on (server, temperature, cache_len), and the
shard_map'd program advances every shard in one launch), so the
sharded run drains the same stream in ~1/N the ticks: throughput
scales with the mesh while per-row results stay bit-identical
(``tests/harness/simulate.py --sharded`` proves the equivalence; this
benchmark gates the performance).

Gates (persisted via ``persist_bench`` to ``BENCH_sharding.json`` +
``experiments/bench/sharding.json``, uploaded nightly by CI):

* virtual-clock throughput (tasks per virtual tick) at data=N must be
  >= 2x the single-device loop;
* aggregate KV page capacity must scale: the sharded pools' summed
  capacity >= 3x the single pool (exactly N x by construction — the
  gate catches accidental pool-sharing regressions), and the summed
  page high-water >= 2x the single high-water (the extra concurrency
  really does spread resident rows across shards).

    PYTHONPATH=src:tests python -m benchmarks.sharding_bench [--smoke]
        [--shards 4]
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from benchmarks.common import csv_line, persist_bench
from benchmarks.serving_bench import (
    bench_zoo, bursty_tasks, forced_modes, index_route_fn)
from repro.configs.acar import ACARConfig
from repro.data import tokenizer as tok
from repro.serving import AdmissionQueue, MicroBatchPolicy
from repro.serving.scheduler import StepPlanner
from repro.serving.step_loop import (
    ShardedStepLoopRunner, StepLoopRunner)


def _run_loop(tasks, modes, *, chunk_tokens, max_new_tokens,
              active_rows, prefix_cache, batch_size, seed,
              shards=None):
    """One step-loop run over a saturated queue (every request arrives
    at tick 0). Returns (runner, makespan, wall_ms)."""
    from repro.serving import BatchedACAREngine
    probe, ensemble = bench_zoo(seed)
    acfg = ACARConfig(probe_temperature=0.9, seed=seed)
    eng = BatchedACAREngine(
        acfg, probe, ensemble, max_new_tokens=max_new_tokens,
        route_fn=index_route_fn(modes), kv_prefix_cache=prefix_cache)
    queue = AdmissionQueue(MicroBatchPolicy(
        max_batch_size=batch_size, max_batch_tokens=1 << 20))
    for t in tasks:
        queue.submit(t, arrival_time=0)
    planner = StepPlanner(chunk_tokens=chunk_tokens,
                          max_active_rows=active_rows)
    t0 = time.perf_counter()
    if shards is None:
        runner = StepLoopRunner(eng, queue, planner)
    else:
        from repro.serving.mesh import ServingMesh
        runner = ShardedStepLoopRunner(eng, queue, planner,
                                       ServingMesh(data=shards))
    stats = runner.run()
    wall_ms = (time.perf_counter() - t0) * 1e3
    makespan = max(t[2] for t in stats.timeline.values())
    return runner, makespan, wall_ms


def run(n_tasks: int = 48, batch_size: int = 8,
        prompt_chars: int = 40, max_new_tokens: int = 6,
        chunk_tokens: int = 8, active_rows: int = 4,
        prefix_cache: int = 4, shards: int = 4,
        seed: int = 0, verbose: bool = True) -> dict:
    """``prefix_cache`` is deliberately small (4 entries/shard): the
    high-water gate measures *resident-row* pages spreading across
    shards, and a large prefix cache would dominate the single-device
    high-water with retained cache pages instead."""
    tasks, _ = bursty_tasks(n_tasks, prompt_chars, seed,
                            burst=n_tasks, gap=0)
    modes = forced_modes(n_tasks, seed)
    prompt_len = int(tok.encode_aligned([tasks[0].text]).shape[1])
    probe_name = bench_zoo(seed)[0].name

    kw = dict(chunk_tokens=chunk_tokens,
              max_new_tokens=max_new_tokens, active_rows=active_rows,
              prefix_cache=prefix_cache, batch_size=batch_size,
              seed=seed)
    single, span_1, wall_1 = _run_loop(tasks, modes, **kw)
    sharded, span_n, wall_n = _run_loop(tasks, modes, shards=shards,
                                        **kw)

    kv_1 = single.kv_stats()[probe_name]
    kv_n = sharded.kv_stats()[probe_name]
    tp_1 = n_tasks / span_1
    tp_n = n_tasks / span_n
    placements = [
        int(sharded.metrics.get("acar_shard_placements_total",
                                shard=str(k)))
        for k in range(shards)]

    out = {
        "n_tasks": n_tasks,
        "shards": shards,
        "prompt_len": prompt_len,
        "chunk_tokens": chunk_tokens,
        "max_new_tokens": max_new_tokens,
        "active_rows_per_shard": active_rows,
        "escalation_rate": float(np.mean(modes >= 1)),
        "single_makespan": int(span_1),
        "sharded_makespan": int(span_n),
        "single_ticks": single.stats.ticks,
        "sharded_ticks": sharded.stats.ticks,
        "single_throughput": tp_1,
        "sharded_throughput": tp_n,
        "throughput_speedup": tp_n / tp_1,
        "single_pool_pages": kv_1.pool_pages,
        "aggregate_pool_pages": kv_n.pool_pages,
        "pool_capacity_ratio": kv_n.pool_pages
        / max(kv_1.pool_pages, 1),
        "single_kv_highwater": kv_1.pages_highwater,
        "aggregate_kv_highwater": kv_n.pages_highwater,
        "kv_highwater_ratio": kv_n.pages_highwater
        / max(kv_1.pages_highwater, 1),
        "shard_placements": placements,
        "wall_ms_single": wall_1,
        "wall_ms_sharded": wall_n,
    }
    persist_bench("sharding", out)
    if verbose:
        for k, v in out.items():
            print(f"  {k}: {v}")
    return out


def check(out: dict) -> list:
    """Perf gates, scaled to the configured shard count (at the
    default data=4: >=2x virtual-clock throughput, >=3x aggregate
    page capacity, >=2x aggregate high-water — capacity is exactly
    N x by construction, so its gate mainly catches accidental
    pool-sharing regressions)."""
    n = out["shards"]
    tp_gate = min(2.0, 0.5 * n)
    cap_gate = 0.75 * n
    hw_gate = min(2.0, 0.5 * n)
    failures = []
    if out["throughput_speedup"] < tp_gate:
        failures.append(
            f"sharded throughput {out['throughput_speedup']:.2f}x "
            f"< {tp_gate:g}x gate at data={n}")
    if out["pool_capacity_ratio"] < cap_gate:
        failures.append(
            f"aggregate pool capacity {out['pool_capacity_ratio']:.2f}x"
            f" < {cap_gate:g}x gate (per-shard pools must not share)")
    if out["kv_highwater_ratio"] < hw_gate:
        failures.append(
            f"aggregate KV high-water {out['kv_highwater_ratio']:.2f}x "
            f"< {hw_gate:g}x gate (resident rows must spread across "
            "shards)")
    return failures


def main() -> str:
    t = run(verbose=False)
    us = t["wall_ms_sharded"] * 1e3 / t["n_tasks"]
    return csv_line(
        "sharding_bench", us,
        f"throughput={t['throughput_speedup']:.2f}x;"
        f"capacity={t['pool_capacity_ratio']:.1f}x")


def _maybe_reexec() -> None:
    """Re-exec under a forced host device count when the mesh needs
    more devices than jax would otherwise expose (same contract as
    tests/harness/simulate.py: a user-set count always wins)."""
    from repro.xla_flags import argv_int, reexec_with_host_devices
    argv = sys.argv[1:]
    reexec_with_host_devices(
        argv_int(argv, "--shards", 4),
        ["-m", "benchmarks.sharding_bench"] + argv)


if __name__ == "__main__":
    _maybe_reexec()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="smaller stream for CI")
    ap.add_argument("--shards", type=int, default=4)
    args = ap.parse_args()
    out = run(n_tasks=24 if args.smoke else 48, shards=args.shards,
              verbose=True)
    failures = check(out)
    for f in failures:
        print(f"GATE FAILED: {f}", file=sys.stderr)
    sys.exit(1 if failures else 0)
