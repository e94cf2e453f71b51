"""Megastep decode benchmark: wall-clock decode throughput of K fused
device-resident ticks vs the per-tick step loop.

Drives a saturated (all arrivals at tick 0) stream of short prompts
with routing forced to mode 0 (probe-only — decode-dominated) through
the mesh-sharded step loop (data=--shards forced host devices) twice:
once with megastep K=1 (the per-tick baseline: one shard_map'd decode
launch + one host logits round-trip per tick) and once with
K=--megastep fused ticks (one launch per megastep, lane state
device-resident, only (K, B) token ids + done bits crossing back).
Each configuration runs twice — an untimed warmup to populate the
jit cache, then the measured run — so the gate measures steady-state
launch/transfer overhead, not compilation.

The two runs serve bit-identical token streams (proved by
``tests/harness/simulate.py --megastep``); this benchmark gates the
wall-clock win that motivates the fusion.

Gates (persisted via ``persist_bench`` to ``BENCH_megastep.json`` +
``experiments/bench/megastep.json``, uploaded nightly by CI):

* wall-clock decode tokens/s at K=16 must be >= 2x the per-tick loop;
* both runs must emit the same decode-token count (same streams — a
  mismatch means the fusion changed semantics, not just speed);
* host<->device transfer events per emitted token must drop by at
  least K/2 (the per-tick logits round-trip really is gone).

    PYTHONPATH=src:tests python -m benchmarks.megastep_bench [--smoke]
        [--shards 4] [--megastep 16]
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from benchmarks.common import csv_line, persist_bench
from benchmarks.serving_bench import (
    bench_zoo, bursty_tasks, index_route_fn)
from repro.configs.acar import ACARConfig
from repro.data import tokenizer as tok
from repro.serving import AdmissionQueue, MicroBatchPolicy
from repro.serving.scheduler import StepPlanner
from repro.serving.step_loop import ShardedStepLoopRunner


def _run_loop(tasks, modes, *, megastep, shards, chunk_tokens,
              max_new_tokens, active_rows, batch_size, seed):
    """One mesh-sharded step-loop run over a saturated queue.
    Returns (runner, wall_s)."""
    from repro.serving import BatchedACAREngine
    from repro.serving.mesh import ServingMesh
    probe, ensemble = bench_zoo(seed)
    acfg = ACARConfig(probe_temperature=0.9, seed=seed)
    eng = BatchedACAREngine(
        acfg, probe, ensemble, max_new_tokens=max_new_tokens,
        route_fn=index_route_fn(modes), kv_prefix_cache=0)
    queue = AdmissionQueue(MicroBatchPolicy(
        max_batch_size=batch_size, max_batch_tokens=1 << 20))
    for t in tasks:
        queue.submit(t, arrival_time=0)
    planner = StepPlanner(chunk_tokens=chunk_tokens,
                          max_active_rows=active_rows,
                          megastep=megastep)
    runner = ShardedStepLoopRunner(eng, queue, planner,
                                   ServingMesh(data=shards))
    t0 = time.perf_counter()
    runner.run()
    return runner, time.perf_counter() - t0


def _measure(tasks, modes, **kw):
    """Warmup (jit-cache fill) + measured run; returns the measured
    runner's stats and decode tokens/s."""
    _run_loop(tasks, modes, **kw)                  # warmup, untimed
    runner, wall_s = _run_loop(tasks, modes, **kw)
    st = runner.stats
    return st, st.decode_tokens / wall_s, wall_s


def run(n_tasks: int = 32, batch_size: int = 8,
        prompt_chars: int = 16, max_new_tokens: int = 16,
        chunk_tokens: int = 8, active_rows: int = 4,
        shards: int = 4, megastep: int = 16, seed: int = 0,
        verbose: bool = True) -> dict:
    """Mode 0 everywhere keeps the run decode-dominated (no member
    prefills), short prompts keep the prefill phase negligible — the
    measured quantity is decode launch + transfer overhead."""
    tasks, _ = bursty_tasks(n_tasks, prompt_chars, seed,
                            burst=n_tasks, gap=0)
    modes = np.zeros(n_tasks, np.int64)
    prompt_len = int(tok.encode_aligned([tasks[0].text]).shape[1])

    kw = dict(shards=shards, chunk_tokens=chunk_tokens,
              max_new_tokens=max_new_tokens, active_rows=active_rows,
              batch_size=batch_size, seed=seed)
    st_1, tps_1, wall_1 = _measure(tasks, modes, megastep=1, **kw)
    st_k, tps_k, wall_k = _measure(tasks, modes, megastep=megastep,
                                   **kw)

    def per_token(st):
        return (st.decode_h2d + st.decode_d2h) \
            / max(st.decode_tokens, 1)

    out = {
        "n_tasks": n_tasks,
        "shards": shards,
        "megastep": megastep,
        "prompt_len": prompt_len,
        "max_new_tokens": max_new_tokens,
        "active_rows_per_shard": active_rows,
        "decode_tokens_per_tick": st_1.decode_tokens,
        "decode_tokens_megastep": st_k.decode_tokens,
        "wall_s_per_tick": wall_1,
        "wall_s_megastep": wall_k,
        "decode_tps_per_tick": tps_1,
        "decode_tps_megastep": tps_k,
        "decode_tps_speedup": tps_k / tps_1,
        "launches_per_tick": st_1.launches,
        "launches_megastep": st_k.launches,
        "masked_decode_steps": st_k.masked_decode_steps,
        "transfers_per_token_per_tick": per_token(st_1),
        "transfers_per_token_megastep": per_token(st_k),
        "transfer_drop": per_token(st_1) / max(per_token(st_k), 1e-9),
    }
    persist_bench("megastep", out)
    if verbose:
        for k, v in out.items():
            print(f"  {k}: {v}")
    return out


def check(out: dict) -> list:
    """Perf gates: >=2x wall-clock decode tokens/s at the configured
    megastep, same decode-token count (stream equality sanity), and
    >= K/2 fewer transfer events per emitted token."""
    k = out["megastep"]
    failures = []
    if out["decode_tps_speedup"] < 2.0:
        failures.append(
            f"megastep K={k} decode throughput "
            f"{out['decode_tps_speedup']:.2f}x < 2x wall-clock gate")
    if out["decode_tokens_megastep"] != out["decode_tokens_per_tick"]:
        failures.append(
            f"decode token counts diverge: "
            f"{out['decode_tokens_per_tick']} per-tick vs "
            f"{out['decode_tokens_megastep']} megastep")
    if out["transfer_drop"] < k / 2:
        failures.append(
            f"transfers per token dropped only "
            f"{out['transfer_drop']:.2f}x < {k / 2:g}x gate at K={k}")
    return failures


def main() -> str:
    t = run(verbose=False)
    us = t["wall_s_megastep"] * 1e6 / t["n_tasks"]
    return csv_line(
        "megastep_bench", us,
        f"decode_tps={t['decode_tps_speedup']:.2f}x;"
        f"transfers={t['transfer_drop']:.1f}x")


def _maybe_reexec() -> None:
    """Re-exec under a forced host device count when the mesh needs
    more devices than jax would otherwise expose (same contract as
    tests/harness/simulate.py: a user-set count always wins)."""
    from repro.xla_flags import argv_int, reexec_with_host_devices
    argv = sys.argv[1:]
    reexec_with_host_devices(
        argv_int(argv, "--shards", 4),
        ["-m", "benchmarks.megastep_bench"] + argv)


if __name__ == "__main__":
    _maybe_reexec()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="smaller stream for CI")
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--megastep", type=int, default=16)
    args = ap.parse_args()
    out = run(n_tasks=16 if args.smoke else 32, shards=args.shards,
              megastep=args.megastep, verbose=True)
    failures = check(out)
    for f in failures:
        print(f"GATE FAILED: {f}", file=sys.stderr)
    sys.exit(1 if failures else 0)
