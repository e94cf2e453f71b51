"""Step-level serving benchmark: p50/p95 virtual-clock task latency
and KV-page high-water, step loop vs wave-lockstep.

Drives a bursty, duplicate-bearing stream of uniform long prompts
through the real-model engine's step-level loop (``run_stepped``) with
routing forced to the paper's published 45.8% escalation rate, and
compares its virtual-clock task latencies against a simulated
wave-lockstep timeline over the *same* arrivals, modes and cost model.

The virtual clock counts **device-program launches**: one decode step
of any bucketed group costs 1, one prefill chunk of ``chunk_tokens``
costs 1. Each model server is an independent executor (ACAR's
ensemble members are separate services in the paper's deployment), so
the step loop's tick advance is the *max* programs any one server
launched that tick — same-server programs serialize, cross-server
ones overlap. The wave timeline is charged in the same units but is
serial by construction (that is what lockstep means — ``run_batch``
drains the probe wave, then each member wave one after another, idling
every other server): a one-shot prefill of an S-token prompt costs
ceil(S/C), each member wave costs its own prefill (twin members reuse
the probe's pages for free) plus ``max_new`` decode steps, and waves
serialize with each other. Prefix-cache hits skip prefill charges on
both sides, tracked with the same seen-prompt logic.

Gates (persisted via ``persist_bench`` to ``BENCH_serving.json`` +
``experiments/bench/serving.json``, uploaded nightly by CI):

* p95 virtual-clock task latency must improve >= 1.5x over
  wave-lockstep at the paper's 45.8% escalation with bursty arrivals
  (the step loop retires single-agent rows while the wave would still
  be draining its slowest full-arena member);
* the step loop's measured probe-server KV-page high-water must not
  regress vs the wave baseline recorded in ``BENCH_kv.json``
  (mid-stream retirement must not cost memory).

    PYTHONPATH=src:tests python -m benchmarks.serving_bench [--smoke]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import PAPER_RATE_BLOCK, csv_line, persist_bench
from repro.configs.acar import ACARConfig
from repro.configs.registry import get_config
from repro.data import tokenizer as tok
from repro.data.tasks import Task
from repro.models import params as params_lib
from repro.serving import (
    BatchedACAREngine, MicroBatchPolicy, ZooModel)

BENCH_KV = Path("BENCH_kv.json")


def bench_zoo(seed: int = 0):
    """Tiny dense zoo mirroring kv_bench: the arena's third member IS
    the probe model (paper ARENA3), so twin reuse is exercised."""
    zoo = []
    for i in range(3):
        cfg = get_config("smollm-135m", reduced=True).replace(
            vocab_size=tok.VOCAB_SIZE, dtype="float32",
            tie_embeddings=True)
        prm = params_lib.init_params(cfg, jax.random.PRNGKey(seed + i))
        zoo.append(ZooModel(name=f"m{i}", cfg=cfg, params=prm))
    probe = zoo[0]
    ensemble = [zoo[1], zoo[2],
                ZooModel(name="m3-probe", cfg=probe.cfg,
                         params=probe.params)]
    return probe, ensemble


def bursty_tasks(n_tasks: int, prompt_chars: int, seed: int,
                 burst: int, gap: int, duplicate_rate: float = 0.15):
    """Uniform long prompts arriving in bursts of ``burst`` every
    ``gap`` virtual ticks. Returns (tasks, arrivals)."""
    rng = np.random.default_rng(seed + 0xB0B5)
    tasks, arrivals = [], []
    for i in range(n_tasks):
        if tasks and rng.random() < duplicate_rate:
            tasks.append(tasks[int(rng.integers(len(tasks)))])
        else:
            digits = "".join(str(rng.integers(10))
                             for _ in range(prompt_chars - 8))
            tasks.append(Task(
                task_id=f"serve-{i:05d}", benchmark="serving_bench",
                kind="math", text=f"{digits} + 1 = ", gold="0",
                difficulty=0.0))
        arrivals.append((i // burst) * gap)
    return tasks, arrivals


def forced_modes(n_tasks: int, seed: int) -> np.ndarray:
    """Per-task modes realising the paper's 45.8% escalation,
    deterministically shuffled and keyed by admission index so wave
    and step execution force identical routes."""
    rng = np.random.default_rng(seed + 0x45A)
    modes: list = []
    while len(modes) < n_tasks:
        block = list(PAPER_RATE_BLOCK)
        rng.shuffle(block)
        modes.extend(block)
    return np.asarray(modes[:n_tasks], np.int32)


def index_route_fn(modes: np.ndarray):
    def route(sig, indices):
        return jnp.asarray(modes[np.asarray(indices, np.int64)])
    return route


def wave_lockstep_latencies(arrivals, modes, *, batch_size: int,
                            max_wait: int, prompt_len: int,
                            chunk_tokens: int, max_new: int,
                            n_members: int, arena_lite: int,
                            twin_members, prompts) -> np.ndarray:
    """Virtual-clock completion simulation of the wave-lockstep engine
    over the same arrivals/modes, in device-program units (see module
    docstring). Batches form fill-or-timeout (``AdmissionQueue.ready``
    semantics) and execute strictly one after another."""
    n = len(arrivals)
    prefill_units = -(-prompt_len // chunk_tokens)
    seen_probe: set = set()
    seen_member = [set() for _ in range(n_members)]
    latencies = np.zeros(n, float)
    i = 0
    busy = 0.0
    while i < n:
        # fill-or-timeout, matching AdmissionQueue.next_ready_at:
        # whichever fires first — the arrival of the batch-size-th
        # request, or the head's wait budget — and only requests that
        # have arrived by the formation instant join the batch
        timeout = arrivals[i] + max_wait
        if i + batch_size <= n:
            formed = min(arrivals[i + batch_size - 1], timeout)
        else:
            formed = timeout
        j = i
        while (j < n and j - i < batch_size
               and arrivals[j] <= formed):
            j += 1
        start = max(busy, formed)
        # probe stage: one (bucketed) prefill over the cache-missed
        # rows + the fixed-length decode scan
        miss = any(prompts[r] not in seen_probe for r in range(i, j))
        seen_probe.update(prompts[r] for r in range(i, j))
        dur = (prefill_units if miss else 0) + max_new
        # member waves, serial (run_batch loops members)
        for mi in range(n_members):
            rows = [r for r in range(i, j)
                    if modes[r] >= (1 if mi < arena_lite else 2)]
            if not rows:
                continue
            if mi in twin_members:
                dur += max_new                # seeded: no prefill
            else:
                mmiss = any(prompts[r] not in seen_member[mi]
                            for r in rows)
                seen_member[mi].update(prompts[r] for r in rows)
                dur += (prefill_units if mmiss else 0) + max_new
        end = start + dur
        for r in range(i, j):
            latencies[r] = end - arrivals[r]
        busy = end
        i = j
    return latencies


def run(n_tasks: int = 48, batch_size: int = 8,
        prompt_chars: int = 56, max_new_tokens: int = 8,
        chunk_tokens: int = 8, burst: int = 8, gap: int = 24,
        active_rows: int = 16, prefix_cache: int = 24,
        seed: int = 0, verbose: bool = True) -> dict:
    """``active_rows`` is the step loop's admission cap: twice the
    wave's batch size, because streaming admission is not bound to
    batch formation — rows join whenever the page budget is open.
    ``prefix_cache`` is smaller than the wave baseline's 32 entries:
    cost-aware eviction (prefill-tokens-saved per page held) keeps the
    valuable prompts cached, so the step loop serves 2x the concurrent
    rows inside a *lower* page high-water than ``BENCH_kv.json``'s
    wave measurement — the gate below is what proves the extra
    concurrency is paid for by shorter page lifetimes (mid-stream
    retirement + chunked prefill), not by more memory."""
    tasks, arrivals = bursty_tasks(n_tasks, prompt_chars, seed, burst,
                                   gap)
    modes = forced_modes(n_tasks, seed)
    probe, ensemble = bench_zoo(seed)
    acfg = ACARConfig(probe_temperature=0.9, seed=seed)
    policy = MicroBatchPolicy(max_batch_size=batch_size,
                              max_batch_tokens=1 << 20)
    prompt_len = int(tok.encode_aligned([tasks[0].text]).shape[1])
    prompts = [t.text for t in tasks]

    eng = BatchedACAREngine(
        acfg, probe, ensemble, max_new_tokens=max_new_tokens,
        route_fn=index_route_fn(modes), kv_prefix_cache=prefix_cache)
    t0 = time.perf_counter()
    # real run: the step loop's own tick accounting is the measurement
    queue_submit = [(t, a) for t, a in zip(tasks, arrivals)]
    from repro.serving import AdmissionQueue
    from repro.serving.scheduler import StepPlanner
    from repro.serving.step_loop import StepLoopRunner
    queue = AdmissionQueue(policy)
    for t, a in queue_submit:
        queue.submit(t, arrival_time=a)
    runner = StepLoopRunner(
        eng, queue, StepPlanner(chunk_tokens=chunk_tokens,
                                max_active_rows=active_rows))
    stats = runner.run()
    wall_ms = (time.perf_counter() - t0) * 1e3

    step_lat = np.asarray(
        [stats.timeline[i][2] - stats.timeline[i][0]
         for i in range(n_tasks)], float)
    twin = {mi for mi, zm in enumerate(ensemble)
            if zm.params is probe.params}
    wave_lat = wave_lockstep_latencies(
        arrivals, modes, batch_size=batch_size,
        max_wait=policy.max_wait_ticks, prompt_len=prompt_len,
        chunk_tokens=chunk_tokens, max_new=max_new_tokens,
        n_members=len(ensemble), arena_lite=acfg.arena_lite_size,
        twin_members=twin, prompts=prompts)

    probe_kv = eng.kv_stats()[probe.name]
    kv_baseline = None
    if BENCH_KV.exists():
        kv_baseline = json.loads(BENCH_KV.read_text()).get(
            "kv_pages_highwater")

    out = {
        "n_tasks": n_tasks,
        "batch_size": batch_size,
        "active_rows": active_rows,
        "prompt_len": prompt_len,
        "chunk_tokens": chunk_tokens,
        "max_new_tokens": max_new_tokens,
        "burst": burst,
        "gap": gap,
        "escalation_rate": float(np.mean(modes >= 1)),
        "step_ticks": stats.ticks,
        "step_invocations": stats.invocations,
        "step_prefill_chunks": stats.prefill_chunks,
        "step_p50_latency": float(np.percentile(step_lat, 50)),
        "step_p95_latency": float(np.percentile(step_lat, 95)),
        "wave_p50_latency": float(np.percentile(wave_lat, 50)),
        "wave_p95_latency": float(np.percentile(wave_lat, 95)),
        "p95_speedup": float(np.percentile(wave_lat, 95)
                             / np.percentile(step_lat, 95)),
        "p50_speedup": float(np.percentile(wave_lat, 50)
                             / np.percentile(step_lat, 50)),
        "kv_pages_highwater_step": probe_kv.pages_highwater,
        "kv_pages_highwater_baseline": kv_baseline,
        "prefix_evictions": probe_kv.prefix_evictions,
        "wall_ms": wall_ms,
    }
    persist_bench("serving", out)
    if verbose:
        for k, v in out.items():
            print(f"  {k}: {v}")
    return out


def check(out: dict) -> list:
    """Perf gates: p95 >= 1.5x over wave-lockstep at the paper's
    escalation; KV high-water no worse than the BENCH_kv baseline."""
    failures = []
    if out["p95_speedup"] < 1.5:
        failures.append(
            f"p95 speedup {out['p95_speedup']:.2f}x < 1.5x gate")
    base = out.get("kv_pages_highwater_baseline")
    if base is not None and out["kv_pages_highwater_step"] > base:
        failures.append(
            f"step KV high-water {out['kv_pages_highwater_step']} "
            f"regressed vs BENCH_kv baseline {base}")
    return failures


def main() -> str:
    t = run(verbose=False)
    us = t["wall_ms"] * 1e3 / t["n_tasks"]
    return csv_line(
        "serving_bench", us,
        f"p95_speedup={t['p95_speedup']:.2f}x;"
        f"kv_hw={t['kv_pages_highwater_step']}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="smaller stream for CI")
    args = ap.parse_args()
    out = run(n_tasks=32 if args.smoke else 48,
              verbose=True)
    failures = check(out)
    for f in failures:
        print(f"GATE FAILED: {f}", file=sys.stderr)
    sys.exit(1 if failures else 0)
