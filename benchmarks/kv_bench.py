"""Paged KV-cache benchmark: probe-KV memory high-water and prefill
reuse at the paper's escalation rate.

Drives a duplicate-bearing stream of uniform long prompts through the
real-model ``BatchedACAREngine`` twice — dense ``tile_cache`` baseline
vs the paged KV subsystem (serving/kv_pool.py) — with routing forced to
the paper's published 45.8% escalation, and measures:

* **probe-KV memory high-water** — pages referenced by the largest
  probe wave (shared prompt pages + COW tails + sample-private decode
  pages) vs the ``B*N*(prompt+new)`` slots ``tile_cache`` materialises.
  The N probe samples share the read-only prompt pages, so the paged
  working set approaches ``prompt + N*new`` per task; the gate asserts
  >= 2x reduction at the benchmark's prompt/decode shape.
* **prefill tokens reused** — prompt prefills served from retained
  pages instead of recomputation: ensemble members that are the probe
  model seed their prefill from the probe's pages the route decision
  kept alive, and duplicate requests hit the prompt prefix cache. The
  gate asserts the probe->ensemble counter is nonzero at the paper
  rate (escalated rows exist, and the arena's third member is the
  probe model, mirroring the paper's ARENA3).

Both engines must produce identical answers (the bit-equivalence
contract is enforced in depth by ``tests/harness/simulate.py
--paged-kv``; here it is a cheap sanity gate). Results persist to
``BENCH_kv.json`` + ``experiments/bench/kv.json`` via
``benchmarks.common.persist_bench``.

    PYTHONPATH=src python -m benchmarks.kv_bench [--smoke]
"""
from __future__ import annotations

import argparse
import sys
import time

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
    BatchedACAREngine, MicroBatchPolicy, ZooModel, dense_tile_slots)


def paper_rate_route_fn(seed: int):
    """route_fn realising the paper's 45.8% escalation rate per wave,
    deterministically shuffled so waves mix modes."""
    rng = np.random.default_rng(seed + 0x45A)

    def route(sig):
        b = int(sig.shape[0])
        block: list = []
        while len(block) < b:
            chunk = list(PAPER_RATE_BLOCK)
            rng.shuffle(chunk)
            block.extend(chunk)
        return jnp.asarray(np.asarray(block[:b], np.int32))
    return route


def bench_zoo(seed: int = 0):
    """Tiny dense zoo; the arena's third member IS the probe model
    (the paper's ARENA3 contains the probe), so probe->ensemble
    prefill-page reuse is sound and exercised."""
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


def long_prompt_tasks(n_tasks: int, prompt_chars: int, seed: int,
                      duplicate_rate: float = 0.15):
    """Uniform long arithmetic-surface prompts (the memory regime where
    prefix sharing matters: prompt >> decode), with duplicate
    resubmissions exercising the prompt prefix cache."""
    rng = np.random.default_rng(seed + 0xA11)
    tasks = []
    for i in range(n_tasks):
        if tasks and rng.random() < duplicate_rate:
            tasks.append(tasks[int(rng.integers(len(tasks)))])
            continue
        digits = "".join(str(rng.integers(10))
                         for _ in range(prompt_chars - 8))
        tasks.append(Task(
            task_id=f"kv-{i:05d}", benchmark="kv_bench",
            kind="arithmetic", text=f"{digits} + 1 = ", gold="0",
            difficulty=0.0))
    return tasks


def run(n_tasks: int = 96, batch_size: int = 8,
        prompt_chars: int = 56, max_new_tokens: int = 8,
        page_size: int = 8, seed: int = 0,
        verbose: bool = True) -> dict:
    tasks = long_prompt_tasks(n_tasks, prompt_chars, seed)
    probe, ensemble = bench_zoo(seed)
    acfg = ACARConfig(probe_temperature=0.9, seed=seed)
    policy = MicroBatchPolicy(max_batch_size=batch_size,
                              max_batch_tokens=1 << 20)
    s = tok.encode_aligned([tasks[0].text]).shape[1]
    n = acfg.n_probe_samples

    dense_eng = BatchedACAREngine(
        acfg, probe, ensemble, max_new_tokens=max_new_tokens,
        compact=True, shared_prefix=True, paged=False,
        route_fn=paper_rate_route_fn(seed))
    paged_eng = BatchedACAREngine(
        acfg, probe, ensemble, max_new_tokens=max_new_tokens,
        compact=True, shared_prefix=True, paged=True,
        kv_page_size=page_size,
        route_fn=paper_rate_route_fn(seed))

    t0 = time.perf_counter()
    res_d = dense_eng.run_queued(tasks, policy)
    dense_wall = (time.perf_counter() - t0) * 1e3
    t0 = time.perf_counter()
    res_p = paged_eng.run_queued(tasks, policy)
    paged_wall = (time.perf_counter() - t0) * 1e3

    identical = (list(res_d.final_answers) == list(res_p.final_answers)
                 and np.array_equal(res_d.modes, res_p.modes)
                 and res_d.member_answers == res_p.member_answers)

    kv = paged_eng.kv_stats()
    probe_kv = kv[probe.name]
    token_bytes = probe_kv.page_bytes / probe_kv.page_size
    dense_probe_bytes = dense_tile_slots(
        batch_size, n, s, max_new_tokens) * token_bytes
    paged_probe_bytes = probe_kv.probe_highwater_bytes
    reduction = dense_probe_bytes / max(paged_probe_bytes, 1)
    reused_probe = sum(st.prefill_tokens_reused_probe
                       for st in kv.values())
    reused_prefix = sum(st.prefill_tokens_reused_prefix
                        for st in kv.values())
    metric_reused = sum(
        res_p.metrics.get("acar_kv_prefill_tokens_reused_total",
                          model=name, source=source)
        for name in kv for source in ("probe", "prefix_cache"))

    out = {
        "n_tasks": n_tasks,
        "batch_size": batch_size,
        "prompt_len": s,
        "max_new_tokens": max_new_tokens,
        "n_probe_samples": n,
        "page_size": page_size,
        "escalation_rate": float(np.mean(np.asarray(res_p.modes) >= 1)),
        "identical_answers": identical,
        # probe-KV memory high-water: tile_cache vs paged working set
        "dense_probe_kv_bytes": dense_probe_bytes,
        "paged_probe_kv_bytes": paged_probe_bytes,
        "probe_kv_memory_reduction": reduction,
        "kv_pool_pages": probe_kv.pool_pages,
        "kv_pages_highwater": probe_kv.pages_highwater,
        # prefill reuse at the paper rate
        "prefill_tokens_reused_probe": reused_probe,
        "prefill_tokens_reused_prefix_cache": reused_prefix,
        "prefill_tokens_reused_total_metric": metric_reused,
        "prefill_tokens_computed": sum(
            st.prefill_tokens_computed for st in kv.values()),
        "cow_forks": sum(st.cow_forks for st in kv.values()),
        "dense_wall_ms": dense_wall,
        "paged_wall_ms": paged_wall,
    }
    persist_bench("kv", out)
    if verbose:
        print(f"tasks={n_tasks} batch={batch_size} prompt={s} "
              f"new={max_new_tokens} page={page_size} "
              f"escalation={out['escalation_rate']:.1%} "
              f"identical={identical}")
        print(f"probe KV high-water: dense {dense_probe_bytes/1e3:.1f}"
              f" kB vs paged {paged_probe_bytes/1e3:.1f} kB "
              f"({reduction:.2f}x smaller)")
        print(f"prefill reuse: probe->ensemble {reused_probe} tok, "
              f"prefix cache {reused_prefix} tok, computed "
              f"{out['prefill_tokens_computed']} tok")
    return out


def main() -> str:
    t = run(n_tasks=48, verbose=False)
    us = t["paged_wall_ms"] * 1e3 / t["n_tasks"]
    return csv_line(
        "kv_bench", us,
        f"mem_reduction={t['probe_kv_memory_reduction']:.2f}x;"
        f"reused={t['prefill_tokens_reused_probe']}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tasks", type=int, default=96)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--prompt-chars", type=int, default=56)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="small fast run for CI artifact tracking")
    args = ap.parse_args()
    n = 48 if args.smoke else args.tasks
    out = run(n_tasks=n, batch_size=args.batch_size,
              prompt_chars=args.prompt_chars,
              page_size=args.page_size, seed=args.seed)
    gates = {
        "identical_answers": out["identical_answers"],
        "probe_kv_memory_reduction >= 2.0":
            out["probe_kv_memory_reduction"] >= 2.0,
        "prefill_tokens_reused_probe > 0":
            out["prefill_tokens_reused_probe"] > 0,
        "reuse counter exported":
            out["prefill_tokens_reused_total_metric"] > 0,
    }
    for name, passed in gates.items():
        if not passed:
            print(f"GATE FAILED: {name}", file=sys.stderr)
    sys.exit(0 if all(gates.values()) else 1)
