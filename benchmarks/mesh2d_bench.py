"""2-D ("data", "model") serving-mesh benchmark: MoE compaction win
and tensor-parallel KV capacity scaling.

Two perf claims ride on the 2-D mesh (bit-equivalence is proved by
``tests/harness/simulate.py --mesh2d``; this benchmark gates the
performance):

* **MoE compaction** — capacity-free gather-dispatch MoE members are
  batch-composition invariant, so they qualify for the escalated-subset
  compacted path exactly like dense members. At the paper's published
  45.8% escalation rate the ensemble decodes the escalated subset
  instead of the full masked batch: decode rows serving requests drop
  >= 2x for a mixed dense+MoE fleet (the bucket-padded device-token
  ratio is reported alongside).
* **KV capacity** — with a "model" axis each model column holds only
  its kv-head slice of every page, so per-device page bytes shrink by
  the model-axis size: for a fixed per-device HBM budget, the page
  pool each member can afford grows ~model-x (gate: >= 1.8x at
  model=2).

A short 2-D step-loop serving leg (mixed dense + gather-MoE fleet,
``megastep="auto"``) runs on the same mesh to report live tick /
launch / placement / steal numbers alongside the measured gates.

Gates (persisted via ``persist_bench`` to ``BENCH_mesh2d.json`` +
``experiments/bench/mesh2d.json``, uploaded nightly by CI):

* ensemble decode-row reduction (masked / compacted) >= 2x with the
  gather-MoE member on the compacted path;
* per-member page capacity in a fixed device byte budget >= 1.8x at
  model=2.

    PYTHONPATH=src:tests python -m benchmarks.mesh2d_bench [--smoke]
        [--data 2] [--model 2]
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from benchmarks.common import csv_line, persist_bench
from benchmarks.serving_bench import (
    bursty_tasks, forced_modes, index_route_fn)
from repro.configs.acar import ACARConfig
from repro.serving import BatchedACAREngine, MicroBatchPolicy


def _engine(modes, seed, max_new_tokens):
    from harness.simulate import mesh2d_zoo
    probe, ensemble = mesh2d_zoo(seed)
    acfg = ACARConfig(probe_temperature=0.9, seed=seed)
    return BatchedACAREngine(
        acfg, probe, ensemble, max_new_tokens=max_new_tokens,
        route_fn=index_route_fn(modes), kv_prefix_cache=8)


def _compaction_leg(tasks, modes, seed, max_new_tokens):
    """Wave-mode run of the mixed dense+MoE fleet at the paper rate:
    the engine's own CompactionStats carry the masked-vs-compacted
    decode-token accounting; the gather-MoE member must be on the
    compacted path for the ratio to clear the gate (a masked MoE
    member contributes full-batch rows and drags it below 2x)."""
    from repro.sampling import batch_invariant
    eng = _engine(modes, seed, max_new_tokens)
    moe = [zm for zm in eng.ensemble if zm.cfg.moe is not None]
    assert moe and all(batch_invariant(zm.cfg) for zm in moe)
    res = eng.run_batch(list(tasks))
    cs = res.compaction
    # row accounting: rows serving escalated requests vs the masked
    # full batch every member would otherwise decode. Bucket padding
    # (power-of-two jit shapes) is reported separately via the token
    # ratio — padded rows burn device work but serve no request.
    compacted_rows = int(sum(cs.bucket_rows))
    masked_rows = cs.batch * len(eng.ensemble)
    return {
        "escalation_rate": float(np.mean(modes >= 1)),
        "escalated_rows": cs.escalated_rows,
        "ensemble_decode_rows": compacted_rows,
        "ensemble_decode_rows_masked": masked_rows,
        "decode_row_reduction": masked_rows / max(compacted_rows, 1),
        "ensemble_decode_tokens": cs.ensemble_decode_tokens,
        "ensemble_decode_tokens_saved":
            cs.ensemble_decode_tokens_saved,
        "decode_token_reduction":
            float(cs.ensemble_decode_token_reduction),
        "moe_members_compacted": len(moe),
    }


def _capacity_leg(data: int, model: int):
    """Per-device page bytes of one member's sharded KV pool, model=1
    vs model=m on the same data extent: the model columns slice
    kv-heads within each page, so a fixed per-device byte budget
    affords ~m-x the pages."""
    from harness.simulate import mesh2d_zoo
    from repro.serving.mesh import ServingMesh, ShardedPagedKVServer

    cfg = mesh2d_zoo(0)[1][1].cfg                # the gather-MoE member
    num_pages = 64

    def device_page_bytes(m: int) -> int:
        smesh = ServingMesh(data=data, model=m)
        srv = ShardedPagedKVServer(cfg, smesh, page_size=8)
        srv._rebuild_all(num_pages, 2, key=(1, 1, 1, 1))
        shard_bytes = srv.k_pages.addressable_shards[0].data.nbytes \
            + srv.v_pages.addressable_shards[0].data.nbytes
        return shard_bytes // num_pages

    bytes_1 = device_page_bytes(1)
    bytes_m = device_page_bytes(model)
    budget = bytes_1 * num_pages                 # model=1 pool footprint
    return {
        "device_page_bytes_model1": int(bytes_1),
        f"device_page_bytes_model{model}": int(bytes_m),
        "pages_in_budget_model1": int(budget // bytes_1),
        f"pages_in_budget_model{model}": int(budget // bytes_m),
        "capacity_ratio": (budget // bytes_m) / (budget // bytes_1),
    }


def _serving_leg(tasks, modes, seed, max_new_tokens, data, model):
    """Live 2-D step-loop leg: mixed fleet, auto megastep."""
    eng = _engine(modes, seed, max_new_tokens)
    t0 = time.perf_counter()
    res = eng.run_stepped(
        list(tasks), MicroBatchPolicy(max_batch_size=8,
                                      max_batch_tokens=1 << 20),
        chunk_tokens=4, max_active_rows=8, data_shards=data,
        model_shards=model, megastep="auto")
    wall_ms = (time.perf_counter() - t0) * 1e3
    placements = [int(res.metrics.get("acar_shard_placements_total",
                                      shard=str(k)))
                  for k in range(data)]
    steals = sum(
        int(res.metrics.get("acar_shard_steals_total",
                            src=str(a), dst=str(b)))
        for a in range(data) for b in range(data) if a != b)
    return {
        "ticks": res.step.ticks,
        "launches": res.step.launches,
        "masked_decode_steps": res.step.masked_decode_steps,
        "shard_placements": placements,
        "shard_steals": steals,
        "wall_ms": wall_ms,
    }


def run(n_tasks: int = 48, prompt_chars: int = 24,
        max_new_tokens: int = 4, data: int = 2, model: int = 2,
        seed: int = 0, verbose: bool = True) -> dict:
    tasks, _ = bursty_tasks(n_tasks, prompt_chars, seed,
                            burst=n_tasks, gap=0)
    modes = forced_modes(n_tasks, seed)
    out = {"n_tasks": n_tasks, "data_shards": data,
           "model_shards": model,
           "max_new_tokens": max_new_tokens}
    out.update(_compaction_leg(tasks, modes, seed, max_new_tokens))
    out.update(_capacity_leg(data, model))
    out.update(_serving_leg(tasks, modes, seed, max_new_tokens,
                            data, model))
    persist_bench("mesh2d", out)
    if verbose:
        for k, v in out.items():
            print(f"  {k}: {v}")
    return out


def check(out: dict) -> list:
    failures = []
    if out["decode_row_reduction"] < 2.0:
        failures.append(
            f"ensemble decode-row reduction "
            f"{out['decode_row_reduction']:.2f}x < 2x gate at "
            f"{out['escalation_rate']:.1%} escalation (MoE members "
            "must take the compacted escalated-subset path)")
    if out["capacity_ratio"] < 1.8:
        failures.append(
            f"KV capacity {out['capacity_ratio']:.2f}x < 1.8x gate "
            f"at model={out['model_shards']} (pages must shard "
            "kv-heads over the model axis)")
    if not out["moe_members_compacted"]:
        failures.append("fleet carried no compactable MoE member")
    return failures


def main() -> str:
    t = run(n_tasks=24, verbose=False)
    us = t["wall_ms"] * 1e3 / t["n_tasks"]
    return csv_line(
        "mesh2d_bench", us,
        f"compaction={t['decode_row_reduction']:.2f}x;"
        f"capacity={t['capacity_ratio']:.1f}x")


def _maybe_reexec() -> None:
    """Re-exec under a forced host device count when the 2-D mesh
    needs more devices than jax would otherwise expose (same contract
    as tests/harness/simulate.py: a user-set count always wins)."""
    from repro.xla_flags import argv_int, reexec_with_host_devices
    argv = sys.argv[1:]
    need = argv_int(argv, "--data", 2) * argv_int(argv, "--model", 2)
    reexec_with_host_devices(
        need, ["-m", "benchmarks.mesh2d_bench"] + argv)


if __name__ == "__main__":
    _maybe_reexec()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="smaller stream for CI")
    ap.add_argument("--data", type=int, default=2)
    ap.add_argument("--model", type=int, default=2)
    args = ap.parse_args()
    out = run(n_tasks=24 if args.smoke else 48, data=args.data,
              model=args.model, verbose=True)
    failures = check(out)
    for f in failures:
        print(f"GATE FAILED: {f}", file=sys.stderr)
    sys.exit(1 if failures else 0)
