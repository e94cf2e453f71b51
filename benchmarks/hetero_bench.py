"""Heterogeneous paged-state benchmark: quant-KV capacity, the ring
window cap, and a live mixed Mamba+quant+SWA+dense serving leg.

Three perf claims ride on the heterogeneous page layouts
(bit-equivalence is proved by ``tests/harness/simulate.py --hetero``;
this benchmark gates the capacity wins):

* **int8 quant pages** — codes plus per-vector f32 scale planes cost
  ``Dh + 4`` bytes per KV vector against bf16's ``2*Dh``: at
  head_dim=64 a fixed per-device HBM budget holds ~1.88x the decode
  rows (gate: >= 1.8x vs the member's bf16 twin, measured on the
  actually-allocated page pools).
* **ring pages** — a sliding-window member's per-row pages cap at
  ``ceil(window/page)`` no matter how long the prompt runs, so the KV
  high-water for long-prompt SWA streams is window-bound while the
  dense twin's grows with the prompt (the dense/ring high-water ratio
  is reported and must exceed the window's share of the prompt).
* **recurrent-state lanes** — an SSM member serves from O(1)-per-lane
  conv+SSM state pages; the live leg proves a Mamba member admits,
  forks and retires lanes inside the stepped engine alongside quant
  and ring members (its lane high-water must be > 0).

Gates (persisted via ``persist_bench`` to ``BENCH_hetero.json`` +
``experiments/bench/hetero.json``, uploaded nightly by CI):

* quant rows-per-device >= 1.8x the bf16 twin at head_dim=64;
* ring per-row pages == the window cap, dense/ring KV high-water
  ratio > 2x on long prompts;
* the live hetero fleet finishes with lanes high-water > 0.

    PYTHONPATH=src:tests python -m benchmarks.hetero_bench [--smoke]
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
from repro.configs.registry import get_config
from repro.serving import BatchedACAREngine, MicroBatchPolicy
from repro.serving.kv_pool import PagedKVServer, pages_for

PAGE = 8


def _bytes_per_page(cfg) -> int:
    """Bytes per page of a member's actually-allocated pool (all
    leaves: codes + scale planes for quant, conv+h for lanes)."""
    srv = PagedKVServer(cfg, page_size=PAGE, prefix_cache_entries=0)
    srv.ensure_capacity_stream(2, 32, 2, 8)
    total = sum(int(leaf.nbytes) for leaf in srv.pages.values())
    return total // srv.pool.num_pages


def _quant_capacity_leg(prompt_len: int = 128,
                        max_new_tokens: int = 16) -> dict:
    """Decode rows a fixed HBM budget affords: int8+scales pages vs
    the same member's bf16 twin. Geometry (pages per row) is layout-
    independent here, so the row ratio is the page-byte ratio."""
    bf16 = get_config("smollm-135m", reduced=True)
    quant = bf16.replace(kv_quant=True)
    assert bf16.dtype == "bfloat16" and bf16.resolved_head_dim == 64
    b_bf16 = _bytes_per_page(bf16)
    b_quant = _bytes_per_page(quant)

    srv = PagedKVServer(bf16, page_size=PAGE, prefix_cache_entries=0)
    g = srv.row_geometry(prompt_len, max_new_tokens)
    row_pages = g.nbp + 2 * g.n_tail             # 2 probe lanes/row
    budget = b_bf16 * 4096                       # bf16 4096-page pool
    rows_bf16 = (budget // b_bf16) // row_pages
    rows_quant = (budget // b_quant) // row_pages
    return {
        "page_bytes_bf16": b_bf16,
        "page_bytes_quant": b_quant,
        "rows_per_device_bf16": int(rows_bf16),
        "rows_per_device_quant": int(rows_quant),
        "quant_rows_ratio": rows_quant / rows_bf16,
    }


def _window_leg(window: int = 16, prompt_len: int = 96,
                max_new_tokens: int = 8, rows: int = 8) -> dict:
    """KV high-water for long-prompt SWA streams: the ring server's
    per-row pages cap at ceil(window/page); the dense twin's grow with
    prompt_len + max_new. Both pools are really allocated and walked
    through a rows-deep admission to read the measured high-water."""
    base = get_config("smollm-135m", reduced=True)
    swa = base.replace(window=window)

    def highwater_bytes(cfg):
        srv = PagedKVServer(cfg, page_size=PAGE,
                            prefix_cache_entries=0)
        srv.ensure_capacity_stream(rows, prompt_len, 1,
                                   max_new_tokens)
        g = srv.row_geometry(prompt_len, max_new_tokens)
        held = [srv._alloc_retry(g.nbp + g.n_tail)
                for _ in range(rows)]
        hw = srv.stats.pages_highwater * _bytes_per_page(cfg)
        for pages in held:
            srv.pool.release(pages)
        return g, int(hw)

    g_dense, hw_dense = highwater_bytes(base)
    g_ring, hw_ring = highwater_bytes(swa)
    return {
        "window": window,
        "swa_prompt_len": prompt_len,
        "ring_row_pages": int(g_ring.nb),
        "ring_row_pages_cap": int(pages_for(
            min(prompt_len + max_new_tokens, window), PAGE)),
        "dense_row_pages": int(g_dense.nb),
        "kv_highwater_bytes_dense": hw_dense,
        "kv_highwater_bytes_ring": hw_ring,
        "swa_highwater_ratio": hw_dense / max(hw_ring, 1),
    }


def _live_leg(n_tasks: int, seed: int, max_new_tokens: int) -> dict:
    """Stepped serving of the mixed hetero fleet (Mamba lanes + SWA
    ring + quant probe/member) at the paper's forced escalation rate:
    proves all three layouts admit/fork/retire through one step loop
    and reports their measured page high-waters."""
    from harness.simulate import hetero_zoo
    from repro.models.transformer import resolve_layout
    tasks, _ = bursty_tasks(n_tasks, 24, seed, burst=n_tasks, gap=0)
    modes = forced_modes(n_tasks, seed)
    probe, ensemble = hetero_zoo(seed)
    acfg = ACARConfig(probe_temperature=0.9, seed=seed)
    eng = BatchedACAREngine(
        acfg, probe, ensemble, max_new_tokens=max_new_tokens,
        route_fn=index_route_fn(modes))
    t0 = time.perf_counter()
    res = eng.run_stepped(
        list(tasks), MicroBatchPolicy(max_batch_size=8,
                                      max_batch_tokens=1 << 20),
        chunk_tokens=8, max_active_rows=8)
    wall_ms = (time.perf_counter() - t0) * 1e3
    layouts = {m.name: (resolve_layout(m.cfg) or "dense*")
               for m in [probe] + list(ensemble)}
    highwater = {name: int(st.pages_highwater)
                 for name, st in eng.kv_stats().items()}
    lanes_hw = sum(hw for name, hw in highwater.items()
                   if layouts.get(name) == "lanes")
    return {
        "n_tasks": n_tasks,
        "escalation_rate": float(np.mean(modes >= 1)),
        "fleet_layouts": layouts,
        "pages_highwater": highwater,
        "lanes_pages_highwater": lanes_hw,
        "ticks": res.step.ticks,
        "launches": res.step.launches,
        "wall_ms": wall_ms,
    }


def run(n_tasks: int = 48, max_new_tokens: int = 6, seed: int = 0,
        verbose: bool = True) -> dict:
    out = {}
    out.update(_quant_capacity_leg())
    out.update(_window_leg())
    out.update(_live_leg(n_tasks, seed, max_new_tokens))
    persist_bench("hetero", out)
    if verbose:
        for k, v in out.items():
            print(f"  {k}: {v}")
    return out


def check(out: dict) -> list:
    failures = []
    if out["quant_rows_ratio"] < 1.8:
        failures.append(
            f"quant rows-per-device {out['quant_rows_ratio']:.2f}x "
            "< 1.8x gate vs the bf16 twin (int8 codes + f32 scale "
            "planes must halve page bytes at head_dim=64)")
    if out["ring_row_pages"] != out["ring_row_pages_cap"]:
        failures.append(
            f"ring row pages {out['ring_row_pages']} != window cap "
            f"{out['ring_row_pages_cap']} (SWA pages must not grow "
            "with prompt length)")
    if out["swa_highwater_ratio"] < 2.0:
        failures.append(
            f"SWA KV high-water only {out['swa_highwater_ratio']:.2f}x "
            "below dense on long prompts (< 2x gate)")
    if out["lanes_pages_highwater"] <= 0:
        failures.append(
            "live fleet's Mamba member held no lanes (lanes "
            "high-water 0 — SSM member never admitted)")
    return failures


def main() -> str:
    t = run(n_tasks=24, verbose=False)
    us = t["wall_ms"] * 1e3 / t["n_tasks"]
    return csv_line(
        "hetero_bench", us,
        f"quant={t['quant_rows_ratio']:.2f}x;"
        f"swa={t['swa_highwater_ratio']:.1f}x;"
        f"lanes_hw={t['lanes_pages_highwater']}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="smaller stream for CI")
    args = ap.parse_args()
    out = run(n_tasks=24 if args.smoke else 48, verbose=True)
    failures = check(out)
    for f in failures:
        print(f"GATE FAILED: {f}", file=sys.stderr)
    sys.exit(1 if failures else 0)
