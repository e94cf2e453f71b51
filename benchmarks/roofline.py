"""Roofline analysis (deliverable g): three terms per (arch x shape)
from the dry-run's compiled artifacts.

    compute    = HLO_FLOPs / (chips x peak bf16 FLOP/s)
    memory     = HLO_bytes / (chips x peak HBM bytes/s)
    collective = collective_bytes / (chips x peak ICI bytes/s)

with the peaks of the device kind the dry-run's meshes model, from the
``PEAKS`` table below.

``compiled.cost_analysis()`` (and the HLO text the collective bytes are
parsed from) is the per-partition program, so per-device quantities are
multiplied by the chip count to recover the global numerators; the two
conventions cancel. The scan-corrected costs from launch/dryrun.py are
used (XLA counts while bodies once — 'raw' would undercount ~L-fold).

MODEL_FLOPS = 6·N_active·D (train) or 2·N_active·D (fwd-only); the
ratio MODEL_FLOPS / HLO_FLOPs exposes remat recompute + redundant
(replicated) compute.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Optional

from benchmarks.common import csv_line, write_json

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 819 GB/s HBM, 1,600 Gbit/s chip-to-chip interconnect).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9,
                    "ici_bytes_s": 1600e9 / 8},
}
# the chip launch/dryrun.py's production meshes are sized for
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip of ``device_kind``; an unknown kind raises
    rather than borrowing another chip's numbers."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


DRYRUN_DIR = Path("experiments/dryrun")
OUT = Path("experiments/bench/roofline.json")


def _advice(dom: str, rec: dict) -> str:
    arch, shape = rec["arch"], rec["shape"]
    if dom == "compute":
        ratio = rec["useful_flops_ratio"]
        if ratio < 0.4:
            return ("compute-bound but only "
                    f"{ratio:.0%} of HLO FLOPs are model FLOPs — cut "
                    "remat recompute / replicated matmuls (sharding "
                    "that actually splits contractions) before chasing "
                    "utilisation")
        return ("compute-bound near useful peak — only larger "
                "per-chip batch or lower-precision matmuls move this")
    if dom == "memory":
        return ("HBM-bound — raise arithmetic intensity: fuse "
                "elementwise chains, keep KV/state in-register across "
                "steps, batch more requests per weight read"
                f" ({arch} {shape})")
    return ("collective-bound — reshard to cut cross-chip traffic "
            "(fewer all-gathers of replicated weights), overlap "
            "collectives with compute, or move the axis the traffic "
            "crosses" f" ({arch} {shape})")


def analyse_record(rec: dict) -> Optional[dict]:
    if rec.get("status") != "ok":
        return None
    pk = peaks(DRYRUN_DEVICE_KIND)
    costs = rec.get("corrected") or rec["raw"]
    chips = rec["chips"]
    # per-partition numbers x chips = global
    flops_g = costs["hlo_flops"] * chips
    bytes_g = costs["hlo_bytes"] * chips
    coll_g = costs["collective"]["total"] * chips
    terms = {
        "compute_s": flops_g / (chips * pk["flops_bf16"]),
        "memory_s": bytes_g / (chips * pk["hbm_bytes_s"]),
        "collective_s": coll_g / (chips * pk["ici_bytes_s"]),
    }
    dom = max(terms, key=terms.get).replace("_s", "")
    model_flops = rec["model_flops"]
    out = {
        "arch": rec["arch"], "shape": rec["shape"],
        "mesh": rec["mesh"], "chips": chips,
        **{k: float(v) for k, v in terms.items()},
        "bottleneck": dom,
        "model_flops": model_flops,
        "hlo_flops_global": flops_g,
        "useful_flops_ratio": (model_flops / flops_g) if flops_g else 0.0,
        "step_time_lower_bound_s": max(terms.values()),
    }
    out["advice"] = _advice(dom, out)
    return out


def run(dryrun_dir: Path = DRYRUN_DIR, mesh: str = "single",
        verbose: bool = True) -> dict:
    rows: List[dict] = []
    skips: List[dict] = []
    for f in sorted(dryrun_dir.glob(f"*__{mesh}.json")):
        rec = json.loads(f.read_text())
        if rec.get("status") == "skipped":
            skips.append({"arch": rec["arch"], "shape": rec["shape"],
                          "reason": rec["reason"]})
            continue
        row = analyse_record(rec)
        if row:
            rows.append(row)
    out = {"rows": rows, "skipped": skips, "mesh": mesh,
           "device_kind": DRYRUN_DEVICE_KIND,
           "constants": peaks(DRYRUN_DEVICE_KIND)}
    write_json(OUT, out)
    if verbose:
        hdr = (f"{'arch':24s} {'shape':12s} {'compute':>10s} "
               f"{'memory':>10s} {'collect':>10s} {'bound':>8s} "
               f"{'useful':>7s}")
        print(hdr)
        for r in rows:
            print(f"{r['arch']:24s} {r['shape']:12s} "
                  f"{r['compute_s']:10.3e} {r['memory_s']:10.3e} "
                  f"{r['collective_s']:10.3e} {r['bottleneck']:>8s} "
                  f"{r['useful_flops_ratio']:7.2%}")
        for s in skips:
            print(f"{s['arch']:24s} {s['shape']:12s} SKIPPED")
    return out


def main() -> str:
    t = run(verbose=False)
    n = len(t["rows"])
    return csv_line("roofline", 0.0, f"combos={n}")


if __name__ == "__main__":
    run()
