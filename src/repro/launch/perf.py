"""§Perf hillclimb runner: named (arch, shape, rules, config-transform)
variants, lowered on the single-pod production mesh, recorded to
experiments/perf/<variant>.json with the same cost extraction as the
dry-run. EXPERIMENTS.md §Perf documents each hypothesis -> change ->
before -> after cycle.

A CPU-only compile tool, like ``launch/dryrun.py``: importing it
forces 512 placeholder host devices, so it never shares a process
with code that needs the chip.

    PYTHONPATH=src python -m repro.launch.perf --variant A2 [--all]
"""
from repro.xla_flags import force_host_device_count
force_host_device_count(512)
# Must run before any jax-touching import — see launch/dryrun.py
# (merges into user-set XLA_FLAGS instead of clobbering them).
import argparse
import dataclasses
import json
import time
from pathlib import Path

from repro.configs.base import INPUT_SHAPES
from repro.configs.registry import get_config
from repro.launch.dryrun import (
    _compile, correction_configs, extract_costs, extrapolate_costs,
    model_flops)
from repro.launch.mesh import make_production_mesh, mesh_chip_count
from repro.sharding import rule_set

OUT = Path("experiments/perf")


def _moe_cap(cfg, cap):
    return cfg.replace(moe=dataclasses.replace(cfg.moe,
                                               capacity_factor=cap))


# variant -> (arch, shape, rules-name, config transform or None)
VARIANTS = {
    # A: smollm-135m x train_4k — worst useful-FLOPs fraction
    "A0": ("smollm-135m", "train_4k", "default", None),
    "A1": ("smollm-135m", "train_4k", "dp", None),
    "A2": ("smollm-135m", "train_4k", "dp",
           lambda c: c.replace(remat="none")),
    # B: deepseek-v2-236b x prefill_32k — most collective-bound
    "B0": ("deepseek-v2-236b", "prefill_32k", "default", None),
    "B1": ("deepseek-v2-236b", "prefill_32k", "ep", None),
    "B2": ("deepseek-v2-236b", "prefill_32k", "ep",
           lambda c: _moe_cap(c, 1.0)),
    # C: llama3-8b x decode_32k — the ACAR serving step
    "C0": ("llama3-8b", "decode_32k", "default", None),
    "C1": ("llama3-8b", "decode_32k", "no-kv-shard", None),
    # C2: int8 KV cache (symmetric per-vector quant; halves cache
    # storage + decode read traffic; scales fold into attention math)
    "C2": ("llama3-8b", "decode_32k", "default",
           lambda c: c.replace(kv_quant=True)),
    # C3: int8 KV + batch also over the model axis (decode is pure
    # request parallelism for the cache; 128 % 256 != 0 so the batch
    # stays on "data" — kept for the record, falls back to C2 behavior)
    "C2_long": ("granite-34b", "decode_32k", "default",
                lambda c: c.replace(kv_quant=True)),
    # C2 applied to the HBM-overflow case found in SDry-run
    "C2_ds7b": ("deepseek-7b", "decode_32k", "default",
                lambda c: c.replace(kv_quant=True)),
    "C2_mixtral": ("mixtral-8x22b", "decode_32k", "default",
                   lambda c: c.replace(kv_quant=True)),
}


def run_variant(name: str) -> dict:
    arch, shape_name, rules_name, transform = VARIANTS[name]
    cfg = get_config(arch)
    if transform:
        cfg = transform(cfg)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh()
    rules = rule_set(rules_name)
    t0 = time.perf_counter()
    compiled = _compile(cfg, shape, mesh, rules)
    try:
        mem = compiled.memory_analysis()
        mem_rec = {"argument_bytes": mem.argument_size_in_bytes,
                   "temp_bytes": mem.temp_size_in_bytes,
                   "output_bytes": mem.output_size_in_bytes}
    except Exception as e:  # noqa: BLE001
        mem_rec = {"error": str(e)}
    cfg1, cfg2, k1, k2, period = correction_configs(cfg)
    c1 = extract_costs(_compile(cfg1, shape, mesh, rules, unrolled=True))
    c2 = extract_costs(_compile(cfg2, shape, mesh, rules, unrolled=True))
    rec = {
        "variant": name, "arch": arch, "shape": shape_name,
        "rules": rules_name, "status": "ok", "mesh": "single",
        "chips": mesh_chip_count(mesh),
        "compile_s": round(time.perf_counter() - t0, 2),
        "raw": extract_costs(compiled),
        "corrected": extrapolate_costs(c1, c2, cfg.num_layers, k1, k2,
                                       period),
        "memory": mem_rec,
        "model_flops": model_flops(cfg, shape),
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / f"{name}.json").write_text(json.dumps(rec, indent=1))
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--variant", action="append",
                    choices=tuple(VARIANTS))
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args(argv)
    names = tuple(VARIANTS) if args.all else (args.variant or ())
    import sys
    sys.path.insert(0, ".")
    from benchmarks.roofline import analyse_record
    for name in names:
        rec = run_variant(name)
        r = analyse_record(rec)
        print(f"[{name}] compute {r['compute_s']:.3e} "
              f"memory {r['memory_s']:.3e} "
              f"collective {r['collective_s']:.3e} "
              f"bound={r['bottleneck']} "
              f"useful={r['useful_flops_ratio']:.2%}", flush=True)


if __name__ == "__main__":
    main()
