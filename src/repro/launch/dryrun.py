"""Multi-pod dry-run: lower + compile every (architecture x input
shape) on the production meshes and extract roofline inputs.

A CPU-only compile tool: importing this module forces 512 placeholder
*host* devices, so it must never share a process with code that needs
the chip (``chip_smoke.py`` does not import it).

Per combo this emits ``experiments/dryrun/<arch>__<shape>__<mesh>.json``
with: HLO FLOPs + bytes (``compiled.cost_analysis()``), per-device
memory (``compiled.memory_analysis()``), and collective bytes parsed
from the post-SPMD HLO (sum of operand sizes over all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute).

    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun \
        --arch llama3-8b --shape train_4k --mesh single
"""
from repro.xla_flags import force_host_device_count
force_host_device_count(512)
# The two lines above MUST run before any jax-touching import: jax
# locks the device count on first initialisation, and the production
# meshes below need 512 placeholder host devices. The helper *merges*
# into any user-exported XLA_FLAGS (preserving their other flags and
# their own device-count override) instead of clobbering the variable.
# Smoke tests and benches see 1 CPU — nothing else sets this flag.
import argparse
import json
import re
import time
import traceback
from pathlib import Path
from typing import Dict, Optional

import jax

from repro.configs.base import INPUT_SHAPES, InputShape, ModelConfig
from repro.configs.registry import ARCH_IDS, get_config
from repro.launch.mesh import (
    make_production_mesh, mesh_chip_count, rules_for)
from repro.sharding import rule_set
from repro.launch.steps import build_lowering
from repro.sharding import axis_rules

DEFAULT_OUT = Path("experiments/dryrun")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
    "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16,
}
_SHAPE_RE = re.compile(
    r"\b(pred|s8|u8|s16|u16|f16|bf16|s32|u32|f32|s64|u64|f64|c64|c128)"
    r"\[([\d,]*)\]")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "all-to-all", "collective-permute")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


_NAME_RE = re.compile(r"%([\w.\-]+)")


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum operand bytes per collective op kind.

    Post-optimisation HLO prints operands as bare ``%name`` references,
    so first build a name -> output-bytes map from every instruction
    definition, then resolve the operand lists of collective calls.
    NOTE: inside a ``while`` body instructions print once — the dry-run
    extrapolates scan-body collectives via the unrolled correction
    compiles (see run_combo).
    """
    defs: Dict[str, int] = {}
    lines = hlo_text.splitlines()
    for line in lines:
        ls = line.strip()
        if not ls.startswith(("%", "ROOT %")) or " = " not in ls:
            continue
        name_part, rhs = ls.split(" = ", 1)
        m = _NAME_RE.search(name_part)
        if not m:
            continue
        # output type(s): everything before the op-call token "name("
        call = re.search(r"[a-z][\w\-]*\(", rhs)
        type_str = rhs[:call.start()] if call else rhs
        defs[m.group(1)] = sum(_shape_bytes(d, s)
                               for d, s in _SHAPE_RE.findall(type_str))
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in lines:
        if " = " not in line:
            continue
        rhs = line.split(" = ", 1)[1]
        for op in _COLLECTIVES:
            if f" {op}(" not in f" {rhs}" \
                    and f" {op}-start(" not in f" {rhs}":
                continue
            idx = rhs.find(op + "(")
            if idx < 0:
                idx = rhs.find(op + "-start(")
            operands = rhs[rhs.index("(", idx):]
            depth = 0
            for j, ch in enumerate(operands):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        operands = operands[:j + 1]
                        break
            inline = sum(_shape_bytes(d, s)
                         for d, s in _SHAPE_RE.findall(operands))
            if inline == 0:
                inline = sum(defs.get(n, 0)
                             for n in _NAME_RE.findall(operands))
            out[op] += inline
            counts[op] += 1
            break
    out["counts"] = counts
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def model_flops(cfg: ModelConfig, shape: InputShape) -> float:
    """Useful-compute reference: 6*N*D train, 2*N*D forward-only."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch   # one token / request


def should_skip(cfg: ModelConfig, shape: InputShape) -> Optional[str]:
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return ("pure full-attention architecture: 524k decode requires "
                "sub-quadratic attention (DESIGN.md §4)")
    return None


# ----------------------------------------------------------------------
# cost-exact correction compiles
# ----------------------------------------------------------------------
def _layer_period(cfg: ModelConfig) -> int:
    return len(cfg.layer_pattern) if cfg.layer_pattern else 1


def _n_unrolled(cfg: ModelConfig) -> int:
    if cfg.moe is not None and cfg.moe.first_moe_layer > 0:
        return cfg.moe.first_moe_layer
    return 0


def correction_configs(cfg: ModelConfig):
    """Two small fully-unrolled variants whose cost difference is the
    exact per-layer-period cost (XLA counts while bodies once)."""
    import dataclasses as _dc
    period = _layer_period(cfg)
    base = _n_unrolled(cfg)
    k1, k2 = base + period, base + 2 * period

    def shrink(k):
        c = cfg.replace(num_layers=k, scan_layers=False)
        if cfg.encoder is not None:
            c = c.replace(encoder=_dc.replace(cfg.encoder, num_layers=k))
        return c

    return shrink(k1), shrink(k2), k1, k2, period


_COST_KEYS = ("hlo_flops", "hlo_bytes", "hlo_transcendentals")


def extract_costs(compiled) -> dict:
    cost = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    return {
        "hlo_flops": float(cost.get("flops", 0.0)),
        "hlo_bytes": float(cost.get("bytes accessed", 0.0)),
        "hlo_transcendentals": float(cost.get("transcendentals", 0.0)),
        "collective": coll,
    }


def extrapolate_costs(c1: dict, c2: dict, num_layers: int, k1: int,
                      k2: int, period: int) -> dict:
    """corrected = cost(k2) + (L-k2)/period * (cost(k2) - cost(k1))."""
    f = (num_layers - k2) / period
    out = {}
    for k in _COST_KEYS:
        out[k] = c2[k] + f * (c2[k] - c1[k])
    coll = {}
    for k in _COLLECTIVES:
        coll[k] = max(c2["collective"][k]
                      + f * (c2["collective"][k] - c1["collective"][k]),
                      0.0)
    coll["total"] = sum(coll.values())
    out["collective"] = coll
    return out


def _compile(cfg: ModelConfig, shape: InputShape, mesh, rules,
             unrolled: bool = False):
    from repro.models.scan_flags import unrolled_costs
    import contextlib
    ctx = unrolled_costs() if unrolled else contextlib.nullcontext()
    with mesh, axis_rules(mesh, rules), ctx:
        jitted, args = build_lowering(cfg, shape, mesh, rules)
        return jitted.lower(*args).compile()


def run_combo(arch: str, shape_name: str, mesh_kind: str,
              out_dir: Path, save_hlo: bool = False,
              correct: bool = True, rules_name: str = "default") -> dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "rules": rules_name}
    skip = should_skip(cfg, shape)
    if skip:
        rec.update(status="skipped", reason=skip)
        _write(out_dir, rec)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rules = rule_set(rules_name, multi_pod=(mesh_kind == "multi")) \
        if rules_name != "default" else rules_for(mesh)
    t0 = time.perf_counter()
    try:
        # The deliverable compile: full config, scanned layer stacks.
        compiled = _compile(cfg, shape, mesh, rules)
        t_compile = time.perf_counter() - t0
    except Exception as e:  # noqa: BLE001 — record the failure
        rec.update(status="failed", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        _write(out_dir, rec)
        return rec

    try:
        mem = compiled.memory_analysis()
        mem_rec = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", 0),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", 0),
        }
    except Exception as e:  # noqa: BLE001
        mem_rec = {"error": str(e)}

    raw = extract_costs(compiled)
    rec.update(
        status="ok",
        chips=mesh_chip_count(mesh),
        compile_s=round(t_compile, 2),
        raw=raw,                      # scan bodies counted once
        memory=mem_rec,
        model_flops=model_flops(cfg, shape),
        params=cfg.param_count(),
        active_params=cfg.active_param_count(),
        num_layers=cfg.num_layers,
    )

    if correct:
        # Cost-exact extrapolation from two small unrolled compiles
        # (XLA counts scan/while bodies once; see models/scan_flags.py).
        try:
            t1 = time.perf_counter()
            cfg1, cfg2, k1, k2, period = correction_configs(cfg)
            c1 = extract_costs(_compile(cfg1, shape, mesh, rules,
                                        unrolled=True))
            c2 = extract_costs(_compile(cfg2, shape, mesh, rules,
                                        unrolled=True))
            rec["corrected"] = extrapolate_costs(
                c1, c2, cfg.num_layers, k1, k2, period)
            rec["correction"] = {
                "k1": k1, "k2": k2, "period": period,
                "compile_s": round(time.perf_counter() - t1, 2)}
        except Exception as e:  # noqa: BLE001
            rec["corrected"] = None
            rec["correction"] = {"error": f"{type(e).__name__}: {e}"}

    if save_hlo:
        (out_dir / f"{arch}__{shape_name}__{mesh_kind}.hlo.txt"
         ).write_text(compiled.as_text())
    _write(out_dir, rec)
    return rec


def _write(out_dir: Path, rec: dict) -> None:
    out_dir.mkdir(parents=True, exist_ok=True)
    suffix = "" if rec.get("rules", "default") == "default" \
        else f"__{rec['rules']}"
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}{suffix}.json"
    (out_dir / name).write_text(json.dumps(rec, indent=1))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", action="append", choices=ARCH_IDS)
    ap.add_argument("--shape", action="append",
                    choices=tuple(INPUT_SHAPES))
    ap.add_argument("--mesh", default="single",
                    choices=("single", "multi", "both"))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=str(DEFAULT_OUT))
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--no-correct", action="store_true",
                    help="skip the cost-exact correction compiles")
    ap.add_argument("--rules", default="default",
                    choices=("default", "dp", "no-kv-shard", "ep"),
                    help="sharding rule-set (perf iterations)")
    args = ap.parse_args(argv)

    archs = list(ARCH_IDS) if (args.all or not args.arch) else args.arch
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) \
        else args.shape
    meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)
    out_dir = Path(args.out)

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                # cost-exact corrections feed the (single-pod) roofline
                # table; the multi-pod pass proves lowering only.
                rec = run_combo(arch, shape, mesh_kind, out_dir,
                                save_hlo=args.save_hlo,
                                correct=(mesh_kind == "single"
                                         and not args.no_correct),
                                rules_name=args.rules)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    cc = rec.get("corrected") or rec["raw"]
                    extra = (f"flops {cc['hlo_flops']:.3e} "
                             f"coll {cc['collective']['total']:.3e}B "
                             f"compile {rec['compile_s']}s")
                elif status == "failed":
                    extra = rec["error"][:120]
                    n_fail += 1
                print(f"[{status:7s}] {arch:24s} {shape:12s} "
                      f"{mesh_kind:6s} {extra}", flush=True)
    if n_fail:
        raise SystemExit(f"{n_fail} combos failed")


if __name__ == "__main__":
    main()
