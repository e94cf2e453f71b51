"""ACAR serving driver — end-to-end over real JAX models.

Trains (or loads) a probe + ensemble of reduced zoo models on the
arithmetic corpus, then serves a task batch through the batched ACAR
engine: (B x N) probe decode -> EXTRACT -> on-device sigma/routing ->
masked ensemble decodes -> vectorised judge. Prints accuracy, routing
distribution, and ensemble calls saved.

    PYTHONPATH=src python -m repro.launch.serve --tasks 32 \
        --train-steps 300

Fleet members are registry arch names, optionally with a page-layout
variant suffix: ``arch:quant`` serves from int8-quantised KV pages,
``arch:swaN`` serves with an N-token sliding window (ring pages in
the stepped engine). Variants share the base arch's training — the
cache layout only changes how the member serves. ``--hetero-fleet``
is the paper's headline mix in one flag (Mamba probe, quant + SWA
members, a full-attention arena member):

    PYTHONPATH=src python -m repro.launch.serve --hetero-fleet \
        --step-loop --tasks 32
"""
from __future__ import annotations

import argparse
import collections
import contextlib
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.checkpoint import restore_checkpoint
from repro.configs.acar import ACARConfig
from repro.configs.registry import ARCH_IDS
from repro.core.extract import extract
from repro.core.sigma import MODE_NAMES
from repro.data.tasks import Task, arithmetic_suite
from repro.launch.train import reduced_for_data, train
from repro.models import params as params_lib
from repro.serving import BatchedACAREngine, ZooModel

DEFAULT_PROBE = "smollm-135m"
DEFAULT_ENSEMBLE = ("llama3-8b", "deepseek-7b", "recurrentgemma-2b")
# the paper's headline heterogeneous mix: a cheap recurrent probe, a
# quant-KV member and a sliding-window member beside a full-attention
# arena member — all four page layouts in one stepped fleet
HETERO_PROBE = "falcon-mamba-7b"
HETERO_ENSEMBLE = ("smollm-135m:quant", "smollm-135m:swa16",
                   "llama3-8b")


def parse_member(spec: str):
    """``arch[:quant|:swaN]`` -> (base arch, cfg variant applier).

    The variant changes the member's serving cache layout only (int8
    KV pages / ring pages); training always runs on the base arch."""
    arch, _, var = spec.partition(":")
    if arch not in ARCH_IDS:
        raise SystemExit(
            f"unknown arch {arch!r} (choose from {sorted(ARCH_IDS)})")
    if not var:
        return arch, lambda cfg: cfg
    if var == "quant":
        return arch, lambda cfg: cfg.replace(kv_quant=True)
    if var.startswith("swa"):
        window = int(var[3:] or 16)
        return arch, lambda cfg: cfg.replace(window=window)
    raise SystemExit(
        f"unknown member variant {spec!r} "
        "(use arch, arch:quant, or arch:swaN)")


def build_zoo(archs: Sequence[str], train_steps: int, seed: int = 0,
              ckpts: Optional[Dict[str, str]] = None,
              verbose: bool = True) -> List[ZooModel]:
    """Train (or restore) reduced arithmetic models for each member
    spec (``arch`` or ``arch:variant``)."""
    zoo = []
    for i, spec in enumerate(archs):
        arch, variant = parse_member(spec)
        cfg = variant(reduced_for_data(arch, "arithmetic"))
        if ckpts and spec in ckpts:
            template = params_lib.init_params(
                cfg, jax.random.PRNGKey(seed + i))
            prm = restore_checkpoint(ckpts[spec], template)
        else:
            if verbose:
                print(f"-- training {spec} ({train_steps} steps)")
            _, prm, _ = train(arch=arch, data="arithmetic",
                              steps=train_steps, batch=64, seq=24,
                              lr=2e-3, seed=seed + i, verbose=False)
        zoo.append(ZooModel(name=spec, cfg=cfg, params=prm))
    return zoo


def request_walls(step) -> dict:
    """Per-request wall times of a step-loop run, on its host spans'
    clock (``StepStats.wall``, ``StepStats.host_spans``): admission to
    route (median) and to retirement (median and 90th percentile), in
    ms, and the mean number of decode launches that served a request
    (the ``acar.decode.launch`` spans whose rows hold it). A row that
    never reached a stamp (an aborted one) counts in none of the times."""
    walls = list(step.wall.values())
    rode = collections.Counter(
        i for sp in step.host_spans if sp.name == "acar.decode.launch"
        for i in set(sp.rows))
    done = [t - a for a, _, t in walls]
    return {
        "time_to_route_p50_ms":
            float(np.nanmedian([r - a for a, r, _ in walls])) * 1e3,
        "request_p50_ms": float(np.nanmedian(done)) * 1e3,
        "request_p90_ms": float(np.nanpercentile(done, 90)) * 1e3,
        "decode_launches_per_request": sum(rode.values()) / len(walls),
    }


def serve(tasks: Sequence[Task], probe: ZooModel,
          ensemble: Sequence[ZooModel], acfg: ACARConfig,
          verbose: bool = True,
          scheduler: bool = False,
          step_loop: bool = False,
          batch_size: int = 8,
          data_shards: Optional[int] = None,
          megastep: int = 1,
          trace_path: Optional[str] = None,
          lineage_task: Optional[str] = None) -> dict:
    """Serve tasks through the batched engine. With ``scheduler=True``
    the request stream flows through the admission queue and is served
    as micro-batches of at most ``batch_size`` (continuous-batching
    path); with ``step_loop=True`` it runs the step-level loop
    (streaming admission + chunked prefill + mixed-phase decode
    steps — requires a paged-capable probe); ``data_shards`` runs that
    loop on a sharded serving mesh (per-shard paged KV pools, needs
    that many visible devices); ``megastep`` fuses up to that many
    decode ticks into one device launch (bit-identical outputs, fewer
    host round-trips); otherwise the whole suite runs as one batch.

    ``trace_path`` arms the deterministic span tracer and flushes the
    hash-chained span JSONL there; ``lineage_task`` (implies tracing)
    walks the PROV graph backwards from that task's final answer and
    prints the verified lineage."""
    tracer = None
    if trace_path is not None or lineage_task is not None:
        from repro.serving.tracing import SpanTracer
        tracer = SpanTracer(trace_path)
    engine = BatchedACAREngine(acfg, probe, ensemble)
    if verbose:
        from repro.models.transformer import resolve_layout
        layouts = {m.name: (resolve_layout(m.cfg) or "dense*")
                   for m in [probe] + list(ensemble)}
        print("fleet layouts     : " + ", ".join(
            f"{n}={l}" for n, l in layouts.items()))
    if step_loop or data_shards is not None or megastep > 1:
        from repro.serving.queue import MicroBatchPolicy
        res = engine.run_stepped(
            list(tasks), MicroBatchPolicy(max_batch_size=batch_size),
            data_shards=data_shards, megastep=megastep,
            tracer=tracer)
        scheduler = True          # report the queued-shape extras
    elif scheduler or tracer is not None:
        from repro.serving.queue import MicroBatchPolicy
        res = engine.run_queued(
            list(tasks), MicroBatchPolicy(max_batch_size=batch_size),
            tracer=tracer)
        scheduler = True
    else:
        res = engine.run_batch(list(tasks))
    correct = sum(
        1 for t, a in zip(tasks, res.final_answers)
        if extract(a, t.kind) == t.gold or a == t.gold)
    dist = collections.Counter(
        MODE_NAMES[m] for m in res.modes)
    out = {
        "accuracy": correct / len(tasks),
        "mode_distribution": dict(dist),
        "ensemble_calls_saved": res.ensemble_calls_saved,
        "wall_ms": res.wall_ms,
        "sigma_mean": float(res.sigma.mean()),
    }
    cs = res.compaction
    if cs is not None:
        out["ensemble_decode_tokens"] = cs.ensemble_decode_tokens
        out["ensemble_decode_tokens_saved"] = \
            cs.ensemble_decode_tokens_saved
        out["ensemble_decode_token_reduction"] = \
            cs.ensemble_decode_token_reduction
        out["probe_prefill_reduction"] = cs.probe_prefill_reduction
    if scheduler:
        out["batch_sizes"] = res.batch_sizes
    step = getattr(res, "step", None)
    if step is not None and step.wall:
        out.update(request_walls(step))
    if verbose:
        print(f"served {len(tasks)} tasks in {res.wall_ms:.0f} ms")
        print(f"accuracy          : {out['accuracy']:.3f}")
        print(f"mode distribution : {out['mode_distribution']}")
        print(f"calls saved       : {out['ensemble_calls_saved']} "
              f"of {3 * len(tasks)}")
        if cs is not None:
            print(f"compaction        : "
                  f"{cs.ensemble_decode_tokens} ensemble decode tokens "
                  f"({cs.ensemble_decode_tokens_saved} saved, "
                  f"{out['ensemble_decode_token_reduction']:.2f}x), "
                  f"probe prefill "
                  f"{out['probe_prefill_reduction']:.2f}x fewer tokens")
        if scheduler:
            print(f"micro-batches     : {res.batch_sizes}")
            if getattr(res, "step", None) is not None:
                print(f"step loop         : {res.step.ticks} ticks, "
                      f"{res.step.invocations} program launches, "
                      f"{res.step.prefill_chunks} prefill chunks")
            if "request_p50_ms" in out:
                print(f"requests          : routed in "
                      f"{out['time_to_route_p50_ms']:.1f} ms, retired "
                      f"in {out['request_p50_ms']:.1f} ms (p50; p90 "
                      f"{out['request_p90_ms']:.1f} ms) after "
                      f"admission, "
                      f"{out['decode_launches_per_request']:.1f} "
                      f"decode launches each")
            print(res.metrics.render())
    if tracer is not None and getattr(res, "spans", None) is not None:
        out["spans"] = len(res.spans)
        out["span_head"] = res.span_head
        if verbose:
            print(f"spans             : {len(res.spans)} "
                  f"(head {res.span_head[:16]}...)"
                  + (f" -> {trace_path}" if trace_path else ""))
        if lineage_task is not None:
            from repro.teamllm.prov import lineage
            lin = lineage(res.spans, lineage_task)
            out["lineage_ok"] = lin["ok"]
            out["lineage_verified"] = lin["verified"]
            if verbose:
                print(f"lineage           : task {lineage_task} "
                      f"trace {lin['trace']} — "
                      f"{lin['verified']} span hashes verified, "
                      f"{'OK' if lin['ok'] else 'FAILED'}")
                for rec in lin["records"]:
                    if rec["kind"] == "entity":
                        print(f"  entity   {rec['id']}")
                    elif rec["kind"] == "wasDerivedFrom":
                        via = f" via {rec['via']}" if "via" in rec \
                            else ""
                        print(f"  derived  {rec['entity']} <- "
                              f"{rec['source']}{via}")
                    elif rec["kind"] == "wasGeneratedBy":
                        print(f"  genBy    {rec['entity']} <- "
                              f"{rec['activity']}")
                for f in lin["hash_failures"]:
                    print(f"  FAIL     {f}")
    return out


def main(argv=None):
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tasks", type=int, default=32)
    ap.add_argument("--train-steps", type=int, default=300)
    ap.add_argument("--probe", default=DEFAULT_PROBE,
                    help="probe member spec: a registry arch name, "
                         "optionally with a page-layout variant "
                         "suffix (arch, arch:quant, arch:swaN)")
    ap.add_argument("--ensemble", nargs="+",
                    default=list(DEFAULT_ENSEMBLE),
                    help="ensemble member specs (same syntax as "
                         "--probe)")
    ap.add_argument("--hetero-fleet", action="store_true",
                    help="serve the paper's heterogeneous mix "
                         f"(probe {HETERO_PROBE}, ensemble "
                         f"{', '.join(HETERO_ENSEMBLE)}) — overrides "
                         "--probe/--ensemble")
    ap.add_argument("--probe-temperature", type=float, default=0.7)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scheduler", action="store_true",
                    help="serve via the admission queue as "
                         "micro-batches (continuous batching)")
    ap.add_argument("--step-loop", action="store_true",
                    help="serve via the step-level loop (streaming "
                         "admission, chunked prefill, mixed-phase "
                         "decode steps; needs a paged-capable probe)")
    ap.add_argument("--shards", type=int, default=None,
                    help="run the step loop on a data-sharded serving "
                         "mesh with this many shards (implies "
                         "--step-loop; needs that many devices — on "
                         "CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count)")
    ap.add_argument("--megastep", type=int, default=1,
                    help="fuse up to K decode ticks per device launch "
                         "in the step loop (implies --step-loop; "
                         "bit-identical outputs at any K)")
    ap.add_argument("--batch-size", type=int, default=8,
                    help="micro-batch size budget for --scheduler")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="arm the deterministic span tracer and flush "
                         "the hash-chained span JSONL here (span "
                         "structure is bit-identical run to run; "
                         "wall-times ride the non-hashed side channel)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="record a jax.profiler trace of the serve "
                         "into DIR: the step loop's acar.* host spans "
                         "beside the device's programs (TensorBoard "
                         "or Perfetto reads it)")
    ap.add_argument("--lineage", default=None, metavar="TASK_ID",
                    help="after serving, walk the PROV lineage of this "
                         "task's final answer (answer -> judge -> "
                         "members -> route -> probe samples, plus KV "
                         "page-reuse derivations) and verify every "
                         "span hash on the walk (implies tracing)")
    args = ap.parse_args(argv)

    if args.hetero_fleet:
        args.probe = HETERO_PROBE
        args.ensemble = list(HETERO_ENSEMBLE)
    zoo = build_zoo([args.probe] + list(args.ensemble),
                    args.train_steps, seed=args.seed)
    probe, ensemble = zoo[0], zoo[1:]
    acfg = ACARConfig(probe_model=args.probe,
                      ensemble_models=tuple(args.ensemble),
                      probe_temperature=args.probe_temperature,
                      seed=args.seed)
    tasks = arithmetic_suite(args.tasks, seed=args.seed + 99)
    with (jax.profiler.trace(args.profile) if args.profile
          else contextlib.nullcontext()):
        serve(tasks, probe, ensemble, acfg,
              scheduler=args.scheduler, step_loop=args.step_loop,
              batch_size=args.batch_size, data_shards=args.shards,
              megastep=args.megastep, trace_path=args.trace,
              lineage_task=args.lineage)


if __name__ == "__main__":
    main()
