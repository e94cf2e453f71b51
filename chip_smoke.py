"""Smoke run of the ACAR serving path on a TPU.

    python chip_smoke.py [--seed S]          # one chip
    python chip_smoke.py --chips 4 [--seed S]

One process; nothing it starts touches JAX. With no option it needs
one chip and runs five phases in order, each printing what it found:

  (a) device check: platform, device kind and count; anything but a
      TPU exits non-zero before any work;
  (b) kernels: each main-path Pallas kernel (paged decode, chunked
      prefill, int8 paged decode, fused SwiGLU) once on the chip at
      smollm-135m widths with ``interpret=False``, against its
      ``kernels/ref.py`` oracle;
  (c) correctness: teacher-forced greedy decode of a few prompts
      through the paged chunked-prefill and decode programs the step
      loop runs, against dense ``sampler.generate`` at float32 and
      ``highest`` matmul precision;
  (d) serving: a probe and three ensemble members, all smollm-135m at
      its published width in bf16 with random weights from ``--seed``,
      serve 16 arithmetic tasks through ``BatchedACAREngine.run_stepped``
      twice; both runs must answer every task, verify their hash
      chains, and agree on every record hash and the chain head;
  (e) serving with kernels: the same tasks once more with
      ``use_pallas=True`` on every member.

``--chips 4`` runs only the sharded step loop, at ``data_shards=4``
(the smollm-135m fleet) and at (data=2, model=2) (see ``mesh_legs``),
each against the one-device run of the same tasks in this process,
and checks that parameters and pages land on every device.

A failed phase raises, so the script exits non-zero; the last line of
standard output is the JSON result only when every phase passed.
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
N_TASKS = 16
BATCH = 8
N_PROMPTS = 8          # phase (c)
TEACHER_STEPS = 4      # phase (c) decode steps


class PhaseFailed(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def describe(cfg) -> str:
    return (f"model: {cfg.name} layers={cfg.num_layers} "
            f"d_model={cfg.d_model} heads={cfg.num_heads}/"
            f"{cfg.num_kv_heads} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
            f"dtype={cfg.dtype}")


# ----------------------------------------------------------------------
# (a) device check
# ----------------------------------------------------------------------
def device_check(chips: int) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    say("a", f"platform={dev['platform']} device_kind={dev['kind']} "
             f"count={dev['count']}")
    check(dev["platform"] == "tpu",
          f"no TPU: JAX found platform {dev['platform']!r}")
    check(dev["count"] >= chips,
          f"--chips {chips} needs {chips} devices, found {dev['count']}")
    return dev


# ----------------------------------------------------------------------
# (b) kernels against their oracles
# ----------------------------------------------------------------------
# Tolerances, relative to the oracle's largest |output|. The oracle
# runs in float32 at ``highest`` precision on the kernel's own bf16
# inputs, so what is left is the kernel's bf16 rounding:
# * attention: the f32 result is rounded to bf16 once (half an ulp,
#   2^-9 relative) and Mosaic may feed f32 operands (softmax weights)
#   to the MXU as bf16, another 2^-9 per term; 2^-7 is twice the sum.
# * SwiGLU: additionally the hidden activation is rounded to bf16
#   before the down projection, and that projection sums 1536 such
#   terms; 2^-5 leaves room for a few ulps of that at output scale.
ATTN_TOL = 2.0 ** -7
SWIGLU_TOL = 2.0 ** -5


def _kernel_cases(cfg, key, *, rows=8, pages=64, nb=4, page=8,
                  chunk=8):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.attention import quantize_kv
    h, kv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    bf = jnp.bfloat16
    ks = jax.random.split(key, 10)
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.permutation(pages)[:rows * nb]
                        .reshape(rows, nb), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, nb * page + 1, size=rows),
                          jnp.int32)
    prompt_len = nb * page - 3
    starts = rng.integers(0, prompt_len - chunk + 1, size=rows)
    qpos = jnp.asarray(starts[:, None] + np.arange(chunk), jnp.int32)
    q = jax.random.normal(ks[0], (rows, h, dh), bf)
    qc = jax.random.normal(ks[1], (rows, chunk, h, dh), bf)
    kp = jax.random.normal(ks[2], (pages, kv, page, dh), bf)
    vp = jax.random.normal(ks[3], (pages, kv, page, dh), bf)
    kq, kqs = quantize_kv(jax.random.normal(ks[4], kp.shape))
    vq, vqs = quantize_kv(jax.random.normal(ks[5], kp.shape))
    d, f = cfg.d_model, cfg.d_ff
    x = jax.random.normal(ks[6], (rows * chunk, d), bf)
    wg = (jax.random.normal(ks[7], (d, f)) / d ** 0.5).astype(bf)
    wu = (jax.random.normal(ks[8], (d, f)) / d ** 0.5).astype(bf)
    wd = (jax.random.normal(ks[9], (f, d)) / f ** 0.5).astype(bf)
    return dict(q=q, qc=qc, kp=kp, vp=vp, kq=kq, kqs=kqs, vq=vq,
                vqs=vqs, table=table, lengths=lengths, qpos=qpos,
                prompt_len=prompt_len, x=x, wg=wg, wu=wu, wd=wd)


def phase_kernels(cfg, *, interpret: bool = False, seed: int = 0
                  ) -> dict:
    """Each main-path kernel once at ``cfg``'s widths against its
    oracle; returns {kernel: (max abs error, tolerance)}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.chunked_prefill_attention import (
        chunked_prefill_attention)
    from repro.kernels.fused_swiglu import fused_swiglu
    from repro.kernels.paged_decode_attention import (
        paged_decode_attention)
    from repro.kernels.paged_decode_attention_quant import (
        paged_decode_attention_quant)

    c = _kernel_cases(cfg, jax.random.PRNGKey(seed))
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    runs = {
        "paged_decode": (
            lambda: paged_decode_attention(
                c["q"], c["kp"], c["vp"], c["table"], c["lengths"],
                interpret=interpret),
            lambda: ref.paged_decode_attention_ref(
                f32(c["q"]), f32(c["kp"]), f32(c["vp"]), c["table"],
                c["lengths"]),
            ATTN_TOL),
        "chunked_prefill": (
            lambda: chunked_prefill_attention(
                c["qc"], c["kp"], c["vp"], c["table"], c["qpos"],
                prompt_len=c["prompt_len"], interpret=interpret),
            lambda: ref.chunked_prefill_attention_ref(
                f32(c["qc"]), f32(c["kp"]), f32(c["vp"]), c["table"],
                c["qpos"], prompt_len=c["prompt_len"]),
            ATTN_TOL),
        "paged_decode_quant": (
            lambda: paged_decode_attention_quant(
                c["q"], c["kq"], c["kqs"], c["vq"], c["vqs"],
                c["table"], c["lengths"], interpret=interpret),
            lambda: ref.paged_decode_attention_quant_ref(
                f32(c["q"]), c["kq"], c["kqs"], c["vq"], c["vqs"],
                c["table"], c["lengths"]),
            ATTN_TOL),
        "fused_swiglu": (
            lambda: fused_swiglu(c["x"], c["wg"], c["wu"], c["wd"],
                                 interpret=interpret),
            lambda: ref.fused_swiglu_ref(
                f32(c["x"]), f32(c["wg"]), f32(c["wu"]), f32(c["wd"])),
            SWIGLU_TOL),
    }
    out = {}
    for name, (kernel, oracle, rel_tol) in runs.items():
        got = np.asarray(jax.block_until_ready(kernel()), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(oracle(), np.float32)
        check(got.shape == want.shape and np.isfinite(got).all(),
              f"{name}: shape {got.shape} vs {want.shape} or non-finite")
        err = float(np.max(np.abs(got - want)))
        tol = rel_tol * float(np.max(np.abs(want)))
        say("b", f"{name}: max_abs_err={err!r} tol={tol!r} "
                 f"({rel_tol!r} x max|oracle|)")
        check(err <= tol, f"{name}: error {err!r} above {tol!r}")
        out[name] = (err, tol)
    return out


# ----------------------------------------------------------------------
# (c) paged serving programs against dense float32 generate
# ----------------------------------------------------------------------
# Logits tolerance: the RMS error of each (prompt, step) row of logits,
# as a share of the reference logits' standard deviation. A bf16
# forward rounds the residual stream (2^-9 relative) at every one of
# the model's sublayers; those errors add like a random walk, about
# sqrt(2 L) x 2^-9 ~ 1.5% of the hidden state's scale at 30 layers,
# and reach the logits at that share of their spread. 5% leaves a
# factor of three. An RMS and not a max: the largest of 49,152 such
# errors sits near five standard deviations of them, so a max-based
# bound would tighten as the vocabulary grows. A float32 member needs
# far less, but is held to the same bound.
LOGIT_TOL = 0.05


def phase_correctness(cfg, params, tasks, *, page_size: int = 8,
                      chunk: int = 8) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data import tokenizer as tok
    from repro.models import transformer as T
    from repro.sampling import sampler as S
    from repro.serving.kv_pool import PagedKVServer

    ids = tok.encode_aligned([t.text for t in tasks])
    b, s = ids.shape
    k = TEACHER_STEPS

    # reference: dense generate at float32, highest precision, on the
    # same weights upcast
    cfg32 = cfg.replace(dtype="float32", use_pallas=False)
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32)
                            if jnp.issubdtype(a.dtype, jnp.floating)
                            else a, params)
    with jax.default_matmul_precision("highest"):
        ref_out = S.generate(cfg32, params32, jnp.asarray(ids),
                             max_new_tokens=k, temperature=0.0)
        ref_toks = np.asarray(ref_out.tokens)
        seq = np.concatenate([ids, ref_toks[:, :k - 1]], axis=1)
        ref_logits, _ = jax.jit(T.forward, static_argnums=0)(
            cfg32, params32, jnp.asarray(seq))
        ref_logits = np.asarray(ref_logits[:, s - 1:], np.float32)

    # the step loop's programs: chunked prefill into pool pages, then
    # one decode step per position, fed the reference's tokens
    srv = PagedKVServer(cfg, page_size=page_size, prefix_cache_entries=0)
    srv.ensure_capacity_stream(b, s, 1, k)
    g = srv.row_geometry(s, k)
    table = jnp.asarray(np.stack([srv.pool.alloc(g.nb)
                                  for _ in range(b)]))
    pages = srv.pages
    start = 0
    while start < s:
        c = min(chunk, s - start)
        lg, pages = S.prefill_chunk_paged(
            cfg, params, jnp.asarray(ids[:, start:start + c]), pages,
            table, jnp.full((b,), start, jnp.int32), prompt_len=s)
        start += c
    step = jax.jit(T.decode_step_paged,
                   static_argnames=("cfg", "cache_len"))
    got = [np.asarray(lg, np.float32)]
    for i in range(k - 1):
        lg, pages = step(cfg, params, pages, table,
                         jnp.asarray(ref_toks[:, i]),
                         jnp.full((b,), s + i, jnp.int32),
                         cache_len=s + k)
        got.append(np.asarray(lg, np.float32))
    got = np.stack(got, axis=1)                        # (B, K, V)

    check(np.isfinite(got).all(), "paged logits not finite")
    spread = float(ref_logits.std())
    diff = np.abs(got - ref_logits)                    # (B, K, V)
    rms = float(np.sqrt((diff ** 2).mean(-1)).max()) / spread
    err = float(diff.max())
    tol = LOGIT_TOL
    say("c", f"logits: worst row rms_err/ref_std={rms!r} tol={tol!r}; "
             f"max_abs_err={err!r} (ref std {spread!r}) over {b} "
             f"prompts x {k} steps")
    check(rms <= tol, f"logits error {rms!r} of the ref std above {tol!r}")

    # first greedy token: a swap needs the reference's top-2 margin to
    # be within twice that row's largest logit error, so rows with a
    # wider margin must pick the reference's token
    first_ref = ref_toks[:, 0]
    first_got = got[:, 0].argmax(-1)
    top2 = np.sort(ref_logits[:, 0], axis=-1)[:, -2:]
    margin = top2[:, 1] - top2[:, 0]
    decided = margin > 2 * diff[:, 0].max(-1)
    match = first_got == first_ref
    say("c", f"first greedy token: {int(match.sum())}/{b} match the "
             f"reference; {int(decided.sum())} rows have a top-2 "
             f"margin above twice their largest logit error, all of "
             f"which must match")
    check(bool(match[decided].all()),
          f"first greedy token differs where the margin decides it: "
          f"rows {np.nonzero(decided & ~match)[0].tolist()}")
    check(bool((first_ref == ref_logits[:, 0].argmax(-1)).all()),
          "generate's greedy token is not its own argmax")
    return {"err": rms, "tol": tol, "max_abs_err": err,
            "match": int(match.sum()),
            "decided": int(decided.sum()), "rows": b}


# ----------------------------------------------------------------------
# (d)/(e) serving through run_stepped
# ----------------------------------------------------------------------
def build_fleet(cfg, seed: int):
    """Probe (seed) and three ensemble members (seed+1..3), random
    weights from ``params.init_params``."""
    import jax

    from repro.models import params as params_lib
    from repro.serving import ZooModel
    zoo = [ZooModel(name="probe" if i == 0 else f"member{i}", cfg=cfg,
                    params=params_lib.init_params(
                        cfg, jax.random.PRNGKey(seed + i)))
           for i in range(4)]
    return zoo[0], zoo[1:]


def forced_route(n_tasks: int, seed: int):
    """Random weights decode to no text the tokenizer can read, so the
    three probe samples always agree on the empty answer and sigma is
    0 everywhere: no member would ever run. The smoke therefore forces
    the paper's routing rates by admission index (the serving
    benchmarks' ``route_fn``) so that every member decodes."""
    from benchmarks.serving_bench import forced_modes, index_route_fn
    return index_route_fn(forced_modes(n_tasks, seed))


def serve(probe, ensemble, tasks, seed: int, **run_kw):
    """One ``run_stepped`` run on a fresh engine; returns the result,
    its trace records and its audit (chain head)."""
    from repro.configs.acar import ACARConfig
    from repro.serving import (
        BatchedACAREngine, MicroBatchPolicy, trace_records)
    from repro.teamllm.artifacts import ArtifactStore

    engine = BatchedACAREngine(ACARConfig(seed=seed), probe, ensemble,
                               route_fn=forced_route(len(tasks), seed))
    t0 = time.perf_counter()
    res = engine.run_stepped(list(tasks),
                             MicroBatchPolicy(max_batch_size=BATCH),
                             megastep="auto", **run_kw)
    wall = time.perf_counter() - t0
    records = trace_records("chip_smoke", tasks, res,
                            [m.name for m in ensemble])
    with tempfile.TemporaryDirectory() as d:
        store = ArtifactStore(Path(d) / "runs.jsonl")
        for r in records:
            store.append(r)
        audit = store.audit()
    return res, [r.record_hash() for r in records], audit, wall


def check_served(phase: str, res, audit, n_tasks: int) -> None:
    from repro.core.sigma import MODE_NAMES
    check(len(res.final_answers) == n_tasks
          and all(isinstance(a, str) for a in res.final_answers),
          f"{len(res.final_answers)} answers for {n_tasks} tasks")
    check(bool(audit["ok"]) and audit["records"] == n_tasks,
          f"hash chain does not verify: {audit}")
    modes = collections.Counter(MODE_NAMES[int(m)] for m in res.modes)
    # random weights rarely sample one of the tokenizer's few readable
    # ids, so most texts are empty and the records compare the route
    # and the answers rather than the emitted tokens (phase (c) checks
    # those)
    texts = [t for row in res.probe_texts for t in row]
    st = res.step
    say(phase, f"modes={dict(sorted(modes.items()))} ticks={st.ticks} "
               f"launches={st.launches} "
               f"prefill_chunks={st.prefill_chunks} "
               f"non-empty probe texts={sum(map(bool, texts))}/"
               f"{len(texts)} chain_head={audit['head'][:16]}")


def phase_serving(probe, ensemble, seed: int, tasks) -> dict:
    """(d): the fleet serves the tasks twice, cold then warm."""
    runs = []
    for label in ("cold", "warm"):
        res, hashes, audit, wall = serve(probe, ensemble, tasks, seed)
        check_served("d", res, audit, len(tasks))
        say("d", f"{label} run: host wall time {wall!r} s "
                 f"({len(tasks)} tasks)")
        runs.append((res, hashes, audit, wall))
    (r1, h1, a1, w1), (r2, h2, a2, w2) = runs
    same = sum(x == y for x, y in zip(h1, h2))
    say("d", f"record hashes identical {same}/{len(tasks)}; chain "
             f"heads {'equal' if a1['head'] == a2['head'] else 'DIFFER'}")
    check(same == len(tasks) and a1["head"] == a2["head"],
          "the two runs of the same tasks differ")
    return {"result": r1, "hashes": h1, "head": a1["head"],
            "wall_cold": w1, "wall_warm": w2, "fleet": (probe, ensemble)}


def phase_pallas_serving(served: dict, seed: int, tasks) -> dict:
    """(e): the same fleet with ``use_pallas=True`` on every member."""
    from repro.serving import ZooModel
    probe, ensemble = served["fleet"]
    pallas = lambda zm: ZooModel(  # noqa: E731
        name=zm.name, cfg=zm.cfg.replace(use_pallas=True),
        params=zm.params)
    res, hashes, audit, wall = serve(pallas(probe),
                                     [pallas(m) for m in ensemble],
                                     tasks, seed)
    check_served("e", res, audit, len(tasks))
    base = served["result"]
    modes = sum(int(a) == int(b) for a, b in zip(res.modes, base.modes))
    answers = sum(a == b for a, b in zip(res.final_answers,
                                         base.final_answers))
    same = sum(x == y for x, y in zip(hashes, served["hashes"]))
    say("e", f"host wall time {wall!r} s; against (d): modes agree "
             f"{modes}/{len(tasks)}, final answers {answers}/"
             f"{len(tasks)}, record hashes {same}/{len(tasks)}")
    return {"modes": modes, "answers": answers, "hashes": same}


# ----------------------------------------------------------------------
# --chips 4: the sharded step loop against one device
# ----------------------------------------------------------------------
def _placement(tree) -> set:
    import jax
    return {d for leaf in jax.tree.leaves(tree)
            for d in leaf.sharding.device_set}


def mesh_legs():
    """(config, data, model) of each sharded run. smollm-135m's 9 query
    and 3 KV heads do not split over two model columns (placement
    refuses it: ``sharding.tp_check_cfg``), so the (data=2, model=2)
    run serves llama3-8b, whose 32/8 heads do, at its published width
    cut to one layer: four such members (2.5 GB each in bf16) then fit
    one chip's 16 GB for the one-device run they are compared with."""
    from repro.configs.registry import get_config
    return ((get_config("smollm-135m"), 4, 1),
            (get_config("llama3-8b").replace(num_layers=1), 2, 2))


def _prefill_logits_one(cfg, params, ids, data: int):
    """The probe's one-shot paged prefill on one device, shard by
    shard: the program shapes each mesh shard runs, so a difference
    from the mesh is the mesh's and not the batch size's."""
    import jax.numpy as jnp
    import numpy as np

    from repro.sampling import sampler as S
    from repro.serving import PagedKVServer
    per, s = ids.shape[0] // data, ids.shape[1]
    srv = PagedKVServer(cfg, prefix_cache_entries=0)
    srv.ensure_capacity_stream(per, s, 1, 8)
    nbp = srv.row_geometry(s, 8).nbp
    table = jnp.asarray(np.stack([srv.pool.alloc(nbp)
                                  for _ in range(per)]))
    return np.stack([np.asarray(S.prefill_paged(
        cfg, params, jnp.asarray(ids[k * per:(k + 1) * per]),
        srv.pages, table)[0], np.float32) for k in range(data)])


def _prefill_logits_mesh(cfg, smesh, placed, ids):
    """The same prefill in one launch on the mesh, with the params as
    the sharded runner places them; checks that params and pages land
    on every mesh device."""
    import jax.numpy as jnp
    import numpy as np

    from repro.sampling import sampler as S
    from repro.serving import ShardedPagedKVServer
    data = smesh.n_shards
    b, s = ids.shape
    per = b // data
    srv = ShardedPagedKVServer(cfg, smesh, prefix_cache_entries=0)
    srv.ensure_capacity_stream(per, s, 1, 8)
    mesh_devs = set(smesh.mesh.devices.flat)
    for what, tree in (("params", placed), ("pages", srv.pages)):
        on = _placement(tree)
        check(on == mesh_devs,
              f"{what} on {len(on)} of {len(mesh_devs)} mesh devices")
    nbp = srv.shards[0].row_geometry(s, 8).nbp
    tables = np.stack([np.stack([sv.pool.alloc(nbp) for _ in range(per)])
                       for sv in srv.shards])
    lg, _ = S.prefill_paged_sharded(
        cfg, placed, smesh.shard_rows(jnp.asarray(ids.reshape(
            data, per, s))), srv.pages,
        smesh.shard_rows(jnp.asarray(tables)), mesh=smesh.mesh)
    return np.asarray(lg, np.float32)


def phase_mesh(seed: int, tasks, legs) -> dict:
    """Each (config, data, model) leg: the fleet serves the tasks on
    one device, then through the sharded step loop on the mesh; reports
    which of modes, answers, record hashes and chain head differ, and
    how far the mesh moves the probe's prefill logits."""
    import numpy as np

    from repro.data import tokenizer as tok
    from repro.serving import ServingMesh, ZooModel
    out = {}
    for cfg, data, model in legs:
        say("mesh", describe(cfg))
        probe, ensemble = build_fleet(cfg, seed)
        fleet = [probe, *ensemble]
        del probe, ensemble
        res1, h1, a1, _ = serve(fleet[0], fleet[1:], tasks, seed)
        check_served("mesh", res1, a1, len(tasks))
        ids = tok.encode_aligned([t.text for t in tasks[:data * 2]])
        lg_one = _prefill_logits_one(cfg, fleet[0].params, ids, data)
        smesh = ServingMesh(data=data, model=model)
        # move the weights to the mesh member by member, so that one
        # chip never holds the one-device fleet and its mesh copy whole;
        # the runner's own placement then finds them in place
        for i, zm in enumerate(fleet):
            fleet[i] = ZooModel(name=zm.name, cfg=cfg,
                                params=smesh.place_params(cfg, zm.params))
        del zm
        res, h, a, wall = serve(fleet[0], fleet[1:], tasks, seed,
                                data_shards=data, model_shards=model)
        check_served("mesh", res, a, len(tasks))
        lg_mesh = _prefill_logits_mesh(cfg, smesh, fleet[0].params, ids)
        diffs = {
            "modes": sum(int(x) != int(y)
                         for x, y in zip(res.modes, res1.modes)),
            "final_answers": sum(x != y for x, y in zip(
                res.final_answers, res1.final_answers)),
            "record_hashes": sum(x != y for x, y in zip(h, h1)),
            "chain_head": int(a["head"] != a1["head"]),
        }
        logit_diff = float(np.max(np.abs(lg_mesh - lg_one)))
        say("mesh", f"(data={data}, model={model}) on {data * model} "
                    f"devices, params and pages on every one; host wall "
                    f"time {wall!r} s; differences from one device: "
                    f"{diffs}; probe prefill logits "
                    f"max_abs_diff={logit_diff!r}")
        out[(data, model)] = {**diffs, "logit_diff": logit_diff}
        del fleet, res1, res
    return out


# ----------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro beside {Path(__file__).name}; "
              "run it from a checkout of the repository",
              file=sys.stderr)
        return 2
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)

    try:
        dev = device_check(args.chips)
    except PhaseFailed as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    # before the first compile, after the device check: a run that
    # finds no chip leaves no cache behind
    from repro.compile_cache import (
        compile_cache_counts, enable_compile_cache)
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    from repro.configs.registry import get_config
    from repro.data.tasks import arithmetic_suite
    tasks = arithmetic_suite(N_TASKS, seed=args.seed)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(args.seed, tasks, mesh_legs())
    else:
        cfg = get_config("smollm-135m")
        print(describe(cfg), flush=True)
        phase_kernels(cfg, seed=args.seed)
        probe, ensemble = build_fleet(cfg, args.seed)
        phase_correctness(cfg, probe.params, tasks[:N_PROMPTS])
        served = phase_serving(probe, ensemble, args.seed, tasks)
        phase_pallas_serving(served, args.seed, tasks)
    print(f"compile cache: {compile_cache_counts()}", flush=True)
    print(f"all phases passed in {time.perf_counter() - t0!r} s "
          "(host wall time)", flush=True)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
