"""Prefill + KV-cache decode sampling loop.

``generate`` is a single jitted XLA program per (config, shape): prefill
builds the cache sized for prompt+new tokens, then a ``lax.scan`` drives
``decode_step`` for ``max_new_tokens`` steps. Temperature 0 is greedy;
otherwise tokens come from a temperature-scaled categorical. Finished
rows (EOS emitted) keep emitting ``pad_id`` without disturbing the
cache, so the whole batch runs a fixed-length program.

``generate_samples`` is the shared-prefix N-sample variant the ACAR
probe uses: each prompt is prefilled **once**, the KV cache is
broadcast across the N samples, and only the decode scan runs at the
expanded (B*N) batch — cutting prefill FLOPs by ~N x while emitting
tokens bit-identical to ``generate`` over an ``np.repeat``-expanded
prompt batch (per-row computation is batch-composition invariant for
every non-MoE family; MoE prefill routes with a capacity that couples
rows, see ``batch_invariant``).
"""
from __future__ import annotations

import contextlib
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer as T
from repro.sharding import tp_context, tp_local_cfg, tp_param_specs


class GenerateOutput(NamedTuple):
    tokens: jax.Array        # (B, max_new) int32, pad_id after EOS
    logprobs: jax.Array      # (B, max_new) float32 logprob of chosen tok
    lengths: jax.Array       # (B,) int32 — emitted tokens incl. EOS


def sample_token(logits: jax.Array, temperature: float,
                 key: jax.Array) -> jax.Array:
    """logits: (B, V) -> (B,) int32."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits.astype(jnp.float32) / temperature
    return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)


def sample_token_rows(logits: jax.Array, temperature: float,
                      row_keys: jax.Array,
                      steps: jax.Array) -> jax.Array:
    """Batch-composition-invariant sampling: one private key stream
    per row. logits: (B, V); row_keys: (B, 2) uint32 raw PRNG keys;
    steps: scalar or (B,) int32 decode-step index per row. Row i draws
    from categorical(fold_in(row_keys[i], steps[i]), logits[i]) — a
    pure function of that row alone, so a row emits identical tokens
    whatever batch it shares. ``sample_token`` draws the whole batch's
    Gumbel noise from one key, which couples every row to the batch
    shape — fine for lockstep waves, fatal for step-level batching.
    """
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits.astype(jnp.float32) / temperature
    steps = jnp.broadcast_to(steps, (logits.shape[0],))

    def draw(key, row, t):
        return jax.random.categorical(jax.random.fold_in(key, t), row)

    return jax.vmap(draw)(row_keys, scaled, steps).astype(jnp.int32)


# row-key streams: disjoint tags keep probe and ensemble draws
# independent even for the same admission index
_PROBE_KEY_TAG = 0x5052_4f42      # "PROB"
_MEMBER_KEY_TAG = 0x454d_4245     # "EMBE"


def probe_row_keys(base_key: jax.Array, admission_indices,
                   n_samples: int) -> jax.Array:
    """Per-(task, sample) probe decode keys, (len(indices)*n, 2).

    Row ``i*n + j`` is sample j of the task with admission index
    ``admission_indices[i]`` — a stable identity shared by the wave
    and step-level execution paths, which is what makes their sampled
    tokens bit-identical under different batch compositions."""
    idx = jnp.asarray(list(admission_indices), jnp.uint32)
    tagged = jax.random.fold_in(base_key, _PROBE_KEY_TAG)
    per_task = jax.vmap(jax.random.fold_in, (None, 0))(tagged, idx)
    per_sample = jax.vmap(
        lambda k: jax.vmap(jax.random.fold_in, (None, 0))(
            k, jnp.arange(n_samples, dtype=jnp.uint32)))(per_task)
    return per_sample.reshape(idx.shape[0] * n_samples, -1)


def member_row_keys(base_key: jax.Array, admission_indices,
                    member_idx: int) -> jax.Array:
    """Per-task ensemble decode keys for one member, (len(indices), 2)."""
    idx = jnp.asarray(list(admission_indices), jnp.uint32)
    tagged = jax.random.fold_in(
        jax.random.fold_in(base_key, _MEMBER_KEY_TAG), member_idx)
    return jax.vmap(jax.random.fold_in, (None, 0))(tagged, idx)


def batch_invariant(cfg: ModelConfig) -> bool:
    """True when one row's forward pass cannot depend on which other
    rows share the batch. Dense / SSM / hybrid stacks compute strictly
    per row. MoE capacity dispatch (``impl`` "tp"/"ep") routes with a
    capacity proportional to the *total* token count, so expert
    overflow (token dropping) couples rows; the capacity-free
    ``impl == "gather"`` dispatch (``models.moe.moe_ffn_gather`` /
    ``moe_ffn_token``) computes each token's top-k combine from that
    token alone, so those configs are invariant too — compaction and
    shared-prefix prefill are only bit-equivalent to the padded/tiled
    paths for batch-invariant configs."""
    return cfg.moe is None or cfg.moe.impl == "gather"


def _decode_scan(cfg: ModelConfig, params: dict, cache, logits0,
                 start_pos: int, batch: int, max_new_tokens: int,
                 temperature: float, key: jax.Array, eos_id: int,
                 pad_id: int, decode_fn=None, row_keys=None
                 ) -> Tuple[GenerateOutput, object]:
    """Shared fixed-length decode loop over an existing prefill cache.

    ``decode_fn(cache, token, pos) -> (logits, cache)`` overrides the
    per-step transition — the paged path threads (k_pages, v_pages)
    through it; the default is the dense ``T.decode_step``. With
    ``row_keys`` ((B, 2) uint32), sampling switches to the per-row key
    streams of ``sample_token_rows`` (step i of row r draws from
    fold_in(row_keys[r], i)) — the batch-composition-invariant scheme
    the step-level serving loop replays one step at a time. Returns the
    final cache alongside the output (dense callers drop it; the paged
    path must keep its updated pages)."""
    if decode_fn is None:
        def decode_fn(cache, token, pos):
            return T.decode_step(cfg, params, cache, token, pos)

    def body(carry, step_in):
        cache, logits, pos, done = carry
        if row_keys is None:
            tok = sample_token(logits, temperature, step_in)
        else:
            tok = sample_token_rows(logits, temperature, row_keys,
                                    step_in)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        tok_logp = jnp.take_along_axis(logp, tok[:, None], -1)[:, 0]
        emit = jnp.where(done, pad_id, tok)
        new_done = done | (tok == eos_id)
        next_logits, cache = decode_fn(cache, emit, pos)
        return ((cache, next_logits, pos + 1, new_done),
                (emit, jnp.where(done, 0.0, tok_logp), ~done))

    steps = jnp.arange(max_new_tokens) if row_keys is not None \
        else jax.random.split(key, max_new_tokens)
    init = (cache, logits0, jnp.int32(start_pos),
            jnp.zeros((batch,), bool))
    (cache, _, _, _), (toks, logps, live) = jax.lax.scan(body, init,
                                                         steps)
    toks = toks.T                      # (B, max_new)
    logps = logps.T
    # a row emits a real token (possibly EOS, possibly one that merely
    # *equals* pad_id) at every step it was not yet done — counting
    # pad_id occurrences would undercount legitimately sampled pads
    lengths = live.T.sum(axis=1).astype(jnp.int32)
    return GenerateOutput(tokens=toks, logprobs=logps,
                          lengths=lengths), cache


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "max_new_tokens", "temperature", "eos_id",
                     "pad_id"))
def generate(cfg: ModelConfig, params: dict, prompt_tokens: jax.Array,
             *, max_new_tokens: int, temperature: float = 0.0,
             key: Optional[jax.Array] = None, eos_id: int = -1,
             pad_id: int = 0,
             frontend_embeds: Optional[jax.Array] = None,
             row_keys: Optional[jax.Array] = None
             ) -> GenerateOutput:
    """prompt_tokens: (B, S) int32 — fixed-length prompts.
    ``row_keys`` ((B, 2) uint32) switches sampling to per-row key
    streams (batch-composition invariant; see ``sample_token_rows``)."""
    b, s = prompt_tokens.shape
    if key is None:
        key = jax.random.PRNGKey(0)
    total = s + max_new_tokens
    logits0, cache = T.prefill(cfg, params, prompt_tokens,
                               frontend_embeds, cache_len=total)
    out, _ = _decode_scan(cfg, params, cache, logits0, s, b,
                          max_new_tokens, temperature, key, eos_id,
                          pad_id, row_keys=row_keys)
    return out


def tile_cache(cache, n: int, batch: Optional[int] = None):
    """Broadcast a prefill cache of batch B to B*n rows (row i's
    replicas occupy rows i*n .. i*n+n-1, matching ``np.repeat`` on the
    prompt batch). Stacked layer pytrees (``layers`` / ``dec_layers`` /
    ``cross``) carry (L, B, ...); unrolled per-layer entries
    (``layer_XX``) carry (B, ...). Pass ``batch`` to assert the chosen
    axis really is the batch axis — the key->axis rule mirrors
    ``transformer.init_cache``'s layout and must fail loudly if a new
    cache entry breaks it."""
    out = {}
    for k, v in cache.items():
        axis = 1 if k in ("layers", "dec_layers", "cross") else 0
        if batch is not None:
            for leaf in jax.tree.leaves(v):
                if leaf.shape[axis] != batch:
                    raise ValueError(
                        f"cache entry {k!r}: expected batch {batch} on "
                        f"axis {axis}, got shape {leaf.shape} — "
                        "tile_cache's key->axis rule no longer matches "
                        "the cache layout")
        out[k] = jax.tree.map(
            lambda a, ax=axis: jnp.repeat(a, n, axis=ax), v)
    return out


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "n", "max_new_tokens", "temperature",
                     "eos_id", "pad_id"))
def generate_samples(cfg: ModelConfig, params: dict,
                     prompt_tokens: jax.Array, n: int, *,
                     max_new_tokens: int, temperature: float = 0.0,
                     key: Optional[jax.Array] = None, eos_id: int = -1,
                     pad_id: int = 0,
                     frontend_embeds: Optional[jax.Array] = None,
                     row_keys: Optional[jax.Array] = None
                     ) -> GenerateOutput:
    """N samples per prompt with a single shared-prefix prefill.

    prompt_tokens: (B, S) -> GenerateOutput over B*n rows, row-major in
    sample index (row i*n+j is sample j of prompt i). Bit-identical to
    ``generate(cfg, params, np.repeat(prompt_tokens, n, axis=0), ...)``
    with the same key for ``batch_invariant`` configs, because the
    decode scan sees the same (B*n, V) logits and the same per-step
    keys — only the redundant n-1 prefills per prompt are elided.
    """
    b, s = prompt_tokens.shape
    if key is None:
        key = jax.random.PRNGKey(0)
    total = s + max_new_tokens
    logits0, cache = T.prefill(cfg, params, prompt_tokens,
                               frontend_embeds, cache_len=total)
    cache = tile_cache(cache, n, batch=b)
    logits0 = jnp.repeat(logits0, n, axis=0)
    out, _ = _decode_scan(cfg, params, cache, logits0, s, b * n,
                          max_new_tokens, temperature, key, eos_id,
                          pad_id, row_keys=row_keys)
    return out


# ----------------------------------------------------------------------
# paged KV-cache path (serving/kv_pool.py owns allocation; these are
# the jitted device programs it drives)
# ----------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("cfg", "cache_len"))
def prefill_paged(cfg: ModelConfig, params: dict,
                  prompt_tokens: jax.Array, pages,
                  prefill_table: jax.Array,
                  cache_len: Optional[int] = None):
    """Prompt prefill scattering K/V into pool pages.

    prompt_tokens: (B, S); pages: the pool's page pytree (leaves
    (L, P, KV, page_size, ...) — dense {k, v}, quant adds the f32
    {k_scale, v_scale} planes); prefill_table: (B, NBp) int32;
    cache_len: dense-equivalent total length, required for ring
    layouts. Returns (logits0 (B, V), pages). Logits are bit-identical
    to the dense ``T.prefill`` — only the cache packing differs."""
    return T.prefill_paged(cfg, params, prompt_tokens, pages,
                           prefill_table, cache_len=cache_len)


@functools.partial(jax.jit, static_argnames=("cfg",))
def prefill_lanes(cfg: ModelConfig, params: dict,
                  prompt_tokens: jax.Array, pages,
                  lane_ids: jax.Array):
    """Prompt prefill for a recurrent-state (SSM) member, scattering
    each row's final state into its pool lane.

    prompt_tokens: (B, S); pages: the lane arena pytree (leaves
    (L, LANES, ...) — the per-layer {conv, h} state with a lane axis
    where the kv layouts have a page axis); lane_ids: (B,) int32 lane
    per row. The prefill itself is the dense ``T.prefill`` scan
    bit-for-bit; only the state parking differs. Returns
    (logits0 (B, V), pages)."""
    logits0, cache = T.prefill(cfg, params, prompt_tokens)
    states = cache["layers"]                  # leaves (L, B, ...)
    for arena, st in zip(jax.tree.leaves(pages),
                         jax.tree.leaves(states)):
        # the scatter must be a pure copy: a dtype cast here would
        # drift the parked state off the dense reference path
        assert arena.dtype == st.dtype, (arena.dtype, st.dtype)
    pages = jax.tree.map(
        lambda a, st: a.at[:, lane_ids].set(st), pages, states)
    return logits0, pages


@jax.jit
def fork_pages(pages, src: jax.Array, dst: jax.Array):
    """Page/lane fork: index ``dst[i]`` becomes a private copy of
    ``src[i]`` across every layer and every leaf of the pytree (axis 1
    is the page axis for dense/quant/ring kv leaves and the lane axis
    for recurrent-state leaves — one program serves COW tail
    materialisation, whole-ring forks and lane state copies alike).
    ``src`` may repeat (one canonical prompt page forked to N
    samples); ``dst`` must not."""
    return jax.tree.map(lambda a: a.at[:, dst].set(a[:, src]), pages)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "start_pos", "max_new_tokens",
                     "temperature", "eos_id", "pad_id"))
def decode_paged(cfg: ModelConfig, params: dict, logits0: jax.Array,
                 pages, block_table: jax.Array, key: jax.Array, *,
                 start_pos: int, max_new_tokens: int,
                 temperature: float = 0.0, eos_id: int = -1,
                 pad_id: int = 0,
                 row_keys: Optional[jax.Array] = None):
    """Fixed-length decode over paged state, from prefill logits.

    logits0: (B, V) last-prompt-position logits (freshly computed or
    reused from a retained probe prefill — bit-identical either way);
    pages: the pool's page pytree; block_table: (B, NB) page ids per
    row. The N-sample probe wave passes block tables whose
    prompt-prefix entries point at *shared* read-only pages — that
    sharing, not a tiled cache copy, is what replaced ``tile_cache``
    for the probe. Returns (GenerateOutput, pages); emitted tokens are
    bit-identical to the dense ``generate``/``generate_samples`` over
    the same prompts and key.
    """
    b = logits0.shape[0]
    cache_len = start_pos + max_new_tokens

    def decode_fn(pages, token, pos):
        return T.decode_step_paged(cfg, params, pages, block_table,
                                   token, pos, cache_len=cache_len)

    out, pages = _decode_scan(
        cfg, params, pages, logits0, start_pos, b, max_new_tokens,
        temperature, key, eos_id, pad_id, decode_fn=decode_fn,
        row_keys=row_keys)
    return out, pages


# ----------------------------------------------------------------------
# step-level programs (serving/step_loop.py drives these one logical
# tick at a time: mixed batches, per-row positions and key streams)
# ----------------------------------------------------------------------
@functools.partial(
    jax.jit, static_argnames=("cfg", "prompt_len"))
def prefill_chunk_paged(cfg: ModelConfig, params: dict,
                        tokens: jax.Array, pages,
                        block_table: jax.Array,
                        start_pos: jax.Array, *, prompt_len: int):
    """One prompt chunk appended to the paged cache (dense layout).
    tokens: (B, C) covering absolute positions
    [start_pos[b], start_pos[b] + C) per row — start offsets are
    traced, so mixed-depth rows share one compiled program;
    block_table: (B, NB). Returns (chunk-final logits (B, V), pages);
    bit-identical composition with ``prefill_paged`` — see
    ``models.transformer.prefill_chunk_paged``.
    """
    return T.prefill_chunk_paged(cfg, params, tokens, pages,
                                 block_table, start_pos,
                                 prompt_len=prompt_len)


def _decode_step_rows_impl(cfg: ModelConfig, params: dict,
                           logits: jax.Array, pages,
                           block_table: jax.Array,
                           pos: jax.Array, row_keys: jax.Array,
                           steps: jax.Array, done: jax.Array, *,
                           cache_len: int, temperature: float,
                           eos_id: int, pad_id: int):
    """Unjitted body of ``decode_step_rows`` — shared with the
    shard_map'd variant so both paths run identical math."""
    tok = sample_token_rows(logits, temperature, row_keys, steps)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    tok_logp = jnp.take_along_axis(logp, tok[:, None], -1)[:, 0]
    emit = jnp.where(done, pad_id, tok)
    new_done = done | (tok == eos_id)
    next_logits, pages = T.decode_step_paged(
        cfg, params, pages, block_table, emit, pos,
        cache_len=cache_len)
    return (emit, jnp.where(done, 0.0, tok_logp), ~done, new_done,
            next_logits, pages)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "cache_len", "temperature", "eos_id",
                     "pad_id"))
def decode_step_rows(cfg: ModelConfig, params: dict,
                     logits: jax.Array, pages,
                     block_table: jax.Array,
                     pos: jax.Array, row_keys: jax.Array,
                     steps: jax.Array, done: jax.Array, *,
                     cache_len: int, temperature: float,
                     eos_id: int, pad_id: int):
    """One decode step for a mixed batch of rows.

    logits: (B, V) each row's pending next-token logits; pages: the
    pool's page pytree (any layout — ``T.decode_step_paged``
    dispatches); pos: (B,) per-row write position; steps: (B,)
    per-row decode-step index; done: (B,) rows already past EOS.
    Mirrors one iteration of ``_decode_scan``'s body exactly (same
    sampling, logprob, emit and done arithmetic), so replaying it
    step-by-step over any batch composition emits the same per-row
    tokens the fixed-length scan does. Returns (emit, logprob, live,
    new_done, next_logits, pages)."""
    return _decode_step_rows_impl(
        cfg, params, logits, pages, block_table, pos,
        row_keys, steps, done, cache_len=cache_len,
        temperature=temperature, eos_id=eos_id, pad_id=pad_id)


def _decode_megastep_rows_impl(cfg: ModelConfig, params: dict,
                               logits: jax.Array, pages,
                               block_table: jax.Array, pos: jax.Array,
                               row_keys: jax.Array, steps: jax.Array,
                               done: jax.Array, *, n_ticks: int,
                               cache_len: int, temperature: float,
                               eos_id: int, pad_id: int):
    """Unjitted body of ``decode_megastep_rows`` — ``n_ticks``
    iterations of the ``_decode_step_rows_impl`` tick arithmetic fused
    into one ``lax.scan``, so lane state (logits, positions, step
    indices, done bits) never leaves the device between ticks.

    Each scan iteration draws from the identical per-row key stream
    (``fold_in(row_keys[i], steps[i])``), emits pad for done rows, and
    appends the emitted token's KV at the row's current position.
    Rows that finish (or exhaust their budget) mid-megastep keep
    ticking with masked emissions; their write position is clamped to
    ``cache_len - 1`` so the dead appends land inside the row's own
    tail page — never read again, because the attention mask keys off
    the true position, and the host replay drops masked emissions.
    """
    def body(carry, _):
        lg, pg, pos_, steps_, done_ = carry
        tok = sample_token_rows(lg, temperature, row_keys, steps_)
        emit = jnp.where(done_, pad_id, tok)
        new_done = done_ | (tok == eos_id)
        write_pos = jnp.minimum(pos_, cache_len - 1)
        next_lg, pg = T.decode_step_paged(
            cfg, params, pg, block_table, emit, write_pos,
            cache_len=cache_len)
        return ((next_lg, pg, pos_ + 1, steps_ + 1, new_done),
                (emit, new_done))

    init = (logits, pages, pos, steps, done)
    (lg, pages, _, _, _), (emits, dones) = jax.lax.scan(
        body, init, None, length=n_ticks)
    return emits, dones, lg, pages


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "n_ticks", "cache_len", "temperature",
                     "eos_id", "pad_id"))
def decode_megastep_rows(cfg: ModelConfig, params: dict,
                         logits: jax.Array, pages,
                         block_table: jax.Array,
                         pos: jax.Array, row_keys: jax.Array,
                         steps: jax.Array, done: jax.Array, *,
                         n_ticks: int, cache_len: int,
                         temperature: float, eos_id: int,
                         pad_id: int):
    """``n_ticks`` fused decode ticks for a mixed batch of rows — the
    device-resident megastep. One launch advances every row K ticks;
    the only arrays that cross back to the host are the (K, B) stacks
    of emitted token ids and done bits (the step loop pulls those once
    per megastep and replays them lane by lane). Per-tick sampling,
    emit and done arithmetic is ``_decode_step_rows_impl``'s exactly,
    and the key stream is indexed by the per-row step counter — so
    ``n_ticks`` is a pure performance knob: K=1 *is* the per-tick
    baseline, and any K produces bit-identical token streams.

    Returns (emits (K, B), dones (K, B), next_logits (B, V), pages);
    ``next_logits`` keeps each lane's pending logits on device for the
    next megastep."""
    return _decode_megastep_rows_impl(
        cfg, params, logits, pages, block_table, pos,
        row_keys, steps, done, n_ticks=n_ticks, cache_len=cache_len,
        temperature=temperature, eos_id=eos_id, pad_id=pad_id)


# ----------------------------------------------------------------------
# mesh-sharded step programs (serving/mesh.py drives these: one
# shard_map'd launch advances every shard's bucket simultaneously;
# on a 2-D ("data", "model") mesh each data shard's program runs
# tensor-parallel across its model columns — see sharding/tp.py)
# ----------------------------------------------------------------------
def _mesh_model_size(mesh) -> int:
    return int(mesh.shape["model"]) if "model" in mesh.axis_names else 1


def _row_spec():
    from jax.sharding import PartitionSpec as P
    return P("data")


def _page_specs(pages, m: int):
    """Per-leaf specs for a sharded page pytree. Code/value leaves
    (n_shards, L, P, KV, page, Dh) and scale planes (n_shards, L, P,
    KV, page) both put rows over "data"; under tensor parallelism each
    model column stores only its kv-head slice, so the KV axis shards
    over "model" (per-shard pool bytes divide by m — capacity at a
    fixed byte budget scales x m). Only the "dense" and "quant"
    layouts reach the sharded runners, so every leaf has KV at axis 3."""
    from jax.sharding import PartitionSpec as P
    spec = P("data", None, None, "model") if m > 1 else P("data")
    return jax.tree.map(lambda _: spec, pages)


def _param_spec(params, m: int):
    """Params replicate over "data"; under tensor parallelism the
    column-parallel leaves shard over "model" (sharding.tp)."""
    from jax.sharding import PartitionSpec as P
    return tp_param_specs(params) if m > 1 else P()


def _tp_trace_ctx(m: int):
    """Trace-time tensor-parallel context for shard_map bodies: makes
    every ``tp_all_gather`` gather point live on the "model" axis.
    No-op (and byte-identical traces) at m == 1."""
    return tp_context("model", m) if m > 1 else contextlib.nullcontext()


def _shard_map(body, mesh, in_specs, out_specs):
    from jax.experimental.shard_map import shard_map
    return shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                     out_specs=tuple(out_specs), check_rep=False)


@functools.partial(
    jax.jit, static_argnames=("cfg", "prompt_len", "mesh"))
def prefill_chunk_paged_sharded(cfg: ModelConfig, params: dict,
                                tokens: jax.Array, pages,
                                block_table: jax.Array,
                                start_pos: jax.Array, *,
                                prompt_len: int, mesh):
    """``prefill_chunk_paged`` across every shard of a serving mesh in
    one launch. All array operands carry a leading ``n_shards`` axis
    (tokens: (n_sh, B, C); page leaves: (n_sh, L, P, KV, page, ...);
    tables: (n_sh, B, NBp); start_pos: (n_sh, B)); params replicate
    over "data". Each shard's slice runs the exact single-device chunk
    program, so per-row results are bit-identical to unsharded
    execution — sharding is placement, not math. On a 2-D ("data",
    "model") mesh the program additionally runs tensor-parallel inside
    each data shard: params/pages carry model-column slices and every
    sharded-axis contraction all-gathers first (sharding/tp.py), which
    keeps the reduction order — and therefore the bits — identical."""
    m = _mesh_model_size(mesh)
    lcfg = tp_local_cfg(cfg, m)
    row, pg = _row_spec(), _page_specs(pages, m)

    def body(p, tk, pgs, table, starts):
        with _tp_trace_ctx(m):
            lg, pgs1 = T.prefill_chunk_paged(
                lcfg, p, tk[0],
                jax.tree.map(lambda a: a[0], pgs),
                table[0], starts[0], prompt_len=prompt_len)
        return lg[None], jax.tree.map(lambda a: a[None], pgs1)

    return _shard_map(
        body, mesh,
        (_param_spec(params, m), row, pg, row, row),
        (row, pg))(
        params, tokens, pages, block_table, start_pos)


@functools.partial(jax.jit, static_argnames=("cfg", "mesh"))
def prefill_paged_sharded(cfg: ModelConfig, params: dict,
                          prompt_tokens: jax.Array, pages,
                          prefill_table: jax.Array, *, mesh):
    """``prefill_paged`` across every shard of a serving mesh in one
    launch — the whole-prompt program the step loop uses for layouts
    that cannot compose chunk-by-chunk (quant: a chunk would re-read
    the already-quantised prefix). prompt_tokens: (n_sh, B, S);
    prefill_table: (n_sh, B, NBp); page leaves carry the leading
    ``n_shards`` axis. Only dense/quant layouts reach the sharded
    runners, so no ``cache_len`` (ring-only) is needed. Returns
    (logits0 (n_sh, B, V), pages)."""
    m = _mesh_model_size(mesh)
    lcfg = tp_local_cfg(cfg, m)
    row, pg = _row_spec(), _page_specs(pages, m)

    def body(p, tk, pgs, table):
        with _tp_trace_ctx(m):
            lg, pgs1 = T.prefill_paged(
                lcfg, p, tk[0],
                jax.tree.map(lambda a: a[0], pgs), table[0])
        return lg[None], jax.tree.map(lambda a: a[None], pgs1)

    return _shard_map(
        body, mesh,
        (_param_spec(params, m), row, pg, row),
        (row, pg))(
        params, prompt_tokens, pages, prefill_table)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "cache_len", "temperature", "eos_id",
                     "pad_id", "mesh"))
def decode_step_rows_sharded(cfg: ModelConfig, params: dict,
                             logits: jax.Array, pages,
                             block_table: jax.Array, pos: jax.Array,
                             row_keys: jax.Array, steps: jax.Array,
                             done: jax.Array, *, cache_len: int,
                             temperature: float, eos_id: int,
                             pad_id: int, mesh):
    """``decode_step_rows`` across every shard of a serving mesh in
    one launch (leading ``n_shards`` axis on every array operand;
    params replicate over "data" and, on a 2-D mesh, tensor-shard over
    "model"). Runs ``_decode_step_rows_impl`` — the identical per-row
    math — on each shard's slice, so a row emits the same token
    whatever shard hosts it and whatever the model-axis size."""
    m = _mesh_model_size(mesh)
    lcfg = tp_local_cfg(cfg, m)
    row, pg = _row_spec(), _page_specs(pages, m)

    def body(p, lg, pgs, table, pos_, keys, steps_, done_):
        with _tp_trace_ctx(m):
            *out, pgs1 = _decode_step_rows_impl(
                lcfg, p, lg[0],
                jax.tree.map(lambda a: a[0], pgs),
                table[0], pos_[0], keys[0], steps_[0], done_[0],
                cache_len=cache_len, temperature=temperature,
                eos_id=eos_id, pad_id=pad_id)
        return (tuple(o[None] for o in out)
                + (jax.tree.map(lambda a: a[None], pgs1),))

    return _shard_map(
        body, mesh,
        (_param_spec(params, m), row, pg, row, row, row, row, row),
        (row, row, row, row, row, pg))(
        params, logits, pages, block_table, pos, row_keys,
        steps, done)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "n_ticks", "cache_len", "temperature",
                     "eos_id", "pad_id", "mesh"))
def decode_megastep_rows_sharded(cfg: ModelConfig, params: dict,
                                 logits: jax.Array, pages,
                                 block_table: jax.Array,
                                 pos: jax.Array, row_keys: jax.Array,
                                 steps: jax.Array, done: jax.Array, *,
                                 n_ticks: int, cache_len: int,
                                 temperature: float, eos_id: int,
                                 pad_id: int, mesh):
    """``decode_megastep_rows`` across every shard of a serving mesh
    in one launch (leading ``n_shards`` axis on every array operand;
    params replicate over "data" and, on a 2-D mesh, tensor-shard over
    "model"; emits/dones come back as (n_sh, K, B)). Each shard's
    slice runs the identical fused scan, so a row emits the same
    tokens whatever shard hosts it, whatever K the planner picked and
    whatever the model-axis size — the decode tick path stays free of
    host-side collectives; the model-axis all-gathers live inside the
    device program."""
    m = _mesh_model_size(mesh)
    lcfg = tp_local_cfg(cfg, m)
    row, pg = _row_spec(), _page_specs(pages, m)

    def body(p, lg, pgs, table, pos_, keys, steps_, done_):
        with _tp_trace_ctx(m):
            *out, pgs1 = _decode_megastep_rows_impl(
                lcfg, p, lg[0],
                jax.tree.map(lambda a: a[0], pgs),
                table[0], pos_[0], keys[0], steps_[0], done_[0],
                n_ticks=n_ticks, cache_len=cache_len,
                temperature=temperature, eos_id=eos_id, pad_id=pad_id)
        return (tuple(o[None] for o in out)
                + (jax.tree.map(lambda a: a[None], pgs1),))

    return _shard_map(
        body, mesh,
        (_param_spec(params, m), row, pg, row, row, row, row, row),
        (row, row, row, pg))(
        params, logits, pages, block_table, pos, row_keys,
        steps, done)


@functools.partial(jax.jit, static_argnames=("mesh",))
def fork_pages_sharded(pages, src: jax.Array, dst: jax.Array, *, mesh):
    """Per-shard ``fork_pages`` in one launch. src/dst: (n_sh, K)
    shard-local page ids; shards with nothing to fork pass
    ``src == dst`` self-copies (the identity write), so one shard's
    COW fork never stalls on the others. On a 2-D mesh each model
    column copies its own kv-head slice of the pages — page ids are
    column-invariant, so the fork stays a pure local copy."""
    m = _mesh_model_size(mesh)
    row, pg = _row_spec(), _page_specs(pages, m)

    def body(pgs, s, d):
        pgs1 = fork_pages(jax.tree.map(lambda a: a[0], pgs),
                          s[0], d[0])
        return (jax.tree.map(lambda a: a[None], pgs1),)

    return _shard_map(body, mesh, (pg, row, row), (pg,))(
        pages, src, dst)[0]


def decode_text(tokens, detok) -> list:
    """Apply a detokenizer callable row-wise (host-side helper)."""
    import numpy as np
    toks = np.asarray(tokens)
    return [detok(row) for row in toks]
