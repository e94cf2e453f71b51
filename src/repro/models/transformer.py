"""Composable transformer: full-sequence forward (train), prefill, and
single-token decode for every architecture family in the zoo.

Layer stacks are scanned (``lax.scan`` over stacked params) whenever the
stack is uniform — dense, MoE, SSM, and whisper's two stacks — keeping
HLO size and compile time bounded for 88-layer models on 512 devices.
The non-uniform hybrid (recurrentgemma) stack is unrolled (26 small
layers). Decode carries a cache pytree whose per-layer entries are
stacked along the scan axis so the same scan drives decoding.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    embed_tokens, gelu_mlp, layer_norm, rms_norm, swiglu_mlp)
from repro.sharding import shard, tp_all_gather

Cache = Dict[str, Any]


# ----------------------------------------------------------------------
# small helpers
# ----------------------------------------------------------------------
def norm_apply(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    if "bias" in p:
        return layer_norm(x, p["scale"], p["bias"], cfg.norm_eps)
    return rms_norm(x, p["scale"], cfg.norm_eps)


def mlp_apply(cfg: ModelConfig, p: dict, x: jax.Array,
              moe_shards: int) -> Tuple[jax.Array, jax.Array]:
    """Returns (y, aux). aux is 0 for dense MLPs."""
    if "router" in p:
        if cfg.moe is not None and cfg.moe.impl == "gather":
            # capacity-free per-token expert math: batch-composition
            # invariant, so MoE members qualify for compacted /
            # shared-prefix execution (sampling.batch_invariant)
            return moe_mod.moe_ffn_gather(cfg, p, x)
        return moe_mod.moe_ffn(cfg, p, x, moe_shards)
    if "w_in" in p:
        return gelu_mlp(p, x), jnp.zeros((), jnp.float32)
    if cfg.use_pallas:
        # TPU deployment: fused-SwiGLU Pallas kernel (kernels/ops.py
        # dispatches to the jnp oracle off-TPU, so CPU tests/examples
        # stay exact).
        from repro.kernels import ops
        t = x.reshape(-1, x.shape[-1])
        y = ops.fused_swiglu(t, p["w_gate"], p["w_up"], p["w_down"])
        return y.reshape(x.shape), jnp.zeros((), jnp.float32)
    return swiglu_mlp(p, x), jnp.zeros((), jnp.float32)


def mlp_apply_token(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    if "router" in p:
        if cfg.moe is not None and cfg.moe.impl == "gather":
            # decode runs the same capacity-free gather math as
            # prefill: one code path, one bit-contract (fixed-shape
            # token blocks make it batch-composition invariant and
            # column-split exact under the 2-D mesh)
            y, _ = moe_mod.moe_ffn_gather(cfg, p, x[:, None])
            return y[:, 0]
        return moe_mod.moe_ffn_token(cfg, p, x)
    if "w_in" in p:
        return gelu_mlp(p, x)
    return swiglu_mlp(p, x)


def _attn_cache_len(cfg: ModelConfig, seq_len: int) -> int:
    if cfg.window is not None:
        return min(seq_len, cfg.window)
    if cfg.use_pallas and seq_len > 0:
        # the flash-decode kernel tiles the cache in BLOCK_S chunks;
        # allocating on the block grid here means its off-grid fallback
        # (pad-and-copy per call) never triggers on the deployment
        # path — positions past the true length are masked like any
        # other invalid slot. Caches shorter than one block stay exact
        # (the kernel runs them as a single s-sized block).
        from repro.kernels.decode_attention import DEFAULT_BLOCK_S
        if seq_len > DEFAULT_BLOCK_S:
            return -(-seq_len // DEFAULT_BLOCK_S) * DEFAULT_BLOCK_S
    return seq_len


def ring_compress(k: jax.Array, cache_len: int) -> jax.Array:
    """Compress prefill keys (B,S,KV,D) to a ring cache
    (B,cache_len,...). Slot = absolute position mod cache_len; when the
    prompt is shorter than the ring, the tail slots are zero-padded
    (decode's slot arithmetic needs the full ring length, else the ring
    wraps early and evicts live positions)."""
    s = k.shape[1]
    if s < cache_len:
        pad = [(0, 0)] * k.ndim
        pad[1] = (0, cache_len - s)
        return jnp.pad(k, pad)
    if s == cache_len:
        return k
    pos = jnp.arange(s - cache_len, s)
    slots = jnp.mod(pos, cache_len)
    out = jnp.zeros((k.shape[0], cache_len) + k.shape[2:], k.dtype)
    return out.at[:, slots].set(k[:, -cache_len:])


# ----------------------------------------------------------------------
# layer forward (training / full sequence)
# ----------------------------------------------------------------------
def layer_fwd(cfg: ModelConfig, lp: dict, x: jax.Array,
              positions: jax.Array, kind: str, moe_shards: int,
              enc_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
              causal: bool = True) -> Tuple[jax.Array, jax.Array]:
    """One decoder/encoder layer over a full sequence. Returns (x, aux)."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "ssm":
        h = norm_apply(cfg, lp["norm"], x)
        x = x + ssm_mod.mamba_block(cfg, lp["ssm"], h)
        return x, aux
    if kind == "rglru":
        h = norm_apply(cfg, lp["mix_norm"], x)
        x = x + rglru_mod.rglru_block(cfg, lp["rglru"], h)
    else:  # attn
        h = norm_apply(cfg, lp["attn_norm"], x)
        if cfg.attn_kind == "mla":
            a = attn.mla_attention(cfg, lp["attn"], h, positions)
        else:
            a = attn.gqa_attention(cfg, lp["attn"], h, positions,
                                   causal=causal, window=cfg.window)
        x = x + a
        if enc_kv is not None:
            h = norm_apply(cfg, lp["cross_norm"], x)
            x = x + attn.cross_attention(cfg, lp["cross"], h, *enc_kv)
    h = norm_apply(cfg, lp["mlp_norm"], x)
    y, aux = mlp_apply(cfg, lp["mlp"], h, moe_shards)
    x = x + y
    x = shard(x, "batch", "seq", "embed")
    return x, aux


# ----------------------------------------------------------------------
# embedding / head
# ----------------------------------------------------------------------
def _embed_inputs(cfg: ModelConfig, params: dict, tokens: jax.Array,
                  frontend_embeds: Optional[jax.Array]) -> jax.Array:
    x = embed_tokens(params["embedding"], tokens)
    if cfg.frontend == "vision" and frontend_embeds is not None:
        # image patches occupy the first num_patches positions
        p = frontend_embeds.shape[1]
        x = jnp.concatenate(
            [frontend_embeds.astype(x.dtype), x[:, p:]], axis=1)
        x = shard(x, "batch", "seq", "embed")
    return x


def _logits(cfg: ModelConfig, params: dict, x: jax.Array) -> jax.Array:
    x = norm_apply(cfg, params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", x, params["embedding"])
    else:
        logits = jnp.einsum("...d,dv->...v", x, params["lm_head"])
        # tensor parallelism: untied lm_head is vocab-column-sharded;
        # gather logits to the full vocab (tied logits contract the
        # replicated embedding and are already full)
        logits = tp_all_gather(logits)
    if logits.ndim == 3:
        logits = shard(logits, "batch", "seq", "vocab")
    return logits


def _dec_pos(cfg: ModelConfig, params: dict,
             positions: jax.Array) -> jax.Array:
    """Learned decoder positions, indexed cyclically: the real whisper
    table has 448 slots; decode shapes past that are a sharding/shape
    exercise (DESIGN.md §4) and wrap modulo the table length."""
    table = params["dec_pos"]
    return jnp.take(table, jnp.mod(positions, table.shape[0]), axis=0)


def _sinusoidal_pos(length: int, d: int) -> jax.Array:
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None]
    ang = pos / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ----------------------------------------------------------------------
# whisper encoder
# ----------------------------------------------------------------------
def _encode(cfg: ModelConfig, params: dict,
            frames: jax.Array) -> jax.Array:
    """frames: (B, F, d) stub frontend output -> encoder states."""
    e = cfg.encoder
    assert e is not None
    x = frames + _sinusoidal_pos(frames.shape[1],
                                 cfg.d_model).astype(frames.dtype)[None]
    positions = jnp.arange(frames.shape[1])

    def body(x, lp):
        x, _ = layer_fwd(cfg, lp, x, positions, "attn", 1, causal=False)
        return x, None

    x, _ = stack_scan(cfg, body, x, params["enc_layers"],
                      e.num_layers)
    return norm_apply(cfg, params["enc_final_norm"], x)


# ----------------------------------------------------------------------
# full-sequence forward (training / scoring)
# ----------------------------------------------------------------------
def forward(cfg: ModelConfig, params: dict, tokens: jax.Array,
            frontend_embeds: Optional[jax.Array] = None,
            remat: bool = False, moe_shards: int = 1
            ) -> Tuple[jax.Array, jax.Array]:
    """tokens: (B, S) -> (logits (B, S, V), moe_aux scalar)."""
    b, s = tokens.shape
    positions = jnp.arange(s)
    aux0 = jnp.zeros((), jnp.float32)

    if cfg.family == "audio":
        enc_out = _encode(cfg, params, frontend_embeds)
        x = embed_tokens(params["embedding"], tokens)
        x = x + _dec_pos(cfg, params, positions).astype(x.dtype)[None]

        def dec_body(carry, lp):
            x, aux = carry
            kv = attn.cross_kv(cfg, lp["cross"], enc_out)
            x, a = layer_fwd(cfg, lp, x, positions, "attn", moe_shards,
                             enc_kv=kv)
            return (x, aux + a), None

        if remat:
            dec_body = jax.checkpoint(dec_body)
        (x, aux), _ = stack_scan(cfg, dec_body, (x, aux0),
                                 params["dec_layers"], cfg.num_layers)
        return _logits(cfg, params, x), aux

    x = _embed_inputs(cfg, params, tokens, frontend_embeds)

    if cfg.family == "hybrid":
        aux = aux0
        for i, kind in enumerate(cfg.layer_kinds):
            lp = params[f"layer_{i:02d}"]
            fn = functools.partial(layer_fwd, cfg, lp,
                                   positions=positions, kind=kind,
                                   moe_shards=moe_shards)
            if remat:
                fn = jax.checkpoint(fn)
            x, a = fn(x)
            aux = aux + a
        return _logits(cfg, params, x), aux

    aux = aux0
    kinds = cfg.layer_kinds
    # leading dense layers (deepseek-v2 keeps layer 0 dense)
    n_unrolled = cfg.moe.first_moe_layer if (
        cfg.moe is not None and cfg.moe.first_moe_layer > 0) else 0
    for i in range(n_unrolled):
        x, a = layer_fwd(cfg, params[f"layer_{i:02d}"], x, positions,
                         "attn", moe_shards)
        aux = aux + a

    def body(carry, lp):
        x, aux = carry
        x, a = layer_fwd(cfg, lp, x, positions, kinds[-1], moe_shards)
        return (x, aux + a), None

    if remat:
        body = jax.checkpoint(body)
    (x, aux), _ = stack_scan(cfg, body, (x, aux), params["layers"],
                             cfg.num_layers - n_unrolled)
    return _logits(cfg, params, x), aux


# ----------------------------------------------------------------------
# cache construction
# ----------------------------------------------------------------------
def _attn_cache_sds(cfg: ModelConfig, batch: int, cache_len: int) -> dict:
    hd = cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    if cfg.attn_kind == "mla":
        m = cfg.mla
        return {
            "c_kv": jnp.zeros((batch, cache_len, m.kv_lora_rank), dt),
            "k_rope": jnp.zeros((batch, cache_len, m.qk_rope_head_dim),
                                dt),
        }
    kv = cfg.num_kv_heads
    if cfg.kv_quant:
        return {
            "k": jnp.zeros((batch, cache_len, kv, hd), jnp.int8),
            "v": jnp.zeros((batch, cache_len, kv, hd), jnp.int8),
            "k_scale": jnp.zeros((batch, cache_len, kv), jnp.float32),
            "v_scale": jnp.zeros((batch, cache_len, kv), jnp.float32),
        }
    return {
        "k": jnp.zeros((batch, cache_len, kv, hd), dt),
        "v": jnp.zeros((batch, cache_len, kv, hd), dt),
    }


def _ssm_cache(cfg: ModelConfig, batch: int) -> dict:
    d_in, _, n = ssm_mod.ssm_dims(cfg)
    w = cfg.ssm.conv_width
    return {
        "conv": jnp.zeros((batch, w - 1, d_in), jnp.dtype(cfg.dtype)),
        "h": jnp.zeros((batch, d_in, n), jnp.float32),
    }


def _rglru_cache(cfg: ModelConfig, batch: int) -> dict:
    w = cfg.rglru.lru_width or cfg.d_model
    cw = cfg.rglru.conv_width
    return {
        "conv": jnp.zeros((batch, cw - 1, w), jnp.dtype(cfg.dtype)),
        "h": jnp.zeros((batch, w), jnp.float32),
    }


def _stack(tree_list):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *tree_list)


def stack_scan(cfg: ModelConfig, body, init, xs, length: int):
    """``lax.scan`` over stacked layer pytrees, or an unrolled python
    loop when ``cfg.scan_layers`` is False (dry-run cost-exact compiles
    — XLA cost analysis counts a while body once)."""
    if cfg.scan_layers:
        return jax.lax.scan(body, init, xs)
    carry = init
    ys = []
    for i in range(length):
        xi = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    if all(y is None for y in ys):
        return carry, None
    return carry, jax.tree.map(lambda *zs: jnp.stack(zs), *ys)


def init_cache(cfg: ModelConfig, batch: int, seq_len: int) -> Cache:
    """Zero-initialised decode cache for ``seq_len`` total positions."""
    cache_len = _attn_cache_len(cfg, seq_len)
    if cfg.family == "audio":
        e = cfg.encoder
        hd = cfg.resolved_head_dim
        dt = jnp.dtype(cfg.dtype)
        self_c = _stack([_attn_cache_sds(cfg, batch, cache_len)
                         for _ in range(cfg.num_layers)])
        cross = {
            "k": jnp.zeros((cfg.num_layers, batch, e.num_frames,
                            cfg.num_kv_heads, hd), dt),
            "v": jnp.zeros((cfg.num_layers, batch, e.num_frames,
                            cfg.num_kv_heads, hd), dt),
        }
        return {"dec_layers": self_c, "cross": cross}
    if cfg.family == "ssm":
        return {"layers": _stack([_ssm_cache(cfg, batch)
                                  for _ in range(cfg.num_layers)])}
    if cfg.family == "hybrid":
        out: Cache = {}
        for i, kind in enumerate(cfg.layer_kinds):
            if kind == "attn":
                out[f"layer_{i:02d}"] = _attn_cache_sds(
                    cfg, batch, cache_len)
            else:
                out[f"layer_{i:02d}"] = _rglru_cache(cfg, batch)
        return out
    out = {}
    n_unrolled = cfg.moe.first_moe_layer if (
        cfg.moe is not None and cfg.moe.first_moe_layer > 0) else 0
    for i in range(n_unrolled):
        out[f"layer_{i:02d}"] = _attn_cache_sds(cfg, batch, cache_len)
    out["layers"] = _stack(
        [_attn_cache_sds(cfg, batch, cache_len)
         for _ in range(cfg.num_layers - n_unrolled)])
    return out


# ----------------------------------------------------------------------
# prefill
# ----------------------------------------------------------------------
def _attn_prefill_layer(cfg: ModelConfig, lp: dict, x, positions,
                        cache_len: int, moe_shards: int,
                        enc_kv=None):
    """Full-seq layer that also emits its decode cache entry."""
    aux = jnp.zeros((), jnp.float32)
    h = norm_apply(cfg, lp["attn_norm"], x)
    if cfg.attn_kind == "mla":
        c_kv, k_rope = attn.mla_project_kv_latent(cfg, lp["attn"], h)
        k_rope_r = attn.apply_rope(
            k_rope[:, :, None], positions[None], cfg.rope_theta)[:, :, 0]
        a = attn.mla_attention(cfg, lp["attn"], h, positions)
        entry = {"c_kv": _pad_cache(c_kv, cache_len),
                 "k_rope": _pad_cache(k_rope_r, cache_len)}
    else:
        q, k, v = attn.gqa_project_qkv(cfg, lp["attn"], h)
        if cfg.use_rope:
            q = attn.apply_rope(q, positions[None], cfg.rope_theta)
            k = attn.apply_rope(k, positions[None], cfg.rope_theta)
        o = attn.flash_attention(q, k, v, positions, positions,
                                 causal=True, window=cfg.window)
        b, s = x.shape[:2]
        o = o.reshape(b, s, cfg.num_heads * cfg.resolved_head_dim)
        a = jnp.einsum("bsh,hd->bsd", o, lp["attn"]["wo"])
        if cfg.kv_quant:
            kq, ks = attn.quantize_kv(k)
            vq, vs = attn.quantize_kv(v)
            pack = ring_compress if cfg.window is not None \
                else _pad_cache
            entry = {"k": pack(kq, cache_len),
                     "v": pack(vq, cache_len),
                     "k_scale": pack(ks, cache_len),
                     "v_scale": pack(vs, cache_len)}
        elif cfg.window is not None:
            entry = {"k": ring_compress(k, cache_len),
                     "v": ring_compress(v, cache_len)}
        else:
            entry = {"k": _pad_cache(k, cache_len),
                     "v": _pad_cache(v, cache_len)}
    x = x + a
    if enc_kv is not None:
        h = norm_apply(cfg, lp["cross_norm"], x)
        x = x + attn.cross_attention(cfg, lp["cross"], h, *enc_kv)
    h = norm_apply(cfg, lp["mlp_norm"], x)
    y, aux = mlp_apply(cfg, lp["mlp"], h, moe_shards)
    x = x + y
    return x, entry, aux


def _pad_cache(k: jax.Array, cache_len: int) -> jax.Array:
    s = k.shape[1]
    if s == cache_len:
        return k
    assert s < cache_len
    pad = [(0, 0)] * k.ndim
    pad[1] = (0, cache_len - s)
    return jnp.pad(k, pad)


def prefill(cfg: ModelConfig, params: dict, tokens: jax.Array,
            frontend_embeds: Optional[jax.Array] = None,
            cache_len: Optional[int] = None, moe_shards: int = 1
            ) -> Tuple[jax.Array, Cache]:
    """Process a prompt, returning (last-position logits, decode cache)."""
    b, s = tokens.shape
    if cache_len is None:
        cache_len = s
    a_len = _attn_cache_len(cfg, cache_len)
    positions = jnp.arange(s)

    if cfg.family == "audio":
        enc_out = _encode(cfg, params, frontend_embeds)
        x = embed_tokens(params["embedding"], tokens)
        x = x + _dec_pos(cfg, params, positions).astype(x.dtype)[None]

        def body(x, lp):
            kv = attn.cross_kv(cfg, lp["cross"], enc_out)
            x, entry, _ = _attn_prefill_layer(cfg, lp, x, positions,
                                              a_len, moe_shards,
                                              enc_kv=kv)
            return x, (entry, {"k": kv[0], "v": kv[1]})

        x, (self_c, cross_c) = stack_scan(cfg, body, x,
                                          params["dec_layers"],
                                          cfg.num_layers)
        logits = _logits(cfg, params, x[:, -1])
        return logits, {"dec_layers": self_c, "cross": cross_c}

    x = _embed_inputs(cfg, params, tokens, frontend_embeds)

    if cfg.family == "ssm":
        def body(x, lp):
            h = norm_apply(cfg, lp["norm"], x)
            y, st = ssm_mod.mamba_prefill(cfg, lp["ssm"], h)
            return x + y, st

        x, states = stack_scan(cfg, body, x, params["layers"],
                               cfg.num_layers)
        return _logits(cfg, params, x[:, -1]), {"layers": states}

    if cfg.family == "hybrid":
        cache: Cache = {}
        for i, kind in enumerate(cfg.layer_kinds):
            lp = params[f"layer_{i:02d}"]
            if kind == "attn":
                x, entry, _ = _attn_prefill_layer(cfg, lp, x, positions,
                                                  a_len, moe_shards)
                cache[f"layer_{i:02d}"] = entry
            else:
                h = norm_apply(cfg, lp["mix_norm"], x)
                y, st = rglru_mod.rglru_prefill(cfg, lp["rglru"], h)
                x = x + y
                h = norm_apply(cfg, lp["mlp_norm"], x)
                y, _ = mlp_apply(cfg, lp["mlp"], h, moe_shards)
                x = x + y
                cache[f"layer_{i:02d}"] = st
        return _logits(cfg, params, x[:, -1]), cache

    cache = {}
    n_unrolled = cfg.moe.first_moe_layer if (
        cfg.moe is not None and cfg.moe.first_moe_layer > 0) else 0
    for i in range(n_unrolled):
        x, entry, _ = _attn_prefill_layer(
            cfg, params[f"layer_{i:02d}"], x, positions, a_len,
            moe_shards)
        cache[f"layer_{i:02d}"] = entry

    def body(x, lp):
        x, entry, _ = _attn_prefill_layer(cfg, lp, x, positions, a_len,
                                          moe_shards)
        return x, entry

    x, entries = stack_scan(cfg, body, x, params["layers"],
                            cfg.num_layers - n_unrolled)
    cache["layers"] = entries
    return _logits(cfg, params, x[:, -1]), cache


# ----------------------------------------------------------------------
# paged KV-cache path (serving/kv_pool.py page pool + block tables)
# ----------------------------------------------------------------------
def resolve_layout(cfg: ModelConfig) -> Optional[str]:
    """Page-pool layout descriptor for ``cfg``, or None when only the
    dense (contiguous-cache) path can serve it.

    - ``"dense"``: bf16 K/V pages, linear cache, COW tail pages.
    - ``"quant"``: int8 code pages + per-vector f32 scale planes
      (``attn.quantize_kv``) — same page/COW geometry as dense at
      roughly half the bytes per position; bit-identical to the quant
      *dense* cache, not to bf16.
    - ``"ring"``: sliding-window layers (``cfg.window``) wrap their
      pages in place, capping pages-per-row at ceil(window/page).
    - ``"lanes"``: fixed-size recurrent-state lanes for SSM members —
      one lane holds a row's conv taps + SSM state, no growth with
      sequence length.

    A uniform GQA stack is required for the kv layouts; MoE configs
    qualify only with the capacity-free ``MoEConfig.impl == "gather"``
    dispatch (per-token expert math — batch-composition invariant,
    which the bucketed prefill relies on; the capacity path cumsums
    across rows) and a uniform stack (``first_moe_layer == 0`` — the
    paged bodies scan ``params["layers"]`` alone). Hybrid stacks
    (recurrentgemma: rglru + SWA layers interleaved) stay on the dense
    fallback — a per-block ring+lane mix is a ROADMAP follow-up.
    """
    if cfg.family == "ssm":
        return "lanes"
    moe_ok = cfg.moe is None or (cfg.moe.impl == "gather"
                                 and cfg.moe.first_moe_layer == 0)
    if not (cfg.family in ("dense", "moe") and cfg.attn_kind == "gqa"
            and moe_ok and cfg.frontend is None):
        return None
    if cfg.kv_quant:
        # quantised sliding-window caches would need ring scale planes
        # too; nothing in the zoo combines them — keep it dense
        return "quant" if cfg.window is None else None
    if cfg.window is not None:
        return "ring"
    return "dense"


def paged_supported(cfg: ModelConfig) -> bool:
    """True when some page layout serves the config bit-identically to
    its dense reference path (see ``resolve_layout``)."""
    return resolve_layout(cfg) is not None


def prefill_paged(cfg: ModelConfig, params: dict, tokens: jax.Array,
                  pages: Dict[str, jax.Array],
                  prefill_table: jax.Array, moe_shards: int = 1, *,
                  cache_len: Optional[int] = None
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Prompt prefill that scatters each layer's K/V into pool pages.

    tokens: (B, S); pages: the pool's page pytree — every leaf has
    leading axes (L, P, ...): dense holds {k, v} bf16
    (L, P, KV, page_size, Dh); quant adds int8 codes plus
    {k_scale, v_scale} f32 (L, P, KV, page_size) planes; ring is the
    dense leaf set over ceil(window/page) pages per row.
    prefill_table: (B, NBp) int32 page ids covering the row's prompt
    pages (rows must not alias writable pages); cache_len: the
    dense-equivalent total cache length (prompt + max_new) — required
    for ring layouts, where the pages hold the min(cache_len, window)
    ring snapshot. Returns (last-position logits, updated pages). The
    hidden-state math is the dense ``prefill`` bit-for-bit — only the
    cache packing differs; quant packing runs the same
    ``attn.quantize_kv`` the dense quant cache does, so codes and
    scales match that path bit-for-bit.
    """
    layout = resolve_layout(cfg)
    assert layout in ("dense", "quant", "ring"), cfg.name
    b, s = tokens.shape
    ps = pages["k"].shape[3]
    nbp = prefill_table.shape[1]
    positions = jnp.arange(s)
    x = _embed_inputs(cfg, params, tokens, None)

    def body(x, lp):
        h = norm_apply(cfg, lp["attn_norm"], x)
        q, k, v = attn.gqa_project_qkv(cfg, lp["attn"], h)
        if cfg.use_rope:
            q = attn.apply_rope(q, positions[None], cfg.rope_theta)
            k = attn.apply_rope(k, positions[None], cfg.rope_theta)
        o = attn.flash_attention(q, k, v, positions, positions,
                                 causal=True, window=cfg.window)
        o = o.reshape(b, s, cfg.num_heads * cfg.resolved_head_dim)
        # tensor parallelism: gather head-local attention outputs to
        # the full head axis before the replicated output projection
        o = tp_all_gather(o)
        x = x + jnp.einsum("bsh,hd->bsd", o, lp["attn"]["wo"])
        h = norm_apply(cfg, lp["mlp_norm"], x)
        y, _ = mlp_apply(cfg, lp["mlp"], h, moe_shards)
        return x + y, (k, v)

    x, (ks, vs) = stack_scan(cfg, body, x, params["layers"],
                             cfg.num_layers)
    logits = _logits(cfg, params, x[:, -1])

    if layout == "ring":
        # compress to the ring snapshot the dense path stores: slot =
        # absolute position mod cache_len over the surviving window
        cl = _attn_cache_len(cfg, s if cache_len is None else cache_len)
        ks = jax.vmap(lambda a: ring_compress(a, cl))(ks)
        vs = jax.vmap(lambda a: ring_compress(a, cl))(vs)

    entry = {"k": ks, "v": vs}
    if layout == "quant":
        kq, ksc = attn.quantize_kv(ks)
        vq, vsc = attn.quantize_kv(vs)
        entry = {"k": kq, "v": vq, "k_scale": ksc, "v_scale": vsc}

    # pack (L, B, S', KV, ...) into head-major pages: pad S' to the
    # page boundary and scatter (KV, page)-shaped chunks at the
    # block-table ids (pad chunks land in the partial tail page's dead
    # slots, matching the dense cache's zero padding)
    s_pad = nbp * ps

    def pack(a):
        if s_pad != a.shape[2]:
            pad = [(0, 0)] * a.ndim
            pad[2] = (0, s_pad - a.shape[2])
            a = jnp.pad(a, pad)
        a = a.reshape((cfg.num_layers, b, nbp, ps) + a.shape[3:])
        return jnp.swapaxes(a, 3, 4)

    pages = {name: pages[name].at[:, prefill_table].set(
                 pack(entry[name]).astype(pages[name].dtype))
             for name in pages}
    return logits, pages


def prefill_chunk_paged(cfg: ModelConfig, params: dict,
                        tokens: jax.Array,
                        pages: Dict[str, jax.Array],
                        block_table: jax.Array,
                        start_pos: jax.Array, *, prompt_len: int,
                        moe_shards: int = 1
                        ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One chunk of a paged prompt prefill (dense layout only).

    tokens: (B, C) — each row's prompt slice covering absolute
    positions [start_pos[b], start_pos[b] + C); start_pos: (B,) int32
    per-row offsets (traced — rows at different prefill depths share
    one compiled program); block_table: (B, NB) page ids covering at
    least ``prompt_len`` positions, with every chunk before a row's
    ``start_pos`` already written by earlier calls. Returns
    (last-chunk-position logits (B, V), k_pages, v_pages).

    Bit-equivalence contract: running chunks [0,C), [C,2C), ... [.., S)
    through this function yields K/V pages and final-position logits
    bit-identical to one ``prefill_paged`` call over the whole prompt.
    Per-token math (embedding, norms, MLP, output head) is position
    independent; attention reads the prefix from the same pages the
    one-shot path writes and always reduces over the full static
    ``prompt_len`` key axis (see ``attn.gqa_prefill_chunk_paged``), so
    no floating-point reduction regroups across chunk boundaries.

    Only the dense layout chunks: a quant chunk would attend the
    already-quantised int8 prefix where the dense quant reference
    attends full precision (quantisation only happens *into* the
    cache); a ring chunk overwrites positions the later chunks still
    attend; a lane prefill is one sequential scan. Those layouts
    prefill one-shot (``prefill_paged`` / the sampler's lane prefill).
    """
    assert resolve_layout(cfg) == "dense", cfg.name
    # the one-shot path switches to blockwise online softmax exactly
    # when prompt_len is a multiple of the flash block (attention.py
    # flash_attention); chunked prefill keeps the plain masked softmax
    # and would silently drift by ulps there — fail loudly instead
    assert (prompt_len <= attn._FLASH_BLOCK
            or prompt_len % attn._FLASH_BLOCK != 0), (
        f"chunked prefill is bit-exact only off the flash-block grid "
        f"(prompt_len={prompt_len} is a multiple of "
        f"{attn._FLASH_BLOCK}); use one-shot prefill_paged")
    b, c = tokens.shape
    x = _embed_inputs(cfg, params, tokens, None)

    def body(x, xs):
        lp, pg = xs
        h = norm_apply(cfg, lp["attn_norm"], x)
        a, kp, vp = attn.gqa_prefill_chunk_paged(
            cfg, lp["attn"], h, pg["k"], pg["v"], block_table,
            start_pos, prompt_len=prompt_len)
        x = x + a
        h = norm_apply(cfg, lp["mlp_norm"], x)
        y, _ = mlp_apply(cfg, lp["mlp"], h, moe_shards)
        return x + y, {"k": kp, "v": vp}

    x, pages = stack_scan(
        cfg, body, x, (params["layers"], pages), cfg.num_layers)
    return _logits(cfg, params, x[:, -1]), pages


def decode_step_paged(cfg: ModelConfig, params: dict,
                      pages: Dict[str, jax.Array],
                      block_table: jax.Array, token: jax.Array,
                      pos: jax.Array, *, cache_len: int
                      ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode step over the paged state, dispatching on the
    config's layout. token: (B,) int32; pos: scalar int32, or (B,)
    int32 per-row positions (the step-level loop advances mixed
    batches whose rows sit at different depths); cache_len: static
    dense-equivalent cache length (prompt + max_new — ring layouts cap
    it at the window internally; lanes ignore it and ``pos``: the
    recurrent state is position free).

    Dense/quant write each layer's K/V (codes + scales) at ``pos``
    into the row's block-table page; ring writes at
    ``pos mod min(cache_len, window)``; lanes gather each row's
    recurrent state at its block-table lane id, run the SSM step, and
    scatter the new state back. Returns (logits, updated pages)."""
    layout = resolve_layout(cfg)
    assert layout is not None, cfg.name
    x = jnp.take(params["embedding"], token, axis=0)
    x = shard(x, "batch", "embed")

    if layout == "lanes":
        lanes = block_table[:, 0]

        def lane_body(x, xs):
            lp, pg = xs
            h = norm_apply(cfg, lp["norm"], x)
            st = jax.tree.map(lambda a: a[lanes], pg)
            y, new_st = ssm_mod.mamba_step(cfg, lp["ssm"], h, st)
            # lane arena dtypes equal the state dtypes mamba emits
            # (conv: cfg.dtype taps, h: f32), so the scatter is a pure
            # copy — the gathered state round-trips bit-exactly
            pg = jax.tree.map(lambda a, ns: a.at[lanes].set(ns),
                              pg, new_st)
            return x + y, pg

        x, pages = stack_scan(cfg, lane_body, x,
                              (params["layers"], pages),
                              cfg.num_layers)
        return _logits(cfg, params, x), pages

    def body(x, xs):
        lp, pg = xs
        h = norm_apply(cfg, lp["attn_norm"], x)
        if layout == "quant":
            a, pg = attn.gqa_decode_quant_paged(
                cfg, lp["attn"], h, pg, block_table, pos,
                cache_len=cache_len)
        elif layout == "ring":
            a, pg = attn.gqa_decode_ring_paged(
                cfg, lp["attn"], h, pg, block_table, pos,
                cache_len=min(cache_len, cfg.window))
        else:
            a, kp, vp = attn.gqa_decode_paged(
                cfg, lp["attn"], h, pg["k"], pg["v"], block_table,
                pos, cache_len=cache_len)
            pg = {"k": kp, "v": vp}
        x = x + a
        h = norm_apply(cfg, lp["mlp_norm"], x)
        x = x + mlp_apply_token(cfg, lp["mlp"], h)
        return x, pg

    x, pages = stack_scan(cfg, body, x, (params["layers"], pages),
                          cfg.num_layers)
    return _logits(cfg, params, x), pages


# ----------------------------------------------------------------------
# decode
# ----------------------------------------------------------------------
def _attn_decode_layer(cfg: ModelConfig, lp: dict, x_t, cache_l, pos,
                       cross=None):
    h = norm_apply(cfg, lp["attn_norm"], x_t)
    if cfg.attn_kind == "mla":
        a, new_c = attn.mla_decode(cfg, lp["attn"], h, cache_l, pos)
    else:
        a, new_c = attn.gqa_decode(cfg, lp["attn"], h, cache_l, pos,
                                   ring=cfg.window is not None)
    x_t = x_t + a
    if cross is not None:
        h = norm_apply(cfg, lp["cross_norm"], x_t)
        x_t = x_t + attn.cross_attention(cfg, lp["cross"], h,
                                         cross["k"], cross["v"])
    h = norm_apply(cfg, lp["mlp_norm"], x_t)
    x_t = x_t + mlp_apply_token(cfg, lp["mlp"], h)
    return x_t, new_c


def decode_step(cfg: ModelConfig, params: dict, cache: Cache,
                token: jax.Array, pos: jax.Array
                ) -> Tuple[jax.Array, Cache]:
    """One decode step. token: (B,) int32; pos: scalar int32.

    Writes KV/state at ``pos`` and returns logits for position pos+1.
    """
    x = jnp.take(params["embedding"], token, axis=0)   # (B, d)
    x = shard(x, "batch", "embed")

    if cfg.family == "audio":
        x = x + _dec_pos(cfg, params,
                         jnp.atleast_1d(pos))[0].astype(
            x.dtype)[None]

        def body(x, xs):
            lp, cache_l, cross_l = xs
            x, new_c = _attn_decode_layer(cfg, lp, x, cache_l, pos,
                                          cross=cross_l)
            return x, new_c

        x, new_self = stack_scan(
            cfg, body, x, (params["dec_layers"], cache["dec_layers"],
                           cache["cross"]), cfg.num_layers)
        logits = _logits(cfg, params, x)
        return logits, {"dec_layers": new_self, "cross": cache["cross"]}

    if cfg.family == "ssm":
        def body(x, xs):
            lp, st = xs
            h = norm_apply(cfg, lp["norm"], x)
            y, new_st = ssm_mod.mamba_step(cfg, lp["ssm"], h, st)
            return x + y, new_st

        x, new_states = stack_scan(
            cfg, body, x, (params["layers"], cache["layers"]),
            cfg.num_layers)
        return _logits(cfg, params, x), {"layers": new_states}

    if cfg.family == "hybrid":
        new_cache: Cache = {}
        for i, kind in enumerate(cfg.layer_kinds):
            lp = params[f"layer_{i:02d}"]
            cl = cache[f"layer_{i:02d}"]
            if kind == "attn":
                x, new_cache[f"layer_{i:02d}"] = _attn_decode_layer(
                    cfg, lp, x, cl, pos)
            else:
                h = norm_apply(cfg, lp["mix_norm"], x)
                y, st = rglru_mod.rglru_block_step(cfg, lp["rglru"], h,
                                                   cl)
                x = x + y
                h = norm_apply(cfg, lp["mlp_norm"], x)
                x = x + mlp_apply_token(cfg, lp["mlp"], h)
                new_cache[f"layer_{i:02d}"] = st
        return _logits(cfg, params, x), new_cache

    new_cache = {}
    n_unrolled = cfg.moe.first_moe_layer if (
        cfg.moe is not None and cfg.moe.first_moe_layer > 0) else 0
    for i in range(n_unrolled):
        x, new_cache[f"layer_{i:02d}"] = _attn_decode_layer(
            cfg, params[f"layer_{i:02d}"], x, cache[f"layer_{i:02d}"],
            pos)

    def body(x, xs):
        lp, cache_l = xs
        x, new_c = _attn_decode_layer(cfg, lp, x, cache_l, pos)
        return x, new_c

    x, entries = stack_scan(cfg, body, x, (params["layers"],
                                           cache["layers"]),
                            cfg.num_layers - n_unrolled)
    new_cache["layers"] = entries
    return _logits(cfg, params, x), new_cache
