"""Attention: GQA/MQA/SWA flash-style prefill + cache decode, and MLA.

TPU adaptation notes (see DESIGN.md §3):
  * training/prefill uses a blockwise online-softmax formulation written
    as ``lax.scan`` over KV blocks, so 32k prefill never materialises the
    (S, S) score matrix;
  * decode attends against a cache whose sharding is decided by the
    partitioning rules (KV-head sharded for kv>=model axis, sequence
    sharded Pope-et-al-style for MQA) — softmax over a sharded axis
    lowers to partial reductions + all-reduce under pjit;
  * sliding-window decode uses a ring buffer of size ``window`` so
    long_500k holds O(window) state, not O(S);
  * MLA decode uses the absorbed formulation: scores and context are
    computed directly against the compressed (kv_lora) cache, never
    expanding per-head K/V for the full history.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.ref import gather_pages
from repro.models.layers import apply_rope
from repro.sharding import shard, tp_all_gather

_NEG_INF = -1e30
_FLASH_BLOCK = 512


# ======================================================================
# core attention math
# ======================================================================
def _gqa_scores_full(q, k):
    """q: (B,Sq,KV,G,Dk), k: (B,Sk,KV,Dk) -> (B,KV,G,Sq,Sk) f32."""
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                      preferred_element_type=jnp.float32)


def full_attention(q, k, v, mask) -> jax.Array:
    """Reference path for short sequences.

    q: (B,Sq,H,Dk); k: (B,Sk,KV,Dk); v: (B,Sk,KV,Dv);
    mask: (Sq,Sk) or (B,Sq,Sk) bool (True = attend).
    Returns (B,Sq,H,Dv).
    """
    b, sq, h, dk = q.shape
    kv = k.shape[2]
    g = h // kv
    scale = 1.0 / jnp.sqrt(dk).astype(jnp.float32)
    qr = q.reshape(b, sq, kv, g, dk)
    scores = _gqa_scores_full(qr, k) * scale            # (B,KV,G,Sq,Sk)
    if mask.ndim == 2:
        m = mask[None, None, None]
    else:
        m = mask[:, None, None]
    scores = jnp.where(m, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v)
    return out.reshape(b, sq, h, v.shape[-1])


def flash_attention(q, k, v, q_positions, k_positions, *,
                    causal: bool = True,
                    window: Optional[int] = None,
                    block: int = _FLASH_BLOCK) -> jax.Array:
    """Blockwise online-softmax attention (pure JAX, lowers everywhere).

    q: (B,Sq,H,Dk); k: (B,Sk,KV,Dk); v: (B,Sk,KV,Dv).
    q_positions: (Sq,) int32; k_positions: (Sk,) int32.
    """
    b, sq, h, dk = q.shape
    sk, kv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = h // kv

    if sk % block != 0 or sk <= block:
        mask = _make_mask(q_positions, k_positions, causal, window)
        return full_attention(q, k, v, mask)

    nblk = sk // block
    scale = 1.0 / jnp.sqrt(dk).astype(jnp.float32)
    qr = (q.reshape(b, sq, kv, g, dk).astype(jnp.float32) * scale)

    k_blocks = k.reshape(b, nblk, block, kv, dk).swapaxes(0, 1)
    v_blocks = v.reshape(b, nblk, block, kv, dv).swapaxes(0, 1)
    kp_blocks = k_positions.reshape(nblk, block)

    m0 = jnp.full((b, kv, g, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kv, g, sq), jnp.float32)
    acc0 = jnp.zeros((b, kv, g, sq, dv), jnp.float32)

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, kp = xs
        s = jnp.einsum("bqkgd,bskd->bkgqs", qr, kb.astype(jnp.float32))
        valid = jnp.ones((sq, block), bool)
        if causal:
            valid &= q_positions[:, None] >= kp[None, :]
        if window is not None:
            valid &= (q_positions[:, None] - kp[None, :]) < window
        s = jnp.where(valid[None, None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    from repro.models.scan_flags import scan_unroll_arg
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0),
                                  (k_blocks, v_blocks, kp_blocks),
                                  unroll=scan_unroll_arg())
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, dv)
    return out.astype(q.dtype)


def _make_mask(q_positions, k_positions, causal, window):
    m = jnp.ones((q_positions.shape[0], k_positions.shape[0]), bool)
    if causal:
        m &= q_positions[:, None] >= k_positions[None, :]
    if window is not None:
        m &= (q_positions[:, None] - k_positions[None, :]) < window
    return m


def decode_attention(q, k_cache, v_cache, k_positions, pos) -> jax.Array:
    """Single-token attention against a cache.

    q: (B,H,Dk); k_cache: (B,S,KV,Dk); v_cache: (B,S,KV,Dv);
    k_positions: (S,) or (B,S) int32 — absolute position held in each
    slot (negative = empty; ring pages hold per-row slot contents, so
    the step loop passes the 2-D form); pos: scalar int32 current
    position, or (B,) int32 per-row positions (the step-level serving
    loop decodes mixed batches whose rows sit at different depths;
    per-row masking is the only difference, so each row's output is
    bit-identical to the scalar-pos call at that row's position).
    Returns (B,H,Dv).
    """
    b, h, dk = q.shape
    kv = k_cache.shape[2]
    g = h // kv
    scale = 1.0 / jnp.sqrt(dk).astype(jnp.float32)
    qr = (q.reshape(b, kv, g, dk).astype(jnp.float32)
          * scale).astype(k_cache.dtype)
    # keep the cache in its storage dtype (bf16): the contraction
    # accumulates in f32 via preferred_element_type, so no f32 COPY of
    # the whole cache is ever materialised (2x HBM traffic at 32k+
    # cache lengths — see EXPERIMENTS.md SPerf C2).
    scores = jnp.einsum("bkgd,bskd->bkgs", qr, k_cache,
                        preferred_element_type=jnp.float32)  # (B,KV,G,S)
    kp = k_positions if k_positions.ndim == 2 else k_positions[None]
    p_col = pos[:, None] if jnp.ndim(pos) else pos
    valid = (kp >= 0) & (kp <= p_col)                    # (1|B, S)
    scores = jnp.where(valid[:, None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs.astype(v_cache.dtype),
                     v_cache, preferred_element_type=jnp.float32)
    return out.reshape(b, h, v_cache.shape[-1]).astype(q.dtype)


# ======================================================================
# int8 KV cache (symmetric per-vector quantization over head_dim)
# ======================================================================
def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: (..., D) -> (int8 codes (..., D), f32 scale (...,)).

    Symmetric per-vector quantisation: scale = max|x| / 127 over the
    head dim. Halves cache storage + decode read traffic; the scales
    fold into the attention math (no dequantised cache copy):
        q.k_vec = (q.k_int8) * k_scale_s
        sum_s p_s v_vec_s = sum_s (p_s v_scale_s) v_int8_s
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    # constant-reciprocal multiply, not divide: XLA rewrites /127 to
    # *(1/127) only in some fusion contexts, and the prefill paths
    # quantise in different ones (inside vs outside the layer scan) —
    # the explicit multiply keeps the stored scales bitwise identical
    scale = jnp.maximum(amax, 1e-8) * (1.0 / 127.0)
    codes = jnp.clip(jnp.round(xf / scale[..., None]),
                     -127, 127).astype(jnp.int8)
    return codes, scale


def decode_attention_quant(q, k_codes, k_scale, v_codes, v_scale,
                           k_positions, pos) -> jax.Array:
    """decode_attention against an int8 cache.

    q: (B,H,Dk); k_codes/v_codes: (B,S,KV,D) int8;
    k_scale/v_scale: (B,S,KV) f32; k_positions: (S,) int32;
    pos: scalar int32, or (B,) int32 per-row positions (step-level
    decode batches mix rows at different depths — per-row masking
    keeps each row bit-identical to the scalar-pos call).
    """
    b, h, dk = q.shape
    kv = k_codes.shape[2]
    g = h // kv
    scale = 1.0 / jnp.sqrt(dk).astype(jnp.float32)
    qr = q.reshape(b, kv, g, dk).astype(jnp.float32) * scale
    scores = jnp.einsum("bkgd,bskd->bkgs", qr, k_codes,
                        preferred_element_type=jnp.float32)
    scores = scores * k_scale.transpose(0, 2, 1)[:, :, None, :]
    p_col = pos[:, None] if jnp.ndim(pos) else pos
    valid = (k_positions[None] >= 0) & (k_positions[None] <= p_col)
    scores = jnp.where(valid[:, None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    # fold the v scales into the probabilities (linearity)
    pv = probs * v_scale.transpose(0, 2, 1)[:, :, None, :]
    out = jnp.einsum("bkgs,bskd->bkgd", pv, v_codes,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, v_codes.shape[-1]).astype(q.dtype)


# ======================================================================
# GQA layer (projections + rope + attend)
# ======================================================================
def gqa_project_qkv(cfg: ModelConfig, p: dict, x: jax.Array):
    """x: (B,S,d) -> q (B,S,H,Dh), k/v (B,S,KV,Dh)."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(
        b, s, cfg.num_heads, hd)
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"]).reshape(
        b, s, cfg.num_kv_heads, hd)
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"]).reshape(
        b, s, cfg.num_kv_heads, hd)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    v = shard(v, "batch", "seq", "kv_heads", None)
    return q, k, v


def gqa_attention(cfg: ModelConfig, p: dict, x: jax.Array,
                  positions: jax.Array, *, causal: bool = True,
                  window: Optional[int] = None) -> jax.Array:
    """Full-sequence GQA attention (train / prefill). x: (B,S,d)."""
    q, k, v = gqa_project_qkv(cfg, p, x)
    if cfg.use_rope:
        q = apply_rope(q, positions[None], cfg.rope_theta)
        k = apply_rope(k, positions[None], cfg.rope_theta)
    out = flash_attention(q, k, v, positions, positions,
                          causal=causal, window=window)
    b, s = x.shape[:2]
    out = out.reshape(b, s, cfg.num_heads * cfg.resolved_head_dim)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"])


def gqa_decode(cfg: ModelConfig, p: dict, x_t: jax.Array, cache: dict,
               pos: jax.Array, *, ring: bool = False
               ) -> Tuple[jax.Array, dict]:
    """Single-token GQA decode. x_t: (B,d); cache: {k,v}: (B,S,KV,Dh).

    With ``ring=True`` the cache is a ring buffer over its own length
    (slot = pos % cache_len — used for sliding-window layers, where
    cache_len = min(seq_len, window)); otherwise a linear cache.
    """
    b, _ = x_t.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bd,dh->bh", x_t, p["wq"]).reshape(
        b, cfg.num_heads, hd)
    k = jnp.einsum("bd,dh->bh", x_t, p["wk"]).reshape(
        b, cfg.num_kv_heads, hd)
    v = jnp.einsum("bd,dh->bh", x_t, p["wv"]).reshape(
        b, cfg.num_kv_heads, hd)
    if cfg.use_rope:
        pos_b = jnp.broadcast_to(pos, (1, 1))
        q = apply_rope(q[:, None], pos_b, cfg.rope_theta)[:, 0]
        k = apply_rope(k[:, None], pos_b, cfg.rope_theta)[:, 0]

    s_cache = cache["k"].shape[1]
    if ring:
        slot = jnp.mod(pos, s_cache)
        slots = jnp.arange(s_cache)
        # absolute position currently held in each ring slot
        k_positions = pos - jnp.mod(pos - slots, s_cache)
    else:
        slot = pos
        k_positions = jnp.arange(s_cache)

    if cfg.use_pallas and not ring and "k_scale" not in cache:
        # TPU deployment: flash-decode Pallas kernel over the linear
        # cache (valid prefix = pos+1). Ring/quant caches use the jnp
        # paths. ops.decode_attention falls back to the oracle off-TPU.
        from repro.kernels import ops
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"], k[:, None].astype(cache["k"].dtype),
            (0, pos, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"], v[:, None].astype(cache["v"].dtype),
            (0, pos, 0, 0))
        out = ops.decode_attention(q, k_cache, v_cache, pos + 1)
        out = out.reshape(b, cfg.num_heads * hd)
        y = jnp.einsum("bh,hd->bd", out, p["wo"])
        return y, {"k": k_cache, "v": v_cache}

    if "k_scale" in cache:                 # int8-quantised cache
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"], kq[:, None], (0, slot, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"], vq[:, None], (0, slot, 0, 0))
        k_scale = jax.lax.dynamic_update_slice(
            cache["k_scale"], ks[:, None].astype(
                cache["k_scale"].dtype), (0, slot, 0))
        v_scale = jax.lax.dynamic_update_slice(
            cache["v_scale"], vs[:, None].astype(
                cache["v_scale"].dtype), (0, slot, 0))
        if cfg.use_pallas and not ring:
            # TPU deployment: int8 flash-decode kernel — scales fold
            # in-kernel, HBM reads stay int8. The op's off-TPU
            # dispatch is the jnp quant path with the same linear
            # k_positions/pos masking, so CPU bits are unchanged.
            from repro.kernels import ops
            out = ops.decode_attention_quant(
                q, k_cache, k_scale, v_cache, v_scale, pos + 1)
        else:
            out = decode_attention_quant(q, k_cache, k_scale, v_cache,
                                         v_scale, k_positions, pos)
        out = out.reshape(b, cfg.num_heads * hd)
        y = jnp.einsum("bh,hd->bd", out, p["wo"])
        return y, {"k": k_cache, "v": v_cache,
                   "k_scale": k_scale, "v_scale": v_scale}

    k_cache = jax.lax.dynamic_update_slice(
        cache["k"], k[:, None].astype(cache["k"].dtype), (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        cache["v"], v[:, None].astype(cache["v"].dtype), (0, slot, 0, 0))
    out = decode_attention(q, k_cache, v_cache, k_positions, pos)
    out = out.reshape(b, cfg.num_heads * hd)
    y = jnp.einsum("bh,hd->bd", out, p["wo"])
    return y, {"k": k_cache, "v": v_cache}


def _paged_decode_qkv(cfg: ModelConfig, p: dict, x_t: jax.Array,
                      pos: jax.Array):
    """The token's q (B, H, Dh), k and v (B, KV, Dh), roped at
    ``pos`` (scalar or (B,))."""
    b, _ = x_t.shape
    hd = cfg.resolved_head_dim
    kv = cfg.num_kv_heads
    q = jnp.einsum("bd,dh->bh", x_t, p["wq"]).reshape(
        b, cfg.num_heads, hd)
    k = jnp.einsum("bd,dh->bh", x_t, p["wk"]).reshape(b, kv, hd)
    v = jnp.einsum("bd,dh->bh", x_t, p["wv"]).reshape(b, kv, hd)
    if cfg.use_rope:
        pos_b = pos[:, None] if jnp.ndim(pos) == 1 else \
            jnp.broadcast_to(pos, (1, 1))
        q = apply_rope(q[:, None], pos_b, cfg.rope_theta)[:, 0]
        k = apply_rope(k[:, None], pos_b, cfg.rope_theta)[:, 0]
    return q, k, v


def _write_slot(block_table: jax.Array, pos: jax.Array, ps: int):
    """(page id (B,), slot) each row writes position ``pos`` to."""
    if jnp.ndim(pos) == 1:
        page_ids = jnp.take_along_axis(
            block_table, (pos // ps)[:, None], axis=1)[:, 0]
    else:
        page_ids = jnp.take(block_table, pos // ps, axis=1)
    return page_ids, pos % ps


def _decode_out(cfg: ModelConfig, p: dict, out: jax.Array) -> jax.Array:
    """Attention output (B, H, Dh) through the output projection."""
    out = out.reshape(out.shape[0], cfg.num_heads * cfg.resolved_head_dim)
    # under tensor parallelism wq/wk/wv are head-column-sharded (cfg
    # carries the local head counts) and wo is replicated: gather the
    # per-head outputs back to the full head axis before the output
    # projection — attention itself is head-local, so each shard's
    # slice is bit-identical to the same heads on one device
    out = tp_all_gather(out)
    return jnp.einsum("bh,hd->bd", out, p["wo"])


def gqa_decode_paged(cfg: ModelConfig, p: dict, x_t: jax.Array,
                     k_pages: jax.Array, v_pages: jax.Array,
                     block_table: jax.Array, pos: jax.Array
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-token GQA decode against one layer's slice of the paged
    KV cache through the block-table flash-decode kernel
    (``cfg.use_pallas``; without it the dense step decodes through
    ``gqa_decode_paged_pool``).

    x_t: (B, d); k_pages/v_pages: (P, KV, page_size, Dh);
    block_table: (B, NB) int32 page ids; pos: scalar int32, or (B,)
    int32 per-row positions (step-level serving mixes rows at
    different depths in one decode batch). The kernel reads the pages
    in place (no gathered copy); off the TPU the op dispatches to the
    gather-based oracle.
    """
    from repro.kernels import ops
    b = x_t.shape[0]
    q, k, v = _paged_decode_qkv(cfg, p, x_t, pos)
    page_ids, slot = _write_slot(block_table, pos, k_pages.shape[2])
    k_pages = k_pages.at[page_ids, :, slot].set(
        k.astype(k_pages.dtype))
    v_pages = v_pages.at[page_ids, :, slot].set(
        v.astype(v_pages.dtype))
    lengths = jnp.broadcast_to(pos + 1, (b,)).astype(jnp.int32)
    out = ops.paged_decode_attention(q, k_pages, v_pages, block_table,
                                     lengths)
    return _decode_out(cfg, p, out), k_pages, v_pages


def _gather_layer_pages(pool: jax.Array, layer: jax.Array,
                        block_table: jax.Array, length: int) -> jax.Array:
    """Each row's whole pages of one layer, read by ``[layer,
    block_table]`` straight from the pool ``(L, P, KV, page_size,
    Dh)``: ``(B, NB, KV, page_size, Dh)``, made token-major and cut to
    ``length``, which gives the ``(B, length, KV, Dh)`` view
    ``gather_pages`` gives from a layer's slice."""
    b, nb = block_table.shape
    rows = jnp.swapaxes(pool[layer, block_table], 2, 3)
    rows = rows.reshape(b, nb * pool.shape[3], *rows.shape[3:])
    return rows[:, :length]


def gqa_decode_paged_pool(cfg: ModelConfig, p: dict, x_t: jax.Array,
                          k_pool: jax.Array, v_pool: jax.Array,
                          layer: jax.Array, block_table: jax.Array,
                          pos: jax.Array, *, cache_len: int
                          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-token GQA decode against the whole paged KV cache,
    updated in place.

    x_t: (B, d); k_pool/v_pool: (L, P, KV, page_size, Dh), carried
    through the layer scan; layer: int32 scalar; block_table: (B, NB)
    int32 page ids; pos: scalar int32, or (B,) int32 per-row positions
    (step-level serving mixes rows at different depths in one decode
    batch); cache_len: static dense-equivalent cache length (prompt +
    max_new).

    The token's K/V are written at ``[layer, page, :, slot]`` and each
    row's pages are gathered by ``[layer, block_table]``, so no copy
    of the layer's slice is made: a decode tick moves the rows it
    writes and the pages it reads, whatever the pool's size.

    Bit-equivalence contract: the gathered page view sliced to
    ``cache_len`` feeds the *same* ``decode_attention`` with the same
    shapes and values as the dense path's contiguous cache, so the
    output is bit-identical. Stale bytes in recycled pages sit at
    positions > pos and are masked to the same -1e30 the dense path's
    zero-initialised slots are, before any softmax math runs."""
    q, k, v = _paged_decode_qkv(cfg, p, x_t, pos)
    page_ids, slot = _write_slot(block_table, pos, k_pool.shape[3])
    k_pool = k_pool.at[layer, page_ids, :, slot].set(
        k.astype(k_pool.dtype))
    v_pool = v_pool.at[layer, page_ids, :, slot].set(
        v.astype(v_pool.dtype))
    k_cache = _gather_layer_pages(k_pool, layer, block_table, cache_len)
    v_cache = _gather_layer_pages(v_pool, layer, block_table, cache_len)
    out = decode_attention(q, k_cache, v_cache, jnp.arange(cache_len),
                           pos)
    return _decode_out(cfg, p, out), k_pool, v_pool


def gqa_decode_quant_paged(cfg: ModelConfig, p: dict, x_t: jax.Array,
                           pages: dict, block_table: jax.Array,
                           pos: jax.Array, *, cache_len: int
                           ) -> Tuple[jax.Array, dict]:
    """Single-token GQA decode against int8-quantised KV pages.

    x_t: (B, d); pages: one layer's slice of the quant pool —
    {"k","v"}: (P, KV, page_size, Dh) int8 codes, {"k_scale",
    "v_scale"}: (P, KV, page_size) f32 per-vector scales;
    block_table: (B, NB) page ids; pos: scalar or (B,) int32;
    cache_len: static dense-equivalent cache length.

    Bit-equivalence contract: identical to the dense *quant* cache
    path (``gqa_decode`` with ``k_scale`` in the cache) — the token's
    K/V quantise through the same ``quantize_kv``, and the gathered
    page view sliced to ``cache_len`` feeds the same
    ``decode_attention_quant``. Stale bytes in recycled pages are
    finite int8 codes x finite f32 scales, masked to the same -1e30
    the dense path's zero-initialised slots are (probabilities exactly
    zero either way).
    """
    b, _ = x_t.shape
    hd = cfg.resolved_head_dim
    kv = cfg.num_kv_heads
    q = jnp.einsum("bd,dh->bh", x_t, p["wq"]).reshape(
        b, cfg.num_heads, hd)
    k = jnp.einsum("bd,dh->bh", x_t, p["wk"]).reshape(b, kv, hd)
    v = jnp.einsum("bd,dh->bh", x_t, p["wv"]).reshape(b, kv, hd)
    per_row = jnp.ndim(pos) == 1
    if cfg.use_rope:
        pos_b = pos[:, None] if per_row else jnp.broadcast_to(
            pos, (1, 1))
        q = apply_rope(q[:, None], pos_b, cfg.rope_theta)[:, 0]
        k = apply_rope(k[:, None], pos_b, cfg.rope_theta)[:, 0]
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)

    ps = pages["k"].shape[2]
    if per_row:
        page_ids = jnp.take_along_axis(
            block_table, (pos // ps)[:, None], axis=1)[:, 0]  # (B,)
        slot = pos % ps
    else:
        page_ids = jnp.take(block_table, pos // ps, axis=1)
        slot = pos % ps
    pages = {
        "k": pages["k"].at[page_ids, :, slot].set(kq),
        "v": pages["v"].at[page_ids, :, slot].set(vq),
        "k_scale": pages["k_scale"].at[page_ids, :, slot].set(
            ks.astype(pages["k_scale"].dtype)),
        "v_scale": pages["v_scale"].at[page_ids, :, slot].set(
            vs.astype(pages["v_scale"].dtype)),
    }

    if cfg.use_pallas:
        # TPU deployment: block-table int8 flash-decode kernel reads
        # codes + scale planes in place. Off-TPU the op dispatches to
        # the gather-based oracle.
        from repro.kernels import ops
        lengths = jnp.broadcast_to(pos + 1, (b,)).astype(jnp.int32)
        out = ops.paged_decode_attention_quant(
            q, pages["k"], pages["k_scale"], pages["v"],
            pages["v_scale"], block_table, lengths)
    else:
        k_cache, v_cache, k_scale, v_scale = (
            gather_pages(pages[name], block_table, cache_len)
            for name in ("k", "v", "k_scale", "v_scale"))
        out = decode_attention_quant(q, k_cache, k_scale, v_cache,
                                     v_scale, jnp.arange(cache_len),
                                     pos)
    out = out.reshape(b, cfg.num_heads * hd)
    # tensor parallelism: gather head-local outputs before the
    # replicated output projection (see ``_decode_out``)
    out = tp_all_gather(out)
    y = jnp.einsum("bh,hd->bd", out, p["wo"])
    return y, pages


def gqa_decode_ring_paged(cfg: ModelConfig, p: dict, x_t: jax.Array,
                          pages: dict, block_table: jax.Array,
                          pos: jax.Array, *, cache_len: int
                          ) -> Tuple[jax.Array, dict]:
    """Single-token sliding-window GQA decode against ring pages.

    x_t: (B, d); pages: one layer's {"k","v"} (P, KV, page_size, Dh);
    block_table: (B, NB) page ids covering exactly
    ceil(cache_len/page_size) pages (NB never grows past the window);
    pos: scalar or (B,) int32; cache_len: the ring length —
    min(prompt + max_new, window), already window-capped by the
    caller.

    The pages hold the same ring the dense path keeps (slot = absolute
    position mod cache_len): the token's K/V scatter to each row's
    current slot, and masking uses the absolute position each slot
    currently holds — bit-identical per row to ``gqa_decode`` with
    ``ring=True`` at that row's position. Ring pages are lane-private
    (forked whole at spawn, never COW-shared), so the in-place slot
    write is safe.
    """
    b, _ = x_t.shape
    hd = cfg.resolved_head_dim
    kv = cfg.num_kv_heads
    q = jnp.einsum("bd,dh->bh", x_t, p["wq"]).reshape(
        b, cfg.num_heads, hd)
    k = jnp.einsum("bd,dh->bh", x_t, p["wk"]).reshape(b, kv, hd)
    v = jnp.einsum("bd,dh->bh", x_t, p["wv"]).reshape(b, kv, hd)
    per_row = jnp.ndim(pos) == 1
    if cfg.use_rope:
        pos_b = pos[:, None] if per_row else jnp.broadcast_to(
            pos, (1, 1))
        q = apply_rope(q[:, None], pos_b, cfg.rope_theta)[:, 0]
        k = apply_rope(k[:, None], pos_b, cfg.rope_theta)[:, 0]

    pos_rows = pos if per_row else jnp.broadcast_to(pos, (b,))
    ps = pages["k"].shape[2]
    slot = jnp.mod(pos_rows, cache_len)                   # (B,)
    page_ids = jnp.take_along_axis(
        block_table, (slot // ps)[:, None], axis=1)[:, 0]
    offset = slot % ps
    pages = {
        "k": pages["k"].at[page_ids, :, offset].set(
            k.astype(pages["k"].dtype)),
        "v": pages["v"].at[page_ids, :, offset].set(
            v.astype(pages["v"].dtype)),
    }

    k_cache = gather_pages(pages["k"], block_table, cache_len)
    v_cache = gather_pages(pages["v"], block_table, cache_len)
    # absolute position currently held in each ring slot, per row
    slots = jnp.arange(cache_len)[None]                   # (1, CL)
    k_positions = pos_rows[:, None] - jnp.mod(
        pos_rows[:, None] - slots, cache_len)             # (B, CL)
    out = decode_attention(q, k_cache, v_cache, k_positions, pos_rows)
    out = out.reshape(b, cfg.num_heads * hd)
    # tensor parallelism: gather head-local outputs before the
    # replicated output projection (see ``_decode_out``)
    out = tp_all_gather(out)
    y = jnp.einsum("bh,hd->bd", out, p["wo"])
    return y, pages


def gqa_prefill_chunk_paged(cfg: ModelConfig, p: dict, x: jax.Array,
                            k_pages: jax.Array, v_pages: jax.Array,
                            block_table: jax.Array,
                            start_pos: jax.Array, *, prompt_len: int
                            ) -> Tuple[jax.Array, jax.Array,
                                       jax.Array]:
    """One layer's chunked-prefill GQA attention against paged KV.

    x: (B, C, d) hidden states of each row's chunk covering absolute
    positions [start_pos[b], start_pos[b] + C); start_pos: (B,) int32
    *per-row* chunk offsets — traced, not static, so rows at different
    prefill depths share one compiled program (the step loop batches
    every row needing a chunk this tick into one launch);
    k_pages/v_pages: (P, KV, page_size, Dh) one layer's page-pool
    slice; block_table: (B, NB) page ids covering at least
    ``prompt_len`` positions. Writes the chunk's rope'd K/V into the
    pages, then attends the chunk queries over the gathered page view.
    Returns (y (B, C, d), k_pages, v_pages).

    Bit-equivalence contract: the key axis is always gathered to the
    *full static* ``prompt_len`` — the same reduction length the
    one-shot prefill's attention uses — never to ``start + C``.
    Key-axis reductions (softmax normaliser, the PV contraction) are
    only reproducible when their length matches: padding with masked
    lanes is exact (masked scores are -1e30, their probabilities exact
    zeros), but a *shorter* axis regroups the partial sums and drifts
    by ulps. Slots past a row's ``start + C`` hold finite stale page
    bytes and are causally masked, exactly like the one-shot path
    masks the not-yet-attended suffix. (For ``prompt_len`` an exact
    multiple of the flash block the one-shot path switches to the
    blockwise-softmax kernel; chunked prefill keeps the plain masked
    softmax, so its bit-contract holds for the non-blockwise regime —
    every prompt below ``_FLASH_BLOCK`` tokens.)
    """
    b, c, _ = x.shape
    hd = cfg.resolved_head_dim
    kv = cfg.num_kv_heads
    positions = start_pos[:, None] + jnp.arange(c)[None]   # (B, C)
    q, k, v = gqa_project_qkv(cfg, p, x)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    # scatter the chunk's K/V into the pages first, so attention reads
    # every key (prefix and self) from the same storage the decode
    # steps will — and so the Pallas kernel path needs no concat
    ps = k_pages.shape[2]
    page_ids = jnp.take_along_axis(block_table, positions // ps,
                                   axis=1)                 # (B, C)
    slots = positions % ps
    k_pages = k_pages.at[page_ids, :, slots].set(
        k.astype(k_pages.dtype))
    v_pages = v_pages.at[page_ids, :, slots].set(
        v.astype(v_pages.dtype))

    if cfg.use_pallas:
        # TPU deployment: paged chunk-prefill kernel reads the pages in
        # place. Off-TPU the op dispatches to the gather-based oracle.
        from repro.kernels import ops
        out = ops.chunked_prefill_attention(
            q, k_pages, v_pages, block_table, positions,
            prompt_len=prompt_len)
    else:
        k_all = gather_pages(k_pages, block_table, prompt_len)
        v_all = gather_pages(v_pages, block_table, prompt_len)
        mask = positions[:, :, None] >= \
            jnp.arange(prompt_len)[None, None]             # (B, C, S)
        out = full_attention(q, k_all, v_all, mask)
    out = out.reshape(b, c, cfg.num_heads * hd)
    # tensor parallelism: gather head-local outputs to the full head
    # axis before the replicated output projection (see
    # ``_decode_out``)
    out = tp_all_gather(out)
    y = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    return y, k_pages, v_pages


# ======================================================================
# MLA (DeepSeek-V2 multi-head latent attention)
# ======================================================================
def _mla_dims(cfg: ModelConfig):
    m = cfg.mla
    assert m is not None
    return m.q_lora_rank, m.kv_lora_rank, m.qk_nope_head_dim, \
        m.qk_rope_head_dim, m.v_head_dim


def mla_project_q(cfg: ModelConfig, p: dict, x: jax.Array):
    """x: (..., d) -> q_nope (..., H, nope), q_rope (..., H, rope)."""
    from repro.models.layers import rms_norm
    _, _, nope, rope, _ = _mla_dims(cfg)
    h = cfg.num_heads
    q_a = jnp.einsum("...d,dr->...r", x, p["wq_a"])
    q_a = rms_norm(q_a, p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("...r,rh->...h", q_a, p["wq_b"])
    q = q.reshape(*x.shape[:-1], h, nope + rope)
    return q[..., :nope], q[..., nope:]


def mla_project_kv_latent(cfg: ModelConfig, p: dict, x: jax.Array):
    """x: (..., d) -> c_kv (..., kv_lora) [normed], k_rope (..., rope)."""
    from repro.models.layers import rms_norm
    _, kvl, _, rope, _ = _mla_dims(cfg)
    kv_a = jnp.einsum("...d,dr->...r", x, p["wkv_a"])
    c_kv, k_rope = kv_a[..., :kvl], kv_a[..., kvl:]
    c_kv = rms_norm(c_kv, p["kv_norm"], cfg.norm_eps)
    return c_kv, k_rope


def mla_attention(cfg: ModelConfig, p: dict, x: jax.Array,
                  positions: jax.Array) -> jax.Array:
    """Full-sequence MLA (train / prefill): expand per-head K/V."""
    _, kvl, nope, rope, vdim = _mla_dims(cfg)
    b, s, _ = x.shape
    h = cfg.num_heads
    q_nope, q_rope = mla_project_q(cfg, p, x)
    q_rope = apply_rope(q_rope, positions[None], cfg.rope_theta)
    c_kv, k_rope = mla_project_kv_latent(cfg, p, x)
    k_rope = apply_rope(k_rope[:, :, None], positions[None],
                        cfg.rope_theta)                    # (B,S,1,rope)
    k_nope = jnp.einsum("bsr,rh->bsh", c_kv, p["wk_b"]).reshape(
        b, s, h, nope)
    v = jnp.einsum("bsr,rh->bsh", c_kv, p["wv_b"]).reshape(b, s, h, vdim)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, s, h, rope))], axis=-1)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "heads", None)
    v = shard(v, "batch", "seq", "heads", None)
    out = flash_attention(q, k, v, positions, positions, causal=True)
    out = out.reshape(b, s, h * vdim)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"])


def mla_decode(cfg: ModelConfig, p: dict, x_t: jax.Array, cache: dict,
               pos: jax.Array) -> Tuple[jax.Array, dict]:
    """Absorbed-form MLA decode against the compressed cache.

    cache: {c_kv: (B,S,kv_lora), k_rope: (B,S,rope)}.
    Scores/context are O(S * kv_lora) per head — per-head K/V for the
    history are never materialised.
    """
    _, kvl, nope, rope, vdim = _mla_dims(cfg)
    b, _ = x_t.shape
    h = cfg.num_heads
    q_nope, q_rope = mla_project_q(cfg, p, x_t)            # (B,H,·)
    pos_b = jnp.broadcast_to(pos, (1, 1))
    q_rope = apply_rope(q_rope[:, None], pos_b, cfg.rope_theta)[:, 0]
    c_kv_t, k_rope_t = mla_project_kv_latent(cfg, p, x_t)  # (B,·)
    k_rope_t = apply_rope(k_rope_t[:, None, None], pos_b,
                          cfg.rope_theta)[:, 0, 0]

    c_cache = jax.lax.dynamic_update_slice(
        cache["c_kv"], c_kv_t[:, None].astype(cache["c_kv"].dtype),
        (0, pos, 0))
    r_cache = jax.lax.dynamic_update_slice(
        cache["k_rope"], k_rope_t[:, None].astype(cache["k_rope"].dtype),
        (0, pos, 0))

    wk_b = p["wk_b"].reshape(kvl, h, nope)
    wv_b = p["wv_b"].reshape(kvl, h, vdim)
    # absorb W_uk into q: (B,H,kv_lora)
    q_c = jnp.einsum("bhn,khn->bhk", q_nope.astype(jnp.float32),
                     wk_b.astype(jnp.float32))
    scale = 1.0 / jnp.sqrt(jnp.float32(nope + rope))
    s_cache = c_cache.shape[1]
    scores = (jnp.einsum("bhk,bsk->bhs", q_c,
                         c_cache.astype(jnp.float32))
              + jnp.einsum("bhr,bsr->bhs", q_rope.astype(jnp.float32),
                           r_cache.astype(jnp.float32))) * scale
    valid = jnp.arange(s_cache) <= pos
    scores = jnp.where(valid[None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx_c = jnp.einsum("bhs,bsk->bhk", probs,
                       c_cache.astype(jnp.float32))        # (B,H,kv_lora)
    out = jnp.einsum("bhk,khv->bhv", ctx_c,
                     wv_b.astype(jnp.float32))             # (B,H,vdim)
    out = out.reshape(b, h * vdim).astype(x_t.dtype)
    y = jnp.einsum("bh,hd->bd", out, p["wo"])
    return y, {"c_kv": c_cache, "k_rope": r_cache}


# ======================================================================
# Cross attention (whisper decoder)
# ======================================================================
def cross_attention(cfg: ModelConfig, p: dict, x: jax.Array,
                    enc_k: jax.Array, enc_v: jax.Array) -> jax.Array:
    """x: (B,S,d) or (B,d); enc_k/enc_v: (B,F,KV,Dh) precomputed."""
    squeeze = x.ndim == 2
    if squeeze:
        x = x[:, None]
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(
        b, s, cfg.num_heads, hd)
    f = enc_k.shape[1]
    mask = jnp.ones((s, f), bool)
    out = full_attention(q, enc_k, enc_v, mask)
    out = out.reshape(b, s, cfg.num_heads * hd)
    y = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    return y[:, 0] if squeeze else y


def cross_kv(cfg: ModelConfig, p: dict, enc_out: jax.Array):
    """Precompute cross-attention K/V from encoder output (B,F,d)."""
    b, f, _ = enc_out.shape
    hd = cfg.resolved_head_dim
    k = jnp.einsum("bfd,dh->bfh", enc_out, p["wk"]).reshape(
        b, f, cfg.num_kv_heads, hd)
    v = jnp.einsum("bfd,dh->bfh", enc_out, p["wv"]).reshape(
        b, f, cfg.num_kv_heads, hd)
    return k, v
