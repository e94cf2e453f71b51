"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics the kernels must match (tests sweep shapes and
dtypes and assert allclose against these). They are also the fallback
implementation on backends without Pallas TPU support.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def decode_attention_ref(q: jax.Array, k_cache: jax.Array,
                         v_cache: jax.Array, length: jax.Array
                         ) -> jax.Array:
    """Single-token GQA decode attention over a padded KV cache.

    q: (B, H, Dk); k_cache/v_cache: (B, S, KV, Dk/Dv);
    length: scalar int32 — number of valid cache positions.
    Returns (B, H, Dv), computed in f32.
    """
    b, h, dk = q.shape
    s, kv = k_cache.shape[1], k_cache.shape[2]
    g = h // kv
    scale = 1.0 / jnp.sqrt(jnp.float32(dk))
    qr = q.reshape(b, kv, g, dk).astype(jnp.float32) * scale
    scores = jnp.einsum("bkgd,bskd->bkgs", qr,
                        k_cache.astype(jnp.float32))
    valid = jnp.arange(s)[None] < length
    scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs,
                     v_cache.astype(jnp.float32))
    return out.reshape(b, h, v_cache.shape[-1]).astype(q.dtype)


def gather_pages(pages: jax.Array, block_table: jax.Array,
                 length: Optional[int] = None) -> jax.Array:
    """Head-major pages ``(P, KV, page_size, ...)`` gathered through a
    ``(B, NB)`` block table into each row's token-major contiguous view
    ``(B, length, KV, ...)`` (``length`` defaults to ``NB *
    page_size``) — the dense cache layout the attention math reads. A
    pure copy, and one gather: the token-major result comes straight
    out of it, so the contractions downstream see the same operand
    layout as a dense cache's."""
    ps = pages.shape[2]
    t = jnp.arange(block_table.shape[1] * ps if length is None
                   else length)
    page_ids = block_table[:, t // ps]                  # (B, length)
    heads = jnp.arange(pages.shape[1])
    return pages[page_ids[:, :, None], heads[None, None, :],
                 (t % ps)[None, :, None]]


def paged_decode_attention_ref(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array,
                               block_table: jax.Array,
                               lengths: jax.Array) -> jax.Array:
    """Single-token GQA decode attention over a paged KV cache.

    q: (B, H, Dk); k_pages/v_pages: (P, KV, page_size, Dk/Dv);
    block_table: (B, NB) int32 page ids per row; lengths: (B,) int32
    valid positions per row. Gathers each row's pages into a
    contiguous view and attends over the valid prefix; math in f32.
    Returns (B, H, Dv).
    """
    b, h, dk = q.shape
    kv, page_size = k_pages.shape[1], k_pages.shape[2]
    nb = block_table.shape[1]
    g = h // kv
    k_cache = gather_pages(k_pages, block_table)
    v_cache = gather_pages(v_pages, block_table)
    scale = 1.0 / jnp.sqrt(jnp.float32(dk))
    qr = q.reshape(b, kv, g, dk).astype(jnp.float32) * scale
    scores = jnp.einsum("bkgd,bskd->bkgs", qr,
                        k_cache.astype(jnp.float32))
    valid = jnp.arange(nb * page_size)[None] < lengths[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs,
                     v_cache.astype(jnp.float32))
    return out.reshape(b, h, v_cache.shape[-1]).astype(q.dtype)


def paged_decode_attention_quant_ref(q: jax.Array, k_pages: jax.Array,
                                     k_scale_pages: jax.Array,
                                     v_pages: jax.Array,
                                     v_scale_pages: jax.Array,
                                     block_table: jax.Array,
                                     lengths: jax.Array) -> jax.Array:
    """Single-token GQA decode over int8-quantised paged KV.

    q: (B, H, Dk); k_pages/v_pages: (P, KV, page_size, Dk/Dv) int8
    codes; k_scale_pages/v_scale_pages: (P, KV, page_size) f32
    per-vector scales; block_table: (B, NB) int32 page ids; lengths:
    (B,) int32 valid positions per row. Scales fold into the
    attention math exactly as in
    ``models.attention.decode_attention_quant``:
        scores_s = (q . k_codes_s) * k_scale_s
        out      = sum_s (p_s * v_scale_s) * v_codes_s
    Math in f32; returns (B, H, Dv).
    """
    b, h, dk = q.shape
    kv, page_size = k_pages.shape[1], k_pages.shape[2]
    nb = block_table.shape[1]
    g = h // kv
    k_cache = gather_pages(k_pages, block_table)
    v_cache = gather_pages(v_pages, block_table)
    k_scale = gather_pages(k_scale_pages, block_table)
    v_scale = gather_pages(v_scale_pages, block_table)
    scale = 1.0 / jnp.sqrt(jnp.float32(dk))
    qr = q.reshape(b, kv, g, dk).astype(jnp.float32) * scale
    scores = jnp.einsum("bkgd,bskd->bkgs", qr,
                        k_cache.astype(jnp.float32))
    scores = scores * k_scale.transpose(0, 2, 1)[:, :, None, :]
    valid = jnp.arange(nb * page_size)[None] < lengths[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    pv = probs * v_scale.transpose(0, 2, 1)[:, :, None, :]
    out = jnp.einsum("bkgs,bskd->bkgd", pv,
                     v_cache.astype(jnp.float32))
    return out.reshape(b, h, v_cache.shape[-1]).astype(q.dtype)


def chunked_prefill_attention_ref(q: jax.Array, k_pages: jax.Array,
                                  v_pages: jax.Array,
                                  block_table: jax.Array,
                                  q_positions: jax.Array, *,
                                  prompt_len: int) -> jax.Array:
    """Chunked-prefill GQA attention over a paged KV cache.

    q: (B, C, H, Dk) chunk queries at absolute positions
    ``q_positions`` (B, C) — rows may sit at different prefill
    depths; k_pages/v_pages: (P, KV, page_size, Dk/Dv); block_table:
    (B, NB) int32 page ids. The chunk's own K/V must already be
    written into the pages. Gathers each row's pages to the static
    ``prompt_len`` and attends causally (key position <= query
    position); math in f32. Returns (B, C, H, Dv).
    """
    b, c, h, dk = q.shape
    kv = k_pages.shape[1]
    g = h // kv
    k_cache = gather_pages(k_pages, block_table, prompt_len)
    v_cache = gather_pages(v_pages, block_table, prompt_len)
    scale = 1.0 / jnp.sqrt(jnp.float32(dk))
    qr = q.reshape(b, c, kv, g, dk).astype(jnp.float32) * scale
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qr,
                        k_cache.astype(jnp.float32))
    valid = q_positions[:, :, None] >= \
        jnp.arange(prompt_len)[None, None]                 # (B, C, S)
    scores = jnp.where(valid[:, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs,
                     v_cache.astype(jnp.float32))
    return out.reshape(b, c, h, v_cache.shape[-1]).astype(q.dtype)


def selective_scan_ref(x: jax.Array, dt: jax.Array, a_log: jax.Array,
                       b_in: jax.Array, c_in: jax.Array,
                       h0: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, jax.Array]:
    """Sequential Mamba-1 selective scan (the definitional oracle).

    x, dt: (B, S, D); a_log: (D, N); b_in, c_in: (B, S, N).
    Returns (y (B, S, D), h_final (B, D, N)); math in f32.
    """
    bsz, s, d = x.shape
    n = a_log.shape[1]
    a = -jnp.exp(a_log.astype(jnp.float32))

    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    bf = b_in.astype(jnp.float32)
    cf = c_in.astype(jnp.float32)
    if h0 is None:
        h0 = jnp.zeros((bsz, d, n), jnp.float32)

    def step(h, t):
        xt, dtt, bt, ct = t
        dta = jnp.exp(dtt[..., None] * a)              # (B, D, N)
        u = (dtt * xt)[..., None] * bt[:, None, :]
        h = dta * h + u
        y = jnp.einsum("bdn,bn->bd", h, ct)
        return h, y

    ts = (xf.swapaxes(0, 1), dtf.swapaxes(0, 1),
          bf.swapaxes(0, 1), cf.swapaxes(0, 1))
    h_final, ys = jax.lax.scan(step, h0, ts)
    return ys.swapaxes(0, 1).astype(x.dtype), h_final


def rglru_scan_ref(a: jax.Array, u: jax.Array,
                   h0: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Sequential linear recurrence h_t = a_t * h_{t-1} + u_t.

    a, u: (B, S, W) f32 gates/inputs. Returns (hs (B,S,W), h_final).
    """
    bsz, s, w = a.shape
    if h0 is None:
        h0 = jnp.zeros((bsz, w), jnp.float32)

    def step(h, t):
        at, ut = t
        h = at * h + ut
        return h, h

    h_final, hs = jax.lax.scan(
        step, h0.astype(jnp.float32),
        (a.astype(jnp.float32).swapaxes(0, 1),
         u.astype(jnp.float32).swapaxes(0, 1)))
    return hs.swapaxes(0, 1), h_final


def fused_swiglu_ref(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                     w_down: jax.Array) -> jax.Array:
    """SwiGLU MLP: silu(x @ w_gate) * (x @ w_up) @ w_down.

    x: (T, D); w_gate/w_up: (D, F); w_down: (F, D).
    """
    g = jnp.einsum("td,df->tf", x, w_gate)
    u = jnp.einsum("td,df->tf", x, w_up)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("tf,fd->td", h, w_down)
