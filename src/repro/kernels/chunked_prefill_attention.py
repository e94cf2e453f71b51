"""Pallas TPU kernel: block-table (paged) chunked-prefill attention.

The step-level serving loop feeds long prompts through the paged KV
pool one fixed-size chunk at a time (serving/step_loop.py): each chunk
writes its K/V into pool pages, then its queries attend causally over
everything written so far. This kernel is the paged flash-decode of
kernels/paged_decode_attention.py widened to a query *chunk*: the grid
walks one page per step per (batch, kv-head), page ids come from the
scalar-prefetched block table (the DMA for page ``n+1`` issues while
page ``n`` computes), and the online-softmax state (m, l, acc) — now
carried per (chunk position, group head) — rides in VMEM scratch.

Masking is two-sided: a key at absolute position ``kp`` is valid for
the chunk query at absolute position ``qp`` iff ``kp <= qp`` (causal)
— which also masks every slot past the chunk's own writes, so stale
bytes in recycled pages never reach the softmax.

Layout notes: pages are head-major, ``(P, KV, page_size, head_dim)``
(see kernels/paged_decode_attention.py), so a page is one
``(page_size, head_dim)`` block. The chunk's queries for one kv-head
are flattened to a ``(C * G, head_dim)`` tile — row ``c * G + g`` is
group head ``g`` at chunk position ``c`` — so every contraction is a
plain 2-D matmul, and each row's absolute query position rides in a
``(C * G, 1)`` int32 column beside it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _chunk_prefill_kernel(bt_ref, qpos_ref, q_ref, k_ref, v_ref,
                          o_ref, m_ref, l_ref, acc_ref, *,
                          page_size: int, scale: float):
    ni = pl.program_id(2)
    n_b = pl.num_programs(2)

    @pl.when(ni == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale        # (C*G, Dk)
    k = k_ref[0, 0].astype(jnp.float32)                # (page, Dk)
    v = v_ref[0, 0].astype(jnp.float32)                # (page, Dv)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    key_pos = ni * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    s = jnp.where(key_pos <= qpos_ref[0], s, -jnp.inf)

    m_prev = m_ref[...]                                # (C*G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(jnp.isfinite(m_new), p, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), alpha, 0.0)

    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ni == n_b - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("prompt_len", "interpret"))
def chunked_prefill_attention(q: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array,
                              block_table: jax.Array,
                              q_positions: jax.Array, *,
                              prompt_len: int,
                              interpret: bool = False) -> jax.Array:
    """q: (B, C, H, Dk) chunk queries; k_pages/v_pages: (P, KV,
    page_size, Dk/Dv); block_table: (B, NB) int32 page ids;
    q_positions: (B, C) int32 absolute positions of each row's chunk
    (rows may sit at different prefill depths); prompt_len: static
    total prompt length (pages past it are never touched). The chunk's
    own K/V must already be written into the pages. Returns
    (B, C, H, Dv)."""
    b, c, h, dk = q.shape
    kv, page_size = k_pages.shape[1], k_pages.shape[2]
    dv = v_pages.shape[-1]
    g = h // kv
    cg = c * g
    nb_used = -(-prompt_len // page_size)
    scale = 1.0 / (dk ** 0.5)

    qk = q.reshape(b, c, kv, g, dk).transpose(0, 2, 1, 3, 4).reshape(
        b, kv, cg, dk)                                 # (B, KV, C*G, Dk)
    qpos = jnp.repeat(q_positions.astype(jnp.int32), g,
                      axis=1)[..., None]               # (B, C*G, 1)
    block_table = block_table.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,           # block_table
        grid=(b, kv, nb_used),
        in_specs=[
            pl.BlockSpec((1, cg, 1),
                         lambda bi, ki, ni, bt: (bi, 0, 0)),
            pl.BlockSpec((1, 1, cg, dk),
                         lambda bi, ki, ni, bt: (bi, ki, 0, 0)),
            pl.BlockSpec((1, 1, page_size, dk),
                         lambda bi, ki, ni, bt: (bt[bi, ni], ki, 0, 0)),
            pl.BlockSpec((1, 1, page_size, dv),
                         lambda bi, ki, ni, bt: (bt[bi, ni], ki, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, cg, dv),
                               lambda bi, ki, ni, bt: (bi, ki, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((cg, 1), jnp.float32),      # running max m
            pltpu.VMEM((cg, 1), jnp.float32),      # running sum l
            pltpu.VMEM((cg, dv), jnp.float32),     # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_chunk_prefill_kernel, page_size=page_size,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, cg, dv), q.dtype),
        interpret=interpret,
    )(block_table, qpos, qk, k_pages, v_pages)
    return out.reshape(b, kv, c, g, dv).transpose(0, 2, 1, 3, 4).reshape(
        b, c, h, dv)
