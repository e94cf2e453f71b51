"""Pallas TPU kernel: block-table (paged) flash-decode over an
int8-quantised KV cache.

kernels/paged_decode_attention.py re-derived for the quant page
layout (serving/kv_pool.py ``layout == "quant"``): pages hold int8
codes plus f32 per-vector scale planes, so HBM reads per position are
Dh + 4 bytes instead of 2*Dh — roughly 2x the rows per device at the
same pool bytes. The grid walks one page per step per
(batch, kv-head); the page id comes from the scalar-prefetched block
table (DMA for page ``n+1`` issues while page ``n`` computes); the
online-softmax state (m, l, acc) rides in VMEM scratch. The scales
fold into the attention math exactly as in the dense quant kernel
(kernels/decode_attention_quant.py):

    scores_s = (q . k_codes_s) * k_scale_s
    out      = sum_s (p_s * v_scale_s) * v_codes_s

The int8->f32 widen happens on the VPU after the VMEM load, so the
MXU contraction runs on the widened page while HBM only ever sees
int8 codes + one f32 scale per vector.

Layout: head-major like the dense pool — codes ``(P, KV, page_size,
Dh)``, scale planes ``(P, KV, page_size)``. A code block is one
``(page_size, Dh)`` tile; a scale block is the page's whole
``(KV, page_size)`` plane, from which the kernel takes its kv-head's
row (a one-row block on the KV axis would break the (8, 128) tiling
rule).

Grid: (B, KV, NB) — page axis innermost so the scratch carries across
one row's pages. Rows shorter than NB pages mask by ``lengths[b]``;
spare block-table slots must hold *valid* page ids (the pool
guarantees this), the data being fully masked.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _paged_decode_quant_kernel(bt_ref, len_ref, q_ref, k_ref, ks_ref,
                               v_ref, vs_ref, o_ref, m_ref, l_ref,
                               acc_ref, *, page_size: int,
                               scale: float):
    bi = pl.program_id(0)
    ki = pl.program_id(1)
    ni = pl.program_id(2)
    n_b = pl.num_programs(2)

    @pl.when(ni == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale        # (G, Dk)
    k = k_ref[0, 0].astype(jnp.float32)                # (page, Dk) int8
    kscale = ks_ref[0, pl.ds(ki, 1), :]                # (1, page) f32
    v = v_ref[0, 0].astype(jnp.float32)
    vscale = vs_ref[0, pl.ds(ki, 1), :]

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    s = s * kscale                                     # fold k scales
    positions = ni * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    valid = positions < len_ref[bi]
    s = jnp.where(valid, s, -jnp.inf)

    m_prev = m_ref[...]                                # (G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(jnp.isfinite(m_new), p, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), alpha, 0.0)

    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    pv = p * vscale                                    # fold v scales
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        pv, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ni == n_b - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_quant(q: jax.Array, k_pages: jax.Array,
                                 k_scale_pages: jax.Array,
                                 v_pages: jax.Array,
                                 v_scale_pages: jax.Array,
                                 block_table: jax.Array,
                                 lengths: jax.Array, *,
                                 interpret: bool = False) -> jax.Array:
    """q: (B, H, Dk); k_pages/v_pages: (P, KV, page_size, Dk/Dv) int8;
    k_scale_pages/v_scale_pages: (P, KV, page_size) f32;
    block_table: (B, NB) int32 page ids; lengths: (B,) int32 valid
    positions per row. Returns (B, H, Dv)."""
    b, h, dk = q.shape
    kv, page_size = k_pages.shape[1], k_pages.shape[2]
    dv = v_pages.shape[-1]
    nb = block_table.shape[1]
    g = h // kv
    scale = 1.0 / (dk ** 0.5)

    qg = q.reshape(b, kv, g, dk)
    block_table = block_table.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # block_table, lengths
        grid=(b, kv, nb),
        in_specs=[
            pl.BlockSpec((1, 1, g, dk),
                         lambda bi, ki, ni, bt, ln: (bi, ki, 0, 0)),
            pl.BlockSpec((1, 1, page_size, dk),
                         lambda bi, ki, ni, bt, ln:
                         (bt[bi, ni], ki, 0, 0)),
            pl.BlockSpec((1, kv, page_size),
                         lambda bi, ki, ni, bt, ln:
                         (bt[bi, ni], 0, 0)),
            pl.BlockSpec((1, 1, page_size, dv),
                         lambda bi, ki, ni, bt, ln:
                         (bt[bi, ni], ki, 0, 0)),
            pl.BlockSpec((1, kv, page_size),
                         lambda bi, ki, ni, bt, ln:
                         (bt[bi, ni], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dv),
                               lambda bi, ki, ni, bt, ln:
                               (bi, ki, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),    # running max m
            pltpu.VMEM((g, 1), jnp.float32),    # running sum l
            pltpu.VMEM((g, dv), jnp.float32),   # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_quant_kernel,
                          page_size=page_size, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, dv), q.dtype),
        interpret=interpret,
    )(block_table, lengths, qg, k_pages, k_scale_pages, v_pages,
      v_scale_pages)
    return out.reshape(b, h, dv)
