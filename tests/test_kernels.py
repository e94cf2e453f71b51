"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret
mode (the kernels target TPU; interpret executes the body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.fused_swiglu import fused_swiglu
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.selective_scan import selective_scan

# JIT/compile-heavy: excluded from the fast inner loop (-m 'not slow')
pytestmark = pytest.mark.slow


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def tol_for(dtype):
    return TOL[jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32]


# ----------------------------------------------------------------------
# decode attention
# ----------------------------------------------------------------------
@pytest.mark.parametrize("b,h,kv,dk,s,blk", [
    (1, 4, 4, 64, 256, 128),      # MHA
    (2, 8, 2, 128, 512, 128),     # GQA
    (2, 8, 1, 128, 512, 256),     # MQA
    (1, 16, 8, 64, 1024, 512),
    (3, 6, 3, 32, 384, 384),      # non-divisible block -> full
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(b, h, kv, dk, s, blk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(b * s + h), 3)
    q = jax.random.normal(ks[0], (b, h, dk), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, dk), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, dk), dtype)
    length = jnp.int32(s - s // 4)
    out = decode_attention(q, k, v, length, block_s=blk,
                           interpret=True)
    want = ref.decode_attention_ref(q, k, v, length)
    np.testing.assert_allclose(
        out.astype(jnp.float32), want.astype(jnp.float32),
        atol=tol_for(dtype), rtol=tol_for(dtype))


@pytest.mark.parametrize("s,blk", [
    (300, 128),      # 300 % 128 != 0 -> pad to 384, 3 blocks
    (520, 512),      # just past one block -> pad to 1024
    (129, 64),       # one token over -> pad to 192
    (96, 128),       # shorter than a block -> single s-sized block
])
def test_decode_attention_odd_lengths_no_block_cliff(s, blk):
    """Regression: cache lengths off the block grid used to collapse
    the kernel to a single (s, head_dim) VMEM tile; they must instead
    pad to the next block multiple and still match the oracle."""
    ks = jax.random.split(jax.random.PRNGKey(s + blk), 3)
    q = jax.random.normal(ks[0], (2, 4, 64))
    k = jax.random.normal(ks[1], (2, s, 2, 64))
    v = jax.random.normal(ks[2], (2, s, 2, 64))
    length = jnp.int32(s - 7)
    out = decode_attention(q, k, v, length, block_s=blk,
                           interpret=True)
    want = ref.decode_attention_ref(q, k, v, length)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_decode_attention_odd_length_uses_multiple_blocks():
    """The padded path must genuinely tile: with s > block_s and
    s % block_s != 0 the grid sees ceil(s/block) blocks, not one
    s-sized block (the VMEM-cliff shape)."""
    from unittest import mock
    import repro.kernels.decode_attention as da
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 4, 64))
    k = jax.random.normal(ks[1], (1, 300, 2, 64))
    v = jax.random.normal(ks[2], (1, 300, 2, 64))
    grids = []
    real_call = da.pl.pallas_call

    def spy(kernel, *a, grid=None, **kw):
        grids.append(grid)
        return real_call(kernel, *a, grid=grid, **kw)

    with mock.patch.object(da.pl, "pallas_call", side_effect=spy):
        da.decode_attention.__wrapped__(q, k, v, jnp.int32(250),
                                        block_s=128, interpret=True)
    assert grids and grids[0][2] == 3      # 300 -> 384 = 3 x 128


def test_decode_attention_respects_length():
    """Entries past `length` must not affect the output."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (1, 4, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    out1 = decode_attention(q, k, v, jnp.int32(100), block_s=128,
                            interpret=True)
    k2 = k.at[:, 100:].set(jax.random.normal(ks[3], (1, 156, 2, 64)))
    out2 = decode_attention(q, k2, v, jnp.int32(100), block_s=128,
                            interpret=True)
    np.testing.assert_allclose(out1, out2, atol=1e-6)


# ----------------------------------------------------------------------
# selective scan
# ----------------------------------------------------------------------
@pytest.mark.parametrize("b,s,d,n,bd,ck", [
    (1, 128, 64, 8, 64, 64),
    (2, 256, 128, 16, 64, 128),
    (2, 512, 256, 16, 256, 256),
    (1, 96, 48, 4, 48, 96),       # non-divisible fallbacks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_selective_scan_sweep(b, s, d, n, bd, ck, dtype):
    ks = jax.random.split(jax.random.PRNGKey(s + d), 5)
    x = (jax.random.normal(ks[0], (b, s, d)) * 0.5).astype(dtype)
    dt = (jax.nn.softplus(jax.random.normal(ks[1], (b, s, d)))
          * 0.1).astype(dtype)
    a_log = jax.random.normal(ks[2], (d, n)) * 0.3
    b_in = jax.random.normal(ks[3], (b, s, n)).astype(dtype)
    c_in = jax.random.normal(ks[4], (b, s, n)).astype(dtype)
    y, h = selective_scan(x, dt, a_log, b_in, c_in, block_d=bd,
                          chunk=ck, interpret=True)
    yr, hr = ref.selective_scan_ref(x, dt, a_log, b_in, c_in)
    np.testing.assert_allclose(y.astype(jnp.float32),
                               yr.astype(jnp.float32),
                               atol=tol_for(dtype) * 5,
                               rtol=tol_for(dtype) * 5)
    np.testing.assert_allclose(h, hr, atol=1e-4, rtol=1e-3)


# ----------------------------------------------------------------------
# rglru scan
# ----------------------------------------------------------------------
@pytest.mark.parametrize("b,s,w,bw,ck", [
    (1, 128, 128, 128, 128),
    (2, 256, 256, 128, 128),
    (2, 384, 96, 96, 192),
])
def test_rglru_scan_sweep(b, s, w, bw, ck):
    ks = jax.random.split(jax.random.PRNGKey(s + w), 2)
    a = jax.random.uniform(ks[0], (b, s, w), minval=0.7, maxval=0.999)
    u = jax.random.normal(ks[1], (b, s, w)) * 0.1
    hs, hf = rglru_scan(a, u, block_w=bw, chunk=ck, interpret=True)
    hsr, hfr = ref.rglru_scan_ref(a, u)
    np.testing.assert_allclose(hs, hsr, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(hf, hfr, atol=1e-5, rtol=1e-5)


# ----------------------------------------------------------------------
# fused swiglu
# ----------------------------------------------------------------------
@pytest.mark.parametrize("t,d,f,bt,bf", [
    (128, 64, 256, 64, 128),
    (256, 128, 512, 128, 256),
    (64, 96, 192, 64, 192),
    (100, 64, 250, 100, 250),     # non-divisible fallbacks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_swiglu_sweep(t, d, f, bt, bf, dtype):
    ks = jax.random.split(jax.random.PRNGKey(t + f), 4)
    x = (jax.random.normal(ks[0], (t, d)) * 0.5).astype(dtype)
    wg = (jax.random.normal(ks[1], (d, f)) * 0.05).astype(dtype)
    wu = (jax.random.normal(ks[2], (d, f)) * 0.05).astype(dtype)
    wd = (jax.random.normal(ks[3], (f, d)) * 0.05).astype(dtype)
    out = fused_swiglu(x, wg, wu, wd, block_t=bt, block_f=bf,
                       interpret=True)
    want = ref.fused_swiglu_ref(x, wg, wu, wd)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32),
                               atol=tol_for(dtype),
                               rtol=tol_for(dtype) * 10)


# ----------------------------------------------------------------------
# int8 quant decode (dense cache)
# ----------------------------------------------------------------------
def _quantized(key, shape):
    from repro.models.attention import quantize_kv
    x = jax.random.normal(key, shape)
    return quantize_kv(x)


@pytest.mark.parametrize("b,h,kv,dk,s,blk", [
    (1, 4, 4, 64, 256, 128),      # MHA
    (2, 8, 2, 128, 512, 128),     # GQA
    (2, 8, 1, 64, 512, 256),      # MQA
    (3, 6, 3, 32, 384, 384),      # non-divisible block -> full
])
def test_decode_attention_quant_sweep(b, h, kv, dk, s, blk):
    from repro.kernels.decode_attention_quant import (
        decode_attention_quant as kernel)
    from repro.models.attention import decode_attention_quant as oracle
    ks = jax.random.split(jax.random.PRNGKey(b * s + h), 3)
    q = jax.random.normal(ks[0], (b, h, dk))
    kq, kscale = _quantized(ks[1], (b, s, kv, dk))
    vq, vscale = _quantized(ks[2], (b, s, kv, dk))
    length = jnp.int32(s - s // 4)
    out = kernel(q, kq, kscale, vq, vscale, length, block_s=blk,
                 interpret=True)
    want = oracle(q, kq, kscale, vq, vscale, jnp.arange(s),
                  length - 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_decode_attention_quant_respects_length():
    """Stale int8 codes and scales past `length` (recycled cache rows)
    must not affect the output — the kernel masks by position, not by
    page contents."""
    from repro.kernels.decode_attention_quant import (
        decode_attention_quant as kernel)
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    q = jax.random.normal(ks[0], (1, 4, 64))
    kq, kscale = _quantized(ks[1], (1, 256, 2, 64))
    vq, vscale = _quantized(ks[2], (1, 256, 2, 64))
    out1 = kernel(q, kq, kscale, vq, vscale, jnp.int32(100),
                  block_s=128, interpret=True)
    kq2 = kq.at[:, 100:].set(127)
    ks2 = kscale.at[:, 100:].set(1e6)
    out2 = kernel(q, kq2, ks2, vq, vscale, jnp.int32(100),
                  block_s=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


# ----------------------------------------------------------------------
# int8 quant decode (paged cache)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("b,h,kv,dk,ps,nb", [
    (1, 4, 4, 64, 16, 4),         # MHA
    (2, 8, 2, 128, 8, 6),         # GQA
    (2, 8, 1, 64, 32, 3),         # MQA
    (3, 6, 3, 32, 8, 5),
])
def test_paged_decode_attention_quant_sweep(b, h, kv, dk, ps, nb):
    from repro.kernels.paged_decode_attention_quant import (
        paged_decode_attention_quant as kernel)
    ks = jax.random.split(jax.random.PRNGKey(b * ps + nb), 3)
    n_pages = b * nb + 2
    q = jax.random.normal(ks[0], (b, h, dk))
    kq, kscale = _quantized(ks[1], (n_pages, kv, ps, dk))
    vq, vscale = _quantized(ks[2], (n_pages, kv, ps, dk))
    table = jnp.arange(b * nb, dtype=jnp.int32).reshape(b, nb)
    lengths = jnp.int32(nb * ps) - jnp.arange(b, dtype=jnp.int32) * 5 \
        - 1
    out = kernel(q, kq, kscale, vq, vscale, table, lengths,
                 interpret=True)
    want = ref.paged_decode_attention_quant_ref(
        q, kq, kscale, vq, vscale, table, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_paged_decode_attention_quant_stale_pages_masked():
    """Bytes past each row's length — including whole recycled pages
    the block table still references — must not affect the output."""
    from repro.kernels.paged_decode_attention_quant import (
        paged_decode_attention_quant as kernel)
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    b, h, kv, dk, ps, nb = 2, 4, 2, 64, 8, 4
    q = jax.random.normal(ks[0], (b, h, dk))
    kq, kscale = _quantized(ks[1], (b * nb, kv, ps, dk))
    vq, vscale = _quantized(ks[2], (b * nb, kv, ps, dk))
    table = jnp.arange(b * nb, dtype=jnp.int32).reshape(b, nb)
    lengths = jnp.array([ps + 3, 2 * ps], jnp.int32)   # rows mid-page
    out1 = kernel(q, kq, kscale, vq, vscale, table, lengths,
                  interpret=True)
    # poison everything past each row's valid prefix
    stale = jnp.concatenate([table[0, 2:], table[1, 2:]])
    poison_k = kq.at[stale].set(127)
    poison_s = kscale.at[stale].set(1e6)
    poison_k = poison_k.at[table[0, 1], :, 3:].set(-127)
    poison_s = poison_s.at[table[0, 1], :, 3:].set(1e6)
    out2 = kernel(q, poison_k, poison_s, vq, vscale, table, lengths,
                  interpret=True)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


# ----------------------------------------------------------------------
# ops dispatch falls back to refs off-TPU
# ----------------------------------------------------------------------
def test_ops_dispatch_cpu_fallback():
    assert jax.default_backend() != "tpu"
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 4, 64))
    k = jax.random.normal(ks[1], (1, 128, 2, 64))
    v = jax.random.normal(ks[2], (1, 128, 2, 64))
    out = ops.decode_attention(q, k, v, jnp.int32(64))
    want = ref.decode_attention_ref(q, k, v, jnp.int32(64))
    np.testing.assert_allclose(out, want, atol=1e-6)


def test_ops_quant_dispatch_cpu_is_bitwise_oracle():
    """The serving determinism contract: off-TPU, the quant ops
    dispatch to the jnp oracles bit-for-bit (the Pallas kernels are
    the TPU deployment path; CPU must be *identical* to the reference
    the bit-equivalence tests are built on)."""
    assert jax.default_backend() != "tpu"
    from repro.models.attention import decode_attention_quant as oracle
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    b, h, kv, dk, s = 2, 8, 2, 64, 128
    q = jax.random.normal(ks[0], (b, h, dk))
    kq, kscale = _quantized(ks[1], (b, s, kv, dk))
    vq, vscale = _quantized(ks[2], (b, s, kv, dk))
    out = ops.decode_attention_quant(q, kq, kscale, vq, vscale,
                                     jnp.int32(100))
    want = oracle(q, kq, kscale, vq, vscale, jnp.arange(s),
                  jnp.int32(99))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))

    ps, nb = 8, 4
    kpq, kps_ = _quantized(ks[1], (b * nb, kv, ps, dk))
    vpq, vps_ = _quantized(ks[2], (b * nb, kv, ps, dk))
    table = jnp.arange(b * nb, dtype=jnp.int32).reshape(b, nb)
    lengths = jnp.array([ps + 3, 2 * ps], jnp.int32)
    pout = ops.paged_decode_attention_quant(
        q, kpq, kps_, vpq, vps_, table, lengths)
    pwant = ref.paged_decode_attention_quant_ref(
        q, kpq, kps_, vpq, vps_, table, lengths)
    np.testing.assert_array_equal(np.asarray(pout), np.asarray(pwant))
