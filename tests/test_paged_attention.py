"""Paged (block-table) decode attention: Pallas kernel vs oracle, and
the oracle vs the dense decode-attention semantics it must preserve."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.paged_decode_attention import paged_decode_attention

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def tol_for(dtype):
    return TOL[jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32]


def _paged_case(key, b, h, kv, dk, ps, nb, n_pages, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, h, dk), dtype)
    k_pages = jax.random.normal(ks[1], (n_pages, kv, ps, dk), dtype)
    v_pages = jax.random.normal(ks[2], (n_pages, kv, ps, dk), dtype)
    # distinct pages per row: a permutation slice, like the pool yields
    rng = np.random.default_rng(b * nb + h)
    bt = jnp.asarray(rng.permutation(n_pages)[:b * nb].reshape(b, nb),
                     jnp.int32)
    return q, k_pages, v_pages, bt


def test_paged_ref_matches_dense_ref_rowwise():
    """Gathering a row's pages into a contiguous cache and running the
    dense oracle must equal the paged oracle — the semantics paging
    must not change."""
    q, kp, vp, bt = _paged_case(jax.random.PRNGKey(0), 3, 8, 2, 64,
                                8, 6, 32, jnp.float32)
    lengths = jnp.asarray([48, 17, 1], jnp.int32)
    got = ref.paged_decode_attention_ref(q, kp, vp, bt, lengths)
    kc = ref.gather_pages(kp, bt)
    vc = ref.gather_pages(vp, bt)
    for r in range(3):
        want = ref.decode_attention_ref(q[r:r + 1], kc[r:r + 1],
                                        vc[r:r + 1], lengths[r])
        np.testing.assert_allclose(np.asarray(got[r]),
                                   np.asarray(want[0]), atol=1e-6)


def test_paged_ref_ignores_unmapped_pages():
    """Positions past ``lengths`` — including whole trailing pages and
    stale data in recycled pages — must not affect the output."""
    q, kp, vp, bt = _paged_case(jax.random.PRNGKey(1), 2, 4, 1, 64,
                                8, 4, 16, jnp.float32)
    lengths = jnp.asarray([9, 25], jnp.int32)
    out1 = ref.paged_decode_attention_ref(q, kp, vp, bt, lengths)
    # scribble over every page position past each row's length
    kp2 = np.asarray(kp).copy()
    pos = np.arange(4 * 8)
    for r in range(2):
        for j, page in enumerate(np.asarray(bt)[r]):
            mask = pos[j * 8:(j + 1) * 8] >= int(lengths[r])
            kp2[page, :, mask] = 99.0
    out2 = ref.paged_decode_attention_ref(q, jnp.asarray(kp2), vp, bt,
                                          lengths)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               atol=1e-6)


def test_ops_dispatch_cpu_fallback():
    assert jax.default_backend() != "tpu"
    q, kp, vp, bt = _paged_case(jax.random.PRNGKey(2), 2, 4, 2, 64,
                                8, 3, 12, jnp.float32)
    lengths = jnp.asarray([20, 11], jnp.int32)
    out = ops.paged_decode_attention(q, kp, vp, bt, lengths)
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-6)


# ----------------------------------------------------------------------
# Pallas kernel (interpret mode) — JIT/compile-heavy: slow lane
# ----------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.parametrize("b,h,kv,dk,ps,nb", [
    (1, 4, 4, 64, 8, 4),        # MHA, serving-default page size
    (2, 8, 2, 128, 8, 6),       # GQA
    (2, 8, 1, 128, 16, 3),      # MQA, bigger pages
    (3, 6, 3, 32, 32, 2),       # few large pages
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_sweep(b, h, kv, dk, ps, nb, dtype):
    n_pages = 2 * b * nb
    q, kp, vp, bt = _paged_case(
        jax.random.PRNGKey(b * nb + kv), b, h, kv, dk, ps, nb,
        n_pages, dtype)
    rng = np.random.default_rng(7 * b + nb)
    lengths = jnp.asarray(
        rng.integers(1, nb * ps + 1, size=b), jnp.int32)
    out = paged_decode_attention(q, kp, vp, bt, lengths,
                                 interpret=True)
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=tol_for(dtype), rtol=tol_for(dtype))


@pytest.mark.slow
def test_paged_kernel_shared_prefix_rows():
    """Rows sharing prefix pages (the probe's N-sample layout) must
    each read the shared pages correctly."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    b, h, kv, dk, ps = 4, 4, 2, 64, 8
    q = jax.random.normal(ks[0], (b, h, dk))
    kp = jax.random.normal(ks[1], (16, kv, ps, dk))
    vp = jax.random.normal(ks[2], (16, kv, ps, dk))
    # all rows share pages [0, 1]; private third page per row
    bt = jnp.asarray([[0, 1, 2 + r] for r in range(b)], jnp.int32)
    lengths = jnp.asarray([17, 18, 19, 20], jnp.int32)
    out = paged_decode_attention(q, kp, vp, bt, lengths,
                                 interpret=True)
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
