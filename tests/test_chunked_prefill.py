"""Chunk-boundary prefill: chunked composition must be bit-identical
to one-shot ``prefill_paged``.

The step-level serving loop streams long prompts through the paged KV
pool in fixed-size chunks (``sampler.prefill_chunk_paged``). The
bit-equivalence contract (see ``models.transformer.prefill_chunk_paged``)
is that for ANY chunk schedule — size 1, a ragged size straddling page
boundaries, exactly one page, or the whole prompt at once — the
written KV pages and the final-position logits match the one-shot
paged prefill bit for bit, even when the pages start out holding stale
garbage. Property tests sweep prompt lengths and chunk sizes through
``tests/_propshim.py`` (hypothesis when available).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:                                   # pragma: no cover
    from _propshim import given, settings
    from _propshim import strategies as st

from repro.configs.registry import get_config
from repro.data import tokenizer as tok
from repro.models import params as params_lib
from repro.models import transformer as T
from repro.sampling import prefill_chunk_paged

# JIT/compile-heavy: excluded from the fast inner loop (-m 'not slow')
pytestmark = pytest.mark.slow

PAGE = 8


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("smollm-135m", reduced=True).replace(
        vocab_size=tok.VOCAB_SIZE, dtype="float32",
        tie_embeddings=True)
    prm = params_lib.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, prm


def _paged_setup(cfg, batch: int, prompt_len: int, garbage_seed=None):
    """Pages + per-row block tables; optionally garbage-initialised
    (recycled pages must not leak into chunked prefill output)."""
    nbp = -(-prompt_len // PAGE)
    n_pages = batch * nbp + 2
    shape = (cfg.num_layers, n_pages, cfg.num_kv_heads, PAGE,
             cfg.resolved_head_dim)
    if garbage_seed is None:
        k = jnp.zeros(shape, jnp.float32)
        v = jnp.zeros(shape, jnp.float32)
    else:
        rng = np.random.default_rng(garbage_seed)
        k = jnp.asarray(rng.normal(size=shape), jnp.float32)
        v = jnp.asarray(rng.normal(size=shape), jnp.float32)
    table = np.arange(batch * nbp, dtype=np.int32).reshape(batch, nbp)
    return k, v, table, nbp


def _prompts(batch: int, length: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    ids = rng.integers(3, tok.VOCAB_SIZE,
                       size=(batch, length)).astype(np.int32)
    ids[:, 0] = tok.BOS
    return ids


def _oneshot(cfg, prm, ids, table):
    k, v, _, _ = _paged_setup(cfg, ids.shape[0], ids.shape[1])
    fn = jax.jit(T.prefill_paged,
                 static_argnames=("cfg", "cache_len"))
    lg, pages = fn(cfg, prm, jnp.asarray(ids), {"k": k, "v": v},
                   jnp.asarray(table))
    return (np.asarray(lg), np.asarray(pages["k"]),
            np.asarray(pages["v"]))


def _chunked(cfg, prm, ids, table, chunk: int, garbage_seed=1):
    b, s = ids.shape
    k, v, _, _ = _paged_setup(cfg, b, s, garbage_seed=garbage_seed)
    pages = {"k": k, "v": v}
    logits = np.zeros((b, cfg.vocab_size), np.float32)
    start = 0
    while start < s:
        c = min(chunk, s - start)
        starts = jnp.full((b,), start, jnp.int32)
        lg, pages = prefill_chunk_paged(
            cfg, prm, jnp.asarray(ids[:, start:start + c]), pages,
            jnp.asarray(table), starts, prompt_len=s)
        start += c
    logits[:] = np.asarray(lg)
    return (logits, np.asarray(pages["k"]), np.asarray(pages["v"]))


def _written_kv(pages, table, prompt_len, cfg):
    """The prompt-covering slots (the tail page's dead slots past the
    prompt are never read — decode overwrites them position by
    position before attending)."""
    gathered = pages[:, table]          # (L, B, NBp, KV, PAGE, Dh)
    layers, b = gathered.shape[0], gathered.shape[1]
    return gathered.swapaxes(3, 4).reshape(
        layers, b, -1, cfg.num_kv_heads,
        cfg.resolved_head_dim)[:, :, :prompt_len]


@pytest.mark.parametrize("prompt_len", [9, 16, 23])
@pytest.mark.parametrize("chunk", [1, 7, PAGE])
def test_chunk_sizes_bit_identical(tiny_model, prompt_len, chunk):
    """Chunk sizes {1, 7, page_size} across page-aligned and
    straddling prompt lengths: pages and logits match one-shot."""
    cfg, prm = tiny_model
    ids = _prompts(3, prompt_len)
    table = _paged_setup(cfg, 3, prompt_len)[2]
    lg1, k1, _ = _oneshot(cfg, prm, ids, table)
    lg2, k2, _ = _chunked(cfg, prm, ids, table, chunk)
    np.testing.assert_array_equal(lg1, lg2)
    np.testing.assert_array_equal(
        _written_kv(k1, table, prompt_len, cfg),
        _written_kv(k2, table, prompt_len, cfg))


def test_whole_prompt_chunk_bit_identical(tiny_model):
    """chunk == L: one chunked call is the one-shot prefill."""
    cfg, prm = tiny_model
    ids = _prompts(2, 21)
    table = _paged_setup(cfg, 2, 21)[2]
    lg1, k1, _ = _oneshot(cfg, prm, ids, table)
    lg2, k2, _ = _chunked(cfg, prm, ids, table, chunk=21)
    np.testing.assert_array_equal(lg1, lg2)
    np.testing.assert_array_equal(
        _written_kv(k1, table, 21, cfg),
        _written_kv(k2, table, 21, cfg))


@settings(max_examples=6)
@given(st.integers(min_value=9, max_value=33),
       st.integers(min_value=1, max_value=11),
       st.integers(min_value=0, max_value=1 << 20))
def test_chunked_prefill_property(prompt_len, chunk, seed):
    """Any (prompt length, chunk size) pair composes bit-identically,
    from garbage-initialised pages."""
    cfg = get_config("smollm-135m", reduced=True).replace(
        vocab_size=tok.VOCAB_SIZE, dtype="float32",
        tie_embeddings=True)
    prm = params_lib.init_params(cfg, jax.random.PRNGKey(0))
    ids = _prompts(2, prompt_len, seed=seed % 1000)
    table = _paged_setup(cfg, 2, prompt_len)[2]
    lg1, k1, _ = _oneshot(cfg, prm, ids, table)
    lg2, k2, _ = _chunked(cfg, prm, ids, table, chunk,
                          garbage_seed=seed % 997)
    np.testing.assert_array_equal(lg1, lg2)
    np.testing.assert_array_equal(
        _written_kv(k1, table, prompt_len, cfg),
        _written_kv(k2, table, prompt_len, cfg))


def test_mixed_depth_rows_share_one_program(tiny_model):
    """Rows at different prefill depths batched into one call (traced
    per-row starts) produce the same bits as rows advanced alone."""
    cfg, prm = tiny_model
    s, c = 16, 4
    ids = _prompts(2, s)
    k, v, table, _ = _paged_setup(cfg, 2, s, garbage_seed=3)
    # row 0 advances alone to depth 4; then both rows step together,
    # row 1 lagging row 0 by one chunk
    lg = None
    pos = np.array([0, 0], np.int32)
    pages = {"k": k, "v": v}
    _, pages = prefill_chunk_paged(
        cfg, prm, jnp.asarray(ids[:1, 0:c]), pages,
        jnp.asarray(table[:1]), jnp.asarray([0], jnp.int32),
        prompt_len=s)
    pos[0] = c
    while pos.min() < s:
        rows = [r for r in range(2) if pos[r] < s]
        toks = np.stack([ids[r, pos[r]:pos[r] + c] for r in rows])
        lg, pages = prefill_chunk_paged(
            cfg, prm, jnp.asarray(toks), pages,
            jnp.asarray(table[rows]),
            jnp.asarray(pos[rows], jnp.int32), prompt_len=s)
        for r in rows:
            pos[r] += c
    lg1, k1, _ = _oneshot(cfg, prm, ids, table)
    np.testing.assert_array_equal(
        _written_kv(k1, table, s, cfg),
        _written_kv(np.asarray(pages["k"]), table, s, cfg))


def test_chunk_kernel_matches_oracle():
    """The Pallas chunked-prefill kernel (interpret mode) matches the
    jnp oracle on mixed-depth rows."""
    from repro.kernels.chunked_prefill_attention import (
        chunked_prefill_attention)
    from repro.kernels.ref import chunked_prefill_attention_ref
    rng = np.random.default_rng(0)
    b, c, h, kv, dk, ps, nb = 2, 4, 4, 2, 16, 8, 3
    prompt_len = 21
    q = jnp.asarray(rng.normal(size=(b, c, h, dk)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(b * nb + 1, kv, ps, dk)),
                     jnp.float32)
    vp = jnp.asarray(rng.normal(size=(b * nb + 1, kv, ps, dk)),
                     jnp.float32)
    table = jnp.asarray(
        np.arange(b * nb, dtype=np.int32).reshape(b, nb))
    qpos = jnp.asarray(np.stack([np.arange(4, 8), np.arange(12, 16)])
                       .astype(np.int32))
    want = chunked_prefill_attention_ref(q, kp, vp, table, qpos,
                                         prompt_len=prompt_len)
    got = chunked_prefill_attention(q, kp, vp, table, qpos,
                                    prompt_len=prompt_len,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
