"""Heterogeneous page layouts (property tests).

Three layout-specific contracts the stepped engine rests on:

* **ring pages** — a sliding-window member's window-capped ring pages
  must emit logits bit-identical to the dense SWA reference (the
  ``ring_compress``'d contiguous cache) at every prompt length, in
  particular every offset where the ring's write pointer straddles a
  page boundary or wraps;
* **recurrent-state lanes** — lane alloc/fork/retire over an SSM
  member's O(1) state must leak nothing: forked lanes are private
  (refcount 1, pairwise distinct), and full retirement returns the
  pool to its scratch-only footprint;
* **quant pages** — int8 code + scale-plane pages round-trip
  bit-for-bit against the dense quant cache: same codes, same scales,
  same logits at prefill and at every decode step.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:                                    # pragma: no cover
    from _propshim import given, settings
    from _propshim import strategies as st

from repro.configs.registry import get_config
from repro.models import params as params_lib
from repro.models import transformer as T
from repro.serving.kv_pool import PagedKVServer, pages_for

# JIT/compile-heavy: excluded from the fast inner loop (-m 'not slow')
pytestmark = pytest.mark.slow

PAGE = 4
WINDOW = 8
MAX_NEW = 3

# property bodies cannot take pytest fixtures (propshim generates
# zero-arg wrappers), so models build lazily into a module cache
_MODELS = {}


def _model(kind):
    if kind not in _MODELS:
        if kind == "mamba":
            cfg = get_config("falcon-mamba-7b", reduced=True).replace(
                dtype="float32")
        else:
            cfg = get_config("smollm-135m", reduced=True).replace(
                dtype="float32", tie_embeddings=True)
            if kind == "ring":
                cfg = cfg.replace(window=WINDOW)
            elif kind == "quant":
                cfg = cfg.replace(kv_quant=True)
        prm = params_lib.init_params(cfg, jax.random.PRNGKey(7))
        _MODELS[kind] = (cfg, prm)
    return _MODELS[kind]


def _paged_row(cfg, s, m):
    """A server plus one row's full-width (prefill+decode) block
    table, allocated exactly as the step loop would."""
    srv = PagedKVServer(cfg, page_size=PAGE, prefix_cache_entries=0)
    srv.ensure_capacity_stream(2, s, 1, m)
    g = srv.row_geometry(s, m)
    table = np.asarray(srv._alloc_retry(g.nb), np.int32)
    return srv, g, table


# ----------------------------------------------------------------------
# ring pages vs dense sliding-window reference
# ----------------------------------------------------------------------
@settings(max_examples=14, deadline=None)
@given(st.integers(min_value=WINDOW - 2,
                   max_value=WINDOW + 2 * PAGE + 1))
def test_ring_wraparound_bit_equals_dense_swa(s):
    """Sweep prompt lengths across the window edge: every page-offset
    phase (s mod page), prompts shorter than the ring, exactly the
    ring, and long enough that prefill itself wraps — the paged ring
    must match the dense SWA cache bit-for-bit through prefill and
    every decode step."""
    cfg, prm = _model("ring")
    m = MAX_NEW
    ids = jax.random.randint(jax.random.PRNGKey(100 + s), (1, s), 0,
                             cfg.vocab_size)
    lg_d, cache = T.prefill(cfg, prm, ids, cache_len=s + m)

    srv, g, table = _paged_row(cfg, s, m)
    # the ring caps the row's pages at ceil(window/page), regardless
    # of prompt length
    assert g.nb == g.nbp == pages_for(min(s + m, WINDOW), PAGE)
    lg_p, pages = T.prefill_paged(cfg, prm, ids, srv.pages,
                                  jnp.asarray(table[None, :g.nbp]),
                                  cache_len=s + m)
    np.testing.assert_array_equal(np.asarray(lg_d), np.asarray(lg_p))

    tok = jnp.argmax(lg_d, axis=-1).astype(jnp.int32)
    bt = jnp.asarray(table[None])
    for i in range(m - 1):
        pos = jnp.int32(s + i)
        lg_d, cache = T.decode_step(cfg, prm, cache, tok, pos)
        lg_p, pages = T.decode_step_paged(cfg, prm, pages, bt, tok,
                                          pos, cache_len=s + m)
        np.testing.assert_array_equal(np.asarray(lg_d),
                                      np.asarray(lg_p))
        tok = jnp.argmax(lg_d, axis=-1).astype(jnp.int32)


# ----------------------------------------------------------------------
# recurrent-state lanes: fork/retire accounting
# ----------------------------------------------------------------------
@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=1, max_value=6),
       st.integers(min_value=1, max_value=4),
       st.integers(min_value=1, max_value=40))
def test_lane_fork_retire_leaks_no_lanes(rows, n_samples, prompt_len):
    """Rows of SSM state: one prefill lane each, forked across
    n_samples probe lanes. Lane geometry is O(1) in prompt length,
    forked lanes are private (no sharing — the whole state is
    writable), and retiring everything returns the pool to its
    scratch-only footprint."""
    cfg, _ = _model("mamba")
    srv = PagedKVServer(cfg, page_size=PAGE, prefix_cache_entries=0)
    srv.ensure_capacity_stream(rows, prompt_len, n_samples, MAX_NEW)
    g = srv.row_geometry(prompt_len, MAX_NEW)
    assert (g.n_shared, g.tail_tokens, g.nbp, g.nb, g.n_tail) \
        == (0, 0, 1, 1, 1)
    base = srv.pool.pages_in_use
    assert base == srv._scratch.size

    held = []
    for _ in range(rows):
        snap = srv._alloc_retry(g.nbp)            # prefill lane
        forks = srv._alloc_retry(n_samples * g.n_tail)
        ids = np.concatenate([snap, forks])
        # every lane private: pairwise distinct, refcount exactly 1
        assert len(set(ids.tolist())) == ids.size
        for p in ids:
            assert srv.pool.refcount(int(p)) == 1
        held.append((snap, forks))
    assert srv.pool.pages_in_use == base + rows * (1 + n_samples)

    for snap, forks in held:
        srv.pool.release(forks)
        srv.pool.release(snap)
    assert srv.pool.pages_in_use == srv._scratch.size
    assert srv.pool.highwater <= srv.pool.num_pages


def test_lane_fork_copies_state_not_aliases():
    """fork_pages on the lane pytree copies the source row's conv+SSM
    state into the destination lane; mutating the fork afterwards must
    not write through to the source."""
    from repro.sampling import fork_pages
    cfg, _ = _model("mamba")
    srv = PagedKVServer(cfg, page_size=PAGE, prefix_cache_entries=0)
    srv.ensure_capacity_stream(2, 8, 2, MAX_NEW)
    pages = jax.tree.map(
        lambda a: jax.random.normal(
            jax.random.PRNGKey(a.ndim), a.shape).astype(a.dtype),
        srv.pages)
    src, dst = jnp.asarray([0]), jnp.asarray([1])
    forked = fork_pages(pages, src, dst)
    for leaf_name in ("conv", "h"):
        np.testing.assert_array_equal(
            np.asarray(forked[leaf_name][:, 1]),
            np.asarray(forked[leaf_name][:, 0]))
    poked = jax.tree.map(lambda a: a.at[:, 1].add(1.0), forked)
    for leaf_name in ("conv", "h"):
        np.testing.assert_array_equal(
            np.asarray(poked[leaf_name][:, 0]),
            np.asarray(forked[leaf_name][:, 0]))


# ----------------------------------------------------------------------
# quant pages vs the dense quant cache
# ----------------------------------------------------------------------
@settings(max_examples=12, deadline=None)
@given(st.integers(min_value=3, max_value=3 * PAGE + 2))
def test_quant_pages_roundtrip_bit_equals_dense_quant(s):
    """int8 code pages and their f32 scale planes hold exactly the
    bytes the dense quant cache holds (same quantize_kv, page-packed),
    and prefill + decode logits match the dense quant path
    bit-for-bit."""
    cfg, prm = _model("quant")
    m = MAX_NEW
    ids = jax.random.randint(jax.random.PRNGKey(200 + s), (1, s), 0,
                             cfg.vocab_size)
    lg_d, cache = T.prefill(cfg, prm, ids, cache_len=s + m)
    assert cache["layers"]["k"].dtype == jnp.int8

    srv, g, table = _paged_row(cfg, s, m)
    lg_p, pages = T.prefill_paged(cfg, prm, ids, srv.pages,
                                  jnp.asarray(table[None, :g.nbp]))
    np.testing.assert_array_equal(np.asarray(lg_d), np.asarray(lg_p))
    assert pages["k"].dtype == jnp.int8
    assert pages["k_scale"].dtype == jnp.float32

    def _gathered(leaf):
        """Row view of the paged bytes over the prompt prefix."""
        rows = leaf[:, table[:g.nbp]].swapaxes(2, 3)
        flat = rows.reshape((leaf.shape[0], g.nbp * PAGE)
                            + rows.shape[3:])
        return np.asarray(flat[:, :s])

    for name in ("k", "v", "k_scale", "v_scale"):
        np.testing.assert_array_equal(
            _gathered(pages[name]),
            np.asarray(cache["layers"][name][:, 0, :s]), name)

    tok = jnp.argmax(lg_d, axis=-1).astype(jnp.int32)
    bt = jnp.asarray(table[None])
    for i in range(m - 1):
        pos = jnp.int32(s + i)
        lg_d, cache = T.decode_step(cfg, prm, cache, tok, pos)
        lg_p, pages = T.decode_step_paged(cfg, prm, pages, bt, tok,
                                          pos, cache_len=s + m)
        np.testing.assert_array_equal(np.asarray(lg_d),
                                      np.asarray(lg_p))
        # the decode write itself round-trips: codes + scales at pos
        # match the dense cache's slot
        pg, off = int(pos) // PAGE, int(pos) % PAGE
        for name in ("k", "v", "k_scale", "v_scale"):
            np.testing.assert_array_equal(
                np.asarray(pages[name][:, table[pg], :, off]),
                np.asarray(cache["layers"][name][:, 0, int(pos)]),
                name)
        tok = jnp.argmax(lg_d, axis=-1).astype(jnp.int32)


def test_quant_prefix_cache_hit_roundtrips_bitwise():
    """The cross-request prefix LRU serves quant layouts (int8 codes +
    scale planes are position-independent, so retained pages are
    directly reusable): a second probe wave over an identical prompt
    hits the cache instead of re-prefilling, the retained code/scale
    pages hold exactly the dense quant cache's bytes before and after
    the hit (COW keeps them immutable), and the hit wave decodes to
    the same tokens as the miss wave."""
    cfg, prm = _model("quant")
    s, m, n = 9, MAX_NEW, 2
    ids = np.asarray(jax.random.randint(
        jax.random.PRNGKey(300), (1, s), 0, cfg.vocab_size), np.int32)
    _, cache = T.prefill(cfg, prm, jnp.asarray(ids), cache_len=s + m)

    srv = PagedKVServer(cfg, page_size=PAGE, prefix_cache_entries=4)
    key = jax.random.PRNGKey(13)
    out1, h1 = srv.probe_wave(prm, ids, n, max_new_tokens=m,
                              temperature=0.0, key=key,
                              eos_id=-1, pad_id=0)
    h1.close()
    assert srv.stats.prefill_tokens_reused_prefix == 0
    entry = srv._prefix_lookup(ids[0].tobytes())
    assert entry is not None

    row_pages = list(entry.shared) + (
        [entry.tail] if entry.tail is not None else [])

    def _gathered(name):
        leaf = srv.pages[name]
        flat = np.asarray(leaf[:, np.asarray(row_pages)]).swapaxes(2, 3)
        flat = flat.reshape((leaf.shape[0], len(row_pages) * PAGE)
                            + flat.shape[3:])
        return flat[:, :s]

    names = ("k", "v", "k_scale", "v_scale")
    for name in names:
        np.testing.assert_array_equal(
            _gathered(name),
            np.asarray(cache["layers"][name][:, 0, :s]), name)
    snap = {name: _gathered(name).copy() for name in names}

    computed = srv.stats.prefill_tokens_computed
    out2, h2 = srv.probe_wave(prm, ids, n, max_new_tokens=m,
                              temperature=0.0, key=key,
                              eos_id=-1, pad_id=0)
    h2.close()
    assert srv.stats.prefill_tokens_computed == computed
    assert srv.stats.prefill_tokens_reused_prefix == s
    np.testing.assert_array_equal(out1.tokens, out2.tokens)
    np.testing.assert_array_equal(out1.logprobs, out2.logprobs)
    for name in names:
        np.testing.assert_array_equal(_gathered(name), snap[name],
                                      name)
