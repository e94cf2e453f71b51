"""Device-resident megastep decode: K fused ticks must be a pure
performance knob.

Three layers of proof, mirroring the repo's equivalence style:

* **sampler-level property tests** (via ``tests/_propshim.py``): one
  ``decode_megastep_rows(n_ticks=K)`` launch emits the exact (K, B)
  emit/done stacks — and the exact next-token logits — that K
  sequential ``decode_step_rows`` launches with host round-trips
  produce, over random initial done bits, heterogeneous step offsets
  and a randomised EOS id so rows finish at every offset in [0, K);
* **engine-level stream equality**: ``run_stepped`` with megastep K
  in {4, 16} emits identical per-task outputs to K=1, with identical
  ``KVStats`` page high-water (all-twin ensemble: every route
  releases its sample tails before member tails allocate, so pool
  usage never exceeds the probe plateau on either path) and leak-free
  mid-megastep retirement page hygiene;
* **transfer-counter hook**: host<->device transfers per emitted
  token drop K-fold at megastep K (the per-tick logits round-trip is
  gone; only (K, B) token ids + done bits cross per megastep).
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies
except ImportError:                                # pragma: no cover
    from _propshim import given, settings, strategies

from repro.data.tasks import Task


# ----------------------------------------------------------------------
# sampler-level fixtures: a real tiny paged model + raw page state
# ----------------------------------------------------------------------
_MODELS = {}


def _tiny_model(dtype="float32"):
    import jax
    from repro.configs.registry import get_config
    from repro.data import tokenizer as tok
    from repro.models import params as params_lib
    if dtype not in _MODELS:
        cfg = get_config("smollm-135m", reduced=True).replace(
            vocab_size=tok.VOCAB_SIZE, dtype=dtype,
            tie_embeddings=True)
        prm = params_lib.init_params(cfg, jax.random.PRNGKey(0))
        _MODELS[dtype] = (cfg, prm)
    return _MODELS[dtype]


def _page_state(cfg, b, cache_len, page_size=8):
    """Zeroed paged KV plus disjoint per-row block tables."""
    from repro.serving.kv_pool import PagedKVServer, pages_for
    nb = pages_for(cache_len, page_size)
    srv = PagedKVServer(cfg, page_size=page_size,
                        prefix_cache_entries=0)
    srv.ensure_capacity_stream(b, page_size, 1, cache_len)
    tables = np.stack([srv.pool.alloc(nb) for _ in range(b)])
    return srv, tables


def _row_keys(b):
    import jax
    from repro.sampling import sampler as S
    base = jax.random.PRNGKey(0)
    return np.stack([np.asarray(S.probe_row_keys(base, [a], 1))[0]
                     for a in range(b)])


def _run_both(cfg, prm, k_ticks, b, seed, eos_id, dtype=np.float32,
              temperature=1.0, p_done=0.25):
    """One megastep vs K sequential per-tick launches from identical
    state; returns the two (emits, dones, next_logits) triples, after
    checking that both left bit-identical K and V pools."""
    import jax.numpy as jnp
    from repro.data import tokenizer as tok
    from repro.sampling import sampler as S

    rng = np.random.default_rng(seed)
    steps0 = rng.integers(0, 4, b).astype(np.int32)
    done0 = rng.random(b) < p_done
    cache_len = 4 + k_ticks                 # no pos overflow baseline
    srv_a, tables = _page_state(cfg, b, cache_len)
    srv_b, _ = _page_state(cfg, b, cache_len)
    logits0 = jnp.asarray(
        rng.standard_normal((b, tok.VOCAB_SIZE)).astype(dtype))
    keys = _row_keys(b)
    pos0 = steps0.copy()                    # empty prompt: pos == steps

    common = dict(cache_len=cache_len, temperature=temperature,
                  eos_id=eos_id, pad_id=tok.PAD)
    emits_m, dones_m, lg_m, pages_m = S.decode_megastep_rows(
        cfg, prm, logits0, srv_a.pages,
        jnp.asarray(tables), jnp.asarray(pos0), jnp.asarray(keys),
        jnp.asarray(steps0), jnp.asarray(done0), n_ticks=k_ticks,
        **common)

    lg, pages = logits0, srv_b.pages
    done = jnp.asarray(done0)
    emits_s, dones_s = [], []
    for t in range(k_ticks):
        (emit, _lp, _lv, done, lg, pages) = S.decode_step_rows(
            cfg, prm, lg, pages, jnp.asarray(tables),
            jnp.asarray(pos0 + t), jnp.asarray(keys),
            jnp.asarray(steps0 + t), done, **common)
        emits_s.append(np.asarray(emit))
        dones_s.append(np.asarray(done))
    for leaf in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(pages_m[leaf]),
                                      np.asarray(pages[leaf]))
    return ((np.asarray(emits_m), np.asarray(dones_m),
             np.asarray(lg_m)),
            (np.stack(emits_s), np.stack(dones_s), np.asarray(lg)))


# deadline=None: the first example compiles both programs, which takes
# seconds on the CPU and is no part of what the property checks
@settings(max_examples=12, deadline=None)
@given(strategies.sampled_from([1, 4, 16]),
       strategies.integers(min_value=0, max_value=10_000),
       strategies.integers(min_value=3, max_value=18))
def test_megastep_bit_equals_sequential_ticks(k_ticks, seed, eos_id):
    """The fused scan and K host-driven per-tick launches emit the
    exact same token/done stacks and end with the exact same pending
    logits and page pools — rows entering done, finishing
    mid-megastep at random offsets (random EOS id), and heterogeneous
    step offsets included."""
    cfg, prm = _tiny_model()
    (em, dm, lm), (es, ds, ls) = _run_both(
        cfg, prm, k_ticks, b=4, seed=seed, eos_id=eos_id)
    np.testing.assert_array_equal(em, es)
    np.testing.assert_array_equal(dm, ds)
    np.testing.assert_array_equal(lm, ls)


def test_megastep_rows_finish_at_every_offset():
    """Coverage guarantee for the property above: across a seeded
    sweep, rows are observed finishing (done flipping) at *every*
    offset in [0, K) of a K=4 megastep — and every example is
    bit-equivalent."""
    cfg, prm = _tiny_model()
    k_ticks = 4
    offsets_seen = set()
    for seed in range(64):
        (em, dm, _), (es, ds, _) = _run_both(
            cfg, prm, k_ticks, b=4, seed=1_000 + seed,
            eos_id=3 + (seed % 12), p_done=0.0)
        np.testing.assert_array_equal(em, es)
        np.testing.assert_array_equal(dm, ds)
        # every row starts live, so a True in dones[t] with False in
        # dones[t-1] is exactly an EOS at megastep offset t
        flipped = dm & ~np.concatenate(
            [np.zeros((1, dm.shape[1]), bool), dm[:-1]])
        for t in range(k_ticks):
            if flipped[t].any():
                offsets_seen.add(t)
        if offsets_seen == set(range(k_ticks)):
            break
    assert offsets_seen == set(range(k_ticks)), \
        f"EOS offsets covered: {sorted(offsets_seen)}"


def test_megastep_preserves_bf16_lane_dtype():
    """Mixed-dtype satellite: under a bf16 model the lane state stays
    bf16 end-to-end (the old per-tick host pull silently widened to
    float32) and the megastep still bit-equals the per-tick path."""
    import jax.numpy as jnp
    cfg, prm = _tiny_model("bfloat16")
    (em, dm, lm), (es, ds, ls) = _run_both(
        cfg, prm, 4, b=2, seed=7, eos_id=6, dtype=jnp.bfloat16)
    assert lm.dtype == jnp.bfloat16
    assert ls.dtype == jnp.bfloat16
    np.testing.assert_array_equal(em, es)
    np.testing.assert_array_equal(dm, ds)
    np.testing.assert_array_equal(
        lm.astype(np.float32), ls.astype(np.float32))


def test_megastep_donates_the_pool():
    """The pool handed to a megastep is written in place and given
    back: the caller's reference to it is dead after the call, and the
    pool returned holds the K/V of the positions the launch wrote."""
    import jax.numpy as jnp
    from repro.data import tokenizer as tok
    from repro.sampling import sampler as S
    cfg, prm = _tiny_model()
    b, cache_len = 2, 8
    srv, tables = _page_state(cfg, b, cache_len)
    pages = srv.pages
    logits0 = jnp.zeros((b, tok.VOCAB_SIZE), jnp.float32)
    _, _, _, out = S.decode_megastep_rows(
        cfg, prm, logits0, pages, jnp.asarray(tables),
        jnp.zeros(b, jnp.int32), jnp.asarray(_row_keys(b)),
        jnp.zeros(b, jnp.int32), jnp.zeros(b, bool), n_ticks=2,
        cache_len=cache_len, temperature=0.0, eos_id=-1, pad_id=tok.PAD)
    assert pages["k"].is_deleted() and pages["v"].is_deleted()
    k = np.asarray(out["k"])
    for row in range(b):
        page = tables[row, 0]
        # positions 0 and 1 written, the rest of the page still zero
        assert np.abs(k[:, page, :, :2]).sum(axis=(1, 3)).all()
        assert not k[:, page, :, 2:].any()


def test_planner_validates_megastep():
    from repro.serving.scheduler import StepPlanner
    with pytest.raises(ValueError):
        StepPlanner(megastep=0)
    assert StepPlanner(megastep=16).megastep == 16


# ----------------------------------------------------------------------
# engine-level: K is invisible in every judge-visible output AND in
# the KV high-water / page hygiene
# ----------------------------------------------------------------------
def _twin_zoo(seed=0):
    """Probe + three probe-twin members: every escalated member
    decodes on the probe's server from reused prompt pages, so each
    row's page usage peaks at its probe plateau — making the KV
    high-water provably K-invariant."""
    import jax
    from repro.configs.registry import get_config
    from repro.data import tokenizer as tok
    from repro.models import params as params_lib
    from repro.serving import ZooModel
    cfg = get_config("smollm-135m", reduced=True).replace(
        vocab_size=tok.VOCAB_SIZE, dtype="float32",
        tie_embeddings=True)
    prm = params_lib.init_params(cfg, jax.random.PRNGKey(seed))
    probe = ZooModel(name="probe", cfg=cfg, params=prm)
    ensemble = [ZooModel(name=f"twin{i}", cfg=cfg, params=prm)
                for i in range(3)]
    return probe, ensemble


def _twin_tasks(n):
    return [Task(task_id=f"m{i}", benchmark="x", kind="math",
                 text=f"{i % 10} {(i * 7) % 10} + 1 = ", gold="0",
                 difficulty=0.0) for i in range(n)]


def _run_twin(megastep, n_tasks=8, max_new=6, temp=1.2, seed=0):
    from repro.configs.acar import ACARConfig
    from repro.serving import BatchedACAREngine, MicroBatchPolicy
    probe, ensemble = _twin_zoo(seed)
    eng = BatchedACAREngine(
        ACARConfig(probe_temperature=temp, seed=seed), probe,
        ensemble, max_new_tokens=max_new, kv_prefix_cache=0)
    res = eng.run_stepped(
        _twin_tasks(n_tasks),
        MicroBatchPolicy(max_batch_size=n_tasks,
                         max_batch_tokens=1 << 20),
        chunk_tokens=4, max_active_rows=n_tasks, megastep=megastep)
    return eng, res


@pytest.mark.slow
def test_megastep_engine_streams_and_highwater_k_invariant():
    """K in {4, 16} vs the per-tick baseline: identical sigma, modes,
    probe texts, member answers and final answers; identical KV page
    high-water; and mid-megastep retirement leaves zero pages behind
    (only scratch survives — the prefix cache is disabled)."""
    base_eng, base = _run_twin(megastep=1)
    hw0 = base_eng.kv_stats()["probe"].pages_highwater
    for k in (4, 16):
        eng, res = _run_twin(megastep=k)
        np.testing.assert_array_equal(base.sigma, res.sigma)
        np.testing.assert_array_equal(base.modes, res.modes)
        assert base.final_answers == res.final_answers
        assert base.probe_texts == res.probe_texts
        assert base.member_answers == res.member_answers
        # identical page high-water: megastep may hold a finished
        # lane's pages <= K-1 ticks longer, but usage never exceeds
        # the probe plateau either way (all-twin ensemble)
        assert eng.kv_stats()["probe"].pages_highwater == hw0
        # mid-megastep retirement page hygiene
        for srv in eng._kv_servers.values():
            assert srv.pool.pages_in_use == srv._scratch.size
        # megastep really fused: fewer launches than ticks advanced,
        # and mid-megastep finishes burned masked steps
        assert res.step.launches < base.step.launches
        assert res.step.masked_decode_steps > 0
        assert res.step.decode_tokens == base.step.decode_tokens


@pytest.mark.slow
def test_megastep_transfers_per_token_drop_k_fold():
    """The transfer-counter hook: with greedy probes (no early EOS),
    mode-0 routing and every row admitted at tick 0 in lockstep,
    megastep K=16 serves the same decode tokens in exactly 16x fewer
    decode launches — so host<->device transfer events per emitted
    token drop exactly K-fold."""
    from repro.configs.acar import ACARConfig
    from repro.serving import BatchedACAREngine, MicroBatchPolicy
    from repro.serving.metrics import PromCounters
    from repro.serving.queue import AdmissionQueue
    from repro.serving.scheduler import StepPlanner
    from repro.serving.step_loop import StepLoopRunner

    def run(megastep):
        probe, ensemble = _twin_zoo(0)
        eng = BatchedACAREngine(
            ACARConfig(probe_temperature=0.0, seed=0), probe,
            ensemble, max_new_tokens=16, kv_prefix_cache=0,
            route_fn=lambda sig, idx: np.zeros(len(idx), np.int32))
        # max_batch_size=1: the queue is ready the instant any request
        # has arrived, so the admission loop pulls all four rows at
        # tick 0 and they decode in lockstep on both paths
        queue = AdmissionQueue(MicroBatchPolicy(
            max_batch_size=1, max_batch_tokens=1 << 20))
        for t in _twin_tasks(4):
            queue.submit(t, arrival_time=0)
        runner = StepLoopRunner(
            eng, queue, StepPlanner(chunk_tokens=4, max_active_rows=4,
                                    megastep=megastep),
            PromCounters())
        return runner.run()

    r1, r16 = run(1), run(16)
    assert r1.decode_tokens == r16.decode_tokens > 0
    rate1 = (r1.decode_h2d + r1.decode_d2h) / r1.decode_tokens
    rate16 = (r16.decode_h2d + r16.decode_d2h) / r16.decode_tokens
    assert rate1 == pytest.approx(16 * rate16), \
        f"per-token transfer rate {rate1} vs {rate16}"
    assert r16.masked_decode_steps == 0         # greedy: no early EOS
