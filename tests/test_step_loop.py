"""Step-level continuous batching: admission wiring, planner policy,
and wave<->step bit-equivalence on small real-model streams.

``AdmissionQueue.ready()`` is the single admission source for both
execution styles now — ``drain_batches`` jumps a virtual clock to each
fill-or-timeout instant, and the step loop polls it every tick — so
the regression tests here pin the fill-or-timeout budget under bursty
tick patterns (the dead-path bug this PR fixes: ready() existed but
nothing called it).
"""
import numpy as np
import pytest

from repro.data.tasks import Task
from repro.serving.queue import AdmissionQueue, MicroBatchPolicy
from repro.serving.scheduler import StepPlanner


def mk_task(i, text="1 + 1 = "):
    return Task(task_id=f"s-{i:03d}", benchmark="arithmetic",
                kind="math", text=text, gold="2", difficulty=0.0)


# ----------------------------------------------------------------------
# AdmissionQueue.ready() as the single admission source
# ----------------------------------------------------------------------
def test_pop_matches_form_batch_numbering():
    """pop() and form_batch() draw admission indices from one
    counter — row numbering (and therefore sampling key streams) is
    identical however the stream is admitted."""
    q1 = AdmissionQueue(MicroBatchPolicy(max_batch_size=4))
    q2 = AdmissionQueue(MicroBatchPolicy(max_batch_size=4))
    for i in range(6):
        q1.submit(mk_task(i))
        q2.submit(mk_task(i))
    flat = [r for b in q1.drain_batches() for r in b.requests]
    popped = [q2.pop() for _ in range(6)]
    assert [r.admission_index for r in flat] == \
        [r.admission_index for r in popped] == list(range(6))
    assert [r.task.task_id for r in flat] == \
        [r.task.task_id for r in popped]


def test_ready_fill_trigger_under_burst():
    """A burst filling the size budget makes ready() fire at the
    burst's arrival tick, not later."""
    q = AdmissionQueue(MicroBatchPolicy(max_batch_size=4,
                                        max_wait_ticks=100))
    for i in range(4):
        q.submit(mk_task(i), arrival_time=50)
    assert q.next_ready_at() == 50
    assert q.ready(now=50)


def test_ready_timeout_budget_holds_under_bursty_ticks():
    """Bursty arrivals smaller than the batch budget: every request
    must become admissible within max_wait_ticks of its burst's
    arrival — the fill-or-timeout guarantee."""
    pol = MicroBatchPolicy(max_batch_size=8, max_wait_ticks=10)
    q = AdmissionQueue(pol)
    bursts = [(0, 3), (4, 2), (37, 3), (38, 1)]    # (tick, size)
    for t, size in bursts:
        for i in range(size):
            q.submit(mk_task(t * 10 + i), arrival_time=t)
    # simulate a streaming loop ticking through time
    admitted_at = {}
    now = 0
    while len(q):
        if q.ready(now):
            batch = q.form_batch(now)
            for r in batch.requests:
                admitted_at[r.task.task_id] = now
        else:
            now += 1
    for t, size in bursts:
        for i in range(size):
            tid = mk_task(t * 10 + i).task_id
            assert admitted_at[tid] - t <= pol.max_wait_ticks, \
                f"{tid} waited past the fill-or-timeout budget"


def test_drain_batches_uses_ready_clock():
    """drain_batches forms the exact batch sequence a streaming loop
    would: the under-sized tail batch forms at its timeout instant."""
    pol = MicroBatchPolicy(max_batch_size=4, max_wait_ticks=7)
    q = AdmissionQueue(pol)
    for i in range(5):
        q.submit(mk_task(i), arrival_time=i)
    batches = q.drain_batches()
    assert [len(b) for b in batches] == [4, 1]
    # the full batch was ready the moment its last member arrived
    # (tick 3), so it forms as soon as the drain starts (the queue
    # clock is already at 5 after the submissions); the under-sized
    # tail batch waits for its oldest member's timeout
    assert batches[0].formed_at == 5
    assert batches[1].formed_at == 4 + pol.max_wait_ticks


def test_next_ready_at_empty_queue():
    assert AdmissionQueue().next_ready_at() is None


# ----------------------------------------------------------------------
# StepPlanner policy
# ----------------------------------------------------------------------
def test_planner_chunk_span():
    p = StepPlanner(chunk_tokens=8, max_active_rows=4)
    assert p.chunk_span(0, 20) == 8
    assert p.chunk_span(16, 20) == 4
    assert p.chunk_span(8, 9) == 1


def test_planner_decode_bucket_powers_of_two():
    p = StepPlanner()
    assert [p.decode_bucket(k) for k in (1, 2, 3, 5, 8)] == \
        [1, 2, 4, 8, 8]


def test_planner_admission_gate():
    p = StepPlanner(chunk_tokens=8, max_active_rows=2)
    assert p.may_admit(0, free_pages=100, reserved_pages=0,
                       row_need=20)
    # active-row cap
    assert not p.may_admit(2, free_pages=100, reserved_pages=0,
                           row_need=20)
    # page budget net of outstanding reservations
    assert not p.may_admit(1, free_pages=100, reserved_pages=90,
                           row_need=20)
    assert p.may_admit(1, free_pages=100, reserved_pages=80,
                       row_need=20)


# ----------------------------------------------------------------------
# wave <-> step bit-equivalence (real tiny models)
# ----------------------------------------------------------------------
@pytest.mark.slow
def test_step_loop_bit_equals_wave():
    """Long prompts straddling chunk boundaries, duplicates, sampled
    probe temperature: the step loop emits the exact per-task outputs
    the wave engine does, and retires pages leak-free."""
    from harness.simulate import paged_zoo
    from repro.configs.acar import ACARConfig
    from repro.serving import BatchedACAREngine, MicroBatchPolicy

    rng = np.random.default_rng(0)
    tasks = []
    for i in range(12):
        if tasks and rng.random() < 0.25:
            tasks.append(tasks[int(rng.integers(len(tasks)))])
            continue
        digits = "".join(str(rng.integers(10)) for _ in range(20))
        tasks.append(Task(task_id=f"t{i}", benchmark="x", kind="math",
                          text=f"{digits} + 1 = ", gold="0",
                          difficulty=0.0))
    probe, ensemble = paged_zoo(seed=0)
    acfg = ACARConfig(probe_temperature=0.9, seed=0)
    policy = MicroBatchPolicy(max_batch_size=4,
                              max_batch_tokens=1 << 20)

    wave = BatchedACAREngine(acfg, probe, ensemble, max_new_tokens=5)
    res_w = wave.run_queued(tasks, policy)
    step = BatchedACAREngine(acfg, probe, ensemble, max_new_tokens=5)
    res_s = step.run_stepped(tasks, policy, chunk_tokens=7)

    np.testing.assert_array_equal(res_w.sigma, res_s.sigma)
    np.testing.assert_array_equal(res_w.modes, res_s.modes)
    assert res_w.final_answers == res_s.final_answers
    assert res_w.probe_texts == res_s.probe_texts
    assert res_w.member_answers == res_s.member_answers
    # pages: nothing outlives the stream except scratch + prefix cache
    for srv in step._kv_servers.values():
        cache = sum(e.pages_held for e in srv._prefix.values())
        assert srv.pool.pages_in_use == srv._scratch.size + cache

    # step metrics exposed (satellite: planner decisions observable)
    m = res_s.metrics
    assert m.get("acar_step_admissions_total") == len(tasks)
    assert m.get("acar_step_rows_active", phase="done") == len(tasks)
    assert res_s.step.prefill_chunks > 0
    rendered = m.render()
    assert "acar_prefill_chunks_total" in rendered
    assert "acar_step_bucket_occupancy" in rendered


@pytest.mark.slow
def test_step_loop_respects_page_budget_admission():
    """With a tiny active cap the loop still serves everything —
    admission defers rather than exhausting the pool."""
    from harness.simulate import paged_zoo
    from repro.configs.acar import ACARConfig
    from repro.serving import BatchedACAREngine, MicroBatchPolicy

    tasks = [mk_task(i, text=f"{i % 10} + 1 = ") for i in range(6)]
    probe, ensemble = paged_zoo(seed=0)
    acfg = ACARConfig(probe_temperature=0.0, seed=0)
    eng = BatchedACAREngine(acfg, probe, ensemble, max_new_tokens=4)
    res = eng.run_stepped(
        tasks, MicroBatchPolicy(max_batch_size=6,
                                max_batch_tokens=1 << 20),
        chunk_tokens=4, max_active_rows=2)
    assert len(res.final_answers) == 6
    assert max(res.batch_sizes) <= 2


@pytest.mark.slow
def test_step_loop_dense_member_fallback_bit_equals_wave():
    """A non-paged ensemble member (hybrid stack) takes the dense
    one-shot fallback inside the step loop — still bit-identical to
    the wave path, because both decode it with the same per-row key
    streams."""
    import jax
    from repro.configs.registry import get_config
    from repro.configs.acar import ACARConfig
    from repro.data import tokenizer as tok
    from repro.models import params as params_lib
    from repro.models.transformer import paged_supported
    from repro.serving import (
        BatchedACAREngine, MicroBatchPolicy, ZooModel)

    def mk(arch, i):
        cfg = get_config(arch, reduced=True).replace(
            vocab_size=tok.VOCAB_SIZE, dtype="float32",
            tie_embeddings=True)
        prm = params_lib.init_params(cfg, jax.random.PRNGKey(i))
        return ZooModel(name=f"{arch}-{i}", cfg=cfg, params=prm)

    probe = mk("smollm-135m", 0)
    hybrid = mk("recurrentgemma-2b", 1)
    assert not paged_supported(hybrid.cfg)
    ensemble = [mk("smollm-135m", 2), hybrid,
                ZooModel(name="twin", cfg=probe.cfg,
                         params=probe.params)]
    tasks = [mk_task(i, text=f"{i % 10} + 2 = ") for i in range(4)]
    acfg = ACARConfig(probe_temperature=0.9, seed=0)
    policy = MicroBatchPolicy(max_batch_size=4,
                              max_batch_tokens=1 << 20)
    wave = BatchedACAREngine(acfg, probe, ensemble, max_new_tokens=4)
    res_w = wave.run_queued(tasks, policy)
    step = BatchedACAREngine(acfg, probe, ensemble, max_new_tokens=4)
    res_s = step.run_stepped(tasks, policy, chunk_tokens=3)
    assert res_w.final_answers == res_s.final_answers
    assert res_w.member_answers == res_s.member_answers
    np.testing.assert_array_equal(res_w.modes, res_s.modes)


# ----------------------------------------------------------------------
# per-request wall stamps (StepStats.wall)
# ----------------------------------------------------------------------
def test_wall_stamps_order_and_stay_out_of_journal_and_records(
        tmp_path, monkeypatch):
    """Every row is stamped admitted <= routed <= retired on the host
    clock. The stamps reach neither the journal nor a record: a run on
    a clock shifted by a day writes the same journal bytes and record
    hashes."""
    import time
    from types import SimpleNamespace

    from harness.simulate import paged_zoo
    from repro.configs.acar import ACARConfig
    from repro.serving import (
        BatchedACAREngine, MicroBatchPolicy, step_loop, trace_records,
        tracing)

    tasks = [mk_task(i, text=f"{i % 10} + 3{i} = ") for i in range(5)]
    probe, ensemble = paged_zoo(seed=0)
    policy = MicroBatchPolicy(max_batch_size=4,
                              max_batch_tokens=1 << 20)

    def serve(name):
        eng = BatchedACAREngine(ACARConfig(probe_temperature=0.9,
                                           seed=0), probe, ensemble,
                                max_new_tokens=4)
        res = eng.run_stepped(tasks, policy, chunk_tokens=4,
                              megastep="auto",
                              journal_path=tmp_path / name)
        hashes = [r.record_hash() for r in trace_records(
            "run", tasks, res, [m.name for m in ensemble])]
        return res, (tmp_path / name).read_bytes(), hashes

    res, journal, hashes = serve("a.jsonl")
    wall = res.step.wall
    assert sorted(wall) == list(range(len(tasks)))
    for admitted, routed, retired in wall.values():
        assert admitted <= routed <= retired

    shifted = SimpleNamespace(
        perf_counter=lambda: time.perf_counter() + 86400.0)
    monkeypatch.setattr(step_loop, "time", shifted)
    monkeypatch.setattr(tracing, "time", shifted)
    res_b, journal_b, hashes_b = serve("b.jsonl")
    assert min(a for a, _, _ in res_b.step.wall.values()) > \
        max(r for _, _, r in wall.values())
    assert res_b.step.host_spans[1].start > res.step.host_spans[-1].end
    assert journal_b == journal
    assert hashes_b == hashes
