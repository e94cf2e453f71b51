"""Span tracing + PROV lineage unit tests (host-only, fast).

Pins the observability substrate's contracts without touching models:
span records are deterministic hashable dicts (wall time rides the
non-hashed side channel, so two runs with different clocks share one
chain head), ``SpanLog.flush`` writes ``ArtifactStore``-verifiable
JSONL, ``SpanTracer`` assigns per-trace ordinal span ids with implicit
stream parenting (row lifecycle vs forked member/probe streams) and
first-writer-wins KV provenance, and ``teamllm.prov`` materializes a
walkable PROV graph whose lineage walk re-verifies span hashes —
including catching a tampered span.

The last tests serve a tiny fleet through ``run_stepped`` to pin the
wall-clock host spans (``HostSpans``): their names and nesting, in the
in-memory log and on the profiler's host plane alike.
"""
import collections
import copy

import pytest

from repro.serving.tracing import NullTracer, SpanTracer
from repro.teamllm.artifacts import ArtifactStore
from repro.teamllm.prov import lineage, prov_records, verify_span_file
from repro.teamllm.spans import SpanLog, make_trace_id, span_record
from repro.teamllm.trace import content_hash


# ----------------------------------------------------------------------
# span records + SpanLog
# ----------------------------------------------------------------------
def test_make_trace_id():
    assert make_trace_id("req-3", 7) == "req-3#7"


def test_span_record_sorts_fields_and_drops_none():
    r = span_record("route", "t#0", "t#0/2", 5, parent="t#0/1",
                    sigma=0.5, mode=1, aborted=None)
    assert list(r) == ["event", "phase", "trace", "span", "tick",
                      "parent", "mode", "sigma"]
    assert "aborted" not in r
    # same fields, different kwarg order -> same hash
    r2 = span_record("route", "t#0", "t#0/2", 5, parent="t#0/1",
                     mode=1, sigma=0.5)
    assert content_hash(r) == content_hash(r2)


def test_spanlog_wall_time_is_outside_the_hash():
    a, b = SpanLog(), SpanLog()
    rec = span_record("admit", "t#0", "t#0/0", 0)
    a.append(rec, wall_time=1.0)
    b.append(rec, wall_time=999.0)
    assert a.head == b.head
    assert a.rows[0]["wall_time"] != b.rows[0]["wall_time"]


def test_spanlog_flush_is_artifact_store_compatible(tmp_path):
    log = SpanLog()
    for i in range(5):
        log.append(span_record("admit", f"t#{i}", f"t#{i}/0", i),
                   wall_time=float(i))
    p = tmp_path / "spans.jsonl"
    head = log.flush(p)
    assert head == log.head
    audit = ArtifactStore(p).audit()
    assert audit["ok"] and audit["records"] == 5
    assert audit["head"] == head
    assert verify_span_file(p)["ok"]


# ----------------------------------------------------------------------
# SpanTracer
# ----------------------------------------------------------------------
def test_null_tracer_is_disarmed_and_inert():
    t = NullTracer()
    assert t.armed is False
    assert t.span("admit", "t#0", 0) is None
    assert t.kv_insert("m", "h", "t#0", "t#0/0") is None
    assert t.kv_source("m", "h") is None
    assert t.records() == [] and t.flush() is None


def test_span_ids_are_per_trace_ordinals():
    t = SpanTracer()
    assert t.span("admit", "a#0", 0) == "a#0/0"
    assert t.span("admit", "b#1", 0) == "b#1/0"
    assert t.span("route", "a#0", 1) == "a#0/1"
    assert t.span("route", "b#1", 1) == "b#1/1"


def test_implicit_parenting_row_stream_and_forks():
    t = SpanTracer()
    s0 = t.span("admit", "a#0", 0)
    s1 = t.span("route", "a#0", 1)
    # forked member stream: first span parents on the row stream...
    m0 = t.span("member_launch", "a#0", 1, key=("m", 0))
    # ...later spans chain within the fork, not the row stream
    m1 = t.span("member_decode", "a#0", 2, key=("m", 0))
    # a second fork also parents on the row stream's latest span
    p0 = t.span("member_launch", "a#0", 1, key=("m", 1))
    # the row stream keeps chaining through its own last span
    s2 = t.span("retire", "a#0", 3)
    recs = {r["span"]: r for r in t.records()}
    assert "parent" not in recs[s0]
    assert recs[s1]["parent"] == s0
    assert recs[m0]["parent"] == s1
    assert recs[m1]["parent"] == m0
    assert recs[p0]["parent"] == s1
    assert recs[s2]["parent"] == s1


def test_explicit_parent_overrides():
    t = SpanTracer()
    s0 = t.span("admit", "a#0", 0)
    t.span("route", "a#0", 1)
    s2 = t.span("requeued", "a#0", 2, parent=s0)
    assert [r for r in t.records()
            if r["span"] == s2][0]["parent"] == s0


def test_kv_insert_first_writer_wins():
    t = SpanTracer()
    t.kv_insert("model-a", "hash1", "a#0", "a#0/3")
    t.kv_insert("model-a", "hash1", "b#1", "b#1/3")   # duplicate
    assert t.kv_source("model-a", "hash1") == ("a#0", "a#0/3")
    assert t.kv_source("model-b", "hash1") is None


def test_memory_only_flush_returns_head(tmp_path):
    t = SpanTracer()                      # path=None
    t.span("admit", "a#0", 0)
    assert t.flush() == t.head
    td = SpanTracer(tmp_path / "s.jsonl")
    td.span("admit", "a#0", 0)
    assert td.flush() == td.head
    assert ArtifactStore(tmp_path / "s.jsonl").audit()["ok"]


def test_identical_span_streams_share_one_head():
    def _run():
        t = SpanTracer()
        t.span("admit", "a#0", 0, prompt_tokens=9)
        t.span("route", "a#0", 1, sigma=0.25, mode=2)
        t.span("retire", "a#0", 2, task_id="q1", final_answer="42")
        return t.head
    assert _run() == _run()


# ----------------------------------------------------------------------
# PROV lineage
# ----------------------------------------------------------------------
def _lifecycle(t, trace, task_id, *, mode=2, members=(0, 1),
               answer="42", kv_source=None):
    """Emit one task's full span lifecycle on ``t``."""
    t.span("admit", trace, 0, prompt_tokens=9)
    t.span("probe_decode", trace, 1, model="probe", n_samples=3,
           key=("p", 0))
    t.span("route", trace, 1, sigma=0.4, mode=mode, n_samples=3)
    for mi in members:
        t.span("member_launch", trace, 1, key=("m", mi), member=mi,
               model=f"member-{mi}", reuse=0)
        if kv_source is not None:
            t.span("kv_reuse", trace, 1, key=("m", mi), kind="prefix",
                   model=f"member-{mi}", source=kv_source)
        t.span("member_decode", trace, 2, key=("m", mi), member=mi,
               model=f"member-{mi}", done=1)
    t.span("judge", trace, 3, mode=mode, members=list(members))
    t.span("retire", trace, 3, task_id=task_id, final_answer=answer,
           sigma=0.4, mode=mode)


def test_prov_graph_and_lineage_walk_verifies_hashes():
    t = SpanTracer()
    _lifecycle(t, "a#0", "q1")
    recs = prov_records(t.records())
    kinds = {}
    for r in recs:
        kinds.setdefault(r["kind"], []).append(r)
    ids = {r["id"] for r in kinds["entity"]}
    assert {"probe:a#0", "route:a#0", "member:a#0/0", "member:a#0/1",
            "answer:a#0"} <= ids
    assert {r["id"] for r in kinds["agent"]} == \
        {"model:probe", "model:member-0", "model:member-1"}
    assert any(r["entity"] == "member:a#0/0"
               and r["agent"] == "model:member-0"
               for r in kinds["wasAttributedTo"])

    lin = lineage(t.records(), "q1")
    assert lin["ok"] and lin["trace"] == "a#0"
    assert lin["verified"] > 0 and not lin["hash_failures"]
    walked = {r.get("id") for r in lin["records"]}
    assert {"answer:a#0", "route:a#0", "probe:a#0"} <= walked


def test_lineage_crosses_kv_reuse_between_traces():
    t = SpanTracer()
    _lifecycle(t, "a#0", "q1")
    _lifecycle(t, "b#1", "q2", kv_source="a#0")   # prefix donated by a#0
    lin = lineage(t.records(), "q2")
    assert lin["ok"]
    # the walk crossed the wasDerivedFrom edge into the donor trace
    assert "answer:a#0" in {r.get("id") for r in lin["records"]}
    assert any(r.get("kind") == "wasDerivedFrom"
               and r.get("source") == "answer:a#0"
               and r.get("kv") == "prefix"
               for r in lin["records"])


def test_lineage_detects_span_tampered_after_prov_build():
    """The PROV graph captures each span's content hash at build time;
    a lineage walk against that graph catches a span mutated since."""
    t = SpanTracer()
    _lifecycle(t, "a#0", "q1")
    recs = prov_records(t.records())      # materialized pre-tamper
    spans = copy.deepcopy(t.records())
    for s in spans:
        if s["phase"] == "route":
            s["mode"] = 99                # tamper the hashed record
    assert lineage(spans, "q1")["ok"]     # rebuilt graph: self-consistent
    lin = lineage(spans, "q1", records=recs)
    assert not lin["ok"]
    assert any("hash mismatch" in f for f in lin["hash_failures"])


def test_lineage_unknown_task_reports_cleanly():
    t = SpanTracer()
    _lifecycle(t, "a#0", "q1")
    lin = lineage(t.records(), "nope")
    assert not lin["ok"] and lin["trace"] is None
    assert lin["verified"] == 0


def test_latest_admission_wins_for_duplicate_task_ids():
    t = SpanTracer()
    _lifecycle(t, "a#0", "q1", answer="first")
    _lifecycle(t, "a#5", "q1", answer="second")
    lin = lineage(t.records(), "q1")
    assert lin["trace"] == "a#5"
    answers = [r.get("answer") for r in lin["records"]
               if r.get("id") == "answer:a#5"]
    assert answers == ["second"]


def test_mode0_answer_derives_from_probe():
    t = SpanTracer()
    _lifecycle(t, "a#0", "q1", mode=0, members=())
    lin = lineage(t.records(), "q1")
    assert lin["ok"]
    assert any(r.get("kind") == "wasDerivedFrom"
               and r.get("entity") == "answer:a#0"
               and r.get("source") == "probe:a#0"
               for r in lin["records"])


# ----------------------------------------------------------------------
# wall-clock host spans of the step loop (serving/tracing.HostSpans)
# ----------------------------------------------------------------------
STEP_SPANS = {
    "acar.call", "acar.call.setup", "acar.call.finish", "acar.tick",
    "acar.admit", "acar.prefill", "acar.decode.stage",
    "acar.decode.launch", "acar.decode.wait", "acar.decode.replay",
    "acar.promote", "acar.route", "acar.judge", "acar.retire"}


@pytest.fixture(scope="module")
def serve():
    """A tiny fleet serving five prompts through ``run_stepped``, once
    already: the pools have grown and the programs compiled, and the
    prefix cache is off, so every later call logs alike."""
    from harness.simulate import paged_zoo
    from repro.configs.acar import ACARConfig
    from repro.data.tasks import Task
    from repro.serving import BatchedACAREngine, MicroBatchPolicy

    probe, ensemble = paged_zoo(seed=0)
    eng = BatchedACAREngine(ACARConfig(probe_temperature=0.9, seed=0),
                            probe, ensemble, max_new_tokens=4,
                            kv_prefix_cache=0)
    tasks = [Task(task_id=f"h-{i}", benchmark="arithmetic", kind="math",
                  text=f"{i} + 1{i} = ", gold="2", difficulty=0.0)
             for i in range(5)]
    policy = MicroBatchPolicy(max_batch_size=4, max_batch_tokens=1 << 20)

    def call(tracer=None):
        return eng.run_stepped(tasks, policy, chunk_tokens=4,
                               megastep="auto", tracer=tracer)
    call()
    return call


def _structure(log):
    return [(sp.name, log[sp.parent].name if sp.parent >= 0 else None)
            for sp in log]


def test_host_spans_nest_and_cover_the_step_loop(serve):
    log = serve().step.host_spans
    assert {sp.name for sp in log} == STEP_SPANS
    assert log[0].name == "acar.call" and log[0].parent == -1
    assert all(0 <= sp.parent < i for i, sp in enumerate(log) if i)
    for sp in log:
        assert sp.start <= sp.end
        if sp.parent >= 0:
            up = log[sp.parent]
            assert up.start <= sp.start and sp.end <= up.end
    parents = dict(_structure(log))
    assert parents["acar.tick"] == "acar.call"
    for name in ("acar.admit", "acar.prefill", "acar.decode.stage",
                 "acar.decode.launch", "acar.decode.wait",
                 "acar.decode.replay", "acar.promote"):
        assert parents[name] == "acar.tick"
    launch = next(sp for sp in log if sp.name == "acar.decode.launch")
    assert set(launch.fields) == {"model", "bucket", "lanes", "ticks"}
    assert launch.rows and launch.fields["lanes"] <= launch.fields[
        "bucket"]


def test_host_spans_land_on_the_profiler_host_plane(serve, tmp_path):
    """Every span is a TraceAnnotation on the profiler's host plane,
    nested as in the in-memory log, and a run with no profiler attached
    logs the same structure."""
    import jax
    from jax.profiler import ProfileData

    quiet = serve().step.host_spans
    jax.profiler.start_trace(str(tmp_path))
    try:
        log = serve().step.host_spans
    finally:
        jax.profiler.stop_trace()
    assert _structure(quiet) == _structure(log)

    planes = ProfileData.from_file(
        str(next(tmp_path.rglob("*.xplane.pb")))).planes
    # by start, an enclosing span before the spans it holds
    events = sorted(
        ((ev.start_ns, -ev.duration_ns, ev.name, dict(ev.stats))
         for plane in planes if plane.name.startswith("/host:")
         for line in plane.lines for ev in line.events
         if ev.name.startswith("acar.")), key=lambda e: e[:2])
    assert [e[2] for e in events] == [sp.name for sp in log]
    for i, sp in enumerate(log):
        if sp.parent >= 0:
            s, neg, _, _ = events[i]
            ps, pneg, _, _ = events[sp.parent]
            assert ps <= s and s - neg <= ps - pneg, sp.name
    launches = [e[3] for e in events if e[2] == "acar.decode.launch"]
    assert launches and all(
        {"model", "bucket", "lanes", "ticks"} <= set(st)
        for st in launches)


def test_host_spans_feed_the_histogram_only_while_armed(serve):
    armed = serve(SpanTracer())
    quiet = serve()
    assert _structure(armed.step.host_spans) == \
        _structure(quiet.step.host_spans)
    # decode spans carry their model as a label: probe and member
    # waits are apart, and together count every wait
    waits = collections.Counter(
        sp.fields["model"] for sp in armed.step.host_spans
        if sp.name == "acar.decode.wait")
    assert len(waits) > 1
    for model, n in waits.items():
        assert armed.metrics.get_histogram(
            "acar_span_duration", phase="acar.decode.wait",
            model=model)[1] == n
    assert armed.metrics.get_histogram(
        "acar_span_duration", phase="acar.promote")[1] == sum(
        sp.name == "acar.promote" for sp in armed.step.host_spans)
    assert "acar_span_duration" not in quiet.metrics.render()


def test_serve_reads_request_walls_from_stamps_and_launch_rows(serve):
    """The operator's per-request readout: every request routes no
    later than it retires, and rides at least one decode launch."""
    from repro.launch.serve import request_walls

    step = serve().step
    got = request_walls(step)
    assert 0 < got["time_to_route_p50_ms"] <= got["request_p50_ms"] \
        <= got["request_p90_ms"]
    rode = {i for sp in step.host_spans
            if sp.name == "acar.decode.launch" for i in sp.rows}
    assert rode == set(step.wall)
    assert got["decode_launches_per_request"] >= 1
