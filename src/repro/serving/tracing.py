"""Pluggable span tracing for the serving stack.

``NullTracer`` is the default: the runners hold ``tracer = None`` when
handed one (or nothing), so every instrumentation site reduces to a
single attribute check — exactly the ``serving/faults.py`` discipline,
and ``benchmarks/obs_bench.py`` gates the armed overhead at ≤3%.

``SpanTracer`` records the full lifecycle — admit → prefill-chunk →
probe-decode → route → ensemble-member launch → judge → retire, plus
every fault-path transition (requeue, retry, quarantine-degraded
route, shard re-placement, crash→restore) — as deterministic hashed
span records (``teamllm.spans``). Structure is a pure function of the
admission-ordered run; wall-clock stamps ride the non-hashed
``wall_time`` side channel. Parenting is implicit per stream: a
trace's row-lifecycle spans chain linearly, while per-lane decode
streams and per-member execution streams fork from the row stream and
chain launch-to-launch across megasteps and retries (``key=`` picks
the stream).

The tracer also carries the KV provenance map: prefix-cache owners are
recorded at insert (first writer in admission order — deterministic),
so a later hit can name its donor trace and PROV can materialize the
reuse as a ``wasDerivedFrom`` edge (``teamllm.prov``).

``HostSpans`` is the other kind: wall-clock spans of the step loop's
host work (``acar.call``, ``acar.tick``, ``acar.decode.wait``, ...),
always on, kept in memory for the run and written into the profiler's
host plane when a profiler records. Nothing in it is hashed or
journaled, so it times the serving path without changing it.
"""
from __future__ import annotations

import time
from pathlib import Path
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple, Union

from jax.profiler import TraceAnnotation

from repro.teamllm.spans import SpanLog, span_record


class NullTracer:
    """Disarmed tracer: every hook is a no-op. The runners normalise
    ``NullTracer`` (or ``None``) to ``tracer = None`` so the serving
    loop pays one attribute check per site and nothing else."""

    armed = False

    def span(self, *a: Any, **k: Any) -> None:
        return None

    def kv_insert(self, *a: Any, **k: Any) -> None:
        return None

    def kv_source(self, *a: Any, **k: Any) -> None:
        return None

    def records(self) -> List[dict]:
        return []

    def flush(self) -> Optional[str]:
        return None


class SpanTracer:
    """JSONL span tracer. ``path=None`` keeps the chain in memory only
    (the harness reads ``records()`` directly); with a path, ``flush``
    writes an ``ArtifactStore``-verifiable hash-chained file.

    ``attribution`` controls whether the step loop schedules
    on-capacity leave-one-out recomputation for full-arena rows
    (span phase ``attribution``); it defaults on — the whole point of
    arming a tracer is the audit story.
    """

    armed = True

    def __init__(self, path: Union[str, Path, None] = None, *,
                 attribution: bool = True):
        self.path = Path(path) if path is not None else None
        self.log = SpanLog()
        self.attribution = attribution
        self._seq: Dict[str, int] = {}
        self._last: Dict[Tuple[str, Any], str] = {}
        # (model, prompt-ids hash) -> (owner trace, owner span): first
        # inserter in admission order owns the cached prefix pages
        self._prefix_owner: Dict[Tuple[str, str], Tuple[str, str]] = {}

    # -- spans ---------------------------------------------------------
    def span(self, phase: str, trace: str, tick: int, *,
             key: Any = None, parent: Optional[str] = None,
             wall: float = 0.0, **fields: Any) -> str:
        """Emit one span on ``trace``. ``key=None`` is the row
        lifecycle stream; any other key names a forked stream (a probe
        lane, a member execution) whose first span parents on the row
        stream and whose later spans chain within the fork."""
        seq = self._seq.get(trace, 0)
        self._seq[trace] = seq + 1
        sid = f"{trace}/{seq}"
        if parent is None:
            parent = self._last.get((trace, key))
            if parent is None and key is not None:
                parent = self._last.get((trace, None))
        self.log.append(
            span_record(phase, trace, sid, tick, parent=parent,
                        **fields),
            wall_time=wall or time.time())
        self._last[(trace, key)] = sid
        return sid

    # -- KV provenance -------------------------------------------------
    def kv_insert(self, model: str, ids_hash: str, trace: str,
                  span: str) -> None:
        """Record the owner of freshly inserted prefix-cache pages.
        ``setdefault`` keeps the first (admission-ordered) writer when
        duplicates race within one run — deterministic."""
        self._prefix_owner.setdefault((model, ids_hash), (trace, span))

    def kv_source(self, model: str, ids_hash: str
                  ) -> Optional[Tuple[str, str]]:
        """The (trace, span) whose prefill populated these cached
        pages, or None for an untracked entry (e.g. inserted before
        the tracer armed)."""
        return self._prefix_owner.get((model, ids_hash))

    # -- output --------------------------------------------------------
    @property
    def head(self) -> str:
        return self.log.head

    def records(self) -> List[dict]:
        return self.log.records()

    def flush(self) -> Optional[str]:
        """Persist the chain (one buffered write; see ``SpanLog``).
        Returns the chain head, or None when memory-only."""
        if self.path is None:
            return self.log.head
        return self.log.flush(self.path)


# ----------------------------------------------------------------------
# wall-clock host spans
# ----------------------------------------------------------------------
@dataclass(slots=True)
class HostSpan:
    """One host span. ``parent`` indexes the enclosing span in the same
    log (-1 at the root); ``start``/``end`` are ``time.perf_counter``
    seconds. ``fields`` (model, bucket, lanes, ticks) also ride the
    profiler's annotation; ``rows``, the admission indices of the rows
    a group launch served (the audit trace ids' index), stay here:
    ``launch/serve.request_walls`` counts a request's decode launches
    from them."""
    name: str
    parent: int
    start: float
    end: float = 0.0
    fields: Dict[str, Any] = field(default_factory=dict)
    rows: Tuple[int, ...] = ()


class HostSpans:
    """The host spans of one ``run_stepped`` call, in opening order.

    ``span(name, ...)`` opens a ``jax.profiler.TraceAnnotation`` of the
    same name (a flag check unless a profiler records, when it lands on
    the profiler's host plane, on the device trace's clock) and appends
    one ``HostSpan``; spans nest by ``with``. The log lives as long as
    the run's result and has no file or exporter. Given ``metrics``
    (the run's ``PromCounters``, handed over only while an audit tracer
    is armed), every span also observes
    ``acar_span_duration{phase=<name>}`` on exit, with a ``model``
    label on the spans that carry one (prefill and decode), so probe
    and member time stay apart."""

    def __init__(self, metrics=None):
        self.records: List[HostSpan] = []
        self.metrics = metrics
        self._open: List[int] = []

    def span(self, name: str, rows: Tuple[int, ...] = (),
             **fields: Any) -> "_HostSpanCtx":
        return _HostSpanCtx(self, name, rows, fields)


class _HostSpanCtx:
    __slots__ = ("log", "name", "rows", "fields", "rec", "ann")

    def __init__(self, log: HostSpans, name: str, rows, fields):
        self.log, self.name, self.rows, self.fields = \
            log, name, rows, fields

    def __enter__(self) -> HostSpan:
        log = self.log
        self.ann = TraceAnnotation(self.name, **self.fields)
        self.ann.__enter__()
        self.rec = HostSpan(self.name,
                            log._open[-1] if log._open else -1,
                            time.perf_counter(), 0.0, self.fields,
                            tuple(self.rows))
        log._open.append(len(log.records))
        log.records.append(self.rec)
        return self.rec

    def __exit__(self, *exc) -> None:
        rec = self.rec
        rec.end = time.perf_counter()
        self.log._open.pop()
        self.ann.__exit__(*exc)
        if self.log.metrics is not None:
            model = rec.fields.get("model")
            self.log.metrics.observe(
                "acar_span_duration", rec.end - rec.start,
                phase=rec.name, **({"model": model} if model else {}),
                help="host wall seconds per step-loop span")
