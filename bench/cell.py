"""One run of one cell: set-up, the measured window, the check.

Everything that belongs to a configuration, a traffic mix or a metric
is data found by name (``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``, ``bench/metrics/<metric>.py``), and
each model group's architecture by its ``model_type``
(``bench/arch/<model_type>.py``), so a later cell, metric or
architecture is added as files and ``BENCHMARK.json`` entries.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import logging
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from bench import arch, capture, check, profile_reduce, traffic, weights
from bench.peaks import peaks

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = ROOT / ".jax_cache"
KV_PAGE_SIZE = 8


class NoChip(RuntimeError):
    pass


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ----------------------------------------------------------------------
# what a run is made of
# ----------------------------------------------------------------------
def load_cell(workload: str):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / conf["file"]).read_text())
    mix = traffic.load(cell["traffic"])

    def wanted(m):
        return "workloads" not in m or workload in m["workloads"]
    metrics = {"end_to_end": [m for m in bench["end_to_end"] if wanted(m)],
               "per_layer": [m for m in bench["per_layer"] if wanted(m)]}
    return cell, config, mix, metrics


def groups(config: dict) -> list:
    """Model groups of a fleet: the probe, then each member."""
    member = {k: v for k, v in config.items() if k != "probe"}
    return [config["probe"]] + [member] * config["members"]


def device_check(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"device: platform={dev['platform']} device_kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if require_tpu and dev["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX found platform {dev['platform']!r}")
    if dev["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips, found {dev['count']}")
    return dev


def compile_cache() -> None:
    """The persistent cache, inside the checkout at a fixed path, and
    every program written to it, however fast it compiled."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Compiles:
    """Programs JAX had to compile or load while ``counting``: every
    backend compile (a persistent-cache hit included) and the names
    and shapes its log gives for each."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    _one = None

    def __init__(self):
        import jax
        self.on, self.n, self.hits, self.names = False, 0, 0, []
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    @classmethod
    def listener(cls) -> "Compiles":
        """One listener per process, counts reset for each run."""
        if cls._one is None:
            cls._one = cls()
        one = cls._one
        one.n, one.hits, one.names = 0, 0, []
        return one

    def _dur(self, event, duration, **_):
        if self.on and event == self.EVENT:
            self.n += 1

    def _ev(self, event, **_):
        if self.on and event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    @contextlib.contextmanager
    def counting(self):
        import jax
        log = logging.getLogger("jax")
        names = self.names

        class Grab(logging.Handler):
            def emit(self, record):
                msg = record.getMessage()
                if msg.startswith("Compiling "):
                    names.append(msg.split(". Argument")[0]
                                 .replace("ShapedArray", "")[:1500])
        grab, prop, others = Grab(), log.propagate, list(log.handlers)
        for h in others:
            log.removeHandler(h)
        log.addHandler(grab)
        log.propagate = False
        jax.config.update("jax_log_compiles", True)
        self.on = True
        try:
            yield self
        finally:
            self.on = False
            jax.config.update("jax_log_compiles", False)
            log.removeHandler(grab)
            for h in others:
                log.addHandler(h)
            log.propagate = prop


@dataclass
class Call:
    pairs: list
    tasks: list
    modes: np.ndarray
    seconds: float
    result: object = None
    launches: list = field(default_factory=list)


@dataclass
class Window:
    """What the metric readers see."""
    cell: dict
    config: dict
    mix: dict
    models: list                     # model groups: probe, members
    device: dict
    setup_s: float
    seconds: float = 0.0
    calls: list = field(default_factory=list)
    kv: dict = field(default_factory=dict)
    trace: dict | None = None
    traced: tuple = (0.0, 0.0)       # host clock: trace edges
    temps: tuple = (0.0, 0.0)        # probe, member sampling temperature
    arena_lite: int = 2
    checked: dict = field(default_factory=dict)

    @property
    def answers(self) -> int:
        return sum(len(c.tasks) for c in self.calls)

    @property
    def latencies(self) -> list:
        return [c.seconds for c in self.calls for _ in c.tasks]

    @property
    def launches(self) -> int:
        return sum(c.result.step.launches for c in self.calls)

    @property
    def peaks(self) -> dict:
        return peaks(self.device["kind"])

    def traced_launches(self) -> list:
        """Launches dispatched between the trace's edges: those, and
        no others, ran on the device inside the traced window."""
        on, off = self.traced
        return [ln for c in self.calls for ln in c.launches
                if on <= ln.t < off]


def mark(name: str) -> None:
    """A zero-length annotation in the profiler's trace."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        pass


class Tracer:
    """Profiles part of the window, both edges where the device has
    finished all it was given, so that the programs run between the
    edges are exactly the launches dispatched between them.

    The mix's ``trace`` gives the part: ``{"calls": [a, b]}`` traces
    calls ``a`` to ``b - 1`` whole (a closed loop, whose calls end
    synced); ``{"start": s, "seconds": d}`` opens at the first decode
    launch that returns ``s`` seconds into the window and closes at the
    first that returns ``d`` seconds later, each waited for (the step
    loop reads its tokens at once anyway). A whole sweep call traced
    made a trace that took 144 s to write. Markers at both edges bound
    the traced window."""

    def __init__(self, spec: dict):
        self.spec, self.dir = spec, tempfile.mkdtemp(prefix="bench_trace_")
        self.t0 = 0.0
        self.t_on = self.t_off = None

    def on(self) -> None:
        import jax
        # no Python tracer: it slowed a traced call threefold
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_on = time.perf_counter()
        mark(profile_reduce.WINDOW)

    def off(self) -> None:
        import jax
        mark(profile_reduce.WINDOW)
        self.t_off = time.perf_counter()
        jax.profiler.stop_trace()

    def call(self, i: int) -> None:
        """Before call ``i`` of the window is served."""
        a, b = self.spec.get("calls", (None, None))
        if i == a:
            self.on()
        elif i == b and self.t_on is not None:
            self.off()

    def decode(self, out) -> None:
        """After a decode launch is dispatched, with its outputs."""
        if "start" not in self.spec or self.t_off is not None:
            return
        import jax
        now = time.perf_counter()
        due = (self.t0 + self.spec["start"] if self.t_on is None
               else self.t_on + self.spec["seconds"])
        if now < due:
            return
        jax.block_until_ready(out)
        if self.t_on is None:
            self.on()
        else:
            self.off()

    def end(self) -> Path:
        if self.t_on is None:
            raise RuntimeError(f"the window ended before the trace "
                               f"{self.spec} opened")
        if self.t_off is None:
            self.off()
        return next(Path(self.dir).rglob("*.xplane.pb"))


def reader(name: str):
    """``bench/metrics/<name>.py``, or the file of the quantity the
    name splits (``<name up to its first dot>.py``)."""
    for stem in (name, name.split(".")[0]):
        path = HERE / "metrics" / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r}")


# ----------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------
def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True, control: bool = False,
        cell_data=None, cache: bool = True) -> dict:
    import jax
    import jax.numpy as jnp

    cell, config, mix, metrics = cell_data or load_cell(workload)
    dev = device_check(cell["chips"], require_tpu)
    if cache:
        compile_cache()
    compiles = Compiles.listener()
    compiles.on = True

    from repro.configs.acar import ACARConfig
    from repro.serving import BatchedACAREngine, MicroBatchPolicy, ZooModel

    words = weights.seeds(seed)
    models = groups(config)
    dtype = config["dtype"]
    zoo = [ZooModel(name="probe" if i == 0 else f"member{i}",
                    cfg=arch.of(g).model_config(g, config["name"] + (
                        "-probe" if i == 0 else "-member"), dtype),
                    params=weights.make(g, words[i], dtype))
           for i, g in enumerate(models)]
    jax.block_until_ready([z.params for z in zoo])
    modes_now = {"modes": np.zeros(1, np.int32)}

    def route(sig, indices):
        return jnp.asarray(modes_now["modes"][np.asarray(indices)])

    engine = BatchedACAREngine(
        ACARConfig(seed=words[5]), zoo[0], zoo[1:],
        max_new_tokens=mix["max_new_tokens"], kv_page_size=KV_PAGE_SIZE,
        kv_prefix_cache=mix["kv_prefix_cache"], route_fn=route)
    policy = MicroBatchPolicy(max_batch_size=mix["max_active_rows"])
    cap = capture.Capture({id(z.params): i for i, z in enumerate(zoo)})
    prompts = traffic.Prompts(mix, words[4])

    def serve(pairs, call_launches, marks=False):
        tasks = prompts.tasks(pairs)
        modes = np.asarray([m for _, m in pairs], np.int32)
        modes_now["modes"] = modes
        cap.calls.append(call_launches)
        t0 = time.perf_counter()
        if marks:
            mark(profile_reduce.CALL_START)
        res = engine.run_stepped(
            tasks, policy, chunk_tokens=mix["chunk_tokens"],
            max_active_rows=mix["max_active_rows"],
            megastep=mix["megastep"])
        if marks:
            mark(profile_reduce.CALL_END)
        return Call(pairs, tasks, modes, time.perf_counter() - t0, res,
                    call_launches)

    single = mix["loop"] == "single"
    with cap.armed():
        # set-up: this cell's shapes, and no others
        for pairs in ([[p] for p in traffic.warmup_pairs(mix)] if single
                      else [traffic.call_sequence(mix)]):
            serve(pairs, [])
        setup_s = time.time() - t_start
        say(f"setup_s={setup_s!r}; programs compiled in set-up: "
            f"{compiles.n} ({compiles.hits} from the persistent cache)")
        compiles.n = compiles.hits = 0

        win = Window(cell, config, mix, models, dev, setup_s,
                     temps=(engine.acfg.probe_temperature,
                            engine.acfg.ensemble_temperature),
                     arena_lite=engine.acfg.arena_lite_size)
        block = len(traffic.block(mix))
        seq = (traffic.single_sequence(mix, 100000) if single
               else None)
        tracer = Tracer(mix["trace"]) if trace else None
        cap.on_decode = tracer.decode if tracer else None
        with compiles.counting():
            t0 = time.perf_counter()
            if tracer:
                tracer.t0 = t0
            while True:
                if tracer:
                    tracer.call(len(win.calls))
                pairs = [seq[len(win.calls)]] if single \
                    else traffic.call_sequence(mix)
                win.calls.append(serve(pairs, [], marks=tracer is not None))
                # a single loop ends on a whole block, so every window
                # holds the block's sizes and routes in equal numbers
                if (time.perf_counter() - t0 >= seconds
                        and not (single and len(win.calls) % block)):
                    break
            win.seconds = time.perf_counter() - t0
    say(f"window: {len(win.calls)} calls, {win.answers} answers in "
        f"{win.seconds!r} s; programs compiled inside the window: "
        f"{compiles.n} ({compiles.hits} from the persistent cache)")
    for name in compiles.names:
        say(f"  compiled in window: {name}")
    win.kv = {k: v for k, v in engine.kv_stats().items()}
    device = {**dev, "memory_peak_bytes": max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()[:cell["chips"]])}

    out_metrics = {}
    breakdown = None
    if trace:
        path = tracer.end()
        win.traced = (tracer.t_on, tracer.t_off)
        win.trace = profile_reduce.read(str(path))
        device["busy_s"] = win.trace["busy_s"]
        device["window_s"] = win.trace["window_s"]
        breakdown = {"device_ops": win.trace["device_ops"],
                     "idle_gaps": win.trace["idle_gaps"]}
        say(f"trace: {json.dumps({k: v for k, v in win.trace.items() if k in ('busy_s', 'window_s', 'program_s', 'program_launches')})}")
        shutil.rmtree(tracer.dir, ignore_errors=True)
    for m in metrics["per_layer" if trace else "end_to_end"]:
        v = reader(m["name"])(win)
        if v is not None:
            out_metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # the check runs once the window has closed and the program's
    # state is freed
    del engine, zoo
    gc.collect()
    checks, failed = check.run(win, words, control=control)
    say(f"checked: {json.dumps(win.checked)}")
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": bool(correct), "attempted": win.answers,
              "failed": failed, "metrics": out_metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checked"] = win.checked
    result["checks"] = checks
    return result
