"""The traced window's edges lie where the device has finished all it
was given, so the launches dispatched between them are the programs
the trace holds: a decode launch waited for, or a call boundary."""
import time
from types import SimpleNamespace as NS

import pytest

from bench import cell


class Edges(cell.Tracer):
    """A tracer that records its edges instead of profiling."""

    def __init__(self, spec):
        super().__init__(spec)
        self.edges = []

    def on(self):
        self.t_on = time.perf_counter()
        self.edges.append("on")

    def off(self):
        self.t_off = time.perf_counter()
        self.edges.append("off")


def test_sweep_edges_are_decode_launches():
    import jax.numpy as jnp
    tr = Edges({"start": 0.0, "seconds": 0.0})
    tr.t0 = time.perf_counter()
    out = (jnp.zeros(3), jnp.ones(2))
    for _ in range(4):
        tr.decode(out)
    assert tr.edges == ["on", "off"]


def test_sweep_waits_for_its_start():
    tr = Edges({"start": 3600.0, "seconds": 1.0})
    tr.t0 = time.perf_counter()
    tr.decode(())
    assert tr.edges == [] and tr.t_on is None
    with pytest.raises(RuntimeError):
        tr.end()


def test_single_edges_are_whole_calls():
    tr = Edges({"calls": [2, 4]})
    seen = []
    for i in range(6):
        tr.call(i)
        seen.append(list(tr.edges))
        tr.decode(())          # no effect on a calls trace
    assert seen == [[], [], ["on"], ["on"], ["on", "off"], ["on", "off"]]


def test_traced_launches_lie_between_the_edges():
    win = cell.Window({}, {}, {}, [], {}, 0.0)
    win.calls = [NS(launches=[NS(t=t) for t in (1.0, 2.0, 3.0, 4.0)])]
    win.traced = (2.0, 4.0)
    assert [ln.t for ln in win.traced_launches()] == [2.0, 3.0]
