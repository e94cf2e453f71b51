"""The reduction from a profiler trace to device time."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import profile_reduce as pr

DATA = Path(__file__).resolve().parent / "data"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def plane(name, lines):
    return NS(name=name, lines=[NS(name=n, events=e)
                                for n, e in lines.items()])


def synthetic():
    host = plane("/host:CPU", {"python3": [
        ev(pr.WINDOW, 100, 0), ev(pr.WINDOW, 1100, 0),
        ev(pr.CALL_END, 500, 0),                # opened before the window
        ev(pr.CALL_START, 600, 0), ev(pr.CALL_END, 1050, 0)]})
    dev = plane("/device:TPU:0", {
        "XLA Modules": [ev("jit_a(1)", 50, 150),      # clipped to 100
                        ev("jit_b(2)", 300, 100),
                        ev("jit_a(1)", 700, 200)],
        "XLA Ops": [ev("fusion", 50, 150), ev("dot", 300, 60),
                    ev("copy", 340, 60), ev("dot", 700, 200)]})
    return [host, dev]


def test_union_and_gaps():
    assert pr.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert pr.gaps([(2, 3), (5, 6)], 0, 10) == [(0, 2), (3, 5), (6, 10)]


def test_call_spans_run_to_the_window_edges():
    marks = [(pr.CALL_END, 3, 3), (pr.CALL_START, 5, 5),
             (pr.CALL_END, 6, 6), (pr.CALL_START, 8, 8)]
    assert pr.call_spans(marks, 0, 10) == [(0, 3), (5, 6), (8, 10)]


def test_synthetic_trace():
    r = pr.reduce(synthetic())
    assert r["window_s"] == pytest.approx(1000e-9)
    # busy: [100, 200) + [300, 400) + [700, 900)
    assert r["busy_s"] == pytest.approx(400e-9)
    assert r["program_s"]["jit_a"] == pytest.approx(300e-9)
    assert r["program_s"]["jit_b"] == pytest.approx(100e-9)
    assert r["program_launches"] == {"jit_a": 2, "jit_b": 1}
    gaps = dict(r["idle_gaps"])
    # [200, 300) and [400, 500) lie in the first call; [500, 600) and
    # [1050, 1100) between calls; [600, 700) and [900, 1050) in the
    # second
    assert gaps["in call, after jit_a"] == pytest.approx(100e-9 + 150e-9)
    assert gaps["in call, after jit_b"] == pytest.approx(200e-9)
    assert gaps["between calls, after jit_b"] == pytest.approx(100e-9)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(
        r["window_s"])


def test_no_window_marker_is_an_error():
    host = plane("/host:CPU", {"python3": [ev(pr.CALL_START, 0, 0)]})
    with pytest.raises(ValueError):
        pr.reduce([host])


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5e by ``record_trace.py``: three calls
    of a 2048^3 bf16 matmul and an elementwise program, with host
    pauses of 2 ms inside each call and 5 ms between them."""
    r = pr.read(str(DATA / "small.xplane.pb"))
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert set(r["program_s"]) == {"jit__lambda"}
    # six launches; the device's clock runs about 1.1 ms ahead of the
    # host's in this trace, so the first launch starts before the
    # window opens and is counted clipped, not as a launch inside it
    assert r["program_launches"]["jit__lambda"] == 5
    assert all(op.startswith("jit__lambda/") for op, _ in
               r["device_ops"])
    gaps = dict(r["idle_gaps"])
    assert any(k.startswith("between calls") for k in gaps)
    assert any(k.startswith("in call") for k in gaps)
    # the device idles through each host pause: at least the three 5 ms
    # pauses between calls and the three 2 ms pauses inside them
    assert sum(v for k, v in gaps.items()
               if k.startswith("between calls")) > 0.010
    assert sum(v for k, v in gaps.items()
               if k.startswith("in call")) > 0.005
    assert r["busy_s"] + sum(gaps.values()) == pytest.approx(
        r["window_s"], rel=1e-6)
