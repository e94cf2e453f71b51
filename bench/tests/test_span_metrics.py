"""The readers of the step loop's host spans and wall stamps, on a
window built by hand: host time is each call less the host's waits on
decode programs, less a decode launch that holds a trace edge, and
time to route is a median over every request."""
from types import SimpleNamespace as NS

import pytest

from bench import cell
from repro.serving.step_loop import StepStats
from repro.serving.tracing import HostSpan

NAN = float("nan")


def window(steps, answers):
    win = cell.Window({}, {}, {}, [], {}, 0.0)
    win.calls = [NS(tasks=[None] * n, result=NS(step=st))
                 for st, n in zip(steps, answers)]
    return win


def call_log(t0, wait_s, call_s):
    """One call: set-up, a tick holding a decode launch and its wait."""
    return [
        HostSpan("acar.call", -1, t0, t0 + call_s),
        HostSpan("acar.call.setup", 0, t0, t0 + 0.001),
        HostSpan("acar.tick", 0, t0 + 0.001, t0 + call_s - 0.001),
        HostSpan("acar.decode.launch", 2, t0 + 0.002, t0 + 0.003),
        HostSpan("acar.decode.wait", 2, t0 + 0.003, t0 + 0.003 + wait_s),
    ]


def test_step_host_ms_subtracts_the_waits(capsys):
    read = cell.reader("step_host_ms_per_answer.single")
    steps = [StepStats(host_spans=call_log(10.0, 0.4, 0.5)),
             StepStats(host_spans=call_log(20.0, 0.1, 0.3))]
    # (0.5 - 0.4) + (0.3 - 0.1) s of host time over 3 answers
    assert read(window(steps, [1, 2])) == pytest.approx(100.0)
    err = capsys.readouterr().err
    assert "acar.decode.wait 166.667" in err
    # the tick's self time: itself less the launch and the wait
    assert "acar.tick 98.000" in err


def test_step_host_ms_leaves_out_the_launches_at_trace_edges(capsys):
    """The profiler starts and stops inside a decode launch of the
    sweep: those two launches, and only they, leave the host time."""
    read = cell.reader("step_host_ms_per_answer.sweep")
    log = call_log(10.0, 0.4, 0.5)
    log[3].end += 0.05                    # the launch that starts a trace
    log[4].start += 0.05
    log[4].end += 0.05
    later = call_log(20.0, 0.1, 0.3)
    later[3].end += 0.02                  # the launch that stops it
    later[4].start += 0.02
    later[4].end += 0.02
    win = window([StepStats(host_spans=log),
                  StepStats(host_spans=later)], [1, 2])
    win.traced = (10.03, 20.004)
    # as in the test above, once both launches are left out with time
    assert read(win) == pytest.approx(
        (0.5 - 0.4 - 0.051 + 0.3 - 0.1 - 0.021) / 3 * 1e3)
    err = capsys.readouterr().err
    assert "acar.decode.launch 0.000" in err
    assert "left out 2 decode launches at a trace edge, 72.0 ms" in err
    win.traced = (0.0, 0.0)               # no trace: nothing left out
    assert read(win) == pytest.approx((0.5 - 0.4 + 0.3 - 0.1) / 3 * 1e3)
    assert "left out 0 decode launches" in capsys.readouterr().err


def test_time_to_route_is_the_median_over_requests(capsys):
    read = cell.reader("time_to_route_p50_ms.single")
    steps = [StepStats(wall={0: (1.0, 1.5, 2.0), 1: (1.0, 1.1, 1.2)}),
             StepStats(wall={0: (5.0, 5.2, 5.3)}),
             # a row aborted before its route has no time to route
             StepStats(wall={0: (7.0, NAN, 7.5), 1: (8.0, 8.3, 9.0)})]
    assert read(window(steps, [2, 1, 2])) == pytest.approx(250.0)
    assert "4 requests" in capsys.readouterr().err


@pytest.mark.parametrize("name", ["step_host_ms_per_answer.sweep",
                                  "time_to_route_p50_ms.single"])
def test_a_program_without_spans_reads_nothing(name):
    """The parent commit's StepStats has neither field: no number, and
    no error."""
    step = NS(launches=3, timeline={})
    assert cell.reader(name)(window([step], [1])) is None
