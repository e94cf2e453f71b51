"""Host time of the step loop per answer, in ms: over every call of
the window, each ``acar.call`` span less the ``acar.decode.wait`` spans
inside it (the host blocked on a decode program), summed, over the
answers. A decode launch span that holds a trace edge
(``win.traced``) is left out, with its time: the benchmark starts or
stops its profiler inside it (``Tracer.decode`` runs from the capture
wrapper), which is no step-loop work. Prints each span's self time per
answer (its time less its children's) on standard error, and the
launches left out. The host spans are ``StepStats.host_spans``; a
program that keeps none reads nothing."""
import collections
import sys


def read(win):
    logs = [getattr(c.result.step, "host_spans", None) for c in win.calls]
    if not logs or not all(logs):
        return None
    edges = [t for t in win.traced if t]
    host = 0.0
    dropped = []
    own: collections.Counter = collections.Counter()
    for log in logs:
        for sp in log:
            d = sp.end - sp.start
            if sp.name == "acar.call":
                host += d
            elif sp.name == "acar.decode.wait":
                host -= d
            elif sp.name == "acar.decode.launch" and any(
                    sp.start <= t <= sp.end for t in edges):
                host -= d
                dropped.append(d)
                d = 0.0
            own[sp.name] += d
            if sp.parent >= 0:
                own[log[sp.parent].name] -= sp.end - sp.start
    n = win.answers
    print("step_host_ms_per_answer: self ms per answer: " + ", ".join(
        f"{name} {v / n * 1e3:.3f}" for name, v in own.most_common())
        + f"; left out {len(dropped)} decode launches at a trace edge, "
        f"{sum(dropped) * 1e3:.1f} ms", file=sys.stderr, flush=True)
    return host / n * 1e3
