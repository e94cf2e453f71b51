"""Whole-step model FLOP utilisation, in percent: the model operations
of every useful prefill and decode token served in the traced window
over the window's length times peak bf16 FLOP/s."""
from bench import work


def read(win):
    if win.trace is None or not win.trace["window_s"]:
        return None
    flops = sum(work.launch(win, ln)[0] for ln in win.traced_launches())
    return 100.0 * flops / (win.trace["window_s"]
                            * win.peaks["flops_bf16"])
