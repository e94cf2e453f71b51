"""Decode step programs (``decode_megastep_rows``) against the
bandwidth bound, in percent: the bytes their useful lanes need (the
weights once per tick, each live lane's cache) over peak HBM bandwidth,
divided by the programs' device time in the trace."""
from bench import work

PROGRAM = "decode_megastep_rows"


def read(win):
    if win.trace is None:
        return None
    t = sum(v for k, v in win.trace["program_s"].items() if PROGRAM in k)
    lns = [ln for ln in win.traced_launches() if ln.kind == "decode"]
    if not t or not lns:
        return None
    by = sum(work.launch(win, ln)[1] for ln in lns)
    return 100.0 * by / win.peaks["hbm_bytes_s"] / t
