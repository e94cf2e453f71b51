"""Chunked-prefill step programs (``prefill_chunk_paged``) against the
roofline, in percent: per launch the larger of its operations over
peak bf16 FLOP/s and its bytes over peak HBM bandwidth, summed, over
the programs' device time in the trace."""
from bench import work

PROGRAM = "prefill_chunk_paged"


def read(win):
    if win.trace is None:
        return None
    t = sum(v for k, v in win.trace["program_s"].items() if PROGRAM in k)
    lns = [ln for ln in win.traced_launches() if ln.kind == "prefill"]
    if not t or not lns:
        return None
    pk = win.peaks
    bound = 0.0
    for ln in lns:
        f, b = work.launch(win, ln)
        bound += max(f / pk["flops_bf16"], b / pk["hbm_bytes_s"])
    return 100.0 * bound / t
