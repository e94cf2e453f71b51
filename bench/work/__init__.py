"""Work counts of a captured launch, by the architecture of the model
group it ran (``bench/arch``: its ``decode_tick`` and
``prefill_chunk``)."""
from __future__ import annotations

from bench import arch


def launch(win, ln) -> tuple:
    """(operations, bytes) the useful rows of one captured launch
    need."""
    from bench import capture
    m = win.models[ln.model]
    mod = arch.of(m)
    if ln.kind == "prefill":
        return mod.prefill_chunk(m, capture.prefill_rows(ln),
                                 ln.static["prompt_len"])
    h = ln.fetch()
    max_new = win.mix["max_new_tokens"]
    ticks: dict = {}
    for i in range(h["keys"].shape[0]):
        if h["done"][i]:
            continue
        for t in range(ln.static["n_ticks"]):
            if int(h["steps"][i]) + t >= max_new:
                break
            ticks.setdefault(t, []).append(int(h["pos"][i]) + t)
            if h["dones"][t, i]:
                break
    flops = by = 0.0
    for positions in ticks.values():
        f, b = mod.decode_tick(m, positions)
        flops += f
        by += b
    return flops, by
