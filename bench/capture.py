"""What the timed path produced, read where it is produced.

While a run is open, the step loop's two device programs,
``sampler.prefill_chunk_paged`` and ``sampler.decode_megastep_rows``,
are wrapped so that every launch leaves a record: the model it ran, its
static shape, and references to its input and output arrays (the
emitted tokens among them). Nothing is copied or synced inside the
window; the arrays are read back once it has closed. The wrapper costs
one Python call and one list append per launch.

``lanes()`` replays each decode lane's emissions exactly as the step
loop does (a lane that is done, or has used its budget, emits nothing
more), which gives the tokens every lane served.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

import numpy as np


@dataclass
class Launch:
    kind: str                  # "prefill" | "decode"
    model: int                 # 0 probe, 1.. members
    static: dict
    arrays: dict
    t: float = 0.0             # host clock at dispatch
    host: dict = field(default_factory=dict)

    def fetch(self) -> dict:
        if not self.host:
            self.host = {k: np.asarray(v) for k, v in self.arrays.items()}
            self.arrays = {}
        return self.host


class Capture:
    def __init__(self, params_models: dict):
        # id(params) -> model index: tells the probe from each member
        self.models = params_models
        self.calls: list = []
        # called with each decode launch's outputs once it is recorded
        self.on_decode = None

    @contextlib.contextmanager
    def armed(self):
        from repro.sampling import sampler
        prefill, decode = sampler.prefill_chunk_paged, \
            sampler.decode_megastep_rows

        def prefill_rec(cfg, params, tokens, pages, block_table,
                        start_pos, *, prompt_len):
            t = time.perf_counter()
            out = prefill(cfg, params, tokens, pages, block_table,
                          start_pos, prompt_len=prompt_len)
            self.calls[-1].append(Launch(
                "prefill", self.models.get(id(params), -1),
                {"prompt_len": prompt_len},
                {"tokens": tokens, "starts": start_pos}, t))
            return out

        def decode_rec(cfg, params, logits, pages, block_table, pos,
                       row_keys, steps, done, *, n_ticks, cache_len,
                       temperature, eos_id, pad_id):
            t = time.perf_counter()
            out = decode(cfg, params, logits, pages, block_table, pos,
                         row_keys, steps, done, n_ticks=n_ticks,
                         cache_len=cache_len, temperature=temperature,
                         eos_id=eos_id, pad_id=pad_id)
            self.calls[-1].append(Launch(
                "decode", self.models.get(id(params), -1),
                {"n_ticks": n_ticks, "cache_len": cache_len,
                 "temperature": temperature},
                {"keys": row_keys, "steps": steps, "done": done,
                 "pos": pos, "emits": out[0], "dones": out[1]}, t))
            if self.on_decode is not None:
                self.on_decode(out)
            return out

        sampler.prefill_chunk_paged = prefill_rec
        sampler.decode_megastep_rows = decode_rec
        try:
            yield self
        finally:
            sampler.prefill_chunk_paged = prefill
            sampler.decode_megastep_rows = decode


def lanes(launches: list, max_new: int) -> dict:
    """(model, key bytes) -> {"tokens": [...], "positions": [...],
    "bad": n}: each lane's served tokens in step order, the position
    each was fed at, and how many launches disagreed with the replay
    (a step counter other than the tokens seen so far)."""
    out: dict = {}
    for ln in launches:
        if ln.kind != "decode":
            continue
        h = ln.fetch()
        for i in range(h["keys"].shape[0]):
            if h["done"][i]:
                continue
            lane = out.setdefault((ln.model, h["keys"][i].tobytes()),
                                  {"tokens": [], "positions": [],
                                   "bad": 0, "done": False})
            if int(h["steps"][i]) != len(lane["tokens"]) or lane["done"]:
                lane["bad"] += 1
            pos = int(h["pos"][i])
            for t in range(ln.static["n_ticks"]):
                if lane["done"] or len(lane["tokens"]) >= max_new:
                    break
                lane["tokens"].append(int(h["emits"][t, i]))
                lane["positions"].append(pos + t)
                lane["done"] = bool(h["dones"][t, i])
    return out


def prefill_rows(launch: Launch) -> list:
    """(start, tokens) of each real row of a prefill launch. Bucket
    padding rows repeat the last real row's tokens and start; the
    rows of one launch are distinct prompts, so trailing repeats are
    the padding."""
    h = launch.fetch()
    toks, starts = h["tokens"], h["starts"]
    n = toks.shape[0]
    while n > 1 and starts[n - 1] == starts[n - 2] and \
            np.array_equal(toks[n - 1], toks[n - 2]):
        n -= 1
    return [(int(starts[i]), toks.shape[1]) for i in range(n)]
