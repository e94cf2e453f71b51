"""The one traffic generator: reads a mix file from ``bench/traffic/``.

A mix fixes a block of (length class, forced mode) pairs by count, in
one order drawn from a fixed seed: every run serves the same sizes and
routes in the same order, whatever its seed.
(Orders drawn per seed moved a sweep's rate by 5%, because the order
decides which rows share a decode launch.) The run's seed draws the
prompt text, fresh for every call from the tokenizer's own characters,
so no prompt repeats and the prefix cache never hits; and the weights
and the sampling.

Two loops:

* ``sweep``: one ``run_stepped`` call serves the whole block, in the
  seed's order; every call of a run repeats that order. The order
  opens with a prompt of the longest class, so the page pools reach
  their final size at the first admission of the warm-up call and
  every later call runs the same program shapes.
* ``single``: a closed loop of one client; each call serves one task.
  The sequence is the block in that order, block after block.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from bench.tokenizer import CHARS

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
# the seed of the one order of every mix's block
ORDER_SEED = 0


def load(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def block(mix: dict) -> list:
    """The mix's fixed multiset as a list of (class index, mode)."""
    out = []
    for ci, cls in enumerate(mix["classes"]):
        for mode, count in enumerate(cls["modes"]):
            out.extend([(ci, mode)] * count)
    return out


def _longest_first(pairs: list, mix: dict) -> list:
    lengths = [mix["classes"][c]["tokens"] for c, _ in pairs]
    i = int(np.argmax(lengths))
    return [pairs[i]] + pairs[:i] + pairs[i + 1:]


def call_sequence(mix: dict) -> list:
    """The (class, mode) order every ``sweep`` call repeats, and every
    block of a ``single`` loop."""
    rng = np.random.default_rng([ORDER_SEED, 0x5EED])
    pairs = block(mix)
    order = rng.permutation(len(pairs))
    return _longest_first([pairs[i] for i in order], mix)


def single_sequence(mix: dict, n: int) -> list:
    """The first ``n`` (class, mode) pairs of a ``single`` loop."""
    seq = call_sequence(mix)
    return [seq[i % len(seq)] for i in range(n)]


def warmup_pairs(mix: dict) -> list:
    """Every distinct (class, mode) pair of the block, longest class
    first: what a ``single`` run's set-up serves once each."""
    pairs = sorted(set(block(mix)),
                   key=lambda p: (-mix["classes"][p[0]]["tokens"], p[1]))
    return pairs


def prompt_text(rng: np.random.Generator, tokens: int) -> str:
    """Text of the tokenizer's characters that encodes to exactly
    ``tokens`` ids (BOS + characters, the last one the trailing space
    the tokenizer expects)."""
    body = rng.integers(0, len(CHARS), size=tokens - 2)
    return "".join(CHARS[i] for i in body) + " "


class Prompts:
    """Fresh prompt text for every task of a run, from the seed."""

    def __init__(self, mix: dict, seed: int):
        self.mix = mix
        self.rng = np.random.default_rng([seed, 0x7E47])
        self.n = 0

    def tasks(self, pairs: list) -> list:
        from repro.data.tasks import Task
        out = []
        for ci, _ in pairs:
            cls = self.mix["classes"][ci]
            out.append(Task(task_id=f"t{self.n}",
                            benchmark=cls["benchmark"], kind=cls["kind"],
                            text=prompt_text(self.rng, cls["tokens"]),
                            gold="0", difficulty=0.0))
            self.n += 1
        return out
