"""The tokenizer the program serves with (``src/repro/data/tokenizer.py``),
copied: PAD 0, BOS 1, EOS 2, then ``CHARS`` from id 3. Prompts are made
of these characters. It belongs to the fleet, not to one model: every
model group reads and writes these ids."""
from __future__ import annotations

BOS, EOS, PAD = 1, 2, 0
CHARS = "0123456789+-*= ."


def encode(text: str) -> list:
    """Prompt ids as the program encodes a generation prompt."""
    if not text.endswith(" "):
        text = text + " "
    return [BOS] + [CHARS.index(c) + 3 for c in text if c in CHARS]


def decode(ids) -> str:
    out = []
    for i in ids:
        i = int(i)
        if i == EOS:
            break
        if 3 <= i < 3 + len(CHARS):
            out.append(CHARS[i - 3])
    return "".join(out)
