"""Operations and bytes a dense GQA decoder needs, from its shapes.

Counts what the algorithm needs for the useful tokens, whatever
implements it: bucket padding rows, masked ticks and a gathered copy
of the cache are not counted, so a kernel that reads pages in place
changes the time and not the count. ``m`` is a model group of a
configuration file (Hugging Face key names); ``elem`` the bytes of one
weight or cache element as served.
"""
from __future__ import annotations


def dims(m: dict):
    d, h = m["hidden_size"], m["num_attention_heads"]
    kv = m["num_key_value_heads"]
    hd = m.get("head_dim") or d // h
    return d, h, kv, hd, m["intermediate_size"], \
        m["num_hidden_layers"], m["vocab_size"]


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies through in one layer."""
    d, h, kv, hd, f, _, _ = dims(m)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def weight_bytes(m: dict, elem: int = 2, head: bool = True) -> int:
    """Bytes of the weights one forward step reads: every layer's
    matrices and norms, the final norm and, with ``head``, the head
    (the embedding, where tied). Embedding rows looked up for the
    inputs are counted with the tokens."""
    d, _, _, _, _, n, v = dims(m)
    per_layer = layer_matmul_params(m) + 2 * d
    return elem * (n * per_layer + d + (d * v if head else 0))


def kv_bytes_per_position(m: dict, elem: int = 2) -> int:
    """Keys and values of one position, all layers."""
    _, _, kv, hd, _, n, _ = dims(m)
    return elem * n * 2 * kv * hd


def token_flops(m: dict, pos: int, head: bool = True) -> float:
    """Model operations of one token at position ``pos`` (0-based):
    its products through every layer, attention over ``pos + 1``
    positions, and the head."""
    d, h, _, hd, _, n, v = dims(m)
    f = 2.0 * n * layer_matmul_params(m) + 4.0 * n * h * hd * (pos + 1)
    return f + (2.0 * d * v if head else 0.0)


def decode_tick(m: dict, positions: list, elem: int = 2):
    """(operations, bytes) of one decode tick over live lanes at
    ``positions``: the weights once, each lane's cache up to its
    position read and its new position written, and its input row of
    the embedding."""
    d = dims(m)[0]
    kvb = kv_bytes_per_position(m, elem)
    flops = sum(token_flops(m, p) for p in positions)
    by = weight_bytes(m, elem) + sum(
        kvb * (p + 1) + elem * d for p in positions)
    return flops, by


def prefill_chunk(m: dict, rows: list, prompt_len: int, elem: int = 2):
    """(operations, bytes) of one prefill launch over real rows
    ``(start, tokens)`` of a prompt of ``prompt_len``: each token's
    products and causal attention, the head only for a row whose chunk
    ends the prompt; the weights once, each row's cached prefix read,
    its chunk's cache written and its embedding rows."""
    d = dims(m)[0]
    kvb = kv_bytes_per_position(m, elem)
    flops, head = 0.0, False
    by = 0
    for start, c in rows:
        ends = start + c >= prompt_len
        head |= ends
        flops += sum(token_flops(m, start + i, head=False)
                     for i in range(c))
        if ends:
            flops += 2.0 * d * m["vocab_size"]
        by += kvb * (start + c) + elem * d * c
    return flops, by + weight_bytes(m, elem, head=head)
