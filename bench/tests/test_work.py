"""Work counters against hand counts at a reduced configuration."""
import pytest

from bench.work import dense

M = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 4,
     "num_key_value_heads": 2, "num_hidden_layers": 3, "vocab_size": 32,
     "tie_word_embeddings": True}


def test_layer_params():
    # q 8x8, k and v 8x4 each, o 8x8, three 8x16 MLP matrices
    assert dense.layer_matmul_params(M) == 64 + 32 + 32 + 64 + 3 * 128


def test_token_flops():
    # 2 x params through 3 layers + attention over 5 positions
    # (QK and PV: 2 x 2 x heads x head_dim x 5 per layer) + the head
    want = 2 * 3 * 576 + 3 * 4 * 4 * 2 * 5 + 2 * 8 * 32
    assert dense.token_flops(M, 4) == want
    assert dense.token_flops(M, 4, head=False) == want - 2 * 8 * 32


def test_decode_tick_bytes():
    # weights: 3 layers x (576 + two norms of 8) + final norm + the
    # tied head (32 x 8), 2 bytes each; each lane reads its cache up
    # to its position (3 layers x K,V x 2 heads x 2 dims x 2 bytes = 48
    # per position) and its embedding row (16 bytes)
    w = 2 * (3 * (576 + 16) + 8 + 256)
    f, b = dense.decode_tick(M, [0, 9])
    assert b == w + 48 * 1 + 16 + 48 * 10 + 16
    assert f == dense.token_flops(M, 0) + dense.token_flops(M, 9)


def test_prefill_chunk_counts_the_head_only_where_the_prompt_ends():
    rows = [(0, 4), (4, 4)]                     # prompt of 8 tokens
    f, b = dense.prefill_chunk(M, rows, 8)
    body = sum(dense.token_flops(M, p, head=False) for p in range(8))
    assert f == body + 2 * 8 * 32
    # head read once (a row ends the prompt); row caches to 4 and 8
    w = 2 * (3 * (576 + 16) + 8 + 256)
    assert b == w + 48 * 4 + 16 * 4 + 48 * 8 + 16 * 4
    f2, b2 = dense.prefill_chunk(M, [(0, 4)], 8)
    assert f2 == sum(dense.token_flops(M, p, head=False) for p in range(4))
    assert b2 == 2 * (3 * (576 + 16) + 8) + 48 * 4 + 16 * 4


def test_untied_head_is_its_own_matrix():
    m = dict(M, tie_word_embeddings=False)
    assert dense.weight_bytes(m) == dense.weight_bytes(M)
    assert dense.weight_bytes(m, head=False) == dense.weight_bytes(
        M, head=False)


@pytest.mark.parametrize("name", ["smollm-fleet", "ds7b-fleet"])
def test_published_parameter_counts(name):
    import json
    from pathlib import Path
    cfg = json.loads((Path(__file__).resolve().parents[1] / "configs"
                      / f"{name}.json").read_text())
    probe = cfg["probe"]
    n = probe["num_hidden_layers"] * dense.layer_matmul_params(probe) \
        + probe["vocab_size"] * probe["hidden_size"]
    assert 134e6 < n < 135e6                  # SmolLM-135M
    if name == "ds7b-fleet":
        per_layer = dense.layer_matmul_params(cfg)
        assert 202e6 < per_layer < 203e6
