"""The traffic generator: one seed, one sequence; every seed the same
multiset of sizes and routes."""
import collections

from bench import traffic


def test_one_order_for_every_seed():
    """The run's seed draws prompt text, weights and sampling; the
    sizes and routes, and their order, are the mix's own."""
    mix = traffic.load("sweep")
    seq = traffic.call_sequence(mix)
    assert seq == traffic.call_sequence(traffic.load("sweep"))
    assert collections.Counter(seq) == collections.Counter(
        traffic.block(mix))
    assert len(seq) == 32
    # the longest class opens every call, so the pools reach their
    # size at the first admission of the warm-up call
    assert mix["classes"][seq[0][0]]["tokens"] == max(
        c["tokens"] for c in mix["classes"])


def test_prompt_text_follows_the_seed():
    mix = traffic.load("sweep")
    pairs = traffic.call_sequence(mix)
    a = [t.text for t in traffic.Prompts(mix, 2**33 + 7).tasks(pairs)]
    b = [t.text for t in traffic.Prompts(mix, 2**33 + 7).tasks(pairs)]
    c = [t.text for t in traffic.Prompts(mix, 2**33 + 8).tasks(pairs)]
    assert a == b and a != c
    p = traffic.Prompts(mix, 5)
    assert p.tasks(pairs)[0].text != p.tasks(pairs)[0].text


def test_paper_rates():
    mix = traffic.load("sweep")
    modes = collections.Counter(m for _, m in traffic.block(mix))
    n = sum(modes.values())
    assert abs(modes[0] / n - 0.542) < 0.02
    assert abs(modes[1] / n - 0.167) < 0.03
    assert abs(modes[2] / n - 0.292) < 0.02


def test_single_loop_cycles_whole_blocks():
    mix = traffic.load("single")
    seq = traffic.single_sequence(mix, 96)
    want = collections.Counter(traffic.block(mix))
    for k in range(3):
        assert seq[32 * k:32 * k + 32] == traffic.call_sequence(mix)
        assert collections.Counter(seq[32 * k:32 * k + 32]) == want
    # set-up serves every pair the loop can draw, longest first
    warm = traffic.warmup_pairs(mix)
    assert set(warm) == set(want)
    assert mix["classes"][warm[0][0]]["tokens"] == 1000


def test_prompts_encode_to_their_class_length():
    import numpy as np

    from bench import tokenizer
    rng = np.random.default_rng(0)
    for n in (12, 128, 1024):
        text = traffic.prompt_text(rng, n)
        assert len(tokenizer.encode(text)) == n


def test_program_tokenizer_agrees_with_the_reference_copy():
    import numpy as np

    from bench import tokenizer
    from repro.data import tokenizer as tok
    rng = np.random.default_rng(1)
    text = traffic.prompt_text(rng, 256)
    assert tok.encode_aligned([text])[0].tolist() == tokenizer.encode(text)
    ids = rng.integers(0, 40, size=64)
    assert tok.decode(ids) == tokenizer.decode(ids)


def test_every_call_has_the_same_program_shapes():
    """Fresh text, same shapes: each call's prompts encode to the same
    lengths under the same routes, so the step programs the window
    runs are the ones set-up compiled."""
    from bench import tokenizer
    mix = traffic.load("sweep")
    pairs = traffic.call_sequence(mix)
    prompts = traffic.Prompts(mix, 2**33 + 9)
    shapes = [[(len(tokenizer.encode(t.text)), t.kind) for t in
               prompts.tasks(pairs)] for _ in range(3)]
    assert shapes[0] == shapes[1] == shapes[2]
    assert [n for n, _ in shapes[0]] == [
        mix["classes"][c]["tokens"] for c, _ in pairs]
