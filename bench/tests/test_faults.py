"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip, breaks one thing in the
program's step programs where it is produced, and drives the rest of a
run of the test fleet: the window, the capture, the check. The cells
run on one chip, so there is no exchange between chips to leave out.
"""

from repro.sampling import sampler

SEED = 2**33 + 21


def _patch(monkeypatch, name, wrap):
    monkeypatch.setattr(sampler, name, wrap(getattr(sampler, name)))


def test_unbroken_run_is_correct(tiny):
    assert tiny(SEED)["correct"]


def test_a_token_altered_where_it_is_produced(tiny, monkeypatch):
    def wrap(fn):
        def decode(*a, **k):
            emits, dones, lg, pages = fn(*a, **k)
            bad = (emits[0] + 7) % a[0].vocab_size
            return emits.at[0].set(bad), dones, lg, pages
        return decode
    _patch(monkeypatch, "decode_megastep_rows", wrap)
    r = tiny(SEED)
    assert not r["correct"]
    assert r["checks"]["gap_member"]["value"] > \
        r["checks"]["gap_member"]["limit"]


def test_a_step_that_returns_its_state_unchanged(tiny, monkeypatch):
    """Decode launches hand back the pages they were given: the keys
    and values of the tokens they emitted are never written. The
    program donates the pool it is given, so the pages handed back are
    a copy taken before the launch."""
    import jax
    import jax.numpy as jnp

    def wrap(fn):
        def decode(cfg, params, logits, pages, *a, **k):
            before = jax.tree.map(jnp.copy, pages)
            emits, dones, lg, _ = fn(cfg, params, logits, pages, *a, **k)
            return emits, dones, lg, before
        return decode
    _patch(monkeypatch, "decode_megastep_rows", wrap)
    r = tiny(SEED)
    assert not r["correct"]


def test_half_of_a_prefill_batch_left_out(tiny, monkeypatch):
    """A prefill launch computes only the first half of its rows; the
    rest are served the first row's prompt."""
    def wrap(fn):
        def prefill(cfg, params, tokens, *a, **k):
            b = tokens.shape[0]
            if b >= 2:
                tokens = tokens.at[b // 2:].set(tokens[0])
            return fn(cfg, params, tokens, *a, **k)
        return prefill
    _patch(monkeypatch, "prefill_chunk_paged", wrap)
    r = tiny(SEED)
    assert not r["correct"]
