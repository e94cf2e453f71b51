"""The served tokens' sampling rule, as the program states it.

Each decode lane has its own key stream: the fleet's key is
``PRNGKey(seed)``; a probe sample's key folds in the tag "PROB", the
task's admission index and the sample index; a member's folds in the
tag "EMBE", the member index and the admission index. Step ``t`` of a
lane draws ``categorical(fold_in(key, t), logits / T)``, which JAX
computes as ``argmax(logits / T + gumbel(fold_in(key, t)))``; at
``T == 0`` it is the greedy ``argmax``.

So a served token ``y`` is right when no token beats it on the
reference's logits ``L`` under the same noise:
``gap = max_j (L_j + T g_j) - (L_y + T g_y)`` is zero for an exact
program, and as large as the program's logit error where two tokens
are close. A wrong token leaves a gap of the spread of the logits.
"""
from __future__ import annotations

import functools

PROBE_TAG = 0x5052_4F42       # "PROB"
MEMBER_TAG = 0x454D_4245      # "EMBE"


def probe_key(seed: int, admission: int, sample: int):
    import jax
    k = jax.random.fold_in(jax.random.PRNGKey(seed), PROBE_TAG)
    return jax.random.fold_in(jax.random.fold_in(k, admission), sample)


def member_key(seed: int, admission: int, member: int):
    import jax
    k = jax.random.fold_in(jax.random.PRNGKey(seed), MEMBER_TAG)
    return jax.random.fold_in(jax.random.fold_in(k, member), admission)


@functools.lru_cache(maxsize=None)
def _gaps(steps: int, temperature: float):
    import jax
    import jax.numpy as jnp

    def gaps(ref, other, start, tokens, n, key):
        """ref/other: (T, V) logits; the lane's step t is predicted at
        position ``start + t``. Returns, per step, the gap of the
        served token and of ``other``'s first choice, both read on
        ``ref`` (0 past ``n``)."""
        r = jax.lax.dynamic_slice_in_dim(ref, start, steps, 0)
        o = jax.lax.dynamic_slice_in_dim(other, start, steps, 0)
        if temperature > 0:
            g = jax.vmap(lambda t: jax.random.gumbel(
                jax.random.fold_in(key, t), (ref.shape[1],),
                jnp.float32))(jnp.arange(steps))
            r = r + temperature * g
            o = o + temperature * g
        best = r.max(-1)
        served = jnp.take_along_axis(r, tokens[:, None], 1)[:, 0]
        pick = jnp.argmax(o, -1)
        picked = jnp.take_along_axis(r, pick[:, None], 1)[:, 0]
        live = jnp.arange(steps) < n
        return (jnp.where(live, best - served, 0.0),
                jnp.where(live, best - picked, 0.0))

    return jax.jit(gaps)


def gaps(ref, other, start: int, tokens, key, temperature: float,
         steps: int):
    """Per-step gaps (served, other's pick) of one lane, as numpy."""
    import jax.numpy as jnp
    import numpy as np
    toks = np.zeros(steps, np.int32)
    toks[:len(tokens)] = tokens
    a, b = _gaps(steps, float(temperature))(
        ref, other, jnp.int32(start), jnp.asarray(toks),
        jnp.int32(len(tokens)), key)
    return np.asarray(a), np.asarray(b)
