"""Plain reference of a dense Llama-style decoder, in float32.

Written from the published architecture (Llama; SmolLM-135M and
DeepSeek LLM 7B use it unchanged): token embedding, then per layer
RMSNorm -> grouped-query attention with rotary positions (rotate-half,
``rope_theta``) and a causal mask -> residual, RMSNorm -> SwiGLU MLP ->
residual; a final RMSNorm and the head (the embedding, transposed,
where the embeddings are tied). Query head ``h`` reads key/value head
``h // (heads / kv_heads)``; scores are scaled by ``1/sqrt(head_dim)``.

It imports nothing of the program. Its weights come from
``bench/weights.py`` (the same seed gives the same bf16 values the
program serves); each layer is upcast to float32 inside the scan, so
one layer at a time is wide. Every product runs at ``HIGHEST``
precision, which on the TPU means float32 and not a bfloat16 pass.

``precision="fp8"`` is the control: every operand of every product
(weights, activations, attention scores and probabilities) is rounded
to float8 e4m3 under a per-tensor scale, then multiplied in float32.
"""
from __future__ import annotations

import functools


def _dims(m: dict):
    d, h = m["hidden_size"], m["num_attention_heads"]
    return (d, h, m["num_key_value_heads"], m.get("head_dim") or d // h,
            m["rms_norm_eps"], m["rope_theta"])


@functools.lru_cache(maxsize=None)
def _forward(dims, tied: bool, length: int, precision: str):
    import jax
    import jax.numpy as jnp

    d, h, kv, hd, eps, theta = dims
    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32

    def q8(x):
        if precision != "fp8":
            return x
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(f32) * s

    def mm(spec, a, b):
        return jnp.einsum(spec, q8(a), q8(b), precision=hi)

    def norm(x, scale):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + eps) * scale.astype(f32)

    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=f32) / half))
    ang = jnp.arange(length, dtype=f32)[:, None] * inv      # (T, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]

    def rope(x):                                             # (T, n, hd)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x2 * cos + x1 * sin], axis=-1)

    causal = jnp.tril(jnp.ones((length, length), bool))

    def layer(x, lp):
        lp = jax.tree.map(lambda a: a.astype(f32), lp)
        a = norm(x, lp["attn_norm"]["scale"])
        q = rope(mm("td,de->te", a, lp["attn"]["wq"]).reshape(
            length, h, hd))
        k = rope(mm("td,de->te", a, lp["attn"]["wk"]).reshape(
            length, kv, hd))
        v = mm("td,de->te", a, lp["attn"]["wv"]).reshape(length, kv, hd)
        q = q.reshape(length, kv, h // kv, hd)
        s = mm("qkgd,skd->kgqs", q, k) / jnp.sqrt(f32(hd))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = mm("kgqs,skd->qkgd", p, v).reshape(length, h * hd)
        x = x + mm("te,ed->td", o, lp["attn"]["wo"])
        a = norm(x, lp["mlp_norm"]["scale"])
        g = mm("td,df->tf", a, lp["mlp"]["w_gate"])
        u = mm("td,df->tf", a, lp["mlp"]["w_up"])
        x = x + mm("tf,fd->td", jax.nn.silu(g) * u, lp["mlp"]["w_down"])
        return x, None

    def forward(w, tokens):
        x = w["embedding"][tokens].astype(f32)
        x, _ = jax.lax.scan(layer, x, w["layers"])
        x = norm(x, w["final_norm"]["scale"])
        if tied:
            return mm("td,vd->tv", x, w["embedding"].astype(f32))
        return mm("td,dv->tv", x, w["lm_head"].astype(f32))

    return jax.jit(forward)


def logits(m: dict, w: dict, tokens, precision: str = "f32"):
    """(T, V) float32 logits of one sequence of ``T`` ids."""
    import jax.numpy as jnp
    tokens = jnp.asarray(tokens, jnp.int32)
    fn = _forward(_dims(m), bool(m["tie_word_embeddings"]),
                  int(tokens.shape[0]), precision)
    return fn(w, tokens)
