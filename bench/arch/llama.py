"""Dense Llama-style decoder (SmolLM-135M, DeepSeek LLM 7B): grouped-
query attention with rotary positions, a SwiGLU MLP, RMSNorm; the head
tied to the embedding or its own matrix. Its reference is
``bench/reference/llama.py``, its work counts ``bench/work/dense.py``.
"""
from __future__ import annotations

from bench.reference.llama import logits  # noqa: F401
from bench.work.dense import decode_tick, prefill_chunk  # noqa: F401


def model_config(group: dict, name: str, dtype: str):
    """The program's ``ModelConfig`` for one model group of a
    configuration file."""
    from repro.configs.base import ModelConfig
    d, h = group["hidden_size"], group["num_attention_heads"]
    return ModelConfig(
        name=name, family="dense", num_layers=group["num_hidden_layers"],
        d_model=d, num_heads=h, num_kv_heads=group["num_key_value_heads"],
        d_ff=group["intermediate_size"], vocab_size=group["vocab_size"],
        head_dim=group.get("head_dim") or d // h,
        rope_theta=float(group["rope_theta"]),
        norm_eps=float(group["rms_norm_eps"]),
        max_position=group["max_position_embeddings"],
        tie_embeddings=bool(group["tie_word_embeddings"]), dtype=dtype)


def shapes(m: dict) -> dict:
    """Leaf name -> shape: the program's parameter layout for a dense
    stack (``layers`` stacked along a leading axis), its input format,
    like a checkpoint's."""
    d, f = m["hidden_size"], m["intermediate_size"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m.get("head_dim") or d // h
    n, v = m["num_hidden_layers"], m["vocab_size"]
    out = {
        "embedding": (v, d),
        "final_norm/scale": (d,),
        "layers/attn_norm/scale": (n, d),
        "layers/attn/wq": (n, d, h * hd),
        "layers/attn/wk": (n, d, kv * hd),
        "layers/attn/wv": (n, d, kv * hd),
        "layers/attn/wo": (n, h * hd, d),
        "layers/mlp_norm/scale": (n, d),
        "layers/mlp/w_gate": (n, d, f),
        "layers/mlp/w_up": (n, d, f),
        "layers/mlp/w_down": (n, f, d),
    }
    if not m["tie_word_embeddings"]:
        out["lm_head"] = (d, v)
    return out
