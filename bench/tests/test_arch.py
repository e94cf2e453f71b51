"""Each model group of a fleet goes through the module of its own
architecture (``bench/arch/<model_type>.py``).

The values written here are what the harness gave before it looked
architectures up by group, computed then from the same files: for each
group of both fleets, the program's ``ModelConfig``, the weight tree,
and the digest of the weights' bytes from one seed. The digests are of
a copy of each group cut to 2 layers and 4,096 rows of vocabulary,
widths kept: a 2-layer copy of a DeepSeek LLM 7B member at its full
vocabulary holds 1.24 G parameters, 15 GB on the CPU while it is made.
"""
import hashlib
import json
import sys
import time
import types
from pathlib import Path

import numpy as np
import pytest

from bench import arch, cell, check, weights, work
from bench.arch import llama

CONFIGS = Path(__file__).resolve().parents[1] / "configs"

SMOLLM = dict(
    family="dense", num_layers=30, d_model=576, num_heads=9, num_kv_heads=3,
    d_ff=1536, vocab_size=49152, head_dim=64, rope_theta=10000.0,
    norm_eps=1e-05, max_position=2048, tie_embeddings=True,
    dtype="bfloat16")
DS7B = dict(
    family="dense", num_layers=4, d_model=4096, num_heads=32,
    num_kv_heads=32, d_ff=11008, vocab_size=102400, head_dim=128,
    rope_theta=10000.0, norm_eps=1e-06, max_position=4096,
    tie_embeddings=False, dtype="bfloat16")

SMOLLM_SHAPES = {
    "embedding": (49152, 576), "final_norm/scale": (576,),
    "layers/attn_norm/scale": (30, 576), "layers/attn/wq": (30, 576, 576),
    "layers/attn/wk": (30, 576, 192), "layers/attn/wv": (30, 576, 192),
    "layers/attn/wo": (30, 576, 576), "layers/mlp_norm/scale": (30, 576),
    "layers/mlp/w_gate": (30, 576, 1536), "layers/mlp/w_up": (30, 576, 1536),
    "layers/mlp/w_down": (30, 1536, 576)}
DS7B_SHAPES = {
    "embedding": (102400, 4096), "final_norm/scale": (4096,),
    "layers/attn_norm/scale": (4, 4096), "layers/attn/wq": (4, 4096, 4096),
    "layers/attn/wk": (4, 4096, 4096), "layers/attn/wv": (4, 4096, 4096),
    "layers/attn/wo": (4, 4096, 4096), "layers/mlp_norm/scale": (4, 4096),
    "layers/mlp/w_gate": (4, 4096, 11008), "layers/mlp/w_up": (4, 4096, 11008),
    "layers/mlp/w_down": (4, 11008, 4096), "lm_head": (4096, 102400)}

SMOLLM_DIGEST = \
    "972e653271c25a01d3d4fd245b7f1db2f7e5bc3c6286b1816ef874eb7e7a9de3"
DS7B_DIGEST = \
    "4084b7eda73565998c1cb77bdd93b586a365e3eee106ff235e751b91eb99d95e"
DIGEST_SEED = 2**33 + 5

# (configuration, group index, ModelConfig fields, shapes, digest)
GROUPS = [
    ("smollm-fleet", 0, SMOLLM, SMOLLM_SHAPES, SMOLLM_DIGEST),
    ("smollm-fleet", 1, SMOLLM, SMOLLM_SHAPES, SMOLLM_DIGEST),
    ("ds7b-fleet", 0, SMOLLM, SMOLLM_SHAPES, SMOLLM_DIGEST),
    ("ds7b-fleet", 1, DS7B, DS7B_SHAPES, DS7B_DIGEST),
]
IDS = ["smollm-probe", "smollm-member", "ds7b-probe", "ds7b-member"]


def _group(config: str, index: int):
    conf = json.loads((CONFIGS / f"{config}.json").read_text())
    return conf, cell.groups(conf)[index]


def digest(tree: dict) -> str:
    """sha256 over every leaf in path order: its path, shape, dtype and
    bytes."""
    h = hashlib.sha256()

    def walk(path, node):
        if isinstance(node, dict):
            for k in sorted(node):
                walk(path + [k], node[k])
            return
        a = np.asarray(node)
        h.update(f"{'/'.join(path)}:{a.shape}:{a.dtype};".encode())
        h.update(a.tobytes())
    walk([], tree)
    return h.hexdigest()


@pytest.mark.parametrize("config,index,want,want_shapes,_",
                         GROUPS, ids=IDS)
def test_published_groups_dispatch_to_what_the_harness_gave(
        config, index, want, want_shapes, _):
    from repro.configs.base import ModelConfig
    conf, g = _group(config, index)
    mod = arch.of(g)
    assert mod is llama
    name = f"{config}-{'probe' if index == 0 else 'member'}"
    assert mod.model_config(g, name, conf["dtype"]) == \
        ModelConfig(name=name, **want)
    assert mod.shapes(g) == want_shapes


@pytest.mark.parametrize("config,index,_,__,want", GROUPS, ids=IDS)
def test_weight_bytes_from_a_seed_are_what_the_harness_made(
        config, index, _, __, want):
    conf, g = _group(config, index)
    cut = dict(g, num_hidden_layers=2,
               vocab_size=min(g["vocab_size"], 4096))
    assert digest(weights.make(cut, DIGEST_SEED, conf["dtype"])) == want


FUNCS = ("model_config", "shapes", "logits", "decode_tick",
         "prefill_chunk")


def test_each_group_of_a_run_goes_through_its_own_architecture(
        monkeypatch):
    """The tiny fleet's members name a test-only architecture that
    delegates to ``llama``; its probe names none. A run on the CPU is
    correct, and every call for the members went through the test
    module, every call for the probe through ``llama``."""
    from conftest import tiny_cell

    calls = []

    def recording(tag, name, fn):
        def rec(group, *a, **k):
            calls.append((tag, name, group.get("model_type", "llama")))
            return fn(group, *a, **k)
        return rec

    recorder = types.ModuleType("bench.arch.recorder")
    for name in FUNCS:
        fn = getattr(llama, name)
        setattr(recorder, name, recording("recorder", name, fn))
        monkeypatch.setattr(llama, name, recording("llama", name, fn))
    monkeypatch.setitem(sys.modules, "bench.arch.recorder", recorder)

    windows = []
    run_check = check.run

    def keep(win, *a, **k):
        windows.append(win)
        return run_check(win, *a, **k)
    monkeypatch.setattr(check, "run", keep)

    data = tiny_cell()
    data[1]["model_type"] = "recorder"        # the member group
    r = cell.run("tiny", 2**33 + 31, 1.0, False, t_start=time.time(),
                 require_tpu=False, cache=False, cell_data=data)
    assert r["correct"], r["checks"]
    (win,) = windows
    for c in win.calls:
        for ln in c.launches:
            work.launch(win, ln)

    for name in FUNCS:
        seen = {(tag, group) for tag, n, group in calls if n == name}
        assert seen == {("llama", "llama"), ("recorder", "recorder")}, \
            (name, seen)
    built = [(tag, group) for tag, n, group in calls
             if n == "model_config"]
    assert sorted(built) == [("llama", "llama")] + \
        [("recorder", "recorder")] * 3
