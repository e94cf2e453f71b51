"""The main-path Pallas kernels compile for a described TPU v5e.

Interpret mode (``tests/test_kernels.py``, ``tests/test_paged_attention.py``)
checks what the kernels compute, but not whether Mosaic accepts them:
block shapes that break the TPU's (8, 128) tiling rule pass every
interpret-mode test and are refused by the chip's compiler. Here each
kernel is lowered and compiled at smollm-135m's published widths (9
heads, 3 KV heads, head_dim 64, d_model 576, d_ff 1536) for one chip of
a ``v5e:2x2`` topology that is described, not attached — nothing runs,
so this needs no accelerator.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest-xdist
worker imports this file. Keep these compiles in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.smollm_135m import CONFIG as SMOLLM
from repro.kernels.chunked_prefill_attention import chunked_prefill_attention
from repro.kernels.fused_swiglu import fused_swiglu
from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.kernels.paged_decode_attention_quant import (
    paged_decode_attention_quant)

H, KV, DH = SMOLLM.num_heads, SMOLLM.num_kv_heads, SMOLLM.resolved_head_dim
D, F = SMOLLM.d_model, SMOLLM.d_ff
ROWS, PAGES, NB, CHUNK = 8, 64, 4, 8
DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("page_size", [8, 16])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_paged_decode_compiles(one_chip, dtype, page_size):
    dt = DTYPES[dtype]
    text = _compile_text(
        paged_decode_attention, one_chip,
        ((ROWS, H, DH), dt), ((PAGES, KV, page_size, DH), dt),
        ((PAGES, KV, page_size, DH), dt), ((ROWS, NB), jnp.int32),
        ((ROWS,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("page_size", [8, 16])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_chunked_prefill_compiles(one_chip, dtype, page_size):
    dt = DTYPES[dtype]

    def fn(q, kp, vp, table, qpos):
        return chunked_prefill_attention(q, kp, vp, table, qpos,
                                         prompt_len=NB * page_size - 3)

    text = _compile_text(
        fn, one_chip,
        ((ROWS, CHUNK, H, DH), dt), ((PAGES, KV, page_size, DH), dt),
        ((PAGES, KV, page_size, DH), dt), ((ROWS, NB), jnp.int32),
        ((ROWS, CHUNK), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("page_size", [8, 16])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_paged_decode_quant_compiles(one_chip, dtype, page_size):
    dt = DTYPES[dtype]
    text = _compile_text(
        paged_decode_attention_quant, one_chip,
        ((ROWS, H, DH), dt),
        ((PAGES, KV, page_size, DH), jnp.int8),
        ((PAGES, KV, page_size), jnp.float32),
        ((PAGES, KV, page_size, DH), jnp.int8),
        ((PAGES, KV, page_size), jnp.float32),
        ((ROWS, NB), jnp.int32), ((ROWS,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("tokens", [ROWS * CHUNK, 512])
def test_fused_swiglu_compiles(one_chip, tokens):
    dt = jnp.bfloat16
    text = _compile_text(
        fused_swiglu, one_chip,
        ((tokens, D), dt), ((D, F), dt), ((D, F), dt), ((F, D), dt))
    assert "tpu_custom_call" in text
