"""The main-path Pallas kernels compile for a described TPU v5e.

Interpret mode (``tests/test_kernels.py``, ``tests/test_paged_attention.py``)
checks what the kernels compute, but not whether Mosaic accepts them:
block shapes that break the TPU's (8, 128) tiling rule pass every
interpret-mode test and are refused by the chip's compiler. Here each
kernel is lowered and compiled at smollm-135m's published widths (9
heads, 3 KV heads, head_dim 64, d_model 576, d_ff 1536) for one chip of
a ``v5e:2x2`` topology that is described, not attached — nothing runs,
so this needs no accelerator.

The dense decode megastep is compiled here too, from shapes alone, to
check the structure of what the chip runs: its page pool is donated and
updated in place, so no loop of the program copies a layer's slice of
the pool, or the whole pool, on each tick.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest-xdist
worker imports this file. Keep these compiles in this one file.
"""
import os
import re
from math import prod

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.smollm_135m import CONFIG as SMOLLM
from repro.models import params as params_lib
from repro.sampling import sampler as S
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


# ----------------------------------------------------------------------
# the dense decode megastep: page pool in place
# ----------------------------------------------------------------------
POOL_PAGES, PAGE, BUCKET, TICKS, CACHE_LEN = 330, 8, 4, 4, 1064
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(
    r"^\s*(ROOT )?%([\w.\-]+) = (.+?) ([\w\-]+)\((.*)$")
_MOVES = ("copy", "copy-start", "dynamic-slice", "dynamic-update-slice")


def _computations(text: str) -> dict:
    """name -> [(root, name, shape, opcode, rest of line)] of each
    computation of compiled HLO text."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None and (m := _INSTRUCTION.match(line)):
            cur.append((bool(m.group(1)),) + m.groups()[1:])
    return comps


def _elements(shape: str) -> int:
    """Elements of an array shape, or of a tuple's first member."""
    m = re.search(r"[a-z]+\d*\[([\d,]*)\]", shape)
    return prod(int(d) for d in m.group(1).split(",") if d) if m else 0


def _operands(rest: str) -> list:
    return re.findall(r"%([\w.\-]+)", rest.split(")")[0])


def _loop_moves(text: str, limit: int) -> list:
    """The copies, dynamic slices and dynamic-update-slices inside a
    ``while`` body (or in a fusion rooted in one there) that move at
    least ``limit`` elements: for a dynamic-update-slice its update
    operand, which leaves out an in-place write of a few rows into a
    large buffer; for the others their result."""
    comps = _computations(text)
    shapes = {c: {i[1]: i[2] for i in ins} for c, ins in comps.items()}
    todo = re.findall(r"body=%([\w.\-]+)", text)
    assert todo, "no while loop in the program"
    loops = set()
    while todo:
        c = todo.pop()
        if c in loops or c not in comps:
            continue
        loops.add(c)
        for _, _, _, op, rest in comps[c]:
            if op in ("call", "conditional"):
                todo += re.findall(r"(?:calls|to_apply)=%([\w.\-]+)",
                                   rest)
                todo += re.findall(r"%([\w.\-]+)", rest.split(
                    "branch_computations={")[-1].split("}")[0]) \
                    if "branch_computations" in rest else []
    found = []

    def check(comp, where, ins):
        _, name, shape, op, rest = ins
        if op not in _MOVES:
            return
        moved = (shapes[comp].get(_operands(rest)[1], "")
                 if op == "dynamic-update-slice" else shape)
        if _elements(moved) >= limit:
            found.append(f"{where}: {name} {op} {moved[:48]}")

    for c in loops:
        for ins in comps[c]:
            if ins[3] != "fusion":
                check(c, c, ins)
                continue
            fused = re.search(r"calls=%([\w.\-]+)", ins[4]).group(1)
            by_name = {i[1]: i for i in comps[fused]}
            stack = [i for i in comps[fused] if i[0]]
            while stack:
                root = stack.pop()
                if root[3] in ("bitcast", "tuple"):
                    stack += [by_name[o] for o in _operands(root[4])
                              if o in by_name]
                else:
                    check(fused, f"{c}/{ins[1]}", root)
    return found


def _pool_shape(cfg, pages=POOL_PAGES):
    return (cfg.num_layers, pages, cfg.num_kv_heads, PAGE,
            cfg.resolved_head_dim)


def test_pool_move_check_sees_a_layer_slice_copy(one_chip):
    """The check below is not blind: a layer scan that takes the pool
    as ``xs`` and stacks it back as ``ys`` (the shape of the dense
    decode step before it carried the pool) is caught."""
    shape = _pool_shape(SMOLLM.replace(num_layers=4))

    def step(pool, rows, page_ids):
        def layer(x, pg):
            pg = pg.at[page_ids, :, 0].set(rows)
            return x + pg[page_ids].astype(jnp.float32).sum(), pg
        return jax.lax.scan(layer, jnp.float32(0), pool)

    text = _compile_text(
        step, one_chip, (shape, jnp.bfloat16),
        ((BUCKET, KV, DH), jnp.bfloat16), ((BUCKET,), jnp.int32))
    assert _loop_moves(text, prod(shape[1:]))


def test_dense_megastep_updates_pool_in_place(one_chip):
    """``decode_megastep_rows`` at smollm-135m widths (4 layers, a
    330-page pool, bucket 4, K=4) for one v5e: the pool is donated
    (its bytes alias an output) and no loop of the program copies, or
    slices out, a layer's slice of it or more; the K/V write into the
    carried pool is a few rows."""
    cfg = SMOLLM.replace(num_layers=4, dtype="bfloat16")
    abstract = jax.eval_shape(
        lambda: params_lib.init_params(cfg, jax.random.PRNGKey(0)))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), abstract)
    shape = _pool_shape(cfg)
    pages = {"k": sds(shape, jnp.bfloat16), "v": sds(shape, jnp.bfloat16)}
    nb = -(-CACHE_LEN // PAGE)
    compiled = S.decode_megastep_rows.lower(
        cfg, params, sds((BUCKET, cfg.vocab_size), jnp.bfloat16), pages,
        sds((BUCKET, nb), jnp.int32), sds((BUCKET,), jnp.int32),
        sds((BUCKET, 2), jnp.uint32), sds((BUCKET,), jnp.int32),
        sds((BUCKET,), jnp.bool_), n_ticks=TICKS, cache_len=CACHE_LEN,
        temperature=1.0, eos_id=2, pad_id=0).compile()
    pool_bytes = 2 * prod(shape) * 2
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
    assert _loop_moves(compiled.as_text(), prod(shape[1:])) == []
