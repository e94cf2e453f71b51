"""Kernel micro-benchmarks: wall-time of the jnp reference paths on CPU
(the Pallas kernels target TPU; interpret mode is a correctness tool,
not a perf proxy) at serving-relevant shapes."""
from __future__ import annotations

import time
from pathlib import Path

import jax
import jax.numpy as jnp

from benchmarks.common import csv_line, write_json
from repro.kernels import ref

OUT = Path("experiments/bench/kernels_micro.json")


def _time(fn, *args, iters: int = 10) -> float:
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) \
        else fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        (out[0] if isinstance(out, tuple) else out).block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6   # us


def run(verbose: bool = True) -> dict:
    key = jax.random.PRNGKey(0)
    out = {}

    # decode attention: llama3-8b decode_32k-like per-chip slice
    b, h, kv, dk, s = 8, 32, 8, 128, 4096
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, h, dk), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv, dk), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv, dk), jnp.float32)
    fn = jax.jit(lambda q, k, v: ref.decode_attention_ref(
        q, k, v, jnp.int32(s)))
    out["decode_attention_us"] = _time(fn, q, k, v)

    # selective scan: falcon-mamba chunk
    b, s2, d, n = 2, 1024, 512, 16
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (b, s2, d)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s2, d))) * 0.1
    alog = jax.random.normal(ks[2], (d, n)) * 0.3
    bi = jax.random.normal(ks[3], (b, s2, n))
    ci = jax.random.normal(ks[4], (b, s2, n))
    fn = jax.jit(ref.selective_scan_ref)
    out["selective_scan_us"] = _time(fn, x, dt, alog, bi, ci)

    # rglru scan
    a = jax.random.uniform(ks[0], (2, 1024, 512), minval=.8, maxval=.99)
    u = jax.random.normal(ks[1], (2, 1024, 512)) * 0.1
    fn = jax.jit(ref.rglru_scan_ref)
    out["rglru_scan_us"] = _time(fn, a, u)

    # fused swiglu
    x = jax.random.normal(ks[0], (1024, 1024), jnp.float32) * 0.5
    wg = jax.random.normal(ks[1], (1024, 2816)) * 0.02
    wu = jax.random.normal(ks[2], (1024, 2816)) * 0.02
    wd = jax.random.normal(ks[3], (2816, 1024)) * 0.02
    fn = jax.jit(ref.fused_swiglu_ref)
    out["fused_swiglu_us"] = _time(fn, x, wg, wu, wd)

    write_json(OUT, out)
    if verbose:
        for k_, v_ in out.items():
            print(f"  {k_:24s} {v_:10.1f}")
    return out


def main() -> str:
    t = run(verbose=False)
    return csv_line("kernels_micro", t["decode_attention_us"],
                    "ref_paths_cpu")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
