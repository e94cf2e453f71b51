"""Production meshes.

Defined as FUNCTIONS (not module-level constants) so importing this
module never touches jax device state — the dry-run sets
``--xla_force_host_platform_device_count`` before first jax init, and
smoke tests must keep seeing one device.

Target hardware: TPU v5e pods — 256 chips/pod; per chip 197 TFLOP/s
bf16, 819 GB/s HBM and 1,600 Gbit/s of chip-to-chip interconnect
(Google Cloud, "TPU v5e"; the table is ``benchmarks/roofline.PEAKS``).
Single pod is a (16, 16) ("data", "model") mesh; multi-pod prepends a
"pod" axis that extends data parallelism (gradient all-reduce crosses
pods in training; pure request parallelism in serving).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

from repro.sharding import MULTI_POD_RULES, SINGLE_POD_RULES


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == n:
        return jax.make_mesh(shape, axes)
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — run "
            "under launch/dryrun.py, which forces 512 host devices")
    dev = np.asarray(devices[:n]).reshape(shape)
    return Mesh(dev, axes)


def make_serving_mesh(data: Optional[int] = None,
                      model: int = 1) -> Mesh:
    """Request-parallel mesh for the sharded serving subsystem: each
    "data" shard owns a slice of the paged KV pool and decodes its
    resident rows. ``model > 1`` adds a second ("model",) axis so each
    data shard runs ensemble members tensor-parallel across ``model``
    devices (heads/kv_heads/ff shard per ``sharding/partitioning.py``);
    ``model=1`` keeps the 1-D ("data",) mesh byte-compatible with the
    pre-2-D subsystem. ``data=None`` takes every visible device (divided
    by ``model``). On CPU, run under
    ``--xla_force_host_platform_device_count=N`` (see
    ``repro.xla_flags.force_host_device_count``) to get N devices."""
    devices = jax.devices()
    m = int(model)
    if m < 1:
        raise ValueError("serving mesh needs model >= 1")
    n = (len(devices) // m) if data is None else int(data)
    if n < 1:
        raise ValueError("serving mesh needs at least one shard")
    need = n * m
    if len(devices) < need:
        raise RuntimeError(
            f"serving mesh data={n} model={m} needs {need} devices, "
            f"have {len(devices)} — on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} before jax "
            "initialises (repro.xla_flags.force_host_device_count)")
    if m == 1:
        return Mesh(np.asarray(devices[:n]), ("data",))
    dev = np.asarray(devices[:need]).reshape(n, m)
    return Mesh(dev, ("data", "model"))


def make_smoke_mesh(model: int = 1) -> Mesh:
    """1xN mesh over however many devices exist (tests/examples)."""
    devices = jax.devices()
    n = len(devices)
    assert n % model == 0
    dev = np.asarray(devices).reshape(n // model, model)
    return Mesh(dev, ("data", "model"))


def rules_for(mesh: Mesh) -> dict:
    return MULTI_POD_RULES if "pod" in mesh.axis_names \
        else SINGLE_POD_RULES


def mesh_chip_count(mesh: Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))
