"""Sharded serving subsystem: a request-parallel device mesh over
per-shard paged KV pools.

Every serving path before this module executed on a single device; the
ROADMAP's north star wants multi-host sharded waves. This module adds
the data-parallel half of that story, exercised on CPU via
``--xla_force_host_platform_device_count``:

* **ServingMesh** — a ``("data",)`` or 2-D ``("data", "model")`` jax
  mesh (built by ``launch.mesh.make_serving_mesh``). Each *data* row
  is one *shard*: an independent serving executor with its own slice
  of every model's KV page pool. When the mesh carries a "model" axis
  each shard's program additionally runs tensor-parallel across its
  model columns: member params shard column-parallel per
  ``sharding.tp.tp_param_specs`` and each page array's kv-head axis
  shards over "model", so per-device page bytes — and therefore
  per-member pool capacity at fixed memory — scale with the
  model-axis size.
* **ShardedPagedKVServer** — one model's paged KV state partitioned
  across the mesh. The device page arrays are one global
  ``(n_shards, L, P, KV, page, Dh)`` array sharded over ``"data"``;
  the host-side allocation state is *per shard*: each shard has its
  own ``PagePool`` (shard-local page ids and LIFO free list), its own
  prompt prefix cache, its own scratch region, and its own ``KVStats``
  — exposed through ``_ShardView`` objects that present the exact
  ``PagedKVServer`` host interface, so the step loop's page plumbing
  (alloc/retain/release/prefix insert/evict-retry) runs unmodified
  against any shard.

Why this is bit-equivalent to single-device execution: a row's decode
is a pure function of (its prompt, its pages, its admission-indexed
sampling key stream). Pages never alias across shards (each shard's
block tables index only its own pool slice), the sampling keys are
keyed by *global* admission index (``sampler.probe_row_keys`` /
``member_row_keys``), and every host decision (placement, grouping,
retirement) is deterministic — so moving a row to a different shard
changes where its bytes live, never what tokens it samples.
``tests/harness/simulate.py --sharded`` proves it end to end:
identical record hashes and artifact-chain heads between data=4 and
single-device step execution over the 200-task duplicate-bearing
stream.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.serving.kv_pool import (
    KVStats, PagedKVServer, PagePool, PagePoolError, kv_page_shape)


class ServingMesh:
    """A ("data",) or ("data", "model") request-parallel serving mesh.

    Thin wrapper over the jax ``Mesh`` adding the placement helpers
    the sharded servers need: ``replicate`` / ``place_params`` (member
    weights) and ``shard_rows`` (per-shard operand stacks, leading
    axis mapped to ``"data"``).
    """

    def __init__(self, data: Optional[int] = None, mesh=None, *,
                 model: int = 1):
        if mesh is None:
            from repro.launch.mesh import make_serving_mesh
            mesh = make_serving_mesh(data, model=model)
        names = tuple(mesh.axis_names)
        if names not in (("data",), ("data", "model")):
            raise ValueError(
                f"serving mesh must be ('data',) or "
                f"('data', 'model'), got {mesh.axis_names}")
        self.mesh = mesh

    @property
    def n_shards(self) -> int:
        return int(self.mesh.shape["data"])

    @property
    def n_model(self) -> int:
        if "model" not in self.mesh.axis_names:
            return 1
        return int(self.mesh.shape["model"])

    def replicate(self, tree):
        """Place a pytree fully replicated across the mesh."""
        return jax.device_put(tree, NamedSharding(self.mesh, P()))

    def place_params(self, cfg: ModelConfig, params):
        """Place one member's params: replicated over "data", and —
        when the mesh carries a "model" axis — column-parallel
        tensor-sharded over it (``sharding.tp.tp_param_specs``; a
        leaf's spec is all-``None`` on the data axis, so replication
        over "data" composes for free). Validates divisibility up
        front so a bad fleet/mesh pairing fails at placement, not
        mid-trace."""
        if self.n_model == 1:
            return self.replicate(params)
        from repro.sharding import tp_check_cfg, tp_param_specs
        tp_check_cfg(cfg, self.n_model)
        specs = tp_param_specs(params)
        return jax.tree_util.tree_map(
            lambda leaf, spec: jax.device_put(
                leaf, NamedSharding(self.mesh, spec)),
            params, specs)

    def shard_rows(self, x):
        """Place an array with its leading axis sharded over "data"."""
        return jax.device_put(x, NamedSharding(self.mesh, P("data")))


class _ShardView(PagedKVServer):
    """One shard's host-side face of a ``ShardedPagedKVServer``.

    Inherits every allocation/prefix-cache/stats method from
    ``PagedKVServer`` — the pool, scratch, and prefix cache are
    genuinely shard-local — but never owns device arrays (``pages``
    stays ``None``; the parent holds the one global sharded pytree)
    and delegates capacity rebuilds to the parent, which must resize
    every shard in lockstep to keep the global arrays rectangular.
    """

    def __init__(self, parent: "ShardedPagedKVServer", index: int,
                 cfg: ModelConfig, **kw):
        self.parent = parent
        self.index = index
        super().__init__(cfg, **kw)

    def _rebuild(self, num_pages: int, scratch_pages: int, key) -> None:
        self.parent._rebuild_all(num_pages, scratch_pages, key)


class ShardedPagedKVServer:
    """Paged KV serving state for one model, partitioned over a
    ``ServingMesh``: shard-local pools/block-tables/free-lists on the
    host, one globally-sharded page array pair on the device mesh."""

    def __init__(self, cfg: ModelConfig, smesh: ServingMesh, *,
                 page_size: int = 8, prefix_cache_entries: int = 32):
        self.cfg = cfg
        self.smesh = smesh
        self.page_size = int(page_size)
        self.pages = None
        self.shards: List[_ShardView] = [
            _ShardView(self, i, cfg, page_size=page_size,
                       prefix_cache_entries=prefix_cache_entries)
            for i in range(smesh.n_shards)]
        self.layout = self.shards[0].layout
        if self.layout not in ("dense", "quant"):
            # ring arenas and recurrent lanes stay single-device for
            # now; ShardedStepLoopRunner routes those members to its
            # dense fallback instead
            raise ValueError(
                f"config {cfg.name!r} resolves to layout "
                f"{self.layout!r}; sharded paged serving supports "
                "'dense' and 'quant' only")

    @property
    def k_pages(self):
        """Global K code leaf (capacity probes read per-shard bytes off
        this); ``self.pages`` is the full layout pytree."""
        return None if self.pages is None else self.pages.get("k")

    @property
    def v_pages(self):
        return None if self.pages is None else self.pages.get("v")

    @property
    def n_shards(self) -> int:
        return self.smesh.n_shards

    @property
    def model_name(self) -> str:
        return self.shards[0].stats.model

    def set_model_name(self, name: str) -> None:
        for sv in self.shards:
            sv.stats.model = name

    # -- capacity ------------------------------------------------------
    def ensure_capacity_stream(self, max_rows_per_shard: int,
                               prompt_len: int, lanes_per_row: int,
                               max_new_tokens: int) -> None:
        """Size every shard for the step loop's per-shard steady state.
        All shards are always sized identically (the global page array
        is rectangular), so checking shard 0 suffices; a rebuild goes
        through ``_rebuild_all`` and resizes the whole set."""
        self.shards[0].ensure_capacity_stream(
            max_rows_per_shard, prompt_len, lanes_per_row,
            max_new_tokens)

    def _rebuild_all(self, num_pages: int, scratch_pages: int,
                     key) -> None:
        self._rebuild_host(num_pages, scratch_pages, key)
        self._rebuild_device(num_pages)

    def _rebuild_host(self, num_pages: int, scratch_pages: int,
                      key) -> None:
        """Shard-local host state: one fresh pool + scratch region per
        shard. Split from the device rebuild so the pool-invariant
        property tests can exercise shard-local free lists without
        allocating device arrays."""
        # phase 1: every shard must be rebuildable before any is
        # touched — a half-rebuilt shard set would desync the global
        # array from the pools
        for sv in self.shards:
            if sv.lost:
                raise PagePoolError(
                    f"cannot rebuild shard {sv.index}: marked lost")
            if sv.pool is not None:
                sv.drop_prefix_cache()
                old_scratch = sv._scratch.size \
                    if sv._scratch is not None else 0
                if sv.pool.pages_in_use > old_scratch:
                    raise PagePoolError(
                        f"cannot rebuild shard {sv.index}'s page pool "
                        "while pages are held")
        for sv in self.shards:
            sv.pool = PagePool(num_pages, self.page_size)
            sv._scratch = sv.pool.alloc(scratch_pages)
            sv._capacity_key = key
            sv.stats.pool_pages = num_pages
            sv._sample_usage()

    def _rebuild_device(self, num_pages: int) -> None:
        import jax.numpy as jnp
        cfg = self.cfg
        shape = (self.n_shards,) + kv_page_shape(cfg, num_pages,
                                                 self.page_size)
        dt = jnp.int8 if self.layout == "quant" \
            else jnp.dtype(cfg.dtype)
        # on a 2-D mesh each model column holds only its kv-head slice
        # of every page (axis 3 of both code and scale leaves) —
        # per-device page bytes shrink by the model-axis size, which
        # is exactly where the capacity gain of tensor parallelism
        # comes from
        spec = P("data", None, None, "model") \
            if self.smesh.n_model > 1 else P("data")

        def put(a):
            return jax.device_put(
                a, NamedSharding(self.smesh.mesh, spec))

        pages = {"k": put(jnp.zeros(shape, dt)),
                 "v": put(jnp.zeros(shape, dt))}
        if self.layout == "quant":
            pages["k_scale"] = put(jnp.zeros(shape[:-1], jnp.float32))
            pages["v_scale"] = put(jnp.zeros(shape[:-1], jnp.float32))
        self.pages = pages

    # -- fault simulation ----------------------------------------------
    def mark_shard_lost(self, index: int) -> None:
        """Simulated shard loss: the shard's host-side pool is
        abandoned in place (pages are forfeited, not released — a dead
        host cannot run a release path) and every allocation or prefix
        lookup against it fails from now on. The device array is left
        as-is; displaced rows re-prefill on surviving shards."""
        self.shards[index].lost = True

    # -- accounting ----------------------------------------------------
    def aggregate_stats(self) -> KVStats:
        """Summed accounting across shards. Pool capacity, high-water
        and reuse counters add (each shard is an independent pool);
        ``page_bytes``/``page_size`` are per-page quantities and stay
        as-is."""
        base = self.shards[0].stats
        out = KVStats(model=base.model, page_size=base.page_size,
                      page_bytes=base.page_bytes)
        for sv in self.shards:
            st = sv.stats
            out.pool_pages += st.pool_pages
            out.pages_in_use += st.pages_in_use
            out.pages_highwater += st.pages_highwater
            out.probe_pages_highwater += st.probe_pages_highwater
            out.prefill_tokens_computed += st.prefill_tokens_computed
            out.prefill_tokens_reused_probe += \
                st.prefill_tokens_reused_probe
            out.prefill_tokens_reused_prefix += \
                st.prefill_tokens_reused_prefix
            out.cow_forks += st.cow_forks
            out.prefill_chunks += st.prefill_chunks
            out.prefix_evictions += st.prefix_evictions
        return out

    def per_shard_pages_in_use(self) -> Dict[int, int]:
        return {sv.index: sv.pool.pages_in_use
                for sv in self.shards if sv.pool is not None}

    def pad_fork_ids(self, k: int) -> np.ndarray:
        """(n_shards, k) self-copy page ids (each shard's first scratch
        page) — the identity fork for shards with nothing to fork."""
        out = np.empty((self.n_shards, k), np.int32)
        for sv in self.shards:
            out[sv.index] = int(sv._scratch[0])
        return out
