"""Batched executor: one dispatch per same-signature micro-batch.

Feeds the batch's table pytrees to the cached executable from
``PlanCache.get_or_compile_batched`` (which stacks them on a leading axis,
runs the ``jax.vmap``ped plan body, and unstacks per-request results — all
inside one jitted dispatch). Singleton batches take the plain cached
executable — they share it with non-batched traffic, so a signature's first
lonely request doesn't compile a B=1 vmap variant nobody else will use.

With a ``mesh``, eligible batches (more than one device and a batch size the
device count divides — ``core.mesh.can_shard``) take the *sharded*
executable instead (``PlanCache.get_or_compile_sharded``): the stacked batch
axis is split over the mesh's data axis, one slice per device. Ineligible
batches fall back to the single-device vmapped program automatically. An
explicit node-level ``backend`` override ('jnp'/'pallas') takes precedence
over the mesh: the sharded realization lowers per-node to jnp, so honoring
the override means not sharding.

Requests the server flagged *partitioned* — oversized single queries whose
working set busts the per-device memory budget — take
``PlanCache.get_or_compile_partitioned`` instead: one intra-query-sharded
dispatch per request (operators partitioned over the mesh, no batch axis),
executed sequentially within the group.

All request timestamps (``dispatch_t``, ``finish_t``) come from the
executor's own single clock read bracketing the dispatch, so
``finish_t - dispatch_t`` equals the measured dispatch duration exactly —
no skew against a caller's earlier clock read.
"""
from __future__ import annotations

import time
from typing import Callable, Optional

import jax

from repro import tracing
from repro.core import costed_lowering
from repro.core import mesh as mesh_util
from repro.core.plan_cache import LRUCache, PlanCache
from repro.serving.batcher import MicroBatch


class BatchedExecutor:
    def __init__(self, cache: Optional[PlanCache] = None,
                 backend: Optional[str] = None,
                 mesh=None,
                 clock: Callable[[], float] = time.monotonic):
        self.cache = cache or PlanCache()
        self.backend = backend  # node-level lowering override (jnp/pallas)
        self.mesh = mesh        # multi-device batch sharding, when eligible
        self.clock = clock  # same timebase as request timestamps
        self.sharded_dispatches = 0
        self.partitioned_dispatches = 0
        # vmapped-vs-sharded is a costed decision (the shared oracle against
        # the cache's profile); memoized off the dispatch path per
        # (signature, batch size, profile epoch)
        self._realization_memo = LRUCache(256)

    def _use_sharded(self, batch: MicroBatch) -> bool:
        reqs = batch.requests
        if (len(reqs) <= 1 or self.backend is not None
                or not mesh_util.can_shard(self.mesh, len(reqs))):
            return False
        mk = (batch.key, len(reqs), self.cache.profile_epoch)
        dec = self._realization_memo.get(mk)
        if dec is None:
            dec = costed_lowering.choose_batch_realization(
                reqs[0].plan, reqs[0].catalog, len(reqs), self.mesh,
                profile=self.cache.profile)
            self._realization_memo.put(mk, dec)
        return dec == "sharded"

    def dispatch(self, batch: MicroBatch) -> float:
        """Execute the micro-batch; fill each request's result. Returns the
        duration of the (blocking) dispatch on the executor's clock."""
        reqs = batch.requests
        rep = reqs[0]  # same signature => same compiled program; any member
        # oversized single queries (flagged at admission: working set busts
        # the per-device budget) take the partitioned executable — one
        # intra-query-sharded dispatch per request
        partitioned = rep.partitioned and self.mesh is not None
        # an explicit node-level backend override disables sharding: the
        # sharded realization lowers per-node to jnp, and silently serving
        # the same signature with different kernel realizations depending on
        # batch size would discard the caller's choice exactly on the hot
        # (grouped) traffic. Eligible batches still go through the cost
        # oracle: sharding only when the profile predicts it pays.
        sharded = (not partitioned) and self._use_sharded(batch)
        batch.sharded, batch.partitioned = sharded, partitioned
        t0 = self.clock()
        with tracing.span("executor.lookup"):
            if partitioned:
                # the caller's node-level kernel override constrains the
                # partitioned lowering too — partitioning is a distribution
                # choice, not a kernel one, so the two compose
                run = self.cache.get_or_compile_partitioned(
                    rep.plan, rep.catalog, self.mesh, backend=self.backend,
                    cache_key=batch.key)
            elif len(reqs) == 1:
                run = self.cache.get_or_compile(rep.plan, rep.catalog,
                                                backend=self.backend,
                                                cache_key=batch.key)
            elif sharded:
                run = self.cache.get_or_compile_sharded(
                    rep.plan, rep.catalog, len(reqs), self.mesh,
                    cache_key=batch.key)
            else:
                run = self.cache.get_or_compile_batched(
                    rep.plan, rep.catalog, len(reqs), backend=self.backend,
                    cache_key=batch.key)
        # host enqueue (on a cache miss also tracing and compiling), then
        # the host waiting for the device
        with tracing.span("executor.launch"):
            if partitioned:
                results = [run(r.tables) for r in reqs]
            elif len(reqs) == 1:
                results = [run(rep.tables)]
            else:
                results = run(tuple(r.tables for r in reqs))
        with tracing.span("executor.wait"):
            jax.block_until_ready(results)
        # counters record *completed* dispatches only (per batch) — a
        # raising dispatch is the server's failure path
        if partitioned:
            self.partitioned_dispatches += 1
        elif sharded:
            self.sharded_dispatches += 1
        dt = self.clock() - t0
        for req, res in zip(reqs, results):
            req.result = res
            req.done = True
            req.dispatch_t = t0
            req.finish_t = t0 + dt
            req.batch_size = len(reqs)
        return dt
