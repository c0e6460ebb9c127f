"""Compiled-plan cache: skip lowering AND jax tracing for repeated queries.

``PlanCache.get_or_compile(plan, catalog)`` returns a jitted callable
``run(tables) -> Table`` keyed by the plan's structural+physical signature
plus the catalog's schema signature (table/column names, dtypes, static
shapes — anything that would force a retrace). Two structurally identical
plans over same-shaped catalogs share one compiled executable; fresh table
*contents* flow through as arguments, so parameterized / repeated query
traffic pays tracing exactly once. Referenced ML functions contribute their
name + architecture (atom kinds, parameter shapes/dtypes) to the key; weight
*values* are assumed stable per name (model-registry contract) — an in-place
weight update that keeps name and shapes needs a fresh name or cache.

Lowering inside the cache is *cost-driven* (``core.costed_lowering``
against the cache's ``DeviceProfile``), and the chosen realization vector
is part of ``key()`` (the ``#cl=...`` suffix). ``recalibrate(profile)`` —
the serving feedback loop's entry point — bumps ``profile_epoch``, which
invalidates the per-signature lowering memo: a recalibrated profile that
changes a lowering decision selects a *different* executable under a new
key instead of aliasing the stale one (equal decisions keep sharing the
old entry, which is exactly right — every realization computes the same
result, only the predicted latency moved).

``get_or_compile_batched(plan, catalog, batch_size)`` is the serving tier's
entry point (repro.serving): same key plus a ``#vmap=B`` suffix, and the
compiled executable is one ``jax.vmap``ped dispatch over B same-signature
table pytrees stacked on a leading axis — N structurally identical in-flight
queries pay one dispatch instead of N.

``get_or_compile_sharded(plan, catalog, batch_size, mesh)`` realizes the
same micro-batch on a multi-device mesh (``backend="sharded"``): the stacked
batch axis is ``shard_map``ped over the mesh's data axis, with automatic
fallback to the vmapped single-device program when the batch doesn't divide
the device count or only one device exists.

``get_or_compile_partitioned(plan, catalog, mesh)`` is the intra-query
counterpart for a *single oversized* query: lowering opens per-node
``PartSpec`` candidates (operators partitioned over the mesh's data axis,
explicit ``PRepartition`` collectives) under the profile's per-device
``memory_budget``, and the chosen plan runs inside ``shard_map`` with
replicated inputs/outputs. ``key(plan, catalog, mesh=...)`` exposes the
matching key (the ``pt*`` decision tokens are the PartSpec vector).

``LRUCache`` + ``CacheStats`` are the shared bounded-cache machinery (also
used to bound the QueryEmbedder's embedding cache).
"""
from __future__ import annotations

import dataclasses
import weakref
from collections import OrderedDict
from typing import Any, Callable, Dict, Hashable, Optional

import jax
import jax.numpy as jnp

from repro import tracing
from repro.core import costed_lowering, ir
from repro.core import physical as ph
from repro.core.cost import DeviceProfile
from repro.relational.table import Table


@dataclasses.dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    evictions: int = 0

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def as_dict(self) -> Dict[str, float]:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions, "hit_rate": self.hit_rate}


class LRUCache:
    """Size-capped mapping with LRU eviction and hit/miss accounting."""

    def __init__(self, maxsize: int = 128):
        self.maxsize = max(1, int(maxsize))
        self._data: "OrderedDict[Hashable, Any]" = OrderedDict()
        self.stats = CacheStats()

    def get(self, key: Hashable, default=None):
        if key in self._data:
            self._data.move_to_end(key)
            self.stats.hits += 1
            return self._data[key]
        self.stats.misses += 1
        return default

    def put(self, key: Hashable, value) -> None:
        if key in self._data:
            self._data.move_to_end(key)
        self._data[key] = value
        while len(self._data) > self.maxsize:
            self._data.popitem(last=False)
            self.stats.evictions += 1

    def __len__(self) -> int:
        return len(self._data)

    def __contains__(self, key: Hashable) -> bool:
        return key in self._data

    def clear(self) -> None:
        self._data.clear()


def scan_table_names(plan: ir.Plan) -> tuple:
    """The catalog tables a plan actually reads, sorted."""
    return tuple(sorted({n.table for n in ir.walk(plan.root)
                         if isinstance(n, ir.Scan)}))


def schema_signature(catalog: ir.Catalog,
                     names: Optional[tuple] = None) -> str:
    """Static catalog shape: anything that changes the traced program.

    ``names`` restricts the signature to the given tables — ``PlanCache.key``
    passes the plan's scanned tables, so catalog entries a plan never reads
    cannot force a false cache miss (and a retrace) when they appear, change
    shape, or disappear. ``None`` signs the whole catalog.
    """
    if names is None:
        names = sorted(catalog.tables)
    parts = []
    for name in names:
        t = catalog.tables[name]
        cols = ",".join(f"{c}:{t.columns[c].dtype}:{t.columns[c].shape}"
                        for c in sorted(t.columns))
        parts.append(f"{name}[{t.capacity}]({cols})")
    return ";".join(parts)


def _plan_fn_names(plan: ir.Plan):
    names = set()

    def from_expr(e: ir.Expr):
        if isinstance(e, ir.Call):
            names.add(e.fn)
        for c in e.children():
            from_expr(c)

    for node in ir.walk(plan.root):
        if isinstance(node, ir.Filter):
            from_expr(node.pred)
        elif isinstance(node, ir.Project):
            for _, e in node.outputs:
                from_expr(e)
        elif isinstance(node, (ir.BlockedMatmul, ir.ForestRelational)):
            names.add(node.fn)
    return sorted(names)


def registry_signature(plan: ir.Plan) -> str:
    """Architecture signature of every ML function the plan references:
    atom kinds + parameter shapes/dtypes (cheap — no weight hashing). Guards
    the name-identity assumption against same-named functions with different
    architectures; a weight update that keeps name AND shapes must bump the
    function name (or use a fresh cache) to invalidate."""
    parts = []
    for name in _plan_fn_names(plan):
        try:
            fn = plan.registry.get(name)
        except KeyError:
            parts.append(f"{name}:?")
            continue
        if fn.graph is None:
            parts.append(f"{name}:opaque")
            continue
        atoms = []
        for n in fn.graph.nodes:
            ps = ",".join(
                f"{k}={getattr(v, 'shape', v)}:{getattr(v, 'dtype', '')}"
                for k, v in sorted(n.atom.params.items()))
            atoms.append(f"{n.atom.kind}({ps})@{n.atom.backend}")
        parts.append(f"{name}:{'|'.join(atoms)}")
    return ";".join(parts)


class PlanCache:
    """Signature-keyed cache of compiled (jitted) plan executables."""

    def __init__(self, maxsize: int = 64,
                 profile: Optional[DeviceProfile] = None):
        self._cache = LRUCache(maxsize)
        self.traces = 0  # times jax actually (re)traced a cached executable
        self._profile = profile  # lazily detected; see profile property
        self.profile_epoch = 0   # bumped by recalibrate()
        # per-(signature, backend, epoch) costed-lowering results: warm
        # dispatches pay one LRU lookup, not a candidate enumeration
        self._lowered = LRUCache(256)
        # times a request was served by something other than what it asked
        # for: 'sharded' / 'partitioned' -> the single-device executable,
        # 'budget_pruned_all' -> a lowering whose every candidate busted
        # the memory budget (so the chosen plan does not fit)
        self.fallbacks: Dict[str, int] = {}

    def _fell_back(self, what: str) -> None:
        self.fallbacks[what] = self.fallbacks.get(what, 0) + 1

    @property
    def stats(self) -> CacheStats:
        return self._cache.stats

    @property
    def profile(self) -> DeviceProfile:
        """The device profile lowering decisions are costed against."""
        if self._profile is None:
            self._profile = DeviceProfile.detect()
        return self._profile

    def recalibrate(self, profile: DeviceProfile) -> None:
        """Install a (feedback-calibrated) profile. Bumping the epoch
        re-derives lowering decisions on the next dispatch of every
        signature; signatures whose decisions change get fresh cache keys
        (no stale-executable aliasing), unchanged ones keep their entry."""
        self._profile = profile
        self.profile_epoch += 1

    def base_key(self, plan: ir.Plan, catalog: ir.Catalog) -> str:
        # sign only the tables the plan scans: the traced program never sees
        # the rest of the catalog, so an unrelated table must not over-key
        # the cache into a false miss (see schema_signature)
        return (plan.signature()
                + "@" + schema_signature(catalog, scan_table_names(plan))
                + "@" + registry_signature(plan))

    def key(self, plan: ir.Plan, catalog: ir.Catalog, *, mesh=None,
            backend: Optional[str] = None) -> str:
        """Full executable key: base signature + the realization vector the
        costed lowering chose under the cache's current profile.

        With ``mesh`` given (and more than one device on it), the key is
        the *partitioned* realization's: ``#be=part#mesh=...`` plus the
        decision vector of the PartSpec-aware lowering — the ``pt*`` site
        tokens in the ``#cl=`` suffix ARE the PartSpec vector, so two
        queries only share a partitioned executable when every node's
        partitioning decision agrees. The serving tier keys oversized
        single queries this way (``QueryServer.submit``); ``backend`` is
        the caller's node-level kernel override, mirrored into the
        partitioned lowering so the key matches what
        ``get_or_compile_partitioned`` will compile."""
        from repro.core import mesh as mesh_util

        base = self.base_key(plan, catalog)
        ways = mesh_util.batch_ways(mesh) if mesh is not None else 1
        if ways > 1:
            base = f"{base}#be=part#mesh={mesh_util.mesh_signature(mesh)}"
            if backend is not None:
                base = f"{base}#nbe={backend}"
            low = self._lowered_for(plan, catalog, base, backend, ways=ways)
        else:
            low = self._lowered_for(plan, catalog, base, None)
        return base + "#cl=" + low.signature

    def _lowered_for(self, plan: ir.Plan, catalog: ir.Catalog,
                     keyed: str, backend: Optional[str], ways: int = 1
                     ) -> costed_lowering.Lowered:
        """Costed-lowering result for ``plan``, memoized per (signature,
        backend, profile epoch, *catalog object*) — ``keyed`` must already
        include the ``#be=`` suffix when ``backend`` is set, and the
        ``#be=part#mesh=`` suffix when ``ways > 1``.

        Catalog identity matters because compaction decisions are sized
        from the catalog's *data* (exact row counts), which the schema-only
        signature cannot see: a different same-schema catalog re-derives
        its own decisions (and, via the ``#cl=`` key suffix, its own
        executable when the counts differ enough to change a capacity).
        The weakref guards id reuse by a freed catalog."""
        mk = (keyed, self.profile_epoch, id(catalog))
        hit = self._lowered.get(mk)
        if hit is not None and hit[0]() is catalog:
            return hit[1]
        low = costed_lowering.lower_costed(plan, catalog,
                                           profile=self.profile,
                                           backend=backend, ways=ways)
        if low.budget_pruned_all:
            self._fell_back("budget_pruned_all")
        self._lowered.put(mk, (weakref.ref(catalog), low))
        return low

    @staticmethod
    def _strip_cl(key: str) -> str:
        """Drop a stale ``#cl=`` decision suffix — and any ``#be=``
        realization suffix preceding it — from a caller-memoized key (both
        are re-derived against the current profile epoch / entry point)."""
        return key.split("#be=", 1)[0].split("#cl=", 1)[0]

    def get_or_compile(self, plan: ir.Plan, catalog: ir.Catalog,
                       *, backend: Optional[str] = None,
                       cache_key: Optional[str] = None
                       ) -> Callable[[Dict[str, Table]], Table]:
        """``cache_key`` lets hot callers (the serving tier memoizes it at
        admission) skip the signature walk on warm dispatches; it must equal
        ``self.key(plan, catalog)``."""
        base = self._strip_cl(cache_key if cache_key is not None
                              else self.base_key(plan, catalog))
        if backend is not None:
            base = f"{base}#be={backend}"
        low = self._lowered_for(plan, catalog, base, backend)
        key = base + "#cl=" + low.signature
        fn = self._cache.get(key)
        if fn is None:
            pplan = low.plan
            names = scan_table_names(plan)

            def traced(tables: Dict[str, Table]) -> Table:
                self.traces += 1  # python side effect: runs only while tracing
                return ph.run(pplan, tables)

            jfn = _jit_named(traced, "plan_single", key, 1)

            def fn(tables: Dict[str, Table]) -> Table:
                # normalize to the scanned tables only: full-catalog and
                # restricted callers share one traced structure (and one
                # trace), and unused tables never cross the jit boundary
                return jfn({k: tables[k] for k in names})

            # the executable's own jax lowering, e.g. for
            # ``fn.lower(tables).compile().as_text()``
            fn.lower = lambda tables: jfn.lower({k: tables[k] for k in names})
            self._cache.put(key, fn)
        return fn

    def get_or_compile_batched(self, plan: ir.Plan, catalog: ir.Catalog,
                               batch_size: int, *,
                               backend: Optional[str] = None,
                               cache_key: Optional[str] = None):
        """One vmapped dispatch over ``batch_size`` same-signature queries.

        Returns ``run(tables_seq) -> tuple[Table, ...]`` taking a sequence
        of ``batch_size`` same-schema ``{name: Table}`` dicts (fresh
        contents per query — the signature grouping guarantees the shapes
        agree). Stacking onto the leading batch axis, the vmapped plan
        body, and the per-query unstacking are all one jitted XLA program:
        a micro-batch costs a single dispatch, which is the whole point
        (per-dispatch overhead dominates repeated small queries). The batch
        size is part of the cache key — the serving scheduler's admission
        policy bounds how many distinct sizes traffic can create. All
        physical operators are mask/capacity-based with static shapes,
        which is what makes the plan body vmap-safe
        (tests/test_serving_batched.py proves batched == sequential on all
        12 workloads).
        """
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        base = self._strip_cl(cache_key if cache_key is not None
                              else self.base_key(plan, catalog))
        if backend is not None:
            base = f"{base}#be={backend}"
        low = self._lowered_for(plan, catalog, base, backend)
        key = base + "#cl=" + low.signature + f"#vmap={batch_size}"
        return self._get_or_compile_stacked(key, low.plan, plan, catalog,
                                            batch_size, kind="batched")

    def _get_or_compile_stacked(self, key: str, pplan, plan: ir.Plan,
                                catalog: ir.Catalog, batch_size: int, *,
                                kind: str,
                                wrap: Optional[Callable] = None):
        """Shared body of the batched/sharded entries: stack ``batch_size``
        same-schema table dicts on a leading axis, run the vmapped plan body
        (optionally transformed by ``wrap``, e.g. shard_map over a mesh),
        and unstack per-query results — all one jitted program under
        ``key``. Keeping one implementation keeps trace accounting, payload
        restriction to scanned tables, and the batch-size guard identical
        across realizations."""
        fn = self._cache.get(key)
        if fn is None:
            names = scan_table_names(plan)

            def batch_body(stacked):
                return jax.vmap(lambda tables: ph.run(pplan, tables))(stacked)

            body = wrap(batch_body) if wrap is not None else batch_body

            def traced(tables_seq):
                self.traces += 1  # python side effect: runs only while tracing
                with jax.named_scope("scan"):
                    stacked = stack_tables(list(tables_seq))
                out = body(stacked)
                with jax.named_scope("scan"):
                    return tuple(unstack_table(out, i)
                                 for i in range(batch_size))

            jfn = _jit_named(traced, f"plan_{kind}{batch_size}", key,
                             batch_size)

            def fn(tables_seq):
                if len(tables_seq) != batch_size:
                    raise ValueError(
                        f"{kind} executable compiled for batch_size="
                        f"{batch_size}, got {len(tables_seq)} table dicts")
                return jfn(tuple({k: t[k] for k in names}
                                 for t in tables_seq))

            self._cache.put(key, fn)
        return fn

    def get_or_compile_sharded(self, plan: ir.Plan, catalog: ir.Catalog,
                               batch_size: int, mesh, *,
                               cache_key: Optional[str] = None):
        """Multi-device variant of ``get_or_compile_batched``: the stacked
        batch axis of the micro-batch is ``shard_map``ped over ``mesh``'s
        data axis, so each device runs the vmapped plan body on its
        ``batch_size / ways`` slice. The batch axis is embarrassingly
        parallel (no cross-query communication), which is why this needs no
        operator changes — weights and other closed-over arrays replicate.

        The realization is first-class in the cache key
        (``#be=sharded#vmap=B#mesh=...``), keeping it distinct from the
        single-device vmapped executable of the same plan and batch size.
        Ineligible calls — a single-device mesh, or a ``batch_size`` the
        device count doesn't divide (``core.mesh.can_shard``, the same
        divisibility-fitting policy as ``models.sharding``) — fall back to
        the plain batched executable under *its* key, so fallback traffic
        shares the existing entry instead of compiling a duplicate; each
        one counts in ``fallbacks['sharded']``.
        """
        from repro.core import mesh as mesh_util

        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        if not mesh_util.can_shard(mesh, batch_size):
            self._fell_back("sharded")
            return self.get_or_compile_batched(plan, catalog, batch_size,
                                               cache_key=cache_key)
        base = self._strip_cl(cache_key if cache_key is not None
                              else self.base_key(plan, catalog))
        base = f"{base}#be=sharded"
        low = self._lowered_for(plan, catalog, base, "sharded")
        key = (base + "#cl=" + low.signature + f"#vmap={batch_size}"
               + f"#mesh={mesh_util.mesh_signature(mesh)}")
        return self._get_or_compile_stacked(
            key, low.plan, plan, catalog, batch_size, kind="sharded",
            wrap=lambda body: mesh_util.shard_batch(body, mesh))

    def get_or_compile_partitioned(self, plan: ir.Plan, catalog: ir.Catalog,
                                   mesh, *, backend: Optional[str] = None,
                                   cache_key: Optional[str] = None):
        """One *intra-query-sharded* executable for a single oversized
        query: lowering opens per-node ``PartSpec`` candidates
        (``ways = batch_ways(mesh)``), rejects candidates whose per-device
        ``phys_peak_memory`` busts the profile's ``memory_budget``, and the
        chosen plan — explicit ``PRepartition`` collectives included — runs
        inside ``shard_map`` over the mesh's data axis with replicated
        inputs/outputs (``core.mesh.shard_replicated``). Unlike
        ``get_or_compile_sharded`` there is no batch axis: the *operators*
        are partitioned (PCrossJoin by left rows, PJoin by probe rows or
        hash bucket, pipelines/ML by row block), which is what lets one
        query larger than a device use the whole mesh.

        Returns ``run(tables) -> Table`` like ``get_or_compile``. The
        realization is first-class in the key
        (``#be=part#mesh=...#cl=...`` — the ``pt*`` decision tokens are
        the PartSpec vector). ``backend`` constrains every node's *kernel*
        realization exactly as in ``get_or_compile`` (partitioning is a
        distribution choice, orthogonal to the caller's kernel choice).
        Single-device meshes, and lowerings that decide partitioning does
        not pay (every PartSpec replicated), fall back to the plain
        executable under *its* key — no duplicate compilation — and count
        in ``fallbacks['partitioned']``."""
        from repro.core import mesh as mesh_util

        ways = mesh_util.batch_ways(mesh) if mesh is not None else 1
        if ways <= 1:
            self._fell_back("partitioned")
            return self.get_or_compile(plan, catalog, backend=backend,
                                       cache_key=cache_key)
        base = self._strip_cl(cache_key if cache_key is not None
                              else self.base_key(plan, catalog))
        base = f"{base}#be=part#mesh={mesh_util.mesh_signature(mesh)}"
        if backend is not None:
            base = f"{base}#nbe={backend}"
        low = self._lowered_for(plan, catalog, base, backend, ways=ways)
        if low.plan.ways <= 1:
            # the oracle kept every node replicated: the partitioned
            # program would be the plain one run redundantly on every
            # device — share the plain executable instead
            self._fell_back("partitioned")
            return self.get_or_compile(plan, catalog, backend=backend)
        key = base + "#cl=" + low.signature
        fn = self._cache.get(key)
        if fn is None:
            pplan = low.plan
            names = scan_table_names(plan)

            def traced(tables: Dict[str, Table]) -> Table:
                self.traces += 1  # python side effect: runs only while tracing
                return ph.run(pplan, tables, axis=mesh_util.DATA_AXIS)

            jfn = _jit_named(mesh_util.shard_replicated(traced, mesh),
                             "plan_partitioned", key, 1)

            def fn(tables: Dict[str, Table]) -> Table:
                return jfn({k: tables[k] for k in names})

            self._cache.put(key, fn)
        return fn

    def __call__(self, plan: ir.Plan, catalog: ir.Catalog) -> Table:
        """Convenience: compile-or-reuse, then execute on catalog tables."""
        return self.get_or_compile(plan, catalog)(dict(catalog.tables))


def _jit_named(fun: Callable, name: str, key: str, batch_size: int):
    """``jax.jit(fun)`` compiled as module ``jit_<name>``, so that a trace's
    modules tell the single, batched, sharded and partitioned executables
    apart (the name is also part of the persistent compile cache's key).
    The first call records the executable's operator scopes
    (``tracing.record_executable``); the lowering and the compiled
    executable are then JAX's in-memory cache hits."""
    def named(*args):
        return fun(*args)

    named.__name__ = named.__qualname__ = name
    jfn = jax.jit(named)
    recorded = []

    def call(*args):
        out = jfn(*args)
        if not recorded:
            recorded.append(key)
            tracing.record_executable(key, batch_size,
                                      jfn.lower(*args).compile())
        return out

    call.lower = jfn.lower
    return call


def stack_tables(tables_list) -> Dict[str, Table]:
    """Stack N same-schema ``{name: Table}`` dicts on a new leading axis.

    All dicts must share one schema signature (same table names, column
    names, dtypes, capacities) — exactly the property the serving tier's
    signature grouping guarantees.
    """
    if not tables_list:
        raise ValueError("stack_tables needs at least one table dict")
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *tables_list)


def unstack_table(batched: Table, i: int) -> Table:
    """Slice query ``i``'s result out of a batched executable's output."""
    return jax.tree_util.tree_map(lambda x: x[i], batched)


GLOBAL_PLAN_CACHE = PlanCache()
