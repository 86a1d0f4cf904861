"""Launch-plan cache for the batched query engine (DESIGN.md §9).

A *plan* is a jit-compiled batched query loop specialised to one
(graph, kernel, batch width) combination: the closure captures the graph's
device arrays, so XLA constant-folds the operand layout, and the while-loop
is traced exactly once per plan. Serving traffic re-traces nothing — the
planner looks plans up by a :class:`PlanKey` built from

  - the graph's **structure fingerprint** (content hash of the ELL layout —
    two `GraphMatrix` wrappers around the same adjacency share plans),
  - the **kernel** name (msbfs / mskhop / ppr),
  - **backend**, **tile_dim**, and the **bucket layout** (per-bucket
    (rows, width) pairs — the bucketed dispatch bakes slab shapes into the
    trace, so a different bucketing is a different program),
  - the **padded batch width** (frontier columns after word padding; the
    batcher additionally quantises to powers of two so widths collapse to
    a handful of plan entries).

Eviction is LRU with a fixed capacity: serving fleets hold plans for the
hot graphs and let cold graph/width combinations fall out.
"""

from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from typing import Callable, Optional, Tuple

import jax
import numpy as np

from repro.core.descriptor import Descriptor
from repro.core.graphblas import GraphMatrix
from repro.obs import cost as obs_cost
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

#: Labels the planner stamps on its registry series (DESIGN.md §14).
_CACHE_LABELS = ("kind", "backend")
_LAUNCH_LABELS = ("op", "backend", "tile_dim", "bucketed", "sharded")


@dataclasses.dataclass(frozen=True)
class PlanKey:
    graph_fp: str
    kernel: str
    backend: str
    tile_dim: int
    bucket_layout: Optional[Tuple[Tuple[int, int], ...]]
    batch_width: int            # padded number of frontier columns (S_pad)
    # descriptor fields the traced loop bakes in (``descriptor_key``);
    # None for plans whose loop shape is fully named by ``kernel``
    desc: Optional[Tuple] = None
    # the mesh fingerprint for sharded graphs (``partition.mesh_fingerprint``:
    # axis names, shape, shard axes, member device ids) — a sharded plan's
    # shard_map trace bakes all of these in, so plans must never leak
    # across mesh shapes (or between sharded and unsharded execution, where
    # this field is None)
    mesh: Optional[Tuple] = None


def descriptor_key(desc: Descriptor,
                   masked: Optional[bool] = None) -> Tuple:
    """Hashable summary of the :class:`Descriptor` fields a plan bakes in.

    A traced query loop specialises on mask presence, complement,
    input-transpose, replace semantics, and row chunking — two loops
    differing in any of these are different XLA programs. ``masked``
    overrides mask presence for plans whose mask is loop-carried (built
    inside the loop, so not present on the descriptor at key time).
    """
    m = (desc.mask is not None) if masked is None else masked
    return (m, desc.complement, desc.transpose_a, desc.replace,
            desc.row_chunk, desc.direction)


@dataclasses.dataclass
class Plan:
    """A cached, jit-compiled batched query loop.

    ``cost`` is the plan's HLO cost-model estimate (FLOPs / HBM bytes /
    wire bytes per launch) — populated on the first call when
    :func:`repro.obs.cost.set_cost_accounting` is on, None otherwise.
    Every call lands one observation in the ``launch_latency_s``
    histogram, labeled by the plan-key coordinates, so the registry can
    report achieved vs roofline rates per (op, backend, tile_dim).
    """

    key: PlanKey
    fn: Callable
    n_calls: int = 0
    cost: Optional[dict] = None

    def _labels(self) -> dict:
        return {"op": self.key.kernel, "backend": self.key.backend,
                "tile_dim": self.key.tile_dim,
                "bucketed": self.key.bucket_layout is not None,
                "sharded": self.key.mesh is not None}

    def __call__(self, *args, **kw):
        first = self.n_calls == 0
        self.n_calls += 1
        if not obs_metrics.enabled():
            return self.fn(*args, **kw)
        if first and self.cost is None and obs_cost.cost_accounting_enabled():
            self.cost = obs_cost.analyze_plan(self.fn, args, kw)
            if self.cost is not None:
                obs_cost.record_plan_cost(self.cost, self.key.kernel,
                                          self.key.backend,
                                          self.key.tile_dim)
        # tag the enclosing launch span: a first call pays trace+compile
        # inside this launch, which is the "slow query" smoking gun
        obs_trace.annotate(first_call=first, op=self.key.kernel)
        t0 = time.perf_counter()
        out = self.fn(*args, **kw)
        # dispatch-to-ready on sync backends (CPU); a dispatch-time lower
        # bound on async ones — callers needing exact device time should
        # block before reading the histogram
        obs_metrics.get_registry().histogram(
            "launch_latency_s", "plan launch wall time",
            _LAUNCH_LABELS).observe(time.perf_counter() - t0,
                                    **self._labels())
        return out


class HoistedJit:
    """``jax.jit(fn)`` that takes the arrays ``fn`` closes over as inputs.

    ``jax.jit`` embeds closed-over arrays in the compiled program as
    constants. A plan closes over its graph's slabs — gigabytes at a real
    scale — so here the first call per argument signature traces ``fn`` to
    a jaxpr once and compiles a program that receives the jaxpr's
    constants as arguments; later calls pass the same device arrays.
    """

    def __init__(self, fn: Callable):
        self.fn = fn
        self._traced: dict = {}

    def _resolve(self, args):
        flat, tree = jax.tree_util.tree_flatten(args)
        sig = (tree, tuple((np.shape(a), np.result_type(a)) for a in flat))
        hit = self._traced.get(sig)
        if hit is None:
            closed, shapes = jax.make_jaxpr(self.fn, return_shape=True)(*args)
            out_tree = jax.tree_util.tree_structure(shapes)

            def run(consts, *xs):
                out = jax.core.eval_jaxpr(closed.jaxpr, consts, *xs)
                return jax.tree_util.tree_unflatten(out_tree, out)

            hit = self._traced[sig] = (jax.jit(run), closed.consts)
        run, consts = hit
        return run, (consts, *flat)

    def __call__(self, *args):
        run, call_args = self._resolve(args)
        return run(*call_args)

    def lower(self, *args):
        run, call_args = self._resolve(args)
        return run.lower(*call_args)


def plan_key(g: GraphMatrix, kernel: str, batch_width: int,
             desc: Optional[Tuple] = None) -> PlanKey:
    """Build the cache key for ``kernel`` on ``g`` at ``batch_width``.

    ``desc`` is a :func:`descriptor_key` tuple for loops parameterised by
    a Descriptor (mask presence / complement / replace / chunking).
    Sharded graphs contribute their mesh fingerprint, so one serving
    process can hold plans for several meshes (and for the unsharded twin)
    without cross-talk.
    """
    bucket_layout = None
    if g.backend != "csr" and g.use_buckets:
        b = g.buckets()
        bucket_layout = tuple(zip(b.bucket_sizes, b.bucket_widths))
    mesh_fp = None
    if g.sharded:
        from repro.core.partition import mesh_fingerprint
        # the comm layout changes the traced collectives (gather vs
        # ppermute exchange), so it is part of the layout identity: plans
        # for a regathered/resharded twin never collide
        mesh_fp = mesh_fingerprint(g.mesh, g.shard_axes) + (g.comm,)
    return PlanKey(
        graph_fp=g.fingerprint(), kernel=kernel, backend=g.backend,
        tile_dim=g.tile_dim, bucket_layout=bucket_layout,
        batch_width=batch_width, desc=desc, mesh=mesh_fp)


class PlanCache:
    """LRU cache of :class:`Plan` objects with a stats snapshot.

    Counters live in one dict (:meth:`stats` / :meth:`reset_stats`) and
    are mirrored into the metrics registry as
    ``plan_cache_{hits,misses,evictions}_total{kind,backend}``; the
    historical ``hits`` / ``misses`` / ``evictions`` attributes remain as
    thin read-only properties over the snapshot.
    """

    def __init__(self, capacity: int = 32,
                 registry: Optional["obs_metrics.MetricsRegistry"] = None):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self._plans: "OrderedDict[PlanKey, Plan]" = OrderedDict()
        self._registry = registry            # None -> default at emit time
        self._stats = {"hits": 0, "misses": 0, "evictions": 0}

    # -- stats ---------------------------------------------------------------
    @property
    def hits(self) -> int:
        return self._stats["hits"]

    @property
    def misses(self) -> int:
        return self._stats["misses"]

    @property
    def evictions(self) -> int:
        return self._stats["evictions"]

    def stats(self) -> dict:
        """Counter snapshot plus occupancy, as one plain dict."""
        return {**self._stats, "size": len(self._plans),
                "capacity": self.capacity}

    def reset_stats(self) -> None:
        for k in self._stats:
            self._stats[k] = 0

    def _count(self, what: str, key: PlanKey) -> None:
        self._stats[what] += 1
        if obs_metrics.enabled():
            reg = self._registry or obs_metrics.get_registry()
            reg.counter(f"plan_cache_{what}_total",
                        f"plan cache {what}", _CACHE_LABELS).inc(
                kind=key.kernel, backend=key.backend)

    # -- lookup --------------------------------------------------------------
    def get(self, key: PlanKey, builder: Callable[[], Callable]) -> Plan:
        """The plan for ``key``, building (and possibly evicting) on miss."""
        plan = self._plans.get(key)
        with obs_trace.current_span("plan_resolve", cache_hit=plan is not None,
                                    op=key.kernel, backend=key.backend):
            if plan is not None:
                self._plans.move_to_end(key)
                self._count("hits", key)
                return plan
            self._count("misses", key)
            plan = Plan(key=key, fn=builder())
            self._plans[key] = plan
            while len(self._plans) > self.capacity:
                old_key, _ = self._plans.popitem(last=False)
                self._count("evictions", old_key)
        return plan

    def __len__(self) -> int:
        return len(self._plans)

    def __contains__(self, key: PlanKey) -> bool:
        return key in self._plans

    def keys(self):
        return list(self._plans.keys())

    def clear(self) -> None:
        self._plans.clear()
        self.reset_stats()


# The module-level cache that GraphMatrix entry points and the batcher use;
# pass an explicit PlanCache to engine.queries for isolated lifetimes.
DEFAULT_PLANNER = PlanCache()
