"""Graph algorithms vs networkx oracles, across backends and tile sizes."""

import jax
import networkx as nx
import numpy as np
import pytest

from repro.algorithms import bfs, connected_components, pagerank, sssp, triangle_count
from repro.core import GraphMatrix
from repro.data import graphs as gen
from repro.obs import metrics as obs_metrics


def build(pattern: str, n: int, tile_dim: int = 8, backend: str = "b2sr",
          seed: int = 0):
    rows, cols = gen.PATTERNS[pattern](n, seed=seed)
    g = GraphMatrix.from_coo(rows, cols, n, n, tile_dim=tile_dim,
                             backend=backend)
    nxg = nx.Graph()
    nxg.add_nodes_from(range(n))
    nxg.add_edges_from(zip(rows.tolist(), cols.tolist()))
    return g, nxg


BACKENDS = ["b2sr", "csr", "b2sr_pallas"]


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("pattern", ["dot", "diagonal", "block"])
def test_bfs_levels(backend, pattern):
    g, nxg = build(pattern, 96, tile_dim=8, backend=backend)
    res = bfs(g, source=0)
    want = nx.single_source_shortest_path_length(nxg, 0)
    got = np.asarray(res.levels)
    for v in range(96):
        if v in want:
            assert got[v] == want[v], f"node {v}"
        else:
            assert got[v] == -1, f"node {v} should be unreachable"


@pytest.mark.parametrize("backend", BACKENDS)
def test_sssp_unit_weights(backend):
    g, nxg = build("hybrid", 80, tile_dim=16, backend=backend)
    res = sssp(g, source=3)
    want = nx.single_source_shortest_path_length(nxg, 3)
    got = np.asarray(res.distances)
    for v in range(80):
        if v in want:
            assert got[v] == want[v]
        else:
            assert np.isinf(got[v])


@pytest.mark.parametrize("backend", BACKENDS)
def test_pagerank_matches_networkx(backend):
    g, nxg = build("block", 64, tile_dim=8, backend=backend)
    res = pagerank(g, alpha=0.85, max_iters=100, eps=1e-12)
    want = nx.pagerank(nxg, alpha=0.85, max_iter=200, tol=1e-12)
    got = np.asarray(res.ranks)
    for v in range(64):
        assert abs(got[v] - want[v]) < 1e-5, f"node {v}: {got[v]} vs {want[v]}"


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("pattern", ["dot", "block", "stripe"])
def test_connected_components(backend, pattern):
    g, nxg = build(pattern, 72, tile_dim=8, backend=backend)
    res = connected_components(g)
    labels = np.asarray(res.labels)
    comps = list(nx.connected_components(nxg))
    # same partition: each nx component maps to exactly one label
    seen = {}
    for comp in comps:
        ls = {int(labels[v]) for v in comp}
        assert len(ls) == 1, f"component split: {ls}"
        l = ls.pop()
        assert l not in seen, "two components merged"
        seen[l] = True
    assert len(seen) == len(comps)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("tile_dim", [4, 8, 32])
def test_triangle_count(backend, tile_dim):
    g, nxg = build("block", 64, tile_dim=tile_dim, backend=backend)
    got = triangle_count(g)
    want = sum(nx.triangles(nxg).values()) // 3
    assert got == want


def test_bfs_pallas_matches_jnp_large():
    g, _ = build("road", 256, tile_dim=32, backend="b2sr")
    r1 = bfs(g, source=0)
    r2 = bfs(g.with_backend("b2sr_pallas"), source=0)
    assert np.array_equal(np.asarray(r1.levels), np.asarray(r2.levels))


# ---------------------------------------------------------------------------
# whole-graph loops are compiled once per graph and option set
# ---------------------------------------------------------------------------

@pytest.fixture
def registry():
    reg = obs_metrics.MetricsRegistry()
    prev = obs_metrics.set_registry(reg)
    prev_enabled = obs_metrics.set_enabled(True)
    yield reg
    obs_metrics.set_enabled(prev_enabled)
    obs_metrics.set_registry(prev)


def lookups(reg, kind, backend):
    """``(misses, hits)`` of ``GraphMatrix.loop_plan`` for ``kind``."""
    def value(what):
        c = reg.get(f"plan_cache_{what}_total")
        return 0 if c is None else c.value(kind=kind, backend=backend)
    return value("misses"), value("hits")


def only_program(g):
    """The one jitted program behind the graph's one loop plan."""
    plan, = g.loop_plans.values()
    (run, _), = plan.fn._traced.values()
    return run


def nx_levels(nxg, n, source, cutoff=None):
    want = np.full(n, -1, np.int32)
    for v, d in nx.single_source_shortest_path_length(
            nxg, source, cutoff=cutoff).items():
        want[v] = d
    return want


#: (root, max_iters): full traversals, none, and cut short
ROOTS = ((0, None), (5, 0), (17, 1), (40, 2), (99, None))


@pytest.mark.parametrize("backend", ["b2sr", "b2sr_pallas"])
@pytest.mark.parametrize("direction", ["auto", "push", "pull"])
def test_bfs_one_program_per_graph_matches_networkx(registry, backend,
                                                    direction):
    g, nxg = build("rmat", 128, tile_dim=8, backend=backend, seed=1)
    for root, max_iters in ROOTS:
        res = bfs(g, root, max_iters=max_iters, direction=direction)
        assert np.array_equal(np.asarray(res.levels),
                              nx_levels(nxg, 128, root, max_iters)), root
        if max_iters is not None:
            assert res.n_iterations <= max_iters
    # one trace and one lowering served every root and every max_iters
    assert lookups(registry, "bfs", backend) == (1, len(ROOTS) - 1)
    assert only_program(g)._cache_size() == 1


def pagerank_ref(rows, cols, n, alpha, max_iters, eps):
    a = np.zeros((n, n))
    a[rows, cols] = 1.0
    deg = a.sum(axis=1)
    dangling = deg == 0
    safe = np.where(dangling, 1.0, deg)
    pr, it, delta = np.full(n, 1.0 / n), 0, np.inf
    while delta > eps and it < max_iters:
        new = (alpha * (a.T @ (pr / safe) + pr[dangling].sum() / n)
               + (1.0 - alpha) / n)
        delta, pr, it = np.abs(new - pr).sum(), new, it + 1
    return pr, it


@pytest.mark.parametrize("backend", BACKENDS)
def test_pagerank_one_program_across_parameters(registry, backend):
    rows, cols = gen.PATTERNS["hybrid"](96, seed=2)
    g = GraphMatrix.from_coo(rows, cols, 96, 96, tile_dim=8,
                             backend=backend)
    params = ((0.85, 10, 0.0), (0.5, 3, 0.0), (0.9, 200, 1e-4))
    for alpha, max_iters, eps in params:
        res = pagerank(g, alpha=alpha, max_iters=max_iters, eps=eps)
        want, it = pagerank_ref(rows, cols, 96, alpha, max_iters, eps)
        assert np.allclose(np.asarray(res.ranks), want, atol=1e-6)
        if eps == 0.0:
            assert res.n_iterations == it == max_iters
        else:
            assert abs(res.n_iterations - it) <= 1
    assert lookups(registry, "pagerank", backend) == (1, len(params) - 1)
    assert only_program(g)._cache_size() == 1


def _one_device_mesh():
    return jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",))


TWINS = {
    "with_backend": lambda g: g.with_backend("b2sr_pallas"),
    "with_buckets": lambda g: g.with_buckets(False),
    "transposed": lambda g: g.transposed(),
    "shard": lambda g: g.shard(_one_device_mesh()),
    "unshard": lambda g: g.shard(_one_device_mesh()).unshard(),
}


@pytest.mark.parametrize("twin", sorted(TWINS))
def test_derived_graphs_do_not_share_loop_plans(registry, twin):
    g, nxg = build("rmat", 128, tile_dim=8, seed=1)
    want = nx_levels(nxg, 128, 3)
    assert np.array_equal(np.asarray(bfs(g, 3).levels), want)
    pagerank(g)
    plans = dict(g.loop_plans)
    h = TWINS[twin](g)
    assert h is not g and not h.loop_plans
    # the graph is symmetric, so the transposed twin has the same levels
    assert np.array_equal(np.asarray(bfs(h, 3).levels), want)
    pagerank(h)
    assert len(h.loop_plans) == 2
    assert not {id(p) for p in h.loop_plans.values()} & {
        id(p) for p in plans.values()}
    assert g.loop_plans == plans
    # the source graph still hits its own plan
    misses, hits = lookups(registry, "bfs", g.backend)
    bfs(g, 3)
    assert lookups(registry, "bfs", g.backend) == (misses, hits + 1)
