"""Fault-tolerant serving layer: deadlines, fallback, breakers, warmup.

Covers DESIGN.md §13 end to end with a deterministic clock and the seeded
:class:`FaultInjector` (no real faults, no real sleeps): deadline-driven
flushing vs fill-driven flushing, bounded-queue rejection, submit-time
validation, circuit-breaker open/half-open/close transitions, the
``b2sr_pallas → b2sr → csr`` fall-through staying bit-exact (buckets
on/off, and on 8 forced host devices for the sharded path), in-flight
dedup, idempotent failure handles, and the restart-safe warmup
round-trip.
"""

import textwrap

import numpy as np
import pytest

from conftest import assert_marker, run_script
from repro.algorithms import bfs, khop_frontier, sssp
from repro.core import GraphMatrix, dispatch
from repro.engine import (CircuitBreaker, FaultInjector, GraphQueryServer,
                          InjectedFault, PlanCache, QueryBatcher,
                          QueryGroupError, QueryRejected, ServerConfig,
                          batched_ppr)
from repro.engine import warmup as warmup_mod
from repro.engine.server import CLOSED, HALF_OPEN, OPEN


def skewed_coo(n, seed, hub_deg=15, base_deg=3):
    rng = np.random.default_rng(seed)
    rows = np.concatenate([
        np.repeat(np.arange(n, dtype=np.int64), base_deg),
        np.repeat(rng.choice(n, 2, replace=False).astype(np.int64), hub_deg),
    ])
    cols = rng.integers(0, n, rows.size)
    return rows, cols


def build(n=64, t=8, backend="b2sr", seed=0, use_buckets=True):
    rows, cols = skewed_coo(n, seed)
    g = GraphMatrix.from_coo(rows, cols, n, n, tile_dim=t, backend=backend)
    return g.with_buckets(use_buckets)


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def make_server(clock=None, injector=None, **cfg_kw):
    cfg_kw.setdefault("backoff_base_s", 0.0)
    return GraphQueryServer(
        planner=PlanCache(), config=ServerConfig(**cfg_kw),
        fault_injector=injector,
        clock=clock if clock is not None else FakeClock(),
        sleep=lambda s: None)


# ---------------------------------------------------------------------------
# deadline-aware admission
# ---------------------------------------------------------------------------

def test_deadline_flush_fires_when_oldest_budget_nears():
    clk = FakeClock()
    srv = make_server(clock=clk, default_budget_s=0.1, flush_margin_s=0.005)
    g = build()
    h1 = srv.bfs(g, 3)
    clk.advance(0.050)
    h2 = srv.bfs(g, 7, budget_s=0.2)         # later deadline, same flush
    assert srv.poll() == 0 and srv.pending() == 2    # nothing near yet
    clk.advance(0.044)                       # oldest deadline 6ms away
    assert not srv.due() and srv.poll() == 0
    clk.advance(0.002)                       # now 4ms away: inside margin
    assert srv.due()
    assert srv.poll() == 2                   # flushes *everything* pending
    assert h1.done() and h2.done() and srv.pending() == 0
    assert srv.stats["deadline_flushes"] == 1
    assert srv.stats["fill_flushes"] == 0
    assert np.array_equal(np.asarray(h1.result()),
                          np.asarray(bfs(g, 3).levels))
    assert h1.completed_at == clk.t and not h1.degraded


def test_fill_flush_at_max_batch():
    srv = make_server(max_batch=4)
    g = build()
    handles = [srv.bfs(g, s) for s in (1, 2, 3)]
    assert srv.pending() == 3 and not handles[0].done()
    handles.append(srv.bfs(g, 4))            # 4th submit trips the fill flush
    assert srv.pending() == 0 and all(h.done() for h in handles)
    assert srv.stats["fill_flushes"] == 1
    for s, h in zip((1, 2, 3, 4), handles):
        assert np.array_equal(np.asarray(h.result()),
                              np.asarray(bfs(g, s).levels))


def test_bounded_queue_rejects_overflow():
    srv = make_server(max_queue=2)
    g = build()
    h1, h2 = srv.bfs(g, 1), srv.bfs(g, 2)
    with pytest.raises(QueryRejected, match=r"queue full \(2/2 pending\)"):
        srv.bfs(g, 3)
    assert srv.stats["rejected"] == 1 and srv.pending() == 2
    srv.flush()                              # accepted queries still resolve
    assert np.array_equal(np.asarray(h1.result()),
                          np.asarray(bfs(g, 1).levels))
    assert np.array_equal(np.asarray(h2.result()),
                          np.asarray(bfs(g, 2).levels))
    assert srv.bfs(g, 3).done() is False     # space freed: admitted again


def test_submit_time_validation_names_node_count():
    g = build(n=64)
    srv = make_server()
    with pytest.raises(ValueError, match=r"graph with 64 nodes.*0\.\.63"):
        srv.bfs(g, 64)
    with pytest.raises(ValueError, match=r"graph with 64 nodes"):
        srv.bfs(g, -1)
    with pytest.raises(ValueError, match="unknown query kind 'pagerank'"):
        srv.submit(g, "pagerank", 0)
    assert srv.pending() == 0                # nothing enqueued by rejects
    assert srv.stats["submitted"] == 0
    b = QueryBatcher()                       # same edge on the raw batcher
    with pytest.raises(ValueError, match=r"graph with 64 nodes"):
        b.bfs(g, 1000)


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------

def test_breaker_state_machine():
    clk = FakeClock()
    br = CircuitBreaker(fail_threshold=2, cooldown_s=1.0, clock=clk)
    assert br.state == CLOSED and br.allow()
    br.record_failure()
    assert br.state == CLOSED and br.allow()
    br.record_failure()                      # 2nd consecutive: opens
    assert br.state == OPEN and not br.allow() and br.n_opens == 1
    clk.advance(0.999)
    assert not br.allow()
    clk.advance(0.001)                       # cooldown elapsed: half-open
    assert br.allow() and br.state == HALF_OPEN
    br.record_failure()                      # failed probe: re-open
    assert br.state == OPEN and br.n_opens == 2 and not br.allow()
    clk.advance(1.0)
    assert br.allow() and br.state == HALF_OPEN
    br.record_success()                      # probe succeeded: closed
    assert br.state == CLOSED and br.allow()
    br.record_failure()                      # success reset the count
    assert br.state == CLOSED


def test_breaker_opens_skips_and_recovers_through_server():
    clk = FakeClock()
    # 4 scripted faults: initial + retry (opens the breaker), then the
    # half-open probe + its retry... the probe is a single attempt, so
    # fault #3 re-opens; #4 is never consumed until the next half-open.
    inj = FaultInjector(seed=0).fail(op="bfs", backend="b2sr_pallas",
                                     script=[True, True, True, True])
    srv = make_server(clock=clk, injector=inj, max_retries=1,
                      fail_threshold=2, cooldown_s=1.0)
    g = build(backend="b2sr_pallas")
    ref = np.asarray(bfs(g.with_backend("b2sr"), 3).levels)

    h = srv.bfs(g, 3)
    srv.flush()                              # fault + retried fault: opens
    assert h.degraded and h.backend_used == "b2sr"
    assert np.array_equal(np.asarray(h.result()), ref)
    assert srv.breaker("bfs", "b2sr_pallas").state == OPEN
    assert srv.stats["retries"] == 1 and inj.script_remaining(
        "bfs", "b2sr_pallas") == 2

    h2 = srv.bfs(g, 3)
    srv.flush()                              # open breaker: pallas skipped
    assert h2.degraded and srv.stats["breaker_skips"] == 1
    assert inj.script_remaining("bfs", "b2sr_pallas") == 2  # not consulted

    clk.advance(1.0)                         # cooldown: half-open probe
    h3 = srv.bfs(g, 3)
    srv.flush()
    br = srv.breaker("bfs", "b2sr_pallas")
    assert h3.degraded and br.state == OPEN and br.n_opens == 2
    assert inj.script_remaining("bfs", "b2sr_pallas") == 1  # one probe shot

    clk.advance(1.0)
    h4 = srv.bfs(g, 3)
    srv.flush()                              # probe faults again, re-opens
    assert h4.degraded and br.n_opens == 3

    clk.advance(1.0)                         # script exhausted: probe passes
    h5 = srv.bfs(g, 3)
    srv.flush()
    assert br.state == CLOSED
    assert not h5.degraded and h5.backend_used == "b2sr_pallas"
    assert np.array_equal(np.asarray(h5.result()), ref)


# ---------------------------------------------------------------------------
# fallback chain: bit-exact degradation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_buckets", (True, False))
def test_fallback_to_b2sr_is_bit_exact(use_buckets):
    inj = FaultInjector(seed=0).fail(backend="b2sr_pallas", rate=1.0)
    srv = make_server(injector=inj)
    g = build(backend="b2sr_pallas", use_buckets=use_buckets)
    gb = srv._backend_view(g, "b2sr")
    hb = srv.bfs(g, 5)
    hk = srv.khop(g, 9, k=2)
    hs = srv.sssp(g, 4)
    hp = srv.ppr(g, 11, max_iters=4, eps=0.0)
    srv.flush()
    for h in (hb, hk, hs, hp):
        assert h.degraded and h.backend_used == "b2sr"
    assert np.array_equal(np.asarray(hb.result()),
                          np.asarray(bfs(gb, 5).levels))
    assert np.array_equal(np.asarray(hk.result()),
                          np.asarray(khop_frontier(gb, 9, 2)))
    assert np.array_equal(np.asarray(hs.result()),
                          np.asarray(sssp(gb, 4).distances))
    # float kind: bit-exact vs the identical healthy launch on b2sr
    assert np.array_equal(
        np.asarray(hp.result()),
        np.asarray(batched_ppr(gb, [11], max_iters=4, eps=0.0).ranks[:, 0]))
    assert srv.stats["degraded_launches"] == 4
    assert srv.stats["launches"] == 4        # pallas faulted pre-launch


def test_fallback_to_csr_last_resort():
    inj = (FaultInjector(seed=0)
           .fail(backend="b2sr_pallas", rate=1.0)
           .fail(backend="b2sr", rate=1.0))
    srv = make_server(injector=inj)
    g = build(backend="b2sr_pallas")
    gc = g.with_backend("csr")
    h = srv.bfs(g, 5)
    srv.flush()
    assert h.degraded and h.backend_used == "csr"
    assert np.array_equal(np.asarray(h.result()),
                          np.asarray(bfs(gc, 5).levels))
    assert np.array_equal(np.asarray(h.result()),
                          np.asarray(bfs(g.with_backend("b2sr"), 5).levels))


def test_fallback_exhausted_fails_handles_idempotently():
    inj = FaultInjector(seed=0).fail(rate=1.0)          # every backend
    srv = make_server(injector=inj, max_retries=1)
    g = build(backend="b2sr_pallas")
    h1, h2 = srv.bfs(g, 1), srv.bfs(g, 2)
    srv.flush()                              # quiet: verdicts on handles
    assert h1.done() and h2.done()
    assert srv.stats["failed_queries"] == 2 and srv.stats["completed"] == 0
    with pytest.raises(QueryGroupError, match="batched 'bfs' group") as e1:
        h1.result()
    assert isinstance(e1.value.__cause__, InjectedFault)
    cause = e1.value.__cause__
    for _ in range(3):                       # satellite: idempotent re-raise
        with pytest.raises(QueryGroupError) as e2:
            h1.result()
        assert e2.value is e1.value          # same object, no re-wrapping
        assert e2.value.__cause__ is cause   # __cause__ chain never grows
    h1._fail(RuntimeError("late"))           # first outcome wins
    h1._fulfill(np.zeros(3))
    with pytest.raises(QueryGroupError):
        h1.result()
    with pytest.raises(QueryGroupError):     # sibling got the same verdict
        h2.result()


# ---------------------------------------------------------------------------
# in-flight dedup
# ---------------------------------------------------------------------------

def test_inflight_duplicates_share_one_column():
    srv = make_server()
    g = build()
    dup = [srv.bfs(g, 13) for _ in range(3)] # a retry storm, same query
    other = srv.bfs(g, 2)
    srv.flush()
    assert srv.stats["deduped"] == 2         # 4 queries, 2 unique sources
    want = np.asarray(bfs(g, 13).levels)
    for h in dup:
        assert np.array_equal(np.asarray(h.result()), want)
    assert np.array_equal(np.asarray(other.result()),
                          np.asarray(bfs(g, 2).levels))
    rec = srv.launch_log[-1]
    assert len(rec.sources) == 2             # padded launch carried 2 cols


def test_batcher_dedup_counter():
    pc = PlanCache()
    b = QueryBatcher(planner=pc)
    g = build()
    hs = [b.ppr(g, 7, max_iters=3, eps=0.0) for _ in range(4)]
    b.flush()
    assert b.n_deduped == 3 and b.n_launches == 1
    first = np.asarray(hs[0].result())
    for h in hs[1:]:
        assert np.array_equal(np.asarray(h.result()), first)


# ---------------------------------------------------------------------------
# fault injector
# ---------------------------------------------------------------------------

def test_injector_outcomes_are_rule_local_and_seeded():
    def outcomes(inj, op, n=40):
        out = []
        for _ in range(n):
            try:
                inj.check(op, "b2sr")
                out.append(False)
            except InjectedFault:
                out.append(True)
        return out

    a = FaultInjector(seed=5).fail(op="bfs", rate=0.3).fail(op="ppr",
                                                            rate=0.3)
    b = FaultInjector(seed=5).fail(op="bfs", rate=0.3).fail(op="ppr",
                                                            rate=0.3)
    seq = outcomes(a, "bfs")
    assert any(seq) and not all(seq)         # an actual 30% mix
    # interleaving an unrelated rule's checks must not perturb this one
    inter = []
    for _ in range(40):
        outcomes(b, "ppr", n=1)
        inter.extend(outcomes(b, "bfs", n=1))
    assert inter == seq
    reseeded = FaultInjector(seed=6).fail(op="bfs", rate=0.3)
    assert outcomes(reseeded, "bfs") != seq  # seed actually matters


def test_injector_threads_through_dispatch_resolve():
    g = build(backend="b2sr")
    bfs(g, 1)                                # healthy before
    with FaultInjector(seed=0).fail(backend="b2sr", rate=1.0):
        with pytest.raises(InjectedFault, match="backend 'b2sr'"):
            bfs(g, 1)
    assert np.asarray(bfs(g, 1).levels)[1] == 0   # hook removed: healthy
    inj = FaultInjector(seed=0).fail(op="no_such_op", rate=1.0)
    inj.install()
    try:
        bfs(g, 1)                            # non-matching rule: inert
    finally:
        inj.uninstall()
    assert dispatch.set_resolve_hook(None) is None  # fully unhooked


# ---------------------------------------------------------------------------
# restart-safe warmup
# ---------------------------------------------------------------------------

def test_warmup_roundtrip_precompiles_hot_plans(tmp_path):
    path = str(tmp_path / "warm.json")
    g = build(n=64, seed=3)
    srv = make_server()
    for s in (1, 9):
        srv.bfs(g, s)
    srv.ppr(g, 5, max_iters=3, eps=0.0)
    srv.flush()
    assert srv.save_warmup(path) == 2        # one bfs recipe + one ppr

    # "restart": same graph rebuilt from scratch, fresh plan cache
    g2 = build(n=64, seed=3)
    srv2 = make_server()
    srv2.register(g2)
    assert srv2.warmup(path) == 2
    compiles = srv2.planner.misses
    assert compiles == 2 and srv2.planner.hits == 0
    for s in (1, 9):
        srv2.bfs(g2, s)
    srv2.ppr(g2, 5, max_iters=3, eps=0.0)
    srv2.flush()                             # live traffic: pure cache hits
    assert srv2.planner.misses == compiles and srv2.planner.hits == 2
    assert srv2.stats["warmup_replayed"] == 2

    # unregistered graph fingerprints are skipped, never fatal
    srv3 = make_server()
    assert srv3.warmup(path) == 0
    assert srv3.stats["warmup_skipped"] == 2


def test_warmup_file_validation(tmp_path):
    with pytest.raises(FileNotFoundError):
        warmup_mod.load(str(tmp_path / "absent.json"))
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    with pytest.raises(ValueError, match="not a warmup file"):
        warmup_mod.load(str(bad))
    vers = tmp_path / "vers.json"
    vers.write_text('{"version": 99, "recipes": []}')
    with pytest.raises(ValueError, match="version 99"):
        warmup_mod.load(str(vers))
    field = tmp_path / "field.json"
    field.write_text('{"version": 1, "recipes": [{"kind": "bfs"}]}')
    with pytest.raises(ValueError, match="missing field 'graph_fp'"):
        warmup_mod.load(str(field))
    with pytest.raises(ValueError, match="missing field"):
        warmup_mod.save(str(tmp_path / "out.json"), [{"kind": "bfs"}])


# ---------------------------------------------------------------------------
# sharded fallback parity (8 forced host devices, subprocess)
# ---------------------------------------------------------------------------

_SHARD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax

    from repro.algorithms.bfs import bfs
    from repro.core.graphblas import GraphMatrix
    from repro.engine import (FaultInjector, GraphQueryServer, PlanCache,
                              ServerConfig)
    from repro.engine.queries import batched_ppr
    from repro.launch.mesh import make_debug_mesh

    assert len(jax.devices()) == 8
    rng = np.random.RandomState(3)
    d = (rng.random((96, 96)) < 0.08).astype(np.uint8)
    g = GraphMatrix.from_dense(d, tile_dim=8)
    mesh = make_debug_mesh(8, model=2)
    gp = g.with_backend("b2sr_pallas").shard(mesh)
    cfg = ServerConfig(backoff_base_s=0.0)
    ref = np.asarray(bfs(g, 5).levels)

    # sharded pallas faults -> served by *sharded* b2sr, bit-exact
    inj = FaultInjector(seed=1).fail(backend="b2sr_pallas", rate=1.0)
    srv = GraphQueryServer(planner=PlanCache(), config=cfg,
                           fault_injector=inj)
    h = srv.bfs(gp, 5)
    hp = srv.ppr(gp, 7, max_iters=4, eps=0.0)
    srv.flush()
    assert h.degraded and h.backend_used == "b2sr"
    assert np.array_equal(np.asarray(h.result()), ref)
    gb = srv._backend_view(gp, "b2sr")
    assert gb.sharded                       # fallback stayed on the mesh
    assert np.array_equal(
        np.asarray(hp.result()),
        np.asarray(batched_ppr(gb, [7], max_iters=4, eps=0.0).ranks[:, 0]))
    print("SHARD_B2SR_OK")

    # both bit backends fault -> csr last resort (server unshards for it)
    inj2 = (FaultInjector(seed=2).fail(backend="b2sr_pallas", rate=1.0)
            .fail(backend="b2sr", rate=1.0))
    srv2 = GraphQueryServer(planner=PlanCache(), config=cfg,
                            fault_injector=inj2)
    h2 = srv2.bfs(gp, 5)
    srv2.flush()
    assert h2.degraded and h2.backend_used == "csr"
    assert not srv2._backend_view(gp, "csr").sharded
    assert np.array_equal(np.asarray(h2.result()), ref)
    print("SHARD_CSR_OK")

    # warmup recipes keep the sharded flag and replay on the mesh
    path = os.path.join(os.environ.get("TMPDIR", "/tmp"),
                        "serving_warm_shard.json")
    assert srv.save_warmup(path) >= 1
    srv3 = GraphQueryServer(planner=PlanCache(), config=cfg)
    srv3.register(gp)
    assert srv3.warmup(path) >= 1 and srv3.stats["warmup_failed"] == 0
    print("SHARD_WARM_OK")
""")

_SHARD_MARKERS = ["SHARD_B2SR_OK", "SHARD_CSR_OK", "SHARD_WARM_OK"]


@pytest.fixture(scope="module")
def sharded_serving_run():
    return run_script(_SHARD_SCRIPT)


@pytest.mark.parametrize("marker", _SHARD_MARKERS)
def test_sharded_fallback_parity(sharded_serving_run, marker):
    assert_marker(sharded_serving_run, marker)
