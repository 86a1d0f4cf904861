"""Chip smoke test: B2SR build -> GraphBLAS algorithms -> query server, on TPU.

Drives the repository's main path once through the entry points a user
calls, on a Graph500 Kronecker graph (R-MAT A=0.57, B=C=0.19, edge factor
16, symmetric) at scale 20 with 32x32 bit tiles, and checks every answer
against scipy / numpy references that share no code with the repository:

  (a) device   the first JAX device must be a TPU with Pallas compiling
               through Mosaic; anything else exits non-zero before (b)
  (b) build    ``GraphMatrix.from_coo`` on ``b2sr_pallas`` plus both
               bucketed views; host seconds and device peak memory
  (c) algos    BFS (push and pull), SSSP, PageRank (10 iterations) and CC,
               on ``b2sr_pallas`` and again on ``b2sr``
  (d) serve    a few dozen mixed bfs/khop/sssp/ppr queries through
               ``GraphQueryServer`` with no degraded, retried or skipped
               launch
  (e) checks   BFS/SSSP/CC/k-hop exact, PageRank/PPR allclose

``--chips 4`` runs only the sharded phase instead: BFS and msbfs on
``g.shard(mesh)`` with ``combine="gather"`` and ``"exchange"``, each
bit-exact against the single-device run of the same graph.

Every time printed is a set-up or correctness timing, not a benchmark. The
last line of standard output is one JSON object naming the device.

    python chip_smoke.py [--scale 20] [--seed 0] [--chips 4]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TILE_DIM = 32
AVG_DEGREE = 16
#: the serve_queries.py mix: kinds in rotation and their parameters
SERVE_KINDS = ("bfs", "khop", "sssp", "ppr")
SERVE_PARAMS = {"bfs": {}, "khop": {"k": 2}, "sssp": {"edge_weight": 1.0},
                "ppr": {"max_iters": 5, "eps": 0.0}}
SERVE_QUERIES = 48
PR_ITERS = 10
ALPHA = 0.85


def log(msg: str) -> None:
    print(msg, flush=True)


class Timer:
    """Wall seconds of one phase, printed as a set-up/correctness timing."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.s = time.perf_counter() - self.t0
        if exc[0] is None:
            log(f"[{self.name}] {self.s:.3f} s (set-up/correctness timing, "
                f"not a benchmark)")


# ---------------------------------------------------------------------------
# (a) device
# ---------------------------------------------------------------------------

def require_tpu():
    """The first device, if it is a TPU that compiles Pallas; else exit."""
    import jax

    from repro.kernels.common import interpret_default
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {dev.platform!r}); "
                 f"refusing to run on anything else")
    if interpret_default() is not False:
        sys.exit("chip_smoke: Pallas would run in interpret mode on the TPU")
    return dev


# ---------------------------------------------------------------------------
# the plain reference (scipy / numpy only)
# ---------------------------------------------------------------------------

class Reference:
    """BFS levels, CC, PageRank and PPR from scipy.sparse / numpy alone."""

    def __init__(self, rows: np.ndarray, cols: np.ndarray, n: int):
        import scipy.sparse as sp
        self.n = n
        self.a = sp.csr_matrix((np.ones(rows.size, np.float64),
                                (rows, cols)), shape=(n, n))
        self.a.sum_duplicates()
        self.a.data[:] = 1.0
        self.at = self.a.T.tocsr()
        self.outdeg = np.asarray(self.a.sum(axis=1)).ravel()

    def levels(self, sources) -> np.ndarray:
        """Hop levels ``[len(sources), n]`` along out-edges, -1 unreachable."""
        from scipy.sparse.csgraph import shortest_path
        d = shortest_path(self.a, directed=True, unweighted=True,
                          indices=np.asarray(sources))
        return np.where(np.isinf(d), -1, d).astype(np.int64)

    def components(self) -> np.ndarray:
        """Smallest vertex id of each vertex's connected component."""
        from scipy.sparse.csgraph import connected_components
        _, lab = connected_components(self.a, directed=False)
        first = np.full(lab.max() + 1, self.n, np.int64)
        np.minimum.at(first, lab, np.arange(self.n))
        return first[lab]

    def _iterate(self, restart, dangling_share, iters, eps):
        pr = restart.copy() if dangling_share is None else \
            np.full(self.n, 1.0 / self.n)
        dangling = self.outdeg == 0
        safe = np.where(dangling, 1.0, self.outdeg)
        it = 0
        delta = np.inf
        while delta > eps and it < iters:
            contrib = self.at @ (pr / safe)
            mass = pr[dangling].sum()
            if dangling_share is None:          # personalised restart
                new = ALPHA * contrib + (ALPHA * mass + 1 - ALPHA) * restart
            else:
                new = ALPHA * (contrib + mass / self.n) + (1 - ALPHA) / self.n
            delta = np.abs(new - pr).sum()
            pr, it = new, it + 1
        return pr

    def pagerank(self, iters: int, eps: float = 1e-9) -> np.ndarray:
        return self._iterate(None, True, iters, eps)

    def ppr(self, seed: int, iters: int) -> np.ndarray:
        r = np.zeros(self.n)
        r[seed] = 1.0
        return self._iterate(r, None, iters, 0.0)


def check_exact(name: str, got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or not np.array_equal(got, want):
        bad = int(np.sum(got != want)) if got.shape == want.shape else -1
        raise AssertionError(f"{name}: {bad} entries differ from the "
                             f"reference (shapes {got.shape} vs "
                             f"{want.shape})")


def check_close(name: str, got, want) -> None:
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               rtol=1e-4, atol=1e-9, err_msg=name)


# ---------------------------------------------------------------------------
# (b) build
# ---------------------------------------------------------------------------

def make_graph(scale: int, seed: int):
    from repro.data.graphs import rmat_graph
    n = 1 << scale
    rows, cols = rmat_graph(n, avg_degree=AVG_DEGREE, seed=seed)
    return rows, cols, n


def build(rows, cols, n: int, backend: str = "b2sr_pallas"):
    """The graph and both orientations' bucketed views, on the device."""
    import jax

    from repro.core import GraphMatrix
    g = GraphMatrix.from_coo(rows, cols, n, n, tile_dim=TILE_DIM,
                             backend=backend)
    gt = g.transposed()                  # builds both bucketed views
    jax.block_until_ready((g.buckets().bit_tiles, gt.buckets().bit_tiles))
    return g


def describe_build(g, dev) -> None:
    """Sizes against the real tile bytes; device peak memory; no ELL."""
    from repro.core import b2sr as b2sr_mod
    real = g.tiles.bit_tiles.nbytes
    log(f"graph: n={g.n_rows} nnz={g.nnz} tiles={g.tiles.n_tiles} "
        f"real tile bytes/orientation={real}")
    for name, view in (("forward", g), ("transpose", g.transposed())):
        b = view.buckets()
        held = sum(a.nbytes for a in b.bit_tiles + b.col_idx + b.rows)
        log(f"bucketed {name}: widths={b.bucket_widths} rows="
            f"{b.bucket_sizes} device bytes={held} "
            f"({held / real:.3f}x real tile bytes)")
        if view.ell_cache is not None:
            raise AssertionError(f"{name}: the padded ELL was built")
    t0 = time.perf_counter()
    b2sr_mod.transpose(g.tiles)
    log(f"host transpose: {time.perf_counter() - t0:.3f} s")
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"device memory after build: peak_bytes_in_use={peak} "
        f"bytes_in_use={stats.get('bytes_in_use')} "
        f"bytes_limit={stats.get('bytes_limit')}")
    if peak is not None and peak >= 16e9:
        raise AssertionError(f"peak device memory {peak} >= 16 GB")


# ---------------------------------------------------------------------------
# (c) algorithms
# ---------------------------------------------------------------------------

def run_algorithms(g, ref: Reference, source: int, backend: str) -> None:
    """BFS, SSSP, PageRank and CC on ``backend``, each against ``ref``."""
    import jax

    from repro import algorithms as alg
    gb = g.with_backend(backend)
    want_lv = ref.levels([source])[0]
    with Timer(f"bfs {backend}"):
        res = alg.bfs(gb, source)
        levels = np.asarray(jax.block_until_ready(res.levels))
    check_exact(f"bfs {backend}", levels, want_lv)
    log(f"bfs {backend}: {res.n_iterations} iterations "
        f"({res.directions.count('push')} push, "
        f"{res.directions.count('pull')} pull), "
        f"reached {int((levels >= 0).sum())} vertices")
    if not {"push", "pull"} <= set(res.directions):
        raise AssertionError(f"bfs {backend} ran {res.directions}: the "
                             f"push and the pull kernel must both run")
    with Timer(f"sssp {backend}"):
        dist = np.asarray(jax.block_until_ready(
            alg.sssp(gb, source).distances))
    check_exact(f"sssp {backend}", dist,
                np.where(want_lv >= 0, want_lv, np.inf).astype(np.float32))
    with Timer(f"pagerank {backend}"):
        pr = alg.pagerank(gb, max_iters=PR_ITERS)
        ranks = np.asarray(jax.block_until_ready(pr.ranks))
    check_close(f"pagerank {backend}", ranks, ref.pagerank(PR_ITERS))
    with Timer(f"cc {backend}"):
        labels = np.asarray(jax.block_until_ready(
            alg.connected_components(gb).labels))
    check_exact(f"cc {backend}", labels, ref.components())
    log(f"algorithms {backend}: bfs, sssp, pagerank, cc match the "
        f"reference")


# ---------------------------------------------------------------------------
# (d) server
# ---------------------------------------------------------------------------

def serve(g, ref: Reference, n_queries: int, seed: int) -> None:
    """The serve_queries mix through GraphQueryServer on ``g``'s backend."""
    from repro.engine import GraphQueryServer, ServerConfig
    server = GraphQueryServer(config=ServerConfig(default_budget_s=600.0))
    server.register(g)
    rng = np.random.default_rng(seed)
    submitted = []
    per_round = 3 * len(SERVE_KINDS)
    for r0 in range(0, n_queries, per_round):
        with Timer(f"serve round {r0 // per_round}"):
            for i in range(r0, min(r0 + per_round, n_queries)):
                kind = SERVE_KINDS[i % len(SERVE_KINDS)]
                src = int(rng.integers(0, g.n_rows))
                submitted.append((kind, src, server.submit(
                    g, kind, src, **SERVE_PARAMS[kind])))
            server.flush()
            for _, _, h in submitted[r0:]:
                np.asarray(h.result())
    lv = ref.levels([s for _, s, _ in submitted])
    for (kind, src, h), want in zip(submitted, lv):
        got = np.asarray(h.result())
        name = f"served {kind}({src})"
        if h.degraded or h.backend_used != g.backend:
            raise AssertionError(f"{name}: answered on {h.backend_used} "
                                 f"(degraded={h.degraded})")
        if kind == "bfs":
            check_exact(name, got, want)
        elif kind == "sssp":
            check_exact(name, got, np.where(want >= 0, want, np.inf)
                        .astype(np.float32))
        elif kind == "khop":
            check_exact(name, got, (want >= 1) & (want <= 2))
        else:
            check_close(name, got, ref.ppr(src, SERVE_PARAMS["ppr"]
                                           ["max_iters"]))
    st = server.stats
    log(f"server: completed={st['completed']} launches={st['launches']} "
        f"degraded_launches={st['degraded_launches']} "
        f"retries={st['retries']} breaker_skips={st['breaker_skips']} "
        f"failed={st['failed_queries']} plan_cache="
        f"{server.planner.misses}m/{server.planner.hits}h")
    for k in ("degraded_launches", "retries", "breaker_skips",
              "failed_queries", "warmup_failed"):
        if st[k]:
            raise AssertionError(f"server {k}={st[k]}")
    if st["completed"] != n_queries:
        raise AssertionError(f"server completed {st['completed']} of "
                             f"{n_queries}")


# ---------------------------------------------------------------------------
# --chips 4: the sharded phase
# ---------------------------------------------------------------------------

def run_sharded(g, n_devices: int, n_sources: int, seed: int) -> None:
    """BFS and msbfs on g.shard(mesh), gather and exchange, vs one device."""
    import jax

    from repro import algorithms as alg
    mesh = jax.make_mesh((n_devices,), ("shards",))
    rng = np.random.default_rng(seed)
    src = int(np.argmax(np.asarray(g.degrees())))
    sources = [int(s) for s in rng.integers(0, g.n_rows, n_sources)]
    with Timer("single-device bfs + msbfs"):
        want_bfs = np.asarray(alg.bfs(g, src).levels)
        want_ms = np.asarray(g.msbfs(sources).levels)
    for combine in ("gather", "exchange"):
        with Timer(f"shard {combine}"):
            gs = g.shard(mesh, combine=combine)
        devs = gs.partitioned.bit_tiles.sharding.device_set
        if len(devs) != n_devices:
            raise AssertionError(f"{combine}: slabs on {len(devs)} devices")
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in jax.devices()]
        log(f"shard {combine}: bytes_in_use per device {in_use}")
        with Timer(f"sharded {combine} bfs + msbfs"):
            got_bfs = np.asarray(alg.bfs(gs, src).levels)
            got_ms = np.asarray(gs.msbfs(sources).levels)
        check_exact(f"sharded {combine} bfs", got_bfs, want_bfs)
        check_exact(f"sharded {combine} msbfs", got_ms, want_ms)
        log(f"sharded {combine}: slabs on {len(devs)} devices; bfs and "
            f"msbfs({n_sources} sources) bit-exact against one device")
        del gs                           # one sharded twin at a time


# ---------------------------------------------------------------------------

def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scale", type=int, default=None,
                    help="log2 vertices (default 20; 17 with --chips 4)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded phase on four chips")
    args = ap.parse_args(argv)

    dev = require_tpu()                              # (a)
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {enable_compile_cache()}")
    if args.chips != 1 and len(jax.devices()) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(jax.devices())} device(s)")
    scale = args.scale or (20 if args.chips == 1 else 17)
    if scale != 20:
        why = (" — partition_rows builds shards from the padded ELL "
               "(ROADMAP 2.8)" if args.chips != 1 else "")
        log(f"reduced scale: {scale} (2^{scale} vertices){why}")

    with Timer("generate graph (host)"):
        rows, cols, n = make_graph(scale, args.seed)
    with Timer("build b2sr_pallas graph"):               # (b)
        g = build(rows, cols, n)
    describe_build(g, dev)
    if args.chips != 1:
        run_sharded(g, args.chips, n_sources=8, seed=args.seed)
    else:
        with Timer("reference (scipy)"):
            ref = Reference(rows, cols, n)
        source = int(np.argmax(ref.outdeg))
        for backend in ("b2sr_pallas", "b2sr"):         # (c) + (e)
            run_algorithms(g, ref, source, backend)
        serve(g, ref, SERVE_QUERIES, args.seed)         # (d) + (e)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
