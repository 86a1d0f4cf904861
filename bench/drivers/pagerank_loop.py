"""Graphalytics-style PageRank, back to back, one client.

Each call is ``repro.algorithms.pagerank`` on the ``b2sr_pallas`` graph
(damping, iterations and tolerance from the mix) and its ranks are read
back to the host. The rate counts every stored edge once per iteration.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from bench.lib import graph as graph_mod
from bench.lib import kbytes
from bench.lib.harness import Check, Window
from bench.lib.reference import Reference, rel_err
from bench.lib.sampling import Reservoir


def _call(g, mix):
    from repro import algorithms as alg
    res = alg.pagerank(g, alpha=mix["alpha"], max_iters=mix["max_iters"],
                       eps=mix["eps"])
    return res, np.asarray(jax.block_until_ready(res.ranks))


def setup(ctx):
    g, rows, cols, n = graph_mod.build(ctx.config, ctx.seed, ctx.log)
    for _ in range(ctx.mix["warm_calls"]):
        _call(g, ctx.mix)
    return {"graph": g, "rows": rows, "cols": cols, "n": n,
            "nnz": int(rows.size), "t": g.tile_dim,
            "shapes": graph_mod.bucket_shapes(g.transposed())}


def window(ctx, state, seconds):
    g = state["graph"]
    sample = Reservoir(ctx.mix["check_sample"],
                       np.random.default_rng([ctx.seed, 1]))
    calls, failed, iters = 0, 0, 0
    t0 = time.monotonic()
    while True:
        with jax.profiler.TraceAnnotation("bench:pagerank_call"):
            try:
                res, ranks = _call(g, ctx.mix)
            except Exception as e:       # noqa: BLE001 — counted, reported
                ctx.log(f"[bench] pagerank failed: {e!r}")
                failed += 1
                res = None
        calls += 1
        if res is not None:
            iters += res.n_iterations
            sample.offer(ranks)
        if time.monotonic() - t0 >= seconds:
            break
    window_s = time.monotonic() - t0
    shapes, t = state["shapes"], state["t"]
    return Window(attempted=calls, failed=failed, window_s=window_s,
                  samples=sample.items,
                  facts={"iterations": iters,
                         "vals_bytes": iters * kbytes.vals_pass_bytes(shapes,
                                                                      t),
                         "edges": iters * state["nnz"]})


def control(ctx, ref, rows, n, seconds):
    """The window as the control would fill it: the reference's PageRank
    with every stored vector rounded to bfloat16, in the program's place."""
    del rows, n, seconds
    ranks = ref.pagerank_bf16(ctx.mix["max_iters"], ctx.mix["eps"])
    return Window(attempted=1, failed=0, window_s=1.0, samples=[ranks],
                  facts={"edges": 0})


def finish(ctx, state, win):
    ref = Reference(state["rows"], state["cols"], state["n"])
    want = ref.pagerank(ctx.mix["max_iters"], ctx.mix["eps"])
    err = max((rel_err(r, want) for r in win.samples), default=float("inf"))
    checks = [Check("rank_rel_err", err, ctx.mix["limits"]["rank_rel_err"])]
    gteps = win.facts["edges"] / win.window_s / 1e9
    return checks, {"gteps": (gteps, "GTEPS")}
