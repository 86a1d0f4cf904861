"""Graph500 kernel 2: BFS from seeded roots, back to back, one client.

Each call is ``repro.algorithms.bfs`` on the ``b2sr_pallas`` graph, as a
user calls it, and its levels are read back to the host. Roots are drawn
from the seed among the vertices with at least one edge (Graph500's
rule). The rate is Graph500's: input edges in each root's component,
summed over the window, over the window's time.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from bench.lib import graph as graph_mod
from bench.lib import kbytes
from bench.lib.harness import Check, Window
from bench.lib.reference import Reference, mismatches
from bench.lib.sampling import Reservoir


def _call(g, root: int, direction: str):
    from repro import algorithms as alg
    res = alg.bfs(g, int(root), direction=direction)
    return res, np.asarray(jax.block_until_ready(res.levels))


def roots(ctx, rows, n):
    """``(window roots, warm-up roots)``, drawn from the seed among the
    vertices with an edge."""
    rng = np.random.default_rng(ctx.seed)
    cand = graph_mod.degree_one_vertices(rows, n)
    order = rng.choice(cand, size=min(ctx.mix["roots"], cand.size),
                       replace=False)
    return order, rng.choice(cand, size=ctx.mix["warm_calls"])


def setup(ctx):
    g, rows, cols, n = graph_mod.build(ctx.config, ctx.seed, ctx.log)
    order, warm = roots(ctx, rows, n)
    for r in warm:
        _call(g, r, ctx.mix["direction"])
    return {"graph": g, "rows": rows, "cols": cols, "n": n, "roots": order,
            "t": g.tile_dim,
            "shapes": graph_mod.bucket_shapes(g.transposed())}


def window(ctx, state, seconds):
    g, roots = state["graph"], state["roots"]
    sample = Reservoir(ctx.mix["check_sample"],
                       np.random.default_rng([ctx.seed, 1]))
    done, failed, iters, pulls = [], 0, 0, 0
    t0 = time.monotonic()
    while True:
        root = int(roots[len(done) % roots.size])
        with jax.profiler.TraceAnnotation("bench:bfs_call"):
            try:
                res, levels = _call(g, root, ctx.mix["direction"])
            except Exception as e:       # noqa: BLE001 — counted, reported
                ctx.log(f"[bench] bfs({root}) failed: {e!r}")
                failed += 1
                res = None
        done.append(root)
        if res is not None:
            iters += res.n_iterations
            pulls += res.directions.count("pull")
            sample.offer((root, levels))
        if time.monotonic() - t0 >= seconds:
            break
    window_s = time.monotonic() - t0
    shapes, t = state["shapes"], state["t"]
    bits_bytes = ((iters - pulls) * kbytes.bits_pass_bytes(shapes, t, False)
                  + pulls * kbytes.bits_pass_bytes(shapes, t, True))
    return Window(attempted=len(done), failed=failed, window_s=window_s,
                  samples={"roots": done, "checked": sample.items},
                  facts={"iterations": iters, "bits_bytes": bits_bytes})


def control(ctx, ref, rows, n, seconds):
    """The window as the control would fill it: the reference's traversal
    stopped one level early, in the program's place, on the first roots."""
    del seconds
    order, _ = roots(ctx, rows, n)
    first = order[:ctx.mix["check_sample"]]
    return Window(attempted=first.size, failed=0, window_s=1.0,
                  samples={"roots": list(first),
                           "checked": list(zip(first,
                                               ref.levels_truncated(first)))})


def finish(ctx, state, win):
    ref = Reference(state["rows"], state["cols"], state["n"])
    checked = win.samples["checked"]
    bad = 0
    if checked:
        want = ref.levels([r for r, _ in checked])
        bad = sum(mismatches(lv, w) for (_, lv), w in zip(checked, want))
    edges = float(np.sum(ref.graph500_edges(win.samples["roots"])))
    limits = ctx.mix["limits"]
    checks = [Check("level_mismatch", bad, limits["level_mismatch"]),
              Check("checked_calls_missing", 0 if checked else 1, 0)]
    return checks, {"gteps": (edges / win.window_s / 1e9, "GTEPS")}
