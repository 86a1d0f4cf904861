"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows; writes per-table JSON into
results/. Roofline rows (from dry-run artifacts, if present) are appended.

  python -m benchmarks.run                 # everything
  python -m benchmarks.run --only fig6     # substring filter
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time
import traceback


def _run_manifest() -> dict:
    """Provenance for one harness invocation: code identity + environment.

    Written next to the per-table results JSONs so a results directory is
    self-describing — which commit produced it, on what device set, with
    which env toggles, and how long each suite took.
    """
    m: dict = {"started_at": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
               "argv": sys.argv[1:]}
    try:
        m["git_sha"] = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or None
    except Exception:                        # noqa: BLE001 — provenance only
        m["git_sha"] = None
    m["env"] = {k: os.environ.get(k) for k in
                ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR", "PYTHONPATH")}
    try:
        import jax

        from repro.core import dispatch
        m["jax_devices"] = [str(d) for d in jax.devices()]
        backends = {}
        for b in ("csr", "b2sr", "b2sr_pallas"):
            try:
                dispatch._ensure_backend(b)
                backends[b] = True
            except Exception as e:           # noqa: BLE001 — availability probe
                backends[b] = f"unavailable: {e!r}"
        m["backends"] = backends
    except Exception as e:                   # noqa: BLE001 — provenance only
        m["jax_devices"] = f"unavailable: {e!r}"
    return m


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="substring filter on table name")
    ap.add_argument("--tiny", action="store_true",
                    help="smoke-pass sizes (CI); suites that support it only")
    ap.add_argument("--combine", nargs="+", choices=("gather", "exchange"),
                    default=("gather", "exchange"),
                    help="collective layouts for the scaling suite")
    ap.add_argument("--assert-scaling", action="store_true",
                    help="scaling suite: fail on regression-gate violation")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (compression, engine_batch, gnn_bit,
                            graph_algorithms, kernels_bmm, kernels_bmv,
                            kernels_bucketed, kernels_spgemm,
                            sampling_profile, scaling_shards, serving_slo,
                            traversal_direction, triangle_counting)
    suites = [
        ("tableI+fig5 compression", compression.run),
        ("fig6a-c bmv", kernels_bmv.run),
        ("fig6d bmm", kernels_bmm.run),
        ("fig8 spgemm", kernels_spgemm.run),
        ("loadbalance bucketed", lambda: kernels_bucketed.run(tiny=args.tiny)),
        ("engine batched queries", lambda: engine_batch.run(tiny=args.tiny)),
        ("serving slo", lambda: serving_slo.run(tiny=args.tiny)),
        ("scaling sharded", lambda: scaling_shards.run(
            tiny=args.tiny, combines=tuple(args.combine),
            assert_scaling=args.assert_scaling)),
        ("direction traversal",
         lambda: traversal_direction.run(tiny=args.tiny)),
        ("gnn bit aggregation", lambda: gnn_bit.run(tiny=args.tiny)),
        ("tableVII/VIII algorithms", graph_algorithms.run),
        ("tableIX tc", triangle_counting.run),
        ("alg1 sampling", sampling_profile.run),
    ]
    manifest = _run_manifest()
    manifest["suites"] = {}
    print("name,us_per_call,derived")
    failures = []
    for name, fn in suites:
        if args.only and args.only not in name:
            continue
        t0 = time.perf_counter()
        try:
            for row in fn():
                print(row.csv())
            status = "ok"
        except Exception as e:
            failures.append((name, repr(e)))
            traceback.print_exc()
            status = repr(e)
        manifest["suites"][name] = {
            "wall_s": time.perf_counter() - t0, "status": status}

    # roofline rows (non-fatal if dry-run artifacts are absent)
    if not args.only or "roofline" in args.only:
        try:
            from benchmarks import roofline
            for r in roofline.run():
                print(f"roofline/{r['arch']}/{r['shape']},0.0,"
                      f"dominant={r['dominant']} frac={r['roofline_fraction']:.3f}")
        except Exception as e:
            print(f"roofline skipped: {e!r}", file=sys.stderr)

    manifest["total_wall_s"] = sum(s["wall_s"]
                                   for s in manifest["suites"].values())
    from benchmarks.common import save_json
    print(f"manifest: {save_json('run_manifest.json', manifest)}",
          file=sys.stderr)

    if failures:
        for name, err in failures:
            print(f"FAILED suite {name}: {err}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
