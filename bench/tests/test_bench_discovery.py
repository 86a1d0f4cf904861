"""The harness finds configurations, mixes and metrics by name, in files."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest

from bench.lib import harness

SPEC = harness.load_spec()


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_to_files(cell):
    w = harness.find(SPEC["workloads"], cell, "workload")
    config = harness.load_config(SPEC, w["config"])
    assert config["name"] == w["config"]
    mix = harness.load_mix(w["traffic"])
    driver = harness.load_driver(mix["driver"])
    for fn in ("setup", "window", "finish"):
        assert callable(getattr(driver, fn))
    assert harness.metrics_for(SPEC, cell, False)
    assert harness.metrics_for(SPEC, cell, True)


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    mod = harness.load_metric(metric)
    empty = harness.MetricCtx(window_s=1.0, facts={}, trace=None, peaks={})
    assert mod.read(empty) is None       # nothing to read: no number


def test_a_new_metric_mix_and_config_are_found_by_name(tmp_path):
    """What a later change adds as files and entries only."""
    root = Path(tmp_path)
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads(json.dumps(SPEC))
    cfg = dict(harness.load_config(SPEC, "graph500-s19"), name="tiny-kron",
               scale=6)
    (root / "bench" / "configs" / "tiny-kron.json").write_text(
        json.dumps(cfg))
    spec["configs"].append({"name": "tiny-kron", "source": "x",
                            "file": "bench/configs/tiny-kron.json",
                            "reduced": [], "why": "test"})
    (root / "bench" / "mixes" / "bfs-push.json").write_text(json.dumps(
        dict(harness.load_mix("bfs"), direction="push")))
    spec["workloads"].append({"name": "tiny-kron.bfs-push",
                              "config": "tiny-kron", "traffic": "bfs-push",
                              "chips": 1, "why": "test"})
    (root / "bench" / "metrics" / "calls_per_s.py").write_text(
        "def read(ctx):\n    return ctx.facts['calls'] / ctx.window_s\n")
    spec["per_layer"].append({"name": "calls_per_s", "unit": "1/s",
                              "better": "higher", "source": "host_clock",
                              "layer": "algorithms", "moves": "gteps",
                              "workloads": ["tiny-kron.bfs-push"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    spec2 = harness.load_spec(root)
    assert harness.load_config(spec2, "tiny-kron", root)["scale"] == 6
    assert harness.load_mix("bfs-push", root)["direction"] == "push"
    names = [m["name"] for m in
             harness.metrics_for(spec2, "tiny-kron.bfs-push", True)]
    assert "calls_per_s" in names and "vals_kernel_hbm_pct" not in names
    mod = harness.load_metric("calls_per_s", root)
    ctx = harness.MetricCtx(window_s=2.0, facts={"calls": 8}, trace=None,
                            peaks={})
    assert mod.read(ctx) == 4.0


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        harness.find(SPEC["workloads"], "no-such-cell", "workload")
    with pytest.raises(FileNotFoundError):
        harness.load_metric("no_such_metric")
