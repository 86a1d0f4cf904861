"""Each cell's driver against the plain reference, at a tiny size on CPU."""

from __future__ import annotations

import json

import pytest

from bench.lib import harness
from bench.tests.tiny import CELLS, run_tiny


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_at_tiny_size(cell):
    out = run_tiny(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    spec = harness.load_spec()
    want = {m["name"] for m in harness.metrics_for(spec, cell, False)}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    json.dumps(out)                      # the result line is plain JSON


def test_traced_run_reports_device_time_and_breakdown():
    out = run_tiny("graph500-s19.pagerank", trace=True)
    assert out["correct"]
    dev = out["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert out["breakdown"]["device_ops"]
    assert len(out["breakdown"]["idle_gaps"]) <= 10
    assert "algo_iter_ms" in out["metrics"]
    assert "idle_pct.algo" in out["metrics"]
    # the Pallas kernels run interpreted on the CPU: no kernel event, so
    # the kernel metrics find nothing and are left out, never read as 0
    assert "vals_kernel_hbm_pct" not in out["metrics"]
