"""``BENCHMARK.json`` is self-consistent, and the device block reads the
memory's real peak."""

from __future__ import annotations

import re

import pytest

from bench.lib import harness

SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
E2E = {m["name"]: m for m in SPEC["end_to_end"]}
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_a_per_layer_metric_moves_what_its_cells_report(metric):
    m = harness.find(SPEC["per_layer"], metric, "metric")
    assert NAME.match(metric) and m["moves"] in E2E
    assert m["workloads"] and set(m["workloads"]) <= set(CELLS)
    for cell in m["workloads"]:
        reported = harness.metrics_for(SPEC, cell, False)
        assert m["moves"] in {e["name"] for e in reported}


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_reports_setup_another_metric_and_a_layer(cell):
    e2e = {m["name"] for m in harness.metrics_for(SPEC, cell, False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.metrics_for(SPEC, cell, True)
    assert all(0.01 <= E2E[n]["bound"] <= 0.25 for n in e2e)
    assert all(E2E[n]["source"] in ("host_clock", "device_trace")
               for n in e2e)


class _Dev:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("stats,want", [
    ([{"peak_bytes_in_use": 7, "peak_bytes_reserved": 5}], 12),
    ([{"peak_bytes_in_use": 7}, {"peak_bytes_in_use": 3,
                                 "peak_bytes_reserved": 9}], 12),
    ([None], None),
])
def test_memory_peak_counts_the_reserved_temporaries(stats, want):
    assert harness._memory_peak([_Dev(s) for s in stats]) == want
