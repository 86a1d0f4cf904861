"""Tiny sizes of the benchmark's cells, for driving a whole run on the CPU.

The graphs and windows are cut until a run takes seconds under the Pallas
interpreter; every other setting is the cell's own.
"""

from __future__ import annotations

import time

from bench.lib import harness

CELLS = ("graph500-s19.bfs", "graph500-s19.pagerank")


def tiny_inputs(cell_name: str, **mix_overrides):
    spec = harness.load_spec()
    cell = harness.find(spec["workloads"], cell_name, "workload")
    config = harness.load_config(spec, cell["config"])
    config["scale"] = 8
    mix = harness.load_mix(cell["traffic"])
    mix.update(mix_overrides)
    return spec, config, mix


def run_tiny(cell_name: str, seed: int = 2 ** 31 + 11, seconds: float = 0.4,
             trace: bool = False, **mix_overrides) -> dict:
    spec, config, mix = tiny_inputs(cell_name, **mix_overrides)
    return harness.run_cell(cell_name, seed, seconds, trace,
                            time.monotonic(), spec=spec, config=config,
                            mix=mix, chip=False)
