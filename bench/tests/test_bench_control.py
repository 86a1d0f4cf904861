"""The correctness control fails each cell's comparison (tiny sizes)."""

from __future__ import annotations

import pytest

import bench.lib.generators as generators
from bench.control import control_checks
from bench.tests.tiny import CELLS, tiny_inputs


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [7, 2 ** 31 + 3, 2 ** 40 + 1])
def test_control_is_not_correct(cell, seed):
    spec, config, mix = tiny_inputs(cell)
    checks = control_checks(cell, seed, spec=spec, config=config, mix=mix)
    failing = [c.name for c in checks if not c.ok]
    assert failing, [(c.name, c.value, c.limit) for c in checks]


def test_cells_of_one_configuration_share_the_seeds_graph(monkeypatch):
    made_graphs = []
    orig = generators.generate

    def generate(config, seed):
        made_graphs.append(seed)
        return orig(config, seed)
    monkeypatch.setattr(generators, "generate", generate)
    made = {}
    for cell in CELLS:
        spec, config, mix = tiny_inputs(cell)
        checks = control_checks(cell, 11, spec=spec, config=config, mix=mix,
                                made=made)
        assert not all(c.ok for c in checks)
    assert made_graphs == [11]
