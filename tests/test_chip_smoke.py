"""chip_smoke.py's phases at a tiny scale on the CPU (Pallas interpreted).

The script itself refuses to run without a TPU; here its phase functions
build a scale-9 Graph500 graph and check BFS, SSSP, PageRank, CC and the
served query mix against the scipy/numpy reference, so a broken phase is
found before any chip time is spent.
"""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from conftest import SCRIPT_TIMEOUT_S

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def graph(smoke):
    rows, cols, n = smoke.make_graph(9, seed=0)
    return smoke.build(rows, cols, n), smoke.Reference(rows, cols, n)


def test_build_keeps_padded_ell_off(graph):
    g, _ = graph
    assert g.ell_cache is None and g.transposed().ell_cache is None
    assert g.buckets().real_words() == g.tiles.n_tiles


@pytest.mark.parametrize("backend", ["b2sr_pallas", "b2sr"])
def test_algorithms_match_reference(smoke, graph, backend):
    g, ref = graph
    smoke.run_algorithms(g, ref, int(np.argmax(ref.outdeg)), backend)


def test_served_mix_matches_reference(smoke, graph):
    g, ref = graph
    smoke.serve(g, ref, n_queries=12, seed=0)


def test_reference_catches_a_wrong_answer(smoke, graph):
    _, ref = graph
    want = ref.levels([0])[0]
    bad = want.copy()
    bad[np.argmax(want)] += 1
    with pytest.raises(AssertionError):
        smoke.check_exact("bfs", bad, want)


def test_refuses_without_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=SCRIPT_TIMEOUT_S)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout
