"""Run a cell's correctness control: the plain reference in the program's
place, one step below the precision the configuration states, through the
cell's own comparison. Every seed should come out not correct.

    python3 bench/control.py --workload <cell> [<cell> ...] --seeds 1 2 3

PageRank is computed with every stored vector rounded to bfloat16 (the
configuration states float32); BFS levels, which state no precision, come
from a traversal stopped one level early. The graph is the cell's, at its own
size, made from each seed (once for the cells of one configuration); the
program is not built. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def control_checks(cell_name: str, seed: int, *, spec=None, config=None,
                   mix=None, made=None):
    """The checks the control's answers get, as a run would print them.
    ``made`` maps a configuration's name to the graph and reference already
    made from ``seed``; a new one is added to it."""
    from bench.lib import harness
    from bench.lib.generators import generate
    from bench.lib.reference import Reference
    spec = spec if spec is not None else harness.load_spec()
    cell = harness.find(spec["workloads"], cell_name, "workload")
    config = config if config is not None else harness.load_config(
        spec, cell["config"])
    mix = mix if mix is not None else harness.load_mix(cell["traffic"])
    driver = harness.load_driver(mix["driver"])
    ctx = harness.Ctx(cell=cell, config=config, mix=mix, seed=seed,
                      chips=int(cell["chips"]), log=harness.log)
    made = {} if made is None else made
    if config["name"] not in made:
        rows, cols, n = generate(config, seed)
        made[config["name"]] = (rows, cols, n, Reference(rows, cols, n))
    rows, cols, n, ref = made[config["name"]]
    win = driver.control(ctx, ref, rows, n, float(spec["run_seconds"]))
    checks, _ = driver.finish(ctx, {"rows": rows, "cols": cols, "n": n},
                              win)
    return checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    caught = 0
    for seed in args.seeds:
        made = {}
        for cell in args.workload:
            checks = control_checks(cell, seed, made=made)
            correct = all(c.ok for c in checks)
            caught += not correct
            print(json.dumps({"workload": cell, "seed": seed,
                              "control_correct": correct,
                              "checks": {c.name: {"value": c.value,
                                                  "limit": c.limit}
                                         for c in checks}}), flush=True)
    return 0 if caught == len(args.seeds) * len(args.workload) else 1


if __name__ == "__main__":
    sys.exit(main())
