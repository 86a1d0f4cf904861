"""Run one cell of the benchmark on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, traffic mix and metrics are looked up by name
in ``BENCHMARK.json`` (see ``bench/lib/harness.py``). The run builds its
graph from the seed, warms the cell's shapes, measures for ``--seconds``,
checks the answers it produced against the plain reference, and prints one
JSON object as the last line of standard output. Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits with 3.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.lib import harness
    try:
        out = harness.run_cell(args.workload, args.seed % (1 << 64),
                               args.seconds, bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    harness.print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
