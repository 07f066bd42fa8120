#!/usr/bin/env python3
"""Run one cell of the FedCore fleet-round chip benchmark once.

    python3 benchmarks/chip/run.py \\
        --workload shakespeare_lstm.stragglers30 --seed 7 --seconds 10 --trace 0

Cells, metrics and their files are listed in ``BENCHMARK.json`` at the
root of the checkout.  Progress, the set-up split, the group shapes and
each number of the correctness check with its limit go to standard
error; the last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``
(``--trace 1``: per-layer metrics, ``breakdown``, busy and window
seconds).  Exits non-zero with no result where JAX finds no TPU or fewer
chips than the cell needs.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (HERE.parents[1] / "src" / "repro").is_dir():
        print("the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    from fedbench import harness
    result = harness.run(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
