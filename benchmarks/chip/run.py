#!/usr/bin/env python3
"""On-chip benchmark of DRIFT serving: one run of one cell.

    python3 benchmarks/chip/run.py --workload dit-xl-512.drift-mild-closed8 \
        --seed 1234 --seconds 51 --trace 0

Runs the cell named in ``BENCHMARK.json`` on the TPU of this machine: sets
up and warms up the served engine, serves the cell's traffic for
``--seconds``, and checks the served finals against the plain reference.
The last line on standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``, each compared number with
its limit. The compared numbers are also the last lines on standard error.
Exits non-zero, printing no result, without a TPU.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import compare, runner, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    devices = runner.require_devices(cell.chips)
    runner.log(f"device {devices[0].platform} {devices[0].device_kind!r}, "
               f"{len(devices)} devices; workload {cell.name}, seed "
               f"{args.seed}, {args.seconds}s, trace {args.trace}")
    out = runner.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          T_START)
    for line in compare.describe(out["checks"]):
        runner.log(f"check {line}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
