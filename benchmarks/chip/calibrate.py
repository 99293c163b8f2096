#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip.

    python3 benchmarks/chip/calibrate.py --workload dit-xl-512.drift-mild-closed8 \
        --seeds 11 12 13 --seconds 5 [--trace-first] [--out FILE]

For each seed, in one process: one run of the cell as ``run.py`` makes it
(set-up, a short window at the cell's own load, the drain), then the
compared numbers of the sampled batches for the program and for the control
(the plain reference one precision step down, int4 GEMMs, put in the
program's place). One JSON line per seed on standard output, and appended
to ``--out``. The limits in ``limits/<workload>.json`` are set from these
readings: above the largest program reading, below the smallest control
reading.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import runner, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--trace-first", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    runner.require_devices(cell.chips)
    t_start = T_START
    for i, seed in enumerate(args.seeds):
        out = runner.run_cell(cell, seed, args.seconds,
                              args.trace_first and i == 0, t_start,
                              control=True)
        line = json.dumps({"workload": cell.name, "seed": seed, **out})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
        t_start = time.monotonic()
    return 0


if __name__ == "__main__":
    sys.exit(main())
