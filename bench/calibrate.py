"""Readings that a cell's limits are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 [--controls 3] [--out FILE]

For each seed, in one process, the ``readings`` of the cell's kind
(``bench/<kind>.py``): the program as a run drives it and, on the first
``--controls`` seeds, the control (the configuration's reference one
precision step lower, put in the program's place) and any faults planted in
the reference, each compared with the reference as a run compares the
program.  Prints one JSON line per seed; the benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3,
                    help="read the control and the faults on the first this many seeds")
    ap.add_argument("--seconds", type=float, default=5.0,
                    help="a serving cell's window for each seed")
    ap.add_argument("--out", default=None, help="also append each line to this file")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench.run import prepare
    from bench.common import require_chips

    cell = prepare(args.workload)
    require_chips(cell.chips)
    kind = importlib.import_module(f"bench.{cell.traffic['kind']}")
    for i, seed in enumerate(args.seeds):
        t = time.perf_counter()
        got = kind.readings(cell, seed, control=i < args.controls, seconds=args.seconds)
        line = json.dumps(dict(got, seconds=time.perf_counter() - t))
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
