#!/usr/bin/env python3
"""Readings that the limits of the correctness check are set from.

    python3 bench/calibrate.py --workload <cell> --first-seed <n> --seeds <k>
        [--seconds <s>] [--out <file>]

In one process and on the chip: one warm-up, then for each of ``k`` seeds a
short window (one repetition at ``--seconds 0``) at the cell's own load,
whose repetitions are held to the float64 reference twice: as the program
produced them, and with the control -- the same reference computed in
float32 -- in the program's place.  Prints one JSON line per seed with both
sets of numbers.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    cell = harness.load_cell(args.workload, ROOT)
    out = open(args.out, "a") if args.out else sys.stdout
    try:
        with harness.Session(cell, False, ROOT) as session:
            session.warm_up(args.first_seed)
            for k in range(args.seeds):
                seed = args.first_seed + k
                reps = session.window(seed, args.seconds)[0]
                for r in reps:
                    r.extract()
                gc.collect()
                prog = harness.check_reps(cell, reps)
                ctl = harness.check_reps(cell, reps, ft=np.float32)
                row = {"workload": cell.name, "seed": seed, "reps": len(reps),
                       "program": {k: v for k, (v, _) in
                                   prog["numbers"].items()},
                       "program_correct": prog["correct"],
                       "control": {k: v for k, (v, _) in
                                   ctl["numbers"].items()},
                       "control_correct": ctl["correct"]}
                print(json.dumps(row), file=out, flush=True)
    finally:
        if out is not sys.stdout:
            out.close()


if __name__ == "__main__":
    main()
