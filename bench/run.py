#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted in ``setup_s``): the device check, the compile cache, one
warm-up repetition of the cell's scenario with a seed outside the measured
sequence, and the first time in a cache the compile of every epoch program
at every batch bucket.  The window: repetitions of
``run_scenario_on_cluster`` back to back, each on a fresh cluster with its
own seed drawn from ``--seed``, until ``--seconds`` have passed (the last
one runs to its end).  After it, untimed, every repetition is held to the
plain reference (bench/reference.py) and the numbers compared are printed
with their limits, last on standard error and under ``check`` last in the
result line.  ``--trace 1`` reports the per-layer metrics instead of the
end-to-end ones, with a profiler trace of the first repetition.

Exits non-zero, printing no result, off a TPU or with fewer chips than the
cell asks for.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def device_check(chips: int) -> None:
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if info["platform"] != "tpu" or info["count"] < chips:
        sys.exit(f"bench: needs {chips} TPU chip(s); JAX's devices are {info}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    cell = harness.load_cell(args.workload, ROOT)
    device_check(cell.chips)
    line = harness.run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace), T_START, ROOT)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
