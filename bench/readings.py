#!/usr/bin/env python3
"""Read a cell's compared numbers over many seeds in one process.

    python bench/readings.py --workload <name> --seeds 1,2,3 [--control]
    python bench/readings.py --workload <name> --seeds 1,2 --n 128  # CPU

For each seed: the operand sets from the seed, one call on each (as
many as a run checks in full), and the reference's numbers for them.
``--control`` runs the program in the configuration's control format
(its next-lower posit format, a path the program has of its own) in
place of the stated one: the control has to come out as not correct.  The compile is paid once
for all seeds.  The limits in bench/limits/ were set from these readings
(PERF.md).  Needs a TPU unless ``--n`` gives a rehearsal order.
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--n", type=int, help="matrix order (CPU rehearsal)")
    args = ap.parse_args(argv)
    c = run.load_cell(args.workload)
    try:
        jax, devices = run.start_jax(c["cell"]["chips"],
                                     require_tpu=args.n is None)
    except run.NoDevice as e:
        run.log(f"readings: {e}")
        return 3
    if args.n:
        c["cfg"] = run.rehearsal_size(c["cfg"], args.n)
    fmt = run.program_format(c["cfg"], control=args.control)
    worst: dict = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = run.Cell(c, seed, fmt, jax)
        calls = [cell.one(i) for i in range(len(cell.host))]
        nums, _ = cell.check(calls, seed)
        del cell, calls
        run.merge_worst(worst, nums)
        print(json.dumps({"seed": seed, "format": str(fmt), **nums}),
              flush=True)
    print(json.dumps({"workload": args.workload, "format": str(fmt),
                      "control": args.control, "worst": worst}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
