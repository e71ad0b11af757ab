"""Readings that the correctness limit of a cell is set from, on the
chip: the program's ``max_logit_gap`` over many seeds, and the float8
control's on some of them, all in one process.

    python3 chipbench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 11,12,13,... --control 3

Per seed: new weights in the same warmed engine, one ramp + window +
drain at the cell's own rate, then the cell's replay sample through the
float32 reference and, for the first ``--control`` seeds, through the
reference in float8 (the control).  Prints one JSON line per seed and a
summary line: the largest program reading (the lower end of the limit)
and the smallest control reading (the upper end).  Not part of a
benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import check  # noqa: E402
from chipbench import run as bench  # noqa: E402
from chipbench import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args(argv)
    seeds = [int(x) for x in args.seeds.split(",")]
    r = spec.resolve(args.workload)
    cell = r["cell"]
    prep = bench.Prepared(r, seeds[0])
    prog, ctrl = [], []
    for i, seed in enumerate(seeds):
        if i:
            prep.reseed(seed)
        ctx, _ = prep.window(seed, args.seconds, float(cell["rate_rps"]))
        nums = check.readings(prep.ref, prep.params, r["config"], cell,
                              r["traffic"], ctx.window, seed,
                              control=i < args.control)
        nums.update(seed=seed, unserved=ctx.unserved)
        prog.append(nums["max_logit_gap"])
        if "control_gap" in nums:
            ctrl.append(nums["control_gap"])
        print(json.dumps(nums), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(prog),
                      "program_max": max(prog),
                      "control_min": min(ctrl) if ctrl else None}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
