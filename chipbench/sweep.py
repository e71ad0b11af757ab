"""Find the highest request rate a cell's engine sustains, on the chip.

    python3 chipbench/sweep.py --workload <cell> --seed <n> \
        --seconds <s> --rates 4,8,12

One process: set-up once, then one ramp + window + drain per rate, in
the order given, each with its own seed.  Per rate it prints one JSON
line with the end-to-end metrics and what "sustained" rests on: every
request due in the window served within the grace, and no backlog
growing through the window (the median wait from due time to admission
in the window's last third against its first third).  The cell's
``rate_rps`` is then set by hand to about 0.8 of the highest sustained
rate.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import run as bench  # noqa: E402
from chipbench import spec  # noqa: E402
from chipbench.readlib import percentile  # noqa: E402


def sustain(ctx) -> dict:
    win = sorted(ctx.window, key=lambda x: x.due_pc)
    third = max(1, len(win) // 3)
    wait = lambda rs: percentile([x.req.t_admit_pc - x.due_pc for x in rs
                                  if x.req is not None and x.req.t_admit_pc],
                                 50)
    first, last = wait(win[:third]), wait(win[-third:])
    starts = [s for s, _ in ctx.step_times]
    per_step = collections.Counter(
        bisect.bisect_right(starts, x.req.t_admit_pc) for x in ctx.recs
        if x.req is not None and x.req.t_admit_pc)
    durs = [e - s for s, e in ctx.step_times if ctx.w0 <= s < ctx.w1]
    return {"attempted": len(win), "unserved": ctx.unserved,
            "steps": len(durs),
            "step_ms_p50": 1e3 * (percentile(durs, 50) or 0.0),
            "step_ms_p95": 1e3 * (percentile(durs, 95) or 0.0),
            "step_ms_max": 1e3 * max(durs, default=0.0),
            "max_admits_per_step": max(per_step.values(), default=0),
            "admit_wait_p50_first_third_ms": None if first is None
            else 1e3 * first,
            "admit_wait_p50_last_third_ms": None if last is None
            else 1e3 * last}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    r = spec.resolve(args.workload)
    prep = bench.Prepared(r, args.seed)
    for i, rate in enumerate(float(x) for x in args.rates.split(",")):
        try:
            ctx, _ = prep.window(args.seed + i, args.seconds, rate)
        except Exception as e:      # a rate the engine cannot run ends it
            print(json.dumps({"rate_rps": rate, "error": repr(e)[:400]}),
                  flush=True)
            return 1
        row = {"rate_rps": rate, "compiles_in_window":
               prep.compiles.compiles, **sustain(ctx),
               **{k: v["value"] for k, v in
                  bench.read_metrics(r["end_to_end"], ctx).items()}}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
