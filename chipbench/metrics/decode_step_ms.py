"""decode_step_ms (model step): device time of the decode program per
execution, over the wholly traced engine steps."""
from chipbench import programs, trace_reduce
from chipbench.readlib import steps_window_ns


def read(ctx):
    win = steps_window_ns(ctx)
    if win is None:
        return None
    runs = trace_reduce.program_runs(ctx.trace, programs.DECODE, *win)
    if not runs:
        return None
    return 1e3 * trace_reduce.seconds(runs) / len(runs)
