"""device_idle_share (device): share of the traced window in which no
operation ran on the device, in percent (1 - union of op intervals /
window)."""
from chipbench import trace_reduce


def read(ctx):
    lo, hi = ctx.trace_window_ns
    if hi <= lo:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_ns(ctx.trace, lo, hi) / (hi - lo))
