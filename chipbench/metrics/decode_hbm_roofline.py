"""decode_hbm_roofline (model step): HBM bytes the decode steps need
(weights once per step, the live KV rows of the active slots read, one
row written per slot; ``flops.decode_bytes``) at the chip's peak HBM
bandwidth, over the device time of the decode program, in percent."""
from chipbench import flops, programs, trace_reduce
from chipbench.readlib import share, steps_in_trace, steps_window_ns


def read(ctx):
    win = steps_window_ns(ctx)
    if win is None:
        return None
    runs = trace_reduce.program_runs(ctx.trace, programs.DECODE, *win)
    need = sum(flops.decode_bytes(ctx.cfg, s[3])
               for s in steps_in_trace(ctx) if s[3])
    return share(need / ctx.peaks["hbm_bytes_per_s"],
                 trace_reduce.seconds(runs))
