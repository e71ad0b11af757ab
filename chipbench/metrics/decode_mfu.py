"""decode_mfu (model step): operations the decode steps need (one token
per active slot over its live context; ``flops.decode_flops``) at the
chip's bf16 peak, over the device time of the decode program, in
percent."""
from chipbench import flops, programs, trace_reduce
from chipbench.readlib import share, steps_in_trace, steps_window_ns


def read(ctx):
    win = steps_window_ns(ctx)
    if win is None:
        return None
    runs = trace_reduce.program_runs(ctx.trace, programs.DECODE, *win)
    need = sum(flops.decode_flops(ctx.cfg, s[3])
               for s in steps_in_trace(ctx) if s[3])
    return share(need / ctx.peaks["bf16_flops_per_s"],
                 trace_reduce.seconds(runs))
