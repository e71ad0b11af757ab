"""prefill_mfu (prefill): operations the prompts prefilled while the
profiler was on need (``flops.prefill_flops``) at the chip's bf16 peak,
over the device time of the prefill program, in percent.  Prefills are
the program's ``serve.prefill`` spans inside the wholly traced steps."""
from chipbench import flops, programs, trace_reduce
from chipbench.readlib import obs_spans, share, steps_window_ns


def read(ctx):
    win = steps_window_ns(ctx)
    if win is None:
        return None
    lo, hi = win
    spans = [s for s in obs_spans(ctx, {"serve.prefill"})
             if lo <= s[1] * 1e9 + ctx.offset_ns and
             s[2] * 1e9 + ctx.offset_ns <= hi]
    runs = trace_reduce.program_runs(ctx.trace, programs.PREFILL, lo, hi)
    need = sum(flops.prefill_flops(ctx.cfg, s[3]["prompt_len"])
               for s in spans)
    return share(need / ctx.peaks["bf16_flops_per_s"],
                 trace_reduce.seconds(runs))
