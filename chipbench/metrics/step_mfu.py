"""step_mfu (whole engine step): operations every prefill and decode
token of the wholly traced steps needs, at the chip's bf16 peak, over
the wall time those steps span, in percent."""
from chipbench import flops
from chipbench.readlib import share, steps_in_trace


def read(ctx):
    steps = steps_in_trace(ctx)
    if not steps:
        return None
    need = sum(flops.prefill_flops(ctx.cfg, p) for s in steps for p in s[2])
    need += sum(flops.decode_flops(ctx.cfg, s[3]) for s in steps if s[3])
    return share(need / ctx.peaks["bf16_flops_per_s"],
                 steps[-1][1] - steps[0][0])
