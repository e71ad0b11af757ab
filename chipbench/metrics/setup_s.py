"""setup_s: seconds from process start to the window's opening (weights,
warm-up of every shape the traffic uses, and the ramp)."""


def read(ctx):
    return ctx.setup_s
