"""Process start to the window's opening: import, weights, warm-up,
compilation in a run that compiles, the correctness check, the ramp."""


def read(ctx):
    return ctx.setup_s
