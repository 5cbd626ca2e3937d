"""Share of the traced span in which no operation ran on the device:
1 − union of the device's operation intervals / span, averaged over
the chips used."""


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace.idle_share() * 100.0
