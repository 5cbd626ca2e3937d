"""The growth of one of the program's counters over the window, per
second of window."""


def read(ctx, family: str, scale: float = 1.0):
    h = ctx.prom_delta(family)
    if h is None:
        return None
    ctx.notes[family] = {"delta": h["value"]}
    return h["value"] / ctx.seconds * scale
