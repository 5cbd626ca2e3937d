"""One of the program's counters as a share, in per cent, of the sum of
it and others, each taken as its growth over the window:
``part / (part + rest...)``.  A program without the families, or a
window in which none of them moved, has nothing to read: no value."""


def read(ctx, part: str, rest: list[str]):
    deltas = {}
    for family in [part, *rest]:
        h = ctx.prom_delta(family)
        if h is None:
            return None
        deltas[family] = h["value"]
    total = sum(deltas.values())
    if total <= 0:
        return None
    ctx.notes[f"ratio:{part}"] = deltas
    return deltas[part] / total * 100.0
