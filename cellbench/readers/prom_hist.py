"""A statistic of one of the program's Prometheus histograms, taken as
the delta over the window (the registry is read in-process at the
window's two ends).  stat: "mean" (sum/count) or "pctile" (PromQL's
bucket interpolation, so only as fine as the program's buckets)."""

from cellbench import reduce


def read(ctx, family: str, stat: str, q: float = 0.95, scale: float = 1.0):
    h = ctx.prom_delta(family)
    if h is None or h["count"] <= 0:
        return None
    ctx.notes[family] = {"count": h["count"], "sum": h["sum"]}
    if stat == "mean":
        return h["sum"] / h["count"] * scale
    if stat == "pctile":
        v = reduce.hist_pctile(h, q)
        return None if v is None else v * scale
    raise ValueError(f"unknown stat {stat!r}")
