"""A percentile over the window's requests of one client-side time.

field: "ttft" = first token event − due (open loop: due by schedule;
closed loop: due == sent); "latency" = full response − sent; "late" =
sent − due (how late the generator ran).  A failed request counts as
the window's length, so it never drops out of a tail."""

from cellbench import reduce


def read(ctx, field: str, q: float, scale: float = 1000.0):
    xs, n_failed = [], 0
    for r in ctx.records:
        if field == "late":
            if "sent" in r:
                xs.append(r["sent"] - r["due"])
            continue
        if reduce.failed(r, ctx.stream):
            xs.append(ctx.seconds)
            n_failed += 1
        elif field == "ttft":
            xs.append(r["first"] - r["due"])
        elif field == "latency":
            xs.append(r["done"] - r["sent"])
        else:
            raise ValueError(f"unknown field {field!r}")
    if not xs:
        return None
    ctx.notes[f"{field}_s"] = {"median": reduce.median(xs), "n": len(xs),
                               f"p{round(q * 100)}": reduce.pctile(xs, q),
                               "failed": n_failed}
    return reduce.pctile(xs, q) * scale
