"""A decode step's (or the paged attention kernel's) share of its
roofline: the least time the chip could take for the bytes and
operations the step needs (cellbench/costs.py, from the configuration
file's sizes and the context the window's streams really held) over
the device time the trace read.

what: "step" — the whole decode step (weights + live KV) against the
decode-chunk executable's time per step; "attention" — the live KV
alone against the paged attention kernel's time per step (its events
on the 'XLA Ops' line, all layers of a step together)."""

from cellbench import costs


def live_context(ctx) -> tuple[float, float]:
    """(streams, tokens of context) alive during the traced span, a
    mean over sample instants, from the load generator's records."""
    lo, hi = ctx.trace_span
    ts = [lo + (hi - lo) * (i + 0.5) / 16 for i in range(16)]
    streams = tokens = 0.0
    for t in ts:
        for r in ctx.all_records:
            if "first" not in r or not r["first"] <= t <= r.get("done", hi):
                continue
            streams += 1
            tokens += r["prompt_tokens"] + sum(
                k for te, k in r["events"] if te <= t)
    return streams / len(ts), tokens / len(ts)


def read(ctx, what: str, module: str, op: str = ""):
    if ctx.trace is None or ctx.peaks is None:
        return None
    seconds, runs = ctx.trace.module_time(module)
    steps = runs * ctx.engine["chunk_tokens"]
    if not steps:
        return None
    batch, tokens = live_context(ctx)
    cost = costs.decode_step(ctx.config, batch, tokens)
    if what == "attention":
        seconds = ctx.trace.ops.get(op, 0.0)
        if not seconds:
            return None
        cost = {"bytes": cost["kv_bytes"], "flops": 0.0}
    least, bound = costs.roofline_seconds(cost, ctx.peaks)
    ctx.notes[f"roofline:{what}"] = {
        "bound": bound, "least_ms": least * 1000.0,
        "measured_ms": seconds / steps * 1000.0, "steps": steps,
        "live_streams": batch, "live_tokens": tokens, **cost}
    return least / (seconds / steps) * 100.0
