"""An AFMoE (Trinity) decode step's share of the roofline, or its
grouped matmuls', or its paged attention kernel's: the least time the
chip could take for the bytes and operations ``cellbench/costs_afmoe.py``
computes from the configuration file's sizes and the contexts the
window's streams really held, over the device time the trace read.

what: "step" — the whole decode step against the decode-chunk
executable's time per step; "experts" — the grouped matmuls alone
against the self time of the operations under the program's ``scope``
(``moe_experts``), all expert layers of a step together; "attention" —
the KV a step reads, WINDOW-AWARE (a sliding layer ``min(context,
window)`` keys a stream), against the paged attention kernel's time per
step (its events by name ``op``, the full layers' width and the window
layers' narrower view together).  A program without the scope or the
kernel has nothing to read there: no value."""

from cellbench import costs, costs_afmoe


def live_contexts(ctx) -> tuple[float, float, float]:
    """(streams, tokens of context, tokens of context inside the
    configuration's sliding window) alive during the traced span, a mean
    over sample instants, from the load generator's records."""
    window = int(ctx.config["sliding_window"])
    lo, hi = ctx.trace_span
    ts = [lo + (hi - lo) * (i + 0.5) / 16 for i in range(16)]
    streams = tokens = inside = 0.0
    for t in ts:
        for r in ctx.all_records:
            if "first" not in r or not r["first"] <= t <= r.get("done", hi):
                continue
            n = r["prompt_tokens"] + sum(k for te, k in r["events"] if te <= t)
            streams += 1
            tokens += n
            inside += min(n, window)
    return streams / len(ts), tokens / len(ts), inside / len(ts)


def read(ctx, what: str, module: str, scope: str = "moe_experts",
         op: str = "paged_decode_attention"):
    if ctx.trace is None or ctx.peaks is None:
        return None
    seconds, runs = ctx.trace.module_time(module)
    steps = runs * ctx.engine["chunk_tokens"]
    if not steps:
        return None
    batch, tokens, inside = live_contexts(ctx)
    cost = costs_afmoe.decode_step(ctx.config, batch, tokens, inside)
    if what == "experts":
        from cellbench.readers import trace_subscope_ms

        table = trace_subscope_ms.table(module, [scope])
        seconds = (table or {}).get("seconds", {}).get(scope, 0.0)
        cost = costs_afmoe.expert_matmuls(ctx.config, batch)
    elif what == "attention":
        seconds = ctx.trace.ops.get(op, 0.0)
        cost = {"bytes": costs_afmoe.kv_read_bytes(ctx.config, tokens, inside),
                "flops": 0.0}
    elif what != "step":
        raise ValueError(f"unknown what {what!r}")
    if not seconds:
        return None
    least, bound = costs.roofline_seconds(cost, ctx.peaks)
    ctx.notes[f"afmoe_roofline:{what}"] = {
        "bound": bound, "least_ms": least * 1000.0,
        "measured_ms": seconds / steps * 1000.0, "steps": steps,
        "live_streams": batch, "live_tokens": tokens,
        "live_tokens_in_window": inside, **cost}
    return least / (seconds / steps) * 100.0
