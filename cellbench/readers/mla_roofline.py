"""A latent-attention (DeepSeek-V2) decode step's share of the roofline,
or its grouped matmuls', or its latent attention kernel's: the least time
the chip could take for the bytes AND operations ``cellbench/costs_mla.py``
computes from the configuration file's sizes and the contexts the
window's streams really held — ``max(bytes / HBM peak, operations / FLOP
peak)``: at 128 heads a key the latent kernel sits on the chip's ridge —
over the device time the trace read.

what: "step" — the whole decode step against the decode-chunk
executable's time per step; "experts" — the grouped matmuls over the
HELD experts alone against the self time of the operations under the
program's ``scope`` (``moe_experts``), all expert layers of a step
together; "attention" — each live cached latent row ONCE a layer against
the latent kernel's time per step (its events by name ``op``).  A program
without the scope or the kernel has nothing to read there: no value."""

from cellbench import costs, costs_mla


def live_contexts(ctx) -> tuple[float, float]:
    """(streams, tokens of context) alive during the traced span, a mean
    over sample instants, from the load generator's records."""
    lo, hi = ctx.trace_span
    ts = [lo + (hi - lo) * (i + 0.5) / 16 for i in range(16)]
    streams = tokens = 0.0
    for t in ts:
        for r in ctx.all_records:
            if "first" not in r or not r["first"] <= t <= r.get("done", hi):
                continue
            streams += 1
            tokens += r["prompt_tokens"] + sum(k for te, k in r["events"] if te <= t)
    return streams / len(ts), tokens / len(ts)


def read(ctx, what: str, module: str, scope: str = "moe_experts",
         op: str = "latent_decode_attention"):
    if ctx.trace is None or ctx.peaks is None:
        return None
    seconds, runs = ctx.trace.module_time(module)
    steps = runs * ctx.engine["chunk_tokens"]
    if not steps:
        return None
    batch, tokens = live_contexts(ctx)
    cost = costs_mla.decode_step(ctx.config, batch, tokens)
    if what == "experts":
        from cellbench.readers import trace_subscope_ms

        table = trace_subscope_ms.table(module, [scope])
        seconds = (table or {}).get("seconds", {}).get(scope, 0.0)
        cost = costs_mla.expert_matmuls(ctx.config, batch)
    elif what == "attention":
        seconds = ctx.trace.ops.get(op, 0.0)
        cost = costs_mla.latent_kernel(ctx.config, batch, tokens)
    elif what != "step":
        raise ValueError(f"unknown what {what!r}")
    if not seconds:
        return None
    least, bound = costs.roofline_seconds(cost, ctx.peaks)
    ctx.notes[f"mla_roofline:{what}"] = {
        "bound": bound, "least_ms": least * 1000.0,
        "measured_ms": seconds / steps * 1000.0, "steps": steps,
        "live_streams": batch, "live_tokens": tokens, **cost}
    return least / (seconds / steps) * 100.0
