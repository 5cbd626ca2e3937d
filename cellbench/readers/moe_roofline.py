"""An expert-FFN decode step's (or its grouped matmuls') share of the
roofline: the least time the chip could take for the bytes and
operations ``cellbench/costs_moe.py`` computes from the configuration
file's sizes and the context the window's streams really held, over
the device time the trace read.

what: "step" — the whole decode step against the decode-chunk
executable's time per step; "experts" — the grouped matmuls alone (the
hit experts' weights, the assignments' activations, k experts' FLOPs a
token) against the self time of the operations under the program's
``scope`` (``moe_experts``), all layers of a step together.  A program
without that scope has nothing to read there: no value."""

from cellbench import costs, costs_moe
from cellbench.readers import decode_roofline


def read(ctx, what: str, module: str, scope: str = "moe_experts"):
    if ctx.trace is None or ctx.peaks is None:
        return None
    seconds, runs = ctx.trace.module_time(module)
    steps = runs * ctx.engine["chunk_tokens"]
    if not steps:
        return None
    batch, tokens = decode_roofline.live_context(ctx)
    cost = costs_moe.decode_step(ctx.config, batch, tokens)
    if what == "experts":
        from cellbench.readers import trace_subscope_ms

        table = trace_subscope_ms.table(module, [scope])
        seconds = (table or {}).get("seconds", {}).get(scope, 0.0)
        if not seconds:
            return None
        cost = costs_moe.expert_matmuls(ctx.config, batch)
    least, bound = costs.roofline_seconds(cost, ctx.peaks)
    ctx.notes[f"moe_roofline:{what}"] = {
        "bound": bound, "least_ms": least * 1000.0,
        "measured_ms": seconds / steps * 1000.0, "steps": steps,
        "live_streams": batch, "live_tokens": tokens, **cost}
    return least / (seconds / steps) * 100.0
