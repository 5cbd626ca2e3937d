"""A GraniteMoeHybrid (Mamba-2 | attention, THEN held experts + a shared one,
every layer) cell's shares of the roofline: the least time the chip could
take for the bytes AND operations ``cellbench/costs_granite.py`` computes
from the configuration file's sizes and what the window's streams really
held — ``max(bytes / HBM peak, operations / FLOP peak)`` — over the device
time the trace read.  ``nemotron_roofline``'s readings of the same scopes,
kernels and counters, costed for this block.

what: "step" — the whole decode step against the decode-chunk executable's
time per step; "experts" — the grouped matmuls over the HELD experts against
the self time under ``moe_experts``; "attention" — each live key and value
once against the paged decode kernel's time (its events by name ``op``);
"ssm_step" — each live stream's recurrent state read and written once a
Mamba layer against the self time under ``ssm_step``; "ssm_scan" — the
chunked scan of a window dispatch (the larger of its operations and bytes)
against the self time under ``ssm_scan`` in the prompt-window executable,
a dispatch's mean width taken from the window's counters.  A program
without the scope, the kernel or the families (the parent, which cannot
build this configuration at all) has nothing to read: no value."""

from cellbench import costs, costs_granite
from cellbench.readers import nemotron_roofline
from cellbench.readers.mla_roofline import live_contexts

STEP_FN = "jit_paged_chunk_fn"


def _share(ctx, what: str, cost: dict, seconds: float, per: float, **facts):
    least, bound = costs.roofline_seconds(cost, ctx.peaks)
    ctx.notes[f"granite_roofline:{what}"] = {
        "bound": bound, "least_ms": least * 1000.0,
        "measured_ms": seconds / per * 1000.0, **facts, **cost}
    return least / (seconds / per) * 100.0


def read(ctx, what: str, op: str = "paged_decode_attention"):
    if ctx.trace is None or ctx.peaks is None:
        return None
    if what == "ssm_scan":
        seconds, runs = nemotron_roofline._scope_seconds(
            nemotron_roofline.WINDOW_FN, "ssm_scan")
        shape = nemotron_roofline.dispatch_width(ctx)
        if not seconds or shape is None:
            return None
        return _share(ctx, what, costs_granite.ssm_scan(ctx.config, *shape),
                      seconds, runs, dispatches=runs, rows=shape[0],
                      positions=shape[1])
    seconds, runs = ctx.trace.module_time(STEP_FN)
    steps = runs * ctx.engine["chunk_tokens"]
    if not steps:
        return None
    batch, tokens = live_contexts(ctx)
    if what == "step":
        cost = costs_granite.decode_step(ctx.config, batch, tokens)
    elif what == "experts":
        seconds, _ = nemotron_roofline._scope_seconds(STEP_FN, "moe_experts")
        cost = costs_granite.expert_matmuls(ctx.config, batch)
    elif what == "ssm_step":
        seconds, _ = nemotron_roofline._scope_seconds(STEP_FN, "ssm_step")
        cost = costs_granite.ssm_step(ctx.config, batch)
    elif what == "attention":
        seconds = ctx.trace.ops.get(op, 0.0)
        cost = costs_granite.attention_kernel(ctx.config, batch, tokens)
    else:
        raise ValueError(f"unknown what {what!r}")
    if not seconds:
        return None
    return _share(ctx, what, cost, seconds, steps, steps=steps,
                  live_streams=batch, live_tokens=tokens)
