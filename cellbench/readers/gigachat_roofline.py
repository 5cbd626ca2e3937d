"""A GigaChat3.5 (Gated DeltaNet | gated latent attention, dense or expert
FFN) cell's shares of the roofline and the readings that need its
counters: the least time the chip could take for the bytes AND operations
``cellbench/costs_gigachat.py`` computes from the configuration file's
sizes and what the window's streams really held — ``max(bytes / HBM peak,
operations / FLOP peak)`` — over the device time the trace read.

what: "step" — the whole decode step against the decode-chunk
executable's time per step; "experts" — the grouped matmuls over the HELD
experts against the self time under ``moe_experts``; "attention" — each
live latent row once against the latent decode kernel's time (its events by
name ``op``); "gdn_step" — every state row's matrices read and written once
a DeltaNet layer (ALL ``MAX_STREAMS`` rows: the step updates them where
they lie under a mask, so that is what it moves; the notes carry the live
rows' share, the ceiling a step over live rows alone would set) against the
self time under ``gdn_step``; "gdn_scan" — the chunked scan of a window
dispatch (what a fused kernel must move and do, the larger of the two)
against the self time under ``gdn_scan`` in the prompt-window executable, a
dispatch's mean width from the window's counters; "window_ms" — the self
time a prompt-window dispatch spends under ``scopes`` (an operation belongs
to the innermost of them on its path), in ms: where a dispatch's time goes,
part by part; "state_share" and "masked_pct" — ``nemotron_roofline``'s, from the
``ssm_state_*`` / ``ssm_scan_*`` families both recurrences share.  A
program without the scope, the kernel or the families (the parent) has
nothing to read: no value."""

from cellbench import costs, costs_gigachat
from cellbench.readers import nemotron_roofline, trace_subscope_ms
from cellbench.readers.mla_roofline import live_contexts


def read(ctx, what: str, module: str = "jit_paged_chunk_fn",
         scope: str = "", op: str = "latent_decode_attention",
         scopes: tuple = ()):
    if what in ("masked_pct", "state_share"):
        return nemotron_roofline.read(ctx, what)
    if ctx.trace is None or ctx.peaks is None:
        return None
    if what == "window_ms":
        t = trace_subscope_ms.table(nemotron_roofline.WINDOW_FN, list(scopes))
        if t is None or not t["runs"] or not any(
                s in t["seconds"] for s in scopes):
            return None
        ms = {k: v / t["runs"] * 1000.0 for k, v in t["seconds"].items()}
        ctx.notes[f"window_ms:{'+'.join(scopes)}"] = {
            "ms_per_dispatch": ms, "dispatches": t["runs"]}
        return sum(ms.get(s, 0.0) for s in scopes)
    if what == "gdn_scan":
        seconds, runs = nemotron_roofline._scope_seconds(
            nemotron_roofline.WINDOW_FN, "gdn_scan")
        if not seconds:
            return None
        shape = nemotron_roofline.dispatch_width(ctx)
        if shape is None:
            return None
        cost = costs_gigachat.gdn_scan(ctx.config, *shape)
        least, bound = costs.roofline_seconds(cost, ctx.peaks)
        ctx.notes["gigachat_roofline:gdn_scan"] = {
            "bound": bound, "least_ms": least * 1000.0,
            "measured_ms": seconds / runs * 1000.0, "dispatches": runs,
            "rows": shape[0], "positions": shape[1], **cost}
        return least / (seconds / runs) * 100.0
    seconds, runs = ctx.trace.module_time(module)
    steps = runs * ctx.engine["chunk_tokens"]
    if not steps:
        return None
    batch, tokens = live_contexts(ctx)
    if what == "step":
        cost = costs_gigachat.decode_step(ctx.config, batch, tokens)
    elif what == "experts":
        seconds, _ = nemotron_roofline._scope_seconds(module, scope or "moe_experts")
        cost = costs_gigachat.expert_matmuls(ctx.config, batch)
    elif what == "gdn_step":
        seconds, _ = nemotron_roofline._scope_seconds(module, scope or "gdn_step")
        cost = costs_gigachat.gdn_step(ctx.config, batch)
    elif what == "attention":
        seconds = ctx.trace.ops.get(op, 0.0)
        cost = costs_gigachat.latent_kernel(ctx.config, batch, tokens)
    else:
        raise ValueError(f"unknown what {what!r}")
    if not seconds:
        return None
    least, bound = costs.roofline_seconds(cost, ctx.peaks)
    ctx.notes[f"gigachat_roofline:{what}"] = {
        "bound": bound, "least_ms": least * 1000.0,
        "measured_ms": seconds / steps * 1000.0, "steps": steps,
        "live_streams": batch, "live_tokens": tokens, **cost}
    return least / (seconds / steps) * 100.0
