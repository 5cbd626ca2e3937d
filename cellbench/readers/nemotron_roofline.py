"""A Nemotron-H (Mamba-2 | attention | LatentMoE) cell's shares of the
roofline and the readings that need its counters: the least time the chip
could take for the bytes AND operations ``cellbench/costs_nemotron.py``
computes from the configuration file's sizes and what the window's
streams really held — ``max(bytes / HBM peak, operations / FLOP peak)`` —
over the device time the trace read.

what: "step" — the whole decode step against the decode-chunk
executable's time per step; "experts" — the grouped matmuls over the HELD
experts against the self time under ``moe_experts``; "attention" — each
live key and value once against the paged decode kernel's time (its
events by name ``op``); "ssm_step" — each live stream's recurrent state
read and written once a Mamba layer against the self time under
``ssm_step``; "ssm_scan" — the chunked scan of a window dispatch (the
larger of its operations and bytes) against the self time under
``ssm_scan`` in the prompt-window executable, a dispatch's mean width
taken from the window's counters; "scan_ms" — that self time a dispatch,
in ms; "state_share" — ``ssm_state_bytes`` over itself plus the committed
KV bytes, at the window's end, in per cent; "masked_pct" — the share of
scanned positions that were padding or fill.  A program without the
scope, the kernel or the families (the parent) has nothing to read: no
value."""

from cellbench import costs, costs_nemotron
from cellbench.readers.mla_roofline import live_contexts

WINDOW_FN = "jit_paged_prefill_chunk_fn"


def _delta(ctx, family: str):
    h = ctx.prom_delta(family)
    return None if h is None else h["value"]


def dispatch_width(ctx):
    """(rows, positions) of the window's mean prompt-window dispatch: a
    dispatch is one window alone or the boundary's full width."""
    scanned = _delta(ctx, "ssm_scan_tokens")
    alone, batched = _delta(ctx, "prefill_windows_alone"), _delta(
        ctx, "prefill_windows_batched")
    if not scanned or alone is None or batched is None:
        return None
    env = ctx.config["env"]
    c = int(env["PREFILL_CHUNK"])
    width = -(-int(env.get("PREFILL_BUDGET", c)) // c)
    wide = max(scanned / c - alone, 0.0) / width  # full-width dispatches
    n = alone + wide
    return None if n <= 0 else ((alone + wide * width) / n, scanned / n)


def _scope_seconds(module: str, scope: str):
    from cellbench.readers import trace_subscope_ms

    t = trace_subscope_ms.table(module, [scope])
    if t is None or not t["runs"] or scope not in t["seconds"]:
        return None, 0
    return t["seconds"][scope], t["runs"]


def read(ctx, what: str, module: str = "jit_paged_chunk_fn",
         scope: str = "", op: str = "paged_decode_attention"):
    if what == "masked_pct":
        scanned, masked = _delta(ctx, "ssm_scan_tokens"), _delta(
            ctx, "ssm_scan_masked_tokens")
        if not scanned or masked is None:
            return None
        ctx.notes["ssm_scan"] = {"scanned": scanned, "masked": masked}
        return masked / scanned * 100.0
    if what == "state_share":
        state = ctx.prom_after.get("ssm_state_bytes")
        kv = ctx.prom_after.get("kv_committed_bytes")
        if state is None or kv is None or state["value"] + kv["value"] <= 0:
            return None
        ctx.notes["stream_state"] = {"ssm_state_bytes": state["value"],
                                     "kv_committed_bytes": kv["value"]}
        return state["value"] / (state["value"] + kv["value"]) * 100.0
    if ctx.trace is None or ctx.peaks is None:
        return None
    if what in ("ssm_scan", "scan_ms"):
        seconds, runs = _scope_seconds(WINDOW_FN, "ssm_scan")
        if not seconds:
            return None
        if what == "scan_ms":
            ctx.notes["ssm_scan_ms"] = {"seconds": seconds, "dispatches": runs}
            return seconds / runs * 1000.0
        shape = dispatch_width(ctx)
        if shape is None:
            return None
        cost = costs_nemotron.ssm_scan(ctx.config, *shape)
        least, bound = costs.roofline_seconds(cost, ctx.peaks)
        ctx.notes["nemotron_roofline:ssm_scan"] = {
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
        cost = costs_nemotron.decode_step(ctx.config, batch, tokens)
    elif what == "experts":
        seconds, _ = _scope_seconds(module, scope or "moe_experts")
        cost = costs_nemotron.expert_matmuls(ctx.config, batch)
    elif what == "ssm_step":
        seconds, _ = _scope_seconds(module, scope or "ssm_step")
        cost = costs_nemotron.ssm_step(ctx.config, batch)
    elif what == "attention":
        seconds = ctx.trace.ops.get(op, 0.0)
        cost = costs_nemotron.attention_kernel(ctx.config, batch, tokens)
    else:
        raise ValueError(f"unknown what {what!r}")
    if not seconds:
        return None
    least, bound = costs.roofline_seconds(cost, ctx.peaks)
    ctx.notes[f"nemotron_roofline:{what}"] = {
        "bound": bound, "least_ms": least * 1000.0,
        "measured_ms": seconds / steps * 1000.0, "steps": steps,
        "live_streams": batch, "live_tokens": tokens, **cost}
    return least / (seconds / steps) * 100.0
