"""A Phi-4-mini-flash (SambaY) cell's shares of the roofline: the least time
the chip could take for the bytes AND operations ``cellbench/costs_phi4flash.py``
computes from the configuration file's sizes and what the window's streams
really held — ``max(bytes / HBM peak, operations / FLOP peak)`` — over the
device time the trace read.

what: "step" — the whole decode step against the decode-chunk executable's
time per step; "attention_full" — the EIGHT reads of the one pool (the full
layer and the seven cross layers: each live key and value once a layer)
against the self time under ``attn_full`` + ``attn_cross``;
"attention_window" — the window layers' reads (at most ``sliding_window`` keys
a stream a layer) against the self time under ``attn_window``; "ssm_scan" —
the selective scan of a window dispatch against the self time under
``ssm_scan`` in the prompt-window executable (binds on BYTES: ``peaks.json``
has no vector-unit peak; the notes carry the vector operations and
exponentials); "gmu" — the Gated Memory Units of a step (both projections'
weights once) against the self time under ``gmu``; "prefill_self" — the
self-decoder's matrix work and bytes of a mean prompt dispatch against the
prompt-window executable's time a dispatch; "prefill_cross" — the share of a
dispatch's time the cross-decoder would ADD if it ran on every position (its
least time over the measured dispatch, in per cent: what the split saves,
from ``costs_phi4flash.prefill_dispatch``'s ``cross_all``; the served
dispatch runs none of it, and the counters ``prefill_self_positions`` /
``prefill_cross_positions`` in the notes say so).  A program without the scope
or the executable (the parent) has nothing to read: no value."""

from cellbench import costs, costs_phi4flash
from cellbench.readers import nemotron_roofline, trace_subscope_ms
from cellbench.readers.mla_roofline import live_contexts

STEP_FN = "jit_paged_chunk_fn"
SCOPES = {"attention_full": ["attn_full", "attn_cross"],
          "attention_window": ["attn_window"], "gmu": ["gmu"]}


def _share(ctx, what: str, cost: dict, seconds: float, per: float, **facts):
    least, bound = costs.roofline_seconds(cost, ctx.peaks)
    ctx.notes[f"phi4flash_roofline:{what}"] = {
        "bound": bound, "least_ms": least * 1000.0,
        "measured_ms": seconds / per * 1000.0, **facts,
        **{k: v for k, v in cost.items() if not isinstance(v, dict)}}
    return least / (seconds / per) * 100.0


def _scope_sum(module: str, scopes: list):
    t = trace_subscope_ms.table(module, scopes)
    if t is None or not t["runs"]:
        return 0.0
    return sum(t["seconds"].get(s, 0.0) for s in scopes)


def _step_share(ctx, what: str):
    seconds, runs = ctx.trace.module_time(STEP_FN)
    steps = runs * ctx.engine["chunk_tokens"]
    if not steps:
        return None
    batch, tokens = live_contexts(ctx)
    if what == "step":
        cost = costs_phi4flash.decode_step(ctx.config, batch, tokens)
    else:  # a cost function by the reading's own name
        seconds = _scope_sum(STEP_FN, SCOPES[what])
        cost = (costs_phi4flash.gmu(ctx.config, batch) if what == "gmu"
                else getattr(costs_phi4flash, what)(ctx.config, batch, tokens))
    if not seconds or not batch:
        return None
    return _share(ctx, what, cost, seconds, steps, steps=steps,
                  live_streams=batch, live_tokens=tokens)


def _dispatch_share(ctx, what: str):
    shape = nemotron_roofline.dispatch_width(ctx)
    if shape is None:
        return None
    if what == "ssm_scan":
        seconds, runs = nemotron_roofline._scope_seconds(
            nemotron_roofline.WINDOW_FN, "ssm_scan")
        cost = costs_phi4flash.ssm_scan(ctx.config, *shape)
    else:
        seconds, runs = ctx.trace.module_time(nemotron_roofline.WINDOW_FN)
        parts = costs_phi4flash.prefill_dispatch(ctx.config, *shape)
        cost = parts["self"] if what == "prefill_self" else parts["cross_all"]
    if not seconds or not runs:
        return None
    facts = {"dispatches": runs, "rows": shape[0], "positions": shape[1]}
    for fam in ("prefill_self_positions", "prefill_cross_positions"):
        facts[fam] = nemotron_roofline._delta(ctx, fam)
    return _share(ctx, what, cost, seconds, runs, **facts)


def read(ctx, what: str):
    if ctx.trace is None or ctx.peaks is None:
        return None
    if what in ("step", "attention_full", "attention_window", "gmu"):
        return _step_share(ctx, what)
    if what in ("ssm_scan", "prefill_self", "prefill_cross"):
        return _dispatch_share(ctx, what)
    raise ValueError(f"unknown what {what!r}")
