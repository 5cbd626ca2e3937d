"""A Jamba (Mamba-1 | multi-query attention, a dense MLP behind each, tied
head) cell's shares of the roofline: the least time the chip could take for
the bytes AND operations ``cellbench/costs_jamba.py`` computes from the
configuration file's sizes and what the window's streams really held —
``max(bytes / HBM peak, operations / FLOP peak)`` — over the device time the
trace read.

what: "ssm_scan" — the selective scan of a window dispatch against the self
time under ``ssm_scan`` in the prompt-window executable, a dispatch's mean
width from the window's counters.  It binds on BYTES: the scan's work is
vector operations and exponentials, ``peaks.json`` has no vector-unit peak,
and the notes carry both counts for the benchmark issue that adds one.
Beside its own note this reading leaves the cell's other shares in the
notes (``jamba_roofline:<what>``; the benchmark holds 128 per-layer entries
and had room for one): "step" — the whole decode step against the
decode-chunk executable's time per step; "ssm_step" — every state row read
and written once a Mamba layer (ALL ``MAX_STREAMS`` rows: the step updates
them where they lie under a mask; the notes carry the live rows' ceiling)
against the self time under ``ssm_step``; "attention" — each live key and
value once against the paged decode kernel's time (its events by name
``op``); "proj_ms" / "window_proj_ms" — the self time of the four Mamba
projections (``ssm_in_proj`` + ``ssm_x_proj`` + ``ssm_dt_proj`` +
``ssm_out_proj``) a decode step / a window dispatch, in ms.  Each is also a
``what`` of its own, for the entries a later benchmark issue gives them.
(The shared ``ssm_scan_*`` families are read through the accepted
``ssm_scan_masked_pct.nemotron`` entry, which lists this cell.)  A program
without the scope or the kernel (the parent) has nothing to read: no
value."""

from cellbench import costs, costs_jamba
from cellbench.readers import nemotron_roofline, trace_subscope_ms
from cellbench.readers.mla_roofline import live_contexts

PROJ = ["ssm_in_proj", "ssm_x_proj", "ssm_dt_proj", "ssm_out_proj"]
STEP_FN = "jit_paged_chunk_fn"


def _proj_ms(ctx, module: str, per_run: float):
    """The four projections' self time a decode step (``per_run`` steps a
    run) or a window dispatch, in ms."""
    t = trace_subscope_ms.table(module, PROJ)
    if t is None or not t["runs"] or not any(s in t["seconds"] for s in PROJ):
        return None
    ms = {k: v / (t["runs"] * per_run) * 1000.0 for k, v in t["seconds"].items()}
    ctx.notes[f"jamba_roofline:proj_ms:{module}"] = ms
    return sum(ms.get(s, 0.0) for s in PROJ)


def _share(ctx, what: str, cost: dict, seconds: float, per: float, **facts):
    least, bound = costs.roofline_seconds(cost, ctx.peaks)
    ctx.notes[f"jamba_roofline:{what}"] = {
        "bound": bound, "least_ms": least * 1000.0,
        "measured_ms": seconds / per * 1000.0, **facts, **cost}
    return least / (seconds / per) * 100.0


def _scan(ctx):
    seconds, runs = nemotron_roofline._scope_seconds(
        nemotron_roofline.WINDOW_FN, "ssm_scan")
    shape = nemotron_roofline.dispatch_width(ctx)
    if not seconds or shape is None:
        return None
    return _share(ctx, "ssm_scan", costs_jamba.ssm_scan(ctx.config, *shape),
                  seconds, runs, dispatches=runs, rows=shape[0], positions=shape[1])


def _step_share(ctx, what: str, op: str):
    seconds, runs = ctx.trace.module_time(STEP_FN)
    steps = runs * ctx.engine["chunk_tokens"]
    if not steps:
        return None
    batch, tokens = live_contexts(ctx)
    if what == "step":
        cost = costs_jamba.decode_step(ctx.config, batch, tokens)
    elif what == "ssm_step":
        seconds, _ = nemotron_roofline._scope_seconds(STEP_FN, "ssm_step")
        cost = costs_jamba.ssm_step(ctx.config, batch)
    else:
        seconds = ctx.trace.ops.get(op, 0.0)
        cost = costs_jamba.attention_kernel(ctx.config, batch, tokens)
    if not seconds:
        return None
    return _share(ctx, what, cost, seconds, steps, steps=steps,
                  live_streams=batch, live_tokens=tokens)


def read(ctx, what: str, op: str = "paged_decode_attention"):
    if ctx.trace is None or ctx.peaks is None:
        return None
    if what == "proj_ms":
        return _proj_ms(ctx, STEP_FN, ctx.engine["chunk_tokens"])
    if what == "window_proj_ms":
        return _proj_ms(ctx, nemotron_roofline.WINDOW_FN, 1.0)
    if what in ("step", "ssm_step", "attention"):
        return _step_share(ctx, what, op)
    if what != "ssm_scan":
        raise ValueError(f"unknown what {what!r}")
    for other in ("step", "ssm_step", "attention"):
        _step_share(ctx, other, op)
    _proj_ms(ctx, STEP_FN, ctx.engine["chunk_tokens"])
    _proj_ms(ctx, nemotron_roofline.WINDOW_FN, 1.0)
    return _scan(ctx)
