"""Share of the device's idle seconds (gaps of the traced span, each
given to the host span that overlaps it most: ``trace.py``) that fall
under a host span the PROGRAM named — a name starting with one of
``prefixes`` (the phases of ``utils/tracing.phase``: ``loop/...``,
``dispatch:...``, ``admission``, ``prefill_window``) — and not under
one of the runtime's (``PjitFunction(insert)`` ...) or under none.  A
program that names no phase, as one from before the tracing seam, has
nothing to read: no value."""

from cellbench import scopes


def read(ctx, prefixes: list[str]):
    if ctx.trace is None:
        return None
    pre = tuple(prefixes)
    if not scopes.host_names(pre):
        return None
    idle = ctx.trace.idle_by_host
    total = sum(idle.values())
    named = sum(s for name, s in idle.items() if name.startswith(pre))
    ctx.notes["idle_named"] = {"named_s": named, "idle_s": total,
                               "spans": len(idle)}
    # no gap long enough to attribute: nothing is left unnamed
    return named / total * 100.0 if total > 0 else 100.0
