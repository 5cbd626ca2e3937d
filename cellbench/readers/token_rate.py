"""Output tokens whose event arrived inside the window, over the
window's seconds — all streams, whenever they began."""


def read(ctx):
    n = sum(k for r in ctx.all_records for t, k in r.get("events", [])
            if 0.0 <= t < ctx.seconds)
    ctx.notes["tokens_in_window"] = n
    return n / ctx.seconds if n else None
