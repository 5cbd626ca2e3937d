"""Requests answered 200 inside the window, over the window's seconds."""


def read(ctx):
    n = sum(1 for r in ctx.all_records
            if r.get("status") == 200 and "error" not in r
            and 0.0 <= r.get("done", -1.0) < ctx.seconds)
    ctx.notes["completed_in_window"] = n
    return n / ctx.seconds if n else None
