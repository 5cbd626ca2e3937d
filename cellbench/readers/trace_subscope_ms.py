"""Device self time, per model step, of the operations of one executable
whose ``jax.named_scope`` path's INNERMOST name among this reader's own
``scopes`` is one of them — for scopes the program nests inside the
parts ``cellbench/scopes.py`` knows (``moe_route`` / ``moe_experts`` /
``moe_combine`` inside ``mlp``), which that table folds into the part.
Built on ``scopes.load_xplane``, ``newest_xplane`` and ``scope_seconds``
(self time on the 'XLA Ops' line, a ``while`` keeps only what is not its
body).  A program whose paths hold none of the names (one from before
them) has nothing to read: no value."""

import re

from cellbench import scopes as scopes_mod

OTHER = "other"


def table(module: str, scopes: list[str]) -> dict | None:
    """{"seconds": {scope: s, "other": s}, "runs"} of the executables
    whose name matches ``module`` in the newest trace."""
    path = scopes_mod.newest_xplane()
    if path is None:
        return None
    rx = re.compile(module)
    seconds: dict[str, float] = {}
    runs = 0

    def innermost(p: str) -> str:
        best = OTHER
        for comp in p.split("/"):
            if comp in scopes:
                best = comp
        return best

    for plane in scopes_mod.load_xplane(path)["planes"]:
        if not plane["name"].startswith("/device:"):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        mods = [m[:3] for m in lines.get("XLA Modules", [])
                if rx.search(m[0].split("(", 1)[0])]
        runs += len(mods)
        ops = [[n, s, d, innermost(p)] for n, s, d, p in lines.get("XLA Ops", [])]
        for k, v in scopes_mod.scope_seconds(mods, ops).items():
            seconds[k] = seconds.get(k, 0.0) + v
    return {"seconds": seconds, "runs": runs}


def read(ctx, module: str, scopes: list[str]):
    if ctx.trace is None:
        return None
    t = table(module, scopes)
    if t is None or not t["runs"]:
        return None
    steps = t["runs"] * ctx.engine["chunk_tokens"]
    per_step = {k: v / steps * 1000.0 for k, v in t["seconds"].items()}
    ctx.notes[f"subscopes:{module}:{'+'.join(scopes)}"] = {
        "ms_per_step": per_step, "steps": steps}
    if not any(s in t["seconds"] for s in scopes):
        return None
    return sum(per_step.get(s, 0.0) for s in scopes)
