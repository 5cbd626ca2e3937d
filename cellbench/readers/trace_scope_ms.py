"""Device time of the operations of one executable whose
``jax.named_scope`` path holds one of ``scopes``, per model step: self
time on the trace's 'XLA Ops' line (a ``while`` keeps only what is not
its body), all layers of a step together, over runs x ``chunk_tokens``
steps.  The whole table — every scope's ms per step, and ``unscoped``
— goes to the notes.  A trace whose operations carry no scope path
(a program from before the scopes) has nothing to read: no value."""

from cellbench import scopes as scopes_mod


def read(ctx, module: str, scopes: list[str]):
    if ctx.trace is None:
        return None
    table = scopes_mod.scope_table(module)
    if table is None or not table["runs"]:
        return None
    steps = table["runs"] * ctx.engine["chunk_tokens"]
    per_step = {k: v / steps * 1000.0 for k, v in table["seconds"].items()}
    ctx.notes[f"scopes:{module}"] = {
        "ms_per_step": per_step, "runs": table["runs"], "steps": steps,
        "module_ms_per_step": table["module_seconds"] / steps * 1000.0}
    if not table["scoped"]:
        return None
    return sum(per_step.get(s, 0.0) for s in scopes)
