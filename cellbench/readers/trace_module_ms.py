"""Device time of the executables whose name matches ``module``, from
the profiler trace's 'XLA Modules' line, per run or per model step
(an executable that runs ``chunk_tokens`` decode steps per dispatch)."""


def read(ctx, module: str, per: str = "run"):
    if ctx.trace is None:
        return None
    seconds, runs = ctx.trace.module_time(module)
    if not runs:
        return None
    steps = runs * (ctx.engine["chunk_tokens"] if per == "step" else 1)
    ctx.notes[f"module:{module}"] = {"seconds": seconds, "runs": runs,
                                     "steps": steps}
    return seconds / steps * 1000.0
