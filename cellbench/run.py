"""Run one cell once: boot, warm, check, measure, print.

    python3 -m cellbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``); everything else worth keeping is on earlier lines.
With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics (and a profiler trace of a few
seconds inside the window).  No chip, a device kind missing from
``peaks.json``, or a compile inside the window is an error: non-zero
exit, no result line.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

if __package__ in (None, ""):  # `python3 cellbench/run.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    __package__ = "cellbench"

from . import reduce, spec, traffic  # noqa: E402
from .loadgen import DRAIN_S  # noqa: E402

CHECK_SEED = 20240924  # the correctness prompts: the same in every run
TRACE_SECONDS = 3.0  # profiler span inside the window of a traced run
EXIT_USAGE, EXIT_NO_DEVICE, EXIT_COMPILED, EXIT_FAILED = 2, 3, 4, 5


def say(tag: str, obj) -> None:
    """An earlier line: ``cellbench <tag> <json>``."""
    print(f"cellbench {tag} {json.dumps(obj, default=str)}", flush=True)


class Context:
    """What a reader may read.  ``records`` are the window's requests."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def prom_delta(self, family: str) -> dict | None:
        after = self.prom_after.get(family)
        if after is None:
            return None
        return reduce.hist_delta(after, self.prom_before.get(family))


class CompileLog(logging.Handler):
    """Names of what JAX compiles while attached (its DEBUG lines
    ``Compiling <name> ...``), to say WHICH executable broke a window."""

    LOGGERS = ("jax._src.interpreters.pxla", "jax._src.dispatch")

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.names: list[str] = []
        self._saved: list = []

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg.split()[1])
        elif record.levelno >= logging.WARNING:
            print(msg, file=sys.stderr)

    def __enter__(self) -> "CompileLog":
        for name in self.LOGGERS:
            lg = logging.getLogger(name)
            self._saved.append((lg, lg.level, lg.propagate))
            lg.addHandler(self)
            lg.setLevel(logging.DEBUG)
            lg.propagate = False
        return self

    def __exit__(self, *exc) -> None:
        for lg, level, prop in self._saved:
            lg.removeHandler(self)
            lg.setLevel(level)
            lg.propagate = prop


def device_facts(peaks: dict, chips: int, rehearse: bool) -> dict:
    import jax

    devs = jax.devices()
    facts = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if rehearse:
        return facts
    if facts["platform"] != "tpu" or facts["kind"] not in peaks["devices"]:
        raise SystemExit(_refuse(
            f"need a TPU listed in peaks.json, JAX reports {facts}"))
    if facts["count"] < chips:
        raise SystemExit(_refuse(
            f"the cell asks for {chips} chip(s), JAX reports {facts}"))
    return facts


def _refuse(msg: str) -> int:
    print(f"cellbench: {msg}", file=sys.stderr, flush=True)
    return EXIT_NO_DEVICE


async def drive(svc, schedule: dict, work: str, name: str,
                trace: bool = False) -> types.SimpleNamespace:
    """Replay ``schedule`` against the running service from a child
    process and hold the window: counters read at both ends, compiles
    counted, a profiler span in the middle of a traced run.  Returns
    what the window left (records, counters, compiles, trace)."""
    import jax

    from mlmicroservicetemplate_tpu.runtime.compile_cache import CompileWindow

    w = types.SimpleNamespace()
    seconds = schedule["seconds"]
    _check_prompt_lengths(svc, schedule)
    schedule["url"] = svc.base + "/predict"
    schedule["ready_url"] = svc.base + "/healthz"
    sched_path = os.path.join(work, f"schedule_{name}.json")
    rec_path = os.path.join(work, f"records_{name}.jsonl")
    with open(sched_path, "w", encoding="utf-8") as f:
        json.dump(schedule, f)
    if os.path.exists(rec_path):
        os.remove(rec_path)
    child = await asyncio.create_subprocess_exec(
        sys.executable, os.path.join(spec.HERE, "loadgen.py"),
        sched_path, rec_path,
        stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE)
    try:
        line = await asyncio.wait_for(child.stdout.readline(), 60.0)
        if line.strip() != b"ready":
            raise RuntimeError(f"load generator said {line!r}")
        t0 = time.monotonic() + 0.25 + schedule["ramp_s"]
        child.stdin.write(f"{t0!r}\n".encode())
        await child.stdin.drain()
        await asyncio.sleep(max(t0 - time.monotonic(), 0.0))
        w.setup_s = time.monotonic() - T_PROCESS
        w.prom_before = reduce.parse_prom(svc.prom())
        w.trace_dir, w.trace_span = None, None
        with CompileWindow() as cw, CompileLog() as clog:
            if trace:
                span = min(TRACE_SECONDS, seconds / 2.0)
                await asyncio.sleep(max((seconds - span) / 2.0, 0.0))
                w.trace_dir = os.path.join(work, f"trace_{name}")
                shutil.rmtree(w.trace_dir, ignore_errors=True)
                # Python frames off: they slow the host path that is
                # being measured and swell the trace tenfold.
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                w.trace_span = [time.monotonic() - t0]
                jax.profiler.start_trace(w.trace_dir, profiler_options=opts)
                await asyncio.sleep(span)
                jax.profiler.stop_trace()
                w.trace_span.append(time.monotonic() - t0)
            await asyncio.sleep(max(t0 + seconds - time.monotonic(), 0.0))
            w.prom_after = reduce.parse_prom(svc.prom())
        w.window_end = time.monotonic() - t0
        w.compiles, w.compile_s, w.compiled = cw.compiles, cw.seconds, clog.names
        await asyncio.wait_for(child.wait(), DRAIN_S + 30.0)
    finally:
        if child.returncode is None:
            child.kill()
            await child.wait()
    if child.returncode != 0:
        raise RuntimeError(f"load generator exited {child.returncode}")
    with open(rec_path, encoding="utf-8") as f:
        w.all_records = [json.loads(ln) for ln in f if ln.strip()]
    w.records = [r for r in w.all_records if reduce.in_window(r, seconds)]
    return w


async def measure(cell: spec.Cell, a, work: str, device: dict, peaks: dict) -> int:
    from .service import Service, memory

    rehearse = a.rehearse is not None
    extra = {"DEVICE": "cpu" if rehearse else "tpu", "WARMUP": "1",
             "LOG_LEVEL": "WARNING"}
    async with Service(cell.config, work, extra) as svc:
        say("boot", svc.facts)
        ref = spec.load_module(
            os.path.join(cell.bench_dir, "references",
                         cell.config["reference"] + ".py"),
            f"cellbench_reference_{cell.config['reference']}")
        t = time.monotonic()
        check = await ref.check(svc, cell.config, CHECK_SEED)
        check["seconds"] = time.monotonic() - t
        say("correct", check)

        schedule = traffic.build(cell.traffic, cell.config["prompt"],
                                 a.seed, a.seconds)
        w = await drive(svc, schedule, work, cell.name, trace=bool(a.trace))
        mem = memory()
        status = await svc.status()
        engine_facts = _engine_facts(svc)
    if w.compiles or w.compiled:
        print(f"cellbench: {w.compiles} XLA compile(s) inside the measured "
              f"window ({w.compile_s:.2f}s): "
              f"{w.compiled or 'names not logged'} — not a measurement",
              file=sys.stderr, flush=True)
        return EXIT_COMPILED

    stream = cell.traffic["endpoint"] == "stream"
    records, all_records = w.records, w.all_records
    bad = [r for r in records if reduce.failed(r, stream)]
    say("window", {
        "seconds": a.seconds, "measured_end": w.window_end,
        "requests_all": len(all_records), "requests_in_window": len(records),
        "failed": len(bad), "failed_sample": bad[:3], "setup_s": w.setup_s,
        "compiles_in_window": w.compiles, "engine": engine_facts,
        "decode": status.get("decode"),
    })
    tr = None
    if w.trace_dir is not None:
        from . import trace as trace_mod

        try:
            tr = trace_mod.summarize(w.trace_dir)
            say("trace", tr.describe())
        except ValueError as e:
            if not rehearse:  # a traced run with no device operation
                raise RuntimeError(str(e)) from None
            say("trace", {"rehearsal": str(e)})
    ctx = Context(
        config=cell.config, mix=cell.traffic, seconds=a.seconds,
        records=records, all_records=all_records, stream=stream,
        trace_span=w.trace_span, setup_s=w.setup_s,
        prom_before=w.prom_before, prom_after=w.prom_after, trace=tr,
        peaks=peaks["devices"].get(device["kind"]), engine=engine_facts,
        notes={},
    )
    values = {}
    for m in (cell.per_layer if a.trace else cell.end_to_end):
        v = m.read(ctx, **m.args)
        if v is not None:  # a reader that finds nothing returns nothing
            values[m.name] = {"value": v, "unit": m.unit}
    say("timings", ctx.notes)
    dev = {**device, "memory_peak_bytes": mem["peak_bytes_in_use"]}
    result = {"correct": bool(check.get("correct")),
              "attempted": len(records), "failed": len(bad)}
    if rehearse:
        # A CPU rehearsal proves the path, never a number: nothing it
        # read goes out under a device metric's name.
        result.update(metrics={}, rehearsal_values=values, rehearsal=True)
    else:
        result["metrics"] = values
    if tr is not None:
        dev.update(busy_s=tr.busy_s, window_s=tr.window_s)
        result["breakdown"] = tr.breakdown()
    result["device"] = dev
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else EXIT_FAILED


def _check_prompt_lengths(svc, schedule: dict) -> None:
    """Prompt lengths are counted in the service's own tokens: hold a
    sample of the schedule to the configuration's tokenizer."""
    for req in schedule["requests"][:: max(len(schedule["requests"]) // 16, 1)]:
        _, mask = svc.bundle.tokenizer.encode(req["body"]["text"], 8192)
        if int(mask.sum()) != req["prompt_tokens"]:
            raise RuntimeError(
                f"a prompt meant to be {req['prompt_tokens']} tokens encodes "
                f"to {int(mask.sum())} with the service's tokenizer")


def _engine_facts(svc) -> dict:
    """What the per-step arithmetic needs, read off the live service."""
    return {"chunk_tokens": int(svc.cfg.stream_chunk_tokens),
            "max_streams": int(svc.cfg.max_streams)}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", metavar="FILE", default=None,
                    help="tests only: a tiny override file for a CPU "
                         "rehearsal; the result says platform cpu and "
                         "carries no metric")
    ap.add_argument("--root", default=spec.REPO, help=argparse.SUPPRESS)
    a = ap.parse_args(argv)
    try:
        cell = spec.resolve(a.workload, a.root)
        peaks = spec.load_json(os.path.join(cell.bench_dir, "peaks.json"))
        if a.rehearse is not None:
            over = spec.load_json(a.rehearse)
            cell.config = _merge(cell.config, over.get("config", {}))
            cell.traffic = _merge(cell.traffic, over.get("traffic", {}))
            os.environ["JAX_PLATFORMS"] = "cpu"
    except spec.SpecError as e:
        print(f"cellbench: {e}", file=sys.stderr)
        return EXIT_USAGE
    work = os.path.join(a.root, ".cellbench_work")
    os.makedirs(work, exist_ok=True)
    import mlmicroservicetemplate_tpu  # noqa: F401  (absent -> not a checkout)

    device = device_facts(peaks, cell.chips, a.rehearse is not None)
    try:
        return asyncio.run(measure(cell, a, work, device, peaks))
    except RuntimeError as e:
        print(f"cellbench: FAILED: {e}", file=sys.stderr, flush=True)
        return EXIT_FAILED


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # The service's worker threads are the program's own and some are
    # not daemons; the result is printed and every child has been
    # waited for, so leave without waiting on them.
    os._exit(code)
