"""What the program named, read back from a traced run's own trace:
the host plane's phase names, and device time per ``jax.named_scope``
of one executable.

``TraceSummary`` (``trace.py``) keeps names, starts and durations; the
scope path of an operation is not in its name, so this helper opens
the trace file again — the newest ``trace_*`` directory under
``<root>/.cellbench_work`` — and caches the load for the process.

Where a v5e trace keeps the path (looked at by hand, PR 25): see
"the trace file" below.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import re

from . import spec, trace

# The program's scope names for the parts of a model step
# (models/llama.py) and its step kinds; an operation belongs to the
# INNERMOST of these on its path, or to "unscoped".
PARTS = ("embed", "qkv_rope", "kv_write", "attn", "attn_out", "mlp",
         "lm_head", "sample")
KINDS = ("prefill_wave", "slot_insert", "decode_chunk")
UNSCOPED = "unscoped"


def newest_xplane(root: str = spec.REPO) -> str | None:
    dirs = glob.glob(os.path.join(root, ".cellbench_work", "trace_*"))
    for d in sorted(dirs, key=os.path.getmtime, reverse=True):
        try:
            return trace.find_xplane(d)
        except FileNotFoundError:
            continue
    return None


def scope_of(path: str) -> str:
    """``jit(f)/jit(main)/decode_chunk/while/body/attn/dot`` -> ``attn``."""
    best = UNSCOPED
    for comp in path.split("/"):
        if comp in PARTS or (comp in KINDS and best not in PARTS):
            best = comp
    return best


def scope_seconds(modules: list[list], ops: list[list]) -> dict:
    """Self seconds per scope of the operations that ran inside the
    executable runs ``modules`` (``[name, start_ns, dur_ns]``);
    ``ops`` are ``[name, start_ns, dur_ns, scope]``.  Nesting is taken
    out as ``trace.self_times`` does it, so a ``while`` keeps only what
    is not its body, whatever scope the body's operations have."""
    runs = sorted((s, s + d) for _, s, d in modules)
    starts = [s for s, _ in runs]
    inside = []
    for _, s, d, scope in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s + d <= runs[i][1]:
            inside.append([scope, s, d])
    return trace.self_times(inside)


# -- the trace file ------------------------------------------------------
#
# ``jax.profiler.ProfileData`` hands out an event's own statistics, not
# those of its METADATA, and on a v5e the scope path is there: the
# 'XLA Ops' event metadata's ``tf_op`` statistic holds the operation's
# JAX name stack, ``jit(paged_chunk_fn)/decode_chunk/while/body/
# closed_call/attn/...`` (beside ``hlo_category``, ``source`` ...).  So
# the file is read as what it is, a serialised ``XSpace`` protocol
# buffer (tsl/profiler/protobuf/xplane.proto), with the few field
# numbers needed here and no dependency beyond the standard library.

PATH_STAT = "tf_op"


def _varint(buf: memoryview, i: int) -> tuple[int, int]:
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _fields(buf: memoryview):
    """(field number, wire type, value) of one protobuf message; a
    length-delimited value is a memoryview, the others are ints."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val = int.from_bytes(buf[i:i + size], "little")
            i += size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, wire, val


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_entry(buf: memoryview) -> tuple[int, memoryview]:
    key, val = 0, memoryview(b"")
    for f, _, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane(buf: memoryview) -> dict:
    """XPlane -> {"name", "lines": [{"name", "events": [[name, start_ns,
    duration_ns, path]]}]} — the structure of ``trace.load_xplane`` with
    the scope path as a fourth element."""
    name, lines, event_md, stat_names = "", [], {}, {}
    for f, _, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            k, md = _map_entry(v)
            event_md[k] = md
        elif f == 5:
            k, md = _map_entry(v)
            stat_names[k] = next(
                (_text(x) for g, _, x in _fields(md) if g == 2), "")
    path_ids = {k for k, n in stat_names.items() if n == PATH_STAT}
    named: dict[int, tuple[str, str]] = {}

    def metadata(mid: int) -> tuple[str, str]:
        if mid not in named:
            ev_name = path = ""
            for f, _, v in _fields(event_md.get(mid, memoryview(b""))):
                if f == 2:
                    ev_name = _text(v)
                elif f == 5:  # XStat: metadata_id=1, str_value=5, ref_value=7
                    st = {g: x for g, _, x in _fields(v)}
                    if st.get(1) in path_ids:
                        path = (_text(st[5]) if 5 in st
                                else stat_names.get(st.get(7), ""))
            named[mid] = (ev_name, path)
        return named[mid]

    out = []
    for ln in lines:
        ln_name, t0, events = "", 0, []
        for f, _, v in _fields(ln):
            if f == 2:
                ln_name = _text(v)
            elif f == 3:
                t0 = v
            elif f == 4:
                events.append(v)
        evs = []
        for ev in events:
            e = {f: v for f, w, v in _fields(ev) if w == 0}
            ev_name, path = metadata(e.get(1, 0))
            evs.append([ev_name, t0 + e.get(2, 0) / 1000.0,
                        e.get(3, 0) / 1000.0, path])
        if evs:
            out.append({"name": ln_name, "events": evs})
    return {"name": name, "lines": out}


@functools.lru_cache(maxsize=2)
def load_xplane(path: str) -> dict:
    """{"planes": [...]} of the host planes and the device planes'
    'XLA Modules' and 'XLA Ops' lines, every event with its path."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    planes = []
    for f, _, v in _fields(data):
        if f != 1:
            continue
        head = next((_text(x) for g, _, x in _fields(v) if g == 2), "")
        if head.startswith("/host:") or (
                head.startswith("/device:") and "CUSTOM" not in head):
            p = _plane(v)
            if head.startswith("/device:"):
                p["lines"] = [ln for ln in p["lines"]
                              if ln["name"] in ("XLA Modules", "XLA Ops")]
            if p["lines"]:
                planes.append(p)
    return {"planes": planes}


# -- what the readers ask ------------------------------------------------


def named_host_spans(planes: dict, prefixes: tuple[str, ...]) -> set[str]:
    return {e[0] for p in planes["planes"] if p["name"].startswith("/host:")
            for ln in p["lines"] for e in ln["events"]
            if e[0].startswith(prefixes)}


def table(planes: dict, module: str) -> dict:
    """{"seconds": {scope: s}, "runs", "module_seconds", "scoped"} of
    the executables whose name matches ``module``, over the devices;
    ``scoped`` says whether any of their operations carries a path."""
    rx = re.compile(module)
    seconds: dict[str, float] = {}
    runs, module_s, scoped = 0, 0.0, False
    for p in planes["planes"]:
        if not p["name"].startswith("/device:"):
            continue
        lines = {ln["name"]: ln["events"] for ln in p["lines"]}
        mods = [m[:3] for m in lines.get("XLA Modules", [])
                if rx.search(m[0].split("(", 1)[0])]
        runs += len(mods)
        module_s += sum(m[2] for m in mods) / 1e9
        ops = [[n, s, d, scope_of(path)]
               for n, s, d, path in lines.get("XLA Ops", [])]
        for k, v in scope_seconds(mods, ops).items():
            seconds[k] = seconds.get(k, 0.0) + v
            scoped = scoped or k != UNSCOPED
    return {"seconds": seconds, "runs": runs, "module_seconds": module_s,
            "scoped": scoped}


def host_names(prefixes: tuple[str, ...], root: str = spec.REPO) -> set[str]:
    """The newest trace's host span names starting with ``prefixes``."""
    path = newest_xplane(root)
    return named_host_spans(load_xplane(path), prefixes) if path else set()


def scope_table(module: str, root: str = spec.REPO) -> dict | None:
    path = newest_xplane(root)
    return table(load_xplane(path), module) if path else None
