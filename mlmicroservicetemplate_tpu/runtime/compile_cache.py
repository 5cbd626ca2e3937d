"""Process-level executable cache: compile once, serve from every replica.

Why this layer exists (ISSUE 14 / ROADMAP item 4): ``jax.jit`` caches
traces and compiled executables PER WRAPPER OBJECT.  Every
``ContinuousDecodeLoop`` and ``InferenceEngine`` used to construct its
own private wrappers (``jax.jit(bundle.generate_chunk_fn)``, the insert
scatters, the window/handoff/swap executables, …), so a second fleet
replica — identical bundle, identical shapes, identical placement —
re-traced and re-compiled every one of them from scratch.  On CPU that
warm compile measured 262 s per ``_spawn_replica`` (a pre-round CPU
record, removed in PR 22 — the honest negative that made elastic
scaling LOSE its A/B); on the chip it is not measured yet.

``ExecutableCache`` is the fix: ONE process-level table of jitted
wrappers keyed by

    (bundle fingerprint, executable kind, static descriptor, placement)

shared across the fleet exactly like the r14 host KV tier and the r15
journal.  A spawned replica's ``warm()`` then finds every wrapper
already built — its warm dispatches hit jit's C++ fast path (same
shapes, same shardings) and perform ZERO XLA compiles, which
``tests/test_compile_cache.py`` pins by counting backend compiles via
``jax.monitoring``.  Supervised restarts (``reset_device_state``) and
journal-replay re-admissions reuse the same wrappers for the same
reason.

Key discipline (the no-aliasing contract, also pinned):

- the **bundle fingerprint** is a fresh unique token minted per bundle
  OBJECT and stored on it — two distinct bundles can never collide,
  even with identical names/dims, and a fleet (which shares one bundle
  object) shares one fingerprint;
- the **kind** names the executable's code path ("gen_chunk",
  "paged_insert", …);
- the **static descriptor** carries everything the builder closes over
  besides the bundle (static argnums are implied by the kind; closure
  constants like a prefix length or block size must be spelled out);
- the **placement** is the device set the engine dispatches onto
  (engines over different meshes never share).

Layering (docs/compilation.md): jit's per-wrapper cache (shapes ×
shardings) sits below this table; the persistent XLA disk cache
(``COMPILE_CACHE_DIR``, runtime/device.py) sits below BOTH and is what
carries compiles across process restarts.

The module is thread-safe: fleet replicas warm concurrently and jitted
callables are themselves thread-safe.

It also keeps the process's ONE ``jax.monitoring`` listener: a record
per executable the backend hands back (``compiled`` or ``loaded`` from
the persistent cache, with its trace / lower / backend seconds), which
``CompileWindow``, ``/status.compile`` and ``xla_executables_total``
read, and the export of the boot timeline (``utils/tracing.boot_phase``)
at readiness.
"""

from __future__ import annotations

import collections
import itertools
import logging
import os
import threading
import time
import weakref
from collections import OrderedDict
from typing import Any, Callable

from ..utils import metrics, tracing

_LOCK = threading.RLock()
_CACHE: OrderedDict[tuple, Any] = OrderedDict()
_COUNTS = {"hit": 0, "miss": 0, "insert": 0}
#: Soft entry cap — an LRU bound, not a correctness surface (an evicted
#: wrapper simply recompiles on next use).  Generous: a real deployment
#: has a few dozen kinds × one bundle.
MAX_ENTRIES = 1024

_fp_counter = itertools.count()

# -- XLA executable accounting (jax.monitoring) -------------------------
#
# One record per executable the backend hands back, from JAX's own
# events.  ``backend_compile_duration`` wraps
# ``compiler.compile_or_get_cached``: it fires for a persistent-cache
# HIT as for a real compile, so the hit is told apart by the
# ``cache_hits`` event JAX announces on the same thread just before it
# (pinned by tests/test_boot_timeline.py on the installed JAX).
_EVENT_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_EVENT_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_EVENT_BACKEND = "/jax/core/compile/backend_compile_duration"
_EVENT_HIT = "/jax/compilation_cache/cache_hits"
_EVENT_WRITE = "/jax/compilation_cache/cache_misses"
_EVENT_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_EVENT_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
OUTCOMES = ("compiled", "loaded")
WHENS = ("boot", "serving")  # before readiness, after
STAGES = ("trace", "lower", "backend")
#: Records kept one by one, then names kept with their totals; past
#: both an executable still counts in the totals by outcome.
MAX_RECORDS = 512
MAX_NAMES = 1024
COSTLIEST = 20

log = logging.getLogger(__name__)


def _zero() -> dict:
    return {"count": 0, "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
            "retrieval_s": 0.0}


def _tally() -> dict:
    return {"compiled": 0, "loaded": 0, "seconds": 0.0}


class _Executables:
    """The process's executable records: a bounded list in order, totals
    by name, totals by (when, outcome).  ``when`` is ``boot`` until the
    boot table closes at readiness, ``serving`` after."""

    def __init__(self):
        self.lock = threading.Lock()
        self.local = threading.local()  # a thread's stages so far
        self.seq = 0
        self.records: collections.deque = collections.deque(maxlen=MAX_RECORDS)
        self.by_name: dict[str, dict] = {}
        self.by_phase: dict[str, dict] = {}  # keyed by boot phase: few
        self.totals = {(w, o): _zero() for w in WHENS for o in OUTCOMES}
        self.cache_writes = 0
        self.saved_s = 0.0
        self.flights: weakref.WeakSet = weakref.WeakSet()


_EXE = _Executables()
_MON_LOCK = threading.Lock()
_MON_INSTALLED = False


def _on_event(name: str, **kw) -> None:
    if name == _EVENT_HIT:
        _EXE.local.hit = True
    elif name == _EVENT_WRITE:
        with _EXE.lock:
            _EXE.cache_writes += 1


def _on_duration(name: str, dur: float, **kw) -> None:
    tl = _EXE.local
    if name == _EVENT_TRACE:
        tl.trace = (kw.get("fun_name"), float(dur))
    elif name == _EVENT_LOWER:
        tl.lower = (kw.get("fun_name"), float(dur))
    elif name == _EVENT_RETRIEVAL:
        tl.retrieval = float(dur)
    elif name == _EVENT_SAVED:
        with _EXE.lock:
            _EXE.saved_s += float(dur)
    elif name == _EVENT_BACKEND:
        _record(str(kw.get("fun_name", "?")), float(dur))


def _stage(slot: tuple | None, name: str) -> float:
    """A thread's last trace / lower time if it was this executable's
    (tracing names the function, lowering and the backend its module:
    ``paged_chunk_fn`` -> ``jit(paged_chunk_fn)``)."""
    if slot is None or slot[0] is None:
        return 0.0
    return slot[1] if name == slot[0] or name.endswith(f"({slot[0]})") else 0.0


def _record(name: str, backend_s: float) -> None:
    """The backend handed an executable back: close its record."""
    tl = _EXE.local
    rec = {
        "name": name,
        "outcome": "loaded" if getattr(tl, "hit", False) else "compiled",
        "trace_s": _stage(getattr(tl, "trace", None), name),
        "lower_s": _stage(getattr(tl, "lower", None), name),
        "backend_s": backend_s,
        "retrieval_s": getattr(tl, "retrieval", 0.0),
        "thread": threading.current_thread().name,
        "phase": tracing.boot_current(),
        "after_ready": tracing.boot_table().closed,
    }
    tl.hit, tl.retrieval, tl.trace, tl.lower = False, 0.0, None, None
    spent = rec["trace_s"] + rec["lower_s"] + backend_s
    rec["start"] = time.monotonic() - spent
    when = "serving" if rec["after_ready"] else "boot"
    with _EXE.lock:
        _EXE.seq += 1
        rec["seq"] = _EXE.seq
        _EXE.records.append(rec)
        tallies = [_EXE.by_phase.setdefault(rec["phase"] or when, _tally())]
        if name in _EXE.by_name or len(_EXE.by_name) < MAX_NAMES:
            tallies.append(_EXE.by_name.setdefault(name, _tally()))
        for tot in tallies:
            tot[rec["outcome"]] += 1
            tot["seconds"] += spent
        tot = _EXE.totals[when, rec["outcome"]]
        tot["count"] += 1
        for key in ("trace_s", "lower_s", "backend_s", "retrieval_s"):
            tot[key] += rec[key]
        flights = list(_EXE.flights) if rec["after_ready"] else ()
    metrics.XLA_EXECUTABLES.labels(rec["outcome"], when).inc()
    for stage in STAGES:
        metrics.XLA_EXECUTABLE_SECONDS.labels(
            rec["outcome"], stage, when).inc(rec[stage + "_s"])
    if not rec["after_ready"]:
        return
    # After readiness an operator has to see WHICH step recompiled
    # without DEBUG logs: a flight event, one line, a ring span (the
    # enclosing dispatch:<site> phase names the device's idle gap).
    log.warning("XLA %s %s after readiness: %.3f s (trace %.3f, lower %.3f, "
                "backend %.3f) on %s", rec["outcome"], name, spent,
                rec["trace_s"], rec["lower_s"], backend_s, rec["thread"])
    for flight in flights:
        flight.event("compile", name=name, outcome=rec["outcome"],
                     seconds=round(spent, 4))
    tr = tracing.tracer()
    if tr is not None:
        tr.add(f"compile:{name}", cat="compile", t0=rec["start"], dur=spent,
               outcome=rec["outcome"])


def _install_monitor() -> None:
    """Register the process's ONE pair of jax.monitoring listeners (the
    staged durations that carry ``fun_name`` and the persistent
    cache's events).  Idempotent; a listener cannot be told apart from
    another once registered, so it accumulates for the process
    lifetime and consumers read deltas (``CompileWindow``)."""
    global _MON_INSTALLED
    with _MON_LOCK:
        if _MON_INSTALLED:
            return
        import jax

        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        # Every child exists from here on: a warm boot reads 0 compiled,
        # not an absent sample.
        for when in WHENS:
            for outcome in OUTCOMES:
                metrics.XLA_EXECUTABLES.labels(outcome, when)
                for stage in STAGES:
                    metrics.XLA_EXECUTABLE_SECONDS.labels(outcome, stage, when)
        _MON_INSTALLED = True


def report_to(flight: Any) -> None:
    """Send every executable compiled or loaded after readiness to this
    flight recorder too (held weakly: an engine's recorder goes with it)."""
    with _EXE.lock:
        _EXE.flights.add(flight)


def compile_counters() -> dict:
    """Process-lifetime totals ``{count, seconds, compiled, loaded,
    seq}`` of executables the backend handed back (zeros until the
    first shared executable installs the monitor).  ``count`` and
    ``seconds`` take a load from the persistent cache and a real
    compile alike, as the ``backend_compile_duration`` event does;
    ``compiled`` / ``loaded`` split them."""
    with _EXE.lock:
        by = {o: sum(t["count"] for (_, oo), t in _EXE.totals.items()
                     if oo == o) for o in OUTCOMES}
        return {
            "count": by["compiled"] + by["loaded"],
            "seconds": sum(t["backend_s"] for t in _EXE.totals.values()),
            "seq": _EXE.seq, **by,
        }


class CompileWindow:
    """Delta view over the compile counters::

        with CompileWindow() as w:
            replica.cdl.warm()
        assert w.compiles == 0          # the zero-compile spawn pin
        breakdown["compile_s"] = w.seconds

    ``compiles`` / ``seconds`` count every executable the backend
    handed back (loaded from the persistent cache or compiled);
    ``compiled`` / ``loaded`` split the count and ``names`` lists them
    in order (the last ``MAX_RECORDS`` of a long window)."""

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.compiled = 0
        self.loaded = 0
        self.names: list[str] = []
        self._base: dict | None = None

    def __enter__(self) -> "CompileWindow":
        _install_monitor()
        self._base = compile_counters()
        return self

    def __exit__(self, *exc) -> None:
        now = compile_counters()
        self.compiles = now["count"] - self._base["count"]
        self.seconds = now["seconds"] - self._base["seconds"]
        self.compiled = now["compiled"] - self._base["compiled"]
        self.loaded = now["loaded"] - self._base["loaded"]
        with _EXE.lock:
            self.names = [r["name"] for r in _EXE.records
                          if r["seq"] > self._base["seq"]]


def executables_status() -> dict:
    """/status.compile.executables: totals by when, outcome and stage,
    the costliest names, every name compiled (not loaded) so far, and
    the work by the boot phase open on the compiling thread (outside a
    boot phase: ``boot`` or ``serving``)."""
    with _EXE.lock:
        totals = {
            when: {o: {k: (v if k == "count" else round(v, 4))
                       for k, v in _EXE.totals[when, o].items()}
                   for o in OUTCOMES}
            for when in WHENS}
        names = sorted(_EXE.by_name.items(), key=lambda kv: -kv[1]["seconds"])
        return {
            "totals": totals,
            "costliest": [dict(name=n, **{**t, "seconds": round(t["seconds"], 4)})
                          for n, t in names[:COSTLIEST]],
            "compiled": {n: t["compiled"] for n, t in names if t["compiled"]},
            "by_phase": {ph: {**t, "seconds": round(t["seconds"], 4)}
                         for ph, t in _EXE.by_phase.items()},
            "cache_writes": _EXE.cache_writes,
            "compile_time_saved_s": round(_EXE.saved_s, 4),
            "records_kept": len(_EXE.records),
        }


def executable_records() -> list[dict]:
    """The records kept, oldest first (tests, tools)."""
    with _EXE.lock:
        return [dict(r) for r in _EXE.records]


def bundle_fingerprint(bundle: Any) -> str:
    """The bundle's cache identity: a unique token minted on first use
    and stored on the bundle object.  Distinct bundle objects ALWAYS
    get distinct tokens (no aliasing, ever — not even after one is
    garbage-collected); everything sharing the object (a whole fleet)
    shares the token."""
    fp = getattr(bundle, "_exec_fingerprint", None)
    if fp is None:
        with _LOCK:
            fp = getattr(bundle, "_exec_fingerprint", None)
            if fp is None:
                fp = (
                    f"{getattr(bundle, 'name', '?')}"
                    f"#{next(_fp_counter)}"
                )
                try:
                    bundle._exec_fingerprint = fp
                except Exception:
                    # Unwritable bundle (slots/frozen): fall back to the
                    # object id with the bundle PINNED by the cache
                    # entry, so the id can never be recycled while a
                    # wrapper is live under it.
                    fp = f"id:{id(bundle)}"
    return fp


def placement_key(replicas: Any) -> tuple:
    """Hashable descriptor of the device set an engine dispatches onto
    PLUS its sharding layout.  Engines sharing one ReplicaSet (every
    fleet replica today) get the same key; distinct meshes/device sets
    never share — and neither do distinct LAYOUTS over the same
    devices: a TP=2 ``('replica','tp')`` mesh and a REPLICAS=2 DP mesh
    cover the same two chips but compile different SPMD programs, so
    the key carries a mesh-topology + PartitionSpec fingerprint
    (parallel/tpserve.py).  Single-device placements fingerprint to ""
    — every pre-TP key stays byte-identical."""
    mesh = getattr(replicas, "mesh", None)
    devs = getattr(mesh, "devices", None)
    if devs is not None:
        try:
            from ..parallel.tpserve import placement_fingerprint

            return (placement_fingerprint(replicas),) + tuple(
                str(d) for d in devs.flat
            )
        except Exception:
            pass
    return ("replicas", id(replicas))


class _GroupPinned:
    """Call-transparent proxy for an executable built for a non-prefix
    device group (a multi-chip fleet replica): it re-enters its
    group's thread-local around every call and lower, so a model-fn
    ``shard_map`` traced from ANY thread (continuous loop, watchdog
    daemon, warmers) reconstructs ``serving_tp_mesh`` over the
    replica's own devices — parallel/tpserve.py.  A single-group
    deployment never sees one: the cache hands out the jitted
    function itself."""

    __slots__ = ("_fn", "_group")

    def __init__(self, fn: Any, group: tuple):
        self._fn = fn
        self._group = group

    def __call__(self, *args, **kwargs):
        from ..parallel.tpserve import use_trace_group

        with use_trace_group(self._group):
            return self._fn(*args, **kwargs)

    def lower(self, *args, **kwargs):
        from ..parallel.tpserve import use_trace_group

        with use_trace_group(self._group):
            return self._fn.lower(*args, **kwargs)

    def __getattr__(self, name: str):
        return getattr(self._fn, name)


def shared_executable(kind: str, bundle: Any, replicas: Any,
                      build: Callable[[], Any], statics: tuple = ()) -> Any:
    """The one lookup every jit-wrapper construction site routes
    through: return the cached wrapper for this (bundle, kind, statics,
    placement) or build-and-insert it.  ``build`` must construct the
    wrapper from state fully described by the key (the bundle's fns +
    the spelled-out statics) — that is the no-aliasing contract."""
    key = (
        bundle_fingerprint(bundle), kind, tuple(statics),
        placement_key(replicas),
    )
    with _LOCK:
        fn = _CACHE.get(key)
        if fn is not None:
            _CACHE.move_to_end(key)
            _COUNTS["hit"] += 1
            metrics.EXEC_CACHE_EVENTS.labels("hit").inc()
            return fn
        _COUNTS["miss"] += 1
    metrics.EXEC_CACHE_EVENTS.labels("miss").inc()
    _install_monitor()  # first build turns on compile accounting
    try:
        from ..parallel.tpserve import device_group, use_trace_group

        grp = device_group(replicas)
    except Exception:
        grp = None
    if grp is not None:
        # Build (and later call/lower) under the placement's device
        # group so any eager trace lands on the right mesh.
        with use_trace_group(grp):
            fn = _GroupPinned(build(), grp)
    else:
        fn = build()
    with _LOCK:
        # A racing builder may have inserted meanwhile: last wins is
        # fine (both wrappers are correct; one just goes unshared), but
        # prefer the first so concurrent warmers converge on one.
        existing = _CACHE.get(key)
        if existing is not None:
            return existing
        _CACHE[key] = fn
        # The id:-fingerprint fallback pins the bundle (see
        # bundle_fingerprint); normal tokens don't need it.
        _COUNTS["insert"] += 1
        while len(_CACHE) > MAX_ENTRIES:
            _CACHE.popitem(last=False)
    metrics.EXEC_CACHE_EVENTS.labels("insert").inc()
    return fn


def cache_stats() -> dict:
    """{entries, hit, miss, insert} — /status.compile."""
    with _LOCK:
        return {"entries": len(_CACHE), **_COUNTS}


def cache_kinds() -> dict:
    """Entry count per ``kind`` — lets /status and the autotuner tests
    see e.g. how many ``paged_decode_kernel`` variants are installed
    without exposing the raw keys (which embed bundle fingerprints)."""
    with _LOCK:
        out: dict = {}
        for key in _CACHE:
            out[key[1]] = out.get(key[1], 0) + 1
        return out


def clear() -> None:
    """Test hook: drop every cached wrapper and zero the event counts
    (compile totals are process-lifetime and stay)."""
    with _LOCK:
        _CACHE.clear()
        for k in _COUNTS:
            _COUNTS[k] = 0


def note_warm_phase(model: str, phase: str, seconds: float) -> None:
    """One warm phase's wall seconds into ``engine_warm_seconds{phase}``:
    the boot's ``boot/warm/<phase>`` timings and the fleet's spawn
    breakdown (``spawn_build`` / ``spawn_warm`` / ``spawn_probe``)."""
    metrics.WARM_SECONDS.labels(model, phase).observe(seconds)


# -- the persistent cache's directory, and the boot's export ------------
_PERSISTENT: dict = {}


def _scan(path: str) -> dict:
    """``{bytes, entries}`` of a cache directory: one ``os.scandir`` pass."""
    size = entries = 0
    try:
        with os.scandir(path) as it:
            for e in it:
                if e.is_file(follow_symlinks=False):
                    entries += 1
                    size += e.stat(follow_symlinks=False).st_size
    except OSError:
        return {"bytes": None, "entries": None}
    return {"bytes": size, "entries": entries}


def note_persistent_cache(path: str | None) -> None:
    """``apply_device_env`` chose this directory (None = off): remember
    it with JAX's size limit for it and what it holds now.  With the
    reading at readiness a boot says whether the cache stood at its
    cap and whether this boot pushed entries out."""
    _install_monitor()  # a boot's first compile is already a record
    _PERSISTENT.clear()
    _PERSISTENT["dir"] = path
    if path is None:
        return
    import jax

    _PERSISTENT["max_size_bytes"] = int(jax.config.jax_compilation_cache_max_size)
    _PERSISTENT["at_device"] = _scan(path)


def mark_ready(model: str) -> dict:
    """Readiness: close the boot table, read the cache directory again
    and set ``boot_phase_seconds{model, phase}`` from the table's
    top-level phases (by the segment after ``boot/``, so the three
    ``boot/warm/*`` phases are one child), ``unnamed``, ``total`` and
    ``pre_build``.  Returns the table's snapshot."""
    table = tracing.boot_table()
    table.ready()
    if _PERSISTENT.get("dir"):
        _PERSISTENT["at_ready"] = _scan(_PERSISTENT["dir"])
    snap = table.snapshot()
    groups: dict[str, float] = {}
    for name, seconds in snap["phases"].items():
        parts = name.split("/")
        key = parts[1] if parts[0] == "boot" and len(parts) > 1 else name
        groups[key] = groups.get(key, 0.0) + seconds
    groups.pop("ready", None)
    groups["unnamed"] = snap["unnamed_s"]
    groups["total"] = snap["total_s"]
    if "pre_build_s" in snap:
        groups["pre_build"] = snap["pre_build_s"]
    for key, seconds in groups.items():
        metrics.BOOT_PHASE_SECONDS.labels(model, key).set(seconds)
    return snap


def boot_status() -> dict:
    """/status.compile's ``boot``, ``executables``, ``persistent_cache``
    and ``warm_phases_s`` (the wall seconds of the boot's
    ``boot/warm/<phase>`` rows by ``<phase>``)."""
    boot = tracing.boot_table().snapshot()
    warm = {name[len("boot/warm/"):]: s for name, s in boot["phases"].items()
            if name.startswith("boot/warm/")}
    return {
        "warm_phases_s": dict(sorted(warm.items())),
        "boot": boot,
        "executables": executables_status(),
        "persistent_cache": dict(_PERSISTENT),
    }
