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

This module is import-light (no jax at import time) and thread-safe:
fleet replicas warm concurrently and jitted callables are themselves
thread-safe.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import OrderedDict
from typing import Any, Callable

from ..utils import metrics

_LOCK = threading.RLock()
_CACHE: OrderedDict[tuple, Any] = OrderedDict()
_COUNTS = {"hit": 0, "miss": 0, "insert": 0}
#: Soft entry cap — an LRU bound, not a correctness surface (an evicted
#: wrapper simply recompiles on next use).  Generous: a real deployment
#: has a few dozen kinds × one bundle.
MAX_ENTRIES = 1024

_fp_counter = itertools.count()

# -- warm-phase accounting (engine_warm_seconds{phase}) ----------------
_WARM_LOCK = threading.Lock()
_WARM_PHASES: dict[str, float] = {}

# -- XLA compile accounting (jax.monitoring) ---------------------------
_MON_LOCK = threading.Lock()
_MON_INSTALLED = False
_COMPILES = {"count": 0, "seconds": 0.0}
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _install_monitor() -> None:
    """Register ONE process-wide jax.monitoring listener that counts
    backend (XLA) compiles and their wall seconds.  Idempotent; the
    listener cannot be unregistered, so it accumulates for the process
    lifetime and consumers read deltas (``CompileWindow``)."""
    global _MON_INSTALLED
    with _MON_LOCK:
        if _MON_INSTALLED:
            return
        import jax

        def on_duration(name: str, dur: float, **kw) -> None:
            if name != _BACKEND_COMPILE_EVENT:
                return
            with _MON_LOCK:
                _COMPILES["count"] += 1
                _COMPILES["seconds"] += float(dur)

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        _MON_INSTALLED = True


def compile_counters() -> dict:
    """Process-lifetime XLA compile totals ``{count, seconds}`` (zeros
    until the first shared executable installs the monitor)."""
    with _MON_LOCK:
        return dict(_COMPILES)


class CompileWindow:
    """Delta view over the compile counters::

        with CompileWindow() as w:
            replica.cdl.warm()
        assert w.compiles == 0          # the zero-compile spawn pin
        breakdown["compile_s"] = w.seconds
    """

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self._base: dict | None = None

    def __enter__(self) -> "CompileWindow":
        _install_monitor()
        self._base = compile_counters()
        return self

    def __exit__(self, *exc) -> None:
        now = compile_counters()
        self.compiles = now["count"] - self._base["count"]
        self.seconds = now["seconds"] - self._base["seconds"]


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
    """Record one warm phase's wall seconds: feeds
    ``engine_warm_seconds{phase}`` and the process totals
    ``/status.compile`` reports."""
    metrics.WARM_SECONDS.labels(model, phase).observe(seconds)
    with _WARM_LOCK:
        _WARM_PHASES[phase] = _WARM_PHASES.get(phase, 0.0) + seconds


class warm_phase:
    """``with warm_phase(model, "loop"): cdl.warm()`` timing helper."""

    def __init__(self, model: str, phase: str):
        self.model = model
        self.phase = phase
        self.seconds = 0.0

    def __enter__(self) -> "warm_phase":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        note_warm_phase(self.model, self.phase, self.seconds)


def warm_stats() -> dict:
    """Accumulated per-phase warm seconds for /status.compile."""
    with _WARM_LOCK:
        return {k: round(v, 4) for k, v in sorted(_WARM_PHASES.items())}
