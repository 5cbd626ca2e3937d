"""Request-level span tracing + the engine flight recorder.

Two instruments, both import-safe (mirroring the ``metrics.py`` stub
pattern — no OpenTelemetry or any other hard dependency beyond JAX):

- **Phases** (``phase(name, ...)``): the ONE call every serving seam
  goes through — admission/classify, the streaming loop's host phases
  (``loop/queue_pop``, ``loop/wave_dispatch``, ``loop/wave_fetch``,
  ``loop/insert``, ``loop/chunk_dispatch``, ``loop/stage_prep``,
  ``loop/deliver``), each prefill window and every ``dispatch_guard``
  site (``dispatch:<site>``).  A phase writes into two sinks:

  * always a ``jax.profiler.TraceAnnotation(name)``: it records only
    while a profiler session runs (``POST /debug/profile``, a
    benchmark's traced run) and lands on the xplane's host plane, on
    the device trace's own clock — so a device idle gap reads
    ``loop/insert``, not ``PjitFunction(insert)``.  With no session an
    enter + exit costs about a microsecond.  The annotation's name is
    the bare phase name; arguments go to the ring only.
  * with ``TRACE=1`` also a ``Span`` (request id, parent, arguments)
    in a bounded ring (``TRACE_RING``), exported as Chrome trace-event
    JSON from ``GET /debug/trace`` (loadable in Perfetto or
    ``chrome://tracing``).  When off, the module-level tracer is
    ``None`` and no ``Span`` is ever constructed (pinned by test).

  Neither sink synchronises with the device: ``TRACE=1`` observes the
  chunk pipeline without serialising it.  Device time per program part
  comes from the profiler's device trace (``jax.named_scope`` names in
  ``models/llama.py`` and on the loop's step kinds), not from here.

- **Flight recorder** (``FLIGHT_RING``, default on): a bounded ring of
  the engine loop's last N iterations (batch composition, slot
  occupancy, KV pool state) plus scheduling/fault events (admission
  sheds, pacer holds, preemptions, dispatch retries/timeouts, engine
  restarts).  It dumps automatically on fatal faults — the supervisor
  snapshots the ring the moment it grants (or refuses) a restart, so
  the post-mortem shows the iterations that LED to the fault — and on
  demand via ``GET /debug/engine``.

Timestamps use ``time.monotonic()`` throughout (the same base the
scheduler stamps ``t_in`` with), anchored to wall-clock once at
configure time so trace events correlate with log lines.
"""

from __future__ import annotations

import collections
import functools
import json
import logging
import threading
import time

import jax
from jax.profiler import TraceAnnotation

log = logging.getLogger(__name__)

_now = time.monotonic


# ---------------------------------------------------------------------------
# span tracer


class Span:
    """One timed interval.  Use as a context manager (records itself on
    exit) or via ``Tracer.add`` for after-the-fact intervals (queue
    wait, whose start predates the pop that observes it)."""

    __slots__ = (
        "name", "cat", "rid", "t0", "dur", "tid", "sid", "parent", "args",
        "_tracer",
    )

    def __init__(self, tracer, name: str, cat: str, rid: str, args: dict):
        self.name = name
        self.cat = cat
        self.rid = rid
        self.args = args
        self.t0 = _now()
        self.dur = 0.0
        self.tid = threading.get_ident()
        self.sid = tracer._next_sid()
        self.parent = 0
        self._tracer = tracer

    def set(self, **kw) -> "Span":
        self.args.update(kw)
        return self

    def __enter__(self) -> "Span":
        stack = self._tracer._stack()
        if stack:
            self.parent = stack[-1].sid
        stack.append(self)
        return self

    def __exit__(self, etype, exc, tb):
        self.dur = _now() - self.t0
        stack = self._tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        if etype is not None:
            self.args.setdefault("error", f"{etype.__name__}: {exc}")
        self._tracer._record(self)
        return False


class Tracer:
    """Bounded ring of completed spans.  Thread-safe appends; parenting
    is per-thread (a span opened inside another on the same thread gets
    its ``parent`` sid), cross-thread correlation rides the request id."""

    def __init__(self, ring: int = 4096):
        self.ring = max(16, int(ring))
        self._spans: collections.deque = collections.deque(maxlen=self.ring)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._sid = 0
        self.spans_created = 0
        self.t_anchor = _now()
        self.wall_anchor = time.time()

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _next_sid(self) -> int:
        with self._lock:
            self._sid += 1
            self.spans_created += 1
            return self._sid

    def _record(self, sp: Span) -> None:
        with self._lock:
            self._spans.append(sp)

    # -- producer API ---------------------------------------------------

    def span(self, name: str, cat: str = "app", rid: str = "", **args) -> Span:
        return Span(self, name, cat, rid, args)

    def add(self, name: str, cat: str = "app", rid: str = "",
            t0: float | None = None, dur: float | None = None,
            **args) -> None:
        """Record a completed interval: ``[t0, t0+dur]`` (dur defaults
        to now−t0).  No parenting — these are after-the-fact spans."""
        sp = Span(self, name, cat, rid, args)
        if t0 is not None:
            sp.t0 = t0
        sp.dur = dur if dur is not None else max(0.0, _now() - sp.t0)
        self._record(sp)

    def instant(self, name: str, cat: str = "app", rid: str = "",
                **args) -> None:
        """Zero-duration marker event."""
        self.add(name, cat, rid, dur=0.0, **args)

    # -- consumer API ---------------------------------------------------

    def snapshot(self, last: int | None = None) -> list[Span]:
        with self._lock:
            spans = list(self._spans)
        return spans[-last:] if last else spans

    def chrome_trace(self, last: int | None = None) -> dict:
        """Chrome trace-event JSON (Perfetto / chrome://tracing).  Spans
        become ``ph:"X"`` complete events; zero-duration spans become
        ``ph:"i"`` instants.  ``ts`` is µs since the tracer anchor."""
        spans = self.snapshot(last)
        tids: dict[int, int] = {}
        events: list[dict] = []
        for sp in spans:
            tid = tids.setdefault(sp.tid, len(tids) + 1)
            args = dict(sp.args)
            if sp.rid:
                args["request_id"] = sp.rid
            if sp.parent:
                args["parent_sid"] = sp.parent
            args["sid"] = sp.sid
            ev = {
                "name": sp.name,
                "cat": sp.cat,
                "pid": 1,
                "tid": tid,
                "ts": round((sp.t0 - self.t_anchor) * 1e6, 3),
                "args": args,
            }
            if sp.dur > 0.0:
                ev["ph"] = "X"
                ev["dur"] = round(sp.dur * 1e6, 3)
            else:
                ev["ph"] = "i"
                ev["s"] = "t"
            events.append(ev)
        meta = [
            {"name": "process_name", "ph": "M", "pid": 1,
             "args": {"name": "mlmicroservicetemplate-tpu"}},
        ]
        for raw, tid in tids.items():
            meta.append({
                "name": "thread_name", "ph": "M", "pid": 1, "tid": tid,
                "args": {"name": f"thread-{raw}"},
            })
        return {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": {
                "wall_anchor": self.wall_anchor,
                "spans_created": self.spans_created,
                "ring": self.ring,
            },
        }


_TRACER: Tracer | None = None


def tracer() -> Tracer | None:
    """The process tracer, or None when TRACE=0 (the zero-overhead
    check every hot path makes first)."""
    return _TRACER


def configure(enabled: bool, ring: int = 4096) -> Tracer | None:
    """Install (or remove) the process tracer.  Serving calls this at
    startup from the TRACE/TRACE_RING knobs; tests call it directly.
    Enabling replaces any existing tracer (fresh ring)."""
    global _TRACER
    _TRACER = Tracer(ring) if enabled else None
    return _TRACER


class _Phase:
    """One ``phase()``: the profiler annotation plus, under TRACE=1,
    the ring ``Span`` of the same name."""

    __slots__ = ("_ann", "_span")

    def __init__(self, name: str, span: Span | None):
        self._ann = TraceAnnotation(name)
        self._span = span

    def set(self, **kw) -> "_Phase":
        if self._span is not None:
            self._span.args.update(kw)
        return self

    def __enter__(self) -> "_Phase":
        self._ann.__enter__()
        if self._span is not None:
            self._span.__enter__()
        return self

    def __exit__(self, etype, exc, tb):
        if self._span is not None:
            self._span.__exit__(etype, exc, tb)
        self._ann.__exit__(etype, exc, tb)
        return False


def phase(name: str, cat: str = "app", rid: str = "", **args) -> _Phase:
    """A context manager naming what the program does from here to its
    exit, in the profiler's trace (always; recorded only while a
    profiler session runs) and in the TRACE=1 ring (same name, plus
    ``rid`` and ``args``).  Phases on one thread are FLAT siblings
    wherever a device idle gap should be attributable: never wrap a
    whole loop iteration.  NOTE: kwargs are evaluated by the caller
    either way — a hot path with expensive args checks ``tracer()``
    and adds them with ``.set()``."""
    tr = _TRACER
    return _Phase(name, None if tr is None else tr.span(name, cat, rid, **args))


def scoped(name: str, fn):
    """``fn`` traced under ``jax.named_scope(name)`` — one scope per
    step kind (``prefill_wave``, ``slot_insert``, ``decode_chunk``), so
    the device trace's operations carry it in their path.  Trace-time
    only: metadata on the compiled operations, no run-time cost.  The
    wrapper keeps ``fn``'s name, which is the executable's name
    (``jit_<fn>``) in logs and traces."""

    @functools.wraps(fn)
    def inner(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)

    return inner


# ---------------------------------------------------------------------------
# flight recorder


class FlightRecorder:
    """Bounded ring of engine-loop iteration snapshots + discrete
    events, dumped on fatal faults and served at ``GET /debug/engine``.

    ``size=0`` disables recording (``record_iteration``/``event``
    return immediately); ``dump`` still works (empty rings)."""

    def __init__(self, size: int = 256):
        self.size = max(0, int(size))
        cap = self.size or 1
        self._iters: collections.deque = collections.deque(maxlen=cap)
        self._events: collections.deque = collections.deque(maxlen=cap)
        self._lock = threading.Lock()
        self.last_dump: dict | None = None
        self.dumps = 0

    def record_iteration(self, **fields) -> None:
        if not self.size:
            return
        fields["t"] = round(_now(), 4)
        with self._lock:
            self._iters.append(fields)

    def event(self, kind: str, **fields) -> None:
        if not self.size:
            return
        fields["event"] = kind
        fields["t"] = round(_now(), 4)
        with self._lock:
            self._events.append(fields)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "size": self.size,
                "iterations": list(self._iters),
                "events": list(self._events),
                "dumps": self.dumps,
                "last_dump": self.last_dump,
            }

    def dump(self, reason: str) -> dict:
        """Snapshot the rings into ``last_dump`` and log it as ONE
        structured JSON line — the post-mortem a fatal fault leaves
        behind even if nobody ever curls /debug/engine."""
        with self._lock:
            snap = {
                "reason": reason,
                "t": round(_now(), 4),
                "wall": time.time(),
                "iterations": list(self._iters),
                "events": list(self._events),
            }
            self.last_dump = snap
            self.dumps += 1
        try:
            log.error(
                "engine flight recorder dump: %s",
                json.dumps(snap, default=str),
            )
        except Exception:  # a dump must never raise into recovery
            log.exception("flight recorder dump serialization failed")
        return snap


# ---------------------------------------------------------------------------
# structured JSON logs


class JsonLogFormatter(logging.Formatter):
    """One JSON object per log line (``LOG_FORMAT=json``): timestamp,
    level, logger, message, and — when the record carries one (via
    ``extra={"request_id": ...}``) — the request id, so log lines
    join against spans and the HTTP error bodies on the same key."""

    def format(self, record: logging.LogRecord) -> str:
        out = {
            "ts": round(record.created, 4),
            "level": record.levelname,
            "logger": record.name,
            "message": record.getMessage(),
        }
        rid = getattr(record, "request_id", None)
        if rid:
            out["request_id"] = rid
        if record.exc_info:
            out["exc"] = self.formatException(record.exc_info)
        return json.dumps(out, default=str)
