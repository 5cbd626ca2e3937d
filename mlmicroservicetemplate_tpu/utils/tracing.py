"""Request-level span tracing + the engine flight recorder.

Two instruments, both import-safe (mirroring the ``metrics.py`` stub
pattern — no OpenTelemetry or any other hard dependency beyond JAX):

- **Phases** (``phase(name, ...)``): the ONE call every serving seam
  goes through — admission/classify, the API's synchronous sections
  (``api/parse``, ``api/tokenize``, ``api/submit``), the streaming
  loop's host phases, each prefill window and every ``dispatch_guard``
  site (``dispatch:<site>``).  The loop's phases, FLAT siblings that
  between them cover every stretch of an iteration:

  * its work: ``loop/queue_pop`` (the non-blocking pops),
    ``loop/wave_dispatch``, ``loop/wave_complete``, ``loop/wave_fetch``,
    ``loop/insert``, ``loop/chunk_prep`` (a paged chunk's host half),
    ``loop/chunk_dispatch``, ``loop/prefill_advance``
    (around each ``prefill_window``), ``loop/swap_advance``,
    ``loop/stage_prep``, ``loop/deliver``, ``loop/housekeeping``
    (expiry, tier drains, gauges, the flight recorder's frame, the
    readiness probe), ``loop/preempt``, ``loop/recover``;
  * its waits, each named by its cause: ``loop/idle`` (nothing live, the
    queue empty, no request announced: no request anywhere in the
    server — the clients' time), ``loop/await_api`` (the API has read a
    request that has not reached the queue — the program's),
    ``loop/await_burst`` (an idle wave's quiet gap: the clients' next
    write), ``loop/poll`` (waiters exist and none fits the KV budget).

  A phase writes into three sinks:

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
  * on a decode loop's thread, always, that loop's **table**
    (``LoopTable``, the boot table's sibling for serving): seconds,
    count and longest instance by phase name, ``wall_s`` = the top-level
    phases + ``unnamed_s`` (a ``dispatch:<site>`` inside a phase is
    counted apart, under ``inside``), and a ring of the last 4096
    iterations' summaries that answers "the slowest" at read time —
    so an untraced run that stalls says where its seconds went
    (``/status.decode.loop_time``, ``loop_phase_seconds_total``,
    ``loop_unnamed_seconds_total``).  Two clock reads and a locked
    dictionary update a phase exit; other threads pay nothing.
    ``utils/pauses.py`` reads the process's own pauses beside it
    (event-loop lag, collector pauses, the loop thread's run delay).

  No sink synchronises with the device: ``TRACE=1`` observes the
  chunk pipeline without serialising it.  Device time per program part
  comes from the profiler's device trace (``jax.named_scope`` names in
  ``models/llama.py`` and on the loop's step kinds), not from here.

- **Boot timeline** (``boot_phase(name, ...)``): ``phase()`` plus an
  always-on row (name, start, seconds, thread, parent) in a small
  table that closes at readiness — a boot runs with ``TRACE=0`` and no
  profiler session, so the ring and the xplane are both empty there.
  ``/status.compile.boot`` and ``boot_phase_seconds{phase}`` read it
  (runtime/compile_cache.py exports it; docs/compilation.md).

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
import os
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
    the ring ``Span`` of the same name, plus, on a decode loop's thread,
    a row of that loop's table."""

    __slots__ = ("_ann", "_span", "_name", "_table", "_t0")

    def __init__(self, name: str, span: Span | None,
                 table: "LoopTable | None" = None):
        self._ann = TraceAnnotation(name)
        self._span = span
        self._name = name
        self._table = table

    def set(self, **kw) -> "_Phase":
        if self._span is not None:
            self._span.args.update(kw)
        return self

    def __enter__(self) -> "_Phase":
        self._ann.__enter__()
        if self._span is not None:
            self._span.__enter__()
        if self._table is not None:
            self._table.depth += 1
            self._t0 = _now()
        return self

    def __exit__(self, etype, exc, tb):
        if self._table is not None:
            self._table.close(self._name, self._t0, _now())
        if self._span is not None:
            self._span.__exit__(etype, exc, tb)
        self._ann.__exit__(etype, exc, tb)
        return False


def phase(name: str, cat: str = "app", rid: str = "", **args) -> _Phase:
    """A context manager naming what the program does from here to its
    exit, in the profiler's trace (always; recorded only while a
    profiler session runs), in the TRACE=1 ring (same name, plus
    ``rid`` and ``args``) and, on a decode loop's thread, in that loop's
    always-on table (``LoopTable``).  Phases on one thread are FLAT
    siblings wherever a device idle gap should be attributable: never
    wrap a whole loop iteration — the table counts a phase inside
    another (``dispatch:<site>``) apart, and what no phase covers as
    ``unnamed``.  NOTE: kwargs are evaluated by the caller either way —
    a hot path with expensive args checks ``tracer()`` and adds them
    with ``.set()``."""
    tr = _TRACER
    return _Phase(name, None if tr is None else tr.span(name, cat, rid, **args),
                  getattr(_LOOP, "table", None))


# ---------------------------------------------------------------------------
# loop table: where a decode loop thread's wall time went, always on


_LOOP = threading.local()  # .table: the LoopTable bound to this thread


class LoopTable:
    """The boot table's sibling for serving: every ``phase()`` that
    closes on the thread this table is bound to (``bind``, the decode
    loop's) adds its seconds to a row by name — seconds, count, the
    longest instance.  A phase with no other open around it is a
    top-level phase (``phases``); one inside another
    (``dispatch:<site>``) goes to ``inside`` and is not counted twice.
    ``wall_s`` runs from ``bind`` to the last phase exit or ``lap``, and
    what no top-level phase covers of it is ``unnamed_s``: ``wall_s`` =
    sum of ``phases[*].s`` + ``unnamed_s``, at every read.

    ``lap`` closes one iteration of the loop and opens the next; the
    last ``RING`` iterations' summaries (start, wall, seconds by
    top-level phase, live streams, wave rows, chunks in flight) answer
    "the slowest" at read time, each with its longest phase named.  An
    iteration that mostly waited with nothing in the server (``loop/idle``
    its longest phase: the blocking pop timed out) leaves no summary.

    One writer (the bound thread), any reader: a phase exit costs two
    clock reads and one locked dictionary update, with tracing off."""

    RING = 4096
    SLOWEST = 8
    IDLE = "loop/idle"

    def __init__(self, model: str = ""):
        self.model = model
        self._lock = threading.Lock()
        self.phases: dict[str, list] = {}  # name -> [s, n, max_s]
        self.inside: dict[str, list] = {}
        self.depth = 0  # phases open on the bound thread
        self.wall = 0.0
        self.iterations = 0
        self.native_id: int | None = None  # the bound thread's, for /proc
        self._edge: float | None = None  # the last instant accounted for
        self._rows: collections.deque = collections.deque(maxlen=self.RING)
        self._it: tuple | None = None  # open iteration: (t0, {phase: s})
        self._exported: dict = {}  # what export_metrics has handed on

    def bind(self) -> None:
        """This thread is the loop's from here on; time since the last
        binding (a loop thread that died and was revived) is nobody's."""
        _LOOP.table = self
        self.native_id = threading.get_native_id()
        self.depth = 0
        now = _now()
        with self._lock:
            self._edge = now
            self._it = None  # the first ``lap`` opens the first iteration

    def unbind(self) -> None:
        if getattr(_LOOP, "table", None) is self:
            _LOOP.table = None

    @staticmethod
    def _add(rows: dict, name: str, dt: float) -> None:
        row = rows.get(name)
        if row is None:
            rows[name] = [dt, 1, dt]
            return
        row[0] += dt
        row[1] += 1
        if dt > row[2]:
            row[2] = dt

    def close(self, name: str, t0: float, now: float) -> None:
        """A phase that opened at ``t0`` on the bound thread ends."""
        self.depth -= 1
        dt = now - t0
        with self._lock:
            if self.depth > 0:
                self._add(self.inside, name, dt)
                return
            self._add(self.phases, name, dt)
            self.wall += now - self._edge
            self._edge = now
            if self._it is not None:
                mine = self._it[1]
                mine[name] = mine.get(name, 0.0) + dt

    def note(self, name: str, seconds: float) -> None:
        """An interval that is no phase of its own — it spans several
        (an idle admission's whole wait) — under ``inside``."""
        with self._lock:
            self._add(self.inside, name, seconds)

    def lap(self, live: int = 0, rows: int = 0, chunks: int = 0) -> None:
        """The open iteration ends here and the next begins."""
        now = _now()
        with self._lock:
            self.wall += now - self._edge
            self._edge = now
            it, self._it = self._it, (now, {})
            if it is None:
                return
            t0, mine = it
            self.iterations += 1
            if max(mine, key=mine.get, default="") != self.IDLE:
                self._rows.append((t0, now - t0, mine, live, rows, chunks))

    def snapshot(self) -> dict:
        """``{wall_s, unnamed_s, iterations, phases, inside, slowest}``:
        ``phases`` / ``inside`` map a name to ``{s, n, max_s}``;
        ``slowest`` holds the ``SLOWEST`` longest of the ring's
        iterations, longest first (``t`` on ``time.monotonic()``,
        ``phase`` the one most of it went to, ``phases`` all of them)."""
        with self._lock:
            wall, n = self.wall, self.iterations
            phases = {k: list(v) for k, v in self.phases.items()}
            inside = {k: list(v) for k, v in self.inside.items()}
            rows = list(self._rows)

        def table(d):
            return {k: {"s": round(v[0], 6), "n": v[1], "max_s": round(v[2], 6)}
                    for k, v in sorted(d.items())}

        def row(t0, w, mine, live, nrows, chunks):
            top = max(mine, key=mine.get, default="")
            return {"t": round(t0, 4), "wall_s": round(w, 6), "phase": top,
                    "phase_s": round(mine.get(top, 0.0), 6),
                    "unnamed_s": round(max(w - sum(mine.values()), 0.0), 6),
                    "phases": {k: round(v, 6) for k, v in mine.items()},
                    "live": live, "rows": nrows, "chunks": chunks}

        rows.sort(key=lambda r: -r[1])
        return {
            "wall_s": round(wall, 6),
            "unnamed_s": round(
                max(wall - sum(v[0] for v in phases.values()), 0.0), 6),
            "iterations": n,
            "phases": table(phases),
            "inside": table(inside),
            "slowest": [row(*r) for r in rows[: self.SLOWEST]],
        }

    def thread_times(self) -> dict | None:
        """``{run_delay_s, cpu_s}`` of the bound thread: the time it was
        runnable and on no CPU, and the time it ran, where Linux says
        (``/proc/self/task/<tid>/schedstat``); None elsewhere."""
        if self.native_id is None:
            return None
        try:
            with open(f"/proc/self/task/{self.native_id}/schedstat", "rb") as f:
                cpu_ns, delay_ns = f.read().split()[:2]
        except (OSError, ValueError):
            return None
        return {"run_delay_s": int(delay_ns) / 1e9, "cpu_s": int(cpu_ns) / 1e9}

    def export_metrics(self) -> None:
        """Hand what was added since the last call on to the counters
        (``utils/metrics.render`` calls this: a phase exit touches no
        Prometheus child)."""
        from . import metrics

        with self._lock:
            phases = {k: v[0] for k, v in self.phases.items()}
            wall = self.wall
        seen, model = self._exported, self.model
        for name, s in phases.items():
            metrics.raise_to(
                metrics.LOOP_PHASE_SECONDS.labels(model, name), seen, name, s)
        metrics.raise_to(metrics.LOOP_UNNAMED_SECONDS.labels(model), seen,
                         "", max(wall - sum(phases.values()), 0.0))
        times = self.thread_times()
        if times is not None:
            metrics.raise_to(metrics.LOOP_THREAD_RUN_DELAY.labels(model), seen,
                             "run_delay", times["run_delay_s"])


# ---------------------------------------------------------------------------
# boot timeline


@functools.cache
def _process_start() -> float | None:
    """When this process started, on ``time.monotonic()``'s scale, where
    Linux says (``/proc/self/stat`` field 22, ticks since the machine's
    boot); None elsewhere.  Read once: it does not move."""
    try:
        with open("/proc/self/stat", "rb") as f:
            ticks = int(f.read().rsplit(b")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - (
            ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return _now() - age if age >= 0.0 else None


class BootTable:
    """The rows of one boot: every ``boot_phase`` that closed between
    ``begin`` (the entry of ``serve.build_service``) and ``ready``.
    Rows on one thread nest by ``parent``; a row without one is a
    top-level phase.  ``total`` runs from ``begin`` to ``ready``, and
    what no top-level row covers inside it is ``unnamed``.  Bounded:
    past ``MAX_ROWS`` a row is counted, not kept."""

    MAX_ROWS = 256

    def __init__(self):
        self._lock = threading.Lock()
        self._local = threading.local()
        self.begin(None)

    def begin(self, t0: float | None) -> None:
        """A new boot starts at ``t0`` (None: at its first row)."""
        with self._lock:
            self.rows: list[dict] = []
            self.dropped = 0
            self.t0 = t0
            self.t_ready: float | None = None

    @property
    def closed(self) -> bool:
        return self.t_ready is not None

    def stack(self) -> list[str]:
        """Names of the boot phases open on this thread, outermost first."""
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def add(self, name: str, start: float, seconds: float,
            parent: str | None = None, **args) -> None:
        row = {"name": name, "start": start, "seconds": seconds,
               "thread": threading.current_thread().name, "parent": parent}
        if args:
            row["args"] = args
        with self._lock:
            if self.closed:
                return
            if self.t0 is None:
                self.t0 = start
            if len(self.rows) < self.MAX_ROWS:
                self.rows.append(row)
            else:
                self.dropped += 1

    def ready(self) -> None:
        """Close the table: the instant row ``boot/ready`` ends ``total``."""
        now = _now()
        self.add("boot/ready", now, 0.0)
        with self._lock:
            if self.t_ready is None:
                self.t_ready = now

    def snapshot(self) -> dict:
        """``{rows, phases, unnamed_s, total_s, pre_build_s, ready}``:
        rows in order of their start, seconds from ``begin``;
        ``phases`` sums the top-level rows by name; ``total_s`` runs to
        readiness (to now while the boot is open)."""
        with self._lock:
            rows = sorted(self.rows, key=lambda r: r["start"])
            t0, t_ready, dropped = self.t0, self.t_ready, self.dropped
        if t0 is None:
            return {"rows": [], "phases": {}, "unnamed_s": 0.0,
                    "total_s": 0.0, "ready": False}
        end = t_ready if t_ready is not None else _now()
        phases: dict[str, float] = {}
        covered, edge = 0.0, t0
        for r in rows:
            if r["parent"] is not None:
                continue
            phases[r["name"]] = phases.get(r["name"], 0.0) + r["seconds"]
            # the union of the top-level rows inside [t0, end]: phases
            # on two threads that overlap are covered once
            lo = max(r["start"], edge)
            hi = min(r["start"] + r["seconds"], end)
            if hi > lo:
                covered += hi - lo
                edge = hi
        out = {
            "rows": [dict(r, start=round(r["start"] - t0, 4),
                          seconds=round(r["seconds"], 4)) for r in rows],
            "phases": {k: round(v, 4) for k, v in phases.items()},
            "unnamed_s": round(max(end - t0 - covered, 0.0), 4),
            "total_s": round(end - t0, 4),
            "ready": t_ready is not None,
        }
        if dropped:
            out["rows_dropped"] = dropped
        started = _process_start()
        if started is not None and started <= t0:
            out["pre_build_s"] = round(t0 - started, 4)
        return out


_BOOT = BootTable()


def boot_table() -> BootTable:
    """The process's boot table (one process, one boot at a time)."""
    return _BOOT


class _BootPhase(_Phase):
    """``phase()`` that also leaves a row in the boot table."""

    __slots__ = ("_parent", "_args", "seconds")

    def __init__(self, name: str, span: Span | None, parent: str | None,
                 args: dict):
        super().__init__(name, span)
        self._parent = parent
        self._args = args
        self.seconds = 0.0

    def set(self, **kw) -> "_BootPhase":
        self._args.update(kw)
        return self

    def __enter__(self) -> "_BootPhase":
        stack = _BOOT.stack()
        if self._parent is None and stack:
            self._parent = stack[-1]
        stack.append(self._name)
        super().__enter__()
        self._t0 = _now()
        return self

    def __exit__(self, etype, exc, tb):
        self.seconds = _now() - self._t0
        if self._span is not None:
            self._span.args.update(self._args)
        super().__exit__(etype, exc, tb)
        stack = _BOOT.stack()
        if stack and stack[-1] == self._name:
            stack.pop()
        _BOOT.add(self._name, self._t0, self.seconds, self._parent,
                  **self._args)
        return False


def boot_phase(name: str, parent: str | None = None, **args) -> _BootPhase:
    """``phase(name)`` plus an always-on row in the boot table while a
    boot is open (after readiness: the phase alone).  ``parent``
    defaults to the boot phase open on this thread; a worker thread of
    a phase names it (``boot_current()`` read on the spawning thread).
    ``.seconds`` holds the wall time after exit."""
    tr = _TRACER
    span = None if tr is None else tr.span(name, "boot", "")
    return _BootPhase(name, span, parent, args)


def boot_current() -> str | None:
    """The innermost boot phase open on this thread, if any."""
    stack = _BOOT.stack()
    return stack[-1] if stack else None


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
