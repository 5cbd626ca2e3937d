"""The server process's own pauses, always on (docs/observability.md).

A request can lose time to nothing the serving code does: the event
loop that reads every request and writes every token event is late
because something held it, the collector stops every thread of the
interpreter, or the decode loop's thread is runnable and has no CPU
(the load generator, the server and a tracer share a machine's cores).
Three readings, beside ``utils/tracing.LoopTable``:

- ``EventLoopLag``: a 20 Hz ``call_later`` tick on the server's event
  loop; how late each tick ran is ``event_loop_lag_seconds``.
- ``GcPauses``: ``gc.callbacks``; collections and seconds by
  generation, ``gc_pause_seconds_total``.
- the decode loop thread's run delay (``LoopTable.thread_times``,
  ``loop_thread_run_delay_seconds_total``), read when asked for.

``/status.process`` shows all three (``snapshot``).
"""

from __future__ import annotations

import collections
import gc
import statistics
import time

from . import metrics


class EventLoopLag:
    """How late the event loop runs a callback it was asked to run at a
    given time: a tick every ``PERIOD`` seconds, each observed on its
    own (the next is set from when this one ran, so one long hold is
    one late tick, not a run of them)."""

    PERIOD = 0.05
    RING = 4096  # ticks the quantiles of ``snapshot`` look back over

    def __init__(self, model: str):
        self._hist = metrics.EVENT_LOOP_LAG.labels(model)
        self._late: collections.deque = collections.deque(maxlen=self.RING)
        self.ticks = 0
        self.max_s = 0.0
        self._loop = None
        self._handle = None
        self._due = 0.0

    def start(self, loop) -> None:
        self._loop = loop
        self._arm()

    def stop(self) -> None:
        if self._handle is not None:
            self._handle.cancel()
            self._handle = None

    def _arm(self) -> None:
        self._due = self._loop.time() + self.PERIOD
        self._handle = self._loop.call_at(self._due, self._tick)

    def _tick(self) -> None:
        late = max(self._loop.time() - self._due, 0.0)
        self._hist.observe(late)
        self._late.append(late)
        self.ticks += 1
        if late > self.max_s:
            self.max_s = late
        self._arm()

    def snapshot(self) -> dict:
        late = sorted(self._late)
        if len(late) < 2:
            return {"ticks": self.ticks, "max_s": round(self.max_s, 6)}
        cuts = statistics.quantiles(late, n=100, method="inclusive")
        return {"ticks": self.ticks, "p50_s": round(cuts[49], 6),
                "p99_s": round(cuts[98], 6), "max_s": round(self.max_s, 6)}


class GcPauses:
    """Seconds the cyclic collector held the interpreter, by generation.
    A collection runs on whichever thread allocated last and stops them
    all; ``install`` is idempotent and ``remove`` takes it out again."""

    def __init__(self):
        self.collections = [0, 0, 0]
        self.seconds = [0.0, 0.0, 0.0]
        self.max_s = [0.0, 0.0, 0.0]
        self._t0 = 0.0
        self._exported: dict = {}

    def install(self) -> None:
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)
        for g in range(3):  # a generation that never ran reads 0
            metrics.GC_PAUSE_SECONDS.labels(str(g))

    def remove(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.monotonic()
            return
        dt = time.monotonic() - self._t0
        g = min(int(info.get("generation", 2)), 2)
        self.collections[g] += 1
        self.seconds[g] += dt
        if dt > self.max_s[g]:
            self.max_s[g] = dt

    def snapshot(self) -> dict:
        return {
            f"gen{g}": {"collections": self.collections[g],
                        "s": round(self.seconds[g], 6),
                        "max_s": round(self.max_s[g], 6)}
            for g in range(3)
        }

    def export_metrics(self) -> None:
        """What was added since the last call goes on to the counter
        (``metrics.render`` asks: a collection touches no Prometheus
        child)."""
        for g in range(3):
            metrics.raise_to(metrics.GC_PAUSE_SECONDS.labels(str(g)),
                             self._exported, g, self.seconds[g])


GC = GcPauses()
metrics.register_exporter(GC)


def snapshot(lag: EventLoopLag | None, loop_table=None) -> dict:
    """``/status.process``: ``{event_loop_lag, gc, loop_thread}``; the
    first where the server's tick runs, the last where Linux says."""
    out: dict = {"gc": GC.snapshot()}
    if lag is not None:
        out["event_loop_lag"] = lag.snapshot()
    times = loop_table.thread_times() if loop_table is not None else None
    if times is not None:
        out["loop_thread"] = {k: round(v, 6) for k, v in times.items()}
    return out
