"""Deadline-aware, class-weighted wait queue (the scheduler's policy
core).

The seed's front door was binary: a raw FIFO ``asyncio.Queue`` in the
batcher and an instant 503 past ``max_streams`` in the stream loop.
This module replaces both with one policy structure, following the
memory-aware / SLA-constrained batching literature (PAPERS.md): what
decides goodput under overload is WHICH request waits, for HOW long,
and which one is shed — not the kernels.

Policy, in one place:

- Two priority classes (``interactive`` > ``batch``), selected per
  request via the ``X-Priority`` header with a config default.
- Earliest-deadline-first ordering WITHIN a class; FIFO tie-break for
  deadline-less requests (so the default config degrades to exactly
  the seed's FIFO behavior).
- Class-weighted dequeue ACROSS classes: ``weight`` interactive pops
  per batch pop while both classes wait, so batch work cannot starve
  but never delays interactive work by more than 1/weight.
- Overload shed on ``put``: the victim is the lowest-class,
  latest-deadline waiter — and only if the newcomer outranks it;
  otherwise the newcomer itself is shed (503).
- Expiry: a request still waiting past its deadline is removed and
  failed FAST (504 before dispatch) instead of being served stale or
  timing out client-side after burning device time.

Thread-safe: the batcher puts/pops on the asyncio event loop while the
continuous decode loop pops from its owner thread.
"""

from __future__ import annotations

import heapq
import itertools
import threading
import time
from collections import deque

from ..utils import metrics, tracing

INTERACTIVE = "interactive"
BATCH = "batch"
#: Rank order: earlier = higher priority.
CLASSES = (INTERACTIVE, BATCH)


class QueueFullError(Exception):
    """Queue at capacity; shed load (HTTP 503).

    ``reason`` labels the shed counter (queue_full | kv_budget | drain |
    quota | adapter_pool); ``retry_after_s`` rides to the HTTP
    Retry-After header.  ``quota`` sheds (per-tenant admission,
    tenancy/accounts.py) map to HTTP 429 instead of 503 — the tenant is
    over ITS budget while the service has capacity to sell elsewhere.
    """

    def __init__(self, msg: str = "", reason: str = "queue_full",
                 retry_after_s: float | None = None):
        super().__init__(msg)
        self.reason = reason
        self.retry_after_s = retry_after_s


class DeadlineExceededError(Exception):
    """The request's deadline passed while it waited (HTTP 504)."""


def _dl(item) -> float:
    """Sort key: absolute monotonic deadline, None = no deadline = last."""
    return item.deadline if item.deadline is not None else float("inf")


class PrefillPacer:
    """Deadline-aware chunk budget for prefill–decode interleaving
    (PREFILL_CHUNK; engine/streams.py).

    Policy, mirroring the dequeue weights: interactive-class prefill
    always advances (it IS the latency-sensitive work — holding it
    back only moves its TTFT); batch-class prefill is starved while
    interactive-class decode is live, EXCEPT one window every
    ``weight`` boundaries so it cannot starve forever; with no
    interactive decode running, batch prefill backfills the idle
    compute freely."""

    def __init__(self, weight: int = 4):
        self.weight = max(1, int(weight))
        self._held = 0
        # Optional flight recorder (utils/tracing.FlightRecorder, wired
        # by the decode loop): every hold/grant decision on batch-class
        # prefill is an event in the engine post-mortem ring — "why
        # didn't my batch prompt advance" answers itself.
        self.recorder = None

    def allow(self, job_klass: str, interactive_active: bool) -> bool:
        """May a ``job_klass`` prefill window dispatch at this chunk
        boundary, given whether interactive decode is live?"""
        if job_klass == INTERACTIVE or not interactive_active:
            return True
        self._held += 1
        if self._held >= self.weight:
            self._held = 0
            if self.recorder is not None:
                self.recorder.event(
                    "pacer_grant", klass=job_klass, weight=self.weight
                )
            return True
        if self.recorder is not None:
            self.recorder.event(
                "pacer_hold", klass=job_klass, held=self._held,
                weight=self.weight,
            )
        return False


class BackfillGovernor:
    """How many bulk-job lines may ride in flight right now
    (JOB_MAX_CONCURRENT_LINES; jobs/executor.py).

    Bulk lines are batch-class streams, so the deadline queue's class
    weights and chunk-boundary preemption already protect interactive
    traffic once a line is ADMITTED — what this governor controls is
    how hard the executor pushes on admission in the first place
    (SLA-constrained batching, arXiv 2503.05248: the bulk lane rides
    the same scheduler, it must not flood it):

    - no interactive work anywhere → claim the full cap (pure
      idle-compute backfill);
    - interactive decode live → half the cap (lines in slots still
      yield via preemption, but fresh claims deepen the next
      preemption sweep);
    - interactive work WAITING (queued or mid-prefill) → one line,
      keeping the lane warm without competing for the very capacity
      the waiters need.
    """

    def __init__(self, cap: int):
        self.cap = max(1, int(cap))

    def target(self, interactive_live: bool,
               interactive_waiting: bool) -> int:
        if interactive_waiting:
            return 1
        if interactive_live:
            return max(1, self.cap // 2)
        return self.cap


class SLOTracker:
    """Per-priority-class latency-SLO burn-rate tracking (r20 perf
    observatory; docs/observability.md).

    Objectives come from the ``SLO_TTFT_MS``/``SLO_TBT_MS`` knobs
    (interactive class) and their ``SLO_BATCH_*`` siblings; a 0 knob
    disables that (kind, class) objective.  Each delivery the decode
    loop already measures (TTFT at the first chunk, TBT per inter-chunk
    gap — ``engine/streams.py::_emit_tokens``) is scored good/bad
    against its objective, and the classic SRE burn rate is derived
    over two windows::

        burn = (bad / total within window) / (1 - SLO_TARGET)

    1.0 = consuming the error budget exactly at the sustainable rate;
    >1 = the SLO is being violated; the FAST window reacts to incidents
    while the SLOW window filters blips.  Exported as
    ``slo_{ttft,tbt}_burn_rate{klass, window}`` gauges (rate-limited to
    ~1/s) and consumed by the ``ScalingGovernor`` when
    ``SCALE_UP_SLO_BURN`` is set (off by default — bit-identical
    scaling decisions when unset, pinned).

    Pure policy: clock-injected (tests drive burn windows without
    sleeping), bounded memory (one deque per objective, pruned to the
    slow window), thread-safe (the decode loop notes; the governor and
    /status read)."""

    KINDS = ("ttft", "tbt")
    WINDOW_NAMES = ("fast", "slow")

    def __init__(self, model: str, objectives: dict, target: float = 0.99,
                 windows_s: tuple = (60.0, 600.0), clock=None,
                 max_samples: int = 4096):
        self.model = model
        #: {(kind, klass): objective_seconds}, only enabled objectives.
        self.objectives = {
            k: float(v) for k, v in objectives.items() if v and v > 0
        }
        self.target = float(target)
        self.windows_s = (float(windows_s[0]), float(windows_s[1]))
        self._budget = max(1e-9, 1.0 - self.target)
        self._clock = clock if clock is not None else time.monotonic
        self._lock = threading.Lock()
        self._max_samples = int(max_samples)
        self._samples: dict = {
            key: deque(maxlen=self._max_samples) for key in self.objectives
        }
        self._last_export = 0.0

    @classmethod
    def from_cfg(cls, model: str, cfg, clock=None):
        """Tracker from the service knobs, or None when every
        objective is 0 (the default) — the zero-overhead-off gate."""
        objectives = {
            ("ttft", INTERACTIVE): float(
                getattr(cfg, "slo_ttft_ms", 0.0) or 0.0
            ) / 1e3,
            ("tbt", INTERACTIVE): float(
                getattr(cfg, "slo_tbt_ms", 0.0) or 0.0
            ) / 1e3,
            ("ttft", BATCH): float(
                getattr(cfg, "slo_batch_ttft_ms", 0.0) or 0.0
            ) / 1e3,
            ("tbt", BATCH): float(
                getattr(cfg, "slo_batch_tbt_ms", 0.0) or 0.0
            ) / 1e3,
        }
        if not any(v > 0 for v in objectives.values()):
            return None
        windows = getattr(cfg, "slo_windows_s", None) or "60,600"
        try:
            parts = [float(x) for x in str(windows).split(",") if x.strip()]
        except ValueError:
            parts = [60.0, 600.0]
        if len(parts) != 2 or parts[0] <= 0 or parts[0] >= parts[1]:
            parts = [60.0, 600.0]
        return cls(
            model, objectives,
            target=float(getattr(cfg, "slo_target", 0.99) or 0.99),
            windows_s=(parts[0], parts[1]), clock=clock,
        )

    # -- write side (the decode loop's delivery path) ------------------

    def note(self, kind: str, klass: str, value_s: float) -> None:
        obj = self.objectives.get((kind, klass))
        if obj is None:
            return
        now = self._clock()
        with self._lock:
            q = self._samples[(kind, klass)]
            q.append((now, value_s <= obj))
            # Prune past the slow window so burn reads stay O(window).
            horizon = now - self.windows_s[1]
            while q and q[0][0] < horizon:
                q.popleft()
            export = now - self._last_export >= 1.0
            if export:
                self._last_export = now
        if export:
            self.export_gauges(now)

    # -- read side -----------------------------------------------------

    def burn_rate(self, kind: str, klass: str,
                  window_s: float | None = None,
                  now: float | None = None) -> float:
        """Burn rate over ``window_s`` (default: the fast window); 0.0
        with no samples (no traffic = no budget burned)."""
        if (kind, klass) not in self.objectives:
            return 0.0
        window = self.windows_s[0] if window_s is None else float(window_s)
        now = self._clock() if now is None else now
        horizon = now - window
        with self._lock:
            q = self._samples[(kind, klass)]
            total = bad = 0
            for ts, good in reversed(q):
                if ts < horizon:
                    break
                total += 1
                if not good:
                    bad += 1
        if not total:
            return 0.0
        return (bad / total) / self._budget

    def worst_burn(self) -> float:
        """Max fast-window burn across every enabled objective — the
        single scalar the ScalingGovernor consumes."""
        return max(
            (
                self.burn_rate(kind, klass)
                for kind, klass in self.objectives
            ),
            default=0.0,
        )

    def export_gauges(self, now: float | None = None) -> None:
        """Set the burn-rate gauges for every (objective, window)."""
        now = self._clock() if now is None else now
        for (kind, klass) in self.objectives:
            gauge = (
                metrics.SLO_TTFT_BURN if kind == "ttft"
                else metrics.SLO_TBT_BURN
            )
            for name, win in zip(self.WINDOW_NAMES, self.windows_s):
                gauge.labels(self.model, klass, name).set(
                    self.burn_rate(kind, klass, win, now=now)
                )

    def snapshot(self) -> dict:
        """/status.slo: objectives + burn rates."""
        now = self._clock()
        out: dict = {
            "target": self.target,
            "windows_s": list(self.windows_s),
            "objectives_ms": {
                f"{kind}:{klass}": round(obj * 1e3, 3)
                for (kind, klass), obj in sorted(self.objectives.items())
            },
            "burn": {},
        }
        for (kind, klass) in sorted(self.objectives):
            for name, win in zip(self.WINDOW_NAMES, self.windows_s):
                out["burn"][f"{kind}:{klass}:{name}"] = round(
                    self.burn_rate(kind, klass, win, now=now), 4
                )
        with self._lock:
            out["samples"] = {
                f"{kind}:{klass}": len(self._samples[(kind, klass)])
                for (kind, klass) in sorted(self.objectives)
            }
        return out


class ScalingGovernor:
    """Decide when the replica fleet should grow or shrink
    (engine/fleet.py drives ``ReplicaFleet`` off these decisions;
    docs/autoscaling.md).

    Pure policy over a load snapshot — no engines, no threads — so the
    thresholds are unit-testable with an injected clock.  The signals
    are the router's OWN load exports (λScale, arXiv 2502.09922: scale
    off serving signals, not external monitors):

    - **queue depth**: waiting streams per live replica ≥ ``up_queue``
      → scale up (the queue is where overload becomes visible first);
    - **committed KV**: the live fleet's committed-KV bytes at
      ``up_kv_frac`` of its budget → scale up (memory saturates before
      compute for long-context traffic);
    - **TTFT EWMA**: the decode loops' time-to-first-chunk EWMA past
      ``up_ttft_s`` → scale up (0 disables the signal — it needs a
      deployment-calibrated threshold);
    - **sustained lull**: total load (active + queued) would fit in
      ``down_load`` of the SURVIVORS' slots for ``down_cooldown_s``
      straight → scale down (the hysteresis that keeps a bursty
      workload from flapping).

    One step per decision (up OR down by 1): each event rebalances the
    fleet budget and re-snapshots, so multi-step corrections converge
    over a few ticks instead of overshooting on a stale signal.
    ``note_event`` stamps the cooldowns when the fleet actually acted
    (a failed spawn must not burn the cooldown silently).
    """

    def __init__(self, min_r: int, max_r: int, *, up_queue: float = 2.0,
                 up_kv_frac: float = 0.85, up_ttft_s: float = 0.0,
                 up_cooldown_s: float = 3.0, down_load: float = 0.25,
                 down_cooldown_s: float = 10.0, up_slo_burn: float = 0.0,
                 clock=None):
        self.min_r = max(1, int(min_r))
        self.max_r = max(self.min_r, int(max_r))
        self.up_queue = float(up_queue)
        self.up_kv_frac = float(up_kv_frac)
        self.up_ttft_s = float(up_ttft_s)
        # SLO-burn scale-up signal (r20; SCALE_UP_SLO_BURN): scale up
        # when the SLOTracker's worst fast-window burn rate reaches
        # this threshold.  0 (default) = signal off — decisions are
        # bit-identical to the pre-SLO governor (pinned).
        self.up_slo_burn = float(up_slo_burn)
        self.up_cooldown_s = float(up_cooldown_s)
        self.down_load = float(down_load)
        self.down_cooldown_s = float(down_cooldown_s)
        self._clock = clock if clock is not None else time.monotonic
        self._last_up: float | None = None
        self._low_since: float | None = None

    def decide(self, *, live: int, queued: int, active: int,
               slots: int, kv_frac: float = 0.0,
               ttft_ewma_s: float = 0.0,
               slo_burn: float = 0.0,
               free_groups: int | None = None) -> tuple[str | None, str]:
        """(direction, cause) for one governor tick.  direction is
        "up" | "down" | None; cause labels the scale-event counter
        (queue | kv | ttft | slo | min | idle | steady | no_devices).

        ``free_groups`` is the multi-chip fleet's group-carve signal:
        how many whole device groups of the fleet's default width the
        host can still seat (None — single-device fleets — leaves every
        decision unchanged).  The governor scales in units of WHOLE
        groups, so an "up" with ``free_groups == 0`` degrades to
        ``(None, "no_devices")`` — an honest stall instead of a doomed
        spawn per tick."""
        now = self._clock()
        if live <= 0:
            # Nothing alive to compare load against: the rejoin path
            # (engine/fleet.py) owns recovery, not the load policy.
            return None, "dead"
        no_seat = free_groups is not None and free_groups <= 0
        if live < self.min_r:
            return (None, "no_devices") if no_seat else ("up", "min")
        up_ready = self._last_up is None or (
            now - self._last_up >= self.up_cooldown_s
        )
        if live < self.max_r and up_ready:
            want_up = None
            if self.up_queue and queued >= self.up_queue * live:
                want_up = "queue"
            elif self.up_kv_frac and kv_frac >= self.up_kv_frac:
                want_up = "kv"
            elif self.up_ttft_s and ttft_ewma_s >= self.up_ttft_s:
                want_up = "ttft"
            elif self.up_slo_burn and slo_burn >= self.up_slo_burn:
                want_up = "slo"
            if want_up is not None:
                return (None, "no_devices") if no_seat else ("up", want_up)
        if live > self.min_r:
            survivors = live - 1
            low = (active + queued) <= self.down_load * slots * survivors
            if low:
                if self._low_since is None:
                    self._low_since = now
                elif now - self._low_since >= self.down_cooldown_s:
                    return "down", "idle"
            else:
                self._low_since = None
        else:
            self._low_since = None
        return None, "steady"

    def note_event(self, direction: str) -> None:
        """The fleet actually scaled: stamp the cooldown clocks."""
        now = self._clock()
        if direction == "up":
            self._last_up = now
        self._low_since = None

    def status(self) -> dict:
        now = self._clock()
        return {
            "min": self.min_r,
            "max": self.max_r,
            "up_cooldown_remaining_s": (
                round(max(
                    0.0, self._last_up + self.up_cooldown_s - now
                ), 3) if self._last_up is not None else 0.0
            ),
            "low_load_for_s": (
                round(now - self._low_since, 3)
                if self._low_since is not None else None
            ),
        }


class Arrival:
    """One request the server has read and not yet queued, counted on
    the decode loops' queues from construction to the first
    ``settle()``: when its stream has been put, or when the request
    failed on the way (400, shed, cancelled).  An idle loop's admission
    waits for what is counted here and for nothing else
    (``DeadlineQueue.pop_expected``).  Event-loop side only."""

    def __init__(self, queues=()):
        self._queues = tuple(queues)
        for q in self._queues:
            q.expect()

    def settle(self) -> None:
        queues, self._queues = self._queues, ()
        for q in queues:
            q.settle()

    def __enter__(self) -> "Arrival":
        return self

    def __exit__(self, *exc) -> None:
        self.settle()


class DeadlineQueue:
    """Bounded two-class EDF wait queue (see module docstring).

    Queued items must expose attributes ``klass`` (interactive|batch),
    ``deadline`` (absolute ``time.monotonic()`` seconds or None),
    ``started`` (True once response bytes went out: exempt from expiry
    and eviction — a preempted stream re-queued for resumption cannot
    be converted to an HTTP error anymore).  The queue stamps a private
    ``_removed`` flag for lazy heap deletion.
    """

    def __init__(self, maxsize: int, weight: int = 4, clock=None):
        self.maxsize = max(1, int(maxsize))
        self.weight = max(1, int(weight))
        self._heaps: dict[str, list] = {k: [] for k in CLASSES}
        self._count: dict[str, int] = {k: 0 for k in CLASSES}
        self._cond = threading.Condition()
        self._seq = itertools.count()
        self._streak = 0  # consecutive interactive pops while batch waits
        # Requests the server has read and not yet put here (``Arrival``):
        # what an idle decode loop's admission waits for (``pop_expected``).
        self._expected = 0
        # Optional weighted fair share across tenants WITHIN a class
        # (tenancy/fairshare.py; set by the batcher when TENANTS is
        # configured).  None = plain EDF, bit-identical to pre-tenancy.
        self._fairshare = None
        # Injectable clock (graftlint: clock-injection) — expiry and
        # pop timeouts pin in tests without sleeping through real
        # deadlines; item deadlines stay absolute seconds on this clock.
        self._clock = clock if clock is not None else time.monotonic

    # -- introspection -------------------------------------------------

    def qsize(self) -> int:
        with self._cond:
            return sum(self._count.values())

    def waiting(self, klass: str) -> int:
        with self._cond:
            return self._count[klass]

    def expected(self) -> int:
        """Requests announced (``expect``) and not yet settled."""
        with self._cond:
            return self._expected

    def expect(self) -> None:
        """One more request is on its way to this queue (``Arrival``)."""
        with self._cond:
            self._expected += 1

    def settle(self) -> None:
        """An announced request was put, or will never be: a loop that
        waits for it (``pop_expected``) looks again."""
        with self._cond:
            self._expected -= 1
            self._cond.notify()

    def waiting_started(self) -> int:
        """Checkpointed (preempted) streams still waiting to resume."""
        with self._cond:
            return sum(
                1
                for heap in self._heaps.values()
                for _, it in heap
                if not it._removed and it.started
            )

    def next_deadline(self) -> float | None:
        """Earliest expirable deadline among waiting items (idle-wake
        timer for the batcher's expiry sweep)."""
        with self._cond:
            best = None
            for heap in self._heaps.values():
                for _, it in heap:
                    if it._removed or it.started or it.deadline is None:
                        continue
                    if best is None or it.deadline < best:
                        best = it.deadline
            return best

    # -- enqueue -------------------------------------------------------

    def put(self, item, force: bool = False):
        """Enqueue; returns an evicted lower-ranked waiter (the caller
        fails it with a 503) or None.  Raises ``QueueFullError`` when
        full and the newcomer outranks nobody.  ``force`` bypasses the
        bound (re-queueing a preempted, already-started stream)."""
        with self._cond:
            victim = None
            if not force and sum(self._count.values()) >= self.maxsize:
                victim = self._pick_victim_locked(item)
                if victim is None:
                    raise QueueFullError(
                        f"queue depth {sum(self._count.values())} >= "
                        f"{self.maxsize}"
                    )
                victim._removed = True
                self._count[victim.klass] -= 1
            item._removed = False
            key = (_dl(item), next(self._seq))
            heapq.heappush(self._heaps[item.klass], (key, item))
            self._count[item.klass] += 1
            self._cond.notify()
            return victim

    def evict_for(self, incoming):
        """Shed-for-admission without enqueueing: returns (and removes)
        the victim ``incoming`` outranks, or None.  Used by callers that
        bound admission on something wider than this queue's size (the
        stream loop counts active slots too)."""
        with self._cond:
            victim = self._pick_victim_locked(incoming)
            if victim is not None:
                victim._removed = True
                self._count[victim.klass] -= 1
            return victim

    def _pick_victim_locked(self, incoming):
        """Lowest-class latest-deadline waiter that ``incoming``
        outranks: strictly lower class, or same class with a strictly
        later deadline.  Started items are never evicted."""
        for klass in reversed(CLASSES):  # lowest class first
            live = [
                it for _, it in self._heaps[klass]
                if not it._removed and not it.started
            ]
            if not live:
                continue
            victim = max(live, key=_dl)
            inc_rank = CLASSES.index(incoming.klass)
            v_rank = CLASSES.index(klass)
            if inc_rank < v_rank:
                return victim
            if inc_rank == v_rank and _dl(incoming) < _dl(victim):
                return victim
            return None
        return None

    # -- dequeue -------------------------------------------------------

    def pop_nowait(self, fits=None):
        """EDF-within-class, class-weighted-across-classes pop; returns
        None when empty (or when no waiter passes ``fits`` — the
        KV-budget admission gate)."""
        with self._cond:
            return self._pop_locked(fits)

    def pop(self, timeout: float | None = None, fits=None):
        """Blocking pop for the decode-loop thread."""
        deadline = None if timeout is None else self._clock() + timeout
        with self._cond:
            while True:
                item = self._pop_locked(fits)
                if item is not None:
                    return item
                remaining = (
                    None if deadline is None else deadline - self._clock()
                )
                if remaining is not None and remaining <= 0:
                    return None
                if not self._cond.wait(timeout=remaining):
                    return self._pop_locked(fits)

    def pop_expected(self, timeout: float, quiet: float = 0.0, fits=None):
        """Blocking pop for an idle decode loop that holds the first rows
        of a burst: waits while a request is announced (``expected()``
        above zero) and, past that, until ``quiet`` seconds from now
        have brought none; never longer than ``timeout``.  None = go:
        nothing is coming (or ``timeout`` ran out with ``expected()``
        still above zero, which the caller can read)."""
        now = self._clock()
        deadline, quiet_until = now + timeout, now + quiet
        with self._cond:
            while True:
                item = self._pop_locked(fits)
                if item is not None:
                    return item
                api = self._expected > 0
                until = deadline if api else min(deadline, quiet_until)
                remaining = until - self._clock()
                if remaining <= 0:
                    return None
                # One slice of the wait (a put or a settle ends it), named
                # by what it waits for: a request the API still holds, or
                # the clients' next write.
                with tracing.phase(
                    "loop/await_api" if api else "loop/await_burst"
                ):
                    self._cond.wait(timeout=remaining)

    def set_fairshare(self, fs) -> None:
        """Attach (or detach, ``None``) a ``WeightedFairShare`` ledger:
        dequeue becomes per-tenant EDF under weighted virtual time —
        within each class the tenant with the lowest virtual finish time
        is served its earliest-deadline waiter, so a heavy tenant's
        backlog cannot starve light tenants (pinned by
        tests/test_tenancy.py)."""
        with self._cond:
            self._fairshare = fs

    def prefer_interactive(self) -> None:
        """Reset the weighted-dequeue streak so the next pop serves the
        interactive class (used right after a preemption: the slot that
        was just vacated must not go back to a batch waiter)."""
        with self._cond:
            self._streak = 0

    def _pop_locked(self, fits):
        for klass in self._class_order_locked():
            item = self._pop_class_locked(klass, fits)
            if item is not None:
                if klass == INTERACTIVE and self._count[BATCH] > 0:
                    self._streak += 1
                else:
                    self._streak = 0
                return item
        return None

    def _class_order_locked(self):
        if self._count[INTERACTIVE] and self._count[BATCH]:
            if self._streak >= self.weight:
                return (BATCH, INTERACTIVE)
            return (INTERACTIVE, BATCH)
        return (INTERACTIVE, BATCH) if self._count[INTERACTIVE] else (
            BATCH, INTERACTIVE
        )

    def _pop_class_locked(self, klass: str, fits):
        if self._fairshare is not None:
            return self._pop_class_fair_locked(klass, fits, self._fairshare)
        heap = self._heaps[klass]
        stash = []
        found = None
        while heap:
            key, it = heapq.heappop(heap)
            if it._removed:
                continue
            if fits is not None and not fits(it):
                # Head-of-line doesn't fit the admission budget: look
                # past it (a smaller request may) — expiry bounds how
                # long the skipped head can languish.
                stash.append((key, it))
                continue
            it._removed = True
            self._count[klass] -= 1
            found = it
            break
        for entry in stash:
            heapq.heappush(heap, entry)
        return found

    def _pop_class_fair_locked(self, klass: str, fits, fs):
        """Weighted-fair pop: per-tenant EDF head, then the fair-share
        ledger picks which tenant is served.  O(n) scan with lazy heap
        deletion — the heap keeps EDF order for the plain path and for
        ``expire``; fairness only reorders ACROSS tenants, never within
        one (EDF-within-tenant is preserved by taking each tenant's
        heap-key minimum)."""
        heads: dict[str, tuple] = {}
        for key, it in self._heaps[klass]:
            if it._removed:
                continue
            if fits is not None and not fits(it):
                continue
            t = getattr(it, "tenant", "") or ""
            cur = heads.get(t)
            if cur is None or key < cur[0]:
                heads[t] = (key, it)
        if not heads:
            return None
        tenant = fs.pick(heads.keys())
        _, it = heads[tenant]
        it._removed = True
        self._count[klass] -= 1
        fs.charge(tenant)
        return it

    # -- expiry / shutdown --------------------------------------------

    def expire(self, now: float | None = None) -> list:
        """Remove and return every waiter whose deadline passed (the
        caller fails them with ``DeadlineExceededError`` → 504).
        Started items never expire."""
        now = self._clock() if now is None else now
        out = []
        with self._cond:
            for klass in CLASSES:
                heap = self._heaps[klass]
                repush = []
                while heap and heap[0][0][0] <= now:
                    key, it = heapq.heappop(heap)
                    if it._removed:
                        continue
                    if it.started:
                        repush.append((key, it))
                        continue
                    it._removed = True
                    self._count[klass] -= 1
                    out.append(it)
                for entry in repush:
                    heapq.heappush(heap, entry)
        return out

    def drain_all(self) -> list:
        """Remove and return everything (shutdown path)."""
        with self._cond:
            out = [
                it
                for heap in self._heaps.values()
                for _, it in heap
                if not it._removed
            ]
            for it in out:
                it._removed = True
            self._heaps = {k: [] for k in CLASSES}
            self._count = {k: 0 for k in CLASSES}
            return out
