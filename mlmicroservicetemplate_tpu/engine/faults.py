"""Fault injection + dispatch watchdog for fault-tolerant serving.

Two cooperating pieces, both OFF by default:

- **FaultInjector** (``FAULT_SPEC``): a deterministic, seedable fault
  schedule wrapped around the device-dispatch boundaries (the
  continuous loop's prefill/chunk/fetch sites, the batcher's batch
  site, the paged allocator's grow site).  It can raise transient
  device errors, raise fatal "device lost" errors, inject hangs
  (sleeps longer than the watchdog deadline), and force
  ``OutOfBlocks`` — on the Nth matching dispatch or at a seeded
  Bernoulli rate.  With no spec the injector is ``None`` and every
  call site skips it entirely (zero overhead).

- **Watchdog** (``DISPATCH_TIMEOUT_S`` / ``DISPATCH_RETRIES`` /
  ``DISPATCH_BACKOFF_S``): runs one dispatch callable under a
  monitored deadline and retries transient failures with capped
  exponential backoff.  Every guarded callable is a pure function of
  its inputs (jitted calls and fetches), so a retry WITH THE SAME
  INPUTS is token-identical.  The executables that replace a decode
  state donate it (engine/streams.py: a state that is replaced is
  donated), so the inputs exist only until the runtime takes them: an
  injected fault fires BEFORE the callable (``_attempt``), the state is
  still live and the retry is exact; a real error raised after the
  state was consumed is re-raised as ``StateConsumedError`` — fatal,
  never retried on deleted arrays — and ends in the rebuild path
  (``guard_donation``).  A deadline overrun raises ``DispatchTimeoutError`` — classified
  FATAL, because a wedged dispatch on the same device state will not
  unwedge by retrying; the supervisor (engine/supervisor.py) rebuilds
  instead.  With timeout 0 and retries 0 and no injector, ``run`` is
  a plain passthrough call.

FAULT_SPEC grammar (``;``-separated rules)::

    rule   := [replica ":"] [site ":"] kind ["(" seconds ")"] trigger
    replica:= "r" N           rule applies only to fleet replica N
                              (default: every replica, independently)
    site   := prefill | prefill_chunk | chunk | fetch | batch | grow
            | handoff | swap | *
              (default *; prefill_chunk = one chunked-prefill window,
              handoff = a slot-insert flipping a prefilled/swapped
              stream live, swap = KV-tier gather/scatter/materialize
              traffic — both r18 sites, so older chunk@N schedules
              never renumber)
    kind   := transient | fatal | hang | oob | device_lost
    trigger:= "@" N ["+" M]   fire on matching dispatches N..N+M-1
            | "~" RATE        fire with probability RATE per dispatch
                              (seeded RNG: FAULT_SEED)

``seconds`` only applies to ``hang`` (default 3600); for
``device_lost`` the parenthesized arg is instead the SHARD ordinal
within the replica's TP group that died (default 0) — the fleet maps
it through the replica's device set to mark the global device lost.
Examples: ``chunk:fatal@5`` kills the 5th chunk dispatch;
``chunk:transient@2+3`` fails chunks 2-4 transiently;
``*:transient~0.05`` fails 5% of all dispatches;
``r1:chunk:fatal@3`` kills replica 1's 3rd chunk dispatch while every
other replica stays clean (replica-scoped chaos — engine/fleet.py);
``r0:chunk:device_lost(1)@4`` kills shard 1 of replica 0's TP group on
its 4th chunk — the whole group evacuates (one shard's arrays are
gone, so every collective on the group is dead) and the fleet retires
that device from future placements (engine/fleet.py).
``@N`` counters are per rule and count only dispatches at the rule's
site ON the rule's replica (each replica engine owns its own injector
with its own counters), so a schedule is reproducible run-to-run
regardless of thread timing.
"""

from __future__ import annotations

import logging
import re
import threading
import time

from ..utils import metrics

log = logging.getLogger(__name__)

SITES = ("prefill", "prefill_chunk", "chunk", "fetch", "batch", "grow",
         "handoff", "swap", "prep", "*")
KINDS = ("transient", "fatal", "hang", "oob", "device_lost")


class TransientDeviceError(Exception):
    """A dispatch failed in a way a retry can fix (flaky link, transport
    hiccup).  The watchdog retries these with backoff."""


class FatalDeviceError(Exception):
    """The device (state) is lost; retrying the same dispatch cannot
    succeed.  The supervisor checkpoints streams and rebuilds."""


class DeviceLostError(FatalDeviceError):
    """One physical device of the replica's placement died (chip
    failure, ICI link down).  Fatal like ``FatalDeviceError`` — but an
    in-place rebuild on the SAME placement cannot help (the device is
    gone), so the continuous loop escalates straight to group
    evacuation and the fleet retires the device from future
    placements.  ``device_index`` is the shard ordinal within the
    replica's device group (0 for single-device replicas)."""

    def __init__(self, msg: str, device_index: int = 0):
        super().__init__(msg)
        self.device_index = int(device_index)


class StateConsumedError(FatalDeviceError):
    """A dispatch that donates its state raised AFTER the runtime took
    the buffers: the arrays it would be retried on are deleted, so the
    error is fatal whatever its cause looked like (``__cause__`` keeps
    it).  The loop rebuilds the state and the streams resume by
    recompute — their KV went with the buffers, so no swap-out runs."""


class DispatchTimeoutError(Exception):
    """A dispatch exceeded ``DISPATCH_TIMEOUT_S``.  Classified fatal:
    the dispatch thread may be wedged forever, so recovery means a
    rebuild, not a retry against the same state."""


def is_transient(exc: BaseException) -> bool:
    return isinstance(exc, (TransientDeviceError, ConnectionError)) or bool(
        getattr(exc, "transient", False)
    )


def is_fatal_device(exc: BaseException) -> bool:
    # A real (non-injected) device loss carries a runtime-error type,
    # not FatalDeviceError — it is still fatal-classified so the
    # checkpoint-requeue path runs before the group evacuates.
    return isinstance(
        exc, (FatalDeviceError, DispatchTimeoutError)
    ) or is_device_loss(exc)


def is_consumed(tree) -> bool:
    """True when any array of ``tree`` was deleted (donated to a
    dispatch).  Host leaves (numpy) cannot be."""
    import jax

    return any(
        getattr(x, "is_deleted", bool)() for x in jax.tree.leaves(tree)
    )


def guard_donation(fn, donated):
    """``fn`` for the watchdog, where ``fn`` donates ``donated``: a
    failure that left ``donated`` consumed becomes
    ``StateConsumedError``, so ``Watchdog.run`` never retries it."""

    def call():
        try:
            return fn()
        except Exception as e:
            if is_consumed(donated):
                raise StateConsumedError(
                    f"dispatch consumed its state, then failed: "
                    f"{type(e).__name__}: {e}"
                ) from e
            raise

    return call


# Real runtimes surface a dead chip as an XlaRuntimeError (or peer)
# whose message names the loss; there is no dedicated exception type to
# isinstance against, so classification is textual — the patterns cover
# the strings PJRT/XLA emit for halted chips, dead ICI links, and
# DATA_LOSS-status collectives.
_DEVICE_LOSS_RE = re.compile(
    r"device\s+(?:is\s+)?lost|DATA_LOSS|device\s+.*halt|"
    r"ICI\s+link|peer\s+access\s+lost|device\s+in\s+an?\s+error\s+state",
    re.IGNORECASE,
)
_DEVICE_LOSS_TYPES = ("XlaRuntimeError", "JaxRuntimeError", "RpcError")


def is_device_loss(exc: BaseException) -> bool:
    """True when ``exc`` means a physical device (or its link) died —
    the injected ``DeviceLostError`` or a real runtime error whose type
    + message match the known device-loss shapes.  A device-loss is
    always ``is_fatal_device``-fatal too; this predicate only decides
    the ESCALATION (skip the in-place rebuild, evacuate the group)."""
    if isinstance(exc, DeviceLostError):
        return True
    if type(exc).__name__ in _DEVICE_LOSS_TYPES:
        return bool(_DEVICE_LOSS_RE.search(str(exc)))
    return False


class FaultRule:
    """One parsed FAULT_SPEC rule with its own dispatch counter."""

    __slots__ = ("site", "kind", "arg", "nth", "count", "rate", "seen",
                 "fired", "replica")

    def __init__(self, site: str, kind: str, arg: float,
                 nth: int = 0, count: int = 1, rate: float = 0.0,
                 replica: int | None = None):
        self.site = site
        self.kind = kind
        self.arg = arg
        self.nth = nth
        self.count = count
        self.rate = rate
        self.seen = 0
        self.fired = 0
        # None = the rule applies on every replica (each replica's own
        # injector counts it independently); an int scopes the rule to
        # that fleet replica only (engine/fleet.py).
        self.replica = replica

    def __repr__(self) -> str:  # shows up in logs when a fault fires
        trig = f"~{self.rate}" if self.rate else f"@{self.nth}+{self.count}"
        rep = f"r{self.replica}:" if self.replica is not None else ""
        return f"{rep}{self.site}:{self.kind}{trig}"


_RULE_RE = re.compile(
    r"^(?:r(?P<replica>\d+):)?"
    r"(?:(?P<site>[a-z_*]+):)?"
    r"(?P<kind>[a-z_]+)"
    r"(?:\((?P<arg>[0-9.]+)\))?"
    r"(?:@(?P<nth>\d+)(?:\+(?P<count>\d+))?|~(?P<rate>[0-9.]+))$"
)


def parse_spec(spec: str) -> list[FaultRule]:
    rules = []
    for part in spec.split(";"):
        part = part.strip()
        if not part:
            continue
        m = _RULE_RE.match(part)
        if m is None:
            raise ValueError(f"unparseable FAULT_SPEC rule {part!r}")
        site = m.group("site") or "*"
        kind = m.group("kind")
        if site not in SITES:
            raise ValueError(
                f"FAULT_SPEC site must be one of {SITES}, got {site!r}"
            )
        if kind not in KINDS:
            raise ValueError(
                f"FAULT_SPEC kind must be one of {KINDS}, got {kind!r}"
            )
        rate = float(m.group("rate") or 0.0)
        if not (0.0 <= rate <= 1.0):
            raise ValueError(f"FAULT_SPEC rate must be in [0, 1], got {rate}")
        rep = m.group("replica")
        # arg is hang seconds (default one hour) — except device_lost,
        # where it is the shard ordinal that dies (default shard 0).
        default_arg = 0.0 if kind == "device_lost" else 3600.0
        rules.append(FaultRule(
            site, kind,
            arg=float(m.group("arg") or default_arg),
            nth=int(m.group("nth") or 0),
            count=int(m.group("count") or 1),
            rate=rate,
            replica=int(rep) if rep is not None else None,
        ))
    return rules


class FaultInjector:
    """Deterministic fault schedule over the dispatch sites.

    Thread-safe: the trigger decision (counters + seeded RNG draw)
    happens under a lock; the fault action (raise/sleep) happens
    outside it so a hang never blocks other sites' bookkeeping."""

    def __init__(self, rules: list[FaultRule], seed: int = 0):
        import random

        self.rules = rules
        self._rng = random.Random(seed)
        self._lock = threading.Lock()

    @classmethod
    def from_spec(cls, spec: str | None, seed: int = 0,
                  replica: int = 0) -> "FaultInjector | None":
        """Build the injector for ONE engine: rules scoped to another
        replica (``rN:`` prefix) are dropped here, so a fleet schedule
        like ``r1:chunk:fatal@3`` kills replica 1 while replica 0's
        injector never even sees the rule."""
        if not spec:
            return None
        rules = [
            r for r in parse_spec(spec)
            if r.replica is None or r.replica == int(replica)
        ]
        return cls(rules, seed) if rules else None

    def fire(self, site: str) -> None:
        """Count one dispatch at ``site``; raise/sleep if a rule says
        so.  Called at the TOP of each guarded dispatch attempt, so
        watchdog retries re-roll the schedule like any real retry
        would re-touch the device."""
        hit = None
        with self._lock:
            for rule in self.rules:
                if rule.site != "*" and rule.site != site:
                    continue
                rule.seen += 1
                if rule.rate > 0.0:
                    trigger = self._rng.random() < rule.rate
                else:
                    trigger = rule.nth <= rule.seen < rule.nth + rule.count
                if trigger:
                    rule.fired += 1
                    hit = rule
                    break
        if hit is None:
            return
        log.warning("fault injected at %s: %r", site, hit)
        if hit.kind == "transient":
            raise TransientDeviceError(f"injected transient fault at {site}")
        if hit.kind == "fatal":
            raise FatalDeviceError(f"injected fatal device fault at {site}")
        if hit.kind == "device_lost":
            shard = int(hit.arg)
            raise DeviceLostError(
                f"injected device loss at {site} (group shard {shard})",
                device_index=shard,
            )
        if hit.kind == "oob":
            from .kv_blocks import OutOfBlocks

            raise OutOfBlocks(f"injected OutOfBlocks at {site}")
        # hang: sleep through the watchdog deadline (or, unsupervised,
        # stall the caller for the full duration — the failure mode
        # the watchdog exists to bound).
        time.sleep(hit.arg)


class Watchdog:
    """Monitored-deadline + transient-retry wrapper for one dispatch.

    ``run(site, fn)`` executes ``injector.fire(site)`` then ``fn()``;
    with ``timeout_s > 0`` the attempt runs on a fresh daemon thread
    and an overrun raises ``DispatchTimeoutError`` (the wedged thread
    is abandoned — its eventual result is discarded, and the engine
    rebuild replaces any state it touched).  Transient failures retry
    up to ``retries`` times with capped exponential backoff."""

    def __init__(self, model: str, timeout_s: float = 0.0, retries: int = 0,
                 backoff_s: float = 0.05, injector: FaultInjector | None = None,
                 recorder=None):
        self.model = model
        self.timeout_s = max(0.0, float(timeout_s))
        self.retries = max(0, int(retries))
        self.backoff_s = max(0.0, float(backoff_s))
        self.injector = injector
        # Optional flight recorder (utils/tracing.FlightRecorder): the
        # retry/timeout events land in the engine post-mortem ring.
        self.recorder = recorder
        self._passthrough = (
            self.injector is None and self.timeout_s <= 0 and self.retries <= 0
        )

    def run(self, site: str, fn):
        if self._passthrough:
            return fn()
        attempt = 0
        while True:
            try:
                return self._attempt(site, fn)
            except Exception as e:
                if is_transient(e) and attempt < self.retries:
                    metrics.DISPATCH_RETRIES.labels(
                        self.model, type(e).__name__
                    ).inc()
                    if self.recorder is not None:
                        self.recorder.event(
                            "dispatch_retry", site=site, attempt=attempt + 1,
                            error=f"{type(e).__name__}: {e}",
                        )
                    time.sleep(min(self.backoff_s * (2 ** attempt), 2.0))
                    attempt += 1
                    continue
                raise

    def _attempt(self, site: str, fn):
        def call():
            if self.injector is not None:
                self.injector.fire(site)
            return fn()

        if self.timeout_s <= 0:
            return call()
        box: dict = {}
        done = threading.Event()

        def worker():
            try:
                box["r"] = call()
            except BaseException as e:
                box["e"] = e
            finally:
                done.set()

        t = threading.Thread(
            target=worker, daemon=True, name=f"dispatch-{site}"
        )
        t.start()
        if not done.wait(self.timeout_s):
            metrics.DISPATCH_TIMEOUTS.labels(self.model).inc()
            if self.recorder is not None:
                self.recorder.event(
                    "dispatch_timeout", site=site, timeout_s=self.timeout_s
                )
            raise DispatchTimeoutError(
                f"{site} dispatch exceeded DISPATCH_TIMEOUT_S="
                f"{self.timeout_s}s"
            )
        if "e" in box:
            raise box["e"]
        return box.get("r")
