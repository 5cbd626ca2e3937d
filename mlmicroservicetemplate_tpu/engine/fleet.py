"""Replica fleet: N independent decode engines behind a health-gated
router with token-identical failover.

The r9 fault-tolerance layer made ONE engine survivable; this layer
removes the remaining single blast radius — one wedged loop or one
spent restart budget no longer takes down the whole listener (ROADMAP
item 3; λScale-style data-parallel serving, arXiv 2502.09922).

Topology: ``FLEET_REPLICAS`` fully independent replicas, each its own
``InferenceEngine`` (own fault injector — ``rN:``-scoped FAULT_SPEC
rules land on one replica only — own watchdog, own KV pool, own prefix
cache, own flight recorder), its own ``ContinuousDecodeLoop``, its own
``Supervisor`` and its own ``AdmissionController`` (per-replica
pool-authoritative ledgers; the fleet splits ``KV_BUDGET_MB`` evenly
so the replicas together honor one fleet budget).

Routing (scheduler/router.py): health → prefix affinity →
least-loaded, or round-robin under ``FLEET_ROUTE=rr``.

Health has two layers:

- The r9 **supervisor**: restart budget (optionally a sliding
  window — ``ENGINE_RESTART_WINDOW_S``) spent → the replica is dead.
- A per-replica **circuit breaker**: ``FLEET_BREAKER_N`` consecutive
  dispatch faults open it (routing avoids the replica while its own
  supervisor restarts churn); after half the eviction interval a
  half-open probe re-admits traffic, and one clean dispatch closes it
  again.  A breaker still open after ``FLEET_EVICT_S`` evicts the
  replica outright.

Failover — the robustness core: when a replica dies (restart budget
spent, loop-thread death, or breaker eviction) its loop checkpoints
EVERY pending and active stream at the delivered-token cursor
(``streams._evacuate``), frees the corpse's pool blocks and prefix
pins (the ledger drains to zero), and hands the checkpoints here; the
fleet re-queues each on a healthy replica (``adopt_stream``), where
the r7 recast/replay resume paths continue it **token-identically** —
a replica crash costs latency, never output.

``FLEET_REPLICAS=1`` (default) never constructs this class: the
single-replica path is bit-identical to the pre-fleet engine.

Elastic scaling (docs/autoscaling.md): when ``FLEET_MIN/MAX_REPLICAS``
open a range around ``FLEET_REPLICAS`` (which becomes the INITIAL
size), a ``ScalingGovernor`` (scheduler/policy.py) ticks every
``SCALE_PERIOD_S`` on the router's own load signals and drives
``scale_to``:

- **scale-UP** builds a fresh engine whose params broadcast from a
  healthy donor replica's already-placed device arrays (λScale — no
  checkpoint reload, no host re-upload; runtime/distributed.py is the
  multi-device seam), warms its executables, and admits it to routing
  only after a probe dispatch succeeds — a spawn that dies mid-build
  never sheds existing traffic because it was never routable;
- **scale-DOWN** drains the least-loaded replica inside
  ``DRAIN_GRACE_S`` (streams finish in place) or evacuates the rest
  through the r13 checkpoint machinery onto survivors,
  token-identically, then retires it;
- **rejoin**: a breaker-evicted replica is rebuilt through the same
  spawn path once it has been dead ``FLEET_EVICT_S`` — eviction makes
  a hole the governor repairs, not a permanent capacity loss;
- every event **rebalances** the fleet KV budget across the LIVE
  replicas (``AdmissionController.set_budget``), so a corpse's share
  returns to the survivors instead of stranding.

``FLEET_MAX_REPLICAS`` unset (or equal to ``FLEET_REPLICAS`` with
``FLEET_MIN`` too) keeps the fleet static: no governor object, no
scaler thread, bit-identical to the pre-elastic code.

Multi-chip placement (ISSUE 19; docs/tensor-parallel.md): when the
base engine sits on a TP group (``TP>1``) or ``FLEET_TP_GROUPS`` names
per-replica widths, the fleet becomes the unit-of-placement owner: it
CARVES the visible device list into disjoint groups — replica 0 keeps
the base engine's devices, every other replica gets its own fresh
group — and each group is one replica for every purpose (breaker,
eviction, KV-budget share, governor unit).  Scale events place whole
groups: ``_spawn_replica`` carves a free group (preferring a rejoining
corpse's old devices, so the placement-keyed ExecutableCache makes the
respawn compile-free), params broadcast donor→group over ICI
(``params_source="donor-ici"``; same-placement spawns alias,
``"donor-alias"``), and a ``device_lost`` fault retires the lost chip
from the carve pool — the group evacuates its streams through the
placement-agnostic checkpoint (a TP=2 stream resumes token-identically
on a TP=1 survivor and vice versa), then rejoin rebuilds the group on
the remaining healthy devices.  Without TP and without
``FLEET_TP_GROUPS`` the shared single-device placement (and every one
of its pins) is bit-identical to the pre-multichip fleet.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque

import numpy as np

from ..utils import locktrace, metrics

log = logging.getLogger(__name__)

#: fleet_breaker_state gauge values.
CLOSED, HALF_OPEN, OPEN, DEAD = 0, 1, 2, 3
_STATE_NAMES = {CLOSED: "closed", HALF_OPEN: "half_open",
                OPEN: "open", DEAD: "dead"}


def _parse_tp_groups(spec) -> tuple[int, ...] | None:
    """FLEET_TP_GROUPS="2,2,1" → (2, 2, 1): per-replica TP widths for
    multi-chip carving.  None/"" → None (widths default to the base
    engine's TP width).  utils/config.py validates the format; this
    re-parse keeps the fleet usable with duck-typed test configs."""
    if not spec:
        return None
    widths = tuple(int(w) for w in str(spec).split(",") if w.strip())
    if not widths or any(w < 1 for w in widths):
        raise ValueError(
            f"FLEET_TP_GROUPS must be comma-separated widths >= 1, "
            f"got {spec!r}"
        )
    return widths


class CircuitBreaker:
    """Consecutive-fault breaker for one replica.

    closed → (``threshold`` consecutive faults) → open → (half the
    eviction interval elapses) → half-open → one clean dispatch closes
    it / one more fault re-opens it.  ``open_elapsed`` measures from
    the FIRST transition out of closed, so flapping half-open probes
    cannot reset the eviction clock.  Thread-safe; ``clock`` is
    injectable for tests."""

    def __init__(self, threshold: int = 3, evict_s: float = 10.0,
                 clock=None):
        self.threshold = max(1, int(threshold))
        self.evict_s = max(0.0, float(evict_s))
        self.probe_after_s = self.evict_s / 2.0
        self._clock = clock if clock is not None else time.monotonic
        self._state = CLOSED
        self._streak = 0
        self.faults = 0  # lifetime, observability
        self._opened_at: float | None = None  # last open transition
        self._first_open_at: float | None = None  # eviction clock
        self._lock = threading.Lock()

    def record_fault(self) -> None:
        with self._lock:
            if self._state == DEAD:
                return
            now = self._clock()
            self.faults += 1
            self._streak += 1
            if self._state == HALF_OPEN or (
                self._state == CLOSED and self._streak >= self.threshold
            ):
                self._state = OPEN
                self._opened_at = now
                if self._first_open_at is None:
                    self._first_open_at = now
            elif self._state == OPEN:
                self._opened_at = now

    def record_ok(self) -> None:
        with self._lock:
            if self._state == DEAD:
                return
            self._streak = 0
            self._state = CLOSED
            self._opened_at = None
            self._first_open_at = None

    def mark_dead(self) -> None:
        with self._lock:
            self._state = DEAD

    def _state_locked(self) -> int:
        if (
            self._state == OPEN
            and self._opened_at is not None
            and self._clock() - self._opened_at >= self.probe_after_s
        ):
            self._state = HALF_OPEN
        return self._state

    @property
    def state(self) -> int:
        with self._lock:
            return self._state_locked()

    @property
    def state_name(self) -> str:
        return _STATE_NAMES[self.state]

    def allow(self) -> bool:
        """May the router send traffic here?  Closed always; half-open
        admits probe traffic (a clean dispatch closes the breaker, a
        fault re-opens it); open and dead never."""
        return self.state in (CLOSED, HALF_OPEN)

    def open_elapsed(self) -> float | None:
        """Seconds since the breaker FIRST left closed (None while
        closed) — the eviction clock."""
        with self._lock:
            if self._state == DEAD or self._first_open_at is None:
                return None
            return self._clock() - self._first_open_at

    def retry_eta_s(self) -> float:
        """Seconds until the next half-open probe window (the
        Retry-After guidance an all-dead fleet returns)."""
        with self._lock:
            st = self._state_locked()
            if st in (CLOSED, HALF_OPEN):
                return 0.0
            if st == DEAD or self._opened_at is None:
                return self.probe_after_s or 1.0
            return max(
                0.0, self._opened_at + self.probe_after_s - self._clock()
            )


class Replica:
    """One fleet member: engine + loop + supervisor + breaker.

    ``devices``/``width`` describe the member's placement — the global
    device ids its mesh covers and its TP width.  A multi-chip fleet
    carves these disjoint; single-device fleets honestly report every
    replica on the one shared device."""

    def __init__(self, rid: int, engine, cdl, supervisor, admission,
                 breaker: CircuitBreaker):
        self.id = rid
        self.engine = engine
        self.cdl = cdl
        self.supervisor = supervisor
        self.admission = admission
        self.breaker = breaker
        self.dead = False
        self.dead_cause: str | None = None
        self.dead_at: float | None = None  # rejoin clock (fleet clock)
        # Scale-down in progress: the router skips a draining replica
        # (no new work) while its loop finishes what it holds.
        self.draining = False
        placement = getattr(engine, "replicas", None)
        try:
            mesh = getattr(placement, "mesh", None)
            self.devices: tuple[int, ...] = tuple(
                int(d.id) for d in mesh.devices.flat
            ) if mesh is not None else ()
        except Exception:
            self.devices = ()
        self.width = int(getattr(placement, "tp_width", 1) or 1)

    def healthy(self) -> bool:
        return (
            not self.dead
            and not self.draining
            and not self.cdl.dead
            and not self.supervisor.failed
            and not self.cdl._stop.is_set()
            and self.breaker.allow()
        )

    def load(self) -> dict:
        cdl = self.cdl
        return {
            "active": len(cdl.active),
            "queued": cdl.queue.qsize(),
            "prefilling": len(cdl._prefilling),
            "swapping": len(getattr(cdl, "_swapping", ())),
            "kv_committed_bytes": self.admission.committed_bytes,
        }


class ReplicaFleet:
    """The fleet: construction, routing, health sweeps, failover."""

    def __init__(self, engine, cfg, clock=None, autoscale_thread=True,
                 bundle_factory=None):
        from ..scheduler.router import Router
        from .engine import InferenceEngine

        if getattr(cfg, "spec_continuous", False):
            raise ValueError(
                "FLEET_REPLICAS>1 does not compose with SPEC_CONTINUOUS "
                "(the spec load gate counts streams across one loop)"
            )
        base_placement = engine.replicas
        base_tp = int(getattr(base_placement, "tp_width", 1) or 1)
        base_dev = int(getattr(base_placement, "n_devices", 1) or 1)
        self._group_widths = _parse_tp_groups(
            getattr(cfg, "fleet_tp_groups", None)
        )
        # Multi-chip carving (ISSUE 19) activates when the base engine
        # IS one TP group, or FLEET_TP_GROUPS names widths explicitly.
        self.multichip = (
            (base_tp > 1 and base_dev == base_tp)
            or self._group_widths is not None
        )
        if self.multichip:
            if base_dev not in (1, base_tp):
                # A multi-REPLICA base mesh is still the shared-mesh
                # deadlock below — carving needs a base that is exactly
                # one group (single device or one TP group).
                raise ValueError(
                    "multi-chip fleet placement requires the base "
                    "engine on a single device or exactly one TP "
                    "group (REPLICAS=1)"
                )
        elif base_dev > 1:
            # Two engines dispatching sharded computations over ONE
            # shared mesh interleave their collectives (each engine has
            # its own pipeline semaphore, so nothing orders the
            # all-gathers) — a silent rendezvous deadlock.  Fail at
            # startup instead: fleet replicas each own a single-device
            # placement (REPLICAS=1) or — with TP>1 / FLEET_TP_GROUPS —
            # a carved TP group of their own.
            raise ValueError(
                "FLEET_REPLICAS>1 requires a single-device replica "
                "placement (set REPLICAS=1): independent engines must "
                "not interleave collectives over one shared mesh"
            )
        self.cfg = cfg
        self.model = engine.bundle.name
        self.n = max(1, int(getattr(cfg, "fleet_replicas", 1)))
        self._initial_n = self.n  # FLEET_REPLICAS; self.n tracks live+dead
        # Elastic bounds (docs/autoscaling.md): FLEET_REPLICAS is the
        # INITIAL size; 0 bounds collapse onto it (static fleet).
        self.min_r = int(getattr(cfg, "fleet_min_replicas", 0) or 0) or self.n
        self.max_r = int(getattr(cfg, "fleet_max_replicas", 0) or 0) or self.n
        self.elastic = self.min_r != self.n or self.max_r != self.n
        self.evict_s = float(getattr(cfg, "fleet_evict_s", 10.0) or 0.0)
        self._breaker_n = int(getattr(cfg, "fleet_breaker_n", 3) or 3)
        self.router = Router(getattr(cfg, "fleet_route", "least"))
        self._clock = clock if clock is not None else time.monotonic
        self._breaker_clock = clock
        self._lock = threading.Lock()
        # Scale events serialize on their own lock: a scale-down WAITS
        # on a draining loop whose evacuation callback takes ``_lock``
        # — holding ``_lock`` across the wait would deadlock.
        self._scale_lock = threading.Lock()
        # LOCKTRACE adjudication: the scale lock IS deliberately held
        # across the spawn's warm-probe dispatch — one scale event at
        # a time is the invariant, and nothing on the serving path
        # ever takes this lock (the governor thread and manual
        # scale_to are its only users), so a slow probe delays only
        # the next scale decision, never traffic.
        locktrace.allow_across_dispatch(self._scale_lock)
        self.failovers = 0
        self.scale_period_s = float(
            getattr(cfg, "scale_period_s", 0.5) or 0.5
        )
        # Streams on the Batcher's legacy per-stream path count against
        # every replica's MAX_STREAMS bound; the Batcher re-points this
        # at its own counter (spawned replicas inherit it through the
        # indirection in _wire_replica).
        self.external_active = lambda: 0
        # Multi-tenancy (tenancy/; set retroactively by the Batcher via
        # set_tenancy AFTER construction — the fleet boots first): ONE
        # shared TenantRegistry (fleet-wide quota ledger), per-replica
        # fair-share cursors and adapter pools.  None = tenancy off.
        self.tenancy: tuple | None = None

        # One fleet budget → per-replica pool-authoritative ledgers:
        # each replica admits against its own share of the LIVE split.
        self.budget_mb = float(getattr(cfg, "kv_budget_mb", 0.0) or 0.0)
        self.budget_bytes = int(self.budget_mb * 1e6)
        per_cfg = self._share_cfg(self.n)
        split = per_cfg is not cfg

        # Multi-chip carve state: disjoint per-replica device groups,
        # a placement cache keyed (width, group) — a same-group respawn
        # reuses the SAME placement object, so its ExecutableCache keys
        # match and the spawn is compile-free — a per-width bundle
        # cache, and the set of devices retired by device_lost faults.
        self._bundle_factory = bundle_factory
        self._bundles: dict[int, object] = {base_tp: engine.bundle}
        self._placements: dict[tuple, object] = {}
        self._param_spec = getattr(base_placement, "param_spec", None)
        self._default_width = base_tp if self.multichip else 1
        self.lost_devices: set[int] = set()
        boot_groups: list[tuple[int, ...]] = []
        if self.multichip:
            widths = self._group_widths or (base_tp,) * self.n
            if len(widths) != self.n:
                raise ValueError(
                    f"FLEET_TP_GROUPS names {len(widths)} groups but "
                    f"FLEET_REPLICAS={self.n} — one width per replica"
                )
            if widths[0] != base_tp:
                raise ValueError(
                    f"FLEET_TP_GROUPS[0]={widths[0]} must equal the "
                    f"base engine's TP width {base_tp} (replica 0 "
                    "keeps the base placement)"
                )
            base_group = tuple(
                int(d.id) for d in base_placement.mesh.devices.flat
            )
            self._placements[(base_tp, base_group)] = base_placement
            boot_groups.append(base_group)
            taken = set(base_group)
            import jax

            n_dev = len(jax.devices())
            ids = [int(d.id) for d in jax.devices()]
            if ids != list(range(n_dev)):
                # Groups are carved by POSITION in jax.devices() and
                # placed by indexing it (serving_tp_mesh), while the
                # base group above is read off as device IDS: the two
                # only agree when ids are positions.
                raise ValueError(
                    f"FLEET_TP_GROUPS needs device ids 0..{n_dev - 1} in "
                    f"jax.devices() order, got {ids}"
                )
            for w in widths[1:]:
                free = [d for d in range(n_dev) if d not in taken]
                if len(free) < w:
                    raise ValueError(
                        f"FLEET device carve needs {sum(widths)} "
                        f"devices for groups {widths}, only {n_dev} "
                        "visible — shrink the fleet or the TP width"
                    )
                grp = tuple(free[:w])
                taken.update(grp)
                boot_groups.append(grp)

        self.replicas: list[Replica] = []
        for r in range(self.n):
            if r == 0 and not (split and getattr(engine, "paged_kv", False)):
                # Reuse the already-built engine as replica 0 — unless
                # its paged pool was sized for the WHOLE fleet budget,
                # in which case it is rebuilt at the per-replica share.
                eng = engine
            else:
                # Boot replicas 1..R-1 broadcast params from replica
                # 0's already-placed arrays — same λScale path live
                # scale-ups use, so boot pays ONE host→device upload
                # total instead of R.  Multi-chip boots give each
                # replica its own carved placement (+ per-width bundle)
                # — the broadcast is a real ICI copy for them.
                if self.multichip and r > 0:
                    w = widths[r]
                    bnd = self._bundle_for(w)
                    placement = self._placement_for(w, boot_groups[r])
                else:
                    bnd, placement = engine.bundle, engine.replicas
                eng = InferenceEngine(
                    bnd, per_cfg, replicas=placement,
                    replica_id=r, donor_params=engine.params,
                )
            self.replicas.append(self._wire_replica(eng, per_cfg))
        # ONE host KV tier, ONE stream journal and ONE disk KV tier for
        # the whole fleet (docs/kv-tiering.md, runtime/durability.py):
        # host KV copies and journal records are replica-agnostic, so a
        # failed-over stream swap-resumes on its adopter and a demoted
        # prefix serves every replica.  The base engine carries the
        # journal (the Batcher attaches it before building the fleet);
        # only a replica-0 engine constructs a disk tier.
        self._shared_journal = getattr(engine, "journal", None)
        self._shared_disk = getattr(engine, "kv_disk", None) or getattr(
            self.replicas[0].engine, "kv_disk", None
        )
        self._shared_host = getattr(self.replicas[0].engine, "kv_host", None)
        # ONE SLO tracker for the whole fleet (r20, like the tiers):
        # burn rates are a fleet-level signal — a replica-local window
        # would let a degraded replica hide behind healthy siblings.
        self._shared_slo = getattr(self.replicas[0].cdl, "slo", None)
        for rep in self.replicas:
            self._share_tiers(rep)
        # Elastic scaling state: the governor decides, scale_tick acts.
        self._next_id = self.n
        self._spawning: dict | None = None
        self._scale_events: deque = deque(maxlen=64)
        self._scale_counts: dict[str, int] = {}
        self._last_scale_duration_s: float | None = None
        self.governor = None
        self._scaler_thread: threading.Thread | None = None
        self._scaler_stop = threading.Event()
        if self.elastic:
            from ..scheduler.policy import ScalingGovernor

            self.governor = ScalingGovernor(
                self.min_r, self.max_r,
                up_queue=float(getattr(cfg, "scale_up_queue", 2.0)),
                up_kv_frac=float(getattr(cfg, "scale_up_kv_frac", 0.85)),
                up_ttft_s=float(
                    getattr(cfg, "scale_up_ttft_ms", 0.0) or 0.0
                ) / 1e3,
                up_cooldown_s=float(
                    getattr(cfg, "scale_up_cooldown_s", 3.0)
                ),
                down_load=float(getattr(cfg, "scale_down_load", 0.25)),
                down_cooldown_s=float(
                    getattr(cfg, "scale_down_cooldown_s", 10.0)
                ),
                up_slo_burn=float(
                    getattr(cfg, "scale_up_slo_burn", 0.0) or 0.0
                ),
                clock=clock,
            )
            self._rebalance()
            if autoscale_thread:
                self._scaler_thread = threading.Thread(
                    target=self._scaler_run, name="fleet-scaler",
                    daemon=True,
                )
                self._scaler_thread.start()
        self._refresh_gauges()
        log.info(
            "replica fleet up: %d replicas%s, route=%s, breaker_n=%d, "
            "evict_s=%.1f", self.n,
            f" (elastic [{self.min_r}, {self.max_r}], "
            f"period={self.scale_period_s:g}s)" if self.elastic else "",
            self.router.policy, self._breaker_n, self.evict_s,
        )

    # -- construction helpers (boot + live scale-up) -------------------

    def _share_cfg(self, live_count: int):
        """Per-replica config at a ``live_count``-way budget split (the
        whole config when no budget is set or the fleet is one wide)."""
        if self.budget_bytes and live_count > 1:
            return self.cfg.model_copy(
                update={"kv_budget_mb": self.budget_mb / live_count}
            )
        return self.cfg

    def _bundle_for(self, width: int):
        """The model bundle for a ``width``-wide replica.  The base
        width reuses the boot bundle; other widths (a TP=1 spare next
        to TP=2 groups) build once via ``bundle_factory`` — or, when
        none was injected, through the model registry with ``TP``
        overridden — and cache for every later spawn, so a serve-time
        respawn never rebuilds (or re-reads) a bundle."""
        width = int(width)
        bnd = self._bundles.get(width)
        if bnd is None:
            if self._bundle_factory is not None:
                bnd = self._bundle_factory(width)
            else:
                from ..models.registry import build_model

                bnd = build_model(
                    self.cfg.model_copy(update={"tp": width})
                )
            self._bundles[width] = bnd
        return bnd

    def _placement_for(self, width: int, group: tuple[int, ...]):
        """The placement object for one carved group — cached so a
        same-group respawn gets the SAME object (identical
        ExecutableCache placement keys → zero serve-time compiles)."""
        key = (int(width), tuple(group))
        placement = self._placements.get(key)
        if placement is None:
            if int(width) <= 1:
                import jax

                from ..parallel.mesh import ReplicaSet, make_mesh

                placement = ReplicaSet(make_mesh(
                    1, devices=[jax.devices()[group[0]]]
                ))
            else:
                from ..parallel.mesh import TensorParallelSet
                from ..parallel.tpserve import serving_tp_mesh

                if self._param_spec is None:
                    raise ValueError(
                        "cannot build a TP group placement without the "
                        "base engine's param spec (base must be TP)"
                    )
                placement = TensorParallelSet(
                    serving_tp_mesh(int(width), 1, group),
                    self._param_spec,
                )
            self._placements[key] = placement
        return placement

    def _carve_group(self, width: int, prefer=None):
        """Pick ``width`` free healthy devices for a new group: devices
        held by non-dead replicas and devices retired by device_lost
        faults are off the table.  ``prefer`` (a corpse's old group) is
        reused when fully free — that is what keeps a same-placement
        respawn on cached executables.  None when the host cannot seat
        the group."""
        import jax

        n_dev = len(jax.devices())
        used: set[int] = set(self.lost_devices)
        for r in self.replicas:
            if not r.dead:
                used.update(r.devices)
        if prefer is not None:
            prefer = tuple(prefer)
            if len(prefer) == int(width) and not used.intersection(prefer):
                return prefer
        free = [d for d in range(n_dev) if d not in used]
        if len(free) < int(width):
            return None
        return tuple(free[:int(width)])

    def _free_group_count(self) -> int:
        """How many default-width groups the free healthy devices can
        seat — the governor's ``free_groups`` signal (an "up" with no
        seatable group returns ``(None, "no_devices")`` instead of
        burning a doomed spawn per tick)."""
        import jax

        n_dev = len(jax.devices())
        used: set[int] = set(self.lost_devices)
        for r in self.replicas:
            if not r.dead:
                used.update(r.devices)
        free = sum(1 for d in range(n_dev) if d not in used)
        return free // max(1, self._default_width)

    def _wire_replica(self, eng, per_cfg) -> Replica:
        """Loop + supervisor + admission + breaker around one engine —
        the same wiring for boot replicas and live spawns."""
        from ..scheduler.admission import AdmissionController
        from .streams import ContinuousDecodeLoop
        from .supervisor import Supervisor

        cdl = ContinuousDecodeLoop(eng, per_cfg)
        sup = Supervisor(per_cfg, recorder=eng.flight)
        cdl.supervisor = sup
        adm = AdmissionController(per_cfg, eng)
        cdl.admission = adm
        breaker = CircuitBreaker(
            self._breaker_n, self.evict_s, clock=self._breaker_clock
        )
        rep = Replica(int(eng.replica_id), eng, cdl, sup, adm, breaker)
        cdl.failover = self._failover_cb(rep)
        cdl.on_fault = self._on_fault_cb(rep)
        cdl.on_ok = breaker.record_ok
        cdl.external_active = lambda: self.external_active()
        if self.tenancy is not None:
            self._apply_tenancy(rep)
        return rep

    def set_tenancy(self, registry, pool, default_weight: float = 1.0
                    ) -> None:
        """Attach the tenancy subsystem (Batcher boot): the SHARED
        registry backs every replica's quota gate (one fleet-wide
        ledger), while fair-share virtual-time cursors and adapter
        device stacks are per replica — a replica's dequeue order and
        LoRA residency are its own.  Applies to live replicas AND every
        replica spawned later (``_wire_replica``)."""
        self.tenancy = (registry, pool, float(default_weight))
        for rep in self.replicas:
            self._apply_tenancy(rep)

    def _apply_tenancy(self, rep: Replica) -> None:
        registry, pool, default_w = self.tenancy
        if registry is not None:
            from ..tenancy.fairshare import WeightedFairShare

            rep.admission.set_tenants(registry)
            rep.cdl.tenants = registry
            rep.cdl.queue.set_fairshare(
                WeightedFairShare(registry.weights(), default_w)
            )
        if pool is not None:
            from ..tenancy.adapters import AdapterPool

            if getattr(rep.cdl, "spec", False):
                raise ValueError(
                    "ADAPTER_DIR does not compose with SPEC_CONTINUOUS"
                )
            # Per-replica device stacks over the ONE host dict (loaded
            # once at boot; replicas never re-read ADAPTER_DIR).
            rep.cdl.adapters = AdapterPool(
                pool.host, slots=pool.n_slots, model=pool.model
            )

    def _share_tiers(self, rep: Replica) -> None:
        """Point one replica's engine at the fleet-shared host tier,
        journal and disk tier."""
        if self._shared_host is not None:
            rep.engine.kv_host = self._shared_host
        if getattr(rep.engine, "journal", None) is None:
            rep.engine.journal = self._shared_journal
        if self._shared_slo is not None:
            rep.cdl.slo = self._shared_slo
        old = getattr(rep.engine, "kv_disk", None)
        if old is not None and old is not self._shared_disk:
            # A rebuilt replica-0 engine (split-budget pool) built its
            # own tier on the SAME directory — two index handles would
            # corrupt each other; the base's wins.
            old.close()
        rep.engine.kv_disk = self._shared_disk

    # -- health --------------------------------------------------------

    def healthy_replicas(self) -> list[Replica]:
        return [r for r in self.replicas if r.healthy()]

    def live_replicas(self) -> list[Replica]:
        """Replicas that count toward capacity (not dead, not on their
        way out) — the budget-split denominator and the governor's
        ``live`` signal.  A breaker-open replica is still LIVE (its
        supervisor is churning restarts; routing just avoids it)."""
        return [r for r in self.replicas if not r.dead and not r.draining]

    @property
    def degraded(self) -> bool:
        """Some (not all) replicas are dead: still serving, at reduced
        capacity — batch-class sheds first, /readyz stamps
        X-Fleet-Degraded."""
        dead = sum(1 for r in self.replicas if r.dead)
        return 0 < dead < len(self.replicas)

    @property
    def all_dead(self) -> bool:
        return not self.healthy_replicas()

    def retry_after_s(self) -> float:
        """Retry-After guidance for an all-dead fleet: the SOONER of
        the nearest breaker half-open ETA (plus any supervisor window
        slot that frees earlier) and — under elastic scaling — the
        governor's replacement spin-up ETA: a dead replica rebuilds
        ``FLEET_EVICT_S`` after its death, within one governor period
        (docs/autoscaling.md)."""
        etas = []
        for r in self.replicas:
            etas.append(r.breaker.retry_eta_s())
            w = r.supervisor.retry_eta_s()
            if w > 0:
                etas.append(w)
            if self.elastic and r.dead and r.dead_at is not None:
                rejoin = max(0.0, r.dead_at + self.evict_s - self._clock())
                etas.append(rejoin + self.scale_period_s)
        positive = [e for e in etas if e > 0]
        return max(1.0, min(positive)) if positive else 1.0

    def sweep(self) -> None:
        """Evict replicas whose breaker sat open past FLEET_EVICT_S:
        their streams hand over at the loop's next iteration top.
        Called on every route, health probe and status read — no
        background thread needed (a faulting replica also drives its
        own supervisor/failover path from inside)."""
        for rep in self.replicas:
            if rep.dead:
                continue
            el = rep.breaker.open_elapsed()
            if el is None or el < self.evict_s:
                continue
            t = rep.cdl._thread
            if t is not None and t.is_alive() and not rep.cdl.dead:
                rep.cdl.request_evacuation("evicted")
            else:
                # Nothing live to hand over: just retire it.
                self._mark_dead(rep, "evicted")
        self._refresh_gauges()

    def _refresh_gauges(self) -> None:
        live = draining = evicted = 0
        for rep in self.replicas:
            metrics.FLEET_BREAKER.labels(self.model, str(rep.id)).set(
                DEAD if rep.dead else rep.breaker.state
            )
            metrics.FLEET_REPLICA_DEVICES.labels(
                self.model, str(rep.id)
            ).set(0 if rep.dead else len(rep.devices))
            if rep.dead:
                evicted += 1
            elif rep.draining:
                draining += 1
            else:
                live += 1
        for state, count in (
            ("live", live), ("draining", draining), ("evicted", evicted),
            ("spawning", 1 if self._spawning is not None else 0),
        ):
            metrics.FLEET_REPLICAS.labels(self.model, state).set(count)

    # -- routing -------------------------------------------------------

    @property
    def max_prompt(self) -> int:
        return self.replicas[0].cdl.max_prompt

    def _shed(self, reason: str) -> None:
        metrics.SHED.labels(self.model, reason).inc()

    def submit_stream(self, feats: dict):
        """Route one stream: health-gate, degraded policy, then the
        router's ordering with shed fall-through (a replica at its own
        queue bound does not fail the request while a sibling has
        room)."""
        from ..scheduler.policy import BATCH, QueueFullError

        self.sweep()
        healthy = self.healthy_replicas()
        if not healthy:
            self._shed("fleet_down")
            raise QueueFullError(
                "every fleet replica is dead",
                reason="fleet_down", retry_after_s=self.retry_after_s(),
            )
        if self.degraded:
            # Degraded capacity goes to the interactive class first:
            # batch work sheds with honest Retry-After guidance.
            klass, _ = healthy[0].admission.classify(feats)
            if klass == BATCH:
                self._shed("degraded")
                raise QueueFullError(
                    "fleet degraded (dead replica): batch class sheds "
                    "first", reason="degraded",
                    retry_after_s=self.retry_after_s(),
                )
        last_err = None
        for rep in self.router.order(healthy, feats):
            try:
                return rep.cdl.submit_stream(feats)
            except (QueueFullError, RuntimeError) as e:
                # QueueFullError: this replica is at its own bound —
                # fall through to a sibling with room.  RuntimeError:
                # the replica died between the health check and the
                # submit (its loop refuses new streams); same answer.
                last_err = e
        raise last_err

    def pick_batch_replica(self, feats: dict):
        """Route one unary ``/predict`` batch dispatch: the same health
        gate + router ordering streams get (ROADMAP item 3 leftover —
        the batch path used to run on the base engine, bypassing
        health gating and least-loaded placement).  Returns a healthy
        ``Replica``; raises ``QueueFullError(fleet_down)`` when none
        remain.  The caller reports the dispatch outcome through
        ``rep.breaker`` so batch faults open the breaker too."""
        from ..scheduler.policy import QueueFullError

        self.sweep()
        healthy = self.healthy_replicas()
        if not healthy:
            self._shed("fleet_down")
            raise QueueFullError(
                "every fleet replica is dead",
                reason="fleet_down", retry_after_s=self.retry_after_s(),
            )
        for rep in self.router.order(healthy, feats):
            return rep
        return healthy[0]

    # -- failover ------------------------------------------------------

    def _on_fault_cb(self, rep: Replica):
        def on_fault():
            rep.breaker.record_fault()
            self._refresh_gauges()
        return on_fault

    def _mark_dead(self, rep: Replica, cause: str) -> None:
        rep.dead = True
        rep.dead_cause = cause
        rep.dead_at = self._clock()  # the rejoin clock starts here
        rep.breaker.mark_dead()
        # A corpse's KV-budget share returns to the survivors instead
        # of stranding with it (elastic fleets only — static split
        # semantics stay bit-identical).
        self._rebalance()

    def _note_lost_device(self, rep: Replica, exc) -> None:
        """Map a device-loss fault onto the global device(s) to retire
        from future carves.  Injected faults name the dead shard
        (``DeviceLostError.device_index``); a real runtime error that
        doesn't is attributed to the WHOLE group — honest conservatism:
        better to strand a maybe-healthy chip than respawn onto a dead
        one.  Caller holds ``_lock``."""
        if not rep.devices:
            return
        idx = getattr(exc, "device_index", None)
        if idx is not None and 0 <= int(idx) < len(rep.devices):
            lost = [rep.devices[int(idx)]]
        else:
            lost = list(rep.devices)
        self.lost_devices.update(lost)
        log.warning(
            "replica %d device loss: retiring device(s) %s from the "
            "carve pool (lost total: %s)",
            rep.id, lost, sorted(self.lost_devices),
        )

    def _failover_cb(self, rep: Replica):
        """The callback ``streams._evacuate`` invokes with the dead
        replica's stream checkpoints (on the dying loop's thread)."""

        def failover(streams, exc, cause):
            with self._lock:
                self._mark_dead(rep, cause)
                self.failovers += 1
                if cause == "device_lost":
                    self._note_lost_device(rep, exc)
            metrics.FLEET_FAILOVERS.labels(
                self.model, str(rep.id), cause
            ).inc()
            healthy = self.healthy_replicas()
            moved = lost = 0
            j = getattr(rep.engine, "journal", None)
            for st in streams:
                target = self.router.pick_adopter(healthy)
                if target is None:
                    # WRITE-AHEAD terminal record before the consumer
                    # sees the error: without it a restart's journal
                    # replay resurrects a stream its client already
                    # watched die (the client saw an error, the journal
                    # still said "incomplete").
                    if j is not None and st.rid and not st.done_journaled:
                        j.done(st.rid)
                        st.done_journaled = True
                    st.emit(
                        exc if isinstance(exc, Exception)
                        else RuntimeError(f"replica {rep.id} died: {exc}")
                    )
                    lost += 1
                    continue
                target.cdl.adopt_stream(st)
                moved += 1
            if moved:
                metrics.STREAMS_RECOVERED.labels(
                    self.model, str(rep.id), "failover"
                ).inc(moved)
            if lost:
                metrics.STREAMS_LOST.labels(
                    self.model, str(rep.id), "no_replica"
                ).inc(lost)
            self._refresh_gauges()
            log.warning(
                "replica %d failover (%s): %d stream(s) re-routed, "
                "%d lost, %d healthy replica(s) remain",
                rep.id, cause, moved, lost, len(healthy),
            )

        return failover

    # -- elastic scaling (docs/autoscaling.md) -------------------------

    def _rebalance(self) -> None:
        """Re-split the fleet KV budget across the LIVE replicas.
        Elastic fleets only — the static boot split is physical (each
        pool sized at budget/R) and must stay bit-identical."""
        if not self.elastic or not self.budget_bytes:
            return
        live = self.live_replicas()
        if not live:
            return
        share = self.budget_bytes // len(live)
        for rep in live:
            rep.admission.set_budget(share)

    def _record_scale(self, direction: str, cause: str, rid: int,
                      t0: float, breakdown: dict | None = None) -> None:
        dt = time.monotonic() - t0
        self._last_scale_duration_s = dt
        metrics.FLEET_SCALE_EVENTS.labels(self.model, direction, cause).inc()
        metrics.FLEET_SCALE_DURATION.labels(self.model, direction).observe(dt)
        key = f"{direction}:{cause}"
        self._scale_counts[key] = self._scale_counts.get(key, 0) + 1
        event = {
            "dir": direction, "cause": cause, "replica": rid,
            "duration_s": round(dt, 3),
        }
        if breakdown:
            # Scale-up latency attribution: where the spin-up wall
            # went — engine build + donor broadcast, loop warm, probe
            # dispatch, budget rebalance — plus the XLA compiles the
            # whole event paid
            # (zero once a sibling replica populated the
            # ExecutableCache; docs/compilation.md).
            event["breakdown"] = {
                k: (round(v, 3) if isinstance(v, float) else v)
                for k, v in breakdown.items()
            }
        self._scale_events.append(event)

    def _probe(self, rep: Replica) -> None:
        """One real dispatch through the spawned engine BEFORE it joins
        routing: collate a minimal prompt, run the fused start and
        fetch the tokens under the dispatch guard (site ``chunk``, so a
        replica-scoped chaos schedule can kill the spawn here).  Raises
        on any fault — the caller discards the replica."""
        import jax

        eng = rep.engine
        s = min(eng.seq_buckets)
        feats = {"input_ids": np.ones(s, np.int32), "length": np.int32(s)}

        def go():
            with eng._lock:
                ids, mask, _ = eng._collate_text([feats])
                sp, _ = eng._collate_sample([feats], ids.shape[0])
                ids, mask = eng.replicas.place_batch(ids, mask)
                _state, toks = eng._start(
                    eng.params, ids, mask, sp,
                    eng.max_decode_len, eng.chunk_tokens, False,
                )
                return jax.device_get(toks)

        eng.dispatch_guard("chunk", go)
        rep.breaker.record_ok()

    def _spawn_replica(self, cause: str, reuse_id: int | None = None,
                       replace: Replica | None = None) -> Replica | None:
        """Build, warm and probe one new replica; admit it to routing
        only on success.  Params broadcast from a live donor's placed
        arrays (λScale) — never a checkpoint reload, never a fresh
        host upload while any replica holds the params.  Returns the
        admitted Replica, or None when the spawn failed (existing
        traffic is untouched either way: the spawn was never
        routable)."""
        from ..runtime.compile_cache import (
            CompileWindow,
            note_warm_phase,
        )
        from .engine import InferenceEngine

        t0 = time.monotonic()
        donor = next((r for r in self.replicas if r.healthy()), None)
        donor_eng = donor.engine if donor is not None \
            else self.replicas[0].engine
        rid = reuse_id if reuse_id is not None else self._next_id
        per_cfg = self._share_cfg(len(self.live_replicas()) + 1)
        # Multi-chip: seat the new replica on its own device group
        # BEFORE building anything.  A rejoin prefers the corpse's old
        # group (same placement object → compile-free respawn); a
        # device_lost corpse's group contains a retired chip, so the
        # carve falls through to fresh devices.  No seatable group →
        # no spawn, loudly (the governor keeps the hole on its books
        # and retries as devices free up).
        group = None
        width = self._default_width
        if self.multichip:
            if replace is not None:
                width = replace.width
            prefer = (
                tuple(replace.devices)
                if replace is not None and replace.width == width else None
            )
            group = self._carve_group(width, prefer)
            if group is None:
                log.warning(
                    "scale-up blocked (replica %d, cause=%s): no free "
                    "group of %d device(s) (lost=%s)",
                    rid, cause, width, sorted(self.lost_devices),
                )
                self._record_scale("up", "no_devices", rid, t0)
                return None
        self._spawning = {"replica": rid, "cause": cause}
        self._refresh_gauges()
        # Spin-up latency breakdown (compile vs probe vs rebalance —
        # ISSUE 14): each phase timed, plus the XLA compiles the whole
        # spawn paid via jax.monitoring.  With the ExecutableCache
        # populated by any sibling replica, xla_compiles is ZERO and
        # warm_s collapses to dispatch time (the second-spawn pin in
        # tests/test_compile_cache.py).
        breakdown: dict = {}
        try:
            with CompileWindow() as cw:
                t = time.monotonic()
                if self.multichip:
                    spawn_bundle = self._bundle_for(width)
                    spawn_placement = self._placement_for(width, group)
                else:
                    spawn_bundle = donor_eng.bundle
                    spawn_placement = donor_eng.replicas
                eng = InferenceEngine(
                    spawn_bundle, per_cfg, replicas=spawn_placement,
                    replica_id=rid, donor_params=donor_eng.params,
                )
                rep = self._wire_replica(eng, per_cfg)
                self._share_tiers(rep)
                breakdown["build_s"] = time.monotonic() - t
                note_warm_phase(self.model, "spawn_build",
                                breakdown["build_s"])
                t = time.monotonic()
                # Fast warm (docs/compilation.md): the donor's loop
                # already populated the ExecutableCache, so the spawn
                # skips the warm-dispatch grid and adopts the donor's
                # RTT calibration; no donor (first boot) = full warm.
                rep.cdl.warm_spawn(donor.cdl if donor is not None
                                   else None)
                breakdown["warm_s"] = time.monotonic() - t
                note_warm_phase(self.model, "spawn_warm",
                                breakdown["warm_s"])
                t = time.monotonic()
                self._probe(rep)
                breakdown["probe_s"] = time.monotonic() - t
                note_warm_phase(self.model, "spawn_probe",
                                breakdown["probe_s"])
            breakdown["compile_s"] = cw.seconds
            breakdown["xla_compiles"] = cw.compiles
        except Exception as e:
            # A mid-scale-up death (probe fault, OOM at warm) aborts
            # JUST the spawn: nothing was routed here yet, so existing
            # traffic never sheds.  The governor retries next tick.
            log.warning(
                "scale-up spawn failed (replica %d, cause=%s): %s: %s",
                rid, cause, type(e).__name__, e,
            )
            self._spawning = None
            self._record_scale("up", "spawn_failed", rid, t0)
            self._refresh_gauges()
            return None
        self._spawning = None
        t = time.monotonic()
        with self._lock:
            if replace is not None and replace in self.replicas:
                # Rejoin: the rebuilt replica takes the corpse's seat
                # (and id — bounded metric labels, restored KV share).
                self.replicas = [
                    rep if r is replace else r for r in self.replicas
                ]
            else:
                self.replicas = self.replicas + [rep]
            self.n = len(self.replicas)
            if reuse_id is None:
                self._next_id = max(self._next_id, rid + 1)
        self._rebalance()
        breakdown["rebalance_s"] = time.monotonic() - t
        self._record_scale("up", cause, rid, t0, breakdown)
        self._refresh_gauges()
        log.info(
            "scale-up: replica %d admitted (cause=%s, params=%s, "
            "devices=%s, %.2fs) — fleet now %d live", rid, cause,
            rep.engine.params_source, list(rep.devices),
            time.monotonic() - t0, len(self.live_replicas()),
        )
        return rep

    def _scale_down(self, cause: str) -> Replica | None:
        """Retire the least-loaded live replica: drain it inside
        DRAIN_GRACE_S (streams finish in place, token-identically), or
        evacuate the stragglers through the r13 checkpoint machinery
        onto the survivors.  Replica id 0 is never retired — its engine
        anchors the shared journal/tier objects and the Batcher's
        introspection.  Returns the retired Replica or None."""
        from ..scheduler.router import replica_load

        live = self.live_replicas()
        floor = max(1, self.min_r if self.elastic else 1)
        candidates = [r for r in live if r.id != 0]
        if len(live) <= floor or not candidates:
            return None
        rep = min(candidates, key=replica_load)
        t0 = time.monotonic()
        rep.draining = True
        self._refresh_gauges()
        grace = float(getattr(self.cfg, "drain_grace_s", 30.0) or 0.0)
        deadline = t0 + grace
        thread = rep.cdl._thread
        started = thread is not None and thread.is_alive()
        while started and time.monotonic() < deadline:
            if rep.cdl.idle():
                break
            time.sleep(0.02)
        if not started or rep.cdl.idle():
            # Clean drain: nothing held, the loop just stops.
            rep.cdl.stop()
            with self._lock:
                rep.dead = True
                rep.dead_cause = cause
                rep.breaker.mark_dead()
        else:
            # Grace expired with streams still live: checkpoint-and-
            # adopt them onto the survivors (token-identical — the r13
            # failover core), then the loop stops itself.
            rep.cdl.request_evacuation("scale_down")
            t = rep.cdl._thread
            if t is not None:
                t.join(timeout=grace + 5.0)
        self._retire(rep, cause, t0)
        return rep

    def _retire(self, rep: Replica, cause: str, t0: float) -> None:
        with self._lock:
            self.replicas = [r for r in self.replicas if r is not rep]
            self.n = len(self.replicas)
        self._rebalance()
        self._record_scale("down", cause, rep.id, t0)
        self._refresh_gauges()
        pool = getattr(rep.engine, "kv_pool", None)
        log.info(
            "scale-down: replica %d retired (cause=%s, %.2fs, pool "
            "used=%s) — fleet now %d live", rep.id, cause,
            time.monotonic() - t0,
            pool.used_blocks if pool is not None else "n/a",
            len(self.live_replicas()),
        )

    def _maybe_rejoin(self) -> None:
        """Rebuild breaker-evicted / budget-spent replicas through the
        spawn path once they have been dead FLEET_EVICT_S — eviction
        opens a hole the governor repairs, not a permanent loss."""
        if not self.elastic:
            return
        now = self._clock()
        for rep in list(self.replicas):
            if not rep.dead or rep.dead_at is None:
                continue
            if now - rep.dead_at < self.evict_s:
                continue
            if len(self.live_replicas()) >= self.max_r:
                break
            if self._spawn_replica("rejoin", reuse_id=rep.id,
                                   replace=rep) is None:
                break  # retry next tick

    def _load_snapshot(self) -> dict:
        """The governor's inputs, from the router's own load signals:
        queue depths, slot occupancy, committed-KV fraction of the live
        budget, and the decode loops' TTFT EWMA."""
        live = self.live_replicas()
        queued = sum(r.cdl.queue.qsize() for r in live)
        active = sum(
            len(r.cdl.active) + len(r.cdl._prefilling)
            + len(r.cdl._swapping)
            for r in live
        )
        slots = max((r.cdl.max_streams for r in live), default=1)
        kv_frac = 0.0
        if self.budget_bytes:
            kv_frac = sum(
                r.admission.committed_bytes for r in live
            ) / self.budget_bytes
        elif live and live[0].admission.paged \
                and live[0].admission.pool is not None:
            total = sum(r.admission.ledger_blocks() for r in live)
            used = sum(r.admission.pool.used_blocks for r in live)
            kv_frac = used / total if total else 0.0
        ttft = max((r.cdl.ttft_ewma_s for r in live), default=0.0)
        # SLO burn (r20): the shared tracker's worst fast-window burn
        # across every enabled objective — 0.0 with no objectives set,
        # so the pre-SLO governor inputs are bit-identical by default.
        slo_burn = (
            self._shared_slo.worst_burn()
            if self._shared_slo is not None else 0.0
        )
        snap = {
            "live": len(live), "queued": queued, "active": active,
            "slots": slots, "kv_frac": kv_frac, "ttft_ewma_s": ttft,
            "slo_burn": slo_burn,
        }
        if self.multichip:
            # Governor scales in whole groups: an "up" only makes sense
            # while the host can seat another default-width group.
            snap["free_groups"] = self._free_group_count()
        return snap

    def scale_tick(self) -> None:
        """One governor period: sweep breaker evictions, rebuild
        rejoin-due corpses, then act on the governor's load decision.
        The scaler thread calls this every SCALE_PERIOD_S; tests may
        call it directly."""
        if not self.elastic:
            return
        if self.draining:
            # SIGTERM drain in progress: the fleet is winding down —
            # spawning would waste the grace window and retiring would
            # race the drain's own quiescence wait.
            return
        with self._scale_lock:
            self.sweep()
            self._maybe_rejoin()
            snap = self._load_snapshot()
            direction, cause = self.governor.decide(**snap)
            if direction == "up":
                if self._spawn_replica(cause) is not None:
                    self.governor.note_event("up")
            elif direction == "down":
                if self._scale_down(cause) is not None:
                    self.governor.note_event("down")

    def scale_to(self, target: int, cause: str = "manual") -> int:
        """Drive the live replica count to ``target`` (clamped to the
        elastic bounds when elastic).  Returns the live count."""
        if self.elastic:
            target = max(self.min_r, min(int(target), self.max_r))
        else:
            target = max(1, int(target))
        with self._scale_lock:
            while len(self.live_replicas()) < target:
                if self._spawn_replica(cause) is None:
                    break
            while len(self.live_replicas()) > target:
                if self._scale_down(cause) is None:
                    break
        return len(self.live_replicas())

    def _scaler_run(self) -> None:
        while not self._scaler_stop.wait(self.scale_period_s):
            try:
                self.scale_tick()
            except Exception:  # pragma: no cover - defensive
                log.exception("fleet scale tick failed")

    def scaling_status(self) -> dict:
        """/status.fleet.scaling: bounds, live count, governor clocks,
        recent events — the operator view of why the fleet is (not)
        moving."""
        out = {
            "elastic": self.elastic,
            "initial": self._initial_n,
            "min": self.min_r,
            "max": self.max_r,
            "live": len(self.live_replicas()),
            "in_progress": self._spawning,
            "draining": [r.id for r in self.replicas if r.draining],
            "last_duration_s": (
                round(self._last_scale_duration_s, 3)
                if self._last_scale_duration_s is not None else None
            ),
            "events": self._scale_counts,
            "recent": list(self._scale_events)[-8:],
        }
        if self.governor is not None:
            out["governor"] = self.governor.status()
            out["signals"] = {
                k: (round(v, 4) if isinstance(v, float) else v)
                for k, v in self._load_snapshot().items()
            }
        return out

    # -- lifecycle -----------------------------------------------------

    def warm(self) -> None:
        """Boot warm: replica 0 pays the full warm (compiling every
        executable INTO the shared cache); replicas 1..R-1 fast-warm
        from it — same λScale economics as a live spawn."""
        donor = None
        for rep in self.replicas:
            if donor is None:
                rep.cdl.warm()
                donor = rep
            else:
                rep.cdl.warm_spawn(donor.cdl)

    def begin_drain(self) -> None:
        for rep in self.replicas:
            rep.admission.draining = True

    @property
    def draining(self) -> bool:
        return any(r.admission.draining for r in self.replicas)

    def admitted(self) -> int:
        return sum(r.cdl._admitted for r in self.replicas)

    def pending_work(self) -> int:
        return sum(
            r.cdl._admitted + len(r.cdl._inflight_chunks)
            for r in self.replicas
        )

    def stop(self) -> None:
        if self._scaler_thread is not None:
            self._scaler_stop.set()
            self._scaler_thread.join(timeout=10)
            self._scaler_thread = None
        for rep in self.replicas:
            rep.cdl.stop()

    # -- observability -------------------------------------------------

    @staticmethod
    def _mesh_shape(rep: Replica) -> dict:
        """Per-replica mesh topology for /status.fleet ({} for
        placement-less duck-typed test engines)."""
        mesh = getattr(getattr(rep.engine, "replicas", None), "mesh", None)
        try:
            return {a: int(n) for a, n in mesh.shape.items()}
        except Exception:
            return {}

    def status(self) -> dict:
        self.sweep()
        healthy = self.healthy_replicas()
        return {
            "replicas": self.n,
            "route": self.router.policy,
            "healthy": len(healthy),
            "dead": sum(1 for r in self.replicas if r.dead),
            "degraded": self.degraded,
            "failovers": self.failovers,
            "multichip": self.multichip,
            "lost_devices": sorted(self.lost_devices),
            "scaling": self.scaling_status(),
            "per_replica": [
                {
                    "id": r.id,
                    "healthy": r.healthy(),
                    "draining": r.draining,
                    "breaker": (
                        "dead" if r.dead else r.breaker.state_name
                    ),
                    "dead_cause": r.dead_cause,
                    "devices": list(r.devices),
                    "mesh": self._mesh_shape(r),
                    "width": r.width,
                    "load": r.load(),
                    "supervisor": r.supervisor.stats(),
                }
                for r in self.replicas
            ],
        }
