"""Asyncio dynamic batcher: admit → queue (deadline-aware) → dispatch →
route futures.

Batch-formation policy (mirrors the reference's queue, SURVEY.md §2
"Dynamic-batching queue"): a batch closes when it reaches ``max_batch``
items or when ``batch_timeout_ms`` has elapsed since its first item
arrived — whichever comes first.  A burst that is already queued forms
a full batch with zero added wait.

On top of that FIFO core sits the SLA scheduler (``policy.py`` +
``admission.py``): requests carry a priority class and an optional
deadline, the wait queue is earliest-deadline-first within class and
class-weighted across classes, stale waiters shed as fast 504s, and a
KV-footprint budget keeps the admitted working set inside HBM.  With
no headers and the default config the observable behavior degrades to
exactly the seed's FIFO + 503 contract.

Dequeue is gated on dispatch capacity (``pipeline_depth`` batches in
flight): the wait queue is the REAL queue, not a hand-off into an
invisible unbounded executor backlog — which is what makes deadlines,
priorities and the KV budget actually bind.

Device dispatch happens on worker threads (``run_in_executor``): JAX's
blocking ``device_get`` must not stall the event loop, which on this
1-vCPU host also runs HTTP parsing and pre/post-processing (SURVEY.md
§7.4.3).

Backpressure: past ``max_queue`` waiting items, ``submit`` sheds —
either the newcomer or, when the newcomer outranks it, the
lowest-class latest-deadline waiter — with ``QueueFullError`` which
the API layer maps to 503 + Retry-After.  ``begin_drain()`` (SIGTERM)
stops admission while everything already admitted runs to completion.
"""

from __future__ import annotations

import asyncio
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, AsyncIterator

import numpy as np

from ..utils import metrics, tracing
from .admission import AdmissionController
from .policy import (  # noqa: F401  (QueueFullError re-exported here)
    BATCH,
    INTERACTIVE,
    Arrival,
    DeadlineExceededError,
    DeadlineQueue,
    QueueFullError,
)

_END = object()


class _QueuedCall:
    """One queued non-stream request: its future + scheduling fields."""

    __slots__ = (
        "feats", "future", "t_in", "klass", "deadline", "started",
        "kv", "kv_held", "_removed", "tenant",
    )

    def __init__(self, feats, future, klass, deadline, kv):
        self.feats = feats
        self.future = future
        self.t_in = time.monotonic()
        self.klass = klass
        self.deadline = deadline
        self.started = False
        self.kv = kv
        self.kv_held = False
        self._removed = False
        # Fair-share dequeue key (tenancy/fairshare.py, via
        # DeadlineQueue.set_fairshare); "" = anonymous.
        self.tenant = str(feats.get("tenant") or "")

    def fail(self, exc: BaseException) -> None:
        if not self.future.done():
            self.future.set_exception(exc)


class Batcher:
    def __init__(self, engine, cfg):
        self.engine = engine
        self.model = engine.bundle.name
        self.max_batch = int(cfg.max_batch)
        self.timeout_s = float(cfg.batch_timeout_ms) / 1000.0
        self.max_queue = int(cfg.max_queue)
        self.admission = AdmissionController(cfg, engine)
        self._queue = DeadlineQueue(
            self.max_queue, weight=int(getattr(cfg, "class_weight", 4))
        )
        self._wake = asyncio.Event()
        # Dispatch threads = pipeline depth: batches overlap in flight
        # so the host<->device round-trip of batch N hides behind the
        # compute of batch N+1 (the engine's semaphore is the real cap).
        depth = max(1, int(getattr(cfg, "pipeline_depth", 4)))
        self._executor = ThreadPoolExecutor(
            max_workers=depth, thread_name_prefix="dispatch"
        )
        # Dequeue gate: at most ``depth`` batches leave the wait queue
        # concurrently, so backpressure (and with it deadline expiry,
        # priority ordering and the KV budget) applies in the QUEUE
        # rather than in an invisible executor backlog.
        self._dispatch_sem = asyncio.Semaphore(depth)
        # EWMAs behind the Retry-After guidance on 503 sheds.
        self._batch_ewma_s = 0.05
        self._stream_ewma_s = 1.0
        # Streams hold a worker for their whole generation, so they get
        # their own pool — a long-running stream must never starve the
        # batch dispatch path.  Beyond max_streams concurrent streams we
        # shed load rather than queue invisibly.
        self.max_streams = int(getattr(cfg, "max_streams", 8))
        self._stream_executor = ThreadPoolExecutor(
            max_workers=self.max_streams, thread_name_prefix="stream"
        )
        self._active_streams = 0
        self._task: asyncio.Task | None = None
        self._inflight: set[asyncio.Task] = set()
        self._closed = False
        # Durable serving (JOURNAL_DIR; runtime/durability.py): ONE
        # write-ahead stream journal per process, attached to the
        # engine so the decode loop's hooks find it; a fleet shares it
        # (engine/fleet.py re-points every replica).  Constructed
        # BEFORE the fleet below so replica engines inherit it.  Unset
        # (default) = no journal object anywhere, every path
        # bit-identical.
        self._owns_journal = False
        jdir = getattr(cfg, "journal_dir", None)
        if (
            jdir
            and getattr(engine.bundle, "kind", None) == "seq2seq"
            and getattr(engine, "journal", None) is None
        ):
            from ..runtime.durability import StreamJournal

            engine.journal = StreamJournal(
                jdir, fsync=getattr(cfg, "journal_fsync", "always"),
                model=engine.bundle.name,
            )
            self._owns_journal = True
        # Continuous batching (default): concurrent generative streams
        # share ONE batched decode dispatch instead of holding a worker
        # each (engine/streams.py).  CONTINUOUS_BATCHING=0 falls back to
        # the per-stream path above (kept for A/B measurement).
        self._cdl = None
        # Supervised crash recovery (engine/supervisor.py): bounded
        # engine rebuilds on fatal dispatch faults.  /readyz reads the
        # ``failed`` flag once the restart budget is spent.
        self.supervisor = None
        # Replica fleet (FLEET_REPLICAS>1; engine/fleet.py): N
        # independent decode loops behind a health-gated router with
        # token-identical failover.  None (the default) keeps the
        # single-loop path below, bit-identical to the pre-fleet code.
        self.fleet = None
        fleet_n = int(getattr(cfg, "fleet_replicas", 1) or 1)
        # Elastic autoscaling (docs/autoscaling.md) needs the fleet
        # wrapper even at an initial size of 1: FLEET_MAX_REPLICAS
        # above the initial size is room the governor scales into.
        fleet_max = int(getattr(cfg, "fleet_max_replicas", 0) or 0)
        fleet_on = fleet_n > 1 or fleet_max > 1
        if getattr(engine.bundle, "kind", None) == "seq2seq" and getattr(
            cfg, "continuous_batching", True
        ):
            if fleet_on:
                from ..engine.fleet import ReplicaFleet

                self.fleet = ReplicaFleet(engine, cfg)
                # MAX_STREAMS caps concurrent generations PER replica;
                # legacy per-stream traffic counts against every
                # replica's bound — including replicas spawned later
                # by the governor (the fleet wires the indirection).
                self.fleet.external_active = (
                    lambda: self._active_streams
                )
                # Introspection compatibility: /status.decode and
                # /debug/engine read replica 0's loop; per-replica
                # detail lives in /status.fleet.
                self._cdl = self.fleet.replicas[0].cdl
                self.supervisor = self.fleet.replicas[0].supervisor
            else:
                from ..engine.streams import ContinuousDecodeLoop

                self._cdl = ContinuousDecodeLoop(engine, cfg)
                # MAX_STREAMS caps TOTAL concurrent generations: each
                # side counts the other's active streams in its
                # admission check.
                self._cdl.external_active = lambda: self._active_streams
                # One admission controller (KV ledger) for both queues.
                self._cdl.admission = self.admission
                if getattr(cfg, "supervise", True):
                    from ..engine.supervisor import Supervisor

                    # The supervisor dumps the engine flight recorder
                    # the moment it grants (or refuses) a restart.
                    self.supervisor = Supervisor(
                        cfg, recorder=getattr(engine, "flight", None)
                    )
                    self._cdl.supervisor = self.supervisor
        elif fleet_on and getattr(
            engine.bundle, "kind", None
        ) == "seq2seq":
            raise ValueError(
                "FLEET_REPLICAS>1 / FLEET_MAX_REPLICAS>1 requires "
                "CONTINUOUS_BATCHING=1 (the fleet replicates the "
                "continuous decode loop)"
            )
        # Bulk inference lane (JOBS_ENABLED; jobs/): the /v1/batches
        # job subsystem — a durable JobStore under JOURNAL_DIR/jobs
        # plus an executor that feeds job lines into THIS batcher as
        # batch-class idle backfill.  None (default) = no job code
        # anywhere on the serving path (pinned by test).
        self.jobs = None
        if getattr(cfg, "jobs_enabled", False):
            from ..jobs.executor import JobManager

            self.jobs = JobManager(engine, self, cfg)
        # Multi-tenant serving platform (tenancy/;
        # docs/multi-tenancy.md): per-tenant quotas + weighted fair
        # share (TENANTS/TENANTS_FILE) and the batched multi-adapter
        # LoRA pool (ADAPTER_DIR).  Both unset (default) builds NONE of
        # it — every queue, ledger and dispatch stays bit-identical to
        # the pre-tenancy code (pinned by test).
        self.tenants = None
        self.adapters = None
        if getattr(cfg, "tenants", None) or getattr(
            cfg, "tenants_file", None
        ) or getattr(cfg, "adapter_dir", None):
            from ..tenancy.accounts import TenantRegistry
            from ..tenancy.adapters import AdapterPool
            from ..tenancy.fairshare import WeightedFairShare

            default_w = float(
                getattr(cfg, "tenant_default_weight", 1.0) or 1.0
            )
            try:
                reg = TenantRegistry.from_cfg(cfg, model=self.model)
                pool = AdapterPool.from_cfg(cfg, model=self.model)
                if pool is not None:
                    if getattr(engine, "spec_enabled", False) or (
                        self._cdl is not None
                        and getattr(self._cdl, "spec", False)
                    ):
                        raise ValueError(
                            "ADAPTER_DIR does not compose with "
                            "SPEC_DECODE/SPEC_CONTINUOUS: the "
                            "draft→verify executables run the base "
                            "model only"
                        )
                    # Wrong-architecture adapters fail the BOOT, not
                    # the first adapted request.
                    pool.validate_against(engine.params)
            except Exception:
                # Fail-fast boot must not leak the already-started
                # decode loop / fleet threads.
                if self.fleet is not None:
                    self.fleet.stop()
                elif self._cdl is not None:
                    self._cdl.stop()
                raise
            self.tenants = reg
            self.adapters = pool
            if reg is not None:
                self.admission.set_tenants(reg)
                self._queue.set_fairshare(
                    WeightedFairShare(reg.weights(), default_w)
                )
            if self.fleet is not None:
                # Shared registry (one quota ledger across replicas),
                # per-replica fair-share cursors + adapter device
                # stacks — applied to live replicas and every replica
                # the governor spawns later.
                self.fleet.set_tenancy(reg, pool, default_w)
            elif self._cdl is not None:
                self._cdl.tenants = reg
                self._cdl.adapters = pool
                if reg is not None:
                    self._cdl.queue.set_fairshare(
                        WeightedFairShare(reg.weights(), default_w)
                    )

    # ------------------------------------------------------------------
    async def start(self) -> None:
        if self._task is None:
            self._task = asyncio.get_running_loop().create_task(self._run())

    async def stop(self) -> None:
        self._closed = True
        if self.jobs is not None:
            # Executor tasks first (they submit into this batcher),
            # store closed with the journal below.
            await self.jobs.stop()
        if self._task is not None:
            self._wake.set()
            await self._task
            self._task = None
        if self._inflight:
            await asyncio.gather(*self._inflight, return_exceptions=True)
        if self.fleet is not None:
            await asyncio.get_running_loop().run_in_executor(
                None, self.fleet.stop
            )
        elif self._cdl is not None:
            await asyncio.get_running_loop().run_in_executor(None, self._cdl.stop)
        self._executor.shutdown(wait=False)
        self._stream_executor.shutdown(wait=False)
        if self._owns_journal:
            j = getattr(self.engine, "journal", None)
            if j is not None:
                j.close()
            d = getattr(self.engine, "kv_disk", None)
            if d is not None:
                d.close()

    def warmup(self) -> None:
        """Blocking: compile the continuous-batching executables (slot
        insert, batched chunk) so the first stream pays no compiles.
        Called from the app's warmup executor, after engine.warmup.
        With the process-level ExecutableCache every replica past the
        first warms compile-free (runtime/compile_cache.py)."""
        if self.fleet is not None:
            for rep in self.fleet.replicas:
                ad = getattr(rep.cdl, "adapters", None)
                if ad is not None:
                    ad.warm()
            self.fleet.warm()
        elif self._cdl is not None:
            if self._cdl.adapters is not None:
                # Trace the slot installers first: serve-time adapter
                # installs/evictions must be dispatch-only.
                self._cdl.adapters.warm()
            self._cdl.warm()

    def compile_status(self) -> dict:
        """/status.compile: the executable-cache counters, the boot
        timeline (``boot``, and ``warm_phases_s`` out of it), the
        process's XLA executables (``xla_compiles`` / ``xla_compile_s``
        count a load from the persistent cache and a real compile
        alike; ``executables`` splits them, by name) and the
        persistent cache's directory — the operator answer to "where
        did the boot go, what did warming cost, which step recompiled"
        (docs/compilation.md)."""
        from ..runtime.compile_cache import (
            boot_status,
            cache_stats,
            compile_counters,
        )

        comp = compile_counters()
        return {
            "executable_cache": cache_stats(),
            "xla_compiles": comp["count"],
            "xla_compile_s": round(comp["seconds"], 3),
            **boot_status(),
        }

    def tenancy_status(self) -> dict | None:
        """/status.tenancy: per-tenant usage + quota envelope, the
        fair-share virtual-time cursors, and adapter-pool residency.
        None (tenancy off) = the key is absent from /status entirely —
        part of the bit-identical-default contract."""
        pools = []
        if self.fleet is not None:
            pools = [
                r.cdl.adapters for r in self.fleet.replicas
                if getattr(r.cdl, "adapters", None) is not None
            ]
        elif self._cdl is not None and self._cdl.adapters is not None:
            pools = [self._cdl.adapters]
        elif self.adapters is not None:
            pools = [self.adapters]
        if self.tenants is None and not pools:
            return None
        out: dict = {}
        if self.tenants is not None:
            out["tenants"] = self.tenants.usage()
            out["totals"] = self.tenants.totals()
            fs = getattr(self._queue, "_fairshare", None)
            if fs is not None:
                out["fairshare"] = fs.snapshot()
        if pools:
            out["adapters"] = (
                pools[0].status() if len(pools) == 1
                else [p.status() for p in pools]
            )
        return out

    # ------------------------------------------------------------------
    # drain lifecycle (SIGTERM)

    def begin_drain(self) -> None:
        """Stop admitting (new work sheds 503 ``drain``); everything
        already queued or in flight runs to completion."""
        self.admission.draining = True
        if self.fleet is not None:
            self.fleet.begin_drain()

    @property
    def draining(self) -> bool:
        return self.admission.draining

    def pending_work(self) -> int:
        """Admitted-but-unfinished items across both serving paths."""
        n = self._queue.qsize() + len(self._inflight) + self._active_streams
        if self.fleet is not None:
            n += self.fleet.pending_work()
        elif self._cdl is not None:
            n += self._cdl._admitted + len(self._cdl._inflight_chunks)
        return n

    async def drained(self, timeout_s: float = 30.0) -> bool:
        """Await quiescence after ``begin_drain``; True when everything
        finished inside the grace window."""
        deadline = time.monotonic() + max(0.0, float(timeout_s))
        while self.pending_work() > 0 and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        return self.pending_work() == 0

    def interactive_load(self) -> tuple[bool, bool]:
        """(interactive decode live, interactive work waiting) across
        the serving paths — the bulk-job backfill governor's claim
        signal (scheduler/policy.BackfillGovernor)."""
        if self.fleet is not None:
            live = waiting = False
            for rep in self.fleet.replicas:
                l, w = rep.cdl.interactive_load()
                live, waiting = live or l, waiting or w
            return live, waiting
        if self._cdl is not None:
            return self._cdl.interactive_load()
        return self._active_streams > 0, False

    # ------------------------------------------------------------------
    # shed helpers

    def _shed(self, reason: str, tenant: str = "") -> None:
        metrics.SHED.labels(self.model, reason).inc()
        if self.tenants is not None and reason != "quota":
            # Per-tenant attribution (bounded label; "" → anon).  Quota
            # sheds are already attributed at the admission gate.
            self.tenants.note_shed(tenant, reason)
        fl = getattr(self.engine, "flight", None)
        if fl is not None:
            fl.event("shed", reason=reason, path="batch")

    def retry_after_s(self, streams: bool = False) -> float:
        """Client guidance on 503: expected seconds until capacity,
        from current depth × the observed service-time EWMA."""
        if streams:
            waiting = self._active_streams
            if self.fleet is not None:
                waiting += self.fleet.admitted()
            elif self._cdl is not None:
                waiting += self._cdl._admitted
            est = (waiting + 1) * self._stream_ewma_s / max(1, self.max_streams)
        else:
            est = (
                self._queue.qsize() / max(1, self.max_batch) + 1.0
            ) * self._batch_ewma_s
        return min(60.0, max(1.0, est))

    def _depth_gauges(self) -> None:
        metrics.QUEUE_DEPTH.labels(self.model).set(self._queue.qsize())
        for klass in (INTERACTIVE, BATCH):
            metrics.CLASS_QUEUE_DEPTH.labels(self.model, "batch", klass).set(
                self._queue.waiting(klass)
            )

    # ------------------------------------------------------------------
    async def submit(self, feats: dict) -> np.ndarray:
        """Enqueue one preprocessed item; resolves to its result row.

        Sheds with ``QueueFullError`` (503: queue_full | kv_budget |
        drain) or, when the deadline passes before dispatch,
        ``DeadlineExceededError`` (504)."""
        if self._closed:
            raise RuntimeError("batcher is stopped")
        # Idempotent unary retries (runtime/durability.py): a CLIENT-
        # SUPPLIED X-Request-Id whose result was journaled before a
        # crash returns the journaled row — the retry after a restart
        # costs a lookup, not a recompute, and can never produce a
        # different completion.  (Minted ids never repeat, so the API
        # layer only flags client-supplied ones.)
        j = getattr(self.engine, "journal", None)
        rid = str(feats.get("request_id") or "")
        if j is not None and rid and feats.get("rid_client"):
            cached = j.lookup_result(rid)
            if cached is not None:
                return np.asarray(cached, np.int32)
        klass, deadline = self.admission.classify(feats)
        try:
            klass, kv = self.admission.admit(feats, klass)
        except QueueFullError as e:
            if e.retry_after_s is None:
                e.retry_after_s = self.retry_after_s()
            self._shed(e.reason, str(feats.get("tenant") or ""))
            raise
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        item = _QueuedCall(feats, fut, klass, deadline, kv)
        try:
            victim = self._queue.put(item)
        except QueueFullError as e:
            self.admission.release_lease(feats)
            e.retry_after_s = self.retry_after_s()
            self._shed("queue_full", item.tenant)
            raise
        if victim is not None:
            self.admission.release_lease(victim.feats)
            self._shed("queue_full", victim.tenant)
            victim.fail(QueueFullError(
                "shed for higher-priority work",
                retry_after_s=self.retry_after_s(),
            ))
        self._wake.set()
        self._depth_gauges()
        return await fut

    def expect_stream(self) -> Arrival:
        """The server has read a streaming request and is about to
        preprocess it: count it on the decode loop's queue until it is
        put there or fails (``Arrival.settle``), so an idle loop admits
        the burst it belongs to as one wave.  Under a fleet every
        replica's loop sees the server's count, not its own share: a
        loop may wait for a request the router sends elsewhere, never
        longer than a wave of its own costs."""
        if self.fleet is not None:
            return Arrival(rep.cdl.queue for rep in self.fleet.replicas)
        if self._cdl is not None:
            return Arrival([self._cdl.queue])
        return Arrival()

    def submit_stream(self, feats: dict) -> AsyncIterator[np.ndarray]:
        """Streaming seq2seq: bridge the engine's blocking chunk
        generator onto the event loop.  Each yielded array is one chunk
        of token ids.

        Admission is atomic: the counter check AND increment both happen
        here, synchronously in the event loop, before the generator is
        returned — so concurrent requests in the same loop window cannot
        all slip under ``max_streams``, and the caller can still return
        a 503 before any response bytes go out.  The decrement rides the
        pump future's done-callback, so an abandoned (never-iterated or
        half-consumed) generator cannot leak a slot."""
        if self._closed:
            raise RuntimeError("batcher is stopped")
        # SPEC_DECODE routes streams to the per-stream path (where the
        # speculative executables live) ONLY in the low-concurrency
        # regime it targets (< spec_max_streams active): under load,
        # one shared batched dispatch for all streams beats N
        # serialized speculative loops, so traffic falls back to the
        # continuous loop.  Sampled streams speculate via rejection-
        # sampling acceptance unless SPEC_SAMPLED=0 opted them out.
        cdl_admitted = self._cdl._admitted if self._cdl is not None else 0
        spec_route = (
            getattr(self.engine, "spec_enabled", False)
            and not feats.get("adapter_id")
            and (
                float(feats.get("temperature", 0.0)) == 0.0
                or getattr(self.engine, "spec_sampled", False)
            )
            and (self._active_streams + cdl_admitted)
            < int(getattr(self.engine.cfg, "spec_max_streams", 1))
        )
        # SPEC_CONTINUOUS loop + SPEC_SAMPLED=0: the shared loop would
        # run rejection-sampling acceptance on sampled rows, violating
        # the opt-out's strict cross-path seed contract — those streams
        # bypass to the per-stream chunked path instead (each holds a
        # worker; the documented cost of the opt-out).
        sampled_opt_out = (
            self._cdl is not None
            and getattr(self._cdl, "spec", False)
            and not getattr(self.engine, "spec_sampled", True)
            and float(feats.get("temperature", 0.0)) > 0.0
        )
        if (
            self._cdl is not None
            and not spec_route
            and not sampled_opt_out
            and int(feats.get("length", 0)) <= self._cdl.max_prompt
        ):
            # Deadline-queued admission (and preemption) live in the
            # continuous loop; it raises QueueFullError / emits
            # DeadlineExceededError itself.  Under a fleet the router
            # picks the replica (health → affinity → least-loaded)
            # and its loop does the same admission.
            if self.fleet is not None:
                return self.fleet.submit_stream(feats)
            return self._cdl.submit_stream(feats)
        # Legacy per-stream path (oversized prompts, spec routing, or
        # CONTINUOUS_BATCHING=0): the worker pool admits instantly or
        # sheds — no wait queue, but the drain/KV admission gates and
        # shed accounting still apply.
        klass, _deadline = self.admission.classify(feats)
        try:
            self.admission.admit(feats, klass)
        except QueueFullError as e:
            if e.retry_after_s is None:
                e.retry_after_s = self.retry_after_s(streams=True)
            self._shed(e.reason, str(feats.get("tenant") or ""))
            raise
        if feats.get("adapter_id"):
            # Adapters serve through the continuous loop's batched
            # multi-adapter dispatch only; this path sheds honestly
            # instead of silently generating base-model tokens.
            self.admission.release_lease(feats)
            self._shed("adapter_pool", str(feats.get("tenant") or ""))
            raise QueueFullError(
                "adapter streams require the continuous batching path "
                "(prompt exceeds the largest seq bucket, or "
                "CONTINUOUS_BATCHING=0)",
                reason="adapter_pool",
                retry_after_s=self.retry_after_s(streams=True),
            )
        # Oversized prompts (longer than the largest seq bucket) cannot
        # join the shared slot batch; they keep the per-stream path —
        # but MAX_STREAMS caps TOTAL concurrent generations, so count
        # the loop's admissions too.
        cdl_active = (
            self.fleet.admitted() if self.fleet is not None
            else self._cdl._admitted if self._cdl is not None else 0
        )
        if self._active_streams + cdl_active >= self.max_streams:
            self.admission.release_lease(feats)
            self._shed("queue_full", str(feats.get("tenant") or ""))
            raise QueueFullError(
                f"{self._active_streams} streams active >= "
                f"max_streams={self.max_streams}",
                retry_after_s=self.retry_after_s(streams=True),
            )
        loop = asyncio.get_running_loop()
        chunks: asyncio.Queue = asyncio.Queue()
        cancelled = threading.Event()

        def pump():
            t_prev = 0.0
            try:
                gen = self.engine.generate_stream(feats)
                try:
                    while True:
                        # Check BEFORE asking the engine for the next
                        # chunk: a disconnected client pays at most the
                        # one dispatch already in flight, never a fresh
                        # one (the generator only touches the device
                        # inside next()).
                        if cancelled.is_set():
                            return
                        try:
                            chunk = next(gen)
                        except StopIteration:
                            break
                        loop.call_soon_threadsafe(chunks.put_nowait, chunk)
                        metrics.TOKENS.labels(self.model).inc(int(chunk.size))
                        # Same TBT series the continuous loop feeds:
                        # inter-chunk cadence after the first chunk.
                        t_now = time.monotonic()
                        if t_prev:
                            metrics.TBT.labels(self.model).observe(
                                t_now - t_prev
                            )
                        t_prev = t_now
                finally:
                    gen.close()
                loop.call_soon_threadsafe(chunks.put_nowait, _END)
            except BaseException as e:  # propagate to the consumer
                loop.call_soon_threadsafe(chunks.put_nowait, e)

        self._active_streams += 1
        t_started = time.monotonic()
        pump_fut = loop.run_in_executor(self._stream_executor, pump)

        def _release(_fut):
            self._active_streams -= 1
            self.admission.release_lease(feats)
            dt = time.monotonic() - t_started
            self._stream_ewma_s = 0.8 * self._stream_ewma_s + 0.2 * dt

        pump_fut.add_done_callback(_release)

        async def gen():
            try:
                while True:
                    item = await chunks.get()
                    if item is _END:
                        break
                    if isinstance(item, BaseException):
                        raise item
                    yield item
            finally:
                # Consumer gone (client disconnect / full drain): tell
                # the pump to stop at the next chunk boundary.
                cancelled.set()

        return gen()

    def resume_stream(self, feats: dict, delivered: list[int]):
        """Journal-replay resume routing: hand a recovered checkpoint
        to the continuous loop (or, under a fleet, to the replica the
        router picks — the adopter-side resume).  Returns the
        continuation generator, or None when the stream had already
        delivered its budget.  Raises RuntimeError when no continuous
        loop exists to resume on (CONTINUOUS_BATCHING=0)."""
        if self.fleet is not None:
            healthy = self.fleet.healthy_replicas()
            last: Exception | None = None
            for rep in self.fleet.router.order(healthy, feats):
                try:
                    return rep.cdl.resume_stream(feats, delivered)
                except (QueueFullError, RuntimeError) as e:
                    last = e
            raise last if last is not None else RuntimeError(
                "no healthy replica to resume on"
            )
        if self._cdl is None:
            raise RuntimeError(
                "journal replay needs the continuous decode loop "
                "(CONTINUOUS_BATCHING=1)"
            )
        return self._cdl.resume_stream(feats, delivered)

    # ------------------------------------------------------------------
    def _expire(self) -> None:
        """Fail every waiter whose deadline passed — a fast 504 NOW
        beats serving stale work or a client-side timeout later."""
        for item in self._queue.expire():
            self.admission.release(item)
            self._shed("deadline", item.tenant)
            item.fail(DeadlineExceededError(
                "deadline passed while queued; request shed before dispatch"
            ))

    def _pop_ready(self):
        """Expire stale waiters, then pop the next schedulable item
        (KV-budget-gated unless the batcher is shutting down) and
        reserve its KV commitment."""
        self._expire()
        fits = None if self._closed else self.admission.fits
        item = self._queue.pop_nowait(fits=fits)
        if item is not None:
            self.admission.reserve(item)
        return item

    async def _wait_wake(self, timeout: float | None) -> None:
        try:
            if timeout is None:
                await self._wake.wait()
            else:
                await asyncio.wait_for(self._wake.wait(), timeout)
        except asyncio.TimeoutError:
            pass
        self._wake.clear()

    async def _next_item(self):
        """Block until an item is schedulable (or the batcher is closed
        AND fully drained → None).  Wakes on submits, on the next
        waiter's deadline (for prompt 504s), and on a short poll while
        items wait only on KV capacity."""
        while True:
            item = self._pop_ready()
            if item is not None:
                return item
            if self._closed and self._queue.qsize() == 0:
                return None
            timeout = None
            nd = self._queue.next_deadline()
            if nd is not None:
                timeout = max(0.01, nd - time.monotonic())
            if self._queue.qsize() > 0 or self._closed:
                # Items waiting on KV release (no event fires for it)
                # or shutdown in progress: poll.
                timeout = 0.05 if timeout is None else min(timeout, 0.05)
            await self._wait_wake(timeout)

    async def _acquire_dispatch(self) -> None:
        """Take a dispatch slot, sweeping deadline expiry while blocked
        (all ``pipeline_depth`` slots busy) so queued work still 504s
        on time instead of rotting behind a saturated device."""
        while True:
            try:
                await asyncio.wait_for(self._dispatch_sem.acquire(), 0.05)
                return
            except asyncio.TimeoutError:
                self._expire()

    async def _run(self) -> None:
        while True:
            await self._acquire_dispatch()
            first = await self._next_item()
            if first is None:
                self._dispatch_sem.release()
                return
            self._depth_gauges()
            batch = [first]
            deadline = time.monotonic() + self.timeout_s
            while len(batch) < self.max_batch:
                # Fast path: drain whatever is already schedulable.
                item = self._pop_ready()
                if item is None:
                    if self._closed:
                        break
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    await self._wait_wake(remaining)
                    item = self._pop_ready()
                    if item is None:
                        if time.monotonic() >= deadline:
                            break
                        continue
                batch.append(item)
            self._depth_gauges()
            # Fire-and-track: the batcher immediately goes back to
            # collecting while this batch's device round-trip is in
            # flight (bounded by the dispatch semaphore + the engine's
            # pipeline semaphore).
            self._spawn_dispatch(batch)

    def _spawn_dispatch(self, batch: list) -> None:
        task = asyncio.get_running_loop().create_task(self._dispatch(batch))
        self._inflight.add(task)

        def _done(t):
            self._inflight.discard(t)
            self._dispatch_sem.release()
            self._wake.set()

        task.add_done_callback(_done)

    async def _dispatch(self, batch: list) -> None:
        loop = asyncio.get_running_loop()
        now = time.monotonic()
        feats = [item.feats for item in batch]
        tr = tracing.tracer()
        for item in batch:
            metrics.QUEUE_WAIT.labels(self.model).observe(now - item.t_in)
            if tr is not None:
                tr.add(
                    "queue_wait", cat="sched",
                    rid=str(item.feats.get("request_id") or ""),
                    t0=item.t_in, dur=now - item.t_in, klass=item.klass,
                )
        metrics.BATCH_SIZE.labels(self.model).observe(len(batch))
        t0 = time.monotonic()
        # Fleet routing for the unary path (ROADMAP item 3 leftover):
        # the batch dispatch goes to a HEALTHY replica picked by the
        # same router streams use (prefix affinity is moot here, so the
        # ladder degrades to health → least-loaded), instead of always
        # hitting the base engine — a replica with an open breaker no
        # longer serves /predict, and batch faults feed its breaker.
        rep = None
        eng = self.engine
        if self.fleet is not None:
            try:
                rep = self.fleet.pick_batch_replica(feats[0] if feats else {})
                eng = rep.engine
            except QueueFullError as e:
                for item in batch:
                    item.fail(e)
                    self.admission.release(item)
                return
        try:
            # The batch path's dispatch boundary runs under the same
            # fault injector + watchdog as the decode loop's chunks:
            # transients retry with backoff, a hang is cut off at
            # DISPATCH_TIMEOUT_S instead of wedging a worker forever.
            # (Duck-typed engines without a guard dispatch bare.)
            guard = getattr(eng, "dispatch_guard", None)
            if guard is None:
                rows = await loop.run_in_executor(
                    self._executor, eng.run_batch, feats
                )
            else:
                rows = await loop.run_in_executor(
                    self._executor,
                    lambda: guard(
                        "batch", lambda: eng.run_batch(feats)
                    ),
                )
        except Exception as e:
            # Classify before the breaker hears about it: only DEVICE
            # faults (transient link errors, fatal device loss, watchdog
            # timeouts) indict the replica.  Poison input — a collate
            # ValueError, a preprocess bug — fails only its own batch;
            # without this gate FLEET_BREAKER_N malformed requests open
            # the breaker and evict a perfectly healthy replica.
            from ..engine import faults

            if rep is not None and (
                faults.is_transient(e) or faults.is_fatal_device(e)
            ):
                rep.breaker.record_fault()
                self.fleet._refresh_gauges()
            for item in batch:
                item.fail(e)
            return
        finally:
            for item in batch:
                self.admission.release(item)
        if rep is not None:
            # One clean batch dispatch closes the replica's fault
            # streak, same as a routed-and-fetched stream chunk.
            rep.breaker.record_ok()
        dt = time.monotonic() - t0
        self._batch_ewma_s = 0.8 * self._batch_ewma_s + 0.2 * dt
        metrics.DEVICE_TIME.labels(self.model).observe(dt)
        j = getattr(self.engine, "journal", None)
        for item, row in zip(batch, rows):
            if j is not None and item.feats.get("rid_client"):
                rid = str(item.feats.get("request_id") or "")
                arr = np.asarray(row)
                if rid and np.issubdtype(arr.dtype, np.integer):
                    # Journal the completion for X-Request-Id dedup
                    # (token rows only — the generative unary path).
                    j.result(rid, arr)
            if not item.future.done():
                item.future.set_result(row)


def batch_results(rows: list[np.ndarray]) -> Any:
    """Helper for tests: stack row results."""
    return np.stack(rows)
