"""Always-on device-time & MFU attribution (the perf observatory core).

Round 11 left the repo with a blind spot this module closes: per-site
HOST time is always measured (``dispatch_host_seconds{site}``), but the
DEVICE half was only visible under ``TRACE=1`` attribution mode, whose
``block_until_ready`` serializes the dispatch pipeline (8–15%
overhead, the pre-round BASELINE record (removed in PR 22) r11) — so no
production run and no headline
BENCH pass has carried device-side numbers since r05.  The estimator
here derives device occupancy from timestamps the serving loop
**already touches**, in the spirit of the benchmark-methodology
guidance of arXiv 2210.04323 (measure the steady pipeline, don't
serialize it to observe it):

- every guarded dispatch is **stamped at submit** (``on_guard`` — two
  clock reads that ``dispatch_guard`` was already paying);
- **completion is sampled at the fetch seams the loop already has**
  (``note_complete`` from ``_deliver_ready``/``_deliver_oldest``/
  ``_deliver_all``/``_admit_complete`` in ``engine/streams.py`` and
  the per-stream fetches in ``engine/engine.py``): a ``device_get``
  returns exactly when the producing dispatch finished, so the fetch
  return IS a device-completion timestamp — no extra sync, no extra
  dispatch, dispatch/fetch counts pinned unchanged
  (``tests/test_perf_obs.py``).

Because one device executes its stream in submission order, a
completion sample at sequence ``s`` also closes every older pending
submit (the linearity rule) — chunked-prefill windows, swap scatters
and handoffs, which have no fetch of their own, are closed by the next
decode-chunk completion.

**Accounting model** (estimator, documented as such): each completion
sample at time ``T`` closing pending submits ``P`` contributes one
busy interval ``[max(prev_busy_end, min_submit(P)), T]``; the gap
before it is device **bubble**.  The interval is attributed across the
closed sites (equal split — per-dispatch FLOP pairing would require
cross-thread plumbing the hot path doesn't need).  Only the
precisely-paired sites accrue busy time (``chunk``, ``prefill``,
``prefill_chunk``; ``batch`` is synchronous and self-closing); rare
un-paired sites (``swap``/``handoff`` tails) conservatively land in
bubble.  ``prep`` host intervals that overlap in-flight device work
accrue ``prep_overlap_s`` — the overlap-with-prep series the r19
double-buffering claims are judged by.

**MFU**: ``runtime/compile_cache.py`` analyzes every shared executable
once per call signature (``Lowered.cost_analysis()`` — a trace+lower,
zero XLA compiles, zero dispatches) and accrues modeled FLOPs/bytes
per (model, kind) into the process-level book here on every dispatch.
``mfu_estimate`` = rolling modeled-FLOP rate / peak chip FLOPs
(``PEAK_TFLOPS`` knob, else the device-kind table, else unknown →
gauge stays 0 and /debug/perf says why).

``PERF_OBS=0`` disables the whole layer: ``on_guard``/``note_*``
return before touching any state (no timestamps kept — pinned), and
shared executables skip cost analysis.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque

from . import metrics

# ---------------------------------------------------------------------------
# process-level switch (set from ServiceConfig at engine construction;
# read by compile_cache's cost-analysis wrapper and the occupancy
# estimators; default on — the whole point is always-on attribution).

_ENABLED = os.environ.get("PERF_OBS", "1").lower() not in ("0", "false", "no")


def configure(enabled: bool) -> None:
    """Flip the process-level switch (engine construction calls this
    with ``cfg.perf_obs``; last engine wins, which only matters to
    tests that build engines with differing knobs)."""
    global _ENABLED
    _ENABLED = bool(enabled)


def enabled() -> bool:
    return _ENABLED


# ---------------------------------------------------------------------------
# modeled-FLOP book: per-(model, kind) accruals fed by compile_cache.

_BOOK_LOCK = threading.Lock()
_BOOK: dict[str, dict] = {}  # model -> {"flops", "bytes", "by_kind": {}}


def note_cost(model: str, kind: str, flops: float, bytes_: float) -> None:
    """One dispatch of an analyzed executable: accrue its modeled cost
    (called by the compile-cache wrapper on every call; any thread)."""
    if flops:
        metrics.MODELED_FLOPS.labels(model, kind).inc(flops)
    with _BOOK_LOCK:
        b = _BOOK.setdefault(
            model, {"flops": 0.0, "bytes": 0.0, "by_kind": {}}
        )
        b["flops"] += flops
        b["bytes"] += bytes_
        b["by_kind"][kind] = b["by_kind"].get(kind, 0.0) + flops


def book_totals(model: str) -> dict:
    """{"flops", "bytes", "by_kind"} accrued for one model so far."""
    with _BOOK_LOCK:
        b = _BOOK.get(model)
        if b is None:
            return {"flops": 0.0, "bytes": 0.0, "by_kind": {}}
        return {
            "flops": b["flops"], "bytes": b["bytes"],
            "by_kind": dict(b["by_kind"]),
        }


def reset_book() -> None:
    """Test hook: zero the modeled-cost accruals."""
    with _BOOK_LOCK:
        _BOOK.clear()


# ---------------------------------------------------------------------------
# peak-FLOP resolution (the MFU denominator).

#: Dense peak FLOP/s by TPU device kind (bf16 MXU numbers from public
#: spec sheets; the PEAK_TFLOPS knob overrides).  CPU backends have no
#: meaningful entry — MFU stays 0/unknown unless the knob says
#: otherwise.
_PEAK_BY_KIND = (
    ("v6e", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def peak_flops(cfg=None) -> float:
    """Peak FLOP/s for the MFU denominator: the PEAK_TFLOPS knob when
    set, else a device-kind lookup.  A CPU backend has no entry and
    resolves 0.0 (MFU unknown); a TPU whose ``device_kind`` is not in
    the table raises — a silent 0.0 there would read as "MFU 0" on a
    chip that is busy."""
    knob = float(getattr(cfg, "peak_tflops", 0.0) or 0.0) if cfg is not None \
        else 0.0
    if not knob:
        try:
            knob = float(os.environ.get("PEAK_TFLOPS", "0") or 0.0)
        except ValueError:
            knob = 0.0
    if knob:
        return knob * 1e12
    import jax

    dev = jax.devices()[0]
    kind = str(dev.device_kind).lower()
    for frag, peak in _PEAK_BY_KIND:
        if frag in kind:
            return peak
    if dev.platform == "tpu":
        raise ValueError(
            f"no peak FLOP/s known for TPU device_kind {dev.device_kind!r}; "
            "add it to utils/perfobs._PEAK_BY_KIND or set PEAK_TFLOPS"
        )
    return 0.0


# ---------------------------------------------------------------------------
# the per-engine occupancy estimator.


class DeviceOccupancy:
    """Zero-extra-sync device busy/bubble estimator for one engine
    (module docstring has the accounting model).  Thread-safe: submits
    arrive from the decode-loop and stream-executor threads,
    completions from whichever thread ran the fetch."""

    #: Sites whose submits are precisely paired with a fetch seam.
    TRACKED_SITES = frozenset({"chunk", "prefill", "prefill_chunk"})
    #: Synchronous sites: the guarded callable contains its own fetch,
    #: so the guard return IS the completion (the unary batch path).
    SYNC_SITES = frozenset({"batch"})
    #: Host-side prep (r19 double-buffering): overlap accounting only.
    HOST_SITES = frozenset({"prep"})
    #: Pending-submit bound: a path that never completes (legacy
    #: engines driven without fetch seams) must not grow memory.
    MAX_PENDING = 4096

    def __init__(self, model: str, enabled: bool = True,
                 peak_flops: float = 0.0, clock=time.perf_counter,
                 window_s: float = 60.0):
        self.model = model
        self.enabled = bool(enabled)
        self.peak_flops = float(peak_flops)
        self.window_s = float(window_s)
        self._clock = clock
        self._lock = threading.Lock()
        self._seq = 0
        self._pending: dict[str, deque] = {}  # site -> deque[(seq, ts)]
        self._pending_total = 0
        self._epoch = clock()
        self._busy_end: float | None = None
        self.busy_s: dict[str, float] = {}
        self.bubble_s = 0.0
        self.prep_overlap_s = 0.0
        self.prep_host_s = 0.0
        self.samples = 0
        self.dropped_submits = 0
        # Rolling MFU ring: (ts, cumulative modeled flops) appended at
        # completion samples; bounded.
        self._flops_ring: deque = deque(maxlen=2048)
        self._last_gauge = 0.0

    # -- capture seams (graftlint: perf-capture — these ride the
    # dispatch_guard boundary / the loop's fetch seams only) ----------

    def on_guard(self, site: str, t0: float, t1: float) -> None:
        """One guarded dispatch returned: stamp it.  Called by
        ``InferenceEngine.dispatch_guard`` with the two clock reads it
        already paid — the layer adds no clock reads of its own on the
        dispatch path."""
        if not self.enabled:
            return
        if site in self.HOST_SITES:
            with self._lock:
                self.prep_host_s += t1 - t0
                if self._pending_total:
                    # Host prep that ran while device work was in
                    # flight: the overlap the r19 double-buffer buys.
                    self.prep_overlap_s += t1 - t0
            return
        if site in self.SYNC_SITES:
            with self._lock:
                self._account_locked([site], t0, t1)
            return
        if site not in self.TRACKED_SITES:
            return
        with self._lock:
            q = self._pending.setdefault(site, deque())
            if self._pending_total >= self.MAX_PENDING:
                # Unpaired path: drop the oldest rather than grow.
                for qq in self._pending.values():
                    if qq:
                        qq.popleft()
                        self._pending_total -= 1
                        self.dropped_submits += 1
                        break
            self._seq += 1
            q.append((self._seq, t0))
            self._pending_total += 1

    def note_complete(self, site: str, n: int = 1) -> None:
        """A fetch seam observed ``n`` dispatches of ``site`` landed:
        close them (and, by device-order linearity, every older pending
        submit of any site) and account the busy interval."""
        if not self.enabled:
            return
        now = self._clock()
        with self._lock:
            q = self._pending.get(site)
            if not q:
                return
            closed: list[tuple[int, float, str]] = []
            for _ in range(min(n, len(q))):
                seq, ts = q.popleft()
                self._pending_total -= 1
                closed.append((seq, ts, site))
            max_seq = closed[-1][0]
            # Linearity: anything submitted before the newest closed
            # dispatch finished before it did.
            for other, qq in self._pending.items():
                while qq and qq[0][0] < max_seq:
                    seq, ts = qq.popleft()
                    self._pending_total -= 1
                    closed.append((seq, ts, other))
            t0 = min(ts for _, ts, _ in closed)
            self._account_locked([s for _, _, s in closed], t0, now)

    # -- accounting ----------------------------------------------------

    def _account_locked(self, sites: list[str], t0: float,
                        t1: float) -> None:
        start = t0 if self._busy_end is None else max(self._busy_end, t0)
        if self._busy_end is not None and start > self._busy_end:
            gap = start - self._busy_end
            self.bubble_s += gap
            metrics.DEVICE_BUBBLE.labels(self.model).inc(gap)
        busy = max(0.0, t1 - start)
        self._busy_end = max(t1, self._busy_end or t1)
        self.samples += 1
        share = busy / len(sites)
        for s in sites:
            self.busy_s[s] = self.busy_s.get(s, 0.0) + share
            if share:
                metrics.DEVICE_BUSY.labels(self.model, s).inc(share)
        self._flops_ring.append((t1, book_totals(self.model)["flops"]))
        if t1 - self._last_gauge >= 1.0:
            self._last_gauge = t1
            metrics.MFU.labels(self.model).set(self._mfu_locked(t1))

    def _mfu_locked(self, now: float) -> float:
        if not self.peak_flops or not self._flops_ring:
            return 0.0
        newest_ts, newest = self._flops_ring[-1]
        oldest_ts, oldest = self._flops_ring[0]
        for ts, cum in self._flops_ring:
            if ts >= now - self.window_s:
                oldest_ts, oldest = ts, cum
                break
        span = newest_ts - oldest_ts
        if span <= 0:
            # One sample in the window: fall back to the epoch rate.
            span = max(now - self._epoch, 1e-9)
            oldest = 0.0
        return (newest - oldest) / span / self.peak_flops

    # -- read side -----------------------------------------------------

    def snapshot(self) -> dict:
        """/debug/perf + /status.perf + the BENCH ``perf`` block."""
        now = self._clock()
        with self._lock:
            busy_total = sum(self.busy_s.values())
            elapsed = max(now - self._epoch, 1e-9)
            book = book_totals(self.model)
            peak = self.peak_flops
            out = {
                "enabled": self.enabled,
                "model": self.model,
                "elapsed_s": round(elapsed, 4),
                "device_busy_s": {
                    k: round(v, 4) for k, v in sorted(self.busy_s.items())
                },
                "device_busy_total_s": round(busy_total, 4),
                "device_bubble_s": round(self.bubble_s, 4),
                "busy_ratio": round(
                    busy_total / (busy_total + self.bubble_s), 4
                ) if busy_total + self.bubble_s > 0 else None,
                "prep_host_s": round(self.prep_host_s, 4),
                "prep_overlap_s": round(self.prep_overlap_s, 4),
                "completion_samples": self.samples,
                "pending_dispatches": self._pending_total,
                "dropped_submits": self.dropped_submits,
                "modeled_flops_total": book["flops"],
                "modeled_bytes_total": book["bytes"],
                "modeled_flops_by_kind": {
                    k: v for k, v in sorted(book["by_kind"].items())
                },
                "peak_flops": peak,
                "mfu_estimate": round(self._mfu_locked(now), 6)
                if peak else None,
                # Roofline-ish companions: modeled flops over the busy
                # union (what the chip sustained while it ran) and over
                # the whole epoch (what the deployment extracted).
                "mfu_busy": round(
                    book["flops"] / busy_total / peak, 6
                ) if peak and busy_total > 0 else None,
                "mfu_epoch": round(
                    book["flops"] / elapsed / peak, 6
                ) if peak else None,
            }
        return out


def merge_snapshots(snaps: list[dict]) -> dict:
    """Fleet-wide rollup: sum the additive fields across per-replica
    occupancy snapshots (ratios recomputed from the sums)."""
    out: dict = {
        "replicas": len(snaps),
        "device_busy_total_s": 0.0,
        "device_bubble_s": 0.0,
        "prep_overlap_s": 0.0,
        "modeled_flops_total": 0.0,
        "completion_samples": 0,
        "device_busy_s": {},
    }
    for s in snaps:
        out["device_busy_total_s"] += s.get("device_busy_total_s", 0.0)
        out["device_bubble_s"] += s.get("device_bubble_s", 0.0)
        out["prep_overlap_s"] += s.get("prep_overlap_s", 0.0)
        out["completion_samples"] += s.get("completion_samples", 0)
        for k, v in (s.get("device_busy_s") or {}).items():
            out["device_busy_s"][k] = out["device_busy_s"].get(k, 0.0) + v
    busy, bubble = out["device_busy_total_s"], out["device_bubble_s"]
    out["busy_ratio"] = (
        round(busy / (busy + bubble), 4) if busy + bubble > 0 else None
    )
    # The modeled-FLOP book is per model (fleet replicas share one
    # model), so take it from the first snapshot rather than summing
    # the same book R times.
    if snaps:
        out["modeled_flops_total"] = snaps[0].get("modeled_flops_total", 0.0)
        out["mfu_estimate"] = snaps[0].get("mfu_estimate")
    return out
