"""Overload A/B: the SLA scheduler vs FIFO at 1×/2×/4× offered load.

Two arms over the SAME service (t5-small streaming through the
continuous-batching loop, bounded stream wait queue):

- **fifo**: no scheduling headers — every request is default-class with
  no deadline, i.e. the seed's behavior (FIFO queue, shed at the bound).
- **sched**: a 50/50 interactive/batch mix where interactive requests
  carry ``X-Priority: interactive`` + ``X-Deadline-Ms``; batch requests
  ride ``X-Priority: batch``.  The deadline queue serves interactive
  first (class-weighted EDF), sheds stale waiters as fast 504s before
  dispatch, and preempts batch-class slot holders for interactive
  arrivals.

Reported per (load, arm): interactive goodput (completions that
finished INSIDE the deadline, per second), p99 TTFT over served
interactive requests, and shed counts (503/504).  The judged claim
(ISSUE 2): at 2× load, interactive goodput under ``sched`` ≥ ``fifo``,
and every deadline miss is shed as a 504 BEFORE dispatch rather than
served stale.

    python benchmarks/overload_ab.py               # current backend
    DEVICE=cpu python benchmarks/overload_ab.py    # CPU sanity run

One JSON line per row to stdout, a markdown table to stderr.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

_here = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _here)
sys.path.insert(0, os.path.dirname(_here))
from harness import ServiceUnderTest, pctile  # noqa: E402

PROMPT = "summarize: the quick brown fox jumps over the lazy dog again"
LOADS = (1.0, 2.0, 4.0)
N_PER_ARM = int(os.environ.get("OVERLOAD_N", "48"))

# Load shapes (round-8 satellite): round 7's single shape (12-deep
# queue, 2.5× deadline, deadlines on interactive only) always hit the
# queue BOUND before any waiter aged out, so its 504 column was
# structurally zero — and class-weighted dequeue serves interactive
# fast enough that a loose deadline never lapses in the queue.  The
# "deep" shape — deeper queue, ~solo-tight deadline, deadlines on
# BOTH classes, overload only — lets waiters age out INSIDE the
# queue, exercising the fast-504 path in the table (not just in unit
# tests).  Fields: (name, queue depth, deadline factor, deadline on
# both classes, loads).  OVERLOAD_SHAPES filters.
SHAPES = (
    ("base", "12", 2.5, False, LOADS),
    ("deep", "24", 1.2, True, (4.0,)),
)


async def _one(client, i: int, sched: bool, deadline_ms: float,
               deadline_all: bool = False):
    """One streamed request; returns (klass, status, ttft_s, wall_s)."""
    klass = "interactive" if i % 2 == 0 else "batch"
    headers = {}
    if sched:
        headers["X-Priority"] = klass
        if klass == "interactive" or deadline_all:
            headers["X-Deadline-Ms"] = str(int(deadline_ms))
    t0 = time.perf_counter()
    try:
        resp = await client.post(
            "/predict", json={"text": PROMPT, "stream": True},
            headers=headers,
        )
        if resp.status != 200:
            await resp.read()
            return klass, resp.status, None, None
        ttft = None
        async for line in resp.content:
            if ttft is None:
                ttft = time.perf_counter() - t0
            if json.loads(line).get("done"):
                break
        return klass, 200, ttft, time.perf_counter() - t0
    except Exception:
        return klass, -1, None, None


async def run_arm(s, sched: bool, rate_sps: float, deadline_ms: float,
                  deadline_all: bool = False):
    """Offered load at ``rate_sps`` arrivals/s, 50/50 class mix.
    Returns raw per-arm tallies; cells aggregate across repeats."""
    tasks = []
    interval = 1.0 / rate_sps
    t0 = time.perf_counter()
    for i in range(N_PER_ARM):
        tasks.append(asyncio.create_task(
            _one(s.client, i, sched, deadline_ms, deadline_all)
        ))
        await asyncio.sleep(interval)
    results = await asyncio.gather(*tasks)
    wall = time.perf_counter() - t0  # makespan: arrivals + drain tail
    inter = [r for r in results if r[0] == "interactive"]
    served = [r for r in inter if r[1] == 200]
    good = [r for r in served if r[3] is not None and r[3] * 1e3 <= deadline_ms]
    return {
        "arm": "sched" if sched else "fifo",
        "offered": len(inter),
        "good": len(good),
        "wall": wall,
        "ttfts": [r[2] for r in served if r[2] is not None],
        "shed_503": sum(1 for r in results if r[1] == 503),
        "shed_504": sum(1 for r in results if r[1] == 504),
    }


async def run_shape(shape: str, queue_depth: str, deadline_factor: float,
                    deadline_all: bool, loads, dev: dict,
                    rows: list) -> None:
    overrides = {
        "MODEL_NAME": "t5-small",
        "BATCH_BUCKETS": "1,4",
        # The prompt byte-tokenizes to 61 tokens: the max seq bucket
        # must COVER it or every stream silently routes to the legacy
        # per-stream path, where the deadline queue, priorities and
        # preemption never bind (round 7 ran with SEQ_BUCKETS=32 and
        # measured exactly that — recorded in the pre-round BASELINE record
        # (removed in PR 22) r8).
        "SEQ_BUCKETS": "32,64",
        "MAX_DECODE_LEN": "8",
        # Narrow slot pool + deep wait queue: time spent waiting lands
        # in the SCHEDULABLE queue (where EDF/priorities/expiry bind)
        # instead of as in-slot compute sharing the scheduler can't
        # reorder — that is also the right shape for a compute-bound
        # backend (slots beyond the parallelism the chip actually has
        # only dilute every stream's cadence).
        "MAX_STREAMS": "2",
        "MAX_STREAM_QUEUE": queue_depth,
        "CLASS_WEIGHT": "4",
        **dev,
    }
    async with ServiceUnderTest(overrides) as s:
        # Capacity calibration: how fast the slot pool ACTUALLY drains
        # a full concurrent wave (on a shared-core CPU host the slots
        # contend, so solo-latency × slots would overestimate badly).
        # First probe discarded: it may still pay one-time lazy costs.
        await _one(s.client, 0, False, 1e9)
        lat = []
        for _ in range(3):
            _, _, _, wall = await _one(s.client, 0, False, 1e9)
            if wall:
                lat.append(wall)
        solo_s = sorted(lat)[len(lat) // 2]
        t0 = time.perf_counter()
        waves = 3
        for _ in range(waves):
            await asyncio.gather(
                *(_one(s.client, 0, False, 1e9) for _ in range(2))
            )
        capacity_sps = waves * 2 / (time.perf_counter() - t0)
        # Deadline budget: a promptly-served request fits comfortably
        # (the base shape's 2.5× a solo run); one that waited out an
        # overloaded FIFO queue does not — that's the SLA the
        # scheduler defends.  The "deep" shape tightens the factor so
        # deep-queued waiters age out IN the queue (the 504 path).
        deadline_ms = max(deadline_factor * solo_s * 1e3, 200.0)
        # Repeats with arm-order alternation: on a shared-core host the
        # run-to-run variance rivals the effect size, so each (load,
        # arm) cell aggregates across repeats and neither arm always
        # runs on a freshly-drained pool.
        repeats = int(os.environ.get("OVERLOAD_REPEATS", "2"))
        cells: dict = {}
        for rep in range(repeats):
            for mult in loads:
                arm_order = (False, True) if rep % 2 == 0 else (True, False)
                for sched in arm_order:
                    r = await run_arm(
                        s, sched, capacity_sps * mult, deadline_ms,
                        deadline_all,
                    )
                    c = cells.setdefault((mult, r["arm"]), {
                        "offered": 0, "good": 0, "wall": 0.0,
                        "ttfts": [], "shed_503": 0, "shed_504": 0,
                    })
                    for k in ("offered", "good", "shed_503", "shed_504"):
                        c[k] += r[k]
                    c["wall"] += r["wall"]
                    c["ttfts"].extend(r["ttfts"])
                    await asyncio.sleep(1.0)  # drain the slot pool
        for (mult, arm), c in sorted(cells.items()):
            rows.append({
                "shape": shape,
                "load_x": mult,
                "arm": arm,
                "interactive_offered": c["offered"],
                "interactive_in_deadline": c["good"],
                "interactive_goodput_rps": round(c["good"] / c["wall"], 3),
                "ttft_p99_ms": (
                    round(pctile(c["ttfts"], 0.99) * 1000, 1)
                    if c["ttfts"] else None
                ),
                "shed_503": c["shed_503"],
                "shed_504": c["shed_504"],
                "solo_ms": round(solo_s * 1e3, 1),
                "deadline_ms": round(deadline_ms, 1),
            })


async def main() -> None:
    dev = {"DEVICE": os.environ["DEVICE"]} if os.environ.get("DEVICE") else {}
    want = tuple(
        s.strip()
        for s in os.environ.get("OVERLOAD_SHAPES", "base,deep").split(",")
        if s.strip()
    )
    rows: list = []
    for shape, queue_depth, factor, deadline_all, loads in SHAPES:
        if shape in want:
            await run_shape(
                shape, queue_depth, factor, deadline_all, loads, dev, rows
            )

    import jax

    backend = jax.default_backend()
    print("\n| shape | load | arm | goodput (rps) | in-deadline "
          "| ttft p99 (ms) | 503 | 504 |", file=sys.stderr)
    print("|---|---|---|---|---|---|---|---|", file=sys.stderr)
    for r in rows:
        print(
            f"| {r['shape']} | {r['load_x']}x | {r['arm']} "
            f"| {r['interactive_goodput_rps']} "
            f"| {r['interactive_in_deadline']}/{r['interactive_offered']} "
            f"| {r['ttft_p99_ms']} | {r['shed_503']} | {r['shed_504']} |",
            file=sys.stderr,
        )
        print(json.dumps({**r, "backend": backend}))


if __name__ == "__main__":
    asyncio.run(main())
