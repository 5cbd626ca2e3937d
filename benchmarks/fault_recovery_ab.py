"""Fault-recovery A/B: goodput + p99 TTFT under an injected fault
schedule, supervised vs unsupervised.

The judged claim (ISSUE 4): with the SAME deterministic ``FAULT_SPEC``
(a transient, a fatal device loss, a 2-second hang, another transient,
all on the continuous loop's chunk dispatches), the supervised engine
(watchdog + retry + checkpoint/rebuild/resume) delivers strictly more
goodput than the unsupervised seed behavior, where a transient or
fatal chunk fault error-terminates every live stream and the hang
stalls the loop for its full duration.

Three arms over the same gpt2 service (random-init weights — recovery
economics depend on dispatch structure, not weights):

- **clean**:        no faults (the reference ceiling).
- **supervised**:   FAULT_SPEC + DISPATCH_TIMEOUT_S/RETRIES + SUPERVISE=1.
- **unsupervised**: same FAULT_SPEC, watchdog and supervisor off.

N streams arrive in two waves; each stream reports TTFT, tokens and
whether it terminated cleanly (a mid-stream in-band ``error`` line
counts as a failed stream).  Goodput = tokens delivered by error-free
streams / wall.

    python benchmarks/fault_recovery_ab.py              # current backend
    DEVICE=cpu python benchmarks/fault_recovery_ab.py   # CPU sanity run

One JSON line per arm to stdout, a markdown table to stderr.
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

N_STREAMS = int(os.environ.get("FAULT_AB_N", "8"))
# Deterministic schedule on the chunk site: transient (retryable),
# fatal (engine rebuild), a FINITE 45 s hang (so the unsupervised arm
# stalls measurably instead of forever), one more transient.
FAULT_SPEC = os.environ.get(
    "FAULT_AB_SPEC",
    "chunk:transient@2;chunk:fatal@4;chunk:hang(45)@6;chunk:transient@8",
)
# Watchdog deadline for the supervised arm: must sit ABOVE this host's
# honest dispatch time (real gpt2 on a 1-vCPU CPU backend runs ~2-5 s
# per batched dispatch; a too-tight deadline crash-loops on false
# positives — measured, see the pre-round BASELINE record (removed in PR 22)
# round 9) and BELOW the hang.
TIMEOUT_S = os.environ.get("FAULT_AB_TIMEOUT_S", "20")

PROMPTS = [
    "the quick brown fox jumps",
    "pack my box with five dozen",
    "a longer prompt that spans a few more tokens than the others do",
    "short one",
]


async def _one(client, i: int):
    text = PROMPTS[i % len(PROMPTS)]
    t0 = time.perf_counter()
    try:
        # Mixed budgets: waves don't finish in lockstep, so follow-up
        # chunk dispatches keep flowing and the later schedule entries
        # (the hang) actually land.
        resp = await client.post(
            "/predict",
            json={"text": text, "stream": True,
                  "max_tokens": 16 if i % 2 == 0 else 8},
        )
        if resp.status != 200:
            await resp.read()
            return {"ok": False, "status": resp.status, "tokens": 0}
        ttft = None
        n_tok = 0
        failed = False
        async for line in resp.content:
            if not line.strip():
                continue
            if ttft is None:
                ttft = time.perf_counter() - t0
            row = json.loads(line)
            if "error" in row:
                failed = True
                break
            if row.get("done"):
                n_tok = int(row.get("tokens_generated", 0))
                break
        return {"ok": not failed and n_tok > 0, "status": 200,
                "tokens": 0 if failed else n_tok, "ttft": ttft}
    except Exception:
        return {"ok": False, "status": -1, "tokens": 0}


async def run_arm(name: str, extra: dict, dev: dict) -> dict:
    overrides = {
        "MODEL_NAME": "gpt2",
        "BATCH_BUCKETS": "1,4",
        "SEQ_BUCKETS": "64",
        "MAX_DECODE_LEN": "16",
        "MAX_STREAMS": "4",
        "MAX_STREAM_QUEUE": "16",
        "WARMUP_SAMPLING": "0",  # greedy-only workload: halve warmup
        **extra,
        **dev,
    }
    async with ServiceUnderTest(overrides) as s:
        t0 = time.perf_counter()
        # Two waves: the second arrives while the schedule's faults are
        # landing on the first, so recovery economics show in BOTH
        # queued and in-flight streams.
        first = asyncio.gather(
            *(_one(s.client, i) for i in range(N_STREAMS // 2))
        )
        await asyncio.sleep(0.2)
        second = asyncio.gather(
            *(_one(s.client, i) for i in range(N_STREAMS // 2, N_STREAMS))
        )
        rows = (await first) + (await second)
        wall = time.perf_counter() - t0
        ok = [r for r in rows if r["ok"]]
        ttfts = [r["ttft"] for r in rows if r.get("ttft") is not None]
        return {
            "arm": name,
            "offered": N_STREAMS,
            "completed": len(ok),
            "failed": N_STREAMS - len(ok),
            "wall_s": round(wall, 2),
            "goodput_tok_s": round(sum(r["tokens"] for r in ok) / wall, 1),
            "p99_ttft_ms": round(pctile(ttfts, 0.99) * 1000, 1) if ttfts else None,
        }


async def main() -> None:
    dev = {"DEVICE": os.environ["DEVICE"]} if os.environ.get("DEVICE") else {}
    guarded = {
        "FAULT_SPEC": FAULT_SPEC,
        "DISPATCH_TIMEOUT_S": TIMEOUT_S,
        "DISPATCH_RETRIES": "2",
        "DISPATCH_BACKOFF_S": "0.02",
        "ENGINE_RESTARTS_MAX": "8",
        "SUPERVISE": "1",
    }
    bare = {
        "FAULT_SPEC": FAULT_SPEC,
        "DISPATCH_TIMEOUT_S": "0",
        "DISPATCH_RETRIES": "0",
        "SUPERVISE": "0",
    }
    rows = [
        await run_arm("clean", {}, dev),
        await run_arm("supervised", guarded, dev),
        await run_arm("unsupervised", bare, dev),
    ]

    import jax

    backend = jax.default_backend()
    print("\n| arm | completed | goodput tok/s | p99 TTFT (ms) | wall (s) |",
          file=sys.stderr)
    print("|---|---|---|---|---|", file=sys.stderr)
    for r in rows:
        print(
            f"| {r['arm']} | {r['completed']}/{r['offered']} "
            f"| {r['goodput_tok_s']} | {r['p99_ttft_ms']} | {r['wall_s']} |",
            file=sys.stderr,
        )
        print(json.dumps({**r, "fault_spec": FAULT_SPEC, "backend": backend}))


if __name__ == "__main__":
    asyncio.run(main())
