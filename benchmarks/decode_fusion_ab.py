"""Decode-fusion A/B: host syncs per token, tokens/s and decode TBT
vs DECODE_WINDOW ∈ {1, 2, 4, 8}.

The judged claim (ISSUE 7): with W chunks fused into one dispatch
(``lax.while_loop`` + on-device EOS early exit), the host submits and
fetches once per window instead of per chunk — so the measured
``dispatch_host_seconds{site="chunk"|"fetch"}`` call count per
generated token must drop ≥ W/2× vs W=1, with output token-identical
and interactive decode TBT p99 no worse while the auto policy governs.

Three measurements per W arm, same gpt2 service (random-init weights —
dispatch counts and cadence depend on shapes, not weights):

- **batch lane** (the fusion target): N batch-class streams
  (``X-Priority: batch``) decode concurrently; reported tokens/s,
  client-side TBT p50/p99 (gaps between ndjson chunk lines after the
  first), and host syncs/token from the ``/status.decode`` chunk+fetch
  dispatch-count deltas.
- **interactive lane** (the SLA guard): the same prompts as
  interactive streams under the SAME ``DECODE_WINDOW`` cap with the
  auto policy on — the governor must hold W=1, so TBT p99 must match
  the W=1 arm (fused windows would multiply it by ~W).
- **token identity**: the batch lane's token streams are compared
  across arms (every W serves the same sequences).

CPU honest-negative expectation: dispatch submit→return is ~free on a
synchronous local backend, so tokens/s is flat-to-noise here — the
wins this harness PINS on CPU are the host-sync divisor and the
interactive TBT guard; the tokens/s claim is for a chip run to verify
(not measured yet).

    DEVICE=cpu python benchmarks/decode_fusion_ab.py
    FUSION_AB_WINDOWS=1,4 python benchmarks/decode_fusion_ab.py

One JSON line per (arm, lane) to stdout, a markdown table to stderr.
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

WINDOWS = [
    int(w)
    for w in os.environ.get("FUSION_AB_WINDOWS", "1,2,4,8").split(",")
    if w.strip()
]
N_STREAMS = int(os.environ.get("FUSION_AB_N", "4"))
# Enough chunks per stream (24 at chunk=4) that the deep arms can
# amortize the per-stream constants (admission fetch, terminal
# boundary): at 12 chunks a W=8 window can only ever fire twice and
# the divisor saturates near 2x regardless of W.
MAX_TOKENS = int(os.environ.get("FUSION_AB_TOKENS", "96"))
PROMPTS = [
    "the quick brown fox",
    "pack my box with five dozen",
    "a third prompt",
    "and one more stream to fill the batch",
]


async def _stream_one(client, text: str, klass: str):
    headers = {"X-Priority": klass}
    t0 = time.perf_counter()
    resp = await client.post(
        "/predict",
        json={"text": text, "stream": True, "max_tokens": MAX_TOKENS},
        headers=headers,
    )
    assert resp.status == 200, await resp.text()
    stamps, tokens, text = [], 0, ""
    async for line in resp.content:
        stamps.append(time.perf_counter())
        msg = json.loads(line)
        if msg.get("done"):
            tokens = int(msg.get("decode_steps", 0))
            text = msg.get("prediction", {}).get("text", "")
            break
    gaps = [b - a for a, b in zip(stamps[1:-1], stamps[2:])]
    return {
        "wall": time.perf_counter() - t0,
        "tokens": tokens,
        "gaps": gaps,
        "out": (text, int(msg.get("tokens_generated", 0))),
    }


async def _decode_status(client) -> dict:
    resp = await client.get("/status")
    return (await resp.json()).get("decode", {})


async def _lane(client, klass: str, n: int) -> dict:
    before = await _decode_status(client)
    t0 = time.perf_counter()
    rows = await asyncio.gather(
        *(_stream_one(client, PROMPTS[i % len(PROMPTS)], klass)
          for i in range(n))
    )
    wall = time.perf_counter() - t0
    after = await _decode_status(client)
    b_counts, a_counts = before.get("dispatch_counts", {}), after.get(
        "dispatch_counts", {}
    )
    syncs = sum(
        a_counts.get(site, 0) - b_counts.get(site, 0)
        for site in ("chunk", "fetch")
    )
    tokens = sum(r["tokens"] for r in rows)
    gaps = [g for r in rows for g in r["gaps"]]
    return {
        "lane": klass,
        "streams": n,
        "tokens": tokens,
        "tokens_per_s": round(tokens / wall, 1) if wall else 0.0,
        "chunk_fetch_syncs": syncs,
        "host_syncs_per_token": round(syncs / tokens, 4) if tokens else None,
        "tbt_p50_ms": round(
            sorted(gaps)[len(gaps) // 2] * 1e3, 2
        ) if gaps else None,
        "tbt_p99_ms": round(pctile(gaps, 0.99) * 1e3, 2) if gaps else None,
        "window_dispatches": after.get("window_dispatches", 0)
        - before.get("window_dispatches", 0),
        "window_early_exits": after.get("window_early_exits", 0)
        - before.get("window_early_exits", 0),
        "outs": [r["out"] for r in rows],
    }


async def run_arm(w: int, dev: dict) -> list[dict]:
    overrides = {
        "MODEL_NAME": "gpt2",
        # One batch bucket + one seq bucket: every prompt here fits 64,
        # and a small warm grid keeps the per-arm service start cheap
        # enough for the 4-arm sweep on CPU.
        "BATCH_BUCKETS": "1",
        "SEQ_BUCKETS": "64",
        "MAX_DECODE_LEN": str(MAX_TOKENS),
        "STREAM_CHUNK_TOKENS": "4",
        "MAX_STREAMS": str(N_STREAMS),
        "MAX_STREAM_QUEUE": "16",
        "DECODE_WINDOW": str(w),
        **dev,
    }
    async with ServiceUnderTest(overrides) as s:
        batch = await _lane(s.client, "batch", N_STREAMS)
        interactive = await _lane(s.client, "interactive", 2)
        out = []
        for lane in (batch, interactive):
            outs = lane.pop("outs")
            out.append({"window": w, **lane, "_outs": outs})
        return out


async def main() -> None:
    dev = {"DEVICE": os.environ["DEVICE"]} if os.environ.get("DEVICE") else {}
    arms = []
    for w in WINDOWS:
        arms.extend(await run_arm(w, dev))

    # Token identity across arms, per lane (same prompts, same greedy
    # model -> every W must serve identical sequences).
    identical = True
    for lane in ("batch", "interactive"):
        seqs = [a["_outs"] for a in arms if a["lane"] == lane]
        identical &= all(s == seqs[0] for s in seqs[1:])

    import jax

    backend = jax.default_backend()
    print(
        "\n| W | lane | tokens/s | syncs/token | TBT p50 (ms) "
        "| TBT p99 (ms) | windows | early exits |",
        file=sys.stderr,
    )
    print("|---|---|---|---|---|---|---|---|", file=sys.stderr)
    for a in arms:
        a.pop("_outs")
        print(
            f"| {a['window']} | {a['lane']} | {a['tokens_per_s']} "
            f"| {a['host_syncs_per_token']} | {a['tbt_p50_ms']} "
            f"| {a['tbt_p99_ms']} | {a['window_dispatches']} "
            f"| {a['window_early_exits']} |",
            file=sys.stderr,
        )
        print(json.dumps({**a, "backend": backend,
                          "token_identical_across_arms": identical}))
    base = next(
        (a for a in arms if a["window"] == 1 and a["lane"] == "batch"), None
    )
    if base and base["host_syncs_per_token"]:
        for a in arms:
            if a["lane"] == "batch" and a["window"] > 1 and (
                a["host_syncs_per_token"]
            ):
                ratio = base["host_syncs_per_token"] / a["host_syncs_per_token"]
                print(
                    f"W={a['window']}: host syncs/token divided by "
                    f"{ratio:.2f}x (acceptance floor {a['window'] / 2:.1f}x)",
                    file=sys.stderr,
                )
    print(f"token identity across arms: {identical}", file=sys.stderr)


if __name__ == "__main__":
    asyncio.run(main())
