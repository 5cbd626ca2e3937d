"""Replica-DP scaling curve on the 8-way virtual CPU mesh.

Round-1 verdict: the pre-round BASELINE record (removed in PR 22) row 4
labeled a 1-device number as the
multi-replica config. This script produces the honest curve: the same
bert-base engine at replicas {1, 2, 4, 8} on a virtual CPU mesh,
fixed total batch, engine-level dispatch (no HTTP noise).

IMPORTANT caveat, printed with the result: the 8 virtual devices share
this box's ONE physical vCPU, so wall-clock cannot speed up with
replica count. What the curve demonstrates is (a) the sharded path is
correct at every width and (b) the sharding/collective overhead XLA
adds per width — the multi-chip speedup claim rides on real ICI
hardware, which this environment does not have (SURVEY.md §7.1).

    python benchmarks/replica_scaling.py
"""

from __future__ import annotations

import json
import os
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import numpy as np  # noqa: E402

TOTAL_BATCH = 32
REPS = 6


def main() -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")

    from mlmicroservicetemplate_tpu.engine import InferenceEngine
    from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
    from mlmicroservicetemplate_tpu.utils.config import ServiceConfig

    from mlmicroservicetemplate_tpu.models.registry import build_model

    bundle = build_model(
        ServiceConfig(device="cpu", model_name="bert-base", warmup=False)
    )
    rows = []
    feats = [
        {"input_ids": np.ones(64, np.int32), "length": np.int32(64)}
        for _ in range(TOTAL_BATCH)
    ]
    for r in (1, 2, 4, 8):
        cfg = ServiceConfig(
            device="cpu", warmup=False, batch_buckets=(TOTAL_BATCH,),
            seq_buckets=(64,), replicas=r,
        )
        eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(r)))
        eng.run_batch(list(feats))  # compile
        t0 = time.perf_counter()
        for _ in range(REPS):
            eng.run_batch(list(feats))
        wall = time.perf_counter() - t0
        rows.append(
            {"replicas": r, "req_s": round(REPS * TOTAL_BATCH / wall, 1),
             "batch_ms": round(wall / REPS * 1000, 1)}
        )
    base = rows[0]["req_s"]
    for row in rows:
        row["rel_vs_1"] = round(row["req_s"] / base, 3)
    print(json.dumps({
        "note": ("8 virtual devices share 1 physical vCPU: rel_vs_1 measures "
                 "sharding overhead, not speedup; ICI speedup needs real chips"),
        "total_batch": TOTAL_BATCH,
        "rows": rows,
    }))
    # Fleet streaming goodput vs FLEET_REPLICAS (the ISSUE-8 satellite:
    # aggregate goodput vs R under the overload_ab traffic shape —
    # FLEET_RS override, FLEET_SCALING=0 skips).  Same caveat: one
    # physical vCPU, so the curve demonstrates routing/ledger
    # correctness and per-replica overhead, not speedup.
    if os.environ.get("FLEET_SCALING", "1").lower() not in ("0", "false", "no"):
        fleet_goodput()


def fleet_goodput() -> None:
    """Aggregate streaming goodput (tok/s over completed streams) for
    FLEET_REPLICAS in FLEET_RS, bursty interactive-heavy traffic
    (overload_ab's shape: a wave of short prompts, mixed budgets)."""
    import asyncio
    import sys as _sys

    _here = os.path.dirname(os.path.abspath(__file__))
    _sys.path.insert(0, _here)
    from harness import ServiceUnderTest  # noqa: E402

    rs = [int(x) for x in os.environ.get("FLEET_RS", "1,2,4").split(",")]
    n_streams = int(os.environ.get("FLEET_SCALING_N", "8"))

    async def one(client, i):
        t0 = time.perf_counter()
        resp = await client.post(
            "/predict",
            json={"text": f"stream {i} the quick brown fox", "stream": True,
                  "max_tokens": 16 if i % 2 == 0 else 8},
        )
        if resp.status != 200:
            await resp.read()
            return 0, None
        n_tok = 0
        async for line in resp.content:
            if not line.strip():
                continue
            row = json.loads(line)
            if row.get("done"):
                n_tok = int(row.get("tokens_generated", 0))
                break
            if "error" in row:
                return 0, None
        return n_tok, time.perf_counter() - t0

    async def arm(r):
        async with ServiceUnderTest({
            "MODEL_NAME": "gpt2", "BATCH_BUCKETS": "1,4",
            "SEQ_BUCKETS": "64", "MAX_DECODE_LEN": "16",
            "MAX_STREAMS": "4", "MAX_STREAM_QUEUE": "16",
            # Each fleet replica owns a single-device placement —
            # engines must not share a sharded mesh (collective
            # interleaving; gated at fleet construction).
            "REPLICAS": "1",
            "FLEET_REPLICAS": str(r), "WARMUP_SAMPLING": "0",
            **({"DEVICE": os.environ["DEVICE"]}
               if os.environ.get("DEVICE") else {}),
        }) as s:
            t0 = time.perf_counter()
            out = await asyncio.gather(
                *(one(s.client, i) for i in range(n_streams))
            )
            wall = time.perf_counter() - t0
            toks = sum(t for t, _ in out)
            return {
                "fleet_replicas": r,
                "streams": n_streams,
                "completed": sum(1 for t, _ in out if t > 0),
                "goodput_tok_s": round(toks / wall, 1),
                "wall_s": round(wall, 2),
            }

    frows = [asyncio.run(arm(r)) for r in rs]
    print(json.dumps({
        "note": ("fleet goodput vs R on ONE physical vCPU: flat-to-down "
                 "is expected locally (replicas contend for the same "
                 "core); the curve pins correctness + per-replica "
                 "overhead, the speedup claim needs real chips"),
        "rows": frows,
    }))


if __name__ == "__main__":
    main()
