"""Run the full benchmark table (five configs) and every A/B script.

    python benchmarks/run_all.py              # on the chip (DEVICE=tpu)
    DEVICE=cpu python benchmarks/run_all.py   # CPU sanity run

One process per chip: this parent never imports JAX.  The config table
(``--table``) and each A/B script run as CHILD processes, one after
another, each holding the chip alone from start to exit; a child that
fails fails the run (``check=True``) — on a machine where the chip
belongs to one process at a time, a parent that held it would make
every child fail or hang, and a swallowed exit code would hide it.

The table child writes one JSON line per config to stdout and a
markdown table to stderr.  ``bench.py`` at the repo root stays the
headline (config 3); this harness is the complete surface:

  1. ResNet-50 single-image /predict       -> p50/p99
  2. BERT-base text /predict, batch=1      -> p50/p99
  3. ResNet-50 dynamic batching, max_batch -> req/s/chip
  4. BERT-base replica serving             -> req/s over all devices
  5. T5-small streaming seq2seq            -> TTFT, chunks/s
  6. gpt2 streaming causal-LM              -> TTFT, chunks/s
"""

from __future__ import annotations

import asyncio
import json
import os
import subprocess
import sys

_here = os.path.dirname(os.path.abspath(__file__))

# (skip knob, script): each A/B is its own process.  <KNOB>=0 skips.
AB_SCRIPTS = (
    ("COMPOSE_AB", "compose_ab.py"),  # stacked decode levers vs each single one
    ("OVERLOAD_AB", "overload_ab.py"),  # SLA scheduler vs FIFO at 1x/2x/4x load
    ("KV_AB", "kv_occupancy_ab.py"),  # paged-KV occupancy at fixed KV_BUDGET_MB
    ("FAULT_AB", "fault_recovery_ab.py"),  # supervised vs unsupervised faults
    ("PREFILL_AB", "prefill_interference_ab.py"),  # chunked vs monolithic prefill
    ("FUSION_AB", "decode_fusion_ab.py"),  # host syncs/token vs DECODE_WINDOW
    ("TIER_AB", "kv_tier_ab.py"),  # host-RAM swap vs recompute checkpoints
    ("CRASH_AB", "crash_resume_ab.py"),  # SIGKILL recovery, journal vs none
    ("JOBS_AB", "bulk_jobs_ab.py"),  # /v1/batches backfill vs interactive-only
    ("FLEET_AB", "replica_failover_ab.py"),  # replica kill + failover
    ("PERFOBS_AB", "perf_obs_ab.py"),  # PERF_OBS on vs off, interleaved
    ("PALLAS_AB", "pallas_ab.py"),  # autotuned vs default kernel, fused attention
    ("SCALE_AB", "autoscale_ab.py"),  # static R=1 vs elastic [1..3]
    ("DEVLOSS_AB", "device_loss_ab.py"),  # fleet-with-spare vs single TP group
    ("TENANT_AB", "tenant_fairness_ab.py"),  # fair-share vs class-weighted EDF
    ("TP_AB", "tp_scaling_ab.py"),  # TP in {1,2} x {dense,int8-KV} decode step
)


async def table() -> None:
    """The config table, in THIS process (the ``--table`` child)."""
    sys.path.insert(0, _here)
    sys.path.insert(0, os.path.dirname(_here))  # repo root, for the package
    from harness import ServiceUnderTest, png_bytes, post_image, post_text
    from perf_ledger import append_row, structural_counters

    def _ledger(config: str, s) -> None:
        # One structural-counter row per measured config (counters, not
        # wall-clock — benchmarks/perf_ledger.py).
        cdl = getattr(s.batcher, "_cdl", None) if s.batcher is not None else None
        append_row(config, structural_counters(s.engine, cdl))

    rows = []
    dev = {"DEVICE": os.environ["DEVICE"]} if os.environ.get("DEVICE") else {}
    png = png_bytes()

    async with ServiceUnderTest(
        {"MODEL_NAME": "resnet50", "BATCH_BUCKETS": "1,8,32", **dev}
    ) as s:
        r1 = await s.latency(post_image(png))
        rows.append({"config": "resnet50 single-image latency", **r1})
        r3 = await s.throughput(post_image(png))
        rows.append({"config": "resnet50 dynamic batching max_batch=32", **r3})
        _ledger("resnet50 dynamic batching", s)

    async with ServiceUnderTest(
        {"MODEL_NAME": "bert-base", "BATCH_BUCKETS": "1,8,32", "SEQ_BUCKETS": "32,128", **dev}
    ) as s:
        r2 = await s.latency(post_text("a short benchmark sentence"))
        rows.append({"config": "bert-base batch=1 latency", **r2})
        n_dev = s.engine.replicas.n_devices
        r4 = await s.throughput(post_text("a short benchmark sentence"))
        rows.append(
            {"config": f"bert-base replica serving ({n_dev} device)", **r4}
        )
        _ledger("bert-base replica serving", s)

    async with ServiceUnderTest(
        {
            "MODEL_NAME": "t5-small",
            "BATCH_BUCKETS": "1,8",
            "SEQ_BUCKETS": "32,64",
            "MAX_DECODE_LEN": "32",
            **dev,
        }
    ) as s:
        r5 = await s.stream_stats("summarize: the quick brown fox jumps over the lazy dog")
        rows.append({"config": "t5-small streaming seq2seq", **r5})
        _ledger("t5-small streaming", s)

    async with ServiceUnderTest(
        {
            "MODEL_NAME": "gpt2",
            "BATCH_BUCKETS": "1,8",
            "SEQ_BUCKETS": "64",
            "MAX_DECODE_LEN": "32",
            **dev,
        }
    ) as s:
        r6 = await s.stream_stats("the quick brown fox jumps over the lazy dog and")
        rows.append({"config": "gpt2 streaming causal-LM", **r6})
        _ledger("gpt2 streaming", s)

    # The flagship generative config: llama at TinyLlama-1.1B dims,
    # int8 weights (the measured recommendation at this scale).
    async with ServiceUnderTest(
        {
            "MODEL_NAME": "llama",
            "QUANTIZE": "int8",
            "BATCH_BUCKETS": "1,8",
            "SEQ_BUCKETS": "64",
            "MAX_DECODE_LEN": "32",
            **dev,
        }
    ) as s:
        r7 = await s.stream_stats("the quick brown fox jumps over the lazy dog and")
        rows.append({"config": "llama-1.1B int8 streaming causal-LM", **r7})
        _ledger("llama int8 streaming", s)

    import jax

    d = jax.devices()
    device = {"platform": d[0].platform, "device_kind": d[0].device_kind,
              "device_count": len(d)}
    print("\n| config | metrics | platform |", file=sys.stderr)
    print("|---|---|---|", file=sys.stderr)
    for row in rows:
        metrics = ", ".join(f"{k}={v}" for k, v in row.items() if k != "config")
        print(f"| {row['config']} | {metrics} | {d[0].platform} |",
              file=sys.stderr)
        print(json.dumps({**row, **device}))


def main() -> None:
    """The parent: no JAX here, children one at a time, failures fatal."""
    assert "jax" not in sys.modules, "run_all's parent must stay off JAX"
    subprocess.run([sys.executable, os.path.abspath(__file__), "--table"],
                   check=True)
    for knob, script in AB_SCRIPTS:
        if os.environ.get(knob, "1").lower() in ("0", "false", "no"):
            continue
        subprocess.run([sys.executable, os.path.join(_here, script)],
                       check=True)


if __name__ == "__main__":
    if sys.argv[1:] == ["--table"]:
        asyncio.run(table())
    else:
        main()
