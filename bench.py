#!/usr/bin/env python
"""Headline benchmark: ResNet-50 ``/predict`` through the full stack
(HTTP → dynamic batcher → jitted engine on the chip).

Prints ONE JSON line:
  {"metric": "resnet50_predict_req_s_chip", "value": <req/s>,
   "unit": "req/s", "vs_baseline": <ratio vs torch-CPU on this box>, ...}

The judged metric is p50/p99 /predict latency + req/s/chip
(BASELINE.json:2).  The reference publishes no numbers (SURVEY.md §6),
so ``vs_baseline`` is measured against the reference's own inference
stack (torch eval-mode ResNet-50) run on this box's CPU — the only
reference path that exists in this environment.
"""

from __future__ import annotations

import asyncio
import io
import json
import math
import os
import statistics
import sys
import time

N_LATENCY = 40
N_THROUGHPUT = 192
CONCURRENCY = 64
TORCH_ITERS = 3
TORCH_BATCH = 8


def _png_bytes(size: int = 224) -> bytes:
    import numpy as np
    from PIL import Image

    rng = np.random.default_rng(0)
    img = Image.fromarray(rng.integers(0, 255, (size, size, 3), dtype=np.uint8))
    buf = io.BytesIO()
    img.save(buf, format="PNG")
    return buf.getvalue()


async def bench_serving() -> "tuple[dict, object]":
    from aiohttp.test_utils import TestClient, TestServer

    from mlmicroservicetemplate_tpu.serve import build_service

    overrides = {
        "MODEL_NAME": "resnet50",
        "WARMUP": "1",
        # Only the buckets this bench exercises: batch-1 latency path +
        # full dynamic batches under load.
        "BATCH_BUCKETS": os.environ.get("BATCH_BUCKETS", "1,8,32"),
        "LOG_LEVEL": "WARNING",
    }
    if os.environ.get("DEVICE"):
        overrides["DEVICE"] = os.environ["DEVICE"]
    cfg, bundle, engine, batcher, app = build_service(overrides)
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        for _ in range(2400):  # warmup compiles all buckets before ready
            resp = await client.get("/readyz")
            if resp.status == 200:
                break
            await asyncio.sleep(0.25)
        else:
            raise RuntimeError("service never became ready")
        png = _png_bytes()
        headers = {"Content-Type": "image/png"}

        # p50/p99: sequential single-image requests (config #1).
        lats = []
        for _ in range(N_LATENCY):
            t0 = time.perf_counter()
            resp = await client.post("/predict", data=png, headers=headers)
            assert resp.status == 200, await resp.text()
            await resp.json()
            lats.append(time.perf_counter() - t0)

        # req/s: concurrent load through the dynamic batcher (config #3).
        # THROUGHPUT_PASSES runs; every pass is reported and the
        # headline is their median.
        sem = asyncio.Semaphore(CONCURRENCY)

        async def one():
            async with sem:
                resp = await client.post("/predict", data=png, headers=headers)
                assert resp.status == 200
                await resp.read()

        walls = []
        for _ in range(int(os.environ.get("THROUGHPUT_PASSES", "3"))):
            t0 = time.perf_counter()
            await asyncio.gather(*(one() for _ in range(N_THROUGHPUT)))
            walls.append(time.perf_counter() - t0)
        wall = statistics.median(walls)

        # Per-site host dispatch accounting over everything served so
        # far (submit→return on the host; device time per site comes
        # from a profiler trace, not from here).
        attribution = {
            site: {"n": a["count"], "host_ms_avg": a["host_ms_avg"]}
            for site, a in engine.dispatch_attribution().items()
            if a["count"] > 0
        }
        import jax

        # Decode-fusion accounting (round 12): host syncs per generated
        # token — the quantity DECODE_WINDOW divides — plus the window
        # stats, recorded in every BENCH json (zero/None on the
        # non-generative resnet headline, populated when MODEL_NAME is
        # a decoder family).
        attrs = engine.dispatch_attribution()
        syncs = sum(
            attrs.get(site, {}).get("count", 0) for site in ("chunk", "fetch")
        )
        cdl = getattr(batcher, "_cdl", None)
        tokens = getattr(cdl, "tokens_emitted", 0) if cdl is not None else 0
        decode_fusion = {
            "host_syncs": syncs,
            "tokens": tokens,
            "host_syncs_per_token": round(syncs / tokens, 4) if tokens else None,
            "window_cap": getattr(cdl, "decode_window", 1) if cdl else 1,
            "window_dispatches": getattr(cdl, "window_dispatches", 0) if cdl else 0,
            "window_chunks": getattr(cdl, "window_chunks", 0) if cdl else 0,
            "window_early_exits": getattr(cdl, "window_early_exits", 0) if cdl else 0,
            "chain_depth": getattr(cdl, "chain_depth", None) if cdl else None,
        }

        # Host KV tier accounting (round 14): swap traffic across the
        # device/host boundary, how much of the resume prefetch
        # overlapped live decode, and host-tier prefix hits — in every
        # BENCH json like decode_fusion (zeros/None when the tier is
        # off or the headline model is non-generative).
        tier = getattr(engine, "kv_host", None)
        pf_total = getattr(cdl, "prefetch_blocks_total", 0) if cdl else 0
        pf_live = getattr(cdl, "prefetch_blocks_live", 0) if cdl else 0
        kv_tier = {
            "enabled": bool(tier is not None and tier.enabled),
            "swap_outs": getattr(cdl, "swap_outs", 0) if cdl else 0,
            "swap_resumes": getattr(cdl, "swap_ins", 0) if cdl else 0,
            "swap_fallbacks": getattr(cdl, "swap_fallbacks", 0) if cdl else 0,
            "swap_out_bytes": getattr(cdl, "swap_out_bytes", 0) if cdl else 0,
            "swap_in_bytes": getattr(cdl, "swap_in_bytes", 0) if cdl else 0,
            "prefetch_overlap_ratio": (
                round(pf_live / pf_total, 4) if pf_total else None
            ),
            "host_prefix_hits": getattr(
                cdl, "host_prefix_promotes", 0
            ) if cdl else 0,
            "host_pool": tier.stats() if tier is not None else None,
        }

        # Warm-up economics (round 19): per-phase warm seconds, the
        # executable-cache hit/miss counts and the process XLA compile
        # totals (docs/compilation.md).
        from mlmicroservicetemplate_tpu.runtime.compile_cache import (
            cache_stats,
            compile_counters,
            warm_stats,
        )

        comp = compile_counters()
        warmup_block = {
            "phases_s": warm_stats(),
            "executable_cache": cache_stats(),
            "xla_compiles": comp["count"],
            "xla_compile_s": round(comp["seconds"], 3),
            "host_prep": {
                "double": getattr(cdl, "host_prep_double", False) if cdl else False,
                "staged": getattr(cdl, "prep_staged", 0) if cdl else 0,
                "hits": getattr(cdl, "prep_hits", 0) if cdl else 0,
                "misses": getattr(cdl, "prep_misses", 0) if cdl else 0,
            },
        }

        # Perf observatory (round 20): the always-on device busy/bubble
        # + MFU estimate — the device-side numbers every BENCH json has
        # been missing since r05, now recorded WITHOUT the TRACE=1
        # serialization (utils/perfobs.py, docs/observability.md).
        perf_est = getattr(engine, "perf", None)
        perf_block = perf_est.snapshot() if perf_est is not None else {}
        perf_block.pop("device_busy_s", None)  # per-site detail stays
        # in /debug/perf; the json keeps the headline aggregates.

        return {
            "perf": perf_block,
            "p50_ms": round(statistics.median(lats) * 1000, 3),
            "p99_ms": round(
                sorted(lats)[max(0, math.ceil(len(lats) * 0.99) - 1)] * 1000, 3
            ),
            "req_s": round(N_THROUGHPUT / wall, 3),  # median pass
            "req_s_passes": [round(N_THROUGHPUT / w, 1) for w in walls],
            "platform": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            "device_count": len(jax.devices()),
            "n_devices": engine.replicas.n_devices,
            "dispatch_attribution": attribution,
            "decode_fusion": decode_fusion,
            "kv_tier": kv_tier,
            "warmup": warmup_block,
        }, engine
    finally:
        await client.close()


def bench_torch_cpu() -> float | None:
    """The reference's inference path (torch eval ResNet-50) on this
    box's CPU: images/s at the same batch size the batcher forms."""
    if os.environ.get("SKIP_TORCH_BASELINE"):
        return None
    try:
        import torch
        from transformers import ResNetConfig, ResNetForImageClassification
    except Exception as e:
        print(f"torch baseline unavailable: {e}", file=sys.stderr)
        return None
    try:
        with torch.no_grad():
            model = ResNetForImageClassification(ResNetConfig()).eval()
            x = torch.randn(TORCH_BATCH, 3, 224, 224)
            model(x)  # warm
            t0 = time.perf_counter()
            for _ in range(TORCH_ITERS):
                model(x)
            wall = time.perf_counter() - t0
        return TORCH_BATCH * TORCH_ITERS / wall
    except Exception as e:
        print(f"torch baseline failed: {e}", file=sys.stderr)
        return None


def bench_device_side(engine) -> dict:
    """Device-compute isolation + MFU.  A failure here fails the run:
    a headline without its device half is not the benchmark."""
    if os.environ.get("SKIP_DEVICE_BENCH"):
        return {}
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "benchmarks"))
    from device_bench import bench_device

    return bench_device(engine)


def require_device() -> None:
    """A measurement path that finds no chip fails; it does not fall
    back to the CPU.  ``DEVICE=cpu`` is the explicit way to ask for a
    CPU run (counts and control flow, never a device metric)."""
    import jax

    dev = jax.devices()
    print(json.dumps({
        "platform": dev[0].platform, "device_kind": dev[0].device_kind,
        "device_count": len(dev),
    }), file=sys.stderr)
    if dev[0].platform != "tpu" and os.environ.get("DEVICE", "").lower() != "cpu":
        sys.exit(
            f"bench.py: no TPU (platform={dev[0].platform!r}); set "
            "DEVICE=cpu to run the CPU path on purpose"
        )


def main() -> None:
    require_device()
    serving, engine = asyncio.run(bench_serving())
    device = bench_device_side(engine)
    torch_rps = bench_torch_cpu()
    result = {
        "metric": "resnet50_predict_req_s_chip",
        "value": serving["req_s"],
        "unit": "req/s",
        "vs_baseline": (
            round(serving["req_s"] / torch_rps, 3) if torch_rps else None
        ),
        **serving,
        **device,
        "torch_cpu_req_s": round(torch_rps, 3) if torch_rps else None,
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
