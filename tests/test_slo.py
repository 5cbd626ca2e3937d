"""SLO burn rates, latency buckets and the fleet's merged flight view.

1. **Burn-rate math**: SLOTracker windows/budgets with an injected
   clock; the all-zero default builds no tracker; the governor's
   SCALE_UP_SLO_BURN signal is off (bit-identical) when unset.
2. **Metrics surface**: the burn-rate series produce real samples
   after a smoke workload (the declaration-introspection pin in
   test_metrics_surface.py covers presence; this covers samples).
3. **One instrument**: after a served stream ``/metrics`` carries no
   family that names a host-clock estimate as device time, and
   ``/status`` carries the SLO snapshot at ``slo``.
4. **Fleet**: one shared tracker, and ``/debug/engine?all=1`` merges
   every replica's flight ring into one replica-tagged timeline.
"""

from __future__ import annotations

import asyncio
import json

import numpy as np
import pytest

from mlmicroservicetemplate_tpu.engine import InferenceEngine
from mlmicroservicetemplate_tpu.engine.streams import ContinuousDecodeLoop
from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
from mlmicroservicetemplate_tpu.scheduler.policy import (
    BATCH,
    INTERACTIVE,
    ScalingGovernor,
    SLOTracker,
)
from mlmicroservicetemplate_tpu.utils import metrics
from mlmicroservicetemplate_tpu.utils.config import ServiceConfig

from helpers import tiny_gpt_bundle


def _cfg(**kw) -> ServiceConfig:
    base = dict(
        device="cpu", warmup=False, batch_buckets=(1, 2),
        seq_buckets=(8,), max_decode_len=16, stream_chunk_tokens=4,
        max_streams=2, stream_pipeline=1,
    )
    base.update(kw)
    return ServiceConfig(**base)


def _run_streams(cdl) -> int:
    async def one(seed: int):
        feats = {
            "input_ids": np.arange(1, 9, dtype=np.int32) + seed,
            "length": np.int32(8),
            "max_tokens": 16,
        }
        out = []
        async for chunk in cdl.submit_stream(feats):
            out.extend(chunk.tolist())
        return out

    async def drive():
        return [await one(i) for i in range(2)]

    outs = asyncio.run(drive())
    return sum(len(o) for o in outs)


def _workload(cfg) -> tuple:
    bundle = tiny_gpt_bundle()
    engine = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    cdl = ContinuousDecodeLoop(engine, cfg)
    cdl.warm()
    try:
        tokens = _run_streams(cdl)
    finally:
        cdl.stop()
    return cdl, tokens


# ---------------------------------------------------------------------------
# 1: SLO burn-rate math


def _tracker(clock, **kw):
    objectives = {
        ("ttft", INTERACTIVE): 0.5,
        ("tbt", INTERACTIVE): 0.1,
        ("ttft", BATCH): 5.0,
    }
    return SLOTracker(
        "slo-model", objectives, target=kw.pop("target", 0.9),
        windows_s=kw.pop("windows_s", (60.0, 600.0)), clock=clock,
    )


def test_slo_burn_rate_math_with_injected_clock():
    now = [1000.0]
    t = _tracker(lambda: now[0])
    # 8 good + 2 bad TTFTs: bad fraction 0.2, budget 0.1 → burn 2.0.
    for _ in range(8):
        t.note("ttft", INTERACTIVE, 0.1)
    for _ in range(2):
        t.note("ttft", INTERACTIVE, 1.0)
    assert t.burn_rate("ttft", INTERACTIVE) == pytest.approx(2.0)
    # All good → burn 0; no samples → burn 0.
    assert t.burn_rate("tbt", INTERACTIVE) == 0.0
    assert t.burn_rate("ttft", BATCH) == 0.0
    assert t.worst_burn() == pytest.approx(2.0)
    # The fast window forgets: advance past it, note one good sample —
    # the old bad samples age out of the fast window but stay in slow.
    now[0] += 120.0
    t.note("ttft", INTERACTIVE, 0.1)
    assert t.burn_rate("ttft", INTERACTIVE, 60.0) == 0.0
    assert t.burn_rate("ttft", INTERACTIVE, 600.0) == pytest.approx(
        (2 / 11) / 0.1
    )
    # Gauges carry the same numbers.
    t.export_gauges()
    if metrics.HAVE_PROM:
        text = metrics.render()[0].decode()
        assert 'slo_ttft_burn_rate{klass="interactive",model="slo-model",window="fast"} 0.0' in text


def test_slo_tracker_disabled_by_default():
    assert SLOTracker.from_cfg("m", _cfg()) is None
    t = SLOTracker.from_cfg("m", _cfg(slo_ttft_ms=500.0))
    assert t is not None
    assert t.objectives == {("ttft", INTERACTIVE): 0.5}


def test_governor_slo_signal_off_is_bit_identical():
    base = dict(live=2, queued=0, active=1, slots=8)
    g0 = ScalingGovernor(1, 4, clock=lambda: 0.0)
    g1 = ScalingGovernor(1, 4, up_slo_burn=2.0, clock=lambda: 0.0)
    # Unset (default 0): a huge burn value changes nothing.
    assert g0.decide(**base, slo_burn=99.0) == (None, "steady")
    # Set: the same inputs scale up with cause "slo".
    assert g1.decide(**base, slo_burn=2.5) == ("up", "slo")
    assert g1.decide(**base, slo_burn=1.9) == (None, "steady")


# ---------------------------------------------------------------------------
# 2: metrics surface samples after a real workload


def test_new_series_sample_after_workload():
    if not metrics.HAVE_PROM:
        pytest.skip("prometheus_client not installed")
    cdl, tokens = _workload(_cfg(slo_ttft_ms=60000.0, slo_tbt_ms=60000.0))
    assert tokens == 32
    text = metrics.render()[0].decode()
    assert 'slo_ttft_burn_rate{klass="interactive",model="gpt2"' in text
    assert 'slo_tbt_burn_rate{klass="interactive",model="gpt2"' in text
    assert cdl.slo is not None
    s = cdl.slo.snapshot()
    assert s["burn"]["ttft:interactive:fast"] == 0.0  # 60 s budget: all good


def test_latency_buckets_knob_validated_and_extended_defaults():
    # Defaults extend past the old 10 s ceiling (the r11 negative).
    assert max(metrics._DEFAULT_LATENCY_BUCKETS) > 10.0
    assert max(metrics._FINE_BUCKETS) > 10.0
    # Strict config validation...
    with pytest.raises(Exception):
        ServiceConfig(latency_buckets="1,0.5")  # not ascending
    with pytest.raises(Exception):
        ServiceConfig(latency_buckets="0,-1")
    assert ServiceConfig(
        latency_buckets="0.1,1,10,60"
    ).latency_buckets == "0.1,1,10,60"
    # ...and the lenient import-time parser mirrors it.
    assert metrics.parse_buckets("1,0.5") is None
    assert metrics.parse_buckets("0.1,1,60") == (0.1, 1.0, 60.0)
    assert metrics.parse_buckets(None) is None


# ---------------------------------------------------------------------------
# 3 + 4: what /metrics, /status and /debug/engine serve after a stream


def _serve_one_stream(cfg, *paths):
    """Boot the app over a tiny GPT, serve one stream through the
    continuous loop, GET each of ``paths``: (batcher, bodies)."""
    from aiohttp.test_utils import TestClient, TestServer

    from mlmicroservicetemplate_tpu.api import build_app
    from mlmicroservicetemplate_tpu.scheduler import Batcher

    bundle = tiny_gpt_bundle()
    engine = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    batcher = Batcher(engine, cfg)

    async def main():
        app = build_app(cfg, bundle, engine, batcher)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            for _ in range(200):
                if (await client.get("/readyz")).status == 200:
                    break
                await asyncio.sleep(0.05)
            # ≤ 8 byte-level tokens: stays inside the seq bucket so the
            # stream runs through the continuous loop (flight frames).
            r = await client.post(
                "/predict", json={"text": "hifleet", "stream": True},
            )
            assert r.status == 200
            async for line in r.content:
                if json.loads(line).get("done"):
                    break
            bodies = []
            for path in paths:
                r = await client.get(path)
                assert r.status == 200, path
                bodies.append(await r.text())
            return bodies
        finally:
            await client.close()

    return batcher, asyncio.run(main())


def test_no_estimated_device_series():
    """Device time is the profiler trace's: nothing on /metrics or
    /status gives a host-clock estimate a device's name, and the SLO
    snapshot is served at ``/status.slo``."""
    if not metrics.HAVE_PROM:
        pytest.skip("prometheus_client not installed")
    batcher, (text, status) = _serve_one_stream(
        _cfg(slo_ttft_ms=60000.0), "/metrics", "/status")
    families = {
        line.split()[2] for line in text.splitlines()
        if line.startswith("# TYPE ")
    }
    assert "dispatch_host_seconds" in families  # the scrape is real
    estimated = sorted(
        f for f in families
        if f.startswith(("device_busy", "device_bubble", "modeled_flops",
                         "mfu"))
    )
    assert not estimated
    status = json.loads(status)
    assert "perf" not in status
    assert status["slo"] == batcher._cdl.slo.snapshot()
    assert status["slo"]["objectives_ms"] == {"ttft:interactive": 60000.0}


def test_fleet_debug_engine_all_merges_replicas():
    cfg = _cfg(fleet_replicas=2, slo_ttft_ms=60000.0)
    batcher, (merged, status) = _serve_one_stream(
        cfg, "/debug/engine?all=1", "/status")
    # The fleet shares ONE tracker (a degraded replica must not hide
    # behind healthy siblings' windows).
    fleet = batcher.fleet
    assert fleet is not None
    slos = {id(rep.cdl.slo) for rep in fleet.replicas}
    assert len(slos) == 1 and None not in slos
    merged, status = json.loads(merged), json.loads(status)
    assert merged["fleet"] is True
    assert set(merged["replicas"]) == {"0", "1"}
    tags = {e["replica"] for e in merged["timeline"] if "replica" in e}
    assert tags, "merged timeline carries no replica-tagged entries"
    # Timeline is time-sorted.
    ts = [e["t"] for e in merged["timeline"] if "t" in e]
    assert ts == sorted(ts)
    assert all(
        "dispatch_attribution" in r for r in merged["replicas"].values())
    # The shared tracker's snapshot, where a single engine serves its own.
    assert "perf" not in status
    assert status["slo"] == fleet.replicas[0].cdl.slo.snapshot()
