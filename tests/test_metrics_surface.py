"""Metrics/series drift guard (utils/metrics.py vs the /metrics scrape).

One smoke request per serving path — unary predict (the dynamic-batch
path), streaming (the continuous loop), and a shed — then one scrape,
asserting:

1. EVERY series declared in ``utils/metrics.py`` appears in the scrape
   (prometheus_client emits HELP/TYPE headers even before a labeled
   metric has children, so a renamed-or-deleted declaration can't
   silently vanish from dashboards).
2. The paths the smoke exercised actually produced samples for their
   core series (a declaration alone isn't observability).
3. Label cardinality stays bounded per family — a label that leaks
   request-unique values would blow up Prometheus, and this is the
   test that catches it before a dashboard does.
"""

import asyncio

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from mlmicroservicetemplate_tpu.api import build_app
from mlmicroservicetemplate_tpu.engine import InferenceEngine
from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
from mlmicroservicetemplate_tpu.scheduler import Batcher
from mlmicroservicetemplate_tpu.utils import metrics
from mlmicroservicetemplate_tpu.utils.config import ServiceConfig

from helpers import tiny_gpt_bundle

CARDINALITY_CAP = 40


def _declared_families() -> dict[str, object]:
    """Every metric object declared at module level in utils/metrics."""
    out = {}
    for attr in dir(metrics):
        obj = getattr(metrics, attr)
        name = getattr(obj, "_name", None)
        if isinstance(name, str) and hasattr(obj, "labels"):
            out[name] = obj
    return out


def _scrape_body() -> str:
    body, _ = metrics.render()
    return body.decode()


def _sample_lines(text: str):
    for line in text.splitlines():
        if line and not line.startswith("#"):
            yield line


def test_every_declared_series_present_and_bounded():
    if not metrics.HAVE_PROM:
        pytest.skip("prometheus_client not installed")

    async def main():
        cfg = ServiceConfig(
            device="cpu", warmup=False, batch_buckets=(1, 2, 4),
            seq_buckets=(16, 32), max_decode_len=8,
            stream_chunk_tokens=4, batch_timeout_ms=1.0, max_streams=2,
        )
        bundle = tiny_gpt_bundle()
        engine = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
        batcher = Batcher(engine, cfg)
        app = build_app(cfg, bundle, engine, batcher)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            for _ in range(200):
                if (await client.get("/readyz")).status == 200:
                    break
                await asyncio.sleep(0.05)
            # Path 1: unary predict — the dynamic-batch dispatch path.
            r = await client.post("/predict", json={"text": "hello batch"})
            assert r.status == 200
            # Path 2: streaming — the continuous decode loop.
            r = await client.post(
                "/predict", json={"text": "hello stream", "stream": True},
            )
            assert r.status == 200
            async for line in r.content:
                import json as _json

                if _json.loads(line).get("done"):
                    break
            # The record that says "done" is routed INSIDE the loop's
            # deliver phase, which closes after it, on the loop's
            # thread: the scrape waits for the phase as it waited for
            # readiness (alone in a process no earlier test has made
            # the gpt2 child, and a loaded worker lost the race).
            for _ in range(200):
                status = await (await client.get("/status")).json()
                if "loop/deliver" in status["decode"]["loop_time"]["phases"]:
                    break
                await asyncio.sleep(0.05)
            # Path 3: a shed — drain refuses admission with 503.
            batcher.begin_drain()
            r = await client.post("/predict", json={"text": "refused"})
            assert r.status == 503
            # /metrics itself.
            r = await client.get("/metrics")
            assert r.status == 200
            return await r.text()
        finally:
            await client.close()

    text = asyncio.run(main())

    # 1. Every declared family is present in the scrape.
    declared = _declared_families()
    assert len(declared) >= 25, "metric introspection broke"
    for name in declared:
        assert f"# HELP {name}" in text or f"# HELP {name}_" in text, (
            f"declared series {name!r} missing from /metrics"
        )

    # 2. The exercised paths produced samples for their core series.
    sampled = set()
    for line in _sample_lines(text):
        sampled.add(line.split("{")[0].split(" ")[0])
    for need in (
        "predict_requests_total", "predict_latency_seconds_count",
        "batch_queue_wait_seconds_count", "batch_size_count",
        "generated_tokens_total", "stream_ttft_seconds_count",
        "stream_tbt_seconds_count", "stream_batch_size_count",
        "dispatch_host_seconds_count", "requests_shed_total",
        # set-up reports itself (ISSUE 35): readiness set the boot's
        # phases, and every outcome x when child exists from the first
        # shared executable on (a warm boot reads 0, not nothing)
        "boot_phase_seconds", "xla_executables_total",
        "xla_executable_seconds_total",
        # every second of the decode loop has a name, a first token
        # closes stage by stage, the process reports its own pauses
        # (ISSUE 53): fed from the loop table at render time
        "loop_phase_seconds_total", "loop_unnamed_seconds_total",
        "stream_api_seconds_count", "stream_handoff_seconds_count",
        "event_loop_lag_seconds_count", "gc_pause_seconds_total",
    ):
        assert need in sampled, f"{need} has no samples after smoke"
    for phase in ("loop/queue_pop", "loop/wave_dispatch", "loop/deliver"):
        assert f'loop_phase_seconds_total{{model="gpt2",phase="{phase}"}}' in text
    for gen in "012":  # a generation that never ran reads 0, not nothing
        assert f'gc_pause_seconds_total{{generation="{gen}"}}' in text
    assert "idle_admit_wait_seconds" not in text  # PR 53: the table has it
    for phase in ("total", "unnamed"):
        assert f'boot_phase_seconds{{model="gpt2",phase="{phase}"}}' in text
    for outcome in ("compiled", "loaded"):
        for when in ("boot", "serving"):
            assert (f'xla_executables_total{{outcome="{outcome}",'
                    f'when="{when}"}}') in text
            for stage in ("trace", "lower", "backend"):
                assert (f'xla_executable_seconds_total{{outcome="{outcome}",'
                        f'stage="{stage}",when="{when}"}}') in text

    # 3. Bounded label cardinality per family.
    from collections import defaultdict

    combos = defaultdict(set)
    for line in _sample_lines(text):
        head = line.rsplit(" ", 1)[0]
        if "{" in head:
            fam, labels = head.split("{", 1)
        else:
            fam, labels = head, ""
        # Histogram buckets inflate sample counts, not label combos:
        # strip the le= pair before counting.
        labels = ",".join(
            kv for kv in labels.rstrip("}").split(",")
            if kv and not kv.startswith("le=")
        )
        base = fam
        for suffix in ("_bucket", "_count", "_sum", "_total", "_created"):
            if base.endswith(suffix):
                base = base[: -len(suffix)]
                break
        combos[base].add(labels)
    for fam, sets in combos.items():
        assert len(sets) <= CARDINALITY_CAP, (
            f"{fam} has {len(sets)} label combinations (cap "
            f"{CARDINALITY_CAP}) — unbounded label?"
        )

    # The shed carried its reason label.
    assert 'requests_shed_total{model="gpt2",reason="drain"}' in text


def test_fleet_scaling_series_present_after_scale_events():
    """Elastic-fleet observability (ISSUE 12 satellite): one manual
    scale-up + scale-down on an elastic fleet produces samples for
    ``fleet_replicas{state=...}`` (all four states declared, live
    tracking the roster), ``fleet_scale_events_total{dir,cause}`` and
    ``fleet_scale_duration_seconds`` in a real scrape."""
    if not metrics.HAVE_PROM:
        pytest.skip("prometheus_client not installed")
    from mlmicroservicetemplate_tpu.engine.fleet import ReplicaFleet

    cfg = ServiceConfig(
        device="cpu", warmup=False, batch_buckets=(1, 2, 4),
        seq_buckets=(16, 32), max_decode_len=8,
        stream_chunk_tokens=4, max_streams=2,
        fleet_replicas=1, fleet_max_replicas=2,
    )
    bundle = tiny_gpt_bundle()
    engine = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    fleet = ReplicaFleet(engine, cfg, autoscale_thread=False)
    try:
        assert fleet.scale_to(2, cause="manual") == 2
        assert fleet.scale_to(1, cause="manual") == 1
    finally:
        fleet.stop()
    text = _scrape_body()
    for name in ("fleet_replicas", "fleet_scale_events_total",
                 "fleet_scale_duration_seconds"):
        assert f"# HELP {name}" in text or f"# HELP {name}_" in text, (
            f"{name} missing from /metrics"
        )
    for state in ("live", "draining", "evicted", "spawning"):
        assert f'fleet_replicas{{model="gpt2",state="{state}"}}' in text, (
            f"fleet_replicas state {state!r} has no sample"
        )
    assert 'fleet_replicas{model="gpt2",state="live"} 1.0' in text
    assert ('fleet_scale_events_total'
            '{cause="manual",dir="up",model="gpt2"}') in text
    assert ('fleet_scale_events_total'
            '{cause="manual",dir="down",model="gpt2"}') in text
    up = [ln for ln in text.splitlines() if ln.startswith(
        'fleet_scale_duration_seconds_count{dir="up",model="gpt2"}'
    )]
    down = [ln for ln in text.splitlines() if ln.startswith(
        'fleet_scale_duration_seconds_count{dir="down",model="gpt2"}'
    )]
    assert up and float(up[0].rsplit(" ", 1)[1]) >= 1
    assert down and float(down[0].rsplit(" ", 1)[1]) >= 1


def test_job_series_present_after_bulk_smoke(tmp_path):
    """Bulk-lane observability (ISSUE 11 satellite): one tiny job
    through a JOBS_ENABLED app produces samples for the job series —
    ``jobs_active`` (gauge, back to 0 at completion),
    ``job_lines_total{state="completed"}`` counting every line, and the
    ``job_replays_total`` family declared for the startup-replay path."""
    if not metrics.HAVE_PROM:
        pytest.skip("prometheus_client not installed")

    async def main():
        cfg = ServiceConfig(
            device="cpu", warmup=False, batch_buckets=(1, 2, 4),
            seq_buckets=(16, 32), max_decode_len=8,
            stream_chunk_tokens=4, batch_timeout_ms=1.0, max_streams=2,
            journal_dir=str(tmp_path / "j"), journal_fsync="off",
            jobs_enabled=True, job_max_concurrent_lines=2,
        )
        bundle = tiny_gpt_bundle()
        engine = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
        batcher = Batcher(engine, cfg)
        app = build_app(cfg, bundle, engine, batcher)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            for _ in range(200):
                if (await client.get("/readyz")).status == 200:
                    break
                await asyncio.sleep(0.05)
            r = await client.post("/v1/batches", json={
                "lines": [{"text": "metrics line a"},
                          {"text": "metrics line b"}],
            })
            assert r.status == 201, await r.text()
            jid = (await r.json())["id"]
            import time

            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                body = await (
                    await client.get(f"/v1/batches/{jid}")
                ).json()
                if body["status"] == "completed":
                    break
                await asyncio.sleep(0.1)
            assert body["status"] == "completed", body
            r = await client.get("/metrics")
            return await r.text()
        finally:
            await client.close()

    text = asyncio.run(main())
    for name in ("jobs_active", "job_lines_total", "job_replays_total"):
        assert f"# HELP {name}" in text, f"{name} missing from /metrics"
    assert 'jobs_active{model="gpt2"} 0.0' in text
    line_samples = [
        ln for ln in text.splitlines()
        if ln.startswith('job_lines_total{model="gpt2",state="completed"}')
    ]
    assert line_samples and float(line_samples[0].rsplit(" ", 1)[1]) >= 2


def test_tenant_series_bounded_topk_plus_other_and_anon():
    """Multi-tenancy observability (ISSUE 17 satellite): the ``tenant``
    label is BOUNDED — the first TENANT_METRICS_TOPK configured tenants
    keep their names, everything past the cap exports as ``other`` and
    keyless traffic as ``anon`` — and every tenancy family declares at
    most 3 labels (the repo-wide cardinality discipline)."""
    if not metrics.HAVE_PROM:
        pytest.skip("prometheus_client not installed")
    from mlmicroservicetemplate_tpu.tenancy.accounts import (
        TenantRegistry,
        TenantSpec,
    )

    for fam in (metrics.TENANT_SHED, metrics.TENANT_KV,
                metrics.TENANT_TOKENS, metrics.TENANT_SLO_BURN,
                metrics.ADAPTER_SLOTS):
        assert len(fam._labelnames) <= 3, fam._name

    specs = [TenantSpec(name=f"t{i:02d}", api_keys=(f"k{i}",))
             for i in range(12)]
    reg = TenantRegistry(specs, model="bound-check", topk=2)
    for s in specs:
        reg.note_shed(s.name, "queue_full")
        lease = reg.admit(s, tokens=5, kv_bytes=64)
        reg.release(lease)
    reg.note_shed("", "deadline")  # keyless traffic

    text = _scrape_body()
    values = set()
    for line in text.splitlines():
        if line.startswith("tenant_requests_shed_total{") and (
            'model="bound-check"' in line
        ):
            labels = line.split("{", 1)[1].split("}", 1)[0]
            for kv in labels.split(","):
                if kv.startswith("tenant="):
                    values.add(kv.split("=", 1)[1].strip('"'))
    assert values == {"t00", "t01", "other", "anon"}, values


def test_recurrent_state_families_are_declared_and_a_row_ledger_feeds_them():
    """The families of a model with recurrent layers (PR 40): declared
    (so ``/metrics`` carries their HELP lines for every model), bounded
    (``state`` is live | prefill | free), and fed by the loop's row ledger
    — ``tests/test_nemotron_serving.py`` drives the loop itself."""
    declared = _declared_families()
    names = {"ssm_state_bytes", "ssm_state_rows", "ssm_scan_tokens",
             "ssm_scan_masked_tokens", "ssm_scan_fused_tokens",
             "ssm_state_recomputes"}
    assert names <= set(declared), names - set(declared)
    for state in ("live", "prefill", "free"):
        metrics.SSM_STATE_ROWS.labels("surface-check", state).set(1)
    metrics.SSM_STATE_BYTES.labels("surface-check").set(2 * 21278720)
    metrics.SSM_SCAN_TOKENS.labels("surface-check").inc(3072)
    metrics.SSM_SCAN_MASKED.labels("surface-check").inc(40)
    metrics.SSM_SCAN_FUSED.labels("surface-check").inc(3072)
    metrics.SSM_STATE_RECOMPUTES.labels("surface-check").inc()
    text = _scrape_body()
    for line in ('ssm_state_rows{model="surface-check",state="prefill"} 1.0',
                 'ssm_state_bytes{model="surface-check"} 4.255744e+07',
                 'ssm_scan_tokens_total{model="surface-check"} 3072.0',
                 'ssm_scan_masked_tokens_total{model="surface-check"} 40.0',
                 'ssm_scan_fused_tokens_total{model="surface-check"} 3072.0',
                 'ssm_state_recomputes_total{model="surface-check"} 1.0'):
        assert line in text, line
    states = {ln.split('state="')[1].split('"')[0]
              for ln in _sample_lines(text) if ln.startswith("ssm_state_rows{")}
    assert states <= {"live", "prefill", "free"}


def test_expert_rows_family_is_declared_and_bounded():
    """``moe_rows_total`` (PR 52): declared (so ``/metrics`` carries its
    HELP line for every model), three labels, ``kind`` decode | prefill
    and ``state`` ran | skipped — ``tests/test_moe.py`` holds the loop's
    counting to the ladder's rule, ``tests/test_nemotron_serving.py``
    drives the loop itself."""
    assert "moe_rows" in _declared_families()
    assert metrics.MOE_ROWS._labelnames == ("model", "kind", "state")
    for kind, state, n in (("decode", "ran", 704), ("decode", "skipped", 0),
                           ("prefill", "ran", 21120), ("prefill", "skipped", 46464)):
        metrics.MOE_ROWS.labels("surface-check", kind, state).inc(n)
    text = _scrape_body()
    for line in (
            'moe_rows_total{kind="prefill",model="surface-check",state="skipped"} 46464.0',
            'moe_rows_total{kind="decode",model="surface-check",state="skipped"} 0.0'):
        assert line in text, line
    seen = {(ln.split('kind="')[1].split('"')[0], ln.split('state="')[1].split('"')[0])
            for ln in _sample_lines(text) if ln.startswith("moe_rows_total{")}
    assert seen <= {(k, s) for k in ("decode", "prefill") for s in ("ran", "skipped")}


def test_fused_expert_rows_family_is_declared_and_bounded():
    """``moe_rows_fused_total`` (PR 57): declared, two labels, ``kind``
    decode | prefill as ``moe_rows_total`` — ``tests/test_moe.py`` holds the
    loop's counting to the shape rule, ``tests/test_nemotron_serving.py``
    drives the loop itself."""
    assert "moe_rows_fused" in _declared_families()
    assert metrics.MOE_ROWS_FUSED._labelnames == ("model", "kind")
    metrics.MOE_ROWS_FUSED.labels("surface-check", "prefill").inc(15360)
    metrics.MOE_ROWS_FUSED.labels("surface-check", "decode").inc(0)
    text = _scrape_body()
    for line in ('moe_rows_fused_total{kind="prefill",model="surface-check"} 15360.0',
                 'moe_rows_fused_total{kind="decode",model="surface-check"} 0.0'):
        assert line in text, line
    seen = {ln.split('kind="')[1].split('"')[0]
            for ln in _sample_lines(text) if ln.startswith("moe_rows_fused_total{")}
    assert seen <= {"decode", "prefill"}
