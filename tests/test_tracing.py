"""Span tracing + flight recorder (utils/tracing.py).

The judged contracts:
1. A single streaming request under TRACE=1 yields spans for every
   stage — admission, queue-wait, prefill windows, decode chunks,
   dispatch sites with host-vs-device attribution — all correlated by
   request id, with dispatch spans PARENTED under their stage spans,
   and the stage spans tile the stream's lifetime (span sum ≈
   end-to-end latency within tolerance).
2. The Chrome trace-event export is schema-valid (Perfetto-loadable).
3. TRACE=0 is zero-overhead: no Span object is ever constructed on
   the serving path.
4. Spans survive checkpoint-resume (fatal fault mid-decode) with the
   SAME request id — the resumed stream gets its own queue-wait span —
   and the flight recorder dumps automatically on the fatal fault.
5. The flight recorder captures loop iterations (slot occupancy, KV
   pool state) and scheduling/fault events (retries, requeues).
6. The SAME phase names land on the profiler's host plane whenever a
   ``jax.profiler`` session runs (TRACE=0 included), as flat siblings
   on the loop thread; TRACE=1 never waits for the device; the
   wave/queue counters observe where the work happens; the lowered
   paged decode chunk carries the model's scope names.
"""

import asyncio
import glob
import os
import time

import numpy as np
import pytest

from mlmicroservicetemplate_tpu.engine import InferenceEngine
from mlmicroservicetemplate_tpu.engine.streams import ContinuousDecodeLoop
from mlmicroservicetemplate_tpu.engine.supervisor import Supervisor
from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
from mlmicroservicetemplate_tpu.utils import tracing
from mlmicroservicetemplate_tpu.utils.config import ServiceConfig

from helpers import one_wave, tiny_gpt_bundle, tiny_llama_bundle, text_feats


def _cfg(**kw) -> ServiceConfig:
    kw.setdefault("device", "cpu")
    kw.setdefault("warmup", False)
    kw.setdefault("batch_buckets", (1, 2, 4))
    kw.setdefault("seq_buckets", (16, 32))
    kw.setdefault("max_decode_len", 12)
    kw.setdefault("stream_chunk_tokens", 4)
    kw.setdefault("max_streams", 4)
    return ServiceConfig(**kw)


@pytest.fixture
def traced():
    tr = tracing.configure(True, 4096)
    yield tr
    tracing.configure(False)


def _consume(cdl, feats):
    async def body():
        out = []
        async for c in cdl.submit_stream(dict(feats)):
            out.extend(np.asarray(c).tolist())
        return out

    return asyncio.run(body())


def _spans_by_name(spans):
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    return by


# ---------------------------------------------------------------------------
# span tree + timing sanity (acceptance criterion)


def test_span_tree_and_timing_sanity(traced):
    """One chunked-prefill stream: every stage span present, rid-
    correlated, dispatch spans parented under their stages, and the
    stage spans' summed duration ≈ the stream span (end-to-end)."""
    cfg = _cfg(prefill_chunk=8)
    bundle = tiny_gpt_bundle()
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    cdl = ContinuousDecodeLoop(eng, cfg)
    feats = text_feats(
        bundle.tokenizer, "the quick brown fox jumps over the lazy dog"
    )
    feats["request_id"] = "req-span-1"
    try:
        toks = _consume(cdl, feats)
    finally:
        cdl.stop()
    assert len(toks) > 0
    spans = traced.snapshot()
    by = _spans_by_name(spans)

    # Every stage of the request's life has spans.
    for name in ("admission", "queue_wait", "prefill_window",
                 "decode_chunk", "dispatch:prefill_chunk",
                 "dispatch:chunk", "dispatch:fetch", "stream"):
        assert name in by, f"missing {name} spans (have {sorted(by)})"
    # The 44-token prompt at PREFILL_CHUNK=8 takes 6 windows.
    assert len(by["prefill_window"]) == 6

    # Correlation: stage spans carry the request id.
    for name in ("admission", "queue_wait", "prefill_window", "stream"):
        assert all(s.rid == "req-span-1" for s in by[name]), name
    # The (single-stream) decode chunk names its streams.
    assert by["decode_chunk"][0].args["streams"] == ["req-span-1"]

    # Parenting: every dispatch:prefill_chunk sits under a
    # prefill_window; every dispatch:chunk under a decode_chunk.
    window_sids = {s.sid for s in by["prefill_window"]}
    assert all(
        s.parent in window_sids for s in by["dispatch:prefill_chunk"]
    )
    chunk_sids = {s.sid for s in by["decode_chunk"]}
    assert all(s.parent in chunk_sids for s in by["dispatch:chunk"])

    # Host attribution on dispatch spans — and no device half: the
    # guard never waits for the device (device time is the profiler's).
    for s in by["dispatch:chunk"]:
        assert "host_ms" in s.args and "device_ms" not in s.args

    # Timing sanity: the stream span is the end-to-end interval; the
    # top-level stage spans (queue wait, prefill windows, decode
    # chunks, fetches) happen sequentially inside it, so their sum
    # approximates it — within tolerance for loop bookkeeping.
    (stream,) = by["stream"]
    stage_sum = sum(
        s.dur
        for name in ("queue_wait", "prefill_window", "decode_chunk",
                     "dispatch:fetch")
        for s in by[name]
    )
    assert stream.dur > 0
    assert 0.4 * stream.dur <= stage_sum <= 1.25 * stream.dur, (
        f"stage sum {stage_sum:.4f}s vs stream {stream.dur:.4f}s"
    )
    # And every stage lies inside the stream interval (small slack for
    # the release-side bookkeeping that closes the stream span).
    lo, hi = stream.t0 - 0.05, stream.t0 + stream.dur + 0.05
    for name in ("queue_wait", "prefill_window", "decode_chunk"):
        for s in by[name]:
            assert lo <= s.t0 and s.t0 + s.dur <= hi, name


def test_chrome_trace_export_schema(traced):
    """The /debug/trace payload is Chrome trace-event JSON Perfetto
    accepts: a traceEvents list of dicts with name/ph/pid/tid/ts, dur
    on complete ("X") events, and metadata ("M") naming entries."""
    cfg = _cfg()
    bundle = tiny_gpt_bundle()
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    cdl = ContinuousDecodeLoop(eng, cfg)
    feats = text_feats(bundle.tokenizer, "hello world")
    feats["request_id"] = "req-schema"
    try:
        _consume(cdl, feats)
    finally:
        cdl.stop()
    out = traced.chrome_trace(last=100)
    assert isinstance(out["traceEvents"], list) and out["traceEvents"]
    assert out["displayTimeUnit"] == "ms"
    phases = set()
    for ev in out["traceEvents"]:
        assert isinstance(ev["name"], str) and ev["name"]
        assert ev["ph"] in ("X", "i", "M")
        assert isinstance(ev["pid"], int)
        phases.add(ev["ph"])
        if ev["ph"] == "M":
            continue
        assert isinstance(ev["tid"], int)
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        assert isinstance(ev["args"], dict)
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
    assert "X" in phases and "M" in phases
    # `last` bounds the span count (metadata events ride on top).
    big = traced.chrome_trace()
    small = traced.chrome_trace(last=3)
    assert len(small["traceEvents"]) <= len(big["traceEvents"])


# ---------------------------------------------------------------------------
# TRACE=0: zero overhead


def test_trace_off_allocates_no_spans(monkeypatch):
    """With tracing off, the serving path never constructs a Span —
    the no-op singleton is the only thing the hot loop touches."""
    tracing.configure(False)
    created = []
    orig = tracing.Span.__init__

    def spy(self, *a, **kw):
        created.append(self)
        orig(self, *a, **kw)

    monkeypatch.setattr(tracing.Span, "__init__", spy)
    cfg = _cfg(prefill_chunk=8)
    bundle = tiny_gpt_bundle()
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    cdl = ContinuousDecodeLoop(eng, cfg)
    feats = text_feats(
        bundle.tokenizer, "the quick brown fox jumps over the lazy dog"
    )
    feats["request_id"] = "req-off"
    try:
        toks = _consume(cdl, feats)
    finally:
        cdl.stop()
    assert len(toks) > 0
    assert tracing.tracer() is None
    assert created == [], f"{len(created)} spans allocated under TRACE=0"
    # The always-on host-dispatch accounting still ran.
    attr = eng.dispatch_attribution()
    assert attr.get("chunk", {}).get("count", 0) > 0
    # ... host seconds only: no device half exists to pay for.
    assert "device_s" not in attr["chunk"]


# ---------------------------------------------------------------------------
# the profiler sink, the no-sync guard, the wave/queue counters, scopes

LOOP_PHASES = ("loop/wave_dispatch", "loop/wave_fetch", "loop/insert",
               "loop/chunk_dispatch", "loop/deliver")


def _paged_llama(**kw):
    cfg = _cfg(paged_kv=True, kv_block_size=4, **kw)
    bundle = tiny_llama_bundle()
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    return bundle, eng, ContinuousDecodeLoop(eng, cfg)


def test_profiler_session_holds_phase_names(tmp_path):
    """TRACE=0, a ``jax.profiler`` session around one tiny stream: the
    xplane's host plane holds the loop's phases and the dispatch sites
    under the program's own names, and the ``loop/`` phases of the
    loop thread are flat siblings — none encloses another, so none
    encloses a whole iteration (an idle gap is attributed to the span
    that overlaps it most; an enclosing one would swallow them all)."""
    import jax
    from jax.profiler import ProfileData

    tracing.configure(False)
    bundle, eng, cdl = _paged_llama()
    feats = text_feats(bundle.tokenizer, "the quick brown fox")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        _consume(cdl, feats)  # compile outside the session
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            toks = _consume(cdl, feats)
        finally:
            jax.profiler.stop_trace()
    finally:
        cdl.stop()
    assert len(toks) > 0
    (path,) = glob.glob(
        os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    host = [p for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:CPU")]
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in line.events] for p in host for line in p.lines]
    names = {n for evs in lines for n, _, _ in evs}
    for want in LOOP_PHASES + ("dispatch:chunk", "dispatch:insert",
                               "dispatch:prefill", "dispatch:fetch",
                               "admission"):
        assert want in names, f"missing {want} (have {sorted(names)})"
    loop_line = next(evs for evs in lines
                     if any(n == "loop/chunk_dispatch" for n, _, _ in evs))
    phases = [e for e in loop_line if e[0].startswith("loop/")]
    for a in phases:
        for b in phases:
            # (b[2] > a[1]: a zero-length neighbour that ended in the
            # nanosecond ``a`` began is beside it, not inside it)
            if a is not b and b[2] > a[1]:
                assert not (a[1] <= b[1] and b[2] <= a[2]), (a, b)
    # Every dispatch on the loop thread sits inside one of its phases
    # (a phase the session's start or end cut in two is not recorded:
    # look between the first and the last that are).
    first, last = min(p[1] for p in phases), max(p[2] for p in phases)
    for n, s, e in loop_line:
        if n.startswith("dispatch:") and first <= s < last:
            assert any(ps <= s and e <= pe for _, ps, pe in phases), n


def test_trace_on_same_names_and_never_syncs(traced, monkeypatch):
    """TRACE=1: the ring holds the phase names the profiler's trace
    holds, and nothing waits for the device on their account — not one
    ``block_until_ready`` more than with TRACE=0 (the empty-state
    build's), and as many chunks in flight."""
    import jax

    synced = []
    orig = jax.block_until_ready
    monkeypatch.setattr(
        jax, "block_until_ready",
        lambda x: (synced.append(1), orig(x))[1],
    )

    def run() -> tuple[int, int]:
        synced.clear()
        cfg = _cfg(max_decode_len=32)
        bundle = tiny_gpt_bundle()
        eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
        cdl = ContinuousDecodeLoop(eng, cfg)
        depth = []
        noted = cdl._note_dispatched

        def spy(entry):
            noted(entry)
            depth.append(len(cdl._inflight_chunks))

        cdl._note_dispatched = spy
        try:
            assert _consume(cdl, text_feats(bundle.tokenizer, "hello world"))
        finally:
            cdl.stop()
        return max(depth), len(synced)

    on = run()
    by = _spans_by_name(traced.snapshot())
    for name in LOOP_PHASES + ("dispatch:chunk", "dispatch:insert",
                               "dispatch:prefill", "admission"):
        assert name in by, f"missing {name} (have {sorted(by)})"
    tracing.configure(False)
    off = run()
    assert on == off and on[0] >= 1


def _sample(name: str, model: str) -> float:
    from prometheus_client import REGISTRY

    return REGISTRY.get_sample_value(name, {"model": model}) or 0.0


def test_wave_and_queue_counters():
    """A 2-stream wave at 8 slots runs its rung R < 8: ``prefill_wave_fill``
    observes (L1 + L2) / (R x S) exactly, ``stream_queue_wait_seconds`` and
    ``stream_admit_seconds`` count one per reservation / stream, and
    ``prefill_stall_seconds`` does not move for a wave nobody was live
    to be stalled by — then grows for a wave admitted while they decode."""
    tracing.configure(False)
    bundle, eng, cdl = _paged_llama(max_streams=8, max_decode_len=64,
                                    seq_buckets=(32,))
    name = bundle.name
    f1 = text_feats(bundle.tokenizer, "the quick brown fox")
    f2 = text_feats(bundle.tokenizer, "jumps over the lazy dog again")
    l1, l2 = int(f1["length"]), int(f2["length"])
    fams = ("prefill_wave_fill_sum", "prefill_wave_fill_count",
            "stream_queue_wait_seconds_count", "stream_admit_seconds_count",
            "prefill_stall_seconds_total")
    before = {k: _sample(k, name) for k in fams}

    async def body():
        async def consume(feats, first: asyncio.Event | None = None):
            n = 0
            async for c in cdl.submit_stream(dict(feats)):
                n += int(np.asarray(c).size)
                if first is not None:
                    first.set()
            return n

        first = asyncio.Event()
        with one_wave(cdl):  # both submits land in ONE wave
            a = asyncio.ensure_future(consume(f1, first))
            b = asyncio.ensure_future(consume(f2))
            await asyncio.sleep(0)  # both tasks ran up to their submit
        await first.wait()
        mid = {k: _sample(k, name) for k in fams}
        live = len(cdl.active) > 0
        c = await consume(f1)  # admitted while the first two decode
        return mid, live, await a, await b, c

    try:
        mid, live, *counts = asyncio.run(body())
    finally:
        cdl.stop()
    assert all(n > 0 for n in counts)
    d = lambda snap, k: snap[k] - before[k]  # noqa: E731
    assert d(mid, "prefill_wave_fill_count") == 1
    assert d(mid, "prefill_wave_fill_sum") == pytest.approx(
        (l1 + l2) / (cdl._wave_rows(2) * 32), rel=1e-12)
    assert cdl._wave_rows(2) < cdl.n_slots == 8
    assert d(mid, "stream_queue_wait_seconds_count") == 2
    assert d(mid, "prefill_stall_seconds_total") == 0.0
    after = {k: _sample(k, name) for k in fams}
    assert d(after, "stream_queue_wait_seconds_count") == 3
    assert d(after, "stream_admit_seconds_count") == 3
    assert d(after, "prefill_wave_fill_count") == 2
    if live:  # the third stream's wave held the loop from live streams
        assert d(after, "prefill_stall_seconds_total") > 0.0
        assert cdl.prefill_stall_s > 0.0


def test_paged_decode_chunk_carries_scope_names():
    """The lowered paged decode chunk (debug info on) names the model's
    parts: the scopes a device trace's operations are grouped by."""
    tracing.configure(False)
    bundle, eng, cdl = _paged_llama()
    try:
        _consume(cdl, text_feats(bundle.tokenizer, "hello world"))
        hlo = cdl.programs.paged_chunk_hlo(
            cdl._state, cdl._table, debug_info=True)
        bare = cdl.programs.paged_chunk_hlo(cdl._state, cdl._table)
    finally:
        cdl.stop()
    for scope in ("decode_chunk", "embed", "qkv_rope", "kv_write", "attn",
                  "attn_out", "mlp", "lm_head", "sample"):
        assert f'"{scope}/' in hlo or f"/{scope}/" in hlo, scope
    assert "kv_write" not in bare  # names are metadata, not operations


# ---------------------------------------------------------------------------
# checkpoint-resume + fault events


def test_spans_survive_fatal_recovery_same_rid(traced):
    """A fatal fault mid-decode checkpoints the stream and resumes it
    token-identically; its spans keep the SAME request id across the
    restart (a second queue-wait span marks the resume) and the
    flight recorder dumped automatically."""
    cfg = _cfg(fault_spec="chunk:fatal@2", max_decode_len=16,
               seq_buckets=(16, 32, 64))
    bundle = tiny_gpt_bundle()
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    ref = InferenceEngine(
        bundle, _cfg(max_decode_len=16, seq_buckets=(16, 32, 64)),
        ReplicaSet(make_mesh(1)),
    )
    feats = text_feats(bundle.tokenizer, "the quick brown fox")
    want = np.concatenate(
        list(ref.generate_stream(dict(feats)))
    ).tolist()
    feats["request_id"] = "req-resume"
    cdl = ContinuousDecodeLoop(eng, cfg)
    cdl.supervisor = Supervisor(cfg, recorder=eng.flight)
    try:
        got = _consume(cdl, feats)
    finally:
        cdl.stop()
    n = min(len(got), len(want))
    np.testing.assert_array_equal(got[:n], want[:n])
    assert cdl.supervisor.restarts >= 1

    by = _spans_by_name(traced.snapshot())
    waits = [s for s in by["queue_wait"] if s.rid == "req-resume"]
    assert len(waits) >= 2, "resume must re-enter the queue with its rid"
    assert any(s.args.get("resumed") for s in waits)
    streams = [s for s in by["stream"] if s.rid == "req-resume"]
    assert streams, "stream span records the full lifetime"

    # The flight recorder dumped on the fatal fault, and the dump
    # carries the events that led there.
    flight = eng.flight.snapshot()
    assert flight["dumps"] >= 1
    assert flight["last_dump"] is not None
    assert "fatal" in flight["last_dump"]["reason"].lower()
    kinds = {e["event"] for e in flight["events"]}
    assert "engine_restart" in kinds
    assert "checkpoint_requeue" in kinds


def test_flight_records_iterations_and_retries():
    """No tracer needed: the flight recorder captures loop iterations
    (slot occupancy, paged pool state) and watchdog retry events."""
    tracing.configure(False)
    cfg = _cfg(
        fault_spec="chunk:transient@2", dispatch_retries=2,
        dispatch_backoff_s=0.01, paged_kv=True, kv_block_size=4,
        prefill_chunk=8,
    )
    bundle = tiny_llama_bundle()
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    cdl = ContinuousDecodeLoop(eng, cfg)
    feats = text_feats(
        bundle.tokenizer, "the quick brown fox jumps over the lazy dog"
    )
    feats["request_id"] = "req-flight"
    try:
        toks = _consume(cdl, feats)
    finally:
        cdl.stop()
    assert len(toks) > 0
    snap = eng.flight.snapshot()
    assert snap["iterations"], "loop iterations recorded"
    it = snap["iterations"][-1]
    for key in ("active", "free_slots", "queued", "chunk_dispatches",
                "slots", "pool_free_blocks"):
        assert key in it, key
    # The transient fault retried under the watchdog → an event.
    kinds = {e["event"] for e in snap["events"]}
    assert "dispatch_retry" in kinds
    # Occupied slot frames name the stream.
    occupied = [
        i for i in snap["iterations"] if i["slots"]
    ]
    assert any(
        s["rid"] == "req-flight"
        for i in occupied for s in i["slots"].values()
    )


def test_flight_ring_zero_disables():
    rec = tracing.FlightRecorder(0)
    rec.record_iteration(active=1)
    rec.event("x")
    snap = rec.snapshot()
    assert snap["iterations"] == [] and snap["events"] == []
    # dump still answers (empty) — the API contract stays total.
    d = rec.dump("test")
    assert d["reason"] == "test" and rec.last_dump is not None


@pytest.mark.chaos
def test_observability_smoke():
    """scripts/check.sh observability stage (OBS_SMOKE=0 skips): the
    full HTTP service under TRACE=1 + transient fault injection —
    requests flow, then /debug/trace yields schema-valid Perfetto
    JSON containing every stage span, /debug/engine yields the flight
    recorder with the injected retry event, and /status reports the
    observability block."""
    import json
    import os

    from aiohttp.test_utils import TestClient, TestServer

    from mlmicroservicetemplate_tpu.api import build_app
    from mlmicroservicetemplate_tpu.scheduler import Batcher

    spec = os.environ.get("OBS_SMOKE_SPEC", "chunk:transient@2")
    tracing.configure(False)  # the engine installs it from cfg.trace
    cfg = _cfg(
        trace=True, trace_ring=8192, prefill_chunk=8,
        fault_spec=spec, dispatch_retries=2, dispatch_backoff_s=0.01,
        max_decode_len=16, batch_timeout_ms=1.0,
    )
    bundle = tiny_gpt_bundle()

    async def main():
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
            # A long-enough prompt to take the chunked-prefill path,
            # plus a unary request for the batch path.
            r = await client.post(
                "/predict",
                json={"text": "the quick brown fox jumps over the lazy "
                              "dog again", "stream": True},
                headers={"X-Request-Id": "obs-smoke-1"},
            )
            assert r.status == 200
            async for line in r.content:
                if json.loads(line).get("done"):
                    break
            r = await client.post("/predict", json={"text": "unary"})
            assert r.status == 200

            r = await client.get("/debug/trace?last=2000")
            assert r.status == 200
            trace = await r.json()
            r = await client.get("/debug/engine")
            assert r.status == 200
            engine_dbg = await r.json()
            r = await client.get("/status")
            status = await r.json()
            return trace, engine_dbg, status
        finally:
            await client.close()
            tracing.configure(False)

    trace, engine_dbg, status = asyncio.run(main())

    # Trace-event JSON schema (the Perfetto contract).
    assert trace["otherData"]["trace_enabled"] is True
    events = trace["traceEvents"]
    assert isinstance(events, list) and events
    names = set()
    for ev in events:
        assert ev["ph"] in ("X", "i", "M"), ev
        assert isinstance(ev["name"], str) and "pid" in ev
        if ev["ph"] == "X":
            assert ev["dur"] >= 0 and ev["ts"] >= 0
        names.add(ev["name"])
    for need in ("request", "admission", "queue_wait", "prefill_window",
                 "decode_chunk", "dispatch:chunk", "stream"):
        assert need in names, f"{need} missing from /debug/trace"
    # Request-id correlation from HTTP header to engine spans.
    rids = {
        ev["args"].get("request_id")
        for ev in events if ev["ph"] != "M"
    }
    assert "obs-smoke-1" in rids

    # Flight recorder surface.
    assert engine_dbg["iterations"], "no loop iterations recorded"
    kinds = {e["event"] for e in engine_dbg["events"]}
    assert "dispatch_retry" in kinds, (
        f"injected {os.environ.get('OBS_SMOKE_SPEC', 'chunk:transient@2')}"
        f" left no retry event (have {kinds})"
    )
    assert "dispatch_attribution" in engine_dbg
    assert engine_dbg["loop"]["chunk_dispatches"] > 0

    # /status observability block.
    obs = status["observability"]
    assert obs["trace"] is True and obs["spans_created"] > 0
    assert obs["flight_ring"] > 0


def test_tbt_histogram_observed():
    """stream_tbt_seconds fills from the loop's inter-chunk delivery
    gaps (the series the prefill-interference A/B reads)."""
    from mlmicroservicetemplate_tpu.utils import metrics

    if not metrics.HAVE_PROM:
        pytest.skip("prometheus_client not installed")
    tracing.configure(False)
    cfg = _cfg(max_decode_len=16)
    bundle = tiny_gpt_bundle()
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    cdl = ContinuousDecodeLoop(eng, cfg)
    feats = text_feats(bundle.tokenizer, "pack my box with jugs")

    def tbt_count():
        for fam in metrics.TBT.collect():
            for s in fam.samples:
                if s.name.endswith("_count") and s.labels.get(
                    "model"
                ) == bundle.name:
                    return s.value
        return 0.0

    before = tbt_count()
    try:
        toks = _consume(cdl, feats)
    finally:
        cdl.stop()
    # 16-token budget at 4-token chunks → ≥3 inter-chunk gaps.
    assert len(toks) > 0
    assert tbt_count() - before >= 2


@pytest.mark.parametrize("route", ["/predict", "/v1/completions"])
def test_idle_admission_counters_over_http(route, monkeypatch):
    """The API counts a streaming request from its parsed body to its
    queued stream, and the loop's wait shows in ``/metrics``
    (``idle_admit_*``) and ``/status.decode.idle_admit`` (its seconds
    and count from the loop table): a burst whose
    requests leave preprocess 20 ms apart lands as ONE wave (waits 1,
    rows 3); a counted request that fails in preprocess (400) or is
    shed (503) lowers the count on its way out."""
    from aiohttp.test_utils import TestClient, TestServer

    from mlmicroservicetemplate_tpu.api import build_app
    from mlmicroservicetemplate_tpu.scheduler import Batcher

    tracing.configure(False)
    cfg = _cfg(max_decode_len=16, batch_timeout_ms=1.0)
    bundle = tiny_gpt_bundle()
    preprocess = bundle.preprocess

    def slow(item):  # request i leaves the executor ~20 ms after i - 1,
        # the first when the server has read (and counted) all four
        if item.text and item.text[0].isdigit():
            time.sleep(0.2 + 0.02 * int(item.text[0]))
        if item.text == "bad":
            raise ValueError("undecodable payload")
        return preprocess(item)

    monkeypatch.setattr(bundle, "preprocess", slow)

    def body(text):
        key = "text" if route == "/predict" else "prompt"
        return {key: text, "stream": True, "max_tokens": 6}

    async def drain(r):
        async for _ in r.content:
            pass

    async def main():
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
            cdl = batcher._cdl
            # (an unwarmed loop has timed no wave and would hold none)
            cdl._wave_seconds = dict.fromkeys(cdl._wave_rungs, 5.0)
            rs = await asyncio.gather(*[
                client.post(route, json=body(f"{i} burst row"))
                for i in range(4)])
            assert [r.status for r in rs] == [200] * 4
            await asyncio.gather(*[drain(r) for r in rs])
            burst = (await (await client.get("/status")).json())["decode"]
            r = await client.post(route, json=body("bad"))
            assert r.status == 400
            assert cdl.queue.expected() == 0
            batcher.begin_drain()  # every admission sheds: 503 `drain`
            r = await client.post(route, json=body("refused"))
            assert r.status == 503
            status = (await (await client.get("/status")).json())["decode"]
            text = await (await client.get("/metrics")).text()
            return burst, status, text, cdl
        finally:
            await client.close()

    fams = ("idle_admit_rows_total", "idle_admit_capped_total")
    before = {k: _sample(k, bundle.name) for k in fams}
    burst, status, text, cdl = asyncio.run(main())
    assert burst["idle_admit"] == {
        "expected": 0, "waits": 1, "rows": 3, "capped": 0,
        "wait_s": burst["idle_admit"]["wait_s"]}
    assert 0.04 <= burst["idle_admit"]["wait_s"] < 5.0
    assert status["idle_admit"]["expected"] == 0
    assert status["idle_admit"]["waits"] == 1  # the 400 and the 503 queued nothing
    d = {k: _sample(k, bundle.name) - before[k] for k in fams}
    assert d == {"idle_admit_rows_total": 3.0, "idle_admit_capped_total": 0.0}
    for fam in fams:
        assert f"# HELP {fam}" in text
    assert "idle_admit_wait_seconds" not in text  # the loop table has it
    # ... as its ``idle_admit`` row, whose waits are the API's by name.
    row = status["loop_time"]["inside"]["idle_admit"]
    assert (row["n"], row["s"]) == (1, status["idle_admit"]["wait_s"])
    assert 0.04 <= status["loop_time"]["phases"]["loop/await_api"]["s"] <= row["s"] + 1e-3


# ---------------------------------------------------------------------------
# the loop table: where the decode loop thread's wall time went, TRACE=0


def _on_thread(fn):
    """``fn()`` on a thread of its own (a table binds to its thread)."""
    import threading

    box = []
    th = threading.Thread(target=lambda: box.append(fn()))
    th.start()
    th.join(30.0)
    assert not th.is_alive() and box
    return box[0]


def _closes(snap: dict, tol: float = 1e-4) -> None:
    """``wall_s`` = sum of the top-level phases + ``unnamed_s``."""
    named = sum(row["s"] for row in snap["phases"].values())
    assert snap["wall_s"] == pytest.approx(named + snap["unnamed_s"], abs=tol)


def test_loop_table_closes_and_counts_nested_phases_apart(monkeypatch):
    """A bound thread's phases add up: top-level ones in ``phases``, a
    ``dispatch:<site>`` inside one in ``inside`` (not counted twice),
    the glue between them in ``unnamed_s`` — with TRACE=0 and no
    ``Span``; another thread's phases never reach the table."""
    tracing.configure(False)
    monkeypatch.setattr(
        tracing.Span, "__init__",
        lambda self, *a, **kw: pytest.fail("a Span under TRACE=0"))
    table = tracing.LoopTable("m")

    def work():
        table.bind()
        try:
            for _ in range(3):
                table.lap(live=2)
                with tracing.phase("loop/chunk_dispatch"):
                    time.sleep(0.004)
                    with tracing.phase("dispatch:chunk"):
                        time.sleep(0.003)
                time.sleep(0.002)  # under no phase
                with tracing.phase("loop/deliver"):
                    time.sleep(0.001)
            table.lap()
        finally:
            table.unbind()
        with tracing.phase("loop/deliver"):  # unbound: nobody's
            pass
        return table.snapshot()

    with tracing.phase("loop/deliver"):  # this thread is not the loop's
        snap = _on_thread(work)
    _closes(snap, 1e-5)
    assert snap["iterations"] == 3
    assert {k: v["n"] for k, v in snap["phases"].items()} == {
        "loop/chunk_dispatch": 3, "loop/deliver": 3}
    assert {k: v["n"] for k, v in snap["inside"].items()} == {"dispatch:chunk": 3}
    disp, inner = snap["phases"]["loop/chunk_dispatch"], snap["inside"]["dispatch:chunk"]
    assert 0.009 <= inner["s"] <= disp["s"] - 0.012 + 1e-3
    assert disp["max_s"] >= 0.007 and disp["s"] >= 3 * 0.007
    assert 0.006 <= snap["unnamed_s"] < 0.5
    assert snap["wall_s"] >= 3 * 0.010


def test_loop_table_ring_is_bounded_and_names_the_slow_iteration(monkeypatch):
    """The ring keeps the last ``RING`` iterations and ``slowest`` the
    ``SLOWEST`` longest of them, longest first: one iteration slowed on
    purpose comes out first with its phase named; an iteration that
    only waited on an empty server leaves no row."""
    assert tracing.LoopTable.RING == 4096
    monkeypatch.setattr(tracing.LoopTable, "RING", 256)  # (a shorter test)
    table = tracing.LoopTable("m")

    def work():
        table.bind()
        try:
            for i in range(2 * table.RING + 200):
                table.lap(live=1, rows=0, chunks=1)
                if i == 2 * table.RING:  # well inside what the ring still holds
                    with tracing.phase("loop/insert"):
                        time.sleep(0.05)
                    continue
                with tracing.phase("loop/housekeeping"):
                    pass
                with tracing.phase("loop/idle" if i % 2 else "loop/deliver"):
                    time.sleep(0.0002)
            table.lap(live=7, rows=3, chunks=2)
        finally:
            table.unbind()
        return table.snapshot()

    snap = _on_thread(work)
    _closes(snap)
    assert snap["iterations"] == 2 * table.RING + 200
    # (half the iterations only idled: they are counted, and left no row;
    # of the other half the ring holds the last RING)
    assert snap["phases"]["loop/idle"]["n"] == table.RING + 100
    assert len(table._rows) == table.RING
    slowest = snap["slowest"]
    assert len(slowest) == table.SLOWEST
    assert [r["wall_s"] for r in slowest] == sorted(
        (r["wall_s"] for r in slowest), reverse=True)
    top = slowest[0]
    assert top["phase"] == "loop/insert" and 0.05 <= top["phase_s"] <= top["wall_s"]
    assert top["phases"] == {"loop/insert": top["phase_s"]}
    assert (top["live"], top["rows"], top["chunks"]) == (1, 0, 1)
    assert all(r["phase"] != "loop/idle" for r in slowest)


@pytest.mark.parametrize("route", ["/predict", "/v1/completions"])
def test_first_token_closes_stage_by_stage_over_http(route, traced):
    """For every request of a burst ``stream_ttft_seconds`` is the sum
    of its four stages — ``stream_api`` (handler entry to the queue),
    ``stream_queue_wait``, ``stream_admit`` and ``stream_handoff`` (the
    loop thread's first emit to the API's observation) — instant for
    instant: the ring's ``api`` / ``queue_wait`` / ``admit`` / ``handoff``
    spans of one request id tile its first token, and the histograms'
    sums add up.  ``/status`` shows where the loop's wall time went
    (``decode.loop_time``, which closes) and the process's own pauses
    (``process``); ``/metrics`` renders the families fed from them."""
    from aiohttp.test_utils import TestClient, TestServer

    from mlmicroservicetemplate_tpu.api import build_app
    from mlmicroservicetemplate_tpu.scheduler import Batcher

    cfg = _cfg(max_decode_len=16, batch_timeout_ms=1.0)
    bundle = tiny_gpt_bundle()
    stages = ("stream_api_seconds", "stream_queue_wait_seconds",
              "stream_admit_seconds", "stream_handoff_seconds")
    fams = stages + ("stream_ttft_seconds",)
    k = 4

    def read():
        def one(f, part):
            labels = {"model": bundle.name}
            if f == "stream_ttft_seconds":
                labels["mode"] = "monolithic"
            return _sample_labels(f"{f}_{part}", labels)

        return {f: (one(f, "sum"), one(f, "count")) for f in fams}

    async def main():
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
            key = "text" if route == "/predict" else "prompt"
            before = read()
            rs = await asyncio.gather(*[
                client.post(route, headers={"X-Request-Id": f"burst-{i}"},
                            json={key: f"{i} burst row", "stream": True,
                                  "max_tokens": 6})
                for i in range(k)])
            assert [r.status for r in rs] == [200] * k
            for r in rs:
                async for _ in r.content:
                    pass
            await asyncio.sleep(0.12)  # a few ticks of the lag timer
            status = await (await client.get("/status")).json()
            text = await (await client.get("/metrics")).text()
            return before, read(), status, text
        finally:
            await client.close()

    before, after, status, text = asyncio.run(main())
    d = {f: (after[f][0] - before[f][0], after[f][1] - before[f][1]) for f in fams}
    assert {f: n for f, (_, n) in d.items()} == dict.fromkeys(fams, float(k))
    assert sum(d[f][0] for f in stages) == pytest.approx(
        d["stream_ttft_seconds"][0], abs=1e-6)
    # ... and request by request, on one clock, with no gap between stages.
    by_rid: dict = {}
    for s in traced.snapshot():
        if s.rid.startswith("burst-") and s.name in (
            "api", "queue_wait", "admit", "handoff", "api/parse",
            "api/tokenize", "api/submit",
        ):
            by_rid.setdefault(s.rid, {})[s.name] = s
    assert sorted(by_rid) == [f"burst-{i}" for i in range(k)]
    total = 0.0
    for rid, sp in by_rid.items():
        a, q, m, h = (sp[n] for n in ("api", "queue_wait", "admit", "handoff"))
        for left, right in ((a, q), (q, m), (m, h)):
            assert left.t0 + left.dur == pytest.approx(right.t0, abs=1e-9), rid
        assert min(a.dur, m.dur, h.dur) > 0.0 and q.dur >= 0.0
        # The API's synchronous sections lie inside its stage (the
        # submit puts the stream on the queue, which ends the stage).
        for name in ("api/parse", "api/tokenize"):
            inner = sp[name]
            assert a.t0 <= inner.t0 and inner.t0 + inner.dur <= q.t0, name
        sub = sp["api/submit"]
        assert a.t0 <= sub.t0 <= q.t0 <= sub.t0 + sub.dur
        total += h.t0 + h.dur - a.t0
    assert total == pytest.approx(d["stream_ttft_seconds"][0], abs=1e-6)

    loop_time = status["decode"]["loop_time"]
    _closes(loop_time, 1e-6 * (len(loop_time["phases"]) + 2))
    assert set(loop_time) == {"wall_s", "unnamed_s", "iterations", "phases",
                              "inside", "slowest"}
    assert 1 <= len(loop_time["slowest"]) <= 8
    assert set(loop_time["slowest"][0]) == {
        "t", "wall_s", "phase", "phase_s", "unnamed_s", "phases", "live",
        "rows", "chunks"}
    process = status["process"]
    assert status["decode"]["process"].keys() == process.keys()
    assert process["event_loop_lag"]["ticks"] >= 2
    assert 0.0 <= process["event_loop_lag"]["p50_s"] <= process["event_loop_lag"]["max_s"]
    assert set(process["gc"]) == {"gen0", "gen1", "gen2"}
    assert process["gc"]["gen0"].keys() == {"collections", "s", "max_s"}
    if os.path.exists("/proc/self/schedstat"):
        assert process["loop_thread"]["cpu_s"] > 0.0
        assert process["loop_thread"]["run_delay_s"] >= 0.0
    for fam in ("loop_phase_seconds_total", "loop_unnamed_seconds_total",
                "gc_pause_seconds_total", "event_loop_lag_seconds",
                "stream_api_seconds", "stream_handoff_seconds"):
        assert f"# HELP {fam}" in text
    # The counters are the table's own seconds, handed on at render time.
    idle = _sample_labels("loop_phase_seconds_total",
                          {"model": bundle.name, "phase": "loop/idle"})
    assert idle >= loop_time["phases"]["loop/idle"]["s"] - 1e-6 > 0.0


def _sample_labels(name: str, labels: dict) -> float:
    from prometheus_client import REGISTRY

    return REGISTRY.get_sample_value(name, labels) or 0.0
