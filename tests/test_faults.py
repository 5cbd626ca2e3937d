"""Fault-tolerance tests (engine/faults.py + engine/supervisor.py and
their integration into the decode loop, batcher and API):

1. FAULT_SPEC parsing + deterministic injection (Nth dispatch, seeded
   rate).
2. Watchdog: transient retries with backoff, hang cut off at
   DISPATCH_TIMEOUT_S.
3. Supervised crash recovery: a fatal device fault mid-decode
   checkpoints live streams, rebuilds the engine and resumes them
   token-identically (no dropped or duplicated delivered tokens);
   transient faults are invisible to clients; a hang never stalls the
   loop; the restart budget bounds recovery before /readyz goes
   permanently unready.
4. API failure surface: structured JSON 500 bodies with X-Request-Id,
   terminal SSE/ndjson error events, canary under the watchdog.
5. Ledger hygiene: after a randomized fault schedule drains, the block
   pool holds zero leaked/double-freed blocks, no slot is orphaned and
   the admission ledger reads zero (chaos tier).
"""

import asyncio
import json
import time

import numpy as np
import pytest

from helpers import text_feats, tiny_gpt_bundle, tiny_llama_bundle, tiny_t5_bundle
from mlmicroservicetemplate_tpu.engine import InferenceEngine
from mlmicroservicetemplate_tpu.engine.faults import (
    DispatchTimeoutError,
    FatalDeviceError,
    FaultInjector,
    TransientDeviceError,
    Watchdog,
    parse_spec,
)
from mlmicroservicetemplate_tpu.engine.streams import ContinuousDecodeLoop
from mlmicroservicetemplate_tpu.engine.supervisor import Supervisor
from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
from mlmicroservicetemplate_tpu.utils.config import ServiceConfig

from test_streams import _collect, _echo_bundle, _run_concurrent, _solo_tokens


def _cfg(**kw) -> ServiceConfig:
    kw.setdefault("device", "cpu")
    kw.setdefault("warmup", False)
    kw.setdefault("batch_buckets", (1, 2, 4, 8))
    kw.setdefault("seq_buckets", (16, 32))
    kw.setdefault("max_decode_len", 12)
    kw.setdefault("stream_chunk_tokens", 4)
    kw.setdefault("max_streams", 4)
    return ServiceConfig(**kw)


def _supervised_cdl(eng, cfg):
    cdl = ContinuousDecodeLoop(eng, cfg)
    cdl.supervisor = Supervisor(cfg)
    return cdl


# ---------------------------------------------------------------------------
# 1. spec parsing + deterministic injection


def test_fault_spec_parse_and_errors():
    rules = parse_spec("chunk:fatal@5;*:transient~0.25;grow:oob@2+3;hang(1.5)@1")
    assert [r.site for r in rules] == ["chunk", "*", "grow", "*"]
    assert [r.kind for r in rules] == ["fatal", "transient", "oob", "hang"]
    assert rules[0].nth == 5 and rules[0].count == 1
    assert rules[1].rate == 0.25
    assert rules[2].nth == 2 and rules[2].count == 3
    assert rules[3].arg == 1.5
    for bad in ("chunk:explode@1", "bogus:fatal@1", "fatal", "fatal~2.0"):
        with pytest.raises(ValueError):
            parse_spec(bad)
    assert FaultInjector.from_spec(None) is None
    assert FaultInjector.from_spec("") is None


def test_injector_nth_window_and_seeded_rate():
    inj = FaultInjector.from_spec("chunk:transient@2+2")
    inj.fire("chunk")  # 1: no fault
    for _ in range(2):  # 2, 3: fault window
        with pytest.raises(TransientDeviceError):
            inj.fire("chunk")
    inj.fire("chunk")  # 4: clean again
    inj.fire("prefill")  # other sites never count toward chunk rules
    assert inj.rules[0].fired == 2 and inj.rules[0].seen == 4

    def fired_seq(seed):
        inj = FaultInjector.from_spec("*:fatal~0.5", seed=seed)
        out = []
        for _ in range(32):
            try:
                inj.fire("chunk")
                out.append(0)
            except FatalDeviceError:
                out.append(1)
        return out

    assert fired_seq(7) == fired_seq(7)  # seeded => reproducible
    assert fired_seq(7) != fired_seq(8)


# ---------------------------------------------------------------------------
# 2. watchdog


def test_watchdog_retries_transient_then_raises():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientDeviceError("flaky")
        return "ok"

    wd = Watchdog("m", retries=3, backoff_s=0.001)
    assert wd.run("chunk", flaky) == "ok"
    assert calls["n"] == 3
    # Retries exhausted -> the transient escalates.
    wd2 = Watchdog("m", retries=1, backoff_s=0.001)
    with pytest.raises(TransientDeviceError):
        wd2.run("chunk", lambda: (_ for _ in ()).throw(TransientDeviceError()))
    # Fatal errors never retry.
    calls["n"] = 0

    def fatal():
        calls["n"] += 1
        raise FatalDeviceError("gone")

    with pytest.raises(FatalDeviceError):
        wd.run("chunk", fatal)
    assert calls["n"] == 1


def test_watchdog_timeout_cuts_hang():
    wd = Watchdog("m", timeout_s=0.05)
    t0 = time.monotonic()
    with pytest.raises(DispatchTimeoutError):
        wd.run("chunk", lambda: time.sleep(0.8))
    assert time.monotonic() - t0 < 0.5  # cut at the deadline, not the sleep
    # Under the deadline: plain passthrough result.
    assert wd.run("chunk", lambda: 42) == 42


# ---------------------------------------------------------------------------
# 3. supervised decode-loop recovery (echo bundle: fast, deterministic)


def _echo_engine(cfg):
    bundle = _echo_bundle()
    return bundle, InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))


def test_transient_chunk_fault_invisible_to_client():
    cfg = _cfg(fault_spec="chunk:transient@2", dispatch_retries=2,
               dispatch_backoff_s=0.001)
    bundle, eng = _echo_engine(cfg)
    feats = [text_feats(bundle.tokenizer, t) for t in ("abc", "wxyz")]
    ref_eng = InferenceEngine(bundle, _cfg(), ReplicaSet(make_mesh(1)))
    solos = [_solo_tokens(ref_eng, f) for f in feats]
    cdl = ContinuousDecodeLoop(eng, cfg)  # no supervisor needed: retry absorbs
    try:
        outs = _run_concurrent(cdl, feats)
        for got, want in zip(outs, solos):
            n = min(len(got), len(want))
            np.testing.assert_array_equal(got[:n], want[:n])
        assert eng.faults.rules[0].fired == 1  # the fault really fired
    finally:
        cdl.stop()


def test_fatal_mid_decode_recovers_token_identical():
    """The acceptance scenario: FAULT_SPEC kills a chunk dispatch
    mid-decode; the supervised loop checkpoints the in-flight streams,
    rebuilds the engine and resumes them with token-identical output —
    no dropped, duplicated or error-terminated stream."""
    cfg = _cfg(fault_spec="chunk:fatal@2", max_decode_len=16,
               engine_restarts_max=2)
    bundle, eng = _echo_engine(cfg)
    feats = [text_feats(bundle.tokenizer, t) for t in
             ("abcdefgh", "stream two text", "x")]
    ref_eng = InferenceEngine(bundle, _cfg(max_decode_len=16),
                              ReplicaSet(make_mesh(1)))
    solos = [_solo_tokens(ref_eng, f) for f in feats]
    cdl = _supervised_cdl(eng, cfg)
    try:
        outs = _run_concurrent(cdl, feats)
        for got, want in zip(outs, solos):
            n = min(len(got), len(want))
            np.testing.assert_array_equal(got[:n], want[:n])
            assert not np.any(want[n:] != 0) and not np.any(got[n:] != 0)
        assert eng.faults.rules[0].fired == 1
        assert cdl.supervisor.restarts == 1
        assert not cdl.supervisor.failed
    finally:
        cdl.stop()


def test_hang_cut_by_watchdog_and_recovered():
    """An injected hang longer than DISPATCH_TIMEOUT_S is cut off by
    the watchdog (classified fatal) instead of stalling the loop; the
    stream still finishes, token-identically, within a bounded wall."""
    # The timeout also covers each site's FIRST dispatch, compile included
    # (the insert's is 0.2-0.45 s with six workers busy): 0.3 s cut that
    # one about one run in five on a loaded machine, 1.5 s still cuts the
    # 30 s hang well inside the wall below.
    cfg = _cfg(fault_spec="chunk:hang(30)@2", dispatch_timeout_s=1.5,
               max_decode_len=16)
    bundle, eng = _echo_engine(cfg)
    feats = text_feats(bundle.tokenizer, "hang survivor")
    ref_eng = InferenceEngine(bundle, _cfg(max_decode_len=16),
                              ReplicaSet(make_mesh(1)))
    solo = _solo_tokens(ref_eng, feats)
    cdl = _supervised_cdl(eng, cfg)
    try:
        t0 = time.monotonic()
        (out,) = _run_concurrent(cdl, [feats])
        wall = time.monotonic() - t0
        n = min(len(out), len(solo))
        np.testing.assert_array_equal(out[:n], solo[:n])
        assert wall < 10.0, f"loop stalled {wall:.1f}s despite the watchdog"
        assert cdl.supervisor.restarts == 1
    finally:
        cdl.stop()


def test_restart_budget_exhaustion_fails_streams_and_loop():
    """Every chunk dispatch fatal: the supervisor spends its budget,
    streams error out, the loop stops, and new submissions are
    refused — the permanently-unready contract."""
    cfg = _cfg(fault_spec="chunk:fatal~1", engine_restarts_max=1)
    bundle, eng = _echo_engine(cfg)
    feats = text_feats(bundle.tokenizer, "doomed stream")
    cdl = _supervised_cdl(eng, cfg)

    async def consume():
        return await _collect(cdl.submit_stream(dict(feats)))

    try:
        with pytest.raises(FatalDeviceError):
            asyncio.run(consume())
        assert cdl.supervisor.failed
        for _ in range(100):
            if cdl._stop.is_set():
                break
            time.sleep(0.05)
        assert cdl._stop.is_set()
        with pytest.raises(RuntimeError):
            cdl.submit_stream(dict(feats))
    finally:
        cdl.stop()


def test_unsupervised_fatal_keeps_seed_behavior():
    """SUPERVISE off (no supervisor attached): a fatal fault errors the
    stream — the historical contract tests and operators rely on."""
    cfg = _cfg(fault_spec="chunk:fatal@2")
    bundle, eng = _echo_engine(cfg)
    feats = text_feats(bundle.tokenizer, "unsupervised text")
    cdl = ContinuousDecodeLoop(eng, cfg)

    async def consume():
        return await _collect(cdl.submit_stream(dict(feats)))

    try:
        with pytest.raises(FatalDeviceError):
            asyncio.run(consume())
    finally:
        cdl.stop()


# ---------------------------------------------------------------------------
# 4. API failure surface


def _serve(bundle_fn, body, **cfg_kw):
    from aiohttp.test_utils import TestClient, TestServer

    from mlmicroservicetemplate_tpu.api import build_app
    from mlmicroservicetemplate_tpu.scheduler import Batcher

    async def main():
        cfg_kw.setdefault("batch_timeout_ms", 1.0)
        cfg_kw.setdefault("max_decode_len", 8)
        cfg = _cfg(**cfg_kw)
        bundle = bundle_fn()
        engine = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
        batcher = Batcher(engine, cfg)
        app = build_app(cfg, bundle, engine, batcher)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            return await body(client, engine, batcher, app)
        finally:
            await client.close()

    return asyncio.run(main())


def test_structured_500_body_and_request_id_echo():
    async def body(client, engine, batcher, app):
        def boom(feats):
            raise RuntimeError("device exploded")

        engine.run_batch = boom
        resp = await client.post(
            "/predict", json={"text": "summarize: hi"},
            headers={"X-Request-Id": "req-abc-123"},
        )
        assert resp.status == 500
        assert resp.headers["X-Request-Id"] == "req-abc-123"
        data = await resp.json()
        err = data["error"]
        assert err["request_id"] == "req-abc-123"
        assert err["type"] and err["message"]
        # Without a client id the server mints one.
        resp = await client.post("/predict", json={"text": "summarize: hi"})
        assert resp.status == 500
        rid = resp.headers["X-Request-Id"]
        assert rid and (await resp.json())["error"]["request_id"] == rid
        # Healthy endpoints echo the id too.
        resp = await client.get("/healthz", headers={"X-Request-Id": "h-1"})
        assert resp.headers["X-Request-Id"] == "h-1"

    _serve(tiny_t5_bundle, body)


def test_stream_terminal_error_event():
    """A fatal device fault mid-SSE (supervision off) surfaces as a
    terminal in-band error event before close, on BOTH streaming
    flavors — never an abrupt connection drop with a clean-looking
    200 body."""

    async def body(client, engine, batcher, app):
        # /predict ndjson flavor.
        resp = await client.post(
            "/predict", json={"text": "summarize: abcdefghij", "stream": True},
            headers={"X-Request-Id": "sse-1"},
        )
        assert resp.status == 200
        lines = [ln for ln in (await resp.text()).splitlines() if ln.strip()]
        last = json.loads(lines[-1])
        assert last["error"]["type"] == "FatalDeviceError"
        assert last["error"]["request_id"] == "sse-1"

    _serve(
        tiny_t5_bundle, body,
        fault_spec="chunk:fatal@1", supervise=False, max_decode_len=16,
    )


def test_sse_error_event_v1_completions():
    async def body(client, engine, batcher, app):
        resp = await client.post(
            "/v1/completions",
            json={"prompt": "abcdefghij", "stream": True, "max_tokens": 12},
        )
        assert resp.status == 200
        text = await resp.text()
        assert "event: error" in text
        frame = [ln for ln in text.splitlines() if ln.startswith("data: ")][-1]
        err = json.loads(frame[len("data: "):])["error"]
        assert err["type"] == "FatalDeviceError" and err["request_id"]

    _serve(
        tiny_gpt_bundle, body,
        fault_spec="chunk:fatal@1", supervise=False, max_decode_len=16,
        seq_buckets=(16,),
    )


def test_canary_under_watchdog_flips_readyz():
    """A wedged probe dispatch must flip /readyz unready with a visible
    error instead of hanging the canary task silently."""

    async def body(client, engine, batcher, app):
        def wedged(feats):
            time.sleep(30)

        engine.run_batch = wedged
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            resp = await client.get("/readyz")
            data = await resp.json()
            if resp.status == 503 and data.get("error"):
                break
            await asyncio.sleep(0.05)
        assert resp.status == 503
        assert "Timeout" in data["error"] or "timeout" in data["error"]

    # warmup=False routes readiness through the canary probe.
    _serve(tiny_t5_bundle, body, dispatch_timeout_s=0.2, dispatch_retries=0)


def test_readyz_permanently_unready_after_budget():
    async def body(client, engine, batcher, app):
        for _ in range(200):
            resp = await client.get("/readyz")
            if resp.status == 200:
                break
            await asyncio.sleep(0.05)
        resp = await client.post(
            "/predict", json={"text": "summarize: doomed", "stream": True}
        )
        # The stream fails (budget exhausted) ...
        assert resp.status in (200, 500, 503)
        await resp.text()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            resp = await client.get("/readyz")
            data = await resp.json()
            if resp.status == 503 and "restart budget" in data.get("error", ""):
                break
            await asyncio.sleep(0.05)
        assert resp.status == 503
        assert "restart budget" in data["error"]
        status = await (await client.get("/status")).json()
        assert status["fault_tolerance"]["failed"] is True

    _serve(
        tiny_t5_bundle, body,
        fault_spec="chunk:fatal~1", engine_restarts_max=0, max_decode_len=16,
    )


# ---------------------------------------------------------------------------
# 5. paged-KV: disconnect returns blocks; oob injection checkpoints


def _paged_cfg(**kw):
    kw.setdefault("paged_kv", True)
    kw.setdefault("kv_block_size", 8)
    kw.setdefault("seq_buckets", (16, 32))
    kw.setdefault("batch_buckets", (1, 2, 4))
    kw.setdefault("max_decode_len", 16)
    kw.setdefault("max_streams", 2)
    return _cfg(**kw)


def test_paged_disconnect_frees_every_block_within_chunk():
    """Satellite: aborting an SSE stream mid-decode sets ``cancelled``,
    frees the slot and returns EVERY block to the pool within one
    chunk boundary (no prefix cache pinning here)."""
    cfg = _paged_cfg()
    bundle = tiny_gpt_bundle()
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    cdl = ContinuousDecodeLoop(eng, cfg)
    feats = text_feats(bundle.tokenizer, "a prompt that decodes a while")

    async def body():
        gen = cdl.submit_stream(dict(feats))
        async for _ in gen:
            break  # client disconnects after the first chunk
        await gen.aclose()
        for _ in range(200):
            if cdl._admitted == 0 and eng.kv_pool.used_blocks == 0:
                break
            await asyncio.sleep(0.05)
        assert cdl._admitted == 0
        assert eng.kv_pool.used_blocks == 0, eng.kv_pool.stats()
        assert sorted(cdl.free) == list(range(cdl.n_slots))
        assert not cdl.active

    try:
        asyncio.run(body())
    finally:
        cdl.stop()


def test_injected_oob_checkpoints_and_resumes():
    """A forced OutOfBlocks at the grow site rides the existing
    checkpoint-and-requeue path: the stream still completes with
    token-identical output."""
    cfg = _paged_cfg(fault_spec="grow:oob@2")
    bundle = tiny_gpt_bundle()
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    ref = InferenceEngine(bundle, _paged_cfg(), ReplicaSet(make_mesh(1)))
    feats = text_feats(bundle.tokenizer, "decode through an oob fault")
    solo = _solo_tokens(ref, feats)
    cdl = _supervised_cdl(eng, cfg)
    try:
        (out,) = _run_concurrent(cdl, [feats])
        n = min(len(out), len(solo))
        np.testing.assert_array_equal(out[:n], solo[:n])
        assert eng.faults.rules[0].fired >= 1
        # _END is emitted before the loop thread frees the slot; give
        # it a beat to finish the release bookkeeping.
        for _ in range(100):
            if eng.kv_pool.used_blocks == 0:
                break
            time.sleep(0.05)
        assert eng.kv_pool.used_blocks == 0, eng.kv_pool.stats()
    finally:
        cdl.stop()


@pytest.mark.parametrize("supervised", [True, False],
                         ids=["supervised", "unsupervised"])
def test_fetch_fault_ahead_of_a_wave_settles_its_streams_once(supervised):
    """The loop delivers the chunks that were in flight before a wave's
    start before it blocks on the wave's fetch (the newcomer meets the
    live stream in its stride: one chunk ahead of its start, the
    iteration's own behind it).  A fetch that fails among THOSE
    deliveries reaches the
    loop's handler with the wave still pending: supervised, the wave's
    stream and the live one are each checkpointed and re-queued once and
    finish token-identically after one rebuild; unsupervised, each
    consumer gets the error once.  Either way the loop admits the next
    request."""
    from test_decode_dispatch import _b_meets_a_live, _until_held, _wait

    cfg = _paged_cfg(max_decode_len=24)
    bundle = tiny_gpt_bundle()
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    fa, fb, fc = (text_feats(bundle.tokenizer, t) for t in
                  ("the live stream's prompt", "a newcomer", "the next one"))
    solos = [_solo_tokens(eng, f).tolist() for f in (fa, fb, fc)]
    cdl = (_supervised_cdl if supervised else ContinuousDecodeLoop)(eng, cfg)
    met = _b_meets_a_live(cdl, in_flight=1)
    fired, settled = [], []
    real_oldest, real_requeue, real_finish = (
        cdl._deliver_oldest, cdl._checkpoint_requeue, cdl._finish)

    def oldest():
        if not cdl._pending_admissions or fired:
            return real_oldest()
        # The first delivery ahead of the newcomer's wave: the entry
        # leaves the chain as the real one's does, then its fetch fails.
        fired.append(len(cdl._inflight_chunks))
        cdl._inflight_chunks.pop(0)

        def lost():
            raise FatalDeviceError("injected: a fetch ahead of the wave")

        eng.dispatch_guard("fetch", lost)

    def requeue(st):
        settled.append(("requeued", int(st.feats["length"])))
        return real_requeue(st)

    def finish(st, *item):
        if item and isinstance(item[0], Exception):
            settled.append(("failed", int(st.feats["length"])))
        return real_finish(st, *item)

    cdl._deliver_oldest, cdl._checkpoint_requeue, cdl._finish = (
        oldest, requeue, finish)

    async def outcome(gen):
        try:
            return (await _collect(gen)).tolist()
        except FatalDeviceError as e:
            return e

    async def body():
        gen_a = cdl.submit_stream(dict(fa))
        first = np.asarray(await gen_a.__anext__()).tolist()
        await _until_held(cdl)  # B arrives once the loop is held
        out_b, rest_a = await asyncio.gather(
            outcome(cdl.submit_stream(dict(fb))), outcome(gen_a))
        out_c = await outcome(cdl.submit_stream(dict(fc)))
        return first, rest_a, out_b, out_c

    try:
        first, rest_a, out_b, out_c = asyncio.run(body())
        assert met == [True] and len(fired) == 1 and fired[0] >= 1
        len_a, len_b = int(fa["length"]), int(fb["length"])
        if supervised:
            assert first + rest_a == solos[0] and out_b == solos[1]
            assert cdl.supervisor.restarts == 1
            assert sorted(settled) == sorted(
                [("requeued", len_a), ("requeued", len_b)])
        else:
            assert isinstance(rest_a, FatalDeviceError)
            assert isinstance(out_b, FatalDeviceError)
            # the wave's stream through the handler's pending list, once
            assert settled == [("failed", len_b)]
        assert out_c == solos[2]  # ... and the loop admits the next request
        assert _wait(lambda: eng.kv_pool.used_blocks == 0), eng.kv_pool.stats()
        assert sorted(cdl.free) == list(range(cdl.n_slots)) and not cdl.active
    finally:
        cdl.stop()


# ---------------------------------------------------------------------------
# 6. chaos tier (kept out of tier-1; scripts/check.sh runs it)


@pytest.mark.chaos
def test_fault_spec_smoke():
    """3-point FAULT_SPEC smoke matrix entry: scripts/check.sh runs
    this with FAULT_SMOKE_SPEC ∈ {transient, fatal, hang} against the
    supervised loop and expects token-identical completion."""
    import os

    spec = os.environ.get("FAULT_SMOKE_SPEC", "chunk:transient@2")
    cfg = _cfg(
        fault_spec=spec, dispatch_timeout_s=0.3, dispatch_retries=2,
        dispatch_backoff_s=0.01, max_decode_len=16,
    )
    bundle, eng = _echo_engine(cfg)
    feats = [text_feats(bundle.tokenizer, t) for t in ("smoke one", "two")]
    ref_eng = InferenceEngine(bundle, _cfg(max_decode_len=16),
                              ReplicaSet(make_mesh(1)))
    solos = [_solo_tokens(ref_eng, f) for f in feats]
    cdl = _supervised_cdl(eng, cfg)
    try:
        outs = _run_concurrent(cdl, feats)
        for got, want in zip(outs, solos):
            n = min(len(got), len(want))
            np.testing.assert_array_equal(got[:n], want[:n])
    finally:
        cdl.stop()


@pytest.mark.chaos
def test_fatal_recovery_gpt_recast_path():
    """Decoder-only greedy streams take the RECAST resume (prompt +
    delivered re-prefill) across an engine rebuild; delivered tokens
    are never re-sent and the final sequence matches the unfaulted
    run exactly."""
    cfg = _cfg(fault_spec="chunk:fatal@3", max_decode_len=16,
               seq_buckets=(16, 32, 64))
    bundle = tiny_gpt_bundle()
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    ref = InferenceEngine(bundle, _cfg(max_decode_len=16,
                                       seq_buckets=(16, 32, 64)),
                          ReplicaSet(make_mesh(1)))
    feats = [text_feats(bundle.tokenizer, t) for t in
             ("the quick brown fox", "pack my box")]
    solos = [_solo_tokens(ref, f) for f in feats]
    cdl = _supervised_cdl(eng, cfg)
    try:
        outs = _run_concurrent(cdl, feats)
        for got, want in zip(outs, solos):
            n = min(len(got), len(want))
            np.testing.assert_array_equal(got[:n], want[:n])
        assert cdl.supervisor.restarts >= 1
    finally:
        cdl.stop()


@pytest.mark.chaos
@pytest.mark.parametrize("family,quant,paged", [
    ("gpt", False, False),
    ("gpt", False, True),
    ("llama", False, False),
    ("llama", True, True),
])
def test_property_ledger_clean_after_random_fault_schedule(family, quant, paged):
    """Property: under a randomized transient/fatal/hang mix, after
    every stream drains the block pool has zero leaked or double-freed
    blocks (BlockPool raises on double free), no slot is orphaned, and
    the admission ledger reads zero committed bytes."""
    import random

    rng = random.Random(hash((family, quant, paged)) & 0xFFFF)
    specs = [
        f"chunk:transient@{rng.randint(1, 3)}",
        f"chunk:fatal@{rng.randint(2, 5)}",
        f"fetch:transient@{rng.randint(1, 4)}",
        "chunk:hang(30)@7",
    ]
    cfg_kw = dict(
        fault_spec=";".join(specs), fault_seed=rng.randint(0, 99),
        dispatch_timeout_s=0.5, dispatch_retries=2,
        dispatch_backoff_s=0.01, max_decode_len=16,
        engine_restarts_max=8, kv_budget_mb=4.0,
        max_streams=4, max_stream_queue=4,
    )
    cfg = _paged_cfg(**cfg_kw) if paged else _cfg(**cfg_kw)
    bundle = tiny_llama_bundle(kv_quant=quant) if family == "llama" \
        else tiny_gpt_bundle()
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    cdl = _supervised_cdl(eng, cfg)
    from mlmicroservicetemplate_tpu.scheduler.admission import AdmissionController

    cdl.admission = AdmissionController(cfg, eng)
    prompts = ["alpha beta", "gamma", "delta epsilon zeta", "eta theta"]
    feats = [text_feats(bundle.tokenizer, t) for t in prompts]

    async def drive():
        gens = [cdl.submit_stream(dict(f)) for f in feats]
        results = await asyncio.gather(
            *[_collect(g) for g in gens], return_exceptions=True
        )
        return results

    try:
        results = asyncio.run(drive())
        # Every stream terminated (tokens or a terminal error) — none
        # hung; with the restart budget this generous, all complete.
        completed = [r for r in results if not isinstance(r, BaseException)]
        assert len(completed) >= 1
        # Drain bookkeeping: no orphan slots, empty queue.
        for _ in range(100):
            if not cdl.active and cdl.queue.qsize() == 0:
                break
            time.sleep(0.05)
        assert not cdl.active
        assert sorted(cdl.free) == list(range(cdl.n_slots))
        if paged:
            # Flush prefix pins (none configured) and check the pool.
            assert eng.kv_pool.used_blocks == 0, eng.kv_pool.stats()
        assert cdl.admission.committed_bytes == 0
    finally:
        cdl.stop()
