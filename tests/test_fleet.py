"""Replica-fleet tests (engine/fleet.py + scheduler/router.py and
their integration into the supervisor, decode loop, batcher and API):

1. Supervisor sliding restart window (clock-injected): the budget
   counts only in-window restarts; window-mode stats report occupancy;
   the default window=0 keeps the historical lifetime cap.
2. Circuit breaker state machine (clock-injected): consecutive faults
   open it, half-open probes re-admit, one clean dispatch closes it,
   the eviction clock survives half-open flapping.
3. Replica-scoped FAULT_SPEC: ``rN:`` rules land on one replica's
   injector only.
4. Router policy: health gating, least-loaded ordering, prefix
   affinity, round-robin.
5. Fleet serving: R=2 token-identical streams; failover — a replica
   whose restart budget is spent hands every live stream to the
   survivor for token-identical resume; the dead replica's ledger
   drains to zero; degraded/all-dead readyz semantics; batch-class
   sheds first while degraded.
6. Bit-identity guard: FLEET_REPLICAS=1 (default) builds no fleet.

The full chaos scenario (R=2, paged, int8, kill one replica with
chunks in flight) lives in the chaos tier — scripts/check.sh
FLEET_SMOKE runs it.
"""

import asyncio
import time

import numpy as np
import pytest

from helpers import text_feats, tiny_llama_bundle
from mlmicroservicetemplate_tpu.engine import InferenceEngine
from mlmicroservicetemplate_tpu.engine.fleet import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    CircuitBreaker,
    ReplicaFleet,
)
from mlmicroservicetemplate_tpu.engine.faults import FaultInjector, parse_spec
from mlmicroservicetemplate_tpu.engine.streams import ContinuousDecodeLoop
from mlmicroservicetemplate_tpu.engine.supervisor import Supervisor
from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
from mlmicroservicetemplate_tpu.scheduler.policy import QueueFullError
from mlmicroservicetemplate_tpu.scheduler.router import Router
from mlmicroservicetemplate_tpu.utils.config import ServiceConfig

from test_streams import _collect, _echo_bundle, _solo_tokens


def _cfg(**kw) -> ServiceConfig:
    kw.setdefault("device", "cpu")
    kw.setdefault("warmup", False)
    kw.setdefault("batch_buckets", (1, 2, 4, 8))
    kw.setdefault("seq_buckets", (16, 32))
    kw.setdefault("max_decode_len", 12)
    kw.setdefault("stream_chunk_tokens", 4)
    kw.setdefault("max_streams", 4)
    return ServiceConfig(**kw)


class _Clock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


# ---------------------------------------------------------------------------
# 1. supervisor sliding window


def test_supervisor_window_slides_budget():
    clk = _Clock()
    sup = Supervisor(max_restarts=2, window_s=10.0, clock=clk)
    assert sup.allow_restart()  # t=0
    clk.t = 1.0
    assert sup.allow_restart()  # t=1: window full (2/2)
    clk.t = 2.0
    assert not sup.allow_restart()
    assert sup.failed
    st = sup.stats()
    assert st["window_s"] == 10.0 and st["window_used"] == 2
    # The oldest in-window restart (t=0) frees its slot at t=10.
    assert 7.9 < sup.retry_eta_s() <= 8.0
    # Hours later the window is empty — the budget is back.  ``failed``
    # stays sticky (the loop already stopped), but occupancy reports
    # honestly for the fleet's Retry-After guidance.
    clk.t = 60.0
    assert sup.window_used() == 0
    assert sup.retry_eta_s() == 0.0


def test_supervisor_window_spread_faults_never_exhaust():
    """The satellite's point: faults hours apart never condemn a
    long-lived replica, where the lifetime cap would have."""
    clk = _Clock()
    sup = Supervisor(max_restarts=2, window_s=5.0, clock=clk)
    for i in range(20):  # one fault every 10s against a 5s window
        clk.t = i * 10.0
        assert sup.allow_restart(), f"refused at restart {i}"
    assert not sup.failed
    assert sup.restarts == 20  # lifetime count stays observable
    # Same schedule under the lifetime cap fails at the third fault.
    sup2 = Supervisor(max_restarts=2, window_s=0.0, clock=clk)
    assert sup2.allow_restart() and sup2.allow_restart()
    assert not sup2.allow_restart() and sup2.failed


def test_supervisor_default_lifetime_semantics_unchanged():
    sup = Supervisor(max_restarts=1)
    assert sup.window_s == 0.0
    assert sup.allow_restart()
    assert not sup.allow_restart()
    assert sup.failed
    assert sup.retry_eta_s() == 0.0
    assert "window_s" not in sup.stats()


# ---------------------------------------------------------------------------
# 2. circuit breaker


def test_breaker_state_machine():
    clk = _Clock()
    br = CircuitBreaker(threshold=3, evict_s=10.0, clock=clk)
    assert br.state == CLOSED and br.allow()
    br.record_fault()
    br.record_fault()
    assert br.state == CLOSED  # streak 2 < 3
    br.record_ok()
    br.record_fault()
    br.record_fault()
    assert br.state == CLOSED  # ok reset the streak
    br.record_fault()
    assert br.state == OPEN and not br.allow()
    # Half-open probe window opens at evict_s/2.
    assert br.retry_eta_s() == pytest.approx(5.0)
    clk.t = 5.0
    assert br.state == HALF_OPEN and br.allow()
    # A probe fault re-opens; the EVICTION clock keeps its origin.
    br.record_fault()
    assert br.state == OPEN
    assert br.open_elapsed() == pytest.approx(5.0)
    clk.t = 10.0
    assert br.open_elapsed() == pytest.approx(10.0)  # eviction due
    # A clean dispatch in a later half-open window closes everything.
    clk.t = 11.0
    assert br.state == HALF_OPEN
    br.record_ok()
    assert br.state == CLOSED and br.open_elapsed() is None


# ---------------------------------------------------------------------------
# 3. replica-scoped FAULT_SPEC


def test_replica_scoped_spec_parse_and_filter():
    rules = parse_spec("r1:chunk:fatal@3;chunk:transient@2;r0:grow:oob@1")
    assert [r.replica for r in rules] == [1, None, 0]
    assert [r.site for r in rules] == ["chunk", "chunk", "grow"]
    # Replica 0's injector sees the unscoped rule and its own.
    inj0 = FaultInjector.from_spec(
        "r1:chunk:fatal@3;chunk:transient@2;r0:grow:oob@1", replica=0
    )
    assert sorted(repr(r) for r in inj0.rules) == sorted(
        ["chunk:transient@2+1", "r0:grow:oob@1+1"]
    )
    inj1 = FaultInjector.from_spec("r1:chunk:fatal@3", replica=0)
    assert inj1 is None  # nothing lands on replica 0 at all
    with pytest.raises(ValueError):
        parse_spec("r1:bogus:fatal@1")


# ---------------------------------------------------------------------------
# 4. router policy (stub replicas — no engines needed)


class _StubQueue:
    def __init__(self, n):
        self.n = n

    def qsize(self):
        return self.n


class _StubCdl:
    def __init__(self, active=0, queued=0, kv=0):
        self.active = {i: None for i in range(active)}
        self.queue = _StubQueue(queued)
        self._prefilling = []
        self.admission = type(
            "A", (), {"committed_bytes": kv}
        )()


class _StubReplica:
    def __init__(self, rid, active=0, queued=0, kv=0, cache=None):
        self.id = rid
        self.cdl = _StubCdl(active, queued, kv)
        self.engine = type("E", (), {"prefix_cache": cache})()


def test_router_least_loaded_order():
    r0 = _StubReplica(0, active=3, queued=2)  # load 5
    r1 = _StubReplica(1, active=1, queued=0)  # load 1
    r2 = _StubReplica(2, active=1, queued=0, kv=int(2e6))  # load 1 + 2 MB
    order = Router("least").order([r0, r1, r2], {"length": 4})
    assert [r.id for r in order] == [1, 2, 0]


def test_router_round_robin_cycles():
    reps = [_StubReplica(i) for i in range(3)]
    router = Router("rr")
    firsts = [router.order(reps, {})[0].id for _ in range(6)]
    assert firsts == [0, 1, 2, 0, 1, 2]


def test_router_prefix_affinity_beats_load():
    from mlmicroservicetemplate_tpu.engine.prefix_cache import PrefixCache

    ids = np.arange(40, dtype=np.int32)
    cache = PrefixCache((16, 32), budget_mb=1.0)
    cache.insert(ids, 32, {"k": np.zeros((1, 32), np.float32)})
    # The replica holding the prefix is BUSIER but still wins.
    hot = _StubReplica(0, active=2, cache=cache)
    idle = _StubReplica(1, active=0)
    feats = {"input_ids": ids, "length": np.int32(40)}
    order = Router("least").order([idle, hot], feats)
    assert order[0].id == 0
    # The probe never mutates stats or recency.
    assert cache.hits == 0 and cache.misses == 0
    # Without a cached prefix, load decides.
    order = Router("least").order(
        [idle, hot], {"input_ids": ids[:4], "length": np.int32(4)}
    )
    assert order[0].id == 1


# ---------------------------------------------------------------------------
# 5. fleet serving + failover (echo bundle: fast, deterministic)


def _echo_fleet(cfg):
    bundle = _echo_bundle()
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    return bundle, ReplicaFleet(eng, cfg)


def _run_fleet(fleet, feats_list):
    async def body():
        gens = [fleet.submit_stream(dict(f)) for f in feats_list]
        return await asyncio.gather(
            *[_collect(g) for g in gens], return_exceptions=True
        )

    return asyncio.run(body())


def test_fleet_streams_token_identical_across_replicas():
    cfg = _cfg(fleet_replicas=2, max_decode_len=16)
    bundle, fleet = _echo_fleet(cfg)
    ref = InferenceEngine(
        _echo_bundle(), _cfg(max_decode_len=16), ReplicaSet(make_mesh(1))
    )
    prompts = ["alpha", "beta two", "gamma three text", "d"]
    feats = [text_feats(bundle.tokenizer, t) for t in prompts]
    solos = [_solo_tokens(ref, f) for f in feats]
    try:
        outs = _run_fleet(fleet, feats)
        for got, want in zip(outs, solos):
            assert not isinstance(got, BaseException), got
            n = min(len(got), len(want))
            np.testing.assert_array_equal(got[:n], want[:n])
        assert len(fleet.healthy_replicas()) == 2
        assert not fleet.degraded
    finally:
        fleet.stop()


def test_fleet_failover_token_identical_on_survivor():
    """The robustness core: replica 0's restart budget is spent on its
    first chunk fault; every live stream checkpoints at the delivered-
    token cursor and finishes token-identically on replica 1."""
    cfg = _cfg(
        fleet_replicas=2, max_decode_len=16,
        fault_spec="r0:chunk:fatal~1", engine_restarts_max=0,
    )
    bundle, fleet = _echo_fleet(cfg)
    ref = InferenceEngine(
        _echo_bundle(), _cfg(max_decode_len=16), ReplicaSet(make_mesh(1))
    )
    prompts = ["failover one", "second stream", "x"]
    feats = [text_feats(bundle.tokenizer, t) for t in prompts]
    solos = [_solo_tokens(ref, f) for f in feats]
    try:
        outs = _run_fleet(fleet, feats)
        for got, want in zip(outs, solos):
            assert not isinstance(got, BaseException), got
            n = min(len(got), len(want))
            np.testing.assert_array_equal(got[:n], want[:n])
            assert not np.any(want[n:] != 0) and not np.any(got[n:] != 0)
        # Someone died and someone survived: the streams were routed by
        # least-loaded, so SOME landed on replica 0 and failed over.
        assert fleet.replicas[0].dead
        assert fleet.replicas[0].dead_cause in ("budget", "fault")
        assert fleet.degraded
        assert fleet.failovers >= 1
        assert len(fleet.healthy_replicas()) == 1
    finally:
        fleet.stop()


def test_degraded_sheds_batch_class_first():
    cfg = _cfg(fleet_replicas=2, max_decode_len=8)
    bundle, fleet = _echo_fleet(cfg)
    async def drive():
        fleet._mark_dead(fleet.replicas[0], "evicted")
        assert fleet.degraded
        feats = text_feats(bundle.tokenizer, "batch job")
        feats["priority"] = "batch"
        with pytest.raises(QueueFullError) as ei:
            fleet.submit_stream(dict(feats))
        assert ei.value.reason == "degraded"
        # Interactive still serves on the survivor.
        ok = dict(text_feats(bundle.tokenizer, "vip"))
        out = await _collect(fleet.submit_stream(ok))
        assert out.size > 0

    try:
        asyncio.run(drive())
    finally:
        fleet.stop()


def test_all_dead_sheds_with_retry_after():
    cfg = _cfg(fleet_replicas=2, fleet_evict_s=6.0)
    bundle, fleet = _echo_fleet(cfg)
    try:
        for rep in fleet.replicas:
            fleet._mark_dead(rep, "budget")
        with pytest.raises(QueueFullError) as ei:
            fleet.submit_stream(text_feats(bundle.tokenizer, "nope"))
        assert ei.value.reason == "fleet_down"
        assert ei.value.retry_after_s >= 1.0
    finally:
        fleet.stop()


def test_all_dead_retry_after_reports_governor_replacement_eta():
    """ISSUE 12 satellite: the all-dead Retry-After reports the SOONER
    of the breaker half-open ETA and the governor's replacement
    spin-up ETA (a dead replica rebuilds FLEET_EVICT_S after death,
    within one governor period).  Pre-elastic, only the breaker clock
    was consulted — a fleet 90% of the way to its rejoin still told
    clients to wait the full half-open interval."""
    clk = _Clock()
    cfg = _cfg(fleet_replicas=2, fleet_min_replicas=1,
               fleet_max_replicas=2, fleet_evict_s=20.0,
               scale_period_s=0.5)
    bundle = _echo_bundle()
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    fleet = ReplicaFleet(eng, cfg, clock=clk, autoscale_thread=False)
    try:
        for rep in fleet.replicas:
            fleet._mark_dead(rep, "budget")  # dead_at = 0
        # Dead breakers report probe_after = evict_s/2 = 10s; the
        # rejoin is due at t=20 → at t=18 the governor ETA (2s + one
        # 0.5s period) wins.
        clk.t = 18.0
        assert fleet.retry_after_s() == pytest.approx(2.5)
        with pytest.raises(QueueFullError) as ei:
            fleet.submit_stream(text_feats(bundle.tokenizer, "x"))
        assert ei.value.reason == "fleet_down"
        assert ei.value.retry_after_s == pytest.approx(2.5)
    finally:
        fleet.stop()
    # Static fleet: the breaker clock alone (historical behavior).
    cfg2 = _cfg(fleet_replicas=2, fleet_evict_s=20.0)
    eng2 = InferenceEngine(_echo_bundle(), cfg2, ReplicaSet(make_mesh(1)))
    fleet2 = ReplicaFleet(eng2, cfg2, clock=clk, autoscale_thread=False)
    try:
        for rep in fleet2.replicas:
            fleet2._mark_dead(rep, "budget")
        assert fleet2.retry_after_s() == pytest.approx(10.0)
    finally:
        fleet2.stop()


def test_breaker_eviction_requests_evacuation():
    """A breaker stuck open past FLEET_EVICT_S retires the replica on
    the next sweep, even with no fault currently in flight."""
    clk = _Clock()
    cfg = _cfg(fleet_replicas=2, fleet_breaker_n=2, fleet_evict_s=4.0)
    bundle, fleet = _echo_fleet(cfg)
    try:
        rep = fleet.replicas[0]
        rep.breaker._clock = clk
        rep.breaker.record_fault()
        rep.breaker.record_fault()  # opens
        assert not rep.healthy()
        clk.t = 4.0  # eviction due; loop thread never started -> retire
        fleet.sweep()
        assert rep.dead and rep.dead_cause == "evicted"
        assert fleet.degraded
    finally:
        fleet.stop()


# ---------------------------------------------------------------------------
# 6. batcher / API integration


def test_default_config_builds_no_fleet():
    """FLEET_REPLICAS=1 (default) must keep the single-loop path —
    the bit-identity guard for every existing suite."""
    from mlmicroservicetemplate_tpu.scheduler import Batcher

    cfg = _cfg()
    bundle = _echo_bundle()
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    b = Batcher(eng, cfg)
    assert b.fleet is None
    assert isinstance(b._cdl, ContinuousDecodeLoop)
    assert b._cdl.failover is None and b._cdl.on_fault is None
    assert b._cdl.engine is eng


def _serve_fleet(body, **cfg_kw):
    from aiohttp.test_utils import TestClient, TestServer

    from helpers import tiny_gpt_bundle
    from mlmicroservicetemplate_tpu.api import build_app
    from mlmicroservicetemplate_tpu.scheduler import Batcher

    async def main():
        cfg_kw.setdefault("fleet_replicas", 2)
        cfg_kw.setdefault("max_decode_len", 8)
        cfg_kw.setdefault("seq_buckets", (16, 32))
        cfg_kw.setdefault("batch_timeout_ms", 1.0)
        cfg = _cfg(**cfg_kw)
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
            return await body(client, batcher)
        finally:
            await client.close()

    return asyncio.run(main())


def test_unary_batch_path_routes_through_fleet():
    """ROADMAP item 3 leftover (round 14): /predict batch dispatches
    go to a HEALTHY replica picked by the router — a dead replica no
    longer serves the unary path, and an all-dead fleet sheds 503
    instead of dispatching onto a corpse."""

    async def body(client, batcher):
        fleet = batcher.fleet
        r0, r1 = fleet.replicas
        # Healthy fleet: the pick is a healthy replica.
        rep = fleet.pick_batch_replica({})
        assert rep in (r0, r1)
        # Kill replica 0: every pick lands on replica 1.
        fleet._mark_dead(r0, "evicted")
        for _ in range(4):
            assert fleet.pick_batch_replica({}) is r1
        resp = await client.post("/predict", json={"text": "hello"})
        assert resp.status == 200
        # All dead: the batch path sheds like the stream path.
        fleet._mark_dead(r1, "evicted")
        with pytest.raises(QueueFullError, match="every fleet replica"):
            fleet.pick_batch_replica({})
        resp = await client.post("/predict", json={"text": "again"})
        assert resp.status == 503

    _serve_fleet(body)


def test_readyz_fleet_degraded_and_all_dead():
    async def body(client, batcher):
        fleet = batcher.fleet
        assert fleet is not None and fleet.n == 2
        resp = await client.get("/readyz")
        assert resp.status == 200
        data = await resp.json()
        assert data["fleet"] == {"healthy": 2, "replicas": 2}
        assert "X-Fleet-Degraded" not in resp.headers
        # One replica dies: still ready, explicitly degraded — and the
        # service still SERVES through the survivor.
        fleet._mark_dead(fleet.replicas[0], "budget")
        resp = await client.get("/readyz")
        assert resp.status == 200
        assert resp.headers["X-Fleet-Degraded"] == "1/2"
        assert (await resp.json())["degraded"] is True
        r = await client.post(
            "/predict", json={"text": "still serving", "stream": True}
        )
        assert r.status == 200
        await r.text()
        # /status surfaces the per-replica detail.
        status = await (await client.get("/status")).json()
        fl = status["fleet"]
        assert fl["dead"] == 1 and fl["healthy"] == 1
        assert fl["per_replica"][0]["breaker"] == "dead"
        # All dead: 503 with Retry-After from the breaker ETA.
        fleet._mark_dead(fleet.replicas[1], "budget")
        resp = await client.get("/readyz")
        assert resp.status == 503
        assert int(resp.headers["Retry-After"]) >= 1
        assert "dead" in (await resp.json())["error"]
        # healthz stays alive (liveness never flips on fleet health).
        resp = await client.get("/healthz")
        assert resp.status == 200
        assert (await resp.json())["fleet_healthy"] == 0

    _serve_fleet(body)


def test_fleet_serving_failover_over_http():
    """End-to-end through the API: replica 0 is killed by a replica-
    scoped schedule mid-serving; every stream completes with 200 and
    the exact tokens of an unfaulted run."""

    async def body(client, batcher):
        async def one(text):
            r = await client.post(
                "/predict", json={"text": text, "stream": True}
            )
            assert r.status == 200
            return await r.text()

        texts = ["fox one", "fox two", "fox three", "fox four"]
        got = await asyncio.gather(*[one(t) for t in texts])
        for g in got:
            assert '"done": true' in g or '"done"' in g
            assert "error" not in g
        fleet = batcher.fleet
        assert fleet.replicas[0].dead
        # The dead replica's pool ledger drained to zero.
        pool = fleet.replicas[0].engine.kv_pool
        if pool is not None:
            assert pool.used_blocks == 0
        return got

    import json

    got = _serve_fleet(
        body,
        fault_spec="r0:chunk:fatal~1", engine_restarts_max=0,
        max_decode_len=16,
    )
    ref = _serve_fleet(
        lambda client, b: _http_all(client, ["fox one", "fox two",
                                             "fox three", "fox four"]),
        fleet_replicas=1, max_decode_len=16,
    )
    # Token-identity over the wire: the faulted fleet's final texts
    # match the clean single-replica run, stream for stream.
    for a, b in zip(got, ref):
        fa = [json.loads(x) for x in a.splitlines() if x.strip()]
        fb = [json.loads(x) for x in b.splitlines() if x.strip()]
        assert fa[-1]["prediction"] == fb[-1]["prediction"]


async def _http_all(client, texts):
    async def one(text):
        r = await client.post(
            "/predict", json={"text": text, "stream": True}
        )
        assert r.status == 200
        return await r.text()

    return await asyncio.gather(*[one(t) for t in texts])


def test_fleet_rejects_shared_multi_device_mesh():
    """Two engines over one sharded mesh would interleave collectives
    (rendezvous deadlock): the fleet must refuse at startup."""
    cfg = _cfg(fleet_replicas=2)
    bundle = _echo_bundle()
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(2)))
    with pytest.raises(ValueError, match="single-device"):
        ReplicaFleet(eng, cfg)


def test_fleet_config_knobs_and_validators():
    from mlmicroservicetemplate_tpu.utils.config import load_config

    cfg = load_config({
        "DEVICE": "cpu", "FLEET_REPLICAS": "2", "FLEET_ROUTE": "rr",
        "FLEET_BREAKER_N": "5", "FLEET_EVICT_S": "3.5",
        "ENGINE_RESTART_WINDOW_S": "60",
    })
    assert cfg.fleet_replicas == 2 and cfg.fleet_route == "rr"
    assert cfg.fleet_breaker_n == 5 and cfg.fleet_evict_s == 3.5
    assert cfg.engine_restart_window_s == 60.0
    for bad in (
        {"fleet_replicas": 0},
        {"fleet_route": "weighted"},
        {"fleet_breaker_n": 0},
        {"fleet_evict_s": -1.0},
        {"engine_restart_window_s": -1.0},
    ):
        with pytest.raises(Exception):
            ServiceConfig(device="cpu", **bad)
    # Defaults: the bit-identity contract.
    dflt = ServiceConfig(device="cpu")
    assert dflt.fleet_replicas == 1
    assert dflt.engine_restart_window_s == 0.0


# ---------------------------------------------------------------------------
# 7. chaos tier: the acceptance scenario (scripts/check.sh FLEET_SMOKE)


@pytest.mark.chaos
def test_fleet_failover_chaos_paged_int8():
    """R=2, paged KV, int8 KV quant: a replica-scoped fatal schedule
    exhausts replica 0's restart window with chunks in flight.  Every
    in-flight stream must resume and complete token-identically on the
    survivor, zero streams lost, and the dead replica's block-pool
    ledger must drain to zero."""
    import os

    spec = os.environ.get("FLEET_SMOKE_SPEC", "r0:chunk:fatal@2")
    # Budget = 8 chunks a stream; the @2 fatal lands on replica 0's
    # SECOND chunk dispatch, i.e. mid-stream.
    cfg = _cfg(
        fleet_replicas=2, fault_spec=spec, engine_restarts_max=0,
        engine_restart_window_s=60.0,
        paged_kv=True, kv_block_size=8,
        max_decode_len=32, seq_buckets=(16, 32), max_streams=4,
    )
    bundle = tiny_llama_bundle(kv_quant=True)
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    fleet = ReplicaFleet(eng, cfg)
    ref = InferenceEngine(
        tiny_llama_bundle(kv_quant=True),
        _cfg(max_decode_len=32, seq_buckets=(16, 32)),
        ReplicaSet(make_mesh(1)),
    )
    prompts = ["the quick brown fox", "pack my box", "jinxed wizards",
               "five dozen jugs"]
    feats = [text_feats(bundle.tokenizer, t) for t in prompts]
    solos = [_solo_tokens(ref, f) for f in feats]
    try:
        outs = _run_fleet(fleet, feats)
        lost = [o for o in outs if isinstance(o, BaseException)]
        assert not lost, f"streams lost across failover: {lost}"
        for got, want in zip(outs, solos):
            n = min(len(got), len(want))
            np.testing.assert_array_equal(got[:n], want[:n])
            assert not np.any(want[n:] != 0) and not np.any(got[n:] != 0)
        assert fleet.replicas[0].dead, "the r0 schedule never landed"
        assert fleet.failovers >= 1
        assert eng.faults.rules[0].fired >= 1
        # Ledger hygiene: the dead replica's pool AND the survivor's
        # both drain to zero once every stream finished.
        for rep in fleet.replicas:
            for _ in range(100):
                if rep.engine.kv_pool.used_blocks == 0:
                    break
                time.sleep(0.05)
            assert rep.engine.kv_pool.used_blocks == 0, (
                rep.id, rep.engine.kv_pool.stats()
            )
    finally:
        fleet.stop()
