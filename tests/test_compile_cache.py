"""Zero-compile spawn + double-buffered host prep (ISSUE 14).

The contracts under test:

1. **Zero-compile second spawn** (the acceptance pin): with the
   process-level ExecutableCache (runtime/compile_cache.py) populated
   by replica 0's warm, growing the fleet performs ZERO XLA backend
   compiles — counted at the ``jax.monitoring`` seam, not inferred —
   and the scale event's breakdown records it.
2. **No aliasing**: distinct bundle objects, kinds and static
   descriptors never share a cache entry; the same (bundle, kind,
   statics, placement) always does — including across an in-process
   "restart" (a second engine over the same bundle).
3. **Persistent XLA cache knob**: ``COMPILE_CACHE_DIR`` is a
   ServiceConfig knob now; a path enables the disk cache (entries
   really land on disk — the layer that carries compiles across
   process restarts / journal replays), "0" disables, CPU default off.
4. **Double-buffered host prep** (docs/compilation.md): token identity
   across gpt/llama × {contig, paged} × {greedy, pinned-seed sampled}
   vs the serial-prep loop, with staged plans actually consumed.
5. **Mid-prep fatal** → supervised checkpoint-resume, token-identical,
   ledger drains.
6. Chaos (out of tier-1): an ``rN:``-scoped kill during STAGED prep
   fails over token-identically onto the survivor.
"""

import asyncio
import time

import numpy as np
import pytest

from helpers import text_feats, tiny_gpt_bundle, tiny_llama_bundle
from mlmicroservicetemplate_tpu.engine import InferenceEngine
from mlmicroservicetemplate_tpu.engine.fleet import ReplicaFleet
from mlmicroservicetemplate_tpu.engine.streams import ContinuousDecodeLoop
from mlmicroservicetemplate_tpu.engine.supervisor import Supervisor
from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
from mlmicroservicetemplate_tpu.runtime import compile_cache as cc
from mlmicroservicetemplate_tpu.utils.config import ServiceConfig

from test_streams import _collect, _run_concurrent, _solo_tokens


def _cfg(**kw) -> ServiceConfig:
    kw.setdefault("device", "cpu")
    kw.setdefault("warmup", False)
    kw.setdefault("batch_buckets", (1, 2))
    kw.setdefault("seq_buckets", (16,))
    kw.setdefault("max_decode_len", 16)
    kw.setdefault("stream_chunk_tokens", 4)
    kw.setdefault("max_streams", 2)
    return ServiceConfig(**kw)


# ---------------------------------------------------------------------------
# 1. the acceptance pin: second replica spawn performs zero XLA compiles


def test_second_replica_spawn_zero_xla_compiles(monkeypatch):
    """Replica 0 warms (pays every compile once, into the shared
    cache); scale_to(2) then builds + warms + probes a whole new
    replica with ZERO backend compiles — counted via jax.monitoring,
    and recorded in the scale event's breakdown."""
    monkeypatch.setenv("WARMUP_SAMPLING", "0")
    cfg = _cfg(fleet_replicas=1, fleet_max_replicas=2,
               max_decode_len=8)
    bundle = tiny_gpt_bundle()
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    fleet = ReplicaFleet(eng, cfg, autoscale_thread=False)
    try:
        fleet.warm()  # replica 0 pays the compiles, into the cache
        before = cc.cache_stats()
        with cc.CompileWindow() as w:
            assert fleet.scale_to(2, cause="manual") == 2
        assert w.compiles == 0, (
            f"second spawn performed {w.compiles} XLA compiles "
            f"({w.seconds:.2f}s) — the ExecutableCache did not share"
        )
        after = cc.cache_stats()
        assert after["insert"] == before["insert"], (
            "the spawn inserted new executables instead of sharing"
        )
        assert after["hit"] > before["hit"]
        # The event breakdown records the same fact for operators.
        ups = [e for e in fleet._scale_events if e["dir"] == "up"]
        assert ups and ups[-1]["breakdown"]["xla_compiles"] == 0
        assert {"build_s", "warm_s", "probe_s", "rebalance_s"} <= set(
            ups[-1]["breakdown"]
        )
        # And the spawned replica actually serves, token-identically.
        ref = InferenceEngine(
            bundle, _cfg(max_decode_len=8), ReplicaSet(make_mesh(1))
        )
        feats = [
            text_feats(bundle.tokenizer, t) for t in ("abc", "wxyz q")
        ]
        solos = [_solo_tokens(ref, f) for f in feats]

        async def body():
            gens = [fleet.submit_stream(dict(f)) for f in feats]
            return await asyncio.gather(*[_collect(g) for g in gens])

        outs = asyncio.run(body())
        for got, want in zip(outs, solos):
            n = min(len(got), len(want))
            np.testing.assert_array_equal(got[:n], want[:n])
    finally:
        fleet.stop()


def test_in_process_restart_reuses_executables(monkeypatch):
    """The journal-replay / supervised-restart path: a SECOND engine
    over the same bundle object (what a Batcher rebuild constructs)
    compiles nothing — its wrappers come from the shared cache."""
    monkeypatch.setenv("WARMUP_SAMPLING", "0")
    cfg = _cfg()
    bundle = tiny_gpt_bundle()
    rs = ReplicaSet(make_mesh(1))
    eng1 = InferenceEngine(bundle, cfg, rs)
    feats = text_feats(bundle.tokenizer, "warm me up")
    _solo_tokens(eng1, feats)  # pays the compiles
    with cc.CompileWindow() as w:
        eng2 = InferenceEngine(bundle, cfg, rs)
        out2 = _solo_tokens(eng2, feats)
    assert w.compiles == 0, (
        f"engine rebuild re-compiled {w.compiles} executables"
    )
    np.testing.assert_array_equal(out2, _solo_tokens(eng1, feats))
    assert eng2._gen_chunk is eng1._gen_chunk
    assert eng2._start is eng1._start


# ---------------------------------------------------------------------------
# 2. cache keying never aliases


def test_cache_keying_never_aliases():
    rs = ReplicaSet(make_mesh(1))
    b1, b2 = tiny_gpt_bundle(), tiny_gpt_bundle(seed=1)
    built = []

    def build():
        token = object()
        built.append(token)
        return token

    # Same (bundle, kind, statics, placement) → one build, shared.
    f1 = cc.shared_executable("k", b1, rs, build)
    f2 = cc.shared_executable("k", b1, rs, build)
    assert f1 is f2 and len(built) == 1
    # Distinct bundle OBJECTS never alias — same name, same dims.
    f3 = cc.shared_executable("k", b2, rs, build)
    assert f3 is not f1 and len(built) == 2
    # Distinct kinds and distinct static descriptors never alias.
    assert cc.shared_executable("k2", b1, rs, build) is not f1
    assert cc.shared_executable("k", b1, rs, build, statics=(32,)) \
        is not f1
    # Same statics share again.
    assert cc.shared_executable(
        "k", b1, rs, build, statics=(32,)
    ) is cc.shared_executable("k", b1, rs, build, statics=(32,))
    # Fingerprints are sticky and unique.
    assert cc.bundle_fingerprint(b1) == cc.bundle_fingerprint(b1)
    assert cc.bundle_fingerprint(b1) != cc.bundle_fingerprint(b2)


def test_shared_executable_is_bare_without_group():
    """A single-group deployment dispatches straight into JAX: the
    cache hands out the jitted function itself.  Only a non-prefix
    device group (a multi-chip fleet replica) gets the proxy, which
    pins its trace group around every call and lower."""
    import jax

    from mlmicroservicetemplate_tpu.parallel import TensorParallelSet
    from mlmicroservicetemplate_tpu.parallel.tp import gpt_param_spec
    from mlmicroservicetemplate_tpu.parallel.tpserve import (
        current_trace_group,
        serving_tp_mesh,
    )

    b = tiny_gpt_bundle(tp=2)
    spec = gpt_param_spec(b.cfg)
    built, traced_under = [], []

    def build():
        def f(x):
            traced_under.append(current_trace_group())
            return x + 1

        built.append(jax.jit(f))
        return built[-1]

    assert cc.shared_executable(
        "bare", b, ReplicaSet(make_mesh(1)), build) is built[0]
    # The default prefix group is no group (device_group normalizes it).
    assert cc.shared_executable(
        "bare", b, TensorParallelSet(serving_tp_mesh(2, 1), spec), build
    ) is built[1]
    pinned = cc.shared_executable(
        "bare", b, TensorParallelSet(serving_tp_mesh(2, 1, (4, 5)), spec),
        build,
    )
    assert pinned is not built[2] and pinned._fn is built[2]
    assert int(pinned(1)) == 2
    pinned.lower(1.0)  # another dtype: traced again
    assert traced_under == [(4, 5), (4, 5)]
    assert current_trace_group() is None


# ---------------------------------------------------------------------------
# 3. COMPILE_CACHE_DIR as a ServiceConfig knob + the disk layer


def test_compile_cache_dir_knob(tmp_path, monkeypatch):
    from mlmicroservicetemplate_tpu.runtime.device import (
        enable_compilation_cache,
    )

    monkeypatch.delenv("COMPILE_CACHE_DIR", raising=False)
    # The knob overrides (even on CPU, where the default is off)…
    cfg = ServiceConfig(device="cpu",
                        compile_cache_dir=str(tmp_path / "xla"))
    assert enable_compilation_cache("cpu", cfg.compile_cache_dir) \
        == str(tmp_path / "xla")
    # …"0" disables even on tpu, and unset keeps CPU off.
    assert enable_compilation_cache("tpu", "0") is None
    assert enable_compilation_cache("cpu", None) is None
    # Env-var mapping: load_config plumbs COMPILE_CACHE_DIR through.
    from mlmicroservicetemplate_tpu.utils.config import load_config

    got = load_config({"COMPILE_CACHE_DIR": "/tmp/x", "DEVICE": "cpu"})
    assert got.compile_cache_dir == "/tmp/x"


def test_persistent_cache_writes_entries(tmp_path, monkeypatch):
    """The disk layer restart replay leans on: with the knob set,
    fresh compiles land in COMPILE_CACHE_DIR (a restarted process
    reads them back instead of re-compiling)."""
    import jax

    from mlmicroservicetemplate_tpu.runtime.device import (
        enable_compilation_cache,
    )

    cache_dir = str(tmp_path / "xla")
    assert enable_compilation_cache("cpu", cache_dir) == cache_dir
    # named_scope paths are part of the key: an executable cached
    # by another commit never comes back under that commit's names
    assert jax.config.jax_compilation_cache_include_metadata_in_key

    @jax.jit
    def f(x):
        return (x * 3.0 + 1.0).sum()

    f(np.arange(17.0))  # unique shape → fresh compile → disk entry
    import os

    entries = os.listdir(cache_dir)
    assert entries, "no persistent cache entry written"
    # tests/conftest.py::_compile_cache_guard puts the four values back
    # and drops the cache object, which is latched on ``cache_dir`` now


def test_no_test_leaves_the_persistent_cache_on():
    """Whatever this worker ran before — the three tests above, in one
    process — the cache stands where the session found it: no directory
    but one placed from outside, and no cache object on a ``tmp_path``."""
    import os

    import jax
    from jax._src import compilation_cache

    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR") or None
    assert jax.config.jax_compilation_cache_dir == outside
    assert outside or compilation_cache._cache is None


# ---------------------------------------------------------------------------
# 4. double-buffered host prep: token identity


_BUNDLES = {
    "gpt2": tiny_gpt_bundle(),
    "llama": tiny_llama_bundle(),
}


def _identity_cfg(paged: bool, **kw) -> ServiceConfig:
    if paged:
        kw.setdefault("paged_kv", True)
        kw.setdefault("kv_block_size", 8)
    kw.setdefault("seq_buckets", (16, 32))
    kw.setdefault("max_streams", 3)
    kw.setdefault("max_decode_len", 24)
    return _cfg(**kw)


@pytest.mark.parametrize("family", ["gpt2", "llama"])
@pytest.mark.parametrize("paged", [False, True],
                         ids=["contig", "paged"])
@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_double_buffer_token_identity(family, paged, sampled):
    """Staged host prep is token-identical to the serial prep order (the
    inline pass, forced through the loop by a no-op ``_stage_host_prep``)
    across the matrix — and in paged mode the staged plans are genuinely
    consumed, not always rolled back."""
    bundle = _BUNDLES[family]
    prompts = ["the quick brown fox", "pack my box", "jinx"]

    def run(double: bool):
        cfg = _identity_cfg(paged)
        eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
        cdl = ContinuousDecodeLoop(eng, cfg)
        if not double:
            cdl._stage_host_prep = lambda: None
        feats = []
        for i, t in enumerate(prompts):
            f = text_feats(bundle.tokenizer, t)
            if sampled:
                f["temperature"] = 1.0
                f["seed"] = 70 + i
            feats.append(f)
        try:
            outs = _run_concurrent(cdl, feats)
        finally:
            cdl.stop()
        return outs, cdl

    base, cdl_base = run(double=False)
    assert cdl_base.prep_staged == 0  # nothing staged = serial order exactly
    dbl, cdl_dbl = run(double=True)
    for got, want in zip(dbl, base):
        np.testing.assert_array_equal(got, want)
    if paged:
        assert cdl_dbl.prep_staged > 0, "double buffering never staged"
        assert cdl_dbl.prep_hits > 0, (
            "every staged plan was rolled back — overlap never happened"
        )


def test_double_buffer_pool_drains_after_streams():
    """Staged grants never leak: after a paged double-buffered run the
    pool ledger reads zero."""
    bundle = _BUNDLES["gpt2"]
    cfg = _identity_cfg(paged=True)
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    cdl = ContinuousDecodeLoop(eng, cfg)
    feats = [
        text_feats(bundle.tokenizer, t)
        for t in ("alpha beta", "gamma", "delta epsilon zeta")
    ]
    try:
        outs = _run_concurrent(cdl, feats)
        assert all(len(o) for o in outs)
        for _ in range(100):
            if eng.kv_pool.used_blocks == 0:
                break
            time.sleep(0.05)
        assert eng.kv_pool.used_blocks == 0, eng.kv_pool.stats()
    finally:
        cdl.stop()


# ---------------------------------------------------------------------------
# 5. mid-prep fatal → checkpoint-resume identity


def test_mid_prep_fatal_checkpoint_resume_identity():
    """A fatal fault on the STAGED prep upload (site ``prep``) rides
    the supervised recovery path: streams checkpoint at the delivered
    cursor and resume token-identically; the pool drains."""
    cfg = _identity_cfg(paged=True, fault_spec="prep:fatal@2")
    bundle = _BUNDLES["gpt2"]
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    ref = InferenceEngine(
        bundle, _identity_cfg(paged=True), ReplicaSet(make_mesh(1))
    )
    feats = text_feats(bundle.tokenizer, "decode through a prep fault")
    solo = _solo_tokens(ref, feats)
    cdl = ContinuousDecodeLoop(eng, cfg)
    cdl.supervisor = Supervisor(cfg)
    try:
        (out,) = _run_concurrent(cdl, [feats])
        n = min(len(out), len(solo))
        np.testing.assert_array_equal(out[:n], solo[:n])
        assert eng.faults.rules[0].fired >= 1, "prep fault never fired"
        assert cdl.supervisor.restarts == 1
        for _ in range(100):
            if eng.kv_pool.used_blocks == 0:
                break
            time.sleep(0.05)
        assert eng.kv_pool.used_blocks == 0, eng.kv_pool.stats()
    finally:
        cdl.stop()


# ---------------------------------------------------------------------------
# 6. chaos: rN:-scoped kill during staged prep → failover identity


@pytest.mark.chaos
def test_prep_kill_fails_over_token_identically():
    """R=2 paged fleet; replica 1's restart budget is zero and a
    replica-scoped fatal lands on its staged-prep upload — its streams
    must resume token-identically on replica 0, both ledgers drain."""
    cfg = _cfg(
        fleet_replicas=2, fault_spec="r1:prep:fatal@1",
        engine_restarts_max=0, engine_restart_window_s=60.0,
        paged_kv=True, kv_block_size=8, max_decode_len=32,
        seq_buckets=(16, 32), max_streams=4,
    )
    bundle = tiny_llama_bundle()
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    fleet = ReplicaFleet(eng, cfg)
    ref = InferenceEngine(
        bundle,
        _cfg(max_decode_len=32, seq_buckets=(16, 32), paged_kv=True,
             kv_block_size=8),
        ReplicaSet(make_mesh(1)),
    )
    prompts = ["the quick brown fox", "pack my box", "jinxed wizards",
               "five dozen jugs"]
    feats = [text_feats(bundle.tokenizer, t) for t in prompts]
    solos = [_solo_tokens(ref, f) for f in feats]
    try:
        async def body():
            gens = [fleet.submit_stream(dict(f)) for f in feats]
            return await asyncio.gather(
                *[_collect(g) for g in gens], return_exceptions=True
            )

        outs = asyncio.run(body())
        lost = [o for o in outs if isinstance(o, BaseException)]
        assert not lost, f"streams lost across prep-kill failover: {lost}"
        for got, want in zip(outs, solos):
            n = min(len(got), len(want))
            np.testing.assert_array_equal(got[:n], want[:n])
        r1 = next(r for r in fleet.replicas if r.id == 1)
        assert r1.engine.faults.rules[0].fired >= 1, (
            "the r1 prep schedule never landed"
        )
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


# ---------------------------------------------------------------------------
# 7. observability pins


def test_warm_and_cache_series_have_samples(monkeypatch):
    """engine_warm_seconds{phase="loop"} + executable_cache_events
    carry real samples after a loop warm (the metric-surface smoke
    runs with WARMUP=0 and only checks the HELP headers)."""
    from mlmicroservicetemplate_tpu.utils import metrics

    if not metrics.HAVE_PROM:
        pytest.skip("prometheus_client not installed")
    monkeypatch.setenv("WARMUP_SAMPLING", "0")
    bundle = _BUNDLES["gpt2"]
    cfg = _cfg(max_decode_len=8)
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    cdl = ContinuousDecodeLoop(eng, cfg)
    from mlmicroservicetemplate_tpu.utils import tracing

    tracing.boot_table().begin(None)  # a boot of this test's own
    cdl.warm()
    body, _ = metrics.render()
    text = body.decode()
    assert 'engine_warm_seconds_count{model="gpt2",phase="loop"}' in text
    for event in ("hit", "miss", "insert"):
        assert f'executable_cache_events_total{{event="{event}"}}' \
            in text, f"no {event} sample"
    # The /status.compile payload reads from the same counters.
    assert cc.cache_stats()["entries"] > 0
    status = cc.boot_status()
    assert "loop" in status["warm_phases_s"]  # one timing: the boot table's
    assert [r["name"] for r in status["boot"]["rows"] if r["parent"] is None][-2:] \
        == ["boot/warm/autotune", "boot/warm/loop"]
    comp = cc.compile_counters()
    assert comp["count"] == comp["compiled"] + comp["loaded"] >= 0
    cdl.stop()
