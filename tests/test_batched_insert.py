"""A wave's rows land in their slots through ONE ``paged_insert``.

(1) the batched insert leaves the batched state as the row-at-a-time
    insert it replaced did (kept here as the plain reference), leaf for
    leaf, bitwise: rungs 1 / 4 / 64 with pad rows and rows that do not
    land, ``s_lo > 0``, an (int8, scale) pair a pool, a latent pool with
    no ``cache_v``, recurrent state rows;
(2) ``OutOfBlocks`` on the middle row of a wave re-queues that row alone
    and leaks no block, slot or state row; the others go live;
(3) a wave of k rows is one ``insert`` dispatch and one
    ``stream_insert_rows`` observation of k;
(4) a 64-caller burst streams the tokens it streams with every row
    admitted alone (the rung of 1).
"""

from __future__ import annotations

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from helpers import one_wave, tiny_llama_bundle
from test_nemotron_block import config, kw  # noqa: F401
from mlmicroservicetemplate_tpu.engine import InferenceEngine
from mlmicroservicetemplate_tpu.engine.programs import paged_insert
from mlmicroservicetemplate_tpu.engine.streams import ContinuousDecodeLoop
from mlmicroservicetemplate_tpu.models.gpt import PagedState
from mlmicroservicetemplate_tpu.models.llama import SsmState
from mlmicroservicetemplate_tpu.models.sampling import SampleParams
from mlmicroservicetemplate_tpu.ops.paged_attention import scatter_pages
from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
from mlmicroservicetemplate_tpu.utils import metrics
from mlmicroservicetemplate_tpu.utils.config import ServiceConfig

# ---------------------------------------------------------------------------
# (1) the function, against the insert it replaced

BS, T, KVH, D, LAYERS, S, STEPS, TMAX = 4, 6, 2, 4, 2, 12, 4, 8
B = 64  # slots


def _row_at_a_time(batched, single, table_row, slot, row, s_lo, s_cut, ssm_row=None):
    """The insert before this file's subject: ONE row of ``single`` into
    ONE slot (``scatter_pages`` a pool, a ``dynamic_update_slice`` a
    per-row leaf)."""

    def ins(dst, src):
        src = lax.dynamic_slice_in_dim(src, row, 1, axis=0)
        pad = [(0, 0)] + [(0, d - s) for d, s in zip(dst.shape[1:], src.shape[1:])]
        return lax.dynamic_update_slice(
            dst, jnp.pad(src.astype(dst.dtype), pad), (slot,) + (0,) * (dst.ndim - 1))

    def scat(pool, src):
        if isinstance(pool, tuple):
            return tuple(scat(p, s) for p, s in zip(pool, src))
        return scatter_pages(pool, table_row, src[row, s_lo:s_cut], BS, start=s_lo)

    ssm = batched.ssm
    if ssm_row is not None:
        def put(dst, src):
            return dst.at[ssm_row].set(src[row].astype(dst.dtype), mode="drop")

        ssm = ssm._replace(
            conv=[put(d, x) for d, x in zip(ssm.conv, single.ssm.conv)],
            state=[put(d, x) for d, x in zip(ssm.state, single.ssm.state)],
            row=ssm.row.at[slot].set(ssm_row))
    return PagedState(
        cache_k=[scat(d, s) for d, s in zip(batched.cache_k, single.cache_k)],
        cache_v=[scat(d, s) for d, s in zip(batched.cache_v, single.cache_v)],
        **{f: ins(getattr(batched, f), getattr(single, f)) for f in (
            "key_valid", "write_idx", "pos", "last_token", "done", "tokens")},
        sample=jax.tree.map(ins, batched.sample, single.sample), ssm=ssm)


def _states(rng, rows: int, kind: str):
    """A live batched state (every leaf random: what a write must not
    disturb shows) and a ``rows``-row wave's prefill state."""
    nb = 3 * B + 5

    def f32(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def i32(*shape, hi=50):
        return jnp.asarray(rng.integers(0, hi, shape), jnp.int32)

    def pool_and_slab():
        if kind == "pair":  # QUANT_KV: (int8 payload, scale rows)
            return ((i32(nb, BS, KVH * D).astype(jnp.int8), f32(nb, BS, KVH)),
                    (i32(rows, S + TMAX, KVH, D).astype(jnp.int8),
                     f32(rows, S + TMAX, KVH, 1)))
        if kind == "latent":  # one merged row a token
            return f32(nb, BS, KVH * D), f32(rows, S + TMAX, KVH * D)
        return f32(nb, BS, KVH * D), f32(rows, S + TMAX, KVH, D)

    def sample(n):
        return SampleParams(
            rng=i32(n, 2).astype(jnp.uint32), temperature=f32(n),
            top_k=i32(n), top_p=f32(n))

    def state(n, width, layers_k, layers_v, ssm):
        return PagedState(
            cache_k=layers_k, cache_v=layers_v, key_valid=i32(n, width, hi=2),
            write_idx=i32(n), pos=i32(n), last_token=i32(n),
            done=i32(n, hi=2).astype(bool), tokens=i32(n, TMAX), sample=sample(n),
            ssm=ssm)

    k, v = zip(*[pool_and_slab() for _ in range(LAYERS)]), \
        zip(*[pool_and_slab() for _ in range(LAYERS)])
    (pk, sk), (pv, sv) = (list(map(list, k)), list(map(list, v)))
    if kind == "latent":
        pv, sv = [], []
    ssm_b = ssm_s = ()
    if kind == "ssm":
        def ssm(n, row):
            return SsmState(conv=[f32(n, 3, 5)] * 2, state=[f32(n, 2, 3, 4)] * 2, row=row)

        ssm_b = ssm(B, i32(B, hi=B))
        ssm_s = ssm(rows, jnp.arange(rows, dtype=jnp.int32))
    return (state(B, T * BS, pk, pv, ssm_b), state(rows, S + TMAX, sk, sv, ssm_s), nb)


#: rung -> the wave rows that land (the rest: a pad row of the rung, a row
#: done in its first chunk, a re-queued row: all-sentinel, slot past the last)
LANDING = {1: [0], 4: [0, 2], 64: [r for r in range(41) if r not in (5, 17)]}


@pytest.mark.parametrize("kind", ["plain", "prefix", "pair", "latent", "ssm"])
@pytest.mark.parametrize("rung", [1, 4, 64])
def test_the_waves_insert_is_the_row_at_a_time_insert(rung, kind):
    rng = np.random.default_rng(rung * 7 + len(kind))
    batched, single, nb = _states(rng, rung, kind)
    s_lo, s_cut = (BS if kind == "prefix" else 0), S + STEPS
    n_blocks = -(-s_cut // BS)
    landing = LANDING[rung]
    slot_of = dict(zip(landing, rng.permutation(B)[: len(landing)].tolist()))
    ssm_of = dict(zip(landing, rng.permutation(B)[: len(landing)].tolist()))
    blocks = rng.permutation(nb)
    table_rows = np.full((rung, T), nb, np.int32)
    slots = np.full(rung, B, np.int32)
    ssm_rows = np.full(rung, B, np.int32)
    for i, row in enumerate(landing):
        table_rows[row, :n_blocks] = blocks[i * n_blocks: (i + 1) * n_blocks]
        slots[row], ssm_rows[row] = slot_of[row], ssm_of[row]
    ssm_arg = (ssm_rows,) if kind == "ssm" else ()

    got = jax.jit(paged_insert(BS), static_argnums=(4, 5))(
        batched, single, table_rows, slots, s_lo, s_cut, *ssm_arg)
    one = jax.jit(_row_at_a_time, static_argnums=(5, 6))
    want = batched
    for row in landing:
        want = one(want, single, jnp.asarray(table_rows[row]), np.int32(slot_of[row]),
                   np.int32(row), s_lo, s_cut,
                   *((np.int32(ssm_of[row]),) if kind == "ssm" else ()))
    moved = 0
    for g, w, before in zip(*map(jax.tree.leaves, (got, want, batched))):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(np.asarray(g), np.asarray(w))
        moved += int(not np.array_equal(np.asarray(g), np.asarray(before)))
    assert moved >= 8  # the reference did write: the equality is not vacuous


def test_a_wave_none_of_whose_rows_lands_writes_nothing():
    """Every row all-sentinel with the slot past the last (what a pad row
    carries): the state comes back as it went in."""
    rng = np.random.default_rng(3)
    batched, single, nb = _states(rng, 4, "ssm")
    got = jax.jit(paged_insert(BS), static_argnums=(4, 5))(
        batched, single, np.full((4, T), nb, np.int32), np.full(4, B, np.int32),
        0, S + STEPS, np.full(4, B, np.int32))
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(batched)):
        assert np.array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# the loop


def _cfg(**kw) -> ServiceConfig:
    kw.setdefault("device", "cpu")
    kw.setdefault("warmup", False)
    kw.setdefault("batch_buckets", (1, 2, 4))
    kw.setdefault("seq_buckets", (16,))
    kw.setdefault("max_decode_len", 12)
    kw.setdefault("stream_chunk_tokens", 4)
    kw.setdefault("max_streams", 8)
    kw.setdefault("paged_kv", True)
    kw.setdefault("kv_block_size", 8)
    return ServiceConfig(**kw)


def _prompts(n: int, seed: int = 5):
    rng = np.random.default_rng(seed)
    return [{"input_ids": rng.integers(5, 250, int(k)).astype(np.int32),
             "length": np.int32(k)} for k in rng.integers(4, 15, n)]


async def _consume(gen):
    out = []
    async for c in gen:
        out.extend(np.asarray(c).tolist())
    return out


def _burst(cdl, feats):
    """Every stream queued before any is consumed, announced as the API
    announces a burst: one wave holds them all."""
    async def body():
        with one_wave(cdl):
            gens = [cdl.submit_stream(dict(f)) for f in feats]
        return await asyncio.gather(*[_consume(g) for g in gens])

    return asyncio.run(body())


def _loop(bundle, cfg):
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    cdl = ContinuousDecodeLoop(eng, cfg)
    return eng, cdl


def _spy_waves(cdl):
    """Each ``_insert_wave``: (the wave's streams, who was live after)."""
    seen = []
    orig = cdl._insert_wave

    def spy(wave, fetched):
        orig(wave, fetched)
        seen.append(([e[0] for e in wave], set(cdl.active.values())))

    cdl._insert_wave = spy
    return seen


def _insert_rows(name: str) -> tuple[float, float]:
    from prometheus_client import REGISTRY

    return tuple(
        REGISTRY.get_sample_value(f"stream_insert_rows_{k}", {"model": name}) or 0.0
        for k in ("sum", "count"))


def _nemotron_bundle(monkeypatch, kw):  # noqa: F811
    from test_nemotron_block import _svc

    from mlmicroservicetemplate_tpu.models.registry import build_model

    pattern = "ME*M"  # tests/test_nemotron_serving.py's toy
    return build_model(_svc(monkeypatch, {
        **kw, "layer_pattern": pattern, "num_layers": len(pattern)}))


@pytest.mark.parametrize("family", ["llama", "nemotron"])
def test_a_dry_pool_on_the_middle_row_requeues_that_row_alone(family, monkeypatch, kw):  # noqa: F811
    """``grow:oob@2``: the second row of a three-row wave finds the pool
    dry.  The wave is still ONE insert, of the two rows that land; they go
    live; the middle row is re-queued with its first chunk delivered and
    comes back in a wave of its own; every stream's tokens are an
    undisturbed run's; no block, slot or state row is lost."""
    if family == "nemotron":
        bundle = _nemotron_bundle(monkeypatch, kw)
        over = dict(kv_block_size=4, pallas_interpret=True, kv_budget_mb=0.12,
                    max_streams=4)
    else:
        bundle, over = tiny_llama_bundle(), {}
    feats = _prompts(3)
    _, calm = _loop(bundle, _cfg(**over))
    try:
        want = _burst(calm, feats)
    finally:
        calm.stop()
    cfg = _cfg(fault_spec="grow:oob@2", **over)
    eng, cdl = _loop(bundle, cfg)
    waves = _spy_waves(cdl)
    requeued = []
    orig = cdl._requeue_preempted
    cdl._requeue_preempted = lambda st: (requeued.append(st), orig(st))[1]
    stalls0 = metrics.KV_GROWTH_STALLS.labels(bundle.name)._value.get()
    s0, c0 = _insert_rows(bundle.name)
    try:
        got = _burst(cdl, feats)
        for _ in range(200):
            if eng.kv_pool.used_blocks == 0 and len(cdl.free) == cdl.n_slots:
                break
            asyncio.run(asyncio.sleep(0.02))
        assert eng.kv_pool.used_blocks == 0, eng.kv_pool.stats()
        assert sorted(cdl.free) == list(range(cdl.n_slots)) and not cdl.active
        if family == "nemotron":
            assert sorted(cdl._ssm_free) == list(range(cdl.n_slots))
        else:
            assert cdl._ssm_free is None
    finally:
        cdl.stop()
    assert got == want and all(len(t) == 12 for t in got)
    (first, live), (second, _) = waves
    assert len(first) == 3 and requeued == [first[1]] and second == [first[1]]
    assert live == {first[0], first[2]}
    assert first[1].preempted == 1
    assert metrics.KV_GROWTH_STALLS.labels(bundle.name)._value.get() == stalls0 + 1
    s1, c1 = _insert_rows(bundle.name)
    assert (s1 - s0, c1 - c0) == (3.0, 2.0)  # two rows, then the one
    assert eng.dispatch_stats["insert"][0] == 2


@pytest.mark.parametrize("k", [1, 3, 5, 8])
def test_a_wave_is_one_insert_dispatch_and_one_observation(k):
    """A wave of ``k`` rows (at 8 slots: alone, the rung of 4 with a pad
    row, the slot count with three, the slot count full): exactly one
    guarded ``insert`` dispatch, and ``stream_insert_rows`` observes
    ``k`` once."""
    bundle = tiny_llama_bundle()
    eng, cdl = _loop(bundle, _cfg())
    waves = _spy_waves(cdl)
    s0, c0 = _insert_rows(bundle.name)
    try:
        outs = _burst(cdl, _prompts(k, seed=k))
    finally:
        cdl.stop()
    assert [len(w) for w, _ in waves] == [k] and len(waves[0][1]) == k
    assert all(len(t) == 12 for t in outs)
    assert eng.dispatch_stats["insert"][0] == 1
    s1, c1 = _insert_rows(bundle.name)
    assert (s1 - s0, c1 - c0) == (float(k), 1.0)


def test_a_row_done_in_its_first_chunk_is_not_inserted():
    """A stream whose budget its first chunk spends ends there: the wave's
    insert lands the other rows only, and a wave of such rows alone
    dispatches none."""
    bundle = tiny_llama_bundle()
    eng, cdl = _loop(bundle, _cfg())
    waves = _spy_waves(cdl)
    feats = _prompts(3, seed=9)
    short = [dict(f, max_tokens=4) for f in feats]
    s0, c0 = _insert_rows(bundle.name)
    try:
        outs = _burst(cdl, [short[0], feats[1], short[2]])
        s1, c1 = _insert_rows(bundle.name)
        assert (s1 - s0, c1 - c0) == (1.0, 1.0)
        alone = _burst(cdl, short[:2])
        assert _insert_rows(bundle.name) == (s1, c1)
    finally:
        cdl.stop()
    assert [len(t) for t in outs] == [4, 12, 4] and [len(t) for t in alone] == [4, 4]
    assert [len(w) for w, _ in waves] == [3, 2] and len(waves[0][1]) == 1
    assert eng.dispatch_stats["insert"][0] == 1


def test_a_64_caller_burst_streams_what_lone_admissions_stream():
    """64 callers at once (a lone start and waves up to the slot count)
    stream, each, the tokens they stream when every row is admitted
    alone — the rung of 1, a dispatch a row."""
    bundle = tiny_llama_bundle()
    feats = _prompts(64, seed=41)
    cfg = _cfg(max_streams=64, max_stream_queue=64)
    runs = []
    for lone in (False, True):
        eng, cdl = _loop(bundle, cfg)
        waves = _spy_waves(cdl)
        if lone:
            admit = cdl._admit_dispatch
            cdl._admit_dispatch = lambda wave: [
                e for st in wave for e in admit([st])]
        try:
            runs.append(_burst(cdl, feats))
        finally:
            cdl.stop()
        sizes = [len(w) for w, _ in waves]
        assert sum(sizes) == 64
        assert (max(sizes) == 1) if lone else (max(sizes) > 4)
        assert eng.dispatch_stats["insert"][0] == len(sizes)
    assert runs[0] == runs[1] and all(len(t) == 12 for t in runs[0])
