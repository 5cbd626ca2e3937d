"""Block-paged KV cache tests (PAGED_KV=1).

The judged contracts:
1. Paged decode is TOKEN-IDENTICAL to the contiguous layout on llama
   and gpt (greedy), including the int8-KV composition — the physical
   layout is the only thing that changes.
2. The Pallas paged-attention kernel (interpret mode on CPU, same
   pattern as ring attention) matches the jnp gather reference.
3. The continuous loop under PAGED_KV=1: concurrent streams match
   solo contiguous output; blocks free the moment streams end; prefix
   hits SHARE the donor's blocks by refcount (CoW — no copy, charged
   once); a dry pool checkpoints the stream and resumes it
   token-identically; admission sheds can-never-fit work as
   ``kv_budget``.
4. PAGED_KV=0 leaves the seed layout untouched.
"""

import asyncio
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mlmicroservicetemplate_tpu.engine import InferenceEngine
from mlmicroservicetemplate_tpu.engine.streams import ContinuousDecodeLoop
from mlmicroservicetemplate_tpu.models import gpt as gpt_mod
from mlmicroservicetemplate_tpu.models import llama as llama_mod
from mlmicroservicetemplate_tpu.ops.paged_attention import (
    gather_pages,
    paged_attention_ref,
    paged_decode_attention,
)
from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
from mlmicroservicetemplate_tpu.scheduler.admission import AdmissionController
from mlmicroservicetemplate_tpu.scheduler.policy import QueueFullError
from mlmicroservicetemplate_tpu.utils.config import ServiceConfig

from helpers import TINY_GPT, TINY_LLAMA, tiny_gpt_bundle, tiny_llama_bundle


def _shuffled_table(b: int, tokens: int, bs: int, seed: int = 1):
    """Non-trivial block mapping: a paged bug that only shows with
    out-of-order blocks must not hide behind an identity table."""
    nb_row = -(-tokens // bs)
    total = nb_row * b
    perm = np.random.RandomState(seed).permutation(total)
    return perm.reshape(b, nb_row).astype(np.int32), total


def _prompts(rng, lens, vocab=250):
    s = max(lens)
    ids = np.zeros((len(lens), s), np.int32)
    mask = np.zeros((len(lens), s), np.int32)
    for i, n in enumerate(lens):
        ids[i, :n] = rng.integers(5, vocab, n)
        mask[i, :n] = 1
    return ids, mask


# ---------------------------------------------------------------------------
# kernel numerics


@pytest.mark.parametrize("quant", [False, True])
def test_paged_kernel_matches_reference(quant):
    B, NB, BS, KVH, NREP, D = 2, 3, 8, 2, 3, 16
    POOL = 8
    rng = np.random.default_rng(0)
    q = rng.normal(size=(B, KVH * NREP, D)).astype(np.float32)
    # The pool's layout: [NB, BS, KVH*D], scales [NB, BS, KVH].
    kp = rng.normal(size=(POOL, BS, KVH * D)).astype(np.float32)
    vp = rng.normal(size=(POOL, BS, KVH * D)).astype(np.float32)
    table = np.array([[0, 2, 5], [7, 1, 3]], np.int32)
    valid = (rng.random((B, NB * BS)) > 0.3).astype(np.int32)
    valid[:, 0] = 1  # never a fully-masked row
    ks = vs = None
    if quant:
        kp8 = np.clip(np.round(kp * 16), -127, 127).astype(np.int8)
        vp8 = np.clip(np.round(vp * 16), -127, 127).astype(np.int8)
        ks = (np.abs(rng.normal(size=(POOL, BS, KVH))) + 0.01).astype(np.float32)
        vs = (np.abs(rng.normal(size=(POOL, BS, KVH))) + 0.01).astype(np.float32)
        kp, vp = kp8, vp8
    want = paged_attention_ref(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(table), jnp.asarray(valid), BS,
        k_scale=None if ks is None else jnp.asarray(ks),
        v_scale=None if vs is None else jnp.asarray(vs),
    )
    got = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(table), jnp.asarray(valid), BS,
        k_scale=None if ks is None else jnp.asarray(ks),
        v_scale=None if vs is None else jnp.asarray(vs),
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_gather_pages_clamps_sentinel():
    pool = jnp.arange(4 * 2 * 1, dtype=jnp.float32).reshape(4, 2, 1)
    table = jnp.asarray([[1, 4]], jnp.int32)  # 4 == sentinel (out of range)
    out = gather_pages(pool, table, 2)
    assert out.shape == (1, 4, 1)
    assert gather_pages(pool, table, 2, (1, 1)).shape == (1, 4, 1, 1)
    np.testing.assert_array_equal(
        np.asarray(out[0, :2, 0]), [2.0, 3.0]
    )  # block 1


def test_kernel_reads_the_pool_as_it_lies():
    """The CPU twin of test_chip_compile's scan case: in the traced
    wrapper the ``pallas_call``'s payload-pool operands are the
    function's own inputs, once each — nothing (reshape, transpose,
    copy, convert) stands between the pool a decode state carries and
    the kernel, which copies a row's live blocks out of it itself.  (An
    int8 pair's scales ride as the rows' gathered [B, T*BS, KVH].)"""
    b, h, kvh, d, bs, t, nb = 2, 4, 2, 16, 8, 4, 9
    args = (
        jnp.zeros((b, h, d)), jnp.zeros((nb, bs, kvh * d), jnp.int8),
        jnp.zeros((nb, bs, kvh * d), jnp.int8), jnp.zeros((b, t), jnp.int32),
        jnp.ones((b, t * bs), jnp.int32),
        jnp.ones((nb, bs, kvh)), jnp.ones((nb, bs, kvh)),
    )
    for variant in ("", "b2-hb"):
        jaxpr = jax.make_jaxpr(  # the wrapper's own body, under its jit
            lambda *a: paged_decode_attention.__wrapped__(
                *a[:5], bs, *a[5:], interpret=True, variant=variant)
        )(*args).jaxpr
        (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        payloads = [jaxpr.invars[i] for i in (1, 2)]
        assert [v for v in call.invars if v in payloads] == payloads, variant
        if "hb" in variant:  # q too: the kernel lays out its own (PR 59)
            assert jaxpr.invars[0] in call.invars
            assert call.outvars[0].aval.shape == (b, h, d)
        assert [v.aval.shape for v in call.invars].count((b, t * bs, kvh)) == 2


# ---------------------------------------------------------------------------
# the pool's layout [NB, BS, C]: writers and readers against the
# contiguous cache, and the TP spec on a pool leaf


@pytest.mark.parametrize("quant", [False, True], ids=["dense", "int8"])
@pytest.mark.parametrize("kvh,d", [(8, 128), (16, 128), (4, 64)])
def test_pool_layout_round_trips_against_the_contiguous_cache(kvh, d, quant):
    from mlmicroservicetemplate_tpu.models.common import kv_quantize
    from mlmicroservicetemplate_tpu.ops.paged_attention import scatter_pages
    from mlmicroservicetemplate_tpu.parallel.tpserve import kv_head_spec

    b, bs, s_pre, steps = 3, 4, 6, 3
    total = s_pre + steps
    table, nb = _shuffled_table(b, total, bs, seed=kvh)
    table = jnp.asarray(table)
    rng = np.random.default_rng(d)
    kv = jnp.asarray(rng.normal(size=(b, total, kvh, d)).astype(np.float32))
    # The contiguous cache's leaves, [B, W, KVH, D] (+ [B, W, KVH, 1]).
    leaves = list(kv_quantize(kv)) if quant else [kv]
    pools = [
        jnp.zeros((nb, bs, int(np.prod(x.shape[2:]))), x.dtype) for x in leaves
    ]
    assert [p.shape[2] for p in pools] == ([kvh * d, kvh] if quant else [kvh * d])
    for i, (pool, x) in enumerate(zip(pools, leaves)):
        for row in range(b):  # the insert: a [W, KVH, D] slice of one row
            pool = scatter_pages(pool, table[row], x[row, 2:s_pre], bs, start=2)
            pool = scatter_pages(pool, table[row], x[row, :2], bs)
        for t in range(s_pre, total):  # the decode step's [B, KVH, D] rows
            pool = gpt_mod.paged_write_token(
                pool, table, jnp.full((b,), t, jnp.int32), x[:, t], bs)
        assert pool.shape == pools[i].shape
        merged = gather_pages(pool, table, bs)
        assert merged.shape == (b, table.shape[1] * bs, pool.shape[2])
        back = gather_pages(pool, table, bs, x.shape[2:])[:, :total]
        np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
        # TP: shard i of a pool leaf's merged axis is heads i*KVH/tp ..
        # whole — what the shard_map'd kernel's local view must hold.
        spec = kv_head_spec(paged=True)
        assert tuple(spec) == (None, None, "tp")
        for tp in (2, 4):
            c = pool.shape[2] // tp
            for i_sh in range(tp):
                lo = i_sh * kvh // tp
                shard = gather_pages(
                    pool[:, :, i_sh * c:(i_sh + 1) * c], table, bs,
                    (kvh // tp,) + tuple(x.shape[3:]),
                )[:, :total]
                np.testing.assert_array_equal(
                    np.asarray(shard), np.asarray(x[:, :, lo:lo + kvh // tp]))


# ---------------------------------------------------------------------------
# model-level token identity (shuffled tables)


def test_gpt_paged_identity():
    cfg = gpt_mod.GPTConfig(**{**TINY_GPT, "eos_id": 1, "pad_id": 0})
    params = gpt_mod.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    ids, mask = _prompts(rng, [3, 9, 6])
    max_len = 8
    want = np.asarray(gpt_mod.greedy_generate(params, cfg, ids, mask, max_len))
    bs = 4
    table, nb = _shuffled_table(3, ids.shape[1] + max_len, bs)
    st = gpt_mod.init_paged_state(
        params, cfg, jnp.asarray(ids), jnp.asarray(mask), max_len,
        jnp.asarray(table), nb, bs,
    )
    st, _ = gpt_mod.generate_chunk_paged(
        params, cfg, st, jnp.asarray(table), max_len
    )
    np.testing.assert_array_equal(np.asarray(st.tokens), want)


@pytest.mark.parametrize("kv_quant", [False, True])
def test_llama_paged_identity(kv_quant):
    cfg = llama_mod.LlamaConfig(
        **{**TINY_LLAMA, "eos_id": 1, "pad_id": 0}, kv_quant=kv_quant
    )
    params = llama_mod.init_params(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(1)
    ids, mask = _prompts(rng, [4, 11, 7])
    max_len = 8
    want = np.asarray(llama_mod.greedy_generate(params, cfg, ids, mask, max_len))
    bs = 4
    table, nb = _shuffled_table(3, ids.shape[1] + max_len, bs, seed=2)
    st = llama_mod.init_paged_state(
        params, cfg, jnp.asarray(ids), jnp.asarray(mask), max_len,
        jnp.asarray(table), nb, bs,
    )
    st, _ = llama_mod.generate_chunk_paged(
        params, cfg, st, jnp.asarray(table), max_len
    )
    np.testing.assert_array_equal(np.asarray(st.tokens), want)


# ---------------------------------------------------------------------------
# continuous loop under PAGED_KV=1


def _cfg(**kw) -> ServiceConfig:
    kw.setdefault("device", "cpu")
    kw.setdefault("warmup", False)
    kw.setdefault("batch_buckets", (1, 2, 4))
    kw.setdefault("seq_buckets", (16, 32))
    kw.setdefault("max_decode_len", 12)
    kw.setdefault("stream_chunk_tokens", 4)
    kw.setdefault("max_streams", 4)
    return ServiceConfig(**kw)


async def _consume(gen):
    out = []
    async for c in gen:
        out.extend(np.asarray(c).tolist())
    return out


def _run(cdl, feats_list):
    async def body():
        return await asyncio.gather(
            *[_consume(cdl.submit_stream(dict(f))) for f in feats_list]
        )

    return asyncio.run(body())


def _solo_tokens(engine, feats):
    return np.concatenate(list(engine.generate_stream(dict(feats)))).tolist()


def _wait_pool_drained(pool, allow: int = 0, timeout=5.0):
    deadline = time.monotonic() + timeout
    while pool.used_blocks > allow and time.monotonic() < deadline:
        time.sleep(0.02)
    return pool.used_blocks


def test_paged_loop_identity_and_immediate_free():
    bundle = tiny_gpt_bundle()
    cfgp = _cfg(paged_kv=True, kv_block_size=8)
    engp = InferenceEngine(bundle, cfgp, ReplicaSet(make_mesh(1)))
    eng0 = InferenceEngine(bundle, _cfg(), ReplicaSet(make_mesh(1)))
    rng = np.random.default_rng(0)
    feats = [
        {"input_ids": p, "length": np.int32(len(p))}
        for p in (rng.integers(5, 250, n).astype(np.int32) for n in (7, 19, 12, 30))
    ]
    solos = [_solo_tokens(eng0, f) for f in feats]
    cdl = ContinuousDecodeLoop(engp, cfgp)
    try:
        outs = _run(cdl, feats)
        assert outs == solos
        # Exact ledger: every block returns the moment streams end (no
        # prefix cache here, so the pool drains to zero).
        assert _wait_pool_drained(engp.kv_pool) == 0
    finally:
        cdl.stop()


def test_paged_loop_llama_identity():
    bundle = tiny_llama_bundle()
    cfgp = _cfg(paged_kv=True, kv_block_size=8)
    engp = InferenceEngine(bundle, cfgp, ReplicaSet(make_mesh(1)))
    eng0 = InferenceEngine(bundle, _cfg(), ReplicaSet(make_mesh(1)))
    rng = np.random.default_rng(3)
    feats = [
        {"input_ids": p, "length": np.int32(len(p))}
        for p in (rng.integers(5, 250, n).astype(np.int32) for n in (6, 14))
    ]
    solos = [_solo_tokens(eng0, f) for f in feats]
    cdl = ContinuousDecodeLoop(engp, cfgp)
    try:
        assert _run(cdl, feats) == solos
    finally:
        cdl.stop()


def _table_block_counts(name="llama"):
    from mlmicroservicetemplate_tpu.utils import metrics

    return (metrics.KV_TABLE_BLOCKS_LIVE.labels(name)._value.get(),
            metrics.KV_TABLE_BLOCKS_DEAD.labels(name)._value.get())


def test_table_block_counters_cover_every_slots_table():
    """kv_table_blocks_live_total + kv_table_blocks_dead_total: every
    dispatched chunk adds n_slots x T x steps x layers between them, and
    two streams of 6 and 14 prompt tokens in 4 slots of 6-entry tables
    leave most of that dead."""
    bundle = tiny_llama_bundle()
    cfgp = _cfg(paged_kv=True, kv_block_size=8)
    engp = InferenceEngine(bundle, cfgp, ReplicaSet(make_mesh(1)))
    rng = np.random.default_rng(3)
    feats = [
        {"input_ids": p, "length": np.int32(len(p))}
        for p in (rng.integers(5, 250, n).astype(np.int32) for n in (6, 14))
    ]
    cdl = ContinuousDecodeLoop(engp, cfgp)
    live0, dead0 = _table_block_counts()
    try:
        _run(cdl, feats)
        chunks = cdl.chunk_dispatches
    finally:
        cdl.stop()
    live, dead = (a - b for a, b in zip(_table_block_counts(), (live0, dead0)))
    steps = chunks * cfgp.stream_chunk_tokens
    assert chunks >= 2 and cdl.nb_max == 6  # (32 + 12) / 8
    assert live + dead == cdl.n_slots * cdl.nb_max * steps * bundle.cfg.num_layers
    # A step of a stream reads 1-4 of its 6 entries; two slots hold no stream.
    assert 0 < live <= 2 * 4 * steps * bundle.cfg.num_layers
    assert dead > live


@pytest.mark.parametrize("pattern,nb_max,want", [
    (None, 8, (75, 309)),
    ((2, 8), 16, (63, 449)),  # two window-8 layers through an 8-entry view + one full
], ids=["full", "window"])
def test_table_block_counters_count_through_the_view(pattern, nb_max, want):
    """One chunk of 4 steps, blocks of 4, three layers, 4 slots, streams
    of 5 and 14 prompt tokens (the first prefilled by a wave in a
    16-wide bucket: its table grows off 16, its keys off 5): step k
    attends over prompt + k keys (step 0 rewrites the last prompt
    token's), (n-1)//4 + 1 live entries in a full layer — 2, 2, 2, 2 and
    4, 4, 4, 5 — and in a window-8 layer those from the block of key
    n - 8 on — 2, 2, 2, 2 and 3, 3, 2, 3 — of a view 8 entries wide."""
    import types

    def stream(s_base, length):
        return types.SimpleNamespace(s_base=s_base, s_lo=0, feats={"length": length})

    loop = types.SimpleNamespace(
        block_size=4, nb_max=nb_max, n_slots=4, _window_layers=pattern,
        _attn_layers=3, active={0: stream(16, 5), 2: stream(14, 14)},
        _dispatched_steps={0: 4, 2: 4},
        engine=types.SimpleNamespace(bundle=types.SimpleNamespace(name="blocks-unit")),
    )
    before = _table_block_counts("blocks-unit")
    ContinuousDecodeLoop._note_table_blocks(loop, 4)
    got = tuple(a - b for a, b in zip(_table_block_counts("blocks-unit"), before))
    assert got == want


def test_paged_prefix_hit_shares_blocks_cow():
    """A prefix-cache hit adopts the donor's prompt blocks by refcount:
    no KV copy, the pool charges the shared prefix ONCE, and the hit
    stream's output is token-identical to the cache-off engine."""
    bundle = tiny_gpt_bundle()
    cfgp = _cfg(paged_kv=True, kv_block_size=8, prefix_cache=True)
    engp = InferenceEngine(bundle, cfgp, ReplicaSet(make_mesh(1)))
    cdl = ContinuousDecodeLoop(engp, cfgp)
    try:
        rng = np.random.default_rng(0)
        shared = rng.integers(5, 250, 20).astype(np.int32)
        p1 = np.concatenate([shared, rng.integers(5, 250, 5).astype(np.int32)])
        p2 = np.concatenate([shared, rng.integers(5, 250, 9).astype(np.int32)])
        f1 = {"input_ids": p1, "length": np.int32(len(p1))}
        f2 = {"input_ids": p2, "length": np.int32(len(p2))}

        _run(cdl, [f1])  # donor: pins its 16-token prefix (2 blocks)
        assert _wait_pool_drained(engp.kv_pool, allow=2) == 2
        assert engp.prefix_cache.stats()["entries"] == 1

        out = _run(cdl, [f2])[0]
        assert engp.prefix_cache.hits == 1
        eng0 = InferenceEngine(bundle, _cfg(), ReplicaSet(make_mesh(1)))
        assert out == _solo_tokens(eng0, f2)
        # Sharer released its refs; only the cache pin holds the blocks.
        assert _wait_pool_drained(engp.kv_pool, allow=2) == 2
        # Eviction drops the pin and the pool drains fully.
        while engp.prefix_cache.pop_lru() is not None:
            pass
        assert engp.kv_pool.used_blocks == 0
    finally:
        cdl.stop()


def test_paged_growth_dry_checkpoints_and_resumes():
    """Two streams whose combined decode growth exceeds the pool: one
    checkpoints on the dry pool, re-queues, and finishes
    token-identically once blocks free — never a dropped stream."""
    bundle = tiny_gpt_bundle()
    # token bytes 512, block(8) = 4096B; 6-block pool: both streams
    # admit (3 initial blocks each) but cannot both grow to 4.
    cfgp = _cfg(
        paged_kv=True, kv_block_size=8, max_stream_queue=4,
        kv_budget_mb=6 * 4096 / 1e6,
    )
    engp = InferenceEngine(bundle, cfgp, ReplicaSet(make_mesh(1)))
    assert engp.kv_pool.num_blocks == 6
    cdl = ContinuousDecodeLoop(engp, cfgp)
    cdl.admission = AdmissionController(cfgp, engp)
    try:
        rng = np.random.default_rng(1)
        feats = [
            {"input_ids": p, "length": np.int32(len(p))}
            for p in (rng.integers(5, 250, 14).astype(np.int32) for _ in range(2))
        ]
        outs = _run(cdl, feats)
        eng0 = InferenceEngine(bundle, _cfg(), ReplicaSet(make_mesh(1)))
        assert outs == [_solo_tokens(eng0, f) for f in feats]
    finally:
        cdl.stop()


def test_paged_admission_sheds_can_never_fit():
    bundle = tiny_gpt_bundle()
    cfgp = _cfg(
        paged_kv=True, kv_block_size=8, kv_budget_mb=3 * 4096 / 1e6
    )
    engp = InferenceEngine(bundle, cfgp, ReplicaSet(make_mesh(1)))
    cdl = ContinuousDecodeLoop(engp, cfgp)
    cdl.admission = AdmissionController(cfgp, engp)
    try:
        feats = {
            "input_ids": np.arange(5, 19, dtype=np.int32),
            "length": np.int32(14),
        }

        async def shed():
            try:
                await _consume(cdl.submit_stream(dict(feats)))
                return None
            except QueueFullError as e:
                return e.reason

        assert asyncio.run(shed()) == "kv_budget"
    finally:
        cdl.stop()


def test_paged_off_leaves_seed_layout():
    bundle = tiny_gpt_bundle()
    eng = InferenceEngine(bundle, _cfg(), ReplicaSet(make_mesh(1)))
    assert eng.paged_kv is False and eng.kv_pool is None
    cdl = ContinuousDecodeLoop(eng, _cfg())
    assert cdl.paged is False


def test_paged_rejects_multi_replica_placement():
    bundle = tiny_gpt_bundle()
    with pytest.raises(ValueError, match="single-replica"):
        InferenceEngine(
            bundle, _cfg(paged_kv=True, kv_block_size=8),
            ReplicaSet(make_mesh(2)),
        )


def test_build_model_gates():
    """PAGED_KV invalid combinations reject loudly at build time."""
    import json
    import os

    from mlmicroservicetemplate_tpu.models.registry import build_model
    from mlmicroservicetemplate_tpu.utils.config import load_config

    os.environ["LLAMA_CONFIG"] = json.dumps(
        {k: v for k, v in TINY_LLAMA.items() if k not in ("eos_id", "pad_id")}
    )
    try:
        base = {
            "DEVICE": "cpu", "MODEL_NAME": "llama", "WARMUP": "0",
            "PAGED_KV": "1", "SEQ_BUCKETS": "32,64", "BATCH_BUCKETS": "1,2",
        }
        # Valid combo builds and exposes the paged fn.
        b = build_model(load_config(dict(base)))
        assert b.paged_chunk_fn is not None
        with pytest.raises(ValueError, match="PROMPT_PREFIX"):
            build_model(load_config(dict(base, PROMPT_PREFIX="sys")))
        with pytest.raises(ValueError, match="SPEC_CONTINUOUS"):
            build_model(load_config(dict(
                base, SPEC_DECODE="ngram", SPEC_CONTINUOUS="1"
            )))
        # Unaligned buckets are rounded up to the block grid at parse
        # time instead of rejected (kv_block_size defaults to 16).
        aligned = load_config(dict(base, SEQ_BUCKETS="24,48"))
        assert aligned.seq_buckets == (32, 48)
        assert build_model(aligned).paged_chunk_fn is not None
        with pytest.raises(ValueError, match="REPLICAS=1"):
            build_model(load_config(dict(base, REPLICAS="2")))
    finally:
        del os.environ["LLAMA_CONFIG"]
