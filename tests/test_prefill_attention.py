"""The prompt-window kernel (ops/prefill_attention.py) against
``common.mha_attention`` under ``llama._prefill_mask`` — the XLA form it
replaces in ``paged_prefill_chunk`` — in interpret mode on the CPU, at
small sizes with several query and key tiles; the live-tile bounds
against the mask itself and against the host's count; the counters."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlmicroservicetemplate_tpu.models import llama as llama_mod
from mlmicroservicetemplate_tpu.ops import prefill_attention as pa

C, BS = 32, 8  # a window's queries; the block size key ranges round to


def _qkv(h, kvh, k_len, dk=16, dv=16, dtype=jnp.float32, c=C, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (c, h, dk), dtype),
            jax.random.normal(ks[1], (k_len, kvh, dk), dtype),
            jax.random.normal(ks[2], (k_len, kvh, dv), dtype))


def _span(start, window, k_len):
    """(kpos0, K) as ``paged_prefill_chunk`` gathers them: a full layer
    the row's table from key 0, a window layer the blocks that hold the
    ``C + window - 1`` keys ending with the window's last query."""
    if not window:
        return 0, k_len
    n = min(k_len // BS, -(-(C + window - 1) // BS) + 1)
    first = min(max((start - window + 1) // BS, 0), k_len // BS - n)
    return first * BS, n * BS


def _both(q, k, v, kpos0, start, mask, window, **tiles):
    got = pa.prefill_attention(q, k, v, kpos0, start, mask, window=window,
                               interpret=True, **tiles)
    want = pa.prefill_attention_ref(q, k, v, kpos0, start, mask, window)
    return got, want


@pytest.mark.parametrize("start,n_valid", [(0, C), (40, C), (64, 20)],
                         ids=["first", "mid-row", "last-padded"])
@pytest.mark.parametrize("window", [0, 12, 40], ids=["full", "w<C", "w>C"])
@pytest.mark.parametrize("n_rep", [1, 4, 8])
def test_kernel_matches_mha_attention_under_the_prefill_mask(n_rep, window,
                                                             start, n_valid):
    """Every real query's output, float32: GQA groups of 1, 4 and 8 heads
    as rows of one q tile, no window / one inside the chunk / one wider
    than it, the prompt's first window, one whose keys start mid-row and
    a last one with a padded tail; 104 keys in tiles of 16 (the last
    tile starts at key 88), queries in tiles of 8."""
    kvh = 2
    kpos0, k_len = _span(start, window, 104)
    q, k, v = _qkv(kvh * n_rep, kvh, k_len, seed=start + window)
    mask = (jnp.arange(C) < n_valid).astype(jnp.int32)
    got, want = _both(q, k, v, kpos0, start, mask, window, q_tile=8, key_tile=16)
    assert got.shape == want.shape == (C, kvh * n_rep, 16)
    np.testing.assert_allclose(got[:n_valid], want[:n_valid], atol=2e-5, rtol=2e-5)
    assert bool(jnp.isfinite(got).all())  # pad queries too


@pytest.mark.parametrize("c,n_rep,cap,tq", [
    (1024, 20, 1024, 32),  # Jamba's: 1024 // 20 = 51 divides nothing
    (1024, 5, 1024, 128),  # 204 -> the largest whole-sublane divisor under it
    (32, 5, 60, 8),  # the toy below
    (24, 3, 30, 8),  # 10 -> 8, as the halving rule it replaces found
    (12, 5, 35, 6),  # no multiple of 8 divides 12: the largest divisor
    (1024, 8, 1024, 128), (2048, 1, 1024, 1024), (1024, 4, 1024, 256),  # as ever
])
def test_the_q_tile_divides_the_window_at_any_n_rep(monkeypatch, c, n_rep, cap, tq):
    """``tile_sizes`` gives a q tile that DIVIDES the window whatever the
    query-to-KV head ratio: the cap itself where it divides (every ratio the
    benchmark ran before Jamba's: unchanged), else the largest divisor under
    it that is whole 8-row sublane tiles, else the largest divisor."""
    monkeypatch.setattr(pa, "Q_TILE_ROWS", cap)
    got, _ = pa.tile_sizes(c, n_rep, 6272)
    assert got == tq and c % got == 0 and got * n_rep <= max(cap, 8 * n_rep)


@pytest.mark.parametrize("window", [0, 12], ids=["full", "w<C"])
def test_five_query_heads_on_one_kv_head_with_a_cap_that_does_not_divide(
        monkeypatch, window):
    """``n_rep`` 5 on ONE KV head with 60 rows a program: 12 queries a tile
    do not divide the 32-query window, 8 do — the kernel's answer is
    ``mha_attention``'s under the prefill mask, no key tile given."""
    monkeypatch.setattr(pa, "Q_TILE_ROWS", 60)
    assert pa.tile_sizes(C, 5, 104)[0] == 8
    kpos0, k_len = _span(40, window, 104)
    q, k, v = _qkv(5, 1, k_len, seed=7)
    mask = (jnp.arange(C) < 27).astype(jnp.int32)
    got, want = _both(q, k, v, kpos0, 40, mask, window, key_tile=16)
    np.testing.assert_allclose(got[:27], want[:27], atol=2e-5, rtol=2e-5)


def test_latent_heads_score_192_dims_and_weigh_128():
    """DeepSeek-V2's expanded heads: KVH = H, 192 dims for scores (padded
    to 256 lanes inside), 128 for values, with its softmax scale."""
    q, k, v = _qkv(2, 2, 48, dk=192, dv=128, c=16)
    mask = jnp.ones((16,), jnp.int32)
    got = pa.prefill_attention(q, k, v, 0, 24, mask, scale=0.1147, q_tile=8,
                               key_tile=16, interpret=True)
    want = pa.prefill_attention_ref(q, k, v, 0, 24, mask, scale=0.1147)
    assert got.shape == (16, 2, 128)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_bfloat16_keeps_scores_and_statistics_in_float32():
    """bf16 operands: within bf16's rounding of the float32 reference on
    the same (rounded) operands — the kernel's scores are float32, where
    ``mha_attention`` rounds them to bf16 first."""
    q, k, v = _qkv(8, 2, 96, dtype=jnp.bfloat16)
    mask = jnp.ones((C,), jnp.int32)
    got = pa.prefill_attention(q, k, v, 0, 48, mask, q_tile=16, key_tile=32,
                               interpret=True)
    want = pa.prefill_attention_ref(
        *(x.astype(jnp.float32) for x in (q, k, v)), 0, 48, mask)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=2e-2, rtol=2e-2)


def test_default_tiles_cover_a_window_that_is_smaller_than_a_tile():
    """No tile argument (the served call): one q tile, one key tile."""
    assert pa.tile_sizes(C, 4, 96) == (C, 96)
    assert pa.tile_sizes(1024, 8, 6272) == (128, pa.KEY_TILE)
    assert pa.tile_sizes(2048, 1, 6272) == (1024, pa.KEY_TILE)
    q, k, v = _qkv(8, 2, 96)
    mask = jnp.ones((C,), jnp.int32)
    got, want = _both(q, k, v, 0, 40, mask, 0)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window,start,dead", [
    (0, 0, slice(32, 96)),  # past the window's last key
    (0, 32, slice(64, 96)),
    (16, 64, slice(0, 32)),  # behind every query's band (kpos0 = 0)
], ids=["past-end", "past-end-2nd", "behind-band"])
def test_a_tile_no_query_sees_is_never_read(window, start, dead):
    """NaN keys and values in the key tiles outside every q tile's live
    range: a tile that ran — even fully masked — would carry them into
    the output (0 x NaN).  The XLA form, which multiplies every key by a
    zero probability, does."""
    q, k, v = _qkv(8, 2, 96)
    k, v = (x.at[dead].set(jnp.nan) for x in (k, v))
    mask = jnp.ones((C,), jnp.int32)
    got, want = _both(q, k, v, 0, start, mask, window, q_tile=8, key_tile=16)
    assert bool(jnp.isfinite(got).all())
    assert not bool(jnp.isfinite(want).all())
    clean = pa.prefill_attention_ref(
        q, jnp.nan_to_num(k), jnp.nan_to_num(v), 0, start, mask, window)
    np.testing.assert_allclose(got, clean, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("start,n_valid,window,k_len,tq,tk", [
    (0, 32, 0, 96, 8, 16), (40, 32, 0, 104, 8, 16), (64, 20, 0, 104, 16, 16),
    (0, 32, 12, 96, 8, 16), (40, 32, 12, 104, 8, 16), (64, 20, 40, 104, 8, 32),
    (64, 1, 12, 104, 8, 16), (48, 32, 40, 88, 32, 16),
])
def test_live_tiles_are_the_masks_and_the_hosts_count(start, n_valid, window,
                                                      k_len, tq, tk):
    """``live_tiles`` against ``_prefill_mask`` itself: a q tile's range
    is the hull of the key tiles that hold a (query, key) pair the mask
    shows; ``whole`` iff it shows every pair of the two tiles; a key the
    last, shifted tile holds again is dead there; and the host's
    ``count_live_tiles`` adds up to the same number of live pairs."""
    kpos0, k_len = _span(start, window, k_len)
    mask = (jnp.arange(C) < n_valid).astype(jnp.int32)
    kp, live, whole = (np.asarray(x) for x in pa.live_tiles(
        jnp.int32(kpos0), jnp.int32(start), mask, window, k_len, tq, tk))
    full = np.asarray(llama_mod._prefill_mask(
        kpos0 + jnp.arange(k_len), mask[None], start, window))[0, 0]  # [C, K]
    nqt, nkt = C // tq, -(-k_len // tk)
    assert kp.shape == (nkt, tk) and live.shape == (nqt, 2)
    # every key once, at its position — or dead: a pad, past the window
    # (the band is the kernel's own comparison, query by query)
    alive = kp != pa.DEAD_KEY
    causal = np.asarray(llama_mod._prefill_mask(
        kpos0 + jnp.arange(k_len), mask[None], start, 0))[0, 0]
    np.testing.assert_array_equal(
        np.sort(kp[alive] - kpos0), np.flatnonzero(causal.any(axis=0)))
    for i in range(nqt):
        rows = full[i * tq:(i + 1) * tq]
        hit = [t for t in range(nkt)
               if rows[:, (kp[t][alive[t]] - kpos0)].any()]
        if hit:
            assert tuple(live[i]) == (hit[0], hit[-1]), (i, hit, live[i])
        else:
            assert live[i, 1] < live[i, 0]
        for t in range(nkt):
            sees_all = alive[t].all() and rows[:, kp[t] - kpos0].all()
            assert bool(whole[i, t]) == bool(sees_all), (i, t)
    n_live = int(np.maximum(live[:, 1] - live[:, 0] + 1, 0).sum())
    assert pa.count_live_tiles(
        start, n_valid, C, kpos0, k_len, window, tq, tk) == (n_live, nqt * nkt)


def _tile_counts(name):
    from mlmicroservicetemplate_tpu.utils import metrics

    return (metrics.PREFILL_KEY_TILES_LIVE.labels(name)._value.get(),
            metrics.PREFILL_KEY_TILES_DEAD.labels(name)._value.get())


@pytest.mark.parametrize("attention,kernels,want", [
    ("gqa", True, (184, 88)), ("mla", True, (11, 3)), ("gqa", False, (0, 0)),
], ids=["pattern", "latent", "xla"])
def test_prefill_tile_counters_follow_the_layers_kinds(attention, kernels, want):
    """One window of 2048 queries at 4096, 1500 of them real, a table of
    392 entries of 16 keys, key tiles of 1024.  A pattern of two window
    (2048) layers and a full one, 8 heads a KV head (16 q tiles of 128
    queries): a window layer gathers 4112 keys from 2048 on (5 key tiles)
    and every q tile's band spans 3 of them -> 48 of 80, twice; the full
    layer's 6272 keys are 7 tiles, 5 live for the q tiles whose last query
    lies before key 5120, 6 for the rest (none past the last real token at
    5595) -> 88 of 112.  A latent layer's expanded heads ride 1024
    queries a tile: 5 + 6 of 14.  Nothing without the kernels."""
    from mlmicroservicetemplate_tpu.engine.streams import ContinuousDecodeLoop

    kw = dict(pallas_decode=kernels, num_layers=3)
    if attention == "mla":
        kw.update(attention="mla", num_heads=16, num_kv_heads=16, q_lora_rank=32,
                  kv_lora_rank=64, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=16, num_layers=1)
    else:
        kw.update(num_heads=32, num_kv_heads=4, window=2048,
                  layer_types=("window", "full", "window"))
    cfg = llama_mod.LlamaConfig(**kw)
    name = f"tiles-unit-{attention}-{kernels}"
    loop = types.SimpleNamespace(
        prefill_chunk=2048, nb_max=392, block_size=16,
        engine=types.SimpleNamespace(
            bundle=types.SimpleNamespace(name=name, cfg=cfg)))
    before = _tile_counts(name)
    ContinuousDecodeLoop._note_prefill_tiles(loop, 4096, 4096 + 1500)
    got = tuple(a - b for a, b in zip(_tile_counts(name), before))
    live, total = llama_mod.prefill_tile_counts(cfg, 2048, 392, 16, 4096, 1500)
    assert got == (live, total - live)
    assert got == want, got


def test_the_loop_serves_chunked_prompts_through_the_kernel():
    """PAGED_KV + PREFILL_CHUNK with the kernels on (interpreted): the
    loop's windows run the prompt-window kernel and serve the tokens the
    XLA engine serves; every dispatched window counts its (q tile, key
    tile) pairs, a layer at a time, live or dead."""
    import asyncio

    from helpers import tiny_llama_bundle
    from mlmicroservicetemplate_tpu.engine import InferenceEngine
    from mlmicroservicetemplate_tpu.engine.streams import ContinuousDecodeLoop
    from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
    from mlmicroservicetemplate_tpu.utils.config import ServiceConfig

    def cfg(**kw):
        return ServiceConfig(
            device="cpu", warmup=False, batch_buckets=(1, 2, 4),
            seq_buckets=(16, 32), max_decode_len=8, stream_chunk_tokens=4,
            max_streams=4, **kw)

    async def consume(gen):
        return [t for c in [np.asarray(c).tolist() async for c in gen] for t in c]

    bundle = tiny_llama_bundle(pallas_decode=True, pallas_interpret=True)
    cfgc = cfg(prefill_chunk=8, prefill_max_prompt=48, paged_kv=True, kv_block_size=8)
    engc = InferenceEngine(bundle, cfgc, ReplicaSet(make_mesh(1)))
    eng0 = InferenceEngine(tiny_llama_bundle(), cfg(), ReplicaSet(make_mesh(1)))
    rng = np.random.default_rng(0)
    feats = [{"input_ids": p, "length": np.int32(len(p))}
             for p in (rng.integers(5, 250, n).astype(np.int32) for n in (19, 45))]
    solos = [np.concatenate(list(eng0.generate_stream(dict(f)))).tolist()
             for f in feats]
    before = _tile_counts("llama")
    cdl = ContinuousDecodeLoop(engc, cfgc)
    try:
        async def body():
            return await asyncio.gather(
                *[consume(cdl.submit_stream(dict(f))) for f in feats])

        assert asyncio.run(body()) == solos
        windows, nb_max = cdl.prefill_chunk_dispatches, cdl.nb_max
    finally:
        cdl.stop()
    live, dead = (a - b for a, b in zip(_tile_counts("llama"), before))
    # one q tile and one key tile (8 queries, the table's nb_max * 8 keys) a
    # layer a window: every window sees a key, so every pair is live
    assert windows == 3 + 6
    assert (live, dead) == (windows * bundle.cfg.num_layers, 0)
    assert nb_max * 8 <= pa.KEY_TILE
