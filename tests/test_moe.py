"""The expert FFN (OLMoE-style: router softmax -> top-k -> grouped
matmul over assignments sorted by expert -> weighted combine) and the
q/k-norm through ``models/llama.py``, held to the benchmark's plain
reference (``cellbench/references/olmoe.py``) at a tiny size on the CPU
in float32.

TOL: model and reference both compute in float32 and differ only in the
order of sums (a grouped matmul against a masked loop over experts):
1e-6 on logits of size ~0.6, measured 2e-7.  Every rule a served path
could get wrong moves a logit by 5e-3 or more at this size (top-(k-1)
5.9e-3, renormalised top-k 2.3e-2, no q/k-norm and bf16 arithmetic more:
each shown failing below), so 1e-4 separates them with room both ways.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import spec as bench_spec
from mlmicroservicetemplate_tpu.models import llama as llama_mod
from mlmicroservicetemplate_tpu.ops import moe

TOL = 1e-4
TINY = dict(
    vocab_size=128, d_model=64, num_heads=4, num_kv_heads=4, num_layers=2,
    d_ff=32, max_position=128, num_experts=8, experts_per_token=2,
    qk_norm=True, eos_id=1, pad_id=0, pallas_interpret=True,
)
HP = {"heads": 4, "kv_heads": 4, "head_dim": 16, "theta": 10000.0,
      "eps": 1e-5, "top_k": 2, "norm_topk": False}


@pytest.fixture(scope="module")
def ref():
    return bench_spec.load_module(
        bench_spec.HERE + "/references/olmoe.py", "cellbench_reference_olmoe")


@pytest.fixture(scope="module")
def cfg():
    return llama_mod.LlamaConfig(**TINY)


@pytest.fixture(scope="module")
def params(cfg):
    # The init draws the q/k-norm scales about 1 (not all ones), so a
    # dropped q/k-norm shows: test_qk_norm_scales_are_drawn_not_ones.
    return llama_mod.init_params(jax.random.PRNGKey(0), cfg)


def _prompts(lens, seed=0, vocab=120):
    rng = np.random.default_rng(seed)
    ids = np.zeros((len(lens), max(lens)), np.int32)
    mask = np.zeros_like(ids)
    for i, n in enumerate(lens):
        ids[i, :n] = rng.integers(3, vocab, n)
        mask[i, :n] = 1
    return jnp.asarray(ids), jnp.asarray(mask)


def _close(got, want):
    return float(jnp.max(jnp.abs(jnp.asarray(got) - jnp.asarray(want))))


# ---------------------------------------------------------------------------
# the whole model against the reference


def test_full_forward_matches_the_reference(ref, cfg, params):
    ids, mask = _prompts([12, 12])
    got = llama_mod.lm_logits(params, cfg, ids, mask)
    assert _close(got, ref.logits(params, HP, ids)) < TOL


@pytest.mark.parametrize("wrong", [
    {"top_k": 1}, {"norm_topk": True}, "no_qk_norm", "bf16"])
def test_the_tolerance_fails_a_changed_rule(ref, cfg, params, wrong):
    """Top-(k-1), a renormalised top-k, a dropped q/k-norm and bf16
    arithmetic each land outside TOL."""
    ids, mask = _prompts([12, 12])
    want = ref.logits(params, HP, ids)
    if wrong == "no_qk_norm":
        got = llama_mod.lm_logits(
            params, dataclasses.replace(cfg, qk_norm=False), ids, mask)
    elif wrong == "bf16":
        got = llama_mod.lm_logits(params, cfg, ids, mask, dtype=jnp.bfloat16)
    else:
        got = ref.logits(params, {**HP, **wrong}, ids)
    assert _close(got, want) > 10 * TOL


class _Logits:
    """Every ``lm_head_logits`` a step makes, kept (steps run eagerly)."""

    def __init__(self, monkeypatch):
        self.seen = []
        real = llama_mod.lm_head_logits

        def keep(*a, **kw):
            self.seen.append(real(*a, **kw))
            return self.seen[-1]

        monkeypatch.setattr(llama_mod, "lm_head_logits", keep)


def _teacher_forced(ref, params, ids, lens, steps_tokens):
    """Reference logits of each decode position: row b's step j sees its
    prompt plus the j tokens decoded before."""
    out = []
    for b, n in enumerate(lens):
        seq = list(np.asarray(ids[b, :n])) + [int(t[b]) for t in steps_tokens]
        full = ref.logits(params, HP, jnp.asarray([seq], jnp.int32))[0]
        out.append(full[n - 1: n - 1 + len(steps_tokens)])
    return jnp.stack(out)  # [B, steps, V]


def test_prefill_then_decode_through_the_contiguous_cache(
        ref, cfg, params, monkeypatch):
    lens, steps = [5, 9, 7], 4
    ids, mask = _prompts(lens, seed=1)
    state = llama_mod.init_decode_state(params, cfg, ids, mask, steps)
    seen, toks = _Logits(monkeypatch), []
    for _ in range(steps):
        state, tok = llama_mod._decode_step(params, cfg, state)
        toks.append(np.asarray(tok))
    want = _teacher_forced(ref, params, ids, lens, toks)
    got = jnp.stack(seen.seen, axis=1)
    assert _close(got, want) < TOL


def test_paged_prefill_chunks_then_paged_decode(ref, cfg, params, monkeypatch):
    """Prompt windows written straight into pool blocks, then decode
    through the block table and the paged kernel (interpret mode)."""
    from mlmicroservicetemplate_tpu.models.gpt import PagedState
    from mlmicroservicetemplate_tpu.models.sampling import greedy_params

    kcfg = dataclasses.replace(cfg, pallas_decode=True)
    n, bs, window, steps = 11, 4, 8, 4
    ids, _ = _prompts([n], seed=2)
    nb = 6
    table = jnp.asarray([[4, 1, 5, 0, 2, 3]], jnp.int32)
    shape = (nb, bs, cfg.num_kv_heads * cfg.head_dim)  # the pool's layout
    state = PagedState(
        cache_k=[jnp.zeros(shape) for _ in range(cfg.num_layers)],
        cache_v=[jnp.zeros(shape) for _ in range(cfg.num_layers)],
        key_valid=jnp.zeros((1, nb * bs), jnp.int32),
        write_idx=jnp.zeros((1,), jnp.int32), pos=jnp.zeros((1,), jnp.int32),
        last_token=jnp.zeros((1,), jnp.int32), done=jnp.zeros((1,), bool),
        tokens=jnp.zeros((1, steps), jnp.int32), sample=greedy_params(1),
    )
    for start in range(0, 16, window):
        w_ids = jnp.zeros((1, window), jnp.int32).at[0, : max(n - start, 0)].set(
            ids[0, start:start + window])
        w_mask = (jnp.arange(window)[None] + start < n).astype(jnp.int32)
        state = llama_mod.paged_prefill_chunk(
            params, kcfg, state, table, w_ids, w_mask, jnp.asarray([start]))
    state = state._replace(
        key_valid=state.key_valid.at[0, : n - 1].set(1),
        write_idx=jnp.asarray([n - 1]), last_token=ids[:, n - 1])
    seen, toks = _Logits(monkeypatch), []
    for _ in range(steps):
        state, (tok, counts) = llama_mod._paged_decode_step(
            params, kcfg, state, table)
        toks.append(np.asarray(tok))
        np.testing.assert_array_equal(  # [L, E]: one live row, k a layer
            np.asarray(counts.sum(axis=1)), [cfg.experts_per_token] * cfg.num_layers)
    want = _teacher_forced(ref, params, ids, [n], toks)
    assert _close(jnp.stack(seen.seen, axis=1), want) < TOL


def test_wave_rungs_give_the_same_logits(cfg, params):
    """A prompt run alone, in a rung of 4 and in one of 8 rows (the rest
    padding) reads the same logits, and padding rows are finite."""
    ids, mask = _prompts([10], seed=3)
    alone = llama_mod.lm_logits(params, cfg, ids, mask)
    for rows in (4, 8):
        ids_r = jnp.zeros((rows, 10), jnp.int32).at[0].set(ids[0])
        mask_r = jnp.zeros((rows, 10), jnp.int32).at[0].set(1)
        got = llama_mod.lm_logits(params, cfg, ids_r, mask_r)
        assert bool(jnp.isfinite(got).all())
        assert _close(got[0], alone[0]) < 1e-5


# ---------------------------------------------------------------------------
# the expert block alone


def _mlp(key, d=16, w=8, e=6):
    ks = jax.random.split(key, 4)
    return {
        "router": {"kernel": jax.random.normal(ks[0], (d, e))},
        "gate": {"kernel": jax.random.normal(ks[1], (e, d, w)) * 0.3},
        "up": {"kernel": jax.random.normal(ks[2], (e, d, w)) * 0.3},
        "down": {"kernel": jax.random.normal(ks[3], (e, w, d)) * 0.3},
    }


def _dense_masked(h, mlp, k, norm_topk):
    """Every expert on every token, then a mask: the 8x-FLOPs form the
    served path must not be, good for checking it."""
    p = jax.nn.softmax(h @ mlp["router"]["kernel"], axis=-1)
    w, e = jax.lax.top_k(p, k)
    if norm_topk:
        w = w / w.sum(-1, keepdims=True)
    gate, up, down = (mlp[n]["kernel"] for n in ("gate", "up", "down"))
    y = jnp.einsum(
        "tew,ewd->ted",
        jax.nn.silu(jnp.einsum("td,edw->tew", h, gate))
        * jnp.einsum("td,edw->tew", h, up), down)
    weight = jnp.sum(
        jnp.where(e[:, :, None] == jnp.arange(gate.shape[0]), w[:, :, None], 0.0),
        axis=1)  # [T, E]
    return jnp.einsum("te,ted->td", weight, y)


@pytest.mark.parametrize("norm_topk", [False, True])
def test_grouped_path_equals_a_dense_masked_einsum(norm_topk):
    mlp = _mlp(jax.random.PRNGKey(1))
    h = jax.random.normal(jax.random.PRNGKey(2), (20, 16))
    out, counts = moe.expert_ffn(h, mlp, 3, norm_topk, jnp.ones((20,), bool), interpret=True)
    assert _close(out, _dense_masked(h, mlp, 3, norm_topk)) < 1e-5
    assert int(counts.sum()) == 20 * 3


def test_every_token_to_one_expert_and_experts_with_no_token():
    mlp = _mlp(jax.random.PRNGKey(3))
    # A router that sends everything to expert 4 first, then 1.
    mlp["router"]["kernel"] = jnp.zeros((16, 6)).at[:, 4].set(1.0).at[:, 1].set(0.5)
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (10, 16)))
    out, counts = moe.expert_ffn(h, mlp, 2, False, jnp.ones((10,), bool), interpret=True)
    np.testing.assert_array_equal(np.asarray(counts), [0, 10, 0, 0, 10, 0])
    assert _close(out, _dense_masked(h, mlp, 2, False)) < 1e-5
    out1, counts1 = moe.expert_ffn(h, mlp, 1, False, jnp.ones((10,), bool), interpret=True)
    np.testing.assert_array_equal(np.asarray(counts1), [0, 0, 0, 0, 10, 0])
    assert bool(jnp.isfinite(out1).all())


def test_invalid_rows_are_neither_counted_nor_nan():
    mlp = _mlp(jax.random.PRNGKey(5))
    h = jax.random.normal(jax.random.PRNGKey(6), (12, 16))
    valid = jnp.arange(12) % 3 != 0  # 8 of 12
    out, counts = moe.expert_ffn(h, mlp, 2, False, valid, interpret=True)
    assert int(counts.sum()) == 8 * 2
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_array_equal(np.asarray(out[~valid]), 0.0)
    want = _dense_masked(h, mlp, 2, False)
    assert _close(out[valid], want[valid]) < 1e-5
    # No valid row at all: nothing routed, nothing NaN.
    out0, counts0 = moe.expert_ffn(h, mlp, 2, False, jnp.zeros((12,), bool), interpret=True)
    assert int(counts0.sum()) == 0 and not bool(jnp.any(out0))


# One expert layer's matmuls (gate / up [d, w], down [w, d]) in the
# benchmark's cells: rows M (every assignment, held or not: a chip's share —
# DeepSeek-V2's, Nemotron's quarter of the experts — sorts three rows in
# four past the last group) over G held experts -> (d, w, the N tile of
# gate / up, the N tile of down) in bf16.  A prompt dispatch's mean group is
# 77-192 rows, two 128-row tiles or more; a wave's 1024; a step's 1-8.
CELL_SHAPES = {
    "trinity-prompt-dispatch-24576-rows-128-experts": (2048, 1024, 1024, 2048),
    "dsv2-prompt-window-12288-rows-40-experts": (5120, 1536, 512, 1280),
    "nemotron-prompt-dispatch-67584-rows-128-experts": (1024, 2688, 2688, 1024),
    "olmoe-wave-65536-rows-64-experts": (2048, 1024, 1024, 2048),
    "olmoe-decode-step-512-rows-64-experts": (2048, 1024, 1024, 2048),
    "trinity-decode-step-256-rows-128-experts": (2048, 1024, 1024, 2048),
    "dsv2-decode-step-192-rows-40-experts": (5120, 1536, 512, 1280),
    "nemotron-decode-step-704-rows-128-experts": (1024, 2688, 2688, 1024),
}


@pytest.mark.parametrize("shape", sorted(CELL_SHAPES))
def test_the_tiles_keep_an_experts_k_whole_at_the_shapes_the_cells_run(shape):
    """K is one tile (so a group's weight block keeps its index over the
    group's row tiles and crosses HBM once), every tile is a multiple of
    128 that divides its axis, the blocks fit the budget and the next
    wider N tile would not have."""
    d, w, tn_in, tn_out = CELL_SHAPES[shape]
    for (k, n), tn_want in (((d, w), tn_in), ((w, d), tn_out)):
        tm, tk, tn = moe.matmul_tiles(k, n, 2)
        assert (tm, tk, tn) == (moe.ROW_TILE, k, tn_want)
        assert tm % 128 == 0 and tk % 128 == 0 and tn % 128 == 0 and n % tn == 0
        assert moe.tile_bytes(tm, tk, tn, 2) <= moe.VMEM_BUDGET
        wider = [t for t in moe._tile_sizes(n) if t > tn]
        assert all(moe.tile_bytes(tm, tk, t, 2) > moe.VMEM_BUDGET for t in wider)


def test_a_k_no_n_tile_fits_beside_is_tiled_and_an_odd_axis_stays_whole(monkeypatch):
    assert moe.matmul_tiles(14336, 4096, 2) == (128, 7168, 256)
    assert moe.matmul_tiles(16, 8, 4) == (128, 16, 8)  # the tests' sizes
    monkeypatch.setattr(moe, "VMEM_BUDGET", 1 << 16)
    with pytest.raises(ValueError, match="no tiling"):
        moe.matmul_tiles(1000, 72, 2)


def test_grouped_matmul_over_three_row_tiles_an_empty_group_and_invalid_rows(
        monkeypatch):
    """The kernel under the rule's tiling (K whole, N in three tiles, M
    padded to the row tile) against a dense masked einsum: a group that
    spans three row tiles, an empty group, rows past the last group."""
    monkeypatch.setattr(moe, "VMEM_BUDGET", 1 << 20)
    m, k, n = 500, 256, 384
    assert moe.matmul_tiles(k, n, 4) == (128, 256, 128)
    sizes = jnp.asarray([70, 300, 0, 60], jnp.int32)  # group 1: rows 70..369
    valid = int(sizes.sum())
    lhs = jax.random.normal(jax.random.PRNGKey(7), (m, k))
    rhs = jax.random.normal(jax.random.PRNGKey(8), (4, k, n)) * 0.1
    got = moe.grouped_matmul(lhs, rhs, sizes, interpret=True)
    assert got.shape == (m, n)
    group = jnp.repeat(jnp.arange(4), sizes, total_repeat_length=valid)
    want = jnp.einsum(
        "mgn,mg->mn", jnp.einsum("mk,gkn->mgn", lhs[:valid], rhs),
        jax.nn.one_hot(group, 4))
    assert _close(got[:valid], want) < 1e-4


def test_chunk_counters_add_up_and_skip_done_rows(cfg, params):
    """The paged chunk's counts, a row a layer: each layer's = live rows
    x k x steps; a row that is ``done`` decodes pad tokens and is not
    counted."""
    lens, steps, bs = [6, 9, 4], 4, 4
    ids, mask = _prompts(lens, seed=7)
    nb_row = -(-(ids.shape[1] + steps) // bs)
    table = jnp.arange(3 * nb_row, dtype=jnp.int32).reshape(3, nb_row)
    state = llama_mod.init_paged_state(
        params, cfg, ids, mask, steps, table, 3 * nb_row, bs)
    st, (toks, counts) = llama_mod.generate_chunk_paged(
        params, cfg, state, table, steps)
    assert toks.shape == (3, steps)
    assert counts.shape == (cfg.num_layers, cfg.num_experts)
    assert counts.dtype == jnp.int32
    per_row = cfg.experts_per_token * steps
    np.testing.assert_array_equal(
        np.asarray(counts.sum(axis=1)), [3 * per_row] * cfg.num_layers)
    st2, (toks2, counts2) = llama_mod.generate_chunk_paged(
        params, cfg, state._replace(done=jnp.asarray([False, True, False])),
        table, steps)
    np.testing.assert_array_equal(
        np.asarray(counts2.sum(axis=1)), [2 * per_row] * cfg.num_layers)
    np.testing.assert_array_equal(np.asarray(toks2[1]), cfg.pad_id)
    np.testing.assert_array_equal(np.asarray(toks2[0]), np.asarray(toks[0]))


def test_dense_paged_chunk_returns_nothing_new():
    from tests.helpers import TINY_LLAMA

    dcfg = llama_mod.LlamaConfig(**TINY_LLAMA)
    dparams = llama_mod.init_params(jax.random.PRNGKey(0), dcfg)
    ids, mask = _prompts([5], seed=8)
    table = jnp.arange(4, dtype=jnp.int32)[None]
    state = llama_mod.init_paged_state(dparams, dcfg, ids, mask, 4, table, 4, 4)
    _, toks = llama_mod.generate_chunk_paged(dparams, dcfg, state, table, 4)
    assert isinstance(toks, jax.Array) and toks.shape == (1, 4)


def test_loop_delivers_the_counts_into_the_metrics():
    """The continuous loop's chunk fetch carries the counts home:
    ``moe_assignments_total`` grows by what the delivered chunks routed,
    ``moe_experts_hit`` and ``moe_load_imbalance`` are observed."""
    import asyncio

    from mlmicroservicetemplate_tpu.engine import InferenceEngine
    from mlmicroservicetemplate_tpu.engine.streams import ContinuousDecodeLoop
    from mlmicroservicetemplate_tpu.parallel import make_mesh
    from mlmicroservicetemplate_tpu.parallel.mesh import ReplicaSet
    from mlmicroservicetemplate_tpu.utils import metrics
    from mlmicroservicetemplate_tpu.utils.config import ServiceConfig
    from tests.helpers import tiny_llama_bundle

    over = {k: v for k, v in TINY.items() if k not in ("eos_id", "pad_id")}
    bundle = tiny_llama_bundle(**{**over, "vocab_size": 300})
    svc = ServiceConfig(
        device="cpu", warmup=False, batch_buckets=(1, 2, 4),
        seq_buckets=(16, 32), max_decode_len=8, stream_chunk_tokens=4,
        max_streams=4, paged_kv=True, kv_block_size=8)
    eng = InferenceEngine(bundle, svc, ReplicaSet(make_mesh(1)))
    cdl = ContinuousDecodeLoop(eng, svc)

    def total():
        return metrics.MOE_ASSIGNMENTS.labels("llama")._value.get()

    before = total()

    async def one(n):
        ids = np.random.default_rng(n).integers(5, 250, n).astype(np.int32)
        feats = {"input_ids": ids, "length": np.int32(n)}
        return [c async for c in cdl.submit_stream(feats)]

    async def body():
        return await asyncio.gather(one(6), one(14))

    try:
        chunks = asyncio.run(body())
    finally:
        cdl.stop()
    assert all(chunks)
    grown = total() - before
    per_step = bundle.cfg.num_layers * bundle.cfg.experts_per_token
    assert grown > 0 and grown % per_step == 0
    hit = metrics.MOE_EXPERTS_HIT.labels("llama")._value.get()
    assert bundle.cfg.experts_per_token <= hit <= bundle.cfg.num_experts
    body, _ = metrics.render()
    assert b"moe_load_imbalance_count" in body


def test_imbalance_and_experts_hit_are_a_layers_not_the_sum_over_layers():
    """Two layers, each with ALL its assignments on one expert, but not
    the same one: summed over layers that reads 2x the mean of 4 experts;
    a grouped matmul sees 4x, twice."""
    from mlmicroservicetemplate_tpu.engine.streams import ContinuousDecodeLoop
    from mlmicroservicetemplate_tpu.utils import metrics

    loop = ContinuousDecodeLoop.__new__(ContinuousDecodeLoop)
    loop.engine = type("E", (), {"bundle": type("B", (), {"name": "moe-unit"})})()
    hist = metrics.MOE_LOAD_IMBALANCE.labels("moe-unit")
    loop._note_moe(np.asarray([[8, 0, 0, 0], [0, 0, 8, 0]], np.int32))
    assert hist._sum.get() == pytest.approx(4.0)
    assert metrics.MOE_EXPERTS_HIT.labels("moe-unit")._value.get() == 1.0
    assert metrics.MOE_ASSIGNMENTS.labels("moe-unit")._value.get() == 16
    loop._note_moe(np.asarray([[2, 2, 2, 2], [4, 4, 0, 0]], np.int32))
    assert hist._sum.get() == pytest.approx(4.0 + (1.0 + 2.0) / 2)
    assert metrics.MOE_EXPERTS_HIT.labels("moe-unit")._value.get() == 3.0
    loop._note_moe(np.zeros((2, 4), np.int32))  # every row done: not observed
    assert metrics.MOE_ASSIGNMENTS.labels("moe-unit")._value.get() == 32


# ---------------------------------------------------------------------------
# init, registry, converter


@pytest.mark.parametrize("experts", [False, True])
def test_per_leaf_cast_init_is_bit_identical_to_cast_after(experts):
    from mlmicroservicetemplate_tpu.models.common import cast_pytree
    from tests.helpers import TINY_LLAMA

    c = llama_mod.LlamaConfig(**(TINY if experts else TINY_LLAMA))
    key = jax.random.PRNGKey(0)
    after = cast_pytree(llama_mod.init_params(key, c), jnp.bfloat16)
    at_once = llama_mod.init_params(key, c, dtype=jnp.bfloat16)
    assert jax.tree.structure(after) == jax.tree.structure(at_once)
    for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(at_once)):
        assert a.dtype == b.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(a.astype(jnp.float32)), np.asarray(b.astype(jnp.float32)))


def test_dense_init_draws_are_what_they_were():
    """The expert leaves took no key from the dense tree: a dense
    config's float32 draws are the ones every run since PR 24 served."""
    from tests.helpers import TINY_LLAMA

    c = llama_mod.LlamaConfig(**TINY_LLAMA)
    p = llama_mod.init_params(jax.random.PRNGKey(0), c)
    keys = jax.random.split(jax.random.PRNGKey(0), c.num_layers + 2)
    k = jax.random.split(keys[2], 7)
    want = jax.random.normal(jax.random.split(k[4])[0], (32, 64)) * 0.02
    np.testing.assert_array_equal(
        np.asarray(p["layers"][0]["mlp"]["gate"]["kernel"]), np.asarray(want))


def _svc(monkeypatch, **kw):
    import json

    from mlmicroservicetemplate_tpu.utils.config import ServiceConfig

    over = {k: v for k, v in TINY.items()
            if k not in ("eos_id", "pad_id", "pallas_interpret")}
    over["vocab_size"] = 300
    monkeypatch.setenv("LLAMA_CONFIG", json.dumps(over))
    kw.setdefault("pallas_interpret", True)
    return ServiceConfig(device="cpu", model_name="llama", warmup=False,
                         seq_buckets=(16, 32), max_decode_len=8, **kw)


def test_registry_builds_the_expert_config(monkeypatch):
    from mlmicroservicetemplate_tpu.models.registry import build_model

    bundle = build_model(_svc(monkeypatch))
    assert bundle.cfg.num_experts == 8 and bundle.cfg.qk_norm
    mlp = bundle.params["layers"][0]["mlp"]
    assert mlp["gate"]["kernel"].shape == (8, 64, 32)
    assert mlp["router"]["kernel"].shape == (64, 8)


def test_the_bundle_hands_out_the_programs_own_logits(ref, monkeypatch):
    """``bundle.logits_fn``: every position's logits through the prefill
    forward — what the benchmark's check holds to its reference where a
    served token cannot tell a rule apart (top-(k-1))."""
    from mlmicroservicetemplate_tpu.models.registry import build_model

    bundle = build_model(_svc(monkeypatch))
    ids, mask = _prompts([9, 14], seed=11)
    got = jax.jit(bundle.logits_fn)(bundle.params, ids, mask)
    want = ref.logits(bundle.params, HP, np.asarray(ids))
    assert got.shape == (2, 14, 300)
    assert ref.logit_rms_error(want, got, [9, 14]) < 1e-5
    seven = dataclasses.replace(bundle.cfg, experts_per_token=1)
    wrong = llama_mod.lm_logits(bundle.params, seven, ids, mask)
    assert ref.logit_rms_error(want, wrong, [9, 14]) > 5e-4


def test_qk_norm_scales_are_drawn_not_ones(cfg, params):
    """A scale of all ones would make a dropped norm nearly invisible
    (q = y W_q has an rms near 1 already); every layer draws its own."""
    scales = [np.asarray(layer["attn"][n]["scale"], np.float32)
              for layer in params["layers"] for n in ("q_norm", "k_norm")]
    for s in scales:
        assert 0.15 < s.std() < 0.35 and abs(s.mean() - 1.0) < 0.1
    assert not np.array_equal(scales[0], scales[1])
    assert not np.array_equal(scales[0], scales[2])
    np.testing.assert_array_equal(
        np.asarray(params["layers"][0]["attn_ln"]["scale"]), 1.0)


@pytest.mark.parametrize("add_bos", [True, False])
def test_a_family_without_a_bos_gets_none(add_bos, monkeypatch, tmp_path):
    """``LlamaConfig.add_bos`` reaches the tokenizer: OLMoE's has no
    BOS, and a prompt is then its own tokens and no more."""
    import json

    from mlmicroservicetemplate_tpu.models.registry import build_model

    table = tmp_path / "pieces.tsv"
    table.write_text("<unk>\t0\n<s>\t0\n</s>\t0\n" + "".join(
        f"\u2581w{i}\t-1\n" for i in range(3, 300)), encoding="utf-8")
    svc = _svc(monkeypatch, tokenizer_path=str(table))
    over = json.loads(os.environ["LLAMA_CONFIG"])
    monkeypatch.setenv("LLAMA_CONFIG", json.dumps({**over, "add_bos": add_bos}))
    bundle = build_model(svc)
    assert bundle.cfg.add_bos is add_bos
    ids, mask = bundle.tokenizer.encode("w7 w8 w9", 16)
    want = ([1] if add_bos else []) + [7, 8, 9]
    assert [int(t) for t in ids[: int(mask.sum())]] == want


@pytest.mark.parametrize("knob,needle", [
    ({"tp": 2}, "TP=2 is not supported"),
    ({"quantize": "int8"}, "QUANTIZE=int8 is not supported"),
])
def test_registry_refuses_what_the_experts_do_not_cover(monkeypatch, knob, needle):
    from mlmicroservicetemplate_tpu.models.registry import build_model

    with pytest.raises(ValueError, match=needle):
        build_model(_svc(monkeypatch, **knob))


def test_a_lora_target_inside_the_experts_is_refused(cfg, params):
    """Adapters name attention projections only; one that names an
    expert matrix fails the boot's shape check."""
    from mlmicroservicetemplate_tpu.tenancy.adapters import AdapterPool

    pool = AdapterPool.__new__(AdapterPool)
    pool.projections = ("gate",)
    pool._stacks, pool.num_layers = {}, cfg.num_layers
    with pytest.raises(ValueError, match="adapters target projection 'gate'"):
        pool.validate_against(params)


def test_registry_refuses_the_kernel_off_tpu_without_interpret(monkeypatch):
    from mlmicroservicetemplate_tpu.models.registry import build_model

    with pytest.raises(RuntimeError, match="PALLAS_INTERPRET=1"):
        build_model(_svc(monkeypatch, pallas_interpret=False))


def test_bad_experts_per_token_is_refused():
    with pytest.raises(ValueError, match="experts_per_token"):
        llama_mod.LlamaConfig(num_experts=8, experts_per_token=9)


def test_converter_round_trip(cfg, params):
    """HF OLMoE names -> the stacked leaves, back to what was exported."""
    from mlmicroservicetemplate_tpu.convert import llama_state_to_pytree

    t = lambda a: np.ascontiguousarray(np.asarray(a).T)  # noqa: E731
    state = {
        "model.embed_tokens.weight": np.asarray(params["embed"]["embedding"]),
        "model.norm.weight": np.asarray(params["final_ln"]["scale"]),
        "lm_head.weight": t(params["lm_head"]["kernel"]),
    }
    for i, layer in enumerate(params["layers"]):
        b = f"model.layers.{i}"
        a, m = layer["attn"], layer["mlp"]
        state[f"{b}.input_layernorm.weight"] = np.asarray(layer["attn_ln"]["scale"])
        state[f"{b}.post_attention_layernorm.weight"] = np.asarray(
            layer["mlp_ln"]["scale"])
        for n in "qkvo":
            state[f"{b}.self_attn.{n}_proj.weight"] = t(a[n]["kernel"])
        for n in ("q_norm", "k_norm"):
            state[f"{b}.self_attn.{n}.weight"] = np.asarray(a[n]["scale"])
        state[f"{b}.mlp.gate.weight"] = t(m["router"]["kernel"])
        for e in range(cfg.num_experts):
            for n in ("gate", "up", "down"):
                state[f"{b}.mlp.experts.{e}.{n}_proj.weight"] = t(m[n]["kernel"][e])
    back = llama_state_to_pytree(state)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for x, y in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# the ladder of row counts (ops/moe.row_rungs): a chip's share of the experts


# (tokens a decode step, k, held, published) of the five expert configurations'
# cells; a prompt dispatch's tokens beside them for the three that hold a share.
CELL_EXPERTS = {
    "olmoe": (64, 8, 64, 64, 0),
    "trinity": (32, 8, 128, 128, 0),
    "deepseek-v2": (32, 6, 40, 160, 2048),
    "nemotron": (32, 22, 128, 512, 3072),
    "gigachat": (32, 8, 16, 256, 3072),
}


@pytest.mark.parametrize("n, held, pub", [
    (67584, 128, 512), (22528, 128, 512), (24576, 16, 256), (8192, 16, 256),
    (12288, 40, 160), (704, 128, 512), (65536, 64, 64), (100000, 3, 4),
    (6000, 1, 2), (5000, 7, 8)])
def test_the_rungs_rise_in_row_tiles_and_end_at_every_row(n, held, pub):
    rungs = moe.row_rungs(n, held, pub)
    assert rungs[-1] == n and list(rungs) == sorted(set(rungs))
    for r in rungs[:-1]:
        assert r % moe.ROW_TILE == 0 and n - r >= moe.LADDER_MIN_SKIP
        assert r * pub >= n * held  # an even router's share fits every rung


@pytest.mark.parametrize("cell", sorted(CELL_EXPERTS))
def test_a_decode_step_and_a_whole_tree_have_one_rung(cell):
    t, k, held, pub, prompt = CELL_EXPERTS[cell]
    assert moe.row_rungs(t * k, held, pub) == (t * k,)
    assert moe.row_rungs(3072 * k, pub, pub) == (3072 * k,)
    if prompt:  # a chip's share: the prompt dispatch has rungs below the top
        rungs = moe.row_rungs(prompt * k, held, pub)
        # 5/4 of the share an even router sends the held experts, then all
        assert len(rungs) == 2 and rungs[0] * 16 * pub == prompt * k * held * 20


def _share(key, d=16, w=8, pub=8, held=2):
    """A tree that holds experts ``0 .. held - 1`` of ``pub``, and a router
    whose three leading input features decide a token's two experts: (1,
    0, 0) both held, (0, 1, 0) one held and one absent, (0, 0, 1) both
    absent."""
    ks = jax.random.split(key, 4)
    router = jax.random.normal(ks[0], (d, pub)) * 0.01
    router = router.at[:3].set(0.0).at[0, :2].set(1.0).at[1, 0].set(1.0)
    router = router.at[1, 5].set(1.0).at[2, 4:6].set(1.0)
    return {
        "router": {"kernel": router},
        "gate": {"kernel": jax.random.normal(ks[1], (held, d, w)) * 0.3},
        "up": {"kernel": jax.random.normal(ks[2], (held, d, w)) * 0.3},
        "down": {"kernel": jax.random.normal(ks[3], (held, w, d)) * 0.3},
    }


def _share_tokens(key, t, held, d=16):
    """``t`` tokens of which exactly ``held`` assignments (two a token)
    land on the held experts, the kinds shuffled."""
    both, one = held // 2, held % 2
    kind = jnp.asarray([0] * both + [1] * one + [2] * (t - both - one))
    kind = jax.random.permutation(jax.random.fold_in(key, 1), kind)
    h = jax.random.normal(key, (t, d))
    return h.at[:, :3].set(20.0 * jax.nn.one_hot(kind, 3))


def _share_reference(h, mlp, k):
    """The held experts of the top-k over ALL published experts, densely,
    in float32."""
    p = jax.nn.softmax(h @ mlp["router"]["kernel"], axis=-1)
    w, e = jax.lax.top_k(p, k)
    gate, up, down = (mlp[n]["kernel"] for n in ("gate", "up", "down"))
    y = jnp.einsum(
        "tew,ewd->ted",
        jax.nn.silu(jnp.einsum("td,edw->tew", h, gate))
        * jnp.einsum("td,edw->tew", h, up), down)
    weight = jnp.sum(
        jnp.where(e[:, :, None] == jnp.arange(gate.shape[0]), w[:, :, None], 0.0),
        axis=1)  # [T, held]
    return jnp.einsum("te,ted->td", weight, y)


def _count(jaxpr, name: str) -> int:
    """Equations of primitive ``name`` in a jaxpr and in the jaxprs it
    calls, a Pallas kernel's own body (its ``pl.when``) left out."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n += _count(sub, name)
    return n


T_LADDER, K_LADDER = 512, 2
TWO_BELOW = ((5, 4), (2, 1))  # a ladder of three rungs: the rule takes any
RUNGS = (384, 512, 1024)  # of 1024 assignments, 2 of 8 experts held


@pytest.fixture(scope="module")
def ladder_fns():
    """``expert_ffn`` over ``T_LADDER`` tokens with a ladder of three rungs
    (a rung needs to leave out 128 rows here, not 8192) and with one rung,
    each compiled once: the held count is data."""
    def build(ladder):
        def run(h, mlp):
            keep = moe.LADDER, moe.LADDER_MIN_SKIP
            moe.LADDER, moe.LADDER_MIN_SKIP = ladder, 128
            try:
                return moe.expert_ffn(
                    h, mlp, K_LADDER, False, jnp.ones((h.shape[0],), bool),
                    interpret=True)
            finally:
                moe.LADDER, moe.LADDER_MIN_SKIP = keep
        return jax.jit(run)

    return build(TWO_BELOW), build(())


@pytest.mark.parametrize(
    "held", [0, 383, 384, 385, 511, 512, 513, 1023, 1024])
def test_every_rung_is_the_one_rung_program_and_the_reference(ladder_fns, held):
    """The held count at 0, one under, at and one over every rung, and at
    ``t * k`` (every assignment lands here: the top rung runs, nothing is
    dropped): the output is the one-rung program's bit for bit, the plain
    float32 reference's within rounding, the counts identical."""
    ladder, one_rung = ladder_fns
    mlp = _share(jax.random.PRNGKey(11))
    h = _share_tokens(jax.random.PRNGKey(held), T_LADDER, held)
    out, counts = ladder(h, mlp)
    assert int(counts[:2].sum()) == held and int(counts.sum()) == 1024
    want, want_counts = one_rung(h, mlp)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(want_counts))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    assert _close(out, _share_reference(h, mlp, K_LADDER)) < 1e-5


def test_the_ladder_of_the_rung_tests_has_two_rungs_below_the_top(monkeypatch):
    monkeypatch.setattr(moe, "LADDER_MIN_SKIP", 128)
    assert moe.row_rungs(T_LADDER * K_LADDER, 2, 8) == (384, 1024)  # as shipped
    monkeypatch.setattr(moe, "LADDER", TWO_BELOW)
    assert moe.row_rungs(T_LADDER * K_LADDER, 2, 8) == RUNGS
    mlp = _share(jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(lambda h: moe.expert_ffn(
        h, mlp, K_LADDER, False, jnp.ones((T_LADDER,), bool),
        interpret=True))(jnp.zeros((T_LADDER, 16)))
    # a branch each for the gather, the activation and the combine: the
    # test below can see one; the grouped matmuls stay outside them
    assert _count(jaxpr.jaxpr, "cond") == 3
    assert _count(jaxpr.jaxpr, "pallas_call") == 3


@pytest.mark.parametrize("held", [0, 1, 383, 384, 385, 512, 513, 1024])
def test_the_hosts_rung_is_the_devices(held):
    """``rung_index`` on a host int, on a numpy array of counts and on a
    traced scalar (what ``lax.switch`` branches on) name the same rung:
    the lowest that holds the count."""
    want = next(i for i, r in enumerate(RUNGS) if held <= r)
    assert moe.rung_index(held, RUNGS) == want
    np.testing.assert_array_equal(
        moe.rung_index(np.asarray([held, 0, 1024]), RUNGS), [want, 0, 2])
    on_device = jax.jit(lambda c: moe.rung_index(c, RUNGS))(jnp.int32(held))
    assert int(on_device) == want
    assert moe.rung_index(held, (1024,)) == 0


@pytest.mark.parametrize("cell", sorted(CELL_EXPERTS))
def test_one_rung_traces_no_conditional(cell):
    """A decode step of each expert configuration (its experts' counts and
    k at toy widths), and a prompt's worth of tokens over a tree that holds
    every expert: the jaxpr has no ``cond``."""
    t, k, held, pub, _ = CELL_EXPERTS[cell]
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    stack = lambda key, *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    mlp = {"router": {"kernel": stack(ks[0], 16, pub)},
           "gate": {"kernel": stack(ks[1], held, 16, 8)},
           "up": {"kernel": stack(ks[2], held, 16, 8)},
           "down": {"kernel": stack(ks[3], held, 8, 16)}}
    whole = {n: {"kernel": stack(None, *((pub,) + v["kernel"].shape[1:]))}
             if n != "router" else v for n, v in mlp.items()}
    for tokens, tree in ((t, mlp), (3072, whole)):
        jaxpr = jax.make_jaxpr(lambda h, m: moe.expert_ffn(
            h, m, k, True, jnp.ones((h.shape[0],), bool), interpret=True))(
                jax.ShapeDtypeStruct((tokens, 16), jnp.float32), tree)
        assert _count(jaxpr.jaxpr, "cond") == 0


@pytest.mark.parametrize("kind, tokens, steps, k, held, pub, here, ran", [
    # Nemotron's three-window dispatch: a layer on the lower rung, one a row
    # over it and one far over it on the whole call
    ("prefill", 3072, 1, 22, 128, 512, [17000, 21121, 40000], 21120 + 67584 + 67584),
    # GigaChat's: 6.5 % held, and a layer every assignment lands on
    ("prefill", 3072, 1, 8, 16, 256, [1520, 24576], 1920 + 24576),
    # a decode chunk of four steps: one rung, every row runs
    ("decode", 32, 4, 22, 128, 512, [4 * 170, 4 * 700], 2 * 4 * 704),
    # every expert held: one rung whatever the tokens
    ("prefill", 3072, 1, 8, 64, 64, [24576], 24576),
])
def test_the_loop_counts_the_rows_each_call_ran_from_its_own_counts(
        kind, tokens, steps, k, held, pub, here, ran):
    """``_note_moe_rows`` on counts as they arrive ([L, E], this chip's
    experts first): rows ran = each layer's rung by ITS held count — the
    device's rule — and skipped the rest of ``L x tokens x k x steps``.
    (Rows 2 KB wide: no call takes the DMA kernels, none is counted fused.)"""
    from mlmicroservicetemplate_tpu.utils import metrics

    loop = _counting_loop(f"moe-rows-{kind}-{pub}-{held}", k, held, pub, 1024)
    counts = np.zeros((len(here), pub), np.int64)
    counts[:, 0] = here
    if held != pub:
        counts[:, held] = tokens * k * steps - np.asarray(here)  # the absent
    loop._note_moe_rows(kind, counts, tokens, steps)
    total = len(here) * tokens * k * steps
    assert loop.moe_rows == {kind: [ran, total - ran]}
    name = loop.engine.bundle.name
    assert metrics.MOE_ROWS.labels(name, kind, "ran")._value.get() == ran
    assert metrics.MOE_ROWS.labels(name, kind, "skipped")._value.get() == total - ran
    assert loop.moe_rows_fused == {kind: 0}
    assert metrics.MOE_ROWS_FUSED.labels(name, kind)._value.get() == 0


def _counting_loop(name, k, held, pub, width, latent=0):
    """A loop that is only what ``_note_moe_rows`` reads: a bfloat16 tree of
    ``held`` of ``pub`` experts, rows ``latent or width`` wide."""
    from mlmicroservicetemplate_tpu.engine.streams import ContinuousDecodeLoop

    loop = ContinuousDecodeLoop.__new__(ContinuousDecodeLoop)
    bcfg = type("C", (), {"experts_per_token": k, "num_experts": pub,
                          "d_model": width, "moe_latent": latent})
    policy = type("P", (), {"compute_jnp": jnp.dtype(jnp.bfloat16)})
    loop.engine = type("E", (), {"bundle": type(
        "B", (), {"name": name, "cfg": bcfg, "policy": policy})})()
    loop._experts_held, loop.moe_rows, loop.moe_rows_fused = (0, held), {}, {}
    return loop


@pytest.mark.parametrize("kind,tokens,steps,k,held,pub,width,latent,here,fused", [
    # Granite's three-window dispatch, 8 KB rows: the held rows themselves
    ("prefill", 3072, 1, 10, 36, 72, 4096, 0, [15000, 19300], 15000 + 19300),
    # its decode chunk of four 32-row steps: XLA's form, nothing counted
    ("decode", 32, 4, 10, 36, 72, 4096, 0, [4 * 150, 4 * 170], 0),
    # GigaChat's three-window dispatch (14 KB rows); its lone window's
    # 8192 rows and DeepSeek-V2's 12 288 (10 KB) are too few for the rule
    ("prefill", 3072, 1, 8, 16, 256, 7168, 0, [1520, 24576], 1520 + 24576),
    ("prefill", 1024, 1, 8, 16, 256, 7168, 0, [500], 0),
    ("prefill", 2048, 1, 6, 40, 160, 5120, 0, [3000], 0),
    # Nemotron's latent rows (2 KB) and OLMoE's (4 KB) stay with XLA
    ("prefill", 3072, 1, 22, 128, 512, 4096, 1024, [16000], 0),
    ("prefill", 3072, 1, 8, 64, 64, 2048, 0, [24576], 0),
])
def test_the_loop_counts_the_held_rows_of_the_calls_that_took_the_kernels(
        kind, tokens, steps, k, held, pub, width, latent, here, fused):
    """``moe_rows_fused_total``: the held assignment rows of the calls the
    shape rule (``row_kernels_fit``, as the traced program read it) gave
    the two DMA kernels — a prompt dispatch of wide rows — and nothing for
    a decode chunk or for rows the rule leaves to XLA; ``moe_rows_total``
    counts the rungs as it did."""
    from mlmicroservicetemplate_tpu.utils import metrics

    name = f"moe-fused-{kind}-{tokens}-{pub}-{width}-{latent}"
    loop = _counting_loop(name, k, held, pub, width, latent)
    counts = np.zeros((len(here), pub), np.int64)
    counts[:, 0] = here
    if held != pub:
        counts[:, held] = tokens * k * steps - np.asarray(here)  # the absent
    loop._note_moe_rows(kind, counts, tokens, steps)
    assert loop.moe_rows_fused == {kind: fused}
    assert metrics.MOE_ROWS_FUSED.labels(name, kind)._value.get() == fused
    assert sum(loop.moe_rows[kind]) == len(here) * tokens * k * steps
    assert bool(fused) == moe.row_kernels_fit(tokens * k, latent or width, jnp.bfloat16)


# ---------------------------------------------------------------------------
# the two row shuffles as DMA kernels (``sorted_rows``, ``combine_rows``)
#
# TOLERANCE of the combine: product and sum in float32 in slot order, ONE
# rounding to the rows' dtype.  Against numpy's float32 loop in that order
# the kernel is within one float32 ulp of the running sum a step BEFORE the
# rounding (the interpreter's XLA may fuse a multiply into its add, which
# rounds once where numpy rounds twice): float32 rows agree to 1e-6 of the
# terms' size, bfloat16 outputs to the last bit except where that ulp
# straddles a rounding boundary — at most one bfloat16 ulp, in under 1 % of
# the elements.  A combine that rounded each product, or summed in
# bfloat16, misses by several ulps in most elements (shown below).

#: Decode-step tokens, prompt-dispatch tokens, k, width the routed experts
#: see, and whether the dispatch takes the kernels (the chip's table,
#: docs/kernel_tuning.md: 16 384 rows and more of 8 KB and more win, 4 KB
#: and 2 KB rows lose).
CELL_ROWS = {
    "olmoe": (64, 8192, 8, 2048, False),
    "trinity": (32, 3072, 8, 2048, False),
    "deepseek-v2": (32, 2048, 6, 5120, False),  # 12 288 rows: too few
    "nemotron": (32, 3072, 22, 1024, False),
    "gigachat": (32, 3072, 8, 7168, True),
    "granite": (32, 3072, 10, 4096, True),
}


@pytest.mark.parametrize("call", ["step", "dispatch"])
@pytest.mark.parametrize("cell", sorted(CELL_ROWS))
def test_the_rule_leaves_every_decode_step_to_xla(cell, call):
    """``row_kernels_fit`` at the shapes the six expert cells run in
    bfloat16: no decode step's few hundred rows fit; a prompt dispatch fits
    where it has 16 384 rows of 8 KB or more."""
    step, prompt, k, width, fits = CELL_ROWS[cell]
    if call == "step":
        assert not moe.row_kernels_fit(step * k, width, jnp.bfloat16)
        assert not moe.row_kernels_fit(4 * step * k, width, jnp.bfloat16)
    else:
        assert moe.row_kernels_fit(prompt * k, width, jnp.bfloat16) == fits
        assert not moe.row_kernels_fit(prompt * k + 1, width, jnp.bfloat16)  # row tiles
        assert not moe.row_kernels_fit(prompt * k, width + 64, jnp.bfloat16)  # lanes
        assert not moe.row_kernels_fit(16256, width, jnp.bfloat16)  # 127 tiles
        assert moe.row_kernels_fit(16384, width, jnp.bfloat16) == (width >= 4096)


def _slot_order_sum(ys, pos, w, n_live):
    """numpy: ``sum_j w[t, j] * ys[pos[t, j]]`` over ``pos < n_live`` in
    float32, slot by slot."""
    pos, w = np.asarray(pos), np.asarray(w, np.float32)
    back = np.where((pos < n_live)[:, :, None],
                    np.nan_to_num(np.asarray(ys, np.float32))[pos], 0)
    acc = np.zeros((pos.shape[0], ys.shape[1]), np.float32)
    for j in range(pos.shape[1]):
        acc = acc + back[:, j] * w[:, j, None]
    return acc


def _ulps_off(got, want):
    """``got`` against float32 ``want`` rounded once to got's dtype, in
    ulps of that dtype (bit patterns apart)."""
    bits = {2: np.int16, 4: np.int32}[got.dtype.itemsize]
    once = jnp.asarray(want).astype(got.dtype)
    return np.abs(np.asarray(jax.lax.bitcast_convert_type(got, bits), np.int64)
                  - np.asarray(jax.lax.bitcast_convert_type(once, bits), np.int64))


ROWS_M, ROWS_T, ROWS_K = 384, 96, 3  # sorted rows, tokens (1.5 tiles), slots


@pytest.mark.parametrize("n_live", [0, 1, 128, 200, 384])
@pytest.mark.parametrize("dtype,d", [
    ("float32", 128), ("float32", 1152), ("bfloat16", 256), ("bfloat16", 2560)])
def test_rows_into_expert_order_by_dma_over_the_live_tiles(dtype, d, n_live):
    """``sorted_rows``: every row below the live count, rounded up to its
    row tile, is its source row bit for bit — none, one, a whole tile, a
    tile and a part, every row; slabs of whole tiles and padded ones."""
    rng = np.random.default_rng(d + n_live)
    rows = jnp.asarray(rng.standard_normal((ROWS_T, d)), dtype)
    src = jnp.asarray(rng.integers(0, ROWS_T, ROWS_M), jnp.int32)
    xs = moe.sorted_rows(rows, src, jnp.int32(n_live), interpret=True)
    assert xs.shape == (ROWS_M, d) and xs.dtype == rows.dtype
    up = -(-n_live // moe.ROW_TILE) * moe.ROW_TILE
    np.testing.assert_array_equal(
        np.asarray(xs[:up], np.float32), np.asarray(rows[src[:up]], np.float32))


@pytest.mark.parametrize("n_live", [0, 1, 128, 200, 384])
@pytest.mark.parametrize("dtype,d", [
    ("float32", 128), ("float32", 1152), ("bfloat16", 256), ("bfloat16", 2560)])
def test_a_tokens_rows_back_summed_once_with_the_dead_rows_poisoned(
        dtype, d, n_live):
    """``combine_rows`` with NaN in every row of ``ys`` at or past the live
    count: an assignment there adds exactly zero (a select, never a
    product), no NaN comes out, and the live ones sum in float32 in slot
    order with one rounding (the tolerance above)."""
    rng = np.random.default_rng(d + n_live)
    ys = jnp.asarray(rng.standard_normal((ROWS_M, d)), dtype)
    ys = ys.at[n_live:].set(jnp.nan)
    pos = jnp.asarray(rng.permutation(ROWS_M)[:ROWS_T * ROWS_K].reshape(
        ROWS_T, ROWS_K), jnp.int32)
    w = jnp.asarray(rng.random((ROWS_T, ROWS_K)), jnp.float32)
    out = moe.combine_rows(ys, pos, w, jnp.int32(n_live), interpret=True)
    assert out.shape == (ROWS_T, d) and out.dtype == ys.dtype
    assert bool(jnp.isfinite(out).all())
    want = _slot_order_sum(ys, pos, w, n_live)
    dead = np.all(np.asarray(pos) >= n_live, axis=1)
    assert dead.any() or n_live == ROWS_M
    np.testing.assert_array_equal(np.asarray(out, np.float32)[dead], 0.0)
    if dtype == "float32":
        np.testing.assert_allclose(np.asarray(out), want, rtol=0, atol=1e-6)
    else:
        off = _ulps_off(out, want)
        assert off.max() <= 1 and (off > 0).mean() < 0.01


def test_a_combine_that_rounds_more_than_once_shows():
    """What the tolerance above separates: the same rows with each product
    rounded to bfloat16 before the sum are several ulps off in most
    elements, so a kernel held to one ulp in 1 % multiplies and sums in
    float32."""
    rng = np.random.default_rng(5)
    ys = jnp.asarray(rng.standard_normal((ROWS_M, 256)), jnp.bfloat16)
    pos = jnp.asarray(rng.permutation(ROWS_M)[:ROWS_T * ROWS_K].reshape(
        ROWS_T, ROWS_K), jnp.int32)
    w = jnp.asarray(rng.random((ROWS_T, ROWS_K)), jnp.float32)
    want = _slot_order_sum(ys, pos, w, ROWS_M)
    each = sum((ys[pos[:, j]].astype(jnp.float32) * w[:, j, None]).astype(
        jnp.bfloat16) for j in range(ROWS_K))
    off = _ulps_off(each, want)
    assert (off > 0).mean() > 0.2
    got = moe.combine_rows(ys, pos, w, jnp.int32(ROWS_M), interpret=True)
    assert (_ulps_off(got, want) > 0).mean() < 0.01


def _block_fns(k, **kw):
    """``expert_ffn`` jitted with the two kernels and with XLA's shuffles
    (the rule forced each way at trace time)."""
    def build(fit):
        def run(h, mlp, valid):
            keep = moe.row_kernels_fit
            moe.row_kernels_fit = lambda *a: fit
            try:
                return moe.expert_ffn(h, mlp, k, False, valid, interpret=True, **kw)
            finally:
                moe.row_kernels_fit = keep
        return jax.jit(run)
    return build(True), build(False)


@pytest.fixture(scope="module")
def share_fns():
    return _block_fns(K_LADDER)


@pytest.mark.parametrize("held", [0, 1, 128, 200, 256, 511, 512])
def test_the_kernels_are_xlas_shuffles_on_a_held_share(share_fns, held):
    """A tree that holds 2 of 8 experts, 256 tokens x top-2 at 128 lanes:
    the held count at none, one, a row tile, off a tile, and every
    assignment — the block with the two DMA kernels is XLA's form to the
    float32 ulp (a multiply may fuse into its add), the plain reference's
    within rounding, the counts identical; absent experts add zero."""
    kernels, xla = share_fns
    mlp = _share(jax.random.PRNGKey(11), d=128)
    h = _share_tokens(jax.random.PRNGKey(held), 256, held, d=128)
    valid = jnp.ones((256,), bool)
    out, counts = kernels(h, mlp, valid)
    want, want_counts = xla(h, mlp, valid)
    assert int(counts[:2].sum()) == held
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(want_counts))
    assert bool(jnp.isfinite(out).all()) and _close(out, want) < 1e-5
    assert _close(out, _share_reference(h, mlp, K_LADDER)) < 1e-4
    if held == 0:
        np.testing.assert_array_equal(np.asarray(out), 0.0)


def _whole_tree(key, d, w, e, act="silu", latent=0):
    ks = jax.random.split(key, 6)
    wide = latent or d
    mlp = {"router": {"kernel": jax.random.normal(ks[0], (d, e))},
           "up": {"kernel": jax.random.normal(ks[1], (e, wide, w)) * 0.2},
           "down": {"kernel": jax.random.normal(ks[2], (e, w, wide)) * 0.2}}
    if act == "silu":
        mlp["gate"] = {"kernel": jax.random.normal(ks[3], (e, wide, w)) * 0.2}
    if latent:
        mlp["latent_down"] = {"kernel": jax.random.normal(ks[4], (d, latent)) * 0.2}
        mlp["latent_up"] = {"kernel": jax.random.normal(ks[5], (latent, d)) * 0.2}
    return mlp


@pytest.mark.parametrize("case", [
    "whole-tree", "one-expert", "invalid-rows", "latent-relu2", "bfloat16"])
def test_the_kernels_are_xlas_shuffles_on_a_whole_tree(case):
    """Every expert held (XLA's ``n == n_all`` combine): the router as it
    falls; every token to ONE expert (one group holds every row); a third
    of the rows invalid (they add exactly zero and come out zero); the
    latent pair around a non-gated ``relu2`` expert (128-lane latent rows
    of a 64-wide model); bfloat16 rows (packed slabs)."""
    act, latent, d = ("relu2", 128, 64) if case == "latent-relu2" else ("silu", 0, 128)
    mlp = _whole_tree(jax.random.PRNGKey(3), d, 16, 4, act, latent)
    h = jax.random.normal(jax.random.PRNGKey(4), (128, d))
    valid = jnp.ones((128,), bool)
    k = 2
    if case == "one-expert":
        k = 1
        mlp["router"]["kernel"] = jnp.zeros((d, 4)).at[:, 2].set(1.0)
        h = jnp.abs(h)
    if case == "invalid-rows":
        valid = jnp.arange(128) % 3 != 0
    if case == "bfloat16":
        d = 256
        mlp = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                           _whole_tree(jax.random.PRNGKey(3), d, 16, 4))
        mlp["router"]["kernel"] = mlp["router"]["kernel"].astype(jnp.float32)
        h = jax.random.normal(jax.random.PRNGKey(4), (128, d)).astype(jnp.bfloat16)
    kernels, xla = _block_fns(k, act=act)
    out, counts = kernels(h, mlp, valid)
    want, want_counts = xla(h, mlp, valid)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(want_counts))
    assert int(counts.sum()) == int(valid.sum()) * k
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())
    if case == "one-expert":
        assert counts.tolist() == [0, 0, 128, 0]
    if case == "invalid-rows":
        np.testing.assert_array_equal(np.asarray(out)[~np.asarray(valid)], 0.0)
    if case == "bfloat16":  # one rounding each: a bfloat16 ulp where the
        # float32 sums straddle a boundary (XLA sums token-major here)
        off = _ulps_off(out, want.astype(jnp.float32))
        assert off.max() <= 1 and (off > 0).mean() < 0.02
    else:  # float32 ulps of the largest output (the latent case's are ~40)
        assert _close(out, want) < 2e-6 * max(1.0, float(jnp.max(jnp.abs(want))))


def test_a_call_the_kernels_take_traces_no_conditional_for_its_shuffles(monkeypatch):
    """A share's prompt-sized call under the rule: the gather and the
    combine are kernels outside any branch (their trip count is the held
    count itself), the activation alone keeps the ladder's conditional."""
    from helpers import expert_row_kernels_at_toy_size

    expert_row_kernels_at_toy_size(monkeypatch)
    monkeypatch.setattr(moe, "LADDER_MIN_SKIP", 128)
    mlp = _share(jax.random.PRNGKey(0), d=128)
    assert moe.row_kernels_fit(T_LADDER * K_LADDER, 128, jnp.float32)
    jaxpr = jax.make_jaxpr(lambda h: moe.expert_ffn(
        h, mlp, K_LADDER, False, jnp.ones((T_LADDER,), bool),
        interpret=True))(jnp.zeros((T_LADDER, 128)))
    assert _count(jaxpr.jaxpr, "cond") == 1
    # three grouped matmuls, the row gather, the slabs and the combine
    assert _count(jaxpr.jaxpr, "pallas_call") == 6
