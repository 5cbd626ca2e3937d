"""The SambaY block through ``models/llama.py`` — a self-decoder of Mamba-1
(no inner norm) and window-attention layers ending in ONE full-attention
layer, a cross-decoder of Gated Memory Units (``layer_types`` "gmu": they
gate the last Mamba layer's scan output of the same step) and cross-attention
layers ("cross": a query and an output projection, the full layer's pool),
differential attention (``attention`` "diff"), LayerNorm with bias, attention
biases, no rotation, the head tied to the embedding — held to the benchmark's
plain reference (``cellbench/references/phi4flash.py``) at a toy size on the
CPU in float32: the published pattern in small (3 Mamba, 2 window, 1 full, 2
gmu, 2 cross), hidden 64, 8 / 4 heads of 8 in pairs, window 6, a ring of 24.

TOL: model and reference both compute in float32 and differ in the order of
sums only (the chunk loop against a scan over tokens; grouped-query attention
over lane-placed queries against four plain attentions): measured 3e-7 on
logits; the broken rules of ``tools/phi4flash_variants.py`` move the logits'
rms by 9e-5 (bfloat16 scores) to 0.07.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import spec as bench_spec
from mlmicroservicetemplate_tpu.models import llama as llama_mod
from mlmicroservicetemplate_tpu.models.gpt import PagedState
from mlmicroservicetemplate_tpu.models.sampling import greedy_params
from tools import nemotron_variants, phi4flash_variants

TOL = 2e-6
BS, C, T_W, R = 4, 8, 16, 3  # block, prompt window, table width, state rows
NB = 2 * T_W + 1


@pytest.fixture(scope="module")
def config():
    real = bench_spec.load_json(bench_spec.HERE + "/configs/phi4-mini-flash-d32.json")
    toy = bench_spec.load_json(
        bench_spec.HERE + "/tests/rehearse_phi4flash.json")["config"]
    toy = {k: v for k, v in toy.items() if k not in ("env", "expect_cfg", "prompt")}
    return {**real, **toy, "vocab_size": 128}


@pytest.fixture(scope="module")
def ref():
    return bench_spec.load_module(
        bench_spec.HERE + "/references/phi4flash.py", "cellbench_reference_phi4flash")


@pytest.fixture(scope="module")
def kw(config):
    out = json.loads(bench_spec.service_env(config)["LLAMA_CONFIG"])
    return {**out, "eos_id": 1, "pad_id": 0, "pallas_interpret": True}


@pytest.fixture(scope="module")
def cfg(kw):
    return llama_mod.LlamaConfig(**kw)


@pytest.fixture(scope="module")
def params(cfg):
    return llama_mod.init_params(jax.random.PRNGKey(0), cfg)


def _ids(n, seed=0, vocab=120):
    return np.random.default_rng(seed).integers(3, vocab, n).astype(np.int32)


def _close(got, want):
    return float(jnp.max(jnp.abs(jnp.asarray(got) - jnp.asarray(want))))


# ---------------------------------------------------------------------------
# (i) what the configuration says of each layer


def test_the_toy_is_the_published_pattern_in_small(cfg, params):
    kinds = [cfg.layer_kind(li) for li in range(cfg.num_layers)]
    assert [k.mixer for k in kinds] == [
        "mamba1", "diff", "mamba1", "diff", "mamba1", "diff", "gmu", "diff", "gmu", "diff"]
    assert [k.store for k in kinds] == [
        "", "ring", "", "ring", "", "table", "", "shared", "", "shared"]
    assert all(k.ffn and not k.experts and not k.rope for k in kinds)
    assert (cfg.cross_from, cfg.memory_layer, cfg.kv_layer) == (6, 4, 5)
    assert cfg.cache_layers == (5,) and cfg.ring_layers == (1, 3)
    assert cfg.own_layers == (1, 3, 5) and cfg.pool_entries == (2,)
    assert cfg.layer_counts == {"mamba": 3, "window": 2, "full": 1, "gmu": 2, "cross": 2}
    # differential pairs: 4 KV heads of 8 are 2 pairs of 16, 4 query heads a pair
    assert (cfg.kv_groups, cfg.n_rep, cfg.kv_tail, cfg.gqa_scale) == (2, 4, (2, 16), 8 ** -0.5)
    assert cfg.ssm_row_bytes == 3 * (4 * 128 * 4 + 3 * 128 * 2)
    assert cfg.window_row_bytes == 2 * 24 * 2 * 4 * 8 * 2 and cfg.state_rows
    assert sorted(params) == ["embed", "final_ln", "layers"]  # tied: no lm_head
    assert sorted(params["final_ln"]) == ["bias", "scale"]
    assert sorted(params["layers"][0]) == ["mlp", "mlp_ln", "ssm", "ssm_ln"]
    assert "dt_norm" not in params["layers"][0]["ssm"]  # no inner norm
    assert sorted(params["layers"][1]["attn"]) == ["k", "lambda", "o", "q", "subln", "v"]
    assert sorted(params["layers"][7]["attn"]) == ["lambda", "o", "q", "subln"]  # cross
    assert sorted(params["layers"][6]) == ["gmu", "gmu_ln", "mlp", "mlp_ln"]
    assert params["layers"][1]["attn"]["q"]["bias"].shape == (64,)
    assert params["layers"][6]["gmu"]["in"]["kernel"].shape == (64, 128)
    z = llama_mod.zero_ssm(cfg, 3, jnp.float32)
    assert [r.shape for r in z.ring_k] == [(3, 24, 32)] * 2 == [r.shape for r in z.ring_v]
    assert [s.shape for s in z.state] == [(3, 4, 128)] * 3
    assert sorted(z.leaves) == ["conv", "ring_k", "ring_v", "state"]


def test_the_published_configuration_is_whole():
    """All 32 layers by kind, the pool's one layer, the bytes a stream holds
    beside it: what the cell's ``expect_cfg`` pins, from the file alone."""
    config = bench_spec.load_json(
        bench_spec.HERE + "/configs/phi4-mini-flash-d32.json")
    c = llama_mod.LlamaConfig(**json.loads(
        bench_spec.service_env(config)["LLAMA_CONFIG"]))
    assert config["reduced"] == {} and c.num_layers == 32
    assert c.layer_counts == {"mamba": 9, "window": 8, "full": 1, "gmu": 7, "cross": 7}
    assert (c.memory_layer, c.kv_layer, c.cross_from) == (16, 17, 18)
    assert c.cache_layers == (17,) and len(c.ring_layers) == 8
    assert (c.num_heads, c.num_kv_heads, c.head_dim, c.kv_tail) == (40, 20, 64, (10, 128))
    assert c.ssm_row_bytes == 3_225_600 and c.window_row_bytes == 8 * 1552 * 5120
    assert not any(c.layer_kind(li).rope for li in range(32))
    assert c.tie_embeddings and c.vocab_size == 200_064
    for li, want in config["expect_cfg"].items():
        want = bench_spec.subst(want, config)
        assert getattr(c, li) == want or li == "pallas_decode", li
    # 10.87 GB: weights + ONE pool + the window rings + the state rows
    env = config["env"]
    streams = int(env["MAX_STREAMS"])
    pool = streams * (int(env["PREFILL_MAX_PROMPT"]) + int(env["MAX_DECODE_LEN"])) * 5120
    assert abs(int(env["KV_BUDGET_MB"]) * 1e6 - pool) < 1e6
    held = pool + streams * (c.window_row_bytes + c.ssm_row_bytes)
    assert round((held + 3852.6e6 * 2) / 1e9, 2) == 10.87


@pytest.mark.parametrize("bad,needle", [
    ({"layer_types": ["mamba", "window", "cross", "gmu"] + ["cross"] * 6},
     "a 'cross' layer needs a 'full' layer"),
    ({"layer_types": ["full", "window", "gmu", "cross"] + ["cross"] * 6,
      "ssm_dt_rank": 0, "ssm_heads": 0, "ssm_head_dim": 0, "ssm_groups": 0,
      "ssm_state": 0}, "a 'gmu' layer a\\s+'mamba' layer"),
    ({"layer_types": ["mamba", "window", "mamba", "full", "gmu", "cross",
                      "window", "cross", "gmu", "cross"]},
     "every layer\\s+from the first of them on is 'cross' or 'gmu'"),
    ({"window_ring": 4}, "window_ring=4 needs window layers and holds"),
    ({"num_kv_heads": 1}, "attention='diff' pairs heads"),
    ({"norm": "batch"}, "norm='batch'"),
])
def test_a_config_that_does_not_add_up_is_refused(kw, bad, needle):
    with pytest.raises(ValueError, match=needle):
        llama_mod.LlamaConfig(**{**kw, **bad})


# ---------------------------------------------------------------------------
# (ii) a layer, the wave forward


@pytest.mark.parametrize("li", [0, 1, 5, 6, 7],
                         ids=["mamba", "window", "full", "gmu", "cross"])
def test_a_layer_is_the_reference(ref, config, cfg, params, li):
    """One layer of each kind on random rows: its mixer (the chunk loop from
    zeros | the lane-placed pairs under the window's band or the causal mask |
    the gate on a handed memory | a query over handed keys) and MLP."""
    hp = ref.hyper(config)
    s = 33
    x = jax.random.normal(jax.random.PRNGKey(5 + li), (1, s, 64)) * 0.5
    m = jax.random.normal(jax.random.PRNGKey(50), (1, s, 128))
    kv = (jax.random.normal(jax.random.PRNGKey(51), (1, s, 4, 8)),
          jax.random.normal(jax.random.PRNGKey(52), (1, s, 4, 8)))
    kind, layer = hp["kinds"][li], params["layers"][li]
    want, _, left = ref.layer(
        x[0], ref.layer_weights(layer, kind), hp, kind, "",
        jnp.float32(ref.lambda_init(li)), {"m": m[0], "kv": (kv[0][0], kv[1][0])})
    mask = jnp.ones((1, s), jnp.int32)
    if kind == "mamba":
        z = llama_mod.zero_ssm(cfg, 1, jnp.float32)
        got, _, st = llama_mod._mamba1_block(cfg, layer, x, z.conv[0], z.state[0],
                                             mask=mask)
        assert _close(st[0], left[0]) < TOL
    elif kind == "gmu":
        got = llama_mod._gmu_block(cfg, layer, x, m)
    else:
        q, k, v, g = llama_mod._qkv_rope(cfg, layer, None, li, x, None, None)
        if kind == "cross":
            assert k is None and v is None  # a query alone
            k, v = (llama_mod._split(llama_mod.merge_heads(a), 2) for a in kv)
        causal = jnp.tril(jnp.ones((s, s), bool))
        if kind == "window":
            causal &= llama_mod._band(jnp.arange(s), jnp.arange(s), cfg.window)
        ctx = llama_mod.mha_attention(
            q, llama_mod._repeat_kv(k, 4), llama_mod._repeat_kv(v, 4),
            mask=causal[None, None], scale=cfg.gqa_scale)
        got = llama_mod._attn_out(cfg, layer, None, li, x, ctx, g)
    got = llama_mod._mlp_block(cfg, layer, li, got, mask != 0)
    assert _close(got[0], want) < TOL


def test_the_wave_forward_is_the_reference(ref, config, cfg, params):
    ids = _ids(90, 1).reshape(2, 45)
    got = jax.jit(lambda p: llama_mod.lm_logits(p, cfg, ids, np.ones_like(ids)))(params)
    assert _close(got, ref.logits(params, ref.hyper(config), ids)) < TOL


# ---------------------------------------------------------------------------
# (iii) windows into the pool and the rings, then decode: every step kind


def _paged(cfg, slots=2):
    z = llama_mod.zero_ssm(cfg, R, jnp.float32) or llama_mod.SsmState([], [], None)
    # every row POISONED: a first window must start from zeros, and a ring's
    # stale keys must never be read
    z = z._replace(conv=[c + 7.0 for c in z.conv], state=[s + 3.0 for s in z.state],
                   ring_k=[r + 5.0 for r in z.ring_k], ring_v=[r - 5.0 for r in z.ring_v],
                   row=jnp.full((slots,), R, jnp.int32))
    width = cfg.num_kv_heads * cfg.head_dim
    n = len(cfg.cache_layers)
    return PagedState(
        cache_k=[jnp.zeros((NB, BS, width)) for _ in range(n)],
        cache_v=[jnp.zeros((NB, BS, width)) for _ in range(n)],
        key_valid=jnp.zeros((slots, T_W * BS), jnp.int32),
        write_idx=jnp.zeros((slots,), jnp.int32), pos=jnp.zeros((slots,), jnp.int32),
        last_token=jnp.zeros((slots,), jnp.int32), done=jnp.ones((slots,), bool),
        tokens=jnp.zeros((slots, 8), jnp.int32), sample=greedy_params(slots), ssm=z)


def _table(first):
    return np.arange(first, first + T_W, dtype=np.int32)


def _prefill(params, cfg, state, ids, row, table_row):
    """``ids`` in windows of C into state row ``row`` (a dispatch of two rows:
    the second filled up)."""
    n = len(ids)
    for start in range(0, n, C):
        end = min(start + C, n)
        iw, mw = np.zeros((2, C), np.int32), np.zeros((2, C), np.int32)
        iw[0, :end - start], mw[0, :end - start] = ids[start:end], 1
        tabs = np.full((2, T_W), NB, np.int32)
        tabs[0] = table_row
        rows = np.array([[row, end - start - (end == n)], [R, 0]], np.int32)
        state = llama_mod.paged_prefill_chunk(
            params, cfg, state, jnp.asarray(tabs), iw, mw,
            np.array([start, 0], np.int32), ssm_rows=jnp.asarray(rows))
    return state


def _go_live(state, slot, row, ids, table_row):
    n = len(ids)
    kv = np.zeros((T_W * BS,), np.int32)
    kv[:n - 1] = 1
    state = state._replace(
        key_valid=state.key_valid.at[slot].set(kv),
        write_idx=state.write_idx.at[slot].set(n - 1),
        last_token=state.last_token.at[slot].set(int(ids[-1])),
        done=state.done.at[slot].set(False),
        ssm=state.ssm._replace(row=state.ssm.row.at[slot].set(row)))
    table = np.full((state.done.shape[0], T_W), NB, np.int32)
    table[slot] = table_row
    return state, jnp.asarray(table)


def _decode_logits(monkeypatch, params, cfg, state, table, slot, forced):
    """Teacher-forced paged decode steps -> (each step's logits for ``slot``,
    the state they leave)."""
    seen = []
    head = llama_mod._head_logits
    monkeypatch.setattr(llama_mod, "_head_logits",
                        lambda p, c, x: seen.append(head(p, c, x)) or seen[-1])
    for tok in forced:
        state, _ = llama_mod._paged_decode_step(params, cfg, state, table)
        state = state._replace(last_token=state.last_token.at[slot].set(int(tok)))
    monkeypatch.setattr(llama_mod, "_head_logits", head)
    return [s[slot] for s in seen], state


@pytest.mark.parametrize("ring,kernels", [(0, False), (24, False), (24, True)],
                         ids=["table", "ring", "ring-kernels"])
def test_windows_then_decode_are_the_references_full_forward(
        monkeypatch, ref, config, cfg, params, ring, kernels):
    """A prompt of 29 tokens in windows of 8 into a POISONED row — the
    self-decoder alone on every position — then 11 decode steps through the
    one-token update, the window store (every block kept under the table | a
    ring of 24 that wraps: the context passes 1.6 rings) and the ONE pool,
    every layer on one position: the logits of every decoded position are the
    reference's, which runs every layer at EVERY position, and the state rows
    are its token scan's.  With the kernels on, the prompt-window and paged
    decode kernels (interpret mode) over the lane-placed pairs."""
    run = dataclasses.replace(cfg, window_ring=ring, pallas_decode=kernels)
    ids, n = _ids(40, 7), 29
    state = _prefill(params, run, _paged(run), ids[:n], 1, _table(3))
    state, table = _go_live(state, 1, 1, ids[:n], _table(3))
    got, state = _decode_logits(monkeypatch, params, run, state, table, 1, ids[n:])
    states: list = []
    want = ref.head_logits(params, ref.hidden(
        params, ref.hyper(config), ids[None, :-1], states=states))[0]
    for j, row in enumerate(got):
        assert _close(row, want[n - 1 + j]) < TOL, j
    for li in range(3):
        assert _close(state.ssm.state[li][1], states[li][0][0]) < TOL
        assert _close(state.ssm.state[li][0], 3.0) == 0.0  # no other row moved
    if ring:
        assert [r.shape for r in state.ssm.ring_k] == [(R, ring, 32)] * 2
        assert _close(state.ssm.ring_k[0][0], 5.0) == 0.0  # nor another's ring


def test_a_prompt_window_runs_the_self_decoder_alone(monkeypatch, cfg, params):
    """A window dispatch runs 6 of the toy's 10 layers (``cfg.cross_from``:
    the cross-decoder leaves nothing behind and a window reads no logit), a
    wave's prefill the same; the decode step and the full-logit forward run
    all 10 — on the ONE position whose logit is read, and on every one."""
    ran = []
    mlp = llama_mod._mlp_block
    monkeypatch.setattr(llama_mod, "_mlp_block",
                        lambda c, layer, li, x, *a: ran.append((li, x.shape[1])) or mlp(
                            c, layer, li, x, *a))
    ids = _ids(20, 3)
    state = _prefill(params, cfg, _paged(cfg), ids, 0, _table(3))
    assert [li for li, _ in ran] == list(range(6)) * 3 and {w for _, w in ran} == {C}
    ran.clear()
    llama_mod.forward_hidden(params, cfg, ids[None], np.ones((1, 20), np.int32),
                             collect_kv=True)
    assert [li for li, _ in ran] == list(range(6))
    ran.clear()
    state, table = _go_live(state, 0, 0, ids, _table(3))
    llama_mod._paged_decode_step(params, cfg, state, table)
    assert ran == [(li, 1) for li in range(10)]
    ran.clear()
    llama_mod.lm_logits(params, cfg, ids[None], np.ones((1, 20), np.int32))
    assert ran == [(li, 20) for li in range(10)]


def test_the_split_prompt_path_gives_the_all_positions_logits(
        monkeypatch, cfg, params):
    """Last-position logits: windows (self-decoder) + the first decode step
    (all layers, one position) against the program's own all-positions
    forward."""
    ids = _ids(27, 9)
    state = _prefill(params, cfg, _paged(cfg), ids, 2, _table(5))
    state, table = _go_live(state, 0, 2, ids, _table(5))
    got, _ = _decode_logits(monkeypatch, params, cfg, state, table, 0, [0])
    want = llama_mod.lm_logits(params, cfg, ids[None], np.ones((1, 27), np.int32))
    assert _close(got[0], want[0, -1]) < TOL


def test_a_cross_layer_reads_the_kv_layers_pool_after_this_steps_write(
        monkeypatch, cfg, params):
    """In a decode step the two cross layers are handed the SAME pool arrays
    the full layer's write of this step produced — the new key already in
    them — and no pool, ring or table of their own."""
    ids = _ids(13, 4)
    state = _prefill(params, cfg, _paged(cfg), ids, 1, _table(3))
    state, table = _go_live(state, 0, 1, ids, _table(3))
    state, _ = llama_mod._paged_decode_step(params, cfg, state, table)  # key 12
    seen = []
    attend = llama_mod._paged_cache_attention
    monkeypatch.setattr(
        llama_mod, "_paged_cache_attention",
        lambda c, q, ck, cv, tbl, valid, bs: seen.append((ck, cv, tbl)) or attend(
            c, q, ck, cv, tbl, valid, bs))
    after, _ = llama_mod._paged_decode_step(params, cfg, state, table)
    assert len(seen) == 5  # window, window, full, cross, cross
    full_k, full_v, full_tbl = seen[2]
    for ck, cv, tbl in seen[3:]:
        assert ck is full_k and cv is full_v and tbl is full_tbl
    assert full_k is after.cache_k[0] and len(after.cache_k) == 1
    # position 13 (block 3 of the stream's table, offset 1) held a pad row of
    # the prompt's last window before this step's write; nothing else moved
    blk = int(table[0, 13 // BS])
    moved = np.abs(np.asarray(full_k) - np.asarray(state.cache_k[0])).max(axis=-1) > 0
    assert moved[blk, 13 % BS] and int(moved.sum()) == 1
    # the window layers read their rings through a view of ring blocks
    assert seen[0][0].shape == (R * 24 // BS, BS, 32) and seen[0][2].shape[1] <= T_W


def test_the_window_store_stops_growing(cfg):
    """A stream's window keys: ``window_ring`` a ring layer whatever the
    context, against a table's every block; the ring holds a prompt window's
    view (``prefill_key_blocks``) and a decode step's."""
    per_key = 2 * 4 * 8 * 2  # K and V, 4 KV heads of 8, two bytes
    assert cfg.window_row_bytes == 2 * 24 * per_key
    kept_by_a_table = [2 * n * per_key for n in (24, 64, 4096)]
    assert kept_by_a_table[0] == cfg.window_row_bytes < kept_by_a_table[1]
    _, n = llama_mod.prefill_key_blocks(C, T_W, BS, 16, cfg.window)
    assert n * BS <= cfg.window_ring
    blocks = llama_mod.ring_blocks(jnp.asarray([1, 3]), jnp.asarray([4, 9]), 3, 6, 3)
    assert blocks.tolist() == [[10, 11, 6], [21, 22, 23]]  # row 3 of 3: past the pool


# ---------------------------------------------------------------------------
# (iv) the kernels: lane-placed pairs = the four plain attentions


def _four_calls(ref, q, k, v, window=0):
    """q [S, H, D], k and v [S, KVH, D] -> the family's four plain attentions,
    a pair's two halves side by side: [S, H, 2 D]."""
    s, h, d = q.shape
    kvh = k.shape[1]
    qp = q.reshape(s, h // 2, 2, d)
    kp, vp = k.reshape(s, kvh // 2, 2, d), v.reshape(s, kvh // 2, 2, d)
    rep = (h // 2) // (kvh // 2)
    out = []
    for part in (0, 1):
        kk = jnp.repeat(kp[:, :, part], rep, axis=1)
        out.append(jnp.concatenate(
            [ref.attention(qp[:, :, part], kk, jnp.repeat(vp[:, :, half], rep, axis=1),
                           d ** -0.5, window) for half in (0, 1)], axis=-1))
    return jnp.stack(out, axis=2).reshape(s, h, 2 * d)


@pytest.mark.parametrize("window", [0, 6], ids=["full", "window"])
def test_the_prompt_window_kernel_over_placed_pairs_is_the_four_plain_attentions(
        ref, window):
    from mlmicroservicetemplate_tpu.ops.prefill_attention import prefill_attention

    s, h, kvh, d = 16, 8, 4, 8
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (s, n, d))
               for i, n in ((1, h), (2, kvh), (3, kvh)))
    got = prefill_attention(
        llama_mod._diff_place(q), k.reshape(s, kvh // 2, 2 * d),
        v.reshape(s, kvh // 2, 2 * d), 0, 0, jnp.ones((s,), jnp.int32),
        window=window, scale=d ** -0.5, interpret=True)
    assert _close(got, _four_calls(ref, q, k, v, window)) < TOL


def test_the_paged_decode_kernel_over_placed_pairs_is_the_four_plain_attentions(ref):
    """One query a row over a pool read ONCE: a token's [k1 | k2] and
    [v1 | v2] as they lie are the kernel's 2 D-wide KV heads."""
    from mlmicroservicetemplate_tpu.ops.paged_attention import (
        paged_decode_attention, scatter_pages)

    s, h, kvh, d, bs = 13, 8, 4, 8, 4
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (s, n, d))
               for i, n in ((4, h), (5, kvh), (6, kvh)))
    table = jnp.asarray([[5, 2, 7, 1]], jnp.int32)
    pool_k = scatter_pages(jnp.zeros((9, bs, kvh * d)), table[0], k, bs)
    pool_v = scatter_pages(jnp.zeros((9, bs, kvh * d)), table[0], v, bs)
    valid = (jnp.arange(4 * bs) < s).astype(jnp.int32)[None]
    got = paged_decode_attention(
        llama_mod._diff_place(q[-1:]), pool_k, pool_v, table, valid, bs,
        scale=d ** -0.5, interpret=True)
    assert _close(got[0], _four_calls(ref, q, k, v)[-1]) < TOL


# ---------------------------------------------------------------------------
# (v) each rule matters


@pytest.fixture(scope="module")
def sound(ref, config, kw, params):
    ids, states = _ids(40, 2)[None], []
    hp = ref.hyper(config)
    ref.hidden(params, hp, ids[:, :-1], states=states)
    return ids, ref.logits(params, hp, ids)[0], states


@pytest.mark.parametrize("name", sorted(phi4flash_variants.VARIANTS))
def test_each_broken_variant_departs_from_the_reference(sound, kw, params, name):
    """No lambda, lambda's learned part, the sub-norm, a rotation, Jamba's
    inner norms, the memory taken after the gate, a cross layer reading a
    window layer's keys, bfloat16 scores, ``D`` and eight-bit weights each
    matter: the variant's logits leave the reference's by more than the sound
    program's ever do (a bfloat16 state: what the scan LEAVES does)."""
    ids, want, states = sound
    vkw, vparams, patches = nemotron_variants.broken(
        name, kw, params, phi4flash_variants.VARIANTS)
    vcfg = llama_mod.LlamaConfig(**vkw)

    def run(p):
        left = []
        llama_mod.forward_hidden(p, vcfg, ids[:, :-1],
                                 np.ones_like(ids[:, :-1]), ssm_out=left)
        return llama_mod.lm_logits(p, vcfg, ids, np.ones_like(ids))[0], left[0].state[0]

    with nemotron_variants.patched(patches):
        got, state = run(vparams)
    if name == "state_bf16":
        # what a stream's row would hold: the scan over all but the last token
        got, want = state[0], states[0][0][0]
    rms = float(jnp.sqrt(jnp.mean(jnp.square(got - want))))
    assert rms > 2 * TOL


# ---------------------------------------------------------------------------
# (vi) the registry: builds it, refuses what cannot carry it


def _svc(monkeypatch, kw, **knobs):
    from mlmicroservicetemplate_tpu.utils.config import ServiceConfig

    over = {k: v for k, v in kw.items()
            if k not in ("eos_id", "pad_id", "pallas_interpret")}
    over["vocab_size"] = 300
    monkeypatch.setenv("LLAMA_CONFIG", json.dumps(over))
    knobs.setdefault("pallas_interpret", True)
    knobs.setdefault("paged_kv", True)
    knobs.setdefault("kv_block_size", 4)
    knobs.setdefault("prefill_chunk", 8)
    return ServiceConfig(device="cpu", model_name="llama", warmup=False,
                         seq_buckets=(16,), max_decode_len=8, **knobs)


def test_registry_builds_the_configuration(monkeypatch, kw, ref, config):
    from mlmicroservicetemplate_tpu.models.registry import build_model

    bundle = build_model(_svc(monkeypatch, kw))
    c = bundle.cfg
    assert c.layer_types[4:8] == ("mamba", "full", "gmu", "cross")
    assert c.diff and c.norm == "layer" and c.attn_bias and c.window_ring == 24
    assert "lm_head" not in bundle.params
    ids = _ids(20, 9, vocab=290)[None]
    got = jax.jit(bundle.logits_fn)(bundle.params, ids, np.ones_like(ids))
    want = ref.logits(bundle.params, ref.hyper({**config, "vocab_size": 300}), ids)
    assert _close(got, want) < TOL


@pytest.mark.parametrize("knobs,needle", [
    ({"paged_kv": False}, "PAGED_KV=0 is not supported for a llama config with"),
    ({"spec_decode": "ngram"}, "SPEC_DECODE is not supported"),
    ({"quant_kv": "int8"}, "QUANT_KV is not supported"),
    ({"prefix_cache": True}, "PREFIX_CACHE is not supported"),
    ({"prompt_prefix": "w5 w6"}, "PROMPT_PREFIX is not supported"),
    ({"kv_host_budget_mb": 64.0}, "KV_HOST_BUDGET_MB is not supported"),
    ({"kv_host_budget_mb": 0.0, "kv_disk_budget_mb": 64.0, "journal_dir": "/tmp/j"},
     "KV_DISK_BUDGET_MB is not supported|KV_HOST_BUDGET_MB"),
    ({"tp": 2}, "TP=2 is not supported"),
    ({"quantize": "int8"}, "QUANTIZE=int8 is not supported"),
    ({"prefill_chunk": 32}, "window_ring=24 must be a multiple of KV_BLOCK_SIZE=4 and"),
    ({"kv_block_size": 16, "prefill_chunk": 16}, "window_ring=24 must be a multiple"),
])
def test_registry_refuses_what_cannot_carry_it(monkeypatch, kw, knobs, needle):
    from mlmicroservicetemplate_tpu.models.registry import build_model

    with pytest.raises(ValueError, match=needle):
        build_model(_svc(monkeypatch, kw, **knobs))


@pytest.mark.parametrize("what", ["cross_decoder", "ring", "diff"])
def test_each_of_the_three_is_refused_alone(monkeypatch, kw, what):
    """The cross-decoder without rings, rings without a cross-decoder,
    differential attention on a plain stack: each alone still refuses the
    readers that know one pool a layer."""
    from mlmicroservicetemplate_tpu.models.registry import build_model

    plain = {"layer_types": ["mamba", "window", "mamba", "full"], "num_layers": 4}
    over = {"cross_decoder": {"window_ring": 0},
            "ring": {**plain, "attention": "gqa"},
            "diff": {**plain, "window_ring": 0}}[what]
    with pytest.raises(ValueError, match="PREFIX_CACHE is not supported"):
        build_model(_svc(monkeypatch, {**kw, **over}, prefix_cache=True))


# ---------------------------------------------------------------------------
# (vii) the loop: waves and windows, the three stores, the split's counters


def test_the_loop_serves_waves_and_windows_as_the_reference(monkeypatch, kw, ref, config):
    """Short prompts (the wave path: the state AND the window rings inserted
    into a row, the full layer's keys into blocks) and long ones (windows,
    three different prompts a dispatch, contexts that wrap the ring of 24)
    together: every stream's tokens are the plain reference's greedy
    continuation, teacher-forced; rows and blocks go back; the cross-decoder
    ran on exactly ONE position a prompt (the first decode step's) while the
    self-decoder ran on every prompt position; the rings overwrote what a
    table would have kept; ``/status`` has the three stores apart."""
    from mlmicroservicetemplate_tpu.engine import InferenceEngine
    from mlmicroservicetemplate_tpu.engine.streams import ContinuousDecodeLoop
    from mlmicroservicetemplate_tpu.models.registry import build_model
    from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
    from mlmicroservicetemplate_tpu.utils import metrics
    from test_nemotron_serving import _feats, _loop_cfg
    from test_prefill_chunked import _run, _wait_pool_drained

    bundle = build_model(_svc(monkeypatch, kw))
    cfgc = _loop_cfg()
    eng = InferenceEngine(bundle, cfgc, ReplicaSet(make_mesh(1)))
    feats = _feats((7, 30, 45, 30, 12))

    def counts():
        return [m.labels("llama")._value.get() for m in (
            metrics.PREFILL_SELF_POSITIONS, metrics.PREFILL_CROSS_POSITIONS,
            metrics.KV_WINDOW_KEYS_OVERWRITTEN)]

    before = counts()
    cdl = ContinuousDecodeLoop(eng, cfgc)
    try:
        assert len(cdl._ssm_free) == cdl.n_slots == 4 and cdl._ring == (2, 24)
        assert cdl._cross_decoder and cdl._attn_layers == 5
        outs = _run(cdl, feats)
        assert cdl.prefill_chunk_dispatches > 0
        assert _wait_pool_drained(eng.kv_pool) == 0
        assert sorted(cdl._ssm_free) == list(range(4))
        assert metrics.KV_WINDOW_STORE_BYTES.labels("llama")._value.get() == 0
        # ONE pool under the table; the rings and the rows beside it
        state = cdl._state
        assert len(state.cache_k) == 1 and len(state.ssm.ring_k) == 2
        assert state.ssm.ring_k[0].shape == (4, 24, 32)
    finally:
        cdl.stop()
    hp = ref.hyper({**config, "vocab_size": 300})
    for f, toks in zip(feats, outs):
        assert len(toks) == 12
        seq = np.concatenate([f["input_ids"], toks]).astype(np.int32)[None]
        logits = np.asarray(ref.logits(bundle.params, hp, seq))[0]
        n = int(f["length"])
        rows = logits[n - 1: n - 1 + len(toks)]
        assert float((rows.max(axis=-1) - rows[np.arange(len(toks)), toks]).max()) < 1e-5
    self_pos, cross_pos, overwritten = (a - b for a, b in zip(counts(), before))
    # every prompt position through the self-decoder, ONE a prompt through the
    # cross-decoder's 4 layers: 5 of 124
    assert self_pos == sum(int(f["length"]) for f in feats) == 124 and cross_pos == 5
    # positions written at or past 24, a ring layer each: (30 + 12 - 24) x 2
    # prompts, (45 + 12 - 24), none for the short ones' 19 and 24 (a chunk past
    # the answer writes more: at least these)
    assert overwritten >= 2 * (2 * 18 + 33)
    assert eng.stream_fixed_bytes() == bundle.cfg.ssm_row_bytes + bundle.cfg.window_row_bytes
    assert eng.kv_token_bytes() == 2 * 4 * 8 * 4  # ONE layer's K and V, float32 here


def test_a_ring_serves_a_plain_window_model_too(monkeypatch):
    """``window_ring`` is no property of the cross-decoder: rotated
    grouped-query window layers beside a full one (Trinity's kind, no
    recurrent layer, so a state row holds rings alone) decode the same logits
    from rings as from blocks under the table."""
    kw = dict(vocab_size=97, d_model=32, num_heads=4, num_kv_heads=2, num_layers=3,
              d_ff=48, max_position=128, layer_types=["window", "full", "window"],
              window=6, eos_id=1, pad_id=0, pallas_interpret=True)
    table_cfg = llama_mod.LlamaConfig(**kw)
    ring_cfg = llama_mod.LlamaConfig(**kw, window_ring=24)
    assert ring_cfg.state_rows and not ring_cfg.recurrent_layers
    assert ring_cfg.cache_layers == (1,) and table_cfg.cache_layers == (0, 1, 2)
    params = llama_mod.init_params(jax.random.PRNGKey(1), ring_cfg)
    ids, n = _ids(40, 5, vocab=90), 27
    rows = []
    for c in (table_cfg, ring_cfg):
        state = _paged(c)  # (the table's has rows only to steer the helpers)
        state = _prefill(params, c, state, ids[:n], 1, _table(3))
        state, table = _go_live(state, 1, 1, ids[:n], _table(3))
        got, _ = _decode_logits(monkeypatch, params, c, state, table, 1, ids[n:])
        rows.append(got)
    for a, b in zip(*rows):
        assert _close(a, b) < TOL
