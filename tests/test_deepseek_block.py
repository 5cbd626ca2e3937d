"""The DeepSeek-V2 block through ``models/llama.py`` — multi-head latent
attention (q through a low-rank bottleneck with its own norm; K and V
through one latent a token with its own norm plus ONE rotary key every
head shares; YaRN; the cache one latent row a token a layer, no V pool;
prefill expanded, the decode step absorbed through the latent kernel), a
group-limited softmax router, a chip's share of the experts and two
shared experts — held to the benchmark's plain reference
(``cellbench/references/deepseek_v2.py``) at a toy size on the CPU in
float32: 3 layers of which 1 dense, d 64, 4 heads, ranks 24 / 16, head
dims 8 + 8 / 8, 16 experts in 4 groups keep 2 top-3 of which this tree
holds 4-7, 2 shared, YaRN on (original context 16, so the toy's positions
run past it).

TOL: model and reference both compute in float32 and differ in the order
of sums only (absorbed against expanded attention, an online softmax
against a whole one, a grouped matmul against a masked loop): measured
4e-7 on logits of size ~0.7.  Every broken rule of
``tools/deepseek_variants.py`` moves a logit by 20 x TOL or more at this
size (each shown failing below).
"""

import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import spec as bench_spec
from mlmicroservicetemplate_tpu.models import llama as llama_mod
from mlmicroservicetemplate_tpu.ops import moe
from mlmicroservicetemplate_tpu.ops.paged_attention import (
    latent_decode_attention,
    paged_attention_ref,
)
from tools import deepseek_variants

TOL = 2e-4
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 16,
        "type": "yarn"}
# The toy by its PUBLISHED names: laid over the benchmark's configuration
# file, so that the file's own LLAMA_CONFIG mapping is what builds it.
TOY = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
    v_head_dim=8, intermediate_size=96, moe_intermediate_size=32,
    num_hidden_layers=3, first_k_dense_replace=1, router_experts=16,
    n_routed_experts=4, expert_first=4, n_group=4, topk_group=2,
    num_experts_per_tok=3, n_shared_experts=2, vocab_size=128,
    max_position_embeddings=256, rope_scaling=YARN,
)


@pytest.fixture(scope="module")
def config():
    real = bench_spec.load_json(
        bench_spec.HERE + "/configs/deepseek-v2-ep4-d5.json")
    return {**real, **TOY}


@pytest.fixture(scope="module")
def ref():
    return bench_spec.load_module(
        bench_spec.HERE + "/references/deepseek_v2.py",
        "cellbench_reference_deepseek_v2")


@pytest.fixture(scope="module")
def kw(config):
    out = json.loads(bench_spec.service_env(config)["LLAMA_CONFIG"])
    return {**out, "eos_id": 1, "pad_id": 0, "pallas_interpret": True}


@pytest.fixture(scope="module")
def cfg(kw):
    return llama_mod.LlamaConfig(**kw)


@pytest.fixture(scope="module")
def params(cfg):
    """The seeded tree with W_UQ x 25: at the toy's widths normal-0.02
    kernels give scores of ~0.01 and a flat softmax, under which a wrong
    scale or wrong frequencies move nothing; at the published widths the
    same init gives scores of order 1 (5120-wide sums)."""
    p = llama_mod.init_params(jax.random.PRNGKey(0), cfg)
    for layer in p["layers"]:
        layer["attn"]["q_b"] = {"kernel": layer["attn"]["q_b"]["kernel"] * 25.0}
    return p


def _ids(n, seed=0, vocab=120):
    return np.random.default_rng(seed).integers(3, vocab, n).astype(np.int32)


def _close(got, want):
    return float(jnp.max(jnp.abs(jnp.asarray(got) - jnp.asarray(want))))


def test_the_toy_is_a_latent_model_holding_a_share(cfg, params):
    kinds = [cfg.layer_kind(li) for li in range(cfg.num_layers)]
    assert [k.attention for k in kinds] == ["mla"] * 3
    assert [k.experts for k in kinds] == [False, True, True]
    assert cfg.head_dim == 16 and cfg.rope_dim == 8 and cfg.o_dim == 32
    assert cfg.latent_dim == 24 and cfg.latent_lanes == 128
    assert cfg.held == 4 and cfg.num_experts == 16 and cfg.expert_first == 4
    a, m = params["layers"][1]["attn"], params["layers"][1]["mlp"]
    assert {k: v.shape for k, v in jax.tree.leaves_with_path(a) and
            {n: a[n][next(iter(a[n]))] for n in a}.items()} == {
        "q_a": (64, 24), "q_a_norm": (24,), "q_b": (24, 64), "kv_a": (64, 24),
        "kv_a_norm": (16,), "k_b": (4, 8, 16), "v_b": (4, 16, 8), "o": (32, 64)}
    assert m["router"]["kernel"].shape == (64, 16)  # the published width
    assert m["gate"]["kernel"].shape == (4, 64, 32)  # this chip's share
    assert m["shared"]["gate"]["kernel"].shape == (64, 64)  # 2 x 32
    # YaRN's temperature rides in the softmax scale
    m2 = (0.1 * 0.707 * np.log(40) + 1) ** 2
    assert cfg.attn_scale == pytest.approx(16 ** -0.5 * m2)
    real = llama_mod.LlamaConfig(
        attention="mla", q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        num_heads=128, d_model=5120, rope_scaling=YARN)
    assert (real.head_dim, real.latent_dim, real.latent_lanes) == (192, 576, 640)
    assert real.attn_scale == pytest.approx(192 ** -0.5 * 1.2608 ** 2, rel=1e-4)


# ---------------------------------------------------------------------------
# (i) the program against the reference, in every step kind that is served


class _Logits:
    """Every ``lm_head_logits`` a step makes, kept (steps run eagerly)."""

    def __init__(self, monkeypatch):
        self.seen = []
        real = llama_mod.lm_head_logits

        def keep(*a, **k):
            self.seen.append(real(*a, **k))
            return self.seen[-1]

        monkeypatch.setattr(llama_mod, "lm_head_logits", keep)


def _paged_state(cfg, rows, nb, bs, steps):
    from mlmicroservicetemplate_tpu.models.gpt import PagedState
    from mlmicroservicetemplate_tpu.models.sampling import greedy_params

    t_w = nb // rows
    return PagedState(  # ONE latent pool a layer and no V pool
        cache_k=[jnp.zeros((nb, bs, cfg.latent_lanes))
                 for _ in range(cfg.num_layers)],
        cache_v=[],
        key_valid=jnp.zeros((rows, t_w * bs), jnp.int32),
        write_idx=jnp.zeros((rows,), jnp.int32), pos=jnp.zeros((rows,), jnp.int32),
        last_token=jnp.zeros((rows,), jnp.int32), done=jnp.zeros((rows,), bool),
        tokens=jnp.zeros((rows, steps), jnp.int32), sample=greedy_params(rows),
    )


def _prefill(params, cfg, prompts, steps, chunk, bs=2, t_w=16):
    """Chunked paged prefill of each prompt (windows of ``chunk`` tokens
    straight into pool blocks) -> (state, table)."""
    rows = len(prompts)
    perm = np.random.default_rng(5).permutation(rows * t_w).astype(np.int32)
    table = jnp.asarray(perm.reshape(rows, t_w))
    state = _paged_state(cfg, rows, rows * t_w, bs, steps)
    for b, ids in enumerate(prompts):
        n = len(ids)
        for start in range(0, n, chunk):
            w_ids = np.zeros((1, chunk), np.int32)
            w_ids[0, : min(chunk, n - start)] = ids[start:start + chunk]
            w_mask = (np.arange(chunk)[None] + start < n).astype(np.int32)
            state = llama_mod.paged_prefill_chunk(
                params, cfg, state, table[b:b + 1], jnp.asarray(w_ids),
                jnp.asarray(w_mask), jnp.asarray([start]))
    return state, table


def _serve(params, cfg, prompts, steps, chunk, monkeypatch, bs=2, t_w=16):
    """``_prefill``, then ``steps`` greedy paged decode steps of all rows
    together -> (logits [B, steps, V], tokens, state)."""
    rows = len(prompts)
    state, table = _prefill(params, cfg, prompts, steps, chunk, bs, t_w)
    assert state.cache_v == [] and len(state.cache_k) == cfg.num_layers
    lens = np.asarray([len(p) for p in prompts])
    valid = (np.arange(t_w * bs)[None] < (lens - 1)[:, None]).astype(np.int32)
    state = state._replace(
        key_valid=jnp.asarray(valid), write_idx=jnp.asarray(lens - 1),
        last_token=jnp.asarray([p[-1] for p in prompts]))
    seen, toks = _Logits(monkeypatch), []
    for _ in range(steps):
        state, (tok, counts) = llama_mod._paged_decode_step(params, cfg, state, table)
        toks.append(np.asarray(tok))
        # [expert layers, PUBLISHED experts]: k a live row a layer, held or not
        assert counts.shape == (len(cfg.expert_layers), cfg.num_experts)
        np.testing.assert_array_equal(
            np.asarray(counts.sum(axis=1)),
            [rows * cfg.experts_per_token] * len(cfg.expert_layers))
    return jnp.stack(seen.seen, axis=1), toks, state


def _teacher_forced(ref, config, params, prompts, toks):
    hp = ref.hyper(config)
    out = []
    for b, p in enumerate(prompts):
        seq = list(p) + [int(t[b]) for t in toks]
        full = ref.logits(params, hp, np.asarray([seq], np.int32))[0]
        out.append(full[len(p) - 1: len(p) - 1 + len(toks)])
    return jnp.stack(out)


@pytest.mark.parametrize("path", ["wave", "kernel", "gathered"])
def test_program_matches_the_reference(ref, config, cfg, params, path, monkeypatch):
    """Logits after a prefill wave (expanded attention); and token by
    token through the latent pool (the absorbed step, through the latent
    kernel and through the gathered path) after a chunked paged prefill
    whose windows (5 tokens) end inside, at and past a prompt, positions
    past YaRN's original context (16) among them."""
    if path == "wave":
        ids = np.stack([_ids(21, 1), _ids(21, 2)])
        mask = np.ones_like(ids)
        mask[1, 6:] = 0  # a short row, right-padded
        got = llama_mod.lm_logits(params, cfg, jnp.asarray(ids), jnp.asarray(mask))
        want = ref.logits(params, ref.hyper(config), ids)
        assert _close(got[0], want[0]) < TOL and _close(got[1, :6], want[1, :6]) < TOL
        return
    kcfg = dataclasses.replace(cfg, pallas_decode=path == "kernel")
    prompts = [_ids(n, 10 + n) for n in (3, 5, 11, 23)]
    got, toks, state = _serve(params, kcfg, prompts, 4, 5, monkeypatch)
    assert _close(got, _teacher_forced(ref, config, params, prompts, toks)) < TOL
    # the pool holds latent_dim values a row and zeros past them
    assert float(jnp.abs(state.cache_k[1][..., cfg.latent_dim:]).max()) == 0.0
    assert float(jnp.abs(state.cache_k[1][..., : cfg.latent_dim]).max()) > 0.0


def test_a_prompt_window_on_a_rung_below_the_top_is_the_reference(
        ref, config, cfg, params, monkeypatch):
    """Windows of 16 tokens (48 assignments, 4 of 16 experts held: rungs
    16 and 48 once a rung is 8 rows) through the latent pool, then two
    decode steps: the logits are the reference's, and expert layers ran
    below the top rung — the rows an absent expert would have taken were
    never gathered, multiplied or combined."""
    from helpers import expert_rungs_at_toy_size

    calls = expert_rungs_at_toy_size(monkeypatch)
    prompts = [_ids(27, 41)]
    got, toks, _ = _serve(
        params, dataclasses.replace(cfg, pallas_decode=False), prompts, 2, 16,
        monkeypatch)
    assert _close(got, _teacher_forced(ref, config, params, prompts, toks)) < TOL
    windows = [ran for rungs, ran in calls if rungs == (16, 48)]
    assert len(windows) == 2 * len(cfg.expert_layers) and min(windows) < 48
    assert all(len(rungs) == 1 for rungs, _ in calls if rungs[-1] == 3)  # the steps


def test_prefill_wave_state_inserts_as_one_latent_slab_a_layer(cfg, params):
    """What the wave path hands ``engine/programs.paged_insert``: a latent
    slab a layer in ``cache_k``, nothing in ``cache_v`` — and straight
    into pool blocks the same rows (``init_paged_state``)."""
    ids = jnp.asarray(np.stack([_ids(8, 1), _ids(8, 2)]))
    st = llama_mod.init_decode_state(params, cfg, ids, jnp.ones_like(ids), 4)
    assert st.cache_v == [] and len(st.cache_k) == cfg.num_layers
    assert st.cache_k[0].shape == (2, 12, cfg.latent_lanes)
    table = jnp.asarray(np.arange(12, dtype=np.int32).reshape(2, 6))
    ps = llama_mod.init_paged_state(params, cfg, ids, jnp.ones_like(ids), 4,
                                    table, 12, 2)
    assert ps.cache_v == [] and ps.cache_k[0].shape == (12, 2, cfg.latent_lanes)
    np.testing.assert_array_equal(  # row 1's first block = its first two tokens
        np.asarray(ps.cache_k[2][6]), np.asarray(st.cache_k[2][1, :2]))
    # ... and the wave's ONE insert lands both rows' slabs in those blocks
    from mlmicroservicetemplate_tpu.engine.programs import paged_insert

    empty = ps._replace(
        cache_k=[jnp.zeros_like(c) for c in ps.cache_k],
        key_valid=jnp.zeros_like(ps.key_valid), done=jnp.ones_like(ps.done))
    got = paged_insert(2)(empty, st, table, jnp.asarray([1, 0]), 0, 12)
    assert got.cache_v == []
    for g, w in zip(got.cache_k, ps.cache_k):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    np.testing.assert_array_equal(  # wave row 0 went to slot 1
        np.asarray(got.key_valid[1]), np.asarray(ps.key_valid[0]))
    assert not bool(got.done[0]) and not bool(got.done[1])


def test_absorbed_is_expanded_on_the_same_weights(cfg, params):
    """One query over the same cached latents: scored absorbed (q through
    W_UK, values out through W_UV: the decode step) and expanded (keys
    and values made from the latents: prefill)."""
    rng = np.random.default_rng(3)
    layer, bs, t_w, n = params["layers"][1], 2, 8, 13
    x = jnp.asarray(rng.normal(size=(1, n, cfg.d_model)).astype(np.float32))
    cos, sin = llama_mod._rope_tables(cfg, jnp.arange(n), jnp.float32)
    (qn, qr), latent, _, _ = llama_mod._mla_qkv(
        cfg, layer, x, cos[None, :, None, :], sin[None, :, None, :])
    q1 = (qn[:, -1:], qr[:, -1:])
    expanded = llama_mod._mla_expanded_attention(
        cfg, layer, q1, latent, jnp.ones((1, 1, 1, n), bool))
    pool = jnp.zeros((t_w, bs, cfg.latent_lanes)).reshape(t_w * bs, -1).at[:n].set(
        latent[0]).reshape(t_w, bs, -1)
    table = jnp.arange(t_w, dtype=jnp.int32)[None]
    valid = (jnp.arange(t_w * bs)[None] < n).astype(jnp.int32)
    for pallas in (True, False):
        c = dataclasses.replace(cfg, pallas_decode=pallas)
        absorbed = llama_mod._mla_decode_attention(c, layer, q1, pool, table, valid, bs)
        assert _close(absorbed, expanded) < 1e-5, pallas


def test_the_prompt_window_kernel_is_the_xla_window(cfg, params, monkeypatch):
    """``paged_prefill_chunk`` through the prompt-window kernel
    (``cfg.pallas_decode``: the row's latents expanded once a layer)
    against the XLA form under ``_prefill_mask``, in windows of 8 over
    prompts that end inside a window, at its end and in a second one:
    every pool holds the same rows, and the next token's logits (one
    gathered decode step on either state) agree within the file's
    tolerance."""
    prompts = [_ids(n, 30 + n) for n in (5, 8, 13)]
    states = {}
    for kernels in (True, False):
        kcfg = dataclasses.replace(cfg, pallas_decode=kernels)
        states[kernels], table = _prefill(params, kcfg, prompts, 1, 8)
    pools = [jax.tree.leaves((st.cache_k, st.cache_v)) for st in states.values()]
    assert len(pools[0]) == len(pools[1]) > 0
    for got, want in zip(*pools):
        assert got.shape == want.shape and _close(got, want) < TOL
        np.testing.assert_array_equal(np.asarray(got) == 0, np.asarray(want) == 0)
    lens = np.asarray([len(p) for p in prompts])
    valid = (np.arange(table.shape[1] * 2)[None] < (lens - 1)[:, None]).astype(np.int32)
    seen = _Logits(monkeypatch)
    for st in states.values():
        st = st._replace(
            key_valid=jnp.asarray(valid), write_idx=jnp.asarray(lens - 1),
            last_token=jnp.asarray([p[-1] for p in prompts]))
        llama_mod._paged_decode_step(
            params, dataclasses.replace(cfg, pallas_decode=False), st, table)
    kernel_logits, xla_logits = seen.seen
    assert _close(kernel_logits, xla_logits) < TOL


@pytest.mark.parametrize("start,n_valid", [(0, 8), (8, 5)],
                         ids=["first-window", "behind-a-full-window-padded"])
@pytest.mark.parametrize("blocks", [1, 2], ids=["one-call", "two-head-blocks"])
def test_the_window_branch_is_the_plain_expansion(cfg, params, monkeypatch,
                                                  blocks, start, n_valid):
    """``_mla_expanded_attention``'s window branch — keys ``[K, H * dk]``
    and values ``[K, H * v]`` each out of ONE matmul of the latent rows
    (the rotary key through an identity block of the expansion matrix),
    the kernel called once over all heads, or, past ``MLA_WINDOW_BYTES``,
    once a static head block — against ``prefill_attention_ref`` over keys
    expanded the plain way: a window at start 0, and one behind a full
    earlier window with pad tokens at its end."""
    from helpers import latent_window_both_ways
    from mlmicroservicetemplate_tpu.ops import prefill_attention as pa

    c, k_len, dk = 8, 24, 128
    if blocks == 2:  # two heads' keys, values, queries and output, float32
        monkeypatch.setattr(llama_mod, "MLA_WINDOW_BYTES",
                            2 * (k_len + c) * (dk + cfg.v_head_dim) * 4)
    assert llama_mod.mla_window_head_blocks(
        cfg.num_heads, c, k_len, dk, cfg.v_head_dim, 4) == blocks
    calls, real = [], pa.prefill_attention
    monkeypatch.setattr(pa, "prefill_attention", lambda q, k, v, *a, **kw: (
        calls.append((q.shape, k.shape, v.shape)), real(q, k, v, *a, **kw))[1])
    got, want = latent_window_both_ways(
        cfg, params["layers"][1], 1, start, n_valid, c, k_len)
    hb = cfg.num_heads // blocks
    assert calls == [((c, hb, dk), (k_len, hb, dk), (k_len, hb, cfg.v_head_dim))] * blocks
    assert got.shape == want.shape == (c, cfg.num_heads, cfg.v_head_dim)
    assert _close(got, want) < TOL
    assert float(jnp.max(jnp.abs(want[:n_valid]))) > 100 * TOL


def _window_program(kw, **over) -> tuple:
    """The toy's ``paged_prefill_chunk`` with kernels on, traced on shapes
    (nothing compiled or run): ``(traced, element count of a layer's
    expanded keys, layers)``.  ``qk_nope_head_dim`` 136 makes a key 256
    lanes, so that no other array of the program (the values padded to 128
    lanes, the queries) has as many elements."""
    from mlmicroservicetemplate_tpu.models.gpt import PagedState
    from mlmicroservicetemplate_tpu.models.sampling import greedy_params

    wcfg = llama_mod.LlamaConfig(**{
        **kw, "qk_nope_head_dim": 136, "pallas_interpret": False,
        "pallas_decode": True, **over})
    b, c, bs, nb, t_w = 1, 8, 2, 24, 12
    p = jax.eval_shape(lambda: llama_mod.init_params(jax.random.PRNGKey(0), wcfg))
    state = jax.eval_shape(lambda: PagedState(
        cache_k=[jnp.zeros((nb, bs, wcfg.latent_lanes))] * wcfg.num_layers, cache_v=[],
        key_valid=jnp.zeros((b, t_w * bs), jnp.int32),
        write_idx=jnp.zeros((b,), jnp.int32), pos=jnp.zeros((b,), jnp.int32),
        last_token=jnp.zeros((b,), jnp.int32), done=jnp.ones((b,), bool),
        tokens=jnp.zeros((b, 8), jnp.int32), sample=greedy_params(b)))
    i32 = jnp.int32
    traced = jax.jit(
        lambda p, s, tabs, ids, mask, starts: llama_mod.paged_prefill_chunk(
            p, wcfg, s, tabs, ids, mask, starts),
    ).trace(p, state, jax.ShapeDtypeStruct((b, t_w), i32),
            jax.ShapeDtypeStruct((b, c), i32), jax.ShapeDtypeStruct((b, c), i32),
            jax.ShapeDtypeStruct((b,), i32))
    return traced, t_w * bs * wcfg.num_heads * 256, wcfg.num_layers


def _loops_under(jaxpr, scope: str) -> list:
    """The name stacks of the ``while`` / ``scan`` equations under the
    named scope ``scope``, nested jaxprs included (a kernel's body is the
    kernel's own business)."""
    from jax._src import core

    hits = []

    def walk(jp, outer):
        for e in jp.eqns:
            stack = outer + [str(e.source_info.name_stack)]
            if e.primitive.name in ("while", "scan") and scope in "/".join(stack).split("/"):
                hits.append("/".join(stack))
            if e.primitive.name != "pallas_call":
                for sub in core.jaxprs_in_params(e.params):
                    walk(sub, stack)

    walk(jaxpr.jaxpr, [])
    return hits


def _ops_of_size(text: str, n_elems: int) -> list:
    """``(op, result type)`` of the StableHLO operations whose result holds
    ``n_elems`` elements, whatever its shape — but for a ``pad`` of zero
    widths and the call that wraps it (the kernel's wrapper pads every
    head dim to whole lane tiles: nothing, at 256)."""
    import math
    import re

    hits = []
    for line in text.splitlines():
        if re.search(r"pad .*low = \[0(, 0)*\], high = \[0(, 0)*\], interior = \[0(, 0)*\]"
                     r"|call @_pad", line):
            continue
        m = re.search(r"= (?:stablehlo\.custom_call @|call @|stablehlo\.|\")?"
                      r"([\w.]+).*-> tensor<((?:\d+x)+)[a-z]\w*>\s*(?:loc.*)?$", line)
        if m and math.prod(map(int, m.group(2)[:-1].split("x"))) == n_elems:
            hits.append((m.group(1), m.group(2)))
    return hits


def test_the_windows_keys_are_written_once_in_the_kernels_layout(kw):
    """The layout is the change, so the lowered program is held to it: a
    prompt window of the toy latent configuration with kernels on holds no
    loop under ``attn`` (the wave branch's ``lax.map`` over head blocks is
    one) and, of all operations whose result is as large as a layer's
    expanded keys, ONE matmul a layer that writes ``[K, H * dk]`` — then
    only the layout's statement and reshapes that move nothing, down to
    the kernel's operand: no concatenate, pad, transpose, broadcast or
    update of that size."""
    traced, n_keys, layers = _window_program(kw)
    assert _loops_under(traced.jaxpr, "attn") == []
    text = traced.lower(lowering_platforms=("tpu",)).as_text()
    # (the last layer's attention is dead code: a window reads no logit)
    assert text.count("prefill_attention") >= layers - 1  # the kernel, a layer
    ops = _ops_of_size(text, n_keys)
    assert [o for o, _ in ops].count("dot_general") == layers - 1
    assert {o for o, _ in ops} <= {"dot_general", "LayoutConstraint", "reshape"}, ops
    two_d = f"24x{n_keys // 24}x"  # [K, H * dk], as the kernel's operand lies
    assert all(t == two_d for o, t in ops if o != "reshape"), ops
    # ... and the reader sees what it is there to see: without the kernels
    # the window runs the wave branch, whose map over head blocks is a loop
    traced, _, _ = _window_program(kw, pallas_decode=False)
    assert len(_loops_under(traced.jaxpr, "attn")) >= layers - 1


# ---------------------------------------------------------------------------
# (ii) the latent kernel: one pool, values = the keys' first lanes


def _latent_case(seed=0, b=4, h=8, c=128, bs=4, t=8, lens=(13, 0, 30, 5)):
    rng = np.random.default_rng(seed)
    nb = b * t + 2
    pool = jnp.asarray(rng.normal(size=(nb, bs, c)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(b, h, c)).astype(np.float32))
    table = np.full((b, t), nb, np.int32)  # the sentinel past a row's blocks
    valid = np.zeros((b, t * bs), np.int32)
    perm, o = rng.permutation(nb), 0
    for r, n in enumerate(lens):
        k = min(-(-n // bs) + 1, t) if n else 0  # allocated a block ahead
        table[r, :k] = perm[o:o + k]
        o += k
        valid[r, :n] = 1
    return q, pool, jnp.asarray(table), jnp.asarray(valid), bs


@pytest.mark.parametrize("variant", ["b1", "b2", "b4", "b8", "b2-nat"])
def test_latent_kernel_matches_its_reference(variant):
    """Live rows equal ``paged_attention_ref``'s latent form (one pool,
    values = the first ``v_dim`` lanes of the keys) to float32 rounding,
    whatever the fold; a row with no live key reads zeros; and a row's
    output is bit for bit the same in a table padded with sentinels as in
    one exactly as wide as its blocks — what the live range rests on."""
    q, pool, table, valid, bs = _latent_case()
    out = latent_decode_attention(q, pool, table, valid, bs, 96, 0.3,
                                  interpret=True, variant=variant)
    want = paged_attention_ref(q, pool, None, table, valid, bs, scale=0.3, v_dim=96)
    assert out.shape == (4, 8, 96)
    live = np.asarray([0, 2, 3])
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(want)[live],
                               rtol=2e-5, atol=2e-6)
    assert float(jnp.abs(out[1]).max()) == 0.0
    snug = latent_decode_attention(q[3:], pool, table[3:, :2 * max(
        int(variant[1]), 1)][:, :8], valid[3:, :8 * bs][:, : table[3:, :2 * max(
            int(variant[1]), 1)][:, :8].shape[1] * bs], bs, 96, 0.3,
        interpret=True, variant=variant)
    np.testing.assert_array_equal(np.asarray(snug[0]), np.asarray(out[3]))


def test_latent_values_are_the_first_lanes_only():
    """Values read from ALL of a row's lanes (the ninth broken variant:
    the rotary key's lanes weighed into the output) are another result."""
    q, pool, table, valid, bs = _latent_case(seed=1)
    out = latent_decode_attention(q, pool, table, valid, bs, 96, 0.3, interpret=True)
    wide = latent_decode_attention(q, pool, table, valid, bs, 128, 0.3, interpret=True)
    np.testing.assert_allclose(np.asarray(wide)[0, :, :96], np.asarray(out)[0],
                               rtol=2e-5, atol=2e-6)
    assert float(jnp.abs(wide[0, :, 96:]).max()) > 0.1


# ---------------------------------------------------------------------------
# (iii) the router: the group limit and a chip's share


def test_group_limit_against_a_hand_worked_case():
    """4 groups of 2, keep 2, top-3.  Token 0: group maxima (.30, .20,
    .25, .10) keep groups 0 and 2 -> experts {0, 4, 5}: expert 2 (.20)
    outscores expert 5 (.03) and loses to the limit (plain top-3 is
    {0, 2, 4}).  Token 1: maxima (.02, .40, .03, .27) keep groups 1 and 3
    -> {2, 6, 7}; expert 3 (.05) is in a kept group and too small."""
    p = jnp.asarray([[.30, .02, .20, .01, .25, .03, .10, .09],
                     [.01, .02, .40, .05, .03, .02, .20, .27]])
    sel = moe.group_limited(p, 4, 2)
    np.testing.assert_allclose(
        np.asarray(sel),
        [[.30, .02, 0, 0, .25, .03, 0, 0], [0, 0, .40, .05, 0, 0, .20, .27]])
    _, e = jax.lax.top_k(sel, 3)
    assert sorted(int(i) for i in e[0]) == [0, 4, 5]
    assert sorted(int(i) for i in e[1]) == [2, 6, 7]
    _, plain = jax.lax.top_k(p, 3)
    assert sorted(int(i) for i in plain[0]) == [0, 2, 4]


def test_the_four_shares_add_up_to_the_uncut_layer(ref, config, kw, params):
    """Experts 0-3, 4-7, 8-11 and 12-15 on four chips, the shared expert
    counted once: the shares' layer outputs sum to the uncut reference's
    (all 16 held), and each share's counts are the same [16]."""
    rng = np.random.default_rng(7)
    z = jnp.asarray(rng.normal(size=(11, 64)).astype(np.float32))
    full_kw = {**kw, "experts_held": 16, "expert_first": 0}
    full = llama_mod.init_params(jax.random.PRNGKey(0), llama_mod.LlamaConfig(**full_kw))
    m = full["layers"][1]["mlp"]
    hp = {**ref.hyper(config), "held": 16, "first": 0}
    w = ref.layer_weights(full["layers"][1], dense=False)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.experts(z, w, hp)
    shared = (jax.nn.silu(z @ w["s_gate"]) * (z @ w["s_up"])) @ w["s_down"]
    total, all_counts = 0.0, []
    for first in (0, 4, 8, 12):
        share = {**m, **{n: {"kernel": m[n]["kernel"][first:first + 4]}
                         for n in ("gate", "up", "down")}}
        out, counts = moe.expert_ffn(
            z, share, 3, False, jnp.ones((11,), bool), interpret=True,
            route_scale=16.0, n_group=4, topk_group=2, expert_first=first)
        total = total + out - shared  # each share ran the shared expert
        all_counts.append(np.asarray(counts))
    assert _close(total + shared, want) < TOL
    assert all((c == all_counts[0]).all() for c in all_counts)
    assert all_counts[0].shape == (16,) and all_counts[0].sum() == 33
    # a share alone is NOT the layer: the absent experts' part is left out
    assert _close(out, want) > 20 * TOL


def test_an_uncut_tree_routes_as_it_did(kw):
    """experts_held = 0 (every configuration before this one): the same
    leaves, the same selection — ``sizes`` is ``counts`` itself."""
    c = llama_mod.LlamaConfig(**{**kw, "experts_held": 0, "expert_first": 0,
                                 "n_group": 0, "topk_group": 0})
    assert c.held == 16
    p = llama_mod.init_params(jax.random.PRNGKey(0), c)
    assert p["layers"][1]["mlp"]["gate"]["kernel"].shape == (16, 64, 32)


# ---------------------------------------------------------------------------
# (iv) each broken rule lands outside the tolerance


@pytest.mark.parametrize("name", sorted(deepseek_variants.VARIANTS))
def test_each_broken_variant_departs_from_the_reference(
        ref, config, kw, params, name):
    ids = _ids(40, 3)[None]
    want = ref.logits(params, ref.hyper(config), ids)
    vkw, vparams, patches = deepseek_variants.broken(name, kw, params)
    with deepseek_variants.patched(patches):
        got = llama_mod.lm_logits(vparams, llama_mod.LlamaConfig(**vkw),
                                  jnp.asarray(ids), jnp.ones_like(ids))
    assert _close(got, want) > 20 * TOL, name
    sound = llama_mod.lm_logits(params, llama_mod.LlamaConfig(**kw),
                                jnp.asarray(ids), jnp.ones_like(ids))
    assert _close(sound, want) < TOL  # the patch is gone again
    x = ref.hidden(params, ref.hyper(config), ids)[0]
    r = deepseek_variants.readings(ref, params, x, got[0], tail=8)
    s = deepseek_variants.readings(ref, params, x, sound[0], tail=8)
    assert s["logit_rms_err"] < 1e-5 and s["worst_margin"] == 0.0
    assert r["logit_rms_err"] > 100 * s["logit_rms_err"]


# ---------------------------------------------------------------------------
# (v) what knows nothing of a latent refuses at boot


def _svc(monkeypatch, kw, **knobs):
    from mlmicroservicetemplate_tpu.utils.config import ServiceConfig

    over = {k: v for k, v in kw.items()
            if k not in ("eos_id", "pad_id", "pallas_interpret")}
    over["vocab_size"] = 300
    monkeypatch.setenv("LLAMA_CONFIG", json.dumps(over))
    knobs.setdefault("pallas_interpret", True)
    knobs.setdefault("paged_kv", True)
    return ServiceConfig(device="cpu", model_name="llama", warmup=False,
                         seq_buckets=(16, 32), max_decode_len=8, **knobs)


def test_registry_builds_the_latent_model(monkeypatch, kw, ref, config):
    from mlmicroservicetemplate_tpu.models.registry import build_model

    monkeypatch.setenv("USE_PALLAS_DECODE", "1")
    bundle = build_model(_svc(monkeypatch, kw))
    c = bundle.cfg
    assert c.mla and c.pallas_decode and c.n_group == 4 and c.held == 4
    assert dict(c.rope_scaling)["factor"] == 40
    assert not getattr(bundle.tokenizer, "add_bos", False)
    ids = _ids(20, 9, vocab=290)[None]
    got = jax.jit(bundle.logits_fn)(bundle.params, ids, np.ones_like(ids))
    want = ref.logits(bundle.params, ref.hyper(config), ids)
    assert _close(got, want) < TOL


@pytest.mark.parametrize("knobs,needle", [
    ({"paged_kv": False}, "PAGED_KV=0 is not supported"),
    ({"spec_decode": "ngram"}, "SPEC_DECODE is not supported"),
    ({"quant_kv": "int8"}, "QUANT_KV is not supported"),
    ({"prefix_cache": True}, "PREFIX_CACHE is not supported"),
    ({"prompt_prefix": "w5 w6", "paged_kv": False},
     "is not supported for a llama config with latent"),
    ({"tp": 2}, "TP=2 is not supported"),
    ({"quantize": "int8"}, "QUANTIZE=int8 is not supported"),
])
def test_registry_refuses_what_knows_nothing_of_a_latent(
        monkeypatch, kw, knobs, needle):
    from mlmicroservicetemplate_tpu.models.registry import build_model

    with pytest.raises(ValueError, match=needle):
        build_model(_svc(monkeypatch, kw, **knobs))


@pytest.mark.parametrize("bad,needle", [
    ({"attention": "mqa"}, "attention="),
    ({"kv_lora_rank": 0}, "attention='mla' needs"),
    ({"qk_rope_head_dim": 7}, "attention='mla' needs"),
    ({"attention": "gqa"}, "need attention='mla'"),
    ({"rope_scaling": {"type": "linear", "factor": 2}}, "rope_scaling must be"),
    ({"n_group": 3}, "groups must divide"),
    ({"topk_group": 5}, "groups must divide"),
    ({"topk_group": 1, "experts_per_token": 5}, "groups must divide"),
    ({"experts_held": 14}, "must lie within num_experts"),
    ({"experts_held": 0}, "must lie within num_experts"),  # expert_first without a share
])
def test_a_latent_config_that_does_not_add_up_is_refused(kw, bad, needle):
    with pytest.raises(ValueError, match=needle):
        llama_mod.LlamaConfig(**{**kw, **bad})


def test_yarn_without_latent_attention_is_refused():
    with pytest.raises(ValueError, match="carried by attention='mla' only"):
        llama_mod.LlamaConfig(rope_scaling=YARN)


def test_yarn_blends_the_frequencies(cfg, ref, config):
    """The program's tables against the reference's own blend, at
    positions before and past the original context; and against plain
    RoPE: the slow pairs turn ``factor`` times slower, the fast as ever."""
    pos = jnp.asarray([0, 1, 15, 16, 17, 100, 255])
    cos, sin = llama_mod._rope_tables(cfg, pos, jnp.float32)
    inv, amp = ref.inv_freq(ref.hyper(config))
    ang = np.asarray(pos, np.float32)[:, None] * np.asarray(inv)[None]
    assert amp == 1.0
    np.testing.assert_allclose(np.asarray(cos)[:, :4], np.cos(ang), atol=1e-6)
    np.testing.assert_allclose(np.asarray(sin)[:, 4:], np.sin(ang), atol=1e-6)
    plain = 1.0 / (10000.0 ** (np.arange(4) * 2.0 / 8))
    assert np.asarray(inv)[0] == pytest.approx(plain[0])  # the fastest pair: as ever
    assert np.asarray(inv)[-1] == pytest.approx(plain[-1] / 40)  # the slowest: / factor


# ---------------------------------------------------------------------------
# (vi) the engine sizes and counts a latent cache as what it is


def test_pool_sizing_counts_one_latent_row_a_layer():
    from mlmicroservicetemplate_tpu.engine.kv_blocks import kv_token_bytes

    # 5 layers x 640 lanes x 2 B: the cell's 6400 B a token
    assert kv_token_bytes(5, 128, 192, 2, latent_lanes=640) == 6400
    assert kv_token_bytes(5, 4, 128, 2) == 10240  # Trinity's, as ever
