"""The Granite-4.0-H block through ``models/llama.py`` — a Mamba-2 mixer
(``layer_types`` "mamba2": the ``layer_pattern`` "M" mixer with its FFN behind
it; ONE group of B and C) or an unrotated GQA attention, THEN an expert block
with a shared expert in every layer, four scalar multipliers and a tied head
— held to the benchmark's plain reference
(``cellbench/references/granite_hybrid.py``) at a toy size on the CPU in
float32: one period of the published pattern (5 Mamba + 1 attention + 4
Mamba), hidden 64, 8 Mamba heads of 8 in one group with a state of 16 and a
scan chunk of 8, 4 / 2 attention heads of 16, 8 experts top-3 of which 4
held, a shared expert of 48; the multipliers the published 12 / 0.0078125 /
0.22 / 16.

TOL: model and reference both compute in float32 and differ in the order of
sums only (the chunked scan against a scan over tokens, a grouped matmul
against a masked loop over experts, a softmax over all experts renormalised
against a softmax over the chosen): measured 2e-8 on logits of size 0.01.
"""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import spec as bench_spec
from mlmicroservicetemplate_tpu.models import llama as llama_mod
from mlmicroservicetemplate_tpu.ops import moe, ssm
from tools import granite_variants, nemotron_variants

TOL = 5e-7


@pytest.fixture(scope="module")
def config():
    real = bench_spec.load_json(
        bench_spec.HERE + "/configs/granite-4.0-h-small-ep2-d10.json")
    toy = bench_spec.load_json(
        bench_spec.HERE + "/tests/rehearse_granite.json")["config"]
    toy = {k: v for k, v in toy.items() if k not in ("env", "expect_cfg", "prompt")}
    return {**real, **toy, "vocab_size": 128}


@pytest.fixture(scope="module")
def ref():
    return bench_spec.load_module(
        bench_spec.HERE + "/references/granite_hybrid.py",
        "cellbench_reference_granite_hybrid")


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


#: Three layers of the toy (Mamba, attention, Mamba) for what needs no whole
#: period: a variant's forward compiles in a third of the time.
SMALL_TYPES = ["mamba", "attention", "mamba"]


@pytest.fixture(scope="module")
def small(config, kw):
    """``(config, kwargs, params)`` of the three-layer toy."""
    skw = {**kw, "num_layers": 3, "layer_types": ["mamba2", "attention", "mamba2"]}
    return ({**config, "num_hidden_layers": 3, "layer_types": SMALL_TYPES}, skw,
            llama_mod.init_params(jax.random.PRNGKey(0), llama_mod.LlamaConfig(**skw)))


def _ids(n, seed=0, vocab=120):
    return np.random.default_rng(seed).integers(3, vocab, n).astype(np.int32)


def _close(got, want):
    return float(jnp.max(jnp.abs(jnp.asarray(got) - jnp.asarray(want))))


# ---------------------------------------------------------------------------
# (i) the fused scan at ONE group: a group's heads tiled over the grid


def _scan_inputs(length, b=2, h=32, p=64, g=1, n=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        xbc=jax.random.normal(ks[0], (b, length, h * p + 2 * g * n)),
        dt=jax.nn.softplus(jax.random.normal(ks[1], (b, length, h)) - 2),
        a=-jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.7)),
        d=jax.random.normal(ks[3], (h,)),
        s0=jax.random.normal(ks[4], (b, h, p, n)),  # a NON-ZERO initial state
    )


@functools.partial(jax.jit, static_argnames=("g", "n", "kernel"))
def _scan(i, mask, g, n, kernel):
    with jax.default_matmul_precision("highest"):
        return ssm.ssm_scan(i["xbc"], i["dt"], i["a"], i["d"], i["s0"], mask,
                            groups=g, state=n, chunk=16, kernel=kernel,
                            interpret=True)


@pytest.mark.parametrize("h,g,blocks", [(32, 1, 2), (64, 2, 2), (16, 1, 1)],
                         ids=["one-group-2-blocks", "two-groups-2-blocks-each",
                              "one-group-whole"])
def test_the_fused_kernel_at_one_group_is_the_scan(h, g, blocks):
    """The kernel (interpret mode) against ``_scan_xla``, heads of 64: ONE
    group of 32 heads spans 2048 lanes and runs as two blocks of 16 heads
    that read the SAME B and C; two groups of 32 as four blocks, each
    reading its own group's; a group of 16 heads (Nemotron's) whole.  A
    non-zero initial state, 40 tokens in chunks of 16, row 1's real prefix 23
    tokens (its last chunk all fill: the kernel runs no matmul there and
    writes zeros, where the scan's own rows are don't-cares too)."""
    r, p, n, length = h // g, 64, 16, 40
    assert ssm._head_block(r, p) == 16 and r // ssm._head_block(r, p) == blocks
    i = _scan_inputs(length, h=h, g=g, n=n, seed=h + g)
    real = [length, 23]
    mask = (jnp.arange(length)[None, :] < jnp.asarray(real)[:, None]).astype(jnp.int32)
    y, s = _scan(i, mask, g, n, True)
    ref_y, ref_s = _scan(i, mask, g, n, False)
    assert y.dtype == s.dtype == jnp.float32 and s.shape == i["s0"].shape
    for row, k in enumerate(real):
        assert _close(y[row, :k], ref_y[row, :k]) < 1e-4
    assert _close(s, ref_s) < 1e-4


@pytest.mark.parametrize("h,p,g,n,chunk,block,fits", [
    (128, 64, 1, 128, 128, 16, True),    # Granite: 16 of ONE group's 128 heads
    (128, 64, 8, 128, 128, 16, True),    # Nemotron: a group's 16 heads whole
    (128, 64, 1, 128, 256, 16, True),    # the published mamba_chunk_size
    (128, 64, 1, 128, 64, 16, False),    # a chunk under a lane tile
    (24, 64, 1, 128, 128, 8, True),      # 16 does not divide 24: blocks of 8 heads
    (128, 64, 1, 64, 128, 16, False),    # a state under a lane tile
    (36, 64, 1, 128, 128, 36, False),    # no multiple of 8 divides 36: whole, too wide
], ids=["granite", "nemotron", "chunk256", "chunk64", "r24", "n64", "r36"])
def test_the_kernels_shape_gate_answers_for_the_block_it_takes(
        h, p, g, n, chunk, block, fits):
    assert ssm._head_block(h // g, p) == block
    assert ssm._kernel_fits(h, p, g, n, chunk, False) == fits
    # what a program holds: never more than HEAD_BLOCK_LANES lanes of heads
    assert not fits or block * p <= ssm.HEAD_BLOCK_LANES


def test_scan_fused_is_true_of_the_cells_configuration():
    """``LlamaConfig.scan_fused`` at the published widths: the one-group
    kernel takes the windows' tokens once the decode step runs its kernels
    (``ssm_scan_fused_tokens_total`` counts by it), and Nemotron's blocks are
    what they were (16 heads: its group whole)."""
    for name, r in (("granite-4.0-h-small-ep2-d10", 128),
                    ("nemotron3-super-ep4-d11", 16)):
        c = bench_spec.load_json(f"{bench_spec.HERE}/configs/{name}.json")
        kwargs = json.loads(bench_spec.service_env(c)["LLAMA_CONFIG"])
        for kernels in (False, True):
            lc = llama_mod.LlamaConfig(**kwargs, pallas_decode=kernels, eos_id=2,
                                       pad_id=0)
            assert lc.scan_fused == kernels
        assert lc.ssm_heads // lc.ssm_groups == r
        assert ssm._head_block(r, lc.ssm_head_dim) == 16


# ---------------------------------------------------------------------------
# (ii) the layers and the whole model against the reference


def test_the_toy_is_one_period_of_the_pattern(cfg, params):
    kinds = [cfg.layer_kind(li) for li in range(cfg.num_layers)]
    assert [k.mixer for k in kinds] == ["mamba2"] * 5 + ["gqa"] + ["mamba2"] * 4
    assert all(k.ffn and k.experts and not k.rope for k in kinds)
    assert cfg.layer_types == ("mamba2",) * 5 + ("full",) + ("mamba2",) * 4
    assert cfg.cache_layers == (5,) and cfg.recurrent_layers == (0, 1, 2, 3, 4, 6, 7, 8, 9)
    assert cfg.expert_layers == tuple(range(10))
    assert (cfg.n_rep, cfg.num_kv_heads, cfg.head_dim) == (2, 2, 16)
    assert cfg.recurrent_shapes(0) == ((3, 96), (8, 8, 16))
    assert cfg.ssm_row_bytes == 9 * (8 * 8 * 16 * 4 + 3 * 96 * 2)
    assert sorted(params) == ["embed", "final_ln", "layers"]  # tied: no lm_head
    assert sorted(params["layers"][0]) == ["mlp", "mlp_ln", "ssm", "ssm_ln"]
    assert sorted(params["layers"][5]) == ["attn", "attn_ln", "mlp", "mlp_ln"]
    m = params["layers"][0]["ssm"]
    assert m["in"]["kernel"].shape == (64, 64 + 96 + 8)  # [z | x B C | dt], ONE group
    mlp = params["layers"][0]["mlp"]
    assert mlp["router"]["kernel"].shape == (64, 8)  # the published width
    assert mlp["gate"]["kernel"].shape == (4, 64, 32)  # held experts, gated
    assert mlp["shared"]["up"]["kernel"].shape == (64, 48)
    assert (cfg.attn_scale, cfg.gqa_scale) == (0.0078125, 0.0078125)


def test_the_same_mixer_alone_is_nemotrons_layer(kw):
    """``layer_types`` "mamba2" and ``layer_pattern`` "M" are ONE mixer: the
    same leaves from the same keys, the same block; what differs is the FFN
    behind it."""
    a = llama_mod.LlamaConfig(**{**kw, "num_layers": 2,
                                 "layer_types": ["mamba2", "attention"]})
    pat = {k: v for k, v in kw.items() if k != "layer_types"}
    b = llama_mod.LlamaConfig(**{**pat, "num_layers": 2, "layer_pattern": "M*"})
    pa = llama_mod.init_params(jax.random.PRNGKey(0), a)["layers"][0]
    pb = llama_mod.init_params(jax.random.PRNGKey(0), b)["layers"][0]
    assert sorted(pb) == ["ssm", "ssm_ln"]
    for got, want in zip(jax.tree.leaves({k: pa[k] for k in pb}), jax.tree.leaves(pb)):
        assert _close(got, want) == 0.0
    assert llama_mod._recurrent_block(a, 0) is llama_mod._recurrent_block(b, 0)
    assert a.recurrent_shapes(0) == b.recurrent_shapes(0)


def test_jambas_mamba_still_builds_mamba_1():
    c = bench_spec.load_json(bench_spec.HERE + "/configs/jamba2-3b-d28.json")
    lc = llama_mod.LlamaConfig(
        **json.loads(bench_spec.service_env(c)["LLAMA_CONFIG"]), eos_id=2, pad_id=0)
    assert lc.layer_types.count("mamba") == 26 and "mamba2" not in lc.layer_types
    assert {lc.layer_kind(li).mixer for li in lc.recurrent_layers} == {"mamba1"}
    assert llama_mod._recurrent_block(lc, 0) is llama_mod._mamba1_block


@pytest.mark.parametrize("li", [0, 5], ids=["mamba", "attention"])
def test_a_layer_is_the_reference(ref, config, cfg, params, li):
    """One layer of each kind on random rows: the wave forward's mixer (the
    chunked scan from zeros | 4 heads on 2 KV heads, not rotated, scale
    1/128) then the expert block, each sub-block's output times 0.22."""
    hp = ref.hyper(config)
    x = jax.random.normal(jax.random.PRNGKey(5 + li), (1, 33, 64)) * 0.5
    kind, layer = hp["kinds"][li], params["layers"][li]
    want, chosen, left = ref.layer(x[0], ref.layer_weights(layer, kind), hp, kind)
    mask = jnp.ones((1, 33), jnp.int32)
    if kind == "mamba":
        z = llama_mod.zero_ssm(cfg, 1, jnp.float32)
        got, _, s = llama_mod._mamba_block(cfg, layer, x, z.conv[0], z.state[0],
                                           mask=mask)
        assert _close(s[0], left[0]) < TOL
    else:
        q, k, v, g = llama_mod._qkv_rope(cfg, layer, None, li, x, None, None)
        ctx = llama_mod.mha_attention(
            q, llama_mod._repeat_kv(k, 2), llama_mod._repeat_kv(v, 2),
            mask=jnp.tril(jnp.ones((33, 33), bool))[None, None],
            scale=cfg.gqa_scale)
        got = llama_mod._attn_out(cfg, layer, None, li, x, ctx, g)
    got = llama_mod._mlp_block(cfg, layer, li, got, mask != 0)
    assert _close(got[0], want) < TOL
    held = (np.asarray(chosen) < 4).mean()
    assert 0.2 < held < 0.8  # some assignments land here, some do not


def test_the_router_identity(ref):
    """Top-k over the LOGITS then a softmax over the k (published) equals a
    softmax over all experts, its top-k, renormalised (served): softmax is
    monotone and the renormalisation cancels the other terms.  Ties aside:
    the logits here are distinct."""
    logits = jax.random.normal(jax.random.PRNGKey(3), (200, 72)) * 1.3
    ek, wk = ref.select(logits, 10)
    p = jax.nn.softmax(logits, axis=-1)
    w, e = jax.lax.top_k(p, 10)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    assert bool(jnp.all(e == ek)) and _close(w, wk) < 1e-6
    assert _close(jnp.sum(wk, axis=-1), 1.0) < 1e-6


def _expert_layer(cfg, layer, x):
    return llama_mod._mlp_block(cfg, layer, 0, x, jnp.ones(x.shape[:2], bool))


def test_the_two_shares_add_up_to_the_uncut_layer(ref, config, kw):
    """The expert block on each of the two chips of the deployment (4 of 8
    experts held, ``expert_first`` 0 / 4, slices of ONE uncut tree), the
    shared expert counted ONCE, add up to the uncut reference's block:
    nothing is lost or doubled at the shares' edge."""
    whole = llama_mod.LlamaConfig(**{**kw, "experts_held": 0})
    p = llama_mod.init_params(jax.random.PRNGKey(0), whole)["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 40, 64)) * 0.5
    hp = ref.hyper({**config, "num_local_experts": 8})
    w = ref.layer_weights(p, "mamba")
    v = ref._rmsnorm(x[0], w["mlp_ln"], hp["eps"])
    want, _ = ref.experts(v, w, hp)  # all 8 experts + the shared one
    shared = ref._swiglu(v, w["s_gate"], w["s_up"], w["s_down"])
    rm = hp["residual"]
    total = jnp.zeros_like(want)
    for first in (0, 4):
        share = llama_mod.LlamaConfig(**{**kw, "expert_first": first})
        mlp = {**p["mlp"], **{n: {"kernel": p["mlp"][n]["kernel"][first:first + 4]}
                              for n in ("gate", "up", "down")}}
        # a share's block is x + 0.22 (routed part + shared): take both off
        routed = (_expert_layer(share, {**p, "mlp": mlp}, x)[0] - x[0]) / rm - shared
        total += routed
        # and the reference, given the same share, gives the same part
        part, _ = ref.experts(v, {**w, **{n: w[n][first:first + 4]
                                          for n in ("gate", "up", "down")}},
                              hp, first=first, shared=False)
        assert _close(routed, part) < 4 * TOL
    assert _close(total + shared, want) < 8 * TOL


def test_the_wave_forward_is_the_reference(ref, config, cfg, params):
    ids = _ids(90, 1).reshape(2, 45)
    got = jax.jit(lambda p: llama_mod.lm_logits(p, cfg, ids, np.ones_like(ids)))(params)
    want = ref.logits(params, ref.hyper(config), ids)
    assert _close(got, want) < TOL
    assert 0.002 < float(jnp.std(want)) < 0.05  # logits / 16 of a 0.02 table: small


def test_prefill_then_decode_is_the_reference(ref, config, cfg, params):
    """A ragged wave's prefill, then six decode steps through the one-token
    update and the cache: every token the reference's argmax on the sequence
    so far, and the state the steps leave the reference's token scan's."""
    ids = _ids(40, 3).reshape(2, 20)
    mask = np.ones((2, 20), np.int32)
    mask[1, 13:] = 0
    state, toks = jax.jit(lambda p: llama_mod.generate_chunk(
        p, cfg, llama_mod.init_decode_state(
            p, cfg, jnp.asarray(ids), jnp.asarray(mask), 6), 6))(params)
    hp = ref.hyper(config)
    for b, n in ((0, 20), (1, 13)):
        seq = np.concatenate([ids[b, :n], np.asarray(toks[b])])[None]
        states: list = []
        want = ref.head_logits(params, ref.hidden(params, hp, seq[:, :-1], states=states))
        rows = np.asarray(want[0, n - 1:])
        served = np.asarray(toks[b])
        assert float((rows.max(-1) - rows[np.arange(6), served]).max()) < 1e-6
        for i in range(9):
            assert _close(state.ssm.state[i][b], states[i][0][0]) < TOL


# ---------------------------------------------------------------------------
# (iii) the four multipliers


def _plain(kw, **over):
    """The toy with every multiplier at its neutral default, ``over`` aside."""
    neutral = {"embedding_multiplier": 1.0, "attention_multiplier": 0.0,
               "residual_multiplier": 1.0, "logits_scaling": 1.0}
    return llama_mod.LlamaConfig(**{**kw, **neutral, **over})


def test_default_multipliers_are_the_block_as_it_was(kw, params):
    """A config that names the four fields at their defaults traces the SAME
    program as one that has never heard of them: the jaxpr, operation for
    operation (so today's seven configurations keep their executables)."""
    ids = _ids(24, 4)[None]
    bare = {k: v for k, v in kw.items() if k not in (
        "embedding_multiplier", "attention_multiplier", "residual_multiplier",
        "logits_scaling")}
    texts = []
    for c in (_plain(kw), llama_mod.LlamaConfig(**bare)):
        assert (c.embedding_multiplier, c.attention_multiplier,
                c.residual_multiplier, c.logits_scaling) == (1.0, 0.0, 1.0, 1.0)
        assert c.gqa_scale is None and c.attn_scale == 16 ** -0.5
        texts.append(str(jax.make_jaxpr(lambda p, c=c: llama_mod.lm_logits(
            p, c, ids, np.ones_like(ids)))(params)))
    assert texts[0] == texts[1]
    assert "0.22" not in texts[0] and "mul" in texts[0]


@pytest.mark.parametrize("field,value", [
    ("embedding_multiplier", 12.0), ("attention_multiplier", 0.0078125),
    ("residual_multiplier", 0.22), ("logits_scaling", 16.0)])
def test_each_multiplier_moves_the_output_as_its_equation_says(
        kw, params, field, value):
    """One multiplier at its published value over a neutral block, against
    the same equations written out around the neutral program's own pieces."""
    c, base = _plain(kw, **{field: value}), _plain(kw)
    ids = _ids(24, 6)[None]
    ones = np.ones_like(ids)
    got = llama_mod.lm_logits(params, c, ids, ones)
    plain = llama_mod.lm_logits(params, base, ids, ones)
    assert _close(got, plain) > 1e-4  # it moves the output
    if field == "logits_scaling":
        want = plain / 16.0
    elif field == "embedding_multiplier":
        scaled = {**params, "embed": {"embedding": params["embed"]["embedding"] * 12.0}}
        # the table is tied: the head reads the unscaled one
        x = llama_mod.forward_hidden(scaled, base, ids, ones)
        want = llama_mod._head_logits(params, base, x)
    elif field == "attention_multiplier":
        # q k^T * m = (q * m sqrt(d)) k^T / sqrt(d): W_q of the one attention layer scaled
        layers = list(params["layers"])
        a = layers[5]["attn"]
        layers[5] = {**layers[5], "attn": {**a, "q": {
            "kernel": a["q"]["kernel"] * (0.0078125 * 16 ** 0.5)}}}
        want = llama_mod.lm_logits({**params, "layers": layers}, base, ids, ones)
    else:
        # x + 0.22 f(N(x)): every sub-block's LAST projection scaled
        def scaled(layer):
            out = dict(layer)
            for name, leaf in (("ssm", "out"), ("attn", "o")):
                if name in out:
                    out[name] = {**out[name], leaf: {
                        "kernel": out[name][leaf]["kernel"] * 0.22}}
            m = dict(out["mlp"])
            m["down"] = {"kernel": m["down"]["kernel"] * 0.22}
            m["shared"] = {**m["shared"], "down": {
                "kernel": m["shared"]["down"]["kernel"] * 0.22}}
            return {**out, "mlp": m}

        want = llama_mod.lm_logits(
            {**params, "layers": [scaled(la) for la in params["layers"]]},
            base, ids, ones)
    assert _close(got, want) < 2e-6


def test_the_residual_multiplier_is_applied_in_float32():
    """0.22 is no bfloat16 number: the product and the sum are float32,
    rounded once — not a bfloat16 0.2197 times a bfloat16 output."""
    c = llama_mod.LlamaConfig(vocab_size=8, d_model=8, num_heads=2, num_kv_heads=1,
                              num_layers=1, d_ff=8, residual_multiplier=0.22)
    x = jnp.full((4,), 1.0, jnp.bfloat16)
    out = jnp.asarray([1.0, 3.0, 100.0, 0.37], jnp.bfloat16)
    want = (x.astype(jnp.float32) + out.astype(jnp.float32) * 0.22).astype(jnp.bfloat16)
    got = llama_mod._residual(c, x, out)
    assert got.dtype == jnp.bfloat16 and bool(jnp.all(got == want))
    assert llama_mod._residual(llama_mod.LlamaConfig(), x, out).dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# (iv) the broken variants


@pytest.fixture(scope="module")
def sound(ref, small):
    """One seeded sequence, the reference's logits on it, the Mamba states its
    token scan leaves before the last token and the rms of (the sound program
    - the reference) there (the three-layer toy: measured 1.3e-9 on logits of
    spread 0.012; the nearest variant, the rotation of ONE layer's q and k
    under a nearly flat softmax, 3.4e-8)."""
    ids, states = _ids(40, 2)[None], []
    hp = ref.hyper(small[0])
    ref.hidden(small[2], hp, ids[:, :-1], states=states)
    want = ref.logits(small[2], hp, ids)[0]
    got = llama_mod.lm_logits(small[2], llama_mod.LlamaConfig(**small[1]), ids,
                              np.ones_like(ids))[0]
    assert _close(got, want) < TOL
    return ids, want, states, float(jnp.sqrt(jnp.mean(jnp.square(got - want))))


@pytest.mark.parametrize("name", sorted(granite_variants.VARIANTS))
def test_each_broken_variant_departs_from_the_reference(sound, small, name):
    """Each multiplier, the convolution's bias, the shared expert, a
    rotation, eight groups' norm for one, a bfloat16 decay and float8
    weights each matter: the variant's logits leave the reference's by ten
    times the sound program's rms and more."""
    ids, want, states, sound_rms = sound
    vkw, vparams, patches = nemotron_variants.broken(
        name, small[1], small[2], granite_variants.VARIANTS)
    vcfg = llama_mod.LlamaConfig(**vkw)

    def run(p):
        # One wave reads no stored state: ``state_bf16`` shows in what it
        # LEAVES (the check reads the served stream's row so).
        left = []
        x = llama_mod.forward_hidden(p, vcfg, ids, np.ones_like(ids), ssm_out=left)
        return llama_mod._head_logits(p, vcfg, x)[0], left[0].state[0]

    with nemotron_variants.patched(patches):
        got, state = jax.jit(run)(vparams)
    # with ``ssm_out`` the last position's row is no forward pass's
    got, want = got[:-1], want[:-1]
    if name == "state_bf16":
        got, want = state, states[0][0]
    rms = float(jnp.sqrt(jnp.mean(jnp.square(got - want))))
    assert rms > 10 * sound_rms


# ---------------------------------------------------------------------------
# (v) the configuration and the registry


@pytest.mark.parametrize("bad,needle", [
    ({"layer_types": ["mamba2"] * 10}, "'mamba2' layers need"),
    ({"layer_types": ["mamba2"] * 5 + ["attention"] + ["mamba"] * 4}, "a 'mamba' layer needs|'mamba2' layers need"),
    ({"ssm_heads": 0}, "a 'mamba2' layer needs ssm_heads"),
    ({"ssm_groups": 3}, "a 'mamba2' layer needs ssm_heads"),
    ({"ssm_dt_rank": 8}, "ssm_dt_rank=8 needs a 'mamba' layer"),
    ({"attention": "mla", "q_lora_rank": 8, "kv_lora_rank": 8, "qk_nope_head_dim": 8,
      "qk_rope_head_dim": 8, "v_head_dim": 8}, "'mamba2' layers need attention='gqa'"),
    ({"layer_types": ["mamba3"] * 10}, "layer_types must name each"),
    ({"residual_multiplier": 0.0}, "must be positive"),
    ({"logits_scaling": -1.0}, "must be positive"),
    ({"attention_multiplier": -0.5}, "non-negative"),
])
def test_a_config_that_does_not_add_up_is_refused(kw, bad, needle):
    with pytest.raises(ValueError, match=needle):
        llama_mod.LlamaConfig(**{**kw, **bad})


def _svc(monkeypatch, kw, **knobs):
    from mlmicroservicetemplate_tpu.utils.config import ServiceConfig

    over = {k: v for k, v in kw.items()
            if k not in ("eos_id", "pad_id", "pallas_interpret")}
    over.update(num_layers=3, layer_types=["mamba2", "attention", "mamba2"])
    over["vocab_size"] = 300
    monkeypatch.setenv("LLAMA_CONFIG", json.dumps(over))
    knobs.setdefault("pallas_interpret", True)
    knobs.setdefault("paged_kv", True)
    return ServiceConfig(device="cpu", model_name="llama", warmup=False,
                         seq_buckets=(16, 32), max_decode_len=8, **knobs)


def test_registry_builds_the_configuration(monkeypatch, kw, ref, config):
    from mlmicroservicetemplate_tpu.models.registry import build_model

    bundle = build_model(_svc(monkeypatch, kw))
    c = bundle.cfg
    assert c.layer_types == ("mamba2", "full", "mamba2")
    assert c.tie_embeddings and c.nope_on_full and c.held == 4 and c.num_experts == 8
    assert (c.embedding_multiplier, c.residual_multiplier, c.logits_scaling) == (
        12, 0.22, 16)
    assert "lm_head" not in bundle.params
    assert not getattr(bundle.tokenizer, "add_bos", False)
    ids = _ids(20, 9, vocab=290)[None]
    got = jax.jit(bundle.logits_fn)(bundle.params, ids, np.ones_like(ids))
    small = {**config, "num_hidden_layers": 3, "layer_types": SMALL_TYPES}
    assert _close(got, ref.logits(bundle.params, ref.hyper(small), ids)) < TOL


@pytest.mark.parametrize("knobs,needle", [
    ({"paged_kv": False},
     "PAGED_KV=0 is not supported for a llama config with Mamba-2.*layer_types 'mamba2'"),
    ({"spec_decode": "ngram"}, "SPEC_DECODE is not supported.*roll a recurrent state back"),
    ({"quant_kv": "int8"}, "QUANT_KV is not supported"),
    ({"prefix_cache": True}, "PREFIX_CACHE is not supported.*kept nowhere"),
    ({"prompt_prefix": "w5 w6"}, "PROMPT_PREFIX is not supported"),
    ({"kv_host_budget_mb": 64.0}, "KV_HOST_BUDGET_MB is not supported.*rebuilt by recompute"),
    ({"kv_host_budget_mb": 0.0, "kv_disk_budget_mb": 64.0, "journal_dir": "/tmp/j"},
     "KV_DISK_BUDGET_MB is not supported|KV_HOST_BUDGET_MB"),
    ({"tp": 2}, "TP=2 is not supported"),
    ({"quantize": "int8"}, "QUANTIZE=int8 is not supported"),
])
def test_registry_refuses_every_reader_that_cannot_read_the_state(
        monkeypatch, kw, knobs, needle):
    from mlmicroservicetemplate_tpu.models.registry import build_model

    with pytest.raises(ValueError, match=needle):
        build_model(_svc(monkeypatch, kw, **knobs))


def test_the_densest_routing_runs_one_rung_at_a_decode_step():
    """72 experts, 36 held, top-10: a 32-row decode step's 320 assignments
    are too few for a lower rung (``LADDER_MIN_SKIP``), a 3072-token prompt
    dispatch's 30 720 run on the rung a quarter above half of them."""
    assert moe.row_rungs(32 * 10, 36, 72) == (320,)
    assert moe.row_rungs(3072 * 10, 36, 72) == (19200, 30720)
    # an expert 768 wide on a 4096 hidden: K whole, N by a divisor of 768
    assert moe.matmul_tiles(4096, 768, 2) == (128, 4096, 384)
    assert moe.matmul_tiles(768, 4096, 2) == (128, 768, 2048)
