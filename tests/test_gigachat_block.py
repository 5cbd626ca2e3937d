"""The GigaChat3.5 block through ``models/llama.py`` — Gated-DeltaNet
layers (``layer_types`` "linear"; ``ops/ssm.gdn_scan`` / ``gdn_step``) to one
gated latent-attention layer, each followed by a dense or an expert FFN
under sandwich norms with a gated scale, a clamped SwiGLU — held to the
benchmark's plain reference (``cellbench/references/gigachat35.py``) at a toy
size on the CPU in float32: 5 layers (linear x 4, full), d_model 64, 2 key /
4 value DeltaNet heads of 16, 4 latent heads (q rank
24, kv rank 16, nope 16 + rope 8, values 16), 16 experts top-4 of which 4
held, a shared expert.

TOL: model and reference both compute in float32 and differ in the order of
sums only (the chunked UT form against a scan over tokens, absorbed against
expanded attention, a grouped matmul against a masked loop over experts):
measured 6e-6 at most on logits of size 0.5 (rms 3e-7); the broken rules of
``tools/gigachat_variants.py`` move the logits' rms by 0.009 (the attention
gate: one layer of five) to 0.2.
"""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import spec as bench_spec
from mlmicroservicetemplate_tpu.models import llama as llama_mod
from mlmicroservicetemplate_tpu.ops import ssm
from tools import gigachat_variants, nemotron_variants

TOL = 1e-5


@pytest.fixture(scope="module")
def config():
    real = bench_spec.load_json(
        bench_spec.HERE + "/configs/gigachat35-ep16-d5.json")
    toy = bench_spec.load_json(
        bench_spec.HERE + "/tests/rehearse_gigachat.json")["config"]
    toy = {k: v for k, v in toy.items()
           if k not in ("env", "expect_cfg", "prompt", "rope_scaling")}
    rope = {**real["rope_scaling"], "original_max_position_embeddings": 64}
    # the toy's activations are small: a limit of 10 would never bind
    return {**real, **toy, "rope_scaling": rope, "vocab_size": 128,
            "swiglu_limit": 0.5}


@pytest.fixture(scope="module")
def ref():
    return bench_spec.load_module(
        bench_spec.HERE + "/references/gigachat35.py",
        "cellbench_reference_gigachat35")


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
# (i) the recurrence: the chunked UT form = the delta rule a token at a time


def _rule_inputs(length, b=2, hk=2, r=2, dk=16, dv=8, seed=0):
    """The scan's operands as the convolution leaves them: q and k of
    ``hk`` key heads NOT yet normalised, v of ``hk * r`` value heads, side
    by side; the gates a value head."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    h = hk * r
    return dict(
        qkv=jnp.concatenate([
            jax.random.normal(ks[0], (b, length, hk * dk)),
            jax.random.normal(ks[1], (b, length, hk * dk)),
            jax.random.normal(ks[2], (b, length, h * dv))], axis=-1),
        g=-jnp.exp(jax.random.normal(ks[3], (b, length, h)) - 1.0),
        beta=jax.nn.sigmoid(jax.random.normal(ks[4], (b, length, h))),
        s0=jax.random.normal(ks[5], (b, h, dv, dk)),  # a NON-ZERO initial state
    )


_SEQ = ("qkv", "g", "beta")
KERNEL = pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])


@functools.partial(jax.jit, static_argnames=("chunk", "kernel"))
def _scan(i, mask, s0=None, chunk=8, kernel=False):
    return ssm.gdn_scan(*(i[n] for n in _SEQ), i["s0"] if s0 is None else s0,
                        mask, chunk=chunk, kernel=kernel, interpret=True)


def _heads(i):
    """``i`` with q, k (normalised, a value head each) and v for the step."""
    hv, dv, dk = i["s0"].shape[1:]
    hk = (i["qkv"].shape[-1] - hv * dv) // (2 * dk)
    return (*ssm.gdn_heads(i["qkv"], hk, hv, dk), i["g"], i["beta"])


@functools.partial(jax.jit, static_argnames=("bits",))
def _token_by_token(i, mask, bits=23):
    def step(s, t):
        *row, live = t
        o, s = ssm.gdn_step(*row, s, live != 0)
        return jax.lax.reduce_precision(s, 8, bits), o

    s, o = jax.lax.scan(step, i["s0"], tuple(
        jnp.moveaxis(x, 1, 0) for x in (*_heads(i), mask)))
    return jnp.moveaxis(o, 0, 1), s


def _mask(lens, length):
    return jnp.asarray(np.arange(length)[None, :] < np.asarray(lens)[:, None],
                       jnp.int32)


@KERNEL
@pytest.mark.parametrize("length,lens", [
    (1, (1, 0)), (9, (9, 8)), (37, (37, 20)), (150, (150, 77))])
def test_the_chunked_form_is_the_delta_rule(length, lens, kernel):
    """Every length around the chunk's edge, one row shorter than the
    other: outputs on the real tokens and the final state agree; a row's
    padded tail moves no state."""
    i, mask = _rule_inputs(length), _mask(lens, length)
    o, s = _scan(i, mask, kernel=kernel)
    o1, s1 = _token_by_token(i, mask)
    assert _close(o * mask[..., None, None], o1 * mask[..., None, None]) < TOL
    assert _close(s, s1) < TOL
    if lens[1] == 0:
        assert _close(s[1], i["s0"][1]) == 0.0


@KERNEL
@pytest.mark.parametrize("cut", [8, 13])
def test_two_windows_in_sequence_are_one_scan_of_both(cut, kernel):
    """A prompt's second window continues the state its first one left —
    on a chunk's edge and off it — and a decode step continues a window."""
    scan = functools.partial(_scan, kernel=kernel)
    i, mask = _rule_inputs(30, seed=2), _mask((30, 21), 30)
    _, whole = scan(i, mask)
    first = {n: (v[:, :cut] if n in _SEQ else v) for n, v in i.items()}
    rest = {n: (v[:, cut:] if n in _SEQ else v) for n, v in i.items()}
    _, s_a = scan(first, mask[:, :cut])
    o_b, s_b = scan(rest, mask[:, cut:], s0=s_a)
    assert _close(s_b, whole) < TOL
    o_whole, _ = scan(i, mask)
    assert _close(o_b[0], o_whole[0, cut:]) < TOL
    # one more token by the step = a scan one token longer
    j = _rule_inputs(31, seed=2)
    o_step, s_step = ssm.gdn_step(
        *(x[:, 30] for x in _heads(j)), scan(
            {n: (v[:, :30] if n in _SEQ else v) for n, v in j.items()},
            _mask((30, 30), 30))[1], jnp.asarray([True, False]))
    o_long, s_long = scan(j, _mask((31, 30), 31))
    assert _close(s_step, s_long) < TOL and _close(o_step[0], o_long[0, 30]) < TOL


@KERNEL
@pytest.mark.parametrize("corr", [0.5, 0.99])
def test_correlated_keys_do_not_blow_up_the_chunks_inverse(corr, kernel):
    """Keys that share a direction (what a short convolution leaves) with
    beta near 1 and a chunk of 64: the triangular inverse's entries stay
    small while ``M``'s powers do not — a Neumann series of them overflowed
    float32 here (1e12 at 0.5, NaN at 0.9; my chip run, PR 47), the blocked
    substitution reads 1e-6 — in ``jax.numpy`` and in the kernel, whose
    diagonal blocks are eliminated a column at a time."""
    i = _rule_inputs(128, b=1, hk=1, r=2, dk=32, dv=16, seed=5)
    shared = jax.random.normal(jax.random.PRNGKey(9), (1, 1, 32))
    k = i["qkv"][..., 32:64]
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True))
    i["qkv"] = i["qkv"].at[..., 32:64].set(corr * shared + (1 - corr) * k)
    i["beta"], i["g"] = jnp.full_like(i["beta"], 0.95), jnp.full_like(i["g"], -0.01)
    mask = _mask((128,), 128)
    _, s = _scan(i, mask, chunk=64, kernel=kernel)
    want = _token_by_token(i, mask)[1]
    assert _close(s, want) < TOL * float(jnp.max(jnp.abs(want)) + 1.0)


@pytest.mark.parametrize("b,lens", [(1, (21,)), (3, (70, 33, 0)), (3, (96, 5, 64))])
def test_the_kernel_skips_the_chunks_past_a_rows_prefix(b, lens):
    """Rows whose real prefix ends inside a chunk, on a chunk's edge, inside
    the first chunk, or is empty, in a dispatch of one row and of three, over
    three steps of the kernel's grid: a wholly masked chunk does no matmul
    (``o`` is zero there, the state untouched — bit for bit where the row
    has no token at all), and what is real agrees with the ``jax.numpy``
    form and with the rule a token at a time."""
    i, mask = _rule_inputs(96, b=b, seed=3), _mask(lens, 96)
    o, s = _scan(i, mask, kernel=True)
    o_x, s_x = _scan(i, mask)
    o_1, s_1 = _token_by_token(i, mask)
    live = mask[..., None, None]
    assert _close(o * live, o_x * live) < TOL and _close(s, s_x) < TOL
    assert _close(o * live, o_1 * live) < TOL and _close(s, s_1) < TOL
    for row, n in enumerate(lens):
        past = -(-n // 8) * 8  # the first wholly masked chunk
        assert float(jnp.max(jnp.abs(o[row, past:]), initial=0.0)) == 0.0
        if n == 0:
            assert _close(s[row], i["s0"][row]) == 0.0


@pytest.mark.parametrize("hk,r,dk,dv,chunk,fits", [
    (2, 2, 16, 8, 8, True), (1, 1, 8, 8, 16, True), (4, 1, 8, 8, 32, True),
    (2, 2, 8, 24, 8, False), (3, 1, 8, 16, 8, True)])
def test_widths_the_blocks_do_not_divide_fall_back_to_jax_numpy(
        hk, r, dk, dv, chunk, fits):
    """One value head a key head or several, chunks under, at and over the
    inverse's block: the kernel's answer is the ``jax.numpy`` form's; where
    a key head's values do not start on a block of the convolution's output
    (``2 Hk Dk`` not a multiple of ``r Dv``) ``kernel=True`` IS that form."""
    assert ssm._gdn_kernel_fits(hk, hk * r, dk, dv, chunk, True) is fits
    i = _rule_inputs(70, b=2, hk=hk, r=r, dk=dk, dv=dv, seed=6)
    mask = _mask((70, 41), 70)
    o, s = _scan(i, mask, chunk=chunk, kernel=True)
    o_x, s_x = _scan(i, mask, chunk=chunk)
    live = mask[..., None, None]
    assert _close(o * live, o_x * live) < TOL and _close(s, s_x) < TOL
    if not fits:
        assert _close(o, o_x) == 0.0 and _close(s, s_x) == 0.0


def test_the_chips_tiles_decide_whether_the_kernel_fits():
    """Compiled, the kernel takes whole tiles only: the published widths
    (32 key / 64 value heads of 128, chunks of 64) fit, narrower heads or a
    chunk whose two heads do not fill the lanes do not."""
    assert ssm._gdn_kernel_fits(32, 64, 128, 128, 64, False)
    assert not ssm._gdn_kernel_fits(32, 64, 64, 128, 64, False)
    assert not ssm._gdn_kernel_fits(32, 64, 128, 64, 64, False)
    assert not ssm._gdn_kernel_fits(32, 32, 128, 128, 64, False)
    assert not ssm._gdn_kernel_fits(32, 48, 128, 128, 64, False)
    with pytest.raises(ValueError, match="power of two"):
        ssm._gdn_kernel_fits(32, 64, 128, 128, 48, False)


def test_a_bf16_state_drifts_where_a_float32_one_does_not():
    """The decode step's state rounded to bfloat16 after every token: over
    400 steps of slow heads the roundings pile up to several times one
    rounding (2^-9 of the state), which a float32 state never sees."""
    i = _rule_inputs(400, b=1, seed=4)
    i["g"] = i["g"] * 0.02  # slow heads: a long memory
    mask = _mask((400,), 400)
    exact = _token_by_token(i, mask)[1]
    drift = _close(_token_by_token(i, mask, bits=7)[1], exact) / float(
        jnp.max(jnp.abs(exact)))
    assert drift > 3 * 2.0 ** -9


# ---------------------------------------------------------------------------
# (ii) the layers and the whole model against the reference


def test_the_toy_has_every_kind_of_layer(cfg, params):
    kinds = [cfg.layer_kind(li) for li in range(cfg.num_layers)]
    assert [k.mixer for k in kinds] == ["gdn"] * 4 + ["mla"]
    assert [k.recurrent for k in kinds] == [True] * 4 + [False]
    assert all(k.ffn for k in kinds)
    assert [k.experts for k in kinds] == [False, True, True, True, True]
    assert [k.rope for k in kinds] == [False] * 4 + [True]
    assert cfg.recurrent_layers == (0, 1, 2, 3) and cfg.expert_layers == (1, 2, 3, 4)
    assert cfg.ssm_row_bytes == 4 * (4 * 16 * 16 * 4 + 3 * 128 * 2)
    assert sorted(params["layers"][0]) == [
        "gdn", "gdn_ln", "gdn_post_ln", "mlp", "mlp_ln", "mlp_post_ln"]
    assert sorted(params["layers"][4]) == [
        "attn", "attn_ln", "attn_post_ln", "mlp", "mlp_ln", "mlp_post_ln"]
    assert params["layers"][4]["attn"]["gate"]["kernel"].shape == (64, cfg.o_dim)
    assert params["layers"][1]["mlp"]["gate"]["kernel"].shape == (4, 64, 32)
    # the zero-centred norm leaves are drawn about 0, not about 1
    assert abs(float(jnp.mean(params["final_ln"]["scale"]))) < 0.2
    ssm0 = llama_mod.zero_ssm(cfg, 3, jnp.float32)
    assert [s.shape for s in ssm0.state] == [(3, 4, 16, 16)] * 4
    assert [c.shape for c in ssm0.conv] == [(3, 3, 128)] * 4


@pytest.mark.parametrize("li", [0, 1, 4], ids=["deltanet-dense", "deltanet-experts",
                                              "latent-experts"])
def test_a_layer_is_the_reference(ref, config, cfg, params, li):
    """One layer of each kind on random rows: the wave forward's mixer
    (chunked scan from zeros | expanded attention) and FFN."""
    hp = ref.hyper(config)
    x = jax.random.normal(jax.random.PRNGKey(5 + li), (1, 33, 64)) * 0.5
    kind, dense = hp["kinds"][li], li < hp["dense_layers"]
    want, (left, chosen) = ref.layer(
        x[0], ref.layer_weights(params["layers"][li], kind, dense), hp, kind, dense)
    mask = jnp.ones((1, 33), jnp.int32)
    layer = params["layers"][li]
    left_by_program = []
    if kind == "linear":
        z = llama_mod.zero_ssm(cfg, 1, jnp.float32)
        got, _, s = llama_mod._gdn_block(cfg, layer, x, z.conv[0], z.state[0], mask=mask)
        left_by_program.append(s)
    else:
        cos, sin = llama_mod._rope_tables(cfg, jnp.arange(33, dtype=jnp.int32), jnp.float32)
        q, k, v, g = llama_mod._qkv_rope(
            cfg, layer, None, li, x, cos[None, :, None, :], sin[None, :, None, :])
        ctx = llama_mod._mla_expanded_attention(
            cfg, layer, q, k, jnp.tril(jnp.ones((33, 33), bool))[None, None])
        got = llama_mod._attn_out(cfg, layer, None, li, x, ctx, g)
    got = llama_mod._mlp_block(cfg, layer, li, got, mask != 0)
    assert _close(got[0], want) < TOL
    if kind == "linear":
        assert _close(left_by_program[0][0], left[0]) < TOL
    if not dense:
        held = (np.asarray(chosen) < 4).mean()
        assert 0.05 < held < 0.6  # some assignments land here, most do not


def test_a_prompt_window_at_64_heads_is_the_plain_expansion(kw):
    """The latent layer's prompt window at the PUBLISHED 64 heads (the
    toy's other widths): keys and values of all 64 out of one matmul each,
    one call of the prompt-window kernel, against ``prefill_attention_ref``
    over keys expanded the plain way — a window behind a full earlier one,
    pad tokens at its end."""
    from helpers import latent_window_both_ways

    wide = llama_mod.LlamaConfig(**{**kw, "num_heads": 64})
    li = wide.num_layers - 1
    assert wide.layer_kind(li).attention == "mla"
    layer = llama_mod.init_params(jax.random.PRNGKey(3), wide)["layers"][li]
    assert llama_mod.mla_window_head_blocks(64, 8, 24, 128, wide.v_head_dim, 4) == 1
    got, want = latent_window_both_ways(wide, layer, li, 8, 5)
    assert got.shape == want.shape == (8, 64, wide.v_head_dim)
    assert _close(got, want) < TOL
    assert float(jnp.max(jnp.abs(want[:5]))) > 100 * TOL


def test_the_shares_add_up_to_the_uncut_layer(ref, config, kw):
    """The expert FFN on each of the four chips of the toy's deployment (4
    of 16 experts held, ``expert_first`` 0 / 4 / 8 / 12, slices of ONE uncut
    tree; sixteen shares of 256 at the published sizes), the shared expert
    counted once, add up to the uncut reference: nothing is lost or doubled
    at the shares' edges."""
    whole = llama_mod.LlamaConfig(**{**kw, "experts_held": 0})
    p = llama_mod.init_params(jax.random.PRNGKey(0), whole)["layers"][1]
    u = jax.random.normal(jax.random.PRNGKey(7), (40, 64)) * 0.5
    hp = ref.hyper({**config, "n_routed_experts": 16})
    w = ref.layer_weights(p, "linear", False)
    want, _ = ref.experts(u, w, hp)
    by_reference, by_program = jnp.zeros_like(want), jnp.zeros_like(want)
    from mlmicroservicetemplate_tpu.ops.moe import expert_ffn

    for first in (0, 4, 8, 12):
        cut = {n: w[n][first:first + 4] for n in ("gate", "up", "down")}
        by_reference += ref.experts(u, {**w, **cut}, hp, first=first,
                                    shared=first == 0)[0]
        mlp = {**p["mlp"], **{n: {"kernel": cut[n]} for n in cut}}
        if first:
            mlp.pop("shared")
        by_program += expert_ffn(
            u, mlp, 4, True, jnp.ones((40,), bool), interpret=True,
            score="sigmoid", route_scale=2.5, expert_first=first, limit=0.5)[0]
    assert _close(by_reference, want) < 4 * TOL
    assert _close(by_program, want) < 4 * TOL


@pytest.mark.parametrize("n", [45])
def test_the_wave_forward_is_the_reference(ref, config, cfg, params, n):
    ids = _ids(2 * n, 1).reshape(2, n)
    got = llama_mod.lm_logits(params, cfg, ids, np.ones_like(ids))
    assert _close(got, ref.logits(params, ref.hyper(config), ids)) < TOL


def test_a_prompts_forward_on_a_rung_below_the_top_is_the_reference(
        ref, config, cfg, params, monkeypatch):
    """One prompt of 45 tokens (180 assignments, 4 of 16 experts held:
    rungs 64 and 180 once a rung is 8 rows): the logits are the
    reference's with the expert layers' row work on a lower rung."""
    from helpers import expert_rungs_at_toy_size

    calls = expert_rungs_at_toy_size(monkeypatch)
    ids = _ids(45, 5)[None]
    got = llama_mod.lm_logits(params, cfg, ids, np.ones_like(ids))
    assert _close(got, ref.logits(params, ref.hyper(config), ids)) < TOL
    assert {rungs for rungs, _ in calls} == {(64, 180)}
    assert len(calls) == len(cfg.expert_layers) and min(r for _, r in calls) < 180


def test_prefill_then_decode_is_the_reference(ref, config, cfg, params):
    """A ragged wave's prefill, then six decode steps through the one-token
    delta rule and the absorbed attention: every token the reference's
    argmax on the sequence so far, and the state the steps leave the
    reference's token scan's."""
    ids = _ids(40, 3).reshape(2, 20)
    mask = np.ones((2, 20), np.int32)
    mask[1, 13:] = 0
    state = llama_mod.init_decode_state(params, cfg, ids, mask, 6)
    state, toks = llama_mod.generate_chunk(params, cfg, state, 6)
    hp = ref.hyper(config)
    for b, n in ((0, 20), (1, 13)):
        seq = np.concatenate([ids[b, :n], np.asarray(toks[b])])[None]
        states: list = []
        want = ref.head_logits(params, ref.hidden(params, hp, seq[:, :-1], states=states))
        rows = np.asarray(want[0, n - 1:])
        served = np.asarray(toks[b])
        assert float((rows.max(-1) - rows[np.arange(6), served]).max()) < 1e-5
        for li in range(4):
            assert _close(state.ssm.state[li][b], states[li][0][0]) < TOL


@pytest.fixture(scope="module")
def sound(ref, config, params):
    """One seeded sequence, the reference's logits on it and the DeltaNet
    states its token scan leaves before the last token."""
    ids, states = _ids(40, 2)[None], []
    hp = ref.hyper(config)
    ref.hidden(params, hp, ids[:, :-1], states=states)
    return ids, ref.logits(params, hp, ids)[0], states


@pytest.mark.parametrize("name", sorted(gigachat_variants.VARIANTS))
def test_each_broken_variant_departs_from_the_reference(
        sound, kw, params, name):
    """Clamp, gate, post-norm, the norm's ``2 sigmoid(w)`` and the rest
    each matter: the variant's logits leave the reference's by more than
    the sound program's ever do."""
    ids, want, states = sound
    vkw, vparams, patches = nemotron_variants.broken(
        name, kw, params, gigachat_variants.VARIANTS)
    vcfg = llama_mod.LlamaConfig(**vkw)
    with nemotron_variants.patched(patches):
        got = llama_mod.lm_logits(vparams, vcfg, ids, np.ones_like(ids))[0]
        if name == "state_bf16":
            # One wave reads no stored state: the variant shows in what it
            # LEAVES (the check reads the served stream's row so).
            left = []
            llama_mod.forward_hidden(vparams, vcfg, ids, np.ones_like(ids), ssm_out=left)
            got, want = left[0].state[0], states[0][0]
    rms = float(jnp.sqrt(jnp.mean(jnp.square(got - want))))
    assert rms > 2 * TOL


# ---------------------------------------------------------------------------
# (iii) the configuration


@pytest.mark.parametrize("bad,needle", [
    ({"gdn_key_heads": 0}, "a 'linear' layer needs gdn_key_heads"),
    ({"gdn_value_heads": 3}, "a 'linear' layer needs"),
    ({"layer_types": ["linear"] * 5}, "one attention layer"),
    ({"layer_types": ["full"] * 5}, "Gated-DeltaNet sizes .* need a 'linear' layer"),
    ({"layer_types": ["linear", "conv", "full", "full", "full"]},
     "'window', 'full', 'linear' or 'mamba'"),
])
def test_a_config_that_does_not_add_up_is_refused(kw, bad, needle):
    with pytest.raises(ValueError, match=needle):
        llama_mod.LlamaConfig(**{**kw, **bad})


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


def test_registry_builds_the_configuration(monkeypatch, kw, ref, config):
    from mlmicroservicetemplate_tpu.models.registry import build_model

    bundle = build_model(_svc(monkeypatch, kw))
    c = bundle.cfg
    assert c.layer_types == ("linear",) * 4 + ("full",) and c.mla
    assert c.swiglu_limit == 0.5 and c.norm_gate_weight == 2 and c.attn_gate
    assert c.held == 4 and c.num_experts == 16 and c.sandwich_norm
    assert not getattr(bundle.tokenizer, "add_bos", False)
    ids = _ids(20, 9, vocab=290)[None]
    got = jax.jit(bundle.logits_fn)(bundle.params, ids, np.ones_like(ids))
    want = ref.logits(bundle.params, ref.hyper(config), ids)
    assert _close(got, want) < TOL


@pytest.mark.parametrize("knobs,needle", [
    ({"paged_kv": False}, "PAGED_KV=0 is not supported for a llama config with latent"),
    ({"spec_decode": "ngram"}, "SPEC_DECODE is not supported"),
    ({"quant_kv": "int8"}, "QUANT_KV is not supported"),
    ({"prefix_cache": True}, "PREFIX_CACHE is not supported"),
    ({"prompt_prefix": "w5 w6"}, "PROMPT_PREFIX is not supported"),
    ({"kv_host_budget_mb": 64.0},
     "KV_HOST_BUDGET_MB is not supported.*Gated-DeltaNet.*rebuilt by recompute"),
    ({"kv_host_budget_mb": 0.0, "kv_disk_budget_mb": 64.0, "journal_dir": "/tmp/j"},
     "KV_DISK_BUDGET_MB is not supported|KV_HOST_BUDGET_MB"),
    ({"tp": 2}, "TP=2 is not supported"),
    ({"quantize": "int8"}, "QUANTIZE=int8 is not supported"),
])
def test_registry_refuses_what_reads_neither_state_nor_latent(
        monkeypatch, kw, knobs, needle):
    from mlmicroservicetemplate_tpu.models.registry import build_model

    with pytest.raises(ValueError, match=needle):
        build_model(_svc(monkeypatch, kw, **knobs))
