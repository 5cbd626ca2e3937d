"""The Nemotron-H block through ``models/llama.py`` — layers that are a
Mamba-2 mixer, an attention OR a LatentMoE alone (``layer_pattern``), the
recurrence of ``ops/ssm.py`` and the expert block's shape as data
(``ops/moe.py``: non-gated relu2 experts in a latent) — held to the
benchmark's plain reference (``cellbench/references/nemotron_h.py``) at a
toy size on the CPU in float32: the published pattern's first 11 letters
``MEMEMEM*EME``, 8 Mamba heads of 8 in 2 groups with a state of 16 and a
scan chunk of 8, 4 / 2 attention heads of 24 on d_model 64, 16 experts
top-5 of which 4 held in a latent of 24, a shared expert of 48.

TOL: model and reference both compute in float32 and differ in the order
of sums only (the chunked scan against a scan over tokens, a grouped
matmul against a masked loop over experts): measured 3e-7 on logits of
size 0.16.  The broken rules of ``tools/nemotron_variants.py`` move the
logits' rms by 5e-6 (the route scale: this toy's experts are small beside
its mixers) to 0.23: 2e-6 separates them from the sound program's 6e-8.
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
from tools import nemotron_variants

TOL = 2e-6
TOY = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=24,
    mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
    chunk_size=8, moe_intermediate_size=32, moe_latent_size=24,
    moe_shared_expert_intermediate_size=48, n_routed_experts=4,
    router_experts=16, num_experts_per_tok=5, vocab_size=128,
    max_position_embeddings=256,
)


@pytest.fixture(scope="module")
def config():
    real = bench_spec.load_json(
        bench_spec.HERE + "/configs/nemotron3-super-ep4-d11.json")
    return {**real, **TOY}


@pytest.fixture(scope="module")
def ref():
    return bench_spec.load_module(
        bench_spec.HERE + "/references/nemotron_h.py",
        "cellbench_reference_nemotron_h")


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
# (i) the recurrence: chunked scan = one token at a time


def _scan_inputs(length, b=2, h=8, p=4, g=2, n=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        x=jax.random.normal(ks[0], (b, length, h, p)),
        dt=jax.nn.softplus(jax.random.normal(ks[1], (b, length, h)) - 2),
        a=-jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.7)),
        b=jax.random.normal(ks[3], (b, length, g, n)),
        c=jax.random.normal(ks[4], (b, length, g, n)),
        d=jax.random.normal(ks[5], (h,)),
        s0=jax.random.normal(ks[6], (b, h, p, n)),  # a NON-ZERO initial state
    )


@functools.partial(jax.jit, static_argnames=("chunk", "kernel"))
def _scan(i, mask, chunk=8, kernel=False, s0=None):
    """``ssm_scan`` on ``_scan_inputs``' arrays: x, B and C side by side
    as the convolution leaves them; ``kernel``: the fused chunk kernel in
    interpret mode, else the ``jax.numpy`` form."""
    length = i["x"].shape[1]
    xbc = jnp.concatenate(
        [v.reshape(v.shape[0], length, -1) for v in (i["x"], i["b"], i["c"])], -1)
    return ssm.ssm_scan(
        xbc, i["dt"], i["a"], i["d"], i["s0"] if s0 is None else s0, mask,
        groups=i["b"].shape[2], state=i["b"].shape[3], chunk=chunk,
        kernel=kernel, interpret=True)


def _upto(i, n):
    """The inputs' first ``n`` tokens."""
    return {k: v[:, :n] if k in ("x", "dt", "b", "c") else v for k, v in i.items()}


@jax.jit
def _token_by_token(i, mask, round_to=None):
    """``ssm_step`` a token at a time (one compiled loop: the eager form
    compiles every small operation of every step apart)."""
    def step(s, t):
        x, dt, b, c, live = t
        y, s = ssm.ssm_step(x, dt, i["a"], b, c, i["d"], s, live)
        return s, y

    s, ys = jax.lax.scan(step, i["s0"], tuple(
        jnp.moveaxis(v, 1, 0) for v in (i["x"], i["dt"], i["b"], i["c"], mask != 0)))
    return jnp.moveaxis(ys, 0, 1), s


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("length", [1, 8, 9, 37])
def test_the_chunked_scan_is_the_recurrence(length, kernel):
    """Lengths that the chunk (8) divides and does not, a non-zero initial
    state, row 0 with a masked tail, row 1 masked whole: outputs at the
    real tokens and both final states equal the one-token update run a
    token at a time; the masked row's state has not moved at all."""
    i = _scan_inputs(length)
    mask = jnp.ones((2, length), jnp.int32).at[0, max(1, length - 3):].set(
        0).at[1].set(0)
    y, s = _scan(i, mask, kernel=kernel)
    want_y, want_s = _token_by_token(i, mask)
    n = max(1, length - 3)
    assert y.shape == (2, length, 8 * 4) and s.dtype == jnp.float32
    assert _close(y[0, :n], want_y[0, :n].reshape(n, -1)) < 1e-4
    assert _close(s, want_s) < 1e-4
    assert _close(s[1], i["s0"][1]) == 0.0


@pytest.mark.parametrize("length", [1, 37, 128, 300])
@pytest.mark.parametrize("b", [1, 3])
def test_the_fused_kernel_is_the_scan_and_the_recurrence(b, length):
    """The kernel (interpret mode) against the ``jax.numpy`` scan AND the
    token-by-token update: 8 heads in 2 groups (G < H), a non-zero initial
    state, chunks of 16 that divide no length here but 128; row 0's last
    real chunk partly real, row 1 (of three) with no real token — its
    state comes back bit for bit —, row 2's real prefix two fifths of the
    row, so its last chunks are all fill and run no matmul.  The state
    handed back is float32."""
    i = _scan_inputs(length, b=b, seed=length)
    real = [max(1, length - 3), 0, max(1, 2 * length // 5)][:b]
    mask = (jnp.arange(length)[None, :] < jnp.asarray(real)[:, None]).astype(jnp.int32)
    y, s = _scan(i, mask, chunk=16, kernel=True)
    ref_y, ref_s = _scan(i, mask, chunk=16)
    tok_y, tok_s = _token_by_token(i, mask)
    assert s.dtype == jnp.float32 and y.dtype == jnp.float32
    assert s.shape == i["s0"].shape and y.shape == ref_y.shape
    for row, n in enumerate(real):
        if not n:
            assert _close(s[row], i["s0"][row]) == 0.0
            continue
        assert _close(y[row, :n], ref_y[row, :n]) < 1e-4
        assert _close(y[row, :n], tok_y[row, :n].reshape(n, -1)) < 1e-4
    assert _close(s, ref_s) < 1e-4 and _close(s, tok_s) < 1e-4


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_a_step_after_a_scan_is_one_longer_scan(kernel):
    i = _scan_inputs(21)
    ones = jnp.ones((2, 21), jnp.int32)
    y20, s20 = _scan(_upto(i, 20), ones[:, :20], kernel=kernel)
    y1, s21 = ssm.ssm_step(i["x"][:, 20], i["dt"][:, 20], i["a"], i["b"][:, 20],
                           i["c"][:, 20], i["d"], s20, jnp.ones((2,), bool))
    y21, want = _scan(i, ones, kernel=kernel)
    assert _close(s21, want) < 1e-4 and _close(y1.reshape(2, -1), y21[:, 20]) < 1e-4
    assert _close(y20, y21[:, :20]) < 1e-4


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_two_windows_in_sequence_are_one_scan_of_both(kernel):
    """A prompt's second window continues the state its first left: 24 +
    19 tokens (neither a multiple of the chunk) equal one scan of 43, a
    row whose second window is all fill keeping the first's state."""
    i = _scan_inputs(43, seed=5)
    mask = jnp.ones((2, 43), jnp.int32).at[1, 24:].set(0)
    first = _upto(i, 24)
    y_a, s_a = _scan(first, mask[:, :24], kernel=kernel)
    second = {k: v[:, 24:] if k in ("x", "dt", "b", "c") else v for k, v in i.items()}
    y_b, s_b = _scan(second, mask[:, 24:], kernel=kernel, s0=s_a)
    y, s = _scan(i, mask, kernel=kernel)
    assert _close(s_b, s) < 1e-4 and _close(s_b[1], s_a[1]) == 0.0
    assert _close(y_a, y[:, :24]) < 1e-4 and _close(y_b[0], y[0, 24:]) < 1e-4


def test_the_convolution_keeps_the_last_real_inputs():
    """``conv_scan``'s new taps are the 3 inputs up to each row's last
    REAL token (the old taps for a row with none), and a scan then a step
    is one longer scan."""
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(k[0], (2, 10, 6))
    st = jax.random.normal(k[1], (2, 3, 6))
    w, b = jax.random.normal(k[2], (4, 6)), jax.random.normal(k[3], (6,))
    mask = jnp.ones((2, 10), jnp.int32).at[0, 6:].set(0).at[1].set(0)
    y, new = ssm.conv_scan(x, st, w, b, mask)
    assert _close(new[0], x[0, 3:6]) == 0.0 and _close(new[1], st[1]) == 0.0
    full, _ = ssm.conv_scan(x[:, :7], st, w, b, jnp.ones((2, 7), jnp.int32))
    y7, shifted = ssm.conv_step(x[:, 6], new, w, b, jnp.asarray([True, False]))
    assert _close(y7[0], full[0, 6]) < 1e-6 and _close(y[0, :6], full[0, :6]) < 1e-6
    assert _close(shifted[0], x[0, 4:7]) == 0.0 and _close(shifted[1], new[1]) == 0.0


# ---------------------------------------------------------------------------
# (ii) the layers and the whole model against the reference


def test_the_toy_has_every_kind_of_layer(cfg, params):
    kinds = [cfg.layer_kind(li) for li in range(cfg.num_layers)]
    assert [k.mixer for k in kinds] == [
        "mamba2", None, "mamba2", None, "mamba2", None, "mamba2", "gqa",
        None, "mamba2", None]
    assert [k.ffn for k in kinds] == [c == "E" for c in "MEMEMEM*EME"]
    assert cfg.expert_layers == (1, 3, 5, 8, 10) and cfg.recurrent_layers == (0, 2, 4, 6, 9)
    assert not any(k.rope for k in kinds)  # nope_on_full: no rotation
    assert sorted(params["layers"][0]) == ["ssm", "ssm_ln"]
    assert sorted(params["layers"][7]) == ["attn", "attn_ln"]
    mlp = params["layers"][1]["mlp"]
    assert "gate" not in mlp and "gate" not in mlp["shared"]  # relu2: no gate stack
    assert mlp["up"]["kernel"].shape == (4, 24, 32)  # held experts, in the latent
    assert mlp["router"]["kernel"].shape == (64, 16)  # the published width
    assert cfg.ssm_conv_dim == 128 and cfg.ssm_row_bytes == 5 * (8 * 8 * 16 * 4 + 3 * 128 * 2)


def test_a_mamba_layer_is_the_reference(ref, config, cfg, params):
    hp = ref.hyper(config)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 29, 64)) * 0.5
    zero = llama_mod.zero_ssm(cfg, 1, jnp.float32)
    got, _, _ = llama_mod._mamba_block(
        cfg, params["layers"][0], x, zero.conv[0], zero.state[0],
        mask=jnp.ones((1, 29), jnp.int32))
    want, _ = ref.layer(x[0], ref.layer_weights(params["layers"][0], "M"), hp, "M")
    assert _close(got[0], want) < TOL


def _expert_layer(cfg, layer, x):
    return llama_mod._mlp_block(cfg, layer, 1, x, jnp.ones(x.shape[:2], bool))


def test_a_latent_expert_layer_is_the_reference(ref, config, cfg, params):
    hp = ref.hyper(config)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 33, 64)) * 0.5
    want, chosen = ref.layer(
        x[0], ref.layer_weights(params["layers"][1], "E"), hp, "E")
    assert _close(_expert_layer(cfg, params["layers"][1], x)[0], want) < TOL
    held = (np.asarray(chosen) < 4).mean()
    assert 0.05 < held < 0.6  # some assignments land here, most do not


def test_the_four_shares_add_up_to_the_uncut_layer(ref, config, kw):
    """The expert layer on each of the four chips of the deployment (4 of
    16 experts held, ``expert_first`` 0 / 4 / 8 / 12, slices of ONE uncut
    tree), the shared expert counted once, add up to the uncut reference
    layer: nothing is lost or doubled at the shares' edges."""
    whole = llama_mod.LlamaConfig(**{**kw, "experts_held": 0})
    p = llama_mod.init_params(jax.random.PRNGKey(0), whole)["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 40, 64)) * 0.5
    hp = {**ref.hyper({**config, "n_routed_experts": 16})}
    w = ref.layer_weights(p, "E")
    want, _ = ref.layer(x[0], w, hp, "E")
    u = ref._rmsnorm(x[0], w["ln"], hp["eps"])
    shared = ref._act(u @ w["s_up"], hp) @ w["s_down"]
    total = jnp.zeros_like(want)
    for first in (0, 4, 8, 12):
        share = llama_mod.LlamaConfig(**{**kw, "expert_first": first})
        mlp = {**p["mlp"], **{n: {"kernel": p["mlp"][n]["kernel"][first:first + 4]}
                              for n in ("up", "down")}}
        total += _expert_layer(share, {**p, "mlp": mlp}, x)[0] - x[0] - shared
    assert _close(total + x[0] + shared, want) < 4 * TOL


@pytest.mark.parametrize("n", [45])
def test_the_wave_forward_is_the_reference(ref, config, cfg, params, n):
    ids = _ids(2 * n, 1).reshape(2, n)
    got = llama_mod.lm_logits(params, cfg, ids, np.ones_like(ids))
    assert _close(got, ref.logits(params, ref.hyper(config), ids)) < TOL


def test_a_prompts_forward_on_a_rung_below_the_top_is_the_reference(
        ref, config, cfg, params, monkeypatch):
    """One prompt of 45 tokens (225 assignments, 4 of 16 experts held:
    rungs 72 and 225 once a rung is 8 rows): the logits are the
    reference's with the latent expert layers' row work on a lower rung."""
    from helpers import expert_rungs_at_toy_size

    calls = expert_rungs_at_toy_size(monkeypatch)
    ids = _ids(45, 5)[None]
    got = llama_mod.lm_logits(params, cfg, ids, np.ones_like(ids))
    assert _close(got, ref.logits(params, ref.hyper(config), ids)) < TOL
    assert {rungs for rungs, _ in calls} == {(72, 225)}
    assert len(calls) == len(cfg.expert_layers) and min(r for _, r in calls) < 225


@pytest.mark.parametrize("name", sorted(nemotron_variants.VARIANTS))
def test_each_broken_variant_departs_from_the_reference(
        ref, config, kw, params, name):
    ids = _ids(40, 2)[None]
    want = ref.logits(params, ref.hyper(config), ids)[0]
    vkw, vparams, patches = nemotron_variants.broken(name, kw, params)
    vcfg = llama_mod.LlamaConfig(**vkw)
    with nemotron_variants.patched(patches):
        got = llama_mod.lm_logits(vparams, vcfg, ids, np.ones_like(ids))[0]
        if name == "state_bf16":
            # One wave reads no stored state: the variant shows in what it
            # LEAVES, the states before the last token that a decode state
            # starts from (the check reads the served stream's row so).
            left, sound, states = [], [], []
            llama_mod.forward_hidden(vparams, vcfg, ids, np.ones_like(ids), ssm_out=left)
    if name == "state_bf16":
        llama_mod.forward_hidden(params, vcfg, ids, np.ones_like(ids), ssm_out=sound)
        ref.hidden(params, ref.hyper(config), ids[:, :-1], states=states)
        got, want = left[0].state[0], states[0][0]
        assert _close(sound[0].state[0], want) < TOL
    rms = float(jnp.sqrt(jnp.mean(jnp.square(got - want))))
    assert not rms < 2 * TOL  # NaN (a decay above 1) departs too


def test_a_bf16_state_drifts_where_a_float32_one_does_not():
    """The decode step's state rounded to bfloat16 after every token (the
    variant the wave forward cannot show: it rounds a chunk at a time):
    over 400 steps of slow heads the roundings pile up to several times
    one rounding (2^-9 of the state), which a float32 state never sees."""
    i = _scan_inputs(400, b=1, seed=4)
    i["dt"] = i["dt"] * 0.05  # slow heads: a long memory

    @functools.partial(jax.jit, static_argnames=("bits",))
    def run(bits):
        def step(s, t):
            x, dt, b, c = t
            _, s = ssm.ssm_step(x, dt, i["a"], b, c, i["d"], s, jnp.ones((1,), bool))
            return jax.lax.reduce_precision(s, 8, bits), None

        return jax.lax.scan(step, i["s0"], tuple(
            jnp.moveaxis(v, 1, 0) for v in (i["x"], i["dt"], i["b"], i["c"])))[0]

    exact = run(23)
    drift = _close(run(7), exact) / float(jnp.max(jnp.abs(exact)))
    assert drift > 3 * 2.0 ** -9


# ---------------------------------------------------------------------------
# (iii) the configuration


@pytest.mark.parametrize("case", ["sound", "slow_head_off", "no_slow_head"])
def test_the_checks_state_limit_reads_the_slow_heads_of_the_streams_row(ref, case):
    """``served_state_error``: the nearest row is the stream's, the limit's
    figure is pooled over the heads that KEEP their state alone, and a
    layer without one reads as a wrong row does."""
    import asyncio
    from types import SimpleNamespace as NS

    rng = np.random.default_rng(0)
    want = [rng.normal(size=(4, 3, 5)).astype(np.float32) for _ in range(2)]
    kept = [np.array([-0.5, -1.9, -2.1, -40.0])] * 2  # two slow heads of four
    rows = [rng.normal(size=(3, 4, 3, 5)).astype(np.float32) for _ in want]
    for have, one in zip(rows, want):
        have[2] = one
        have[2, 3] *= 1.05  # a fast head: off, outside the limit's heads
    if case == "slow_head_off":
        rows[1][2, 1] *= 1.02
    if case == "no_slow_head":
        kept = [kept[0], np.full(4, -3.0)]
    cdl = NS(idle=lambda: True, _state=NS(ssm=NS(state=[jnp.asarray(r) for r in rows])))
    out = asyncio.run(ref.served_state_error(NS(batcher=NS(_cdl=cdl)), want, kept))
    assert out["state_row"] == [2, 2]
    assert out["state_slow_heads"] == ([2, 0] if case == "no_slow_head" else [2, 2])
    assert all(0.01 < e < 0.05 for e in out["state_rel_err"])  # the fast head's 5 %
    slow = out["state_slow_rel_err"]
    assert slow[0] < 1e-6
    if case == "sound":
        assert slow[1] < 1e-6
    elif case == "slow_head_off":
        assert 0.005 < slow[1] < 0.02 and slow[1] > ref.STATE_SLOW_REL
    else:
        assert slow[1] == 1.0 > ref.STATE_SLOW_REL


@pytest.mark.parametrize("bad,needle", [
    ({"layer_pattern": "MEMEMEMXEME"}, "layer_pattern must name each"),
    ({"layer_pattern": "MEMEMEMMEME"}, "at least one"),
    ({"layer_pattern": "MEM"}, "layer_pattern must name each"),
    ({"ssm_heads": 0}, "an 'M' layer needs ssm_heads"),
    ({"ssm_groups": 3}, "an 'M' layer needs ssm_heads"),
    ({"num_experts": 0, "experts_per_token": 0, "experts_held": 0}, "needs num_experts"),
    ({"layer_types": ("full",) * 11}, "stands instead of layer_types"),
    ({"num_dense_layers": 1, "d_ff_dense": 8}, "stands instead of layer_types"),
    ({"expert_act": "gelu"}, "expert_act"),
    ({"layer_pattern": "*E*E*E*E*E*"}, "need an 'M' or a 'mamba' layer"),
])
def test_a_pattern_that_does_not_add_up_is_refused(kw, bad, needle):
    with pytest.raises(ValueError, match=needle):
        llama_mod.LlamaConfig(**{**kw, **bad})


@pytest.mark.parametrize("over", [
    dict(num_kv_heads=2, d_ff=96),
    dict(num_kv_heads=4, d_ff=32, num_experts=8, experts_per_token=2),
    dict(num_kv_heads=2, d_ff=32, num_experts=8, experts_per_token=2,
         num_dense_layers=1, d_ff_dense=96, layer_types=("window", "full", "window"),
         window=8, nope_on_full=True),
], ids=["dense", "experts", "pattern-of-types"])
def test_a_config_without_a_pattern_builds_the_kinds_it_built(over):
    c = llama_mod.LlamaConfig(vocab_size=97, d_model=64, num_heads=4, num_layers=3,
                              max_position=64, pallas_interpret=True, **over)
    assert c.layer_pattern == "" and c.recurrent_layers == () and c.ssm_row_bytes == 0
    for li in range(3):
        k = c.layer_kind(li)
        window = c.window if c.layer_types and c.layer_types[li] == "window" else 0
        dense = li < c.num_dense_layers
        assert k == llama_mod.LayerKind(
            window, bool(window) or not c.nope_on_full,
            bool(c.num_experts) and not dense, c.d_ff_dense if dense else c.d_ff)
        assert (k.mixer, k.ffn, k.recurrent) == ("gqa", True, False)
    # and its decode states carry no recurrent leaf
    p = llama_mod.init_params(jax.random.PRNGKey(0), c)
    ids = _ids(6, 3, 90)[None]
    st = llama_mod.init_decode_state(p, c, ids, np.ones_like(ids), 4)
    assert st.ssm == () and llama_mod.zero_ssm(c, 2, jnp.float32) == ()


def test_a_width_1024_does_not_divide_is_tiled_by_its_own_divisors():
    """2688 = 21 x 128 and DeepSeek-V2's 1536 (which ran a ragged 1024
    until PR 45): whole, or by a divisor that is a multiple of 128 — never
    a ragged last tile (``tests/test_moe.py`` pins what the cells take)."""
    assert moe._tile_sizes(2688) == [2688, 896, 384, 128]
    assert moe._tile_sizes(1536) == [1536, 768, 512, 384, 256, 128]


# ---------------------------------------------------------------------------
# (iv) the registry: builds it, and refuses what cannot carry the state


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


def test_registry_builds_the_pattern(monkeypatch, kw, ref, config):
    from mlmicroservicetemplate_tpu.models.registry import build_model

    bundle = build_model(_svc(monkeypatch, kw))
    c = bundle.cfg
    assert c.layer_pattern == "MEMEMEM*EME" and c.expert_act == "relu2"
    assert c.moe_latent == 24 and c.held == 4 and c.num_experts == 16
    assert not getattr(bundle.tokenizer, "add_bos", False)
    ids = _ids(20, 9, vocab=290)[None]
    got = jax.jit(bundle.logits_fn)(bundle.params, ids, np.ones_like(ids))
    want = ref.logits(bundle.params, ref.hyper(config), ids)
    assert _close(got, want) < TOL


@pytest.mark.parametrize("knobs,needle", [
    ({"paged_kv": False}, "PAGED_KV=0 is not supported for a llama config with Mamba"),
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
def test_registry_refuses_what_does_not_carry_the_state(
        monkeypatch, kw, knobs, needle):
    from mlmicroservicetemplate_tpu.models.registry import build_model

    with pytest.raises(ValueError, match=needle):
        build_model(_svc(monkeypatch, kw, **knobs))
