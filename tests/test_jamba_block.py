"""The Jamba block through ``models/llama.py`` — Mamba-1 mixers
(``layer_types`` "mamba"; ``ops/ssm.mamba1_scan`` / ``mamba1_step``) to one
multi-query attention layer (5 query heads on ONE KV head, not rotated),
each followed by a dense SwiGLU MLP, the head tied to the embedding — held
to the benchmark's plain reference (``cellbench/references/jamba.py``) at a
toy size on the CPU in float32: one period of the published pattern (13
Mamba + 1 attention at offset 7), hidden 80, inner 160, state 4, dt rank 8.

TOL: model and reference both compute in float32 and differ in the order of
sums only (the unrolled chunk loop against a scan over tokens): measured
1e-7 on logits; the broken rules of ``tools/jamba_variants.py`` move the
logits' rms by 1e-3 (the rotation: one layer of fourteen) to 0.26.
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
from tools import jamba_variants, nemotron_variants

TOL = 2e-6


@pytest.fixture(scope="module")
def config():
    real = bench_spec.load_json(bench_spec.HERE + "/configs/jamba2-3b-d28.json")
    toy = bench_spec.load_json(
        bench_spec.HERE + "/tests/rehearse_jamba.json")["config"]
    toy = {k: v for k, v in toy.items() if k not in ("env", "expect_cfg", "prompt")}
    return {**real, **toy, "vocab_size": 128}


@pytest.fixture(scope="module")
def ref():
    return bench_spec.load_module(
        bench_spec.HERE + "/references/jamba.py", "cellbench_reference_jamba")


@pytest.fixture(scope="module")
def kw(config):
    out = json.loads(bench_spec.service_env(config)["LLAMA_CONFIG"])
    return {**out, "eos_id": 1, "pad_id": 0, "pallas_interpret": True}


@pytest.fixture(scope="module")
def cfg(kw):
    return llama_mod.LlamaConfig(**kw)


#: Four layers of the toy (Mamba, attention, Mamba, Mamba) for what needs no
#: whole period: a variant's forward compiles in a quarter of the time.
SMALL = {"num_hidden_layers": 4, "attn_layer_period": 4, "attn_layer_offset": 1}
SMALL_TYPES = ["mamba", "attention", "mamba", "mamba"]


@pytest.fixture(scope="module")
def small(config, kw):
    """``(config, kwargs, params)`` of the four-layer toy."""
    skw = {**kw, "num_layers": 4, "layer_types": SMALL_TYPES}
    return ({**config, **SMALL}, skw, llama_mod.init_params(
        jax.random.PRNGKey(0), llama_mod.LlamaConfig(**skw)))


@pytest.fixture(scope="module")
def params(cfg):
    return llama_mod.init_params(jax.random.PRNGKey(0), cfg)


def _ids(n, seed=0, vocab=120):
    return np.random.default_rng(seed).integers(3, vocab, n).astype(np.int32)


def _close(got, want):
    return float(jnp.max(jnp.abs(jnp.asarray(got) - jnp.asarray(want))))


# ---------------------------------------------------------------------------
# (i) the recurrence: the unrolled chunk loop = one token at a time


def _scan_inputs(length, b=2, ch=12, n=4, seed=0, step=0.0):
    """``mamba1_scan``'s operands; ``step`` shifts the softplus' argument (a
    large one makes ``Delta A`` of a chunk sum to hundreds)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        x=jax.random.normal(ks[0], (b, length, ch)),
        delta=jax.nn.softplus(jax.random.normal(ks[1], (b, length, ch)) - 2 + step),
        a=-jnp.exp(jax.random.uniform(ks[2], (n, ch), minval=0.0, maxval=2.7)),
        b=jax.random.normal(ks[3], (b, length, n)),
        c=jax.random.normal(ks[4], (b, length, n)),
        d=jax.random.normal(ks[5], (ch,)),
        s0=jax.random.normal(ks[6], (b, n, ch)),  # a NON-ZERO initial state
    )


_SEQ = ("x", "delta", "b", "c")
_ORDER = ("x", "delta", "a", "b", "c", "d")


@functools.partial(jax.jit, static_argnames=("chunk", "kernel"))
def _scan(i, mask, chunk=8, s0=None, kernel=False):
    """``mamba1_scan`` on ``_scan_inputs``' arrays; ``kernel``: the fused
    kernel in interpret mode, else the ``jax.numpy`` loop."""
    return ssm.mamba1_scan(*(i[k] for k in _ORDER),
                           i["s0"] if s0 is None else s0, mask, chunk=chunk,
                           kernel=kernel, interpret=True)


#: The scan's two forms: every property below holds of both.
FORMS = pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])


def _upto(i, lo, hi):
    return {k: v[:, lo:hi] if k in _SEQ else v for k, v in i.items()}


@functools.partial(jax.jit, static_argnames=("bits",))
def _token_by_token(i, mask, bits=23):
    """The reference's recurrence (``cellbench/references/jamba.mamba``'s
    step) a token at a time, a row at a time, a masked token skipped."""
    def step(h, t):
        x, delta, b, c, live = t
        new = (jnp.exp(delta[:, None, :] * i["a"]) * h
               + (delta * x)[:, None, :] * b[:, :, None])
        new = jax.lax.reduce_precision(
            jnp.where(live[:, None, None] != 0, new, h), 8, bits)
        return new, jnp.sum(new * c[:, :, None], axis=1) + i["d"] * x

    h, y = jax.lax.scan(step, i["s0"], tuple(
        jnp.moveaxis(v, 1, 0) for v in (*(i[k] for k in _SEQ), mask)))
    return jnp.moveaxis(y, 0, 1), h


def _mask(lens, length):
    return jnp.asarray(np.arange(length)[None, :] < np.asarray(lens)[:, None],
                       jnp.int32)


@FORMS
@pytest.mark.parametrize("chunk", [8, 5])
@pytest.mark.parametrize("length,lens", [
    (1, (1, 0)), (8, (8, 3)), (9, (9, 8)), (37, (37, 20))])
def test_the_chunk_loop_is_the_recurrence(length, lens, chunk, kernel):
    """Every length around the chunk's edge, one row shorter than the
    other: outputs on the real tokens and the final state agree; a row's
    padded tail (and a row with no token at all) moves no state.  (The
    kernel's token block is its own constant: ``chunk`` is the loop's.)"""
    i, mask = _scan_inputs(length), _mask(lens, length)
    y, s = _scan(i, mask, chunk=chunk, kernel=kernel)
    y1, s1 = _token_by_token(i, mask)
    assert _close(y * mask[..., None], y1 * mask[..., None]) < 1e-5
    assert _close(s, s1) < 1e-5
    if lens[1] == 0:
        assert _close(s[1], i["s0"][1]) == 0.0


@FORMS
@pytest.mark.parametrize("cut", [8, 13])
def test_two_windows_in_sequence_are_one_scan_of_both(cut, kernel):
    """A prompt's second window continues the state its first one left — on
    a chunk's edge and off it — and a decode step continues a window."""
    i, mask = _scan_inputs(30, seed=2), _mask((30, 21), 30)
    y_whole, whole = _scan(i, mask, kernel=kernel)
    _, s_a = _scan(_upto(i, 0, cut), mask[:, :cut], kernel=kernel)
    y_b, s_b = _scan(_upto(i, cut, 30), mask[:, cut:], s0=s_a, kernel=kernel)
    assert _close(s_b, whole) < 1e-5 and _close(y_b[0], y_whole[0, cut:]) < 1e-5
    # one more token by the step = a scan one token longer
    j = _scan_inputs(31, seed=2)
    _, s30 = _scan(_upto(j, 0, 30), _mask((30, 30), 30), kernel=kernel)
    y_step, s_step = jax.jit(ssm.mamba1_step)(
        j["x"][:, 30], j["delta"][:, 30], j["a"], j["b"][:, 30], j["c"][:, 30],
        j["d"], s30, jnp.asarray([True, False]))
    y_long, s_long = _scan(j, _mask((31, 30), 31), kernel=kernel)
    assert _close(s_step, s_long) < 1e-5 and _close(y_step[0], y_long[0, 30]) < 1e-5
    assert _close(s_step[1], s30[1]) == 0.0  # the row that is not live


@FORMS
def test_a_chunk_whose_decay_would_overflow_a_factored_form_stays_finite(kernel):
    """Steps of about 10 with ``A`` to -15: a chunk's running sum of
    ``Delta A`` passes -1000, so ``exp(-cs)`` of a factored chunk
    (``exp(cs_t) sum_s exp(-cs_s) ..``) is infinite in float32 — the
    composed recurrence reads the token scan's numbers."""
    i, mask = _scan_inputs(24, seed=4, step=12.0), _mask((24, 17), 24)
    cs = jnp.cumsum(i["delta"][:, :8, None, :] * i["a"], axis=1)
    assert not bool(jnp.isfinite(jnp.exp(-cs)).all())  # the trap is real here
    y, s = _scan(i, mask, kernel=kernel)
    y1, s1 = _token_by_token(i, mask)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(s).all())
    scale = float(jnp.max(jnp.abs(y1))) + 1.0
    assert _close(y * mask[..., None], y1 * mask[..., None]) < 1e-5 * scale
    assert _close(s, s1) < 1e-5 * scale


@pytest.mark.parametrize("length", [37, 300, 600])
@pytest.mark.parametrize("lens", [(1.0,), (1.0, 0.0, 0.4)], ids=["b1", "b3"])
def test_the_fused_kernel_is_the_loop_and_the_recurrence(lens, length):
    """The kernel (interpret mode) against the ``jax.numpy`` loop AND the
    token scan, over lengths no multiple of its token block (one block, two
    and three): a batch of one and of three; row 0 whole, row 1 with no real
    token — its state comes back bit for bit —, row 2's real prefix two
    fifths of the row, so its last blocks are all fill and fold nothing.
    Past a row's last real token y is zeros."""
    lens = tuple(int(f * length) for f in lens)
    i, mask = _scan_inputs(length, b=len(lens), seed=length), _mask(lens, length)
    y, s = _scan(i, mask, kernel=True)
    ref_y, ref_s = _scan(i, mask)
    tok_y, tok_s = _token_by_token(i, mask)
    assert s.dtype == y.dtype == jnp.float32
    assert s.shape == i["s0"].shape and y.shape == ref_y.shape
    for row, n in enumerate(lens):
        assert n == length or _close(y[row, n:], 0.0) == 0.0
        if not n:
            assert _close(s[row], i["s0"][row]) == 0.0
            continue
        assert _close(y[row, :n], ref_y[row, :n]) < 1e-5
        assert _close(y[row, :n], tok_y[row, :n]) < 1e-5
    assert _close(s, ref_s) < 1e-5 and _close(s, tok_s) < 1e-5


def test_a_masked_tail_leaves_the_state_bit_for_bit():
    """The same row scanned with 40 and with 400 masked positions behind
    its 21 real tokens (a window's unfilled tail: one partly real block |
    that and a wholly masked one): the two states are EQUAL, not close."""
    i = _scan_inputs(400, b=1, seed=7)
    _, short = _scan(_upto(i, 0, 61), _mask((21,), 61), kernel=True)
    y, long = _scan(i, _mask((21,), 400), kernel=True)
    assert _close(short, long) == 0.0 and _close(y[0, 21:], 0.0) == 0.0


@pytest.mark.parametrize("channels,states,fits", [
    (5120, 16, True), (1536, 8, True), (5100, 16, False), (5120, 64, False)])
def test_the_kernels_shape_gate(channels, states, fits):
    """Whole lane tiles of channels (and no more states than the loop's body
    holds in registers) take the kernel — the served 5120 x 16 among them, in
    tiles of 1024 channels —, anything else the ``jax.numpy`` loop; the
    interpreter takes any widths."""
    assert ssm._mamba1_kernel_fits(channels, states, False) == fits
    assert ssm._mamba1_kernel_fits(channels, states, True)
    if fits:
        tile = ssm._mamba1_tile(channels)
        assert channels % tile == 0 and tile % ssm.LANES == 0
        assert tile == {5120: 1024, 1536: 512}[channels]


def test_a_width_no_lane_tile_divides_falls_back_and_still_agrees(monkeypatch):
    """At 12 channels x 4 states with the interpreter off the gate refuses:
    ``kernel=True`` runs the ``jax.numpy`` loop (no kernel is traced) and
    answers as ``kernel=False`` does, bit for bit."""
    i, mask = _scan_inputs(20), _mask((20, 11), 20)

    def no_kernel(*a, **k):
        raise AssertionError("the gate let the kernel through")

    monkeypatch.setattr(ssm, "_mamba1_kernel_call", no_kernel)
    args = (*(i[k] for k in _ORDER), i["s0"], mask)
    y, s = ssm.mamba1_scan(*args, kernel=True)
    y0, s0 = ssm.mamba1_scan(*args)
    assert _close(y, y0) == 0.0 and _close(s, s0) == 0.0


def test_a_bf16_state_drifts_where_a_float32_one_does_not():
    """The state rounded to bfloat16 after every token: over 400 steps of
    slow elements the roundings pile up to several times one rounding (2^-9
    of the state), which a float32 state never sees."""
    i = _scan_inputs(400, b=1, seed=5)
    i["delta"] = i["delta"] * 0.02  # small steps: a long memory
    mask = _mask((400,), 400)
    exact = _token_by_token(i, mask)[1]
    assert _close(_scan(i, mask)[1], exact) < 1e-5
    drift = _close(_token_by_token(i, mask, bits=7)[1], exact) / float(
        jnp.max(jnp.abs(exact)))
    assert drift > 3 * 2.0 ** -9


# ---------------------------------------------------------------------------
# (ii) the layers and the whole model against the reference


def test_the_toy_is_one_period_of_the_pattern(cfg, params):
    kinds = [cfg.layer_kind(li) for li in range(cfg.num_layers)]
    assert [k.mixer for k in kinds] == ["mamba1"] * 7 + ["gqa"] + ["mamba1"] * 6
    assert all(k.ffn and not k.experts and not k.rope for k in kinds)
    assert cfg.cache_layers == (7,) and len(cfg.recurrent_layers) == 13
    assert (cfg.n_rep, cfg.num_kv_heads, cfg.head_dim) == (5, 1, 16)
    assert cfg.recurrent_shapes(0) == ((3, 160), (4, 160))
    assert cfg.ssm_row_bytes == 13 * (4 * 160 * 4 + 3 * 160 * 2)
    assert sorted(params) == ["embed", "final_ln", "layers"]  # tied: no lm_head
    assert sorted(params["layers"][0]) == ["mlp", "mlp_ln", "ssm", "ssm_ln"]
    assert sorted(params["layers"][7]) == ["attn", "attn_ln", "mlp", "mlp_ln"]
    m = params["layers"][0]["ssm"]
    assert m["in"]["kernel"].shape == (80, 320) and m["x_proj"]["kernel"].shape == (160, 16)
    assert m["dt_proj"]["kernel"].shape == (8, 160) and m["A_log"].shape == (4, 160)
    np.testing.assert_allclose(np.exp(m["A_log"][:, 3]), [1, 2, 3, 4], rtol=1e-6)
    z = llama_mod.zero_ssm(cfg, 3, jnp.float32)
    assert [s.shape for s in z.state] == [(3, 4, 160)] * 13
    assert [c.shape for c in z.conv] == [(3, 3, 160)] * 13


@pytest.mark.parametrize("li", [0, 7], ids=["mamba", "attention"])
def test_a_layer_is_the_reference(ref, config, cfg, params, li):
    """One layer of each kind on random rows: the wave forward's mixer (the
    chunk loop from zeros | 5 heads on one KV head, not rotated) and MLP."""
    hp = ref.hyper(config)
    x = jax.random.normal(jax.random.PRNGKey(5 + li), (1, 33, 80)) * 0.5
    kind, layer = hp["kinds"][li], params["layers"][li]
    want, left = ref.layer(x[0], ref.layer_weights(layer, kind), hp, kind)
    mask = jnp.ones((1, 33), jnp.int32)
    if kind == "mamba":
        z = llama_mod.zero_ssm(cfg, 1, jnp.float32)
        got, _, s = llama_mod._mamba1_block(cfg, layer, x, z.conv[0], z.state[0],
                                            mask=mask)
        assert _close(s[0], left[0]) < TOL
    else:
        q, k, v, g = llama_mod._qkv_rope(cfg, layer, None, li, x, None, None)
        ctx = llama_mod.mha_attention(
            q, llama_mod._repeat_kv(k, 5), llama_mod._repeat_kv(v, 5),
            mask=jnp.tril(jnp.ones((33, 33), bool))[None, None])
        got = llama_mod._attn_out(cfg, layer, None, li, x, ctx, g)
    got = llama_mod._mlp_block(cfg, layer, li, got, mask != 0)
    assert _close(got[0], want) < TOL


def test_the_wave_forward_is_the_reference(ref, config, cfg, params):
    ids = _ids(90, 1).reshape(2, 45)
    got = jax.jit(lambda p: llama_mod.lm_logits(p, cfg, ids, np.ones_like(ids)))(params)
    assert _close(got, ref.logits(params, ref.hyper(config), ids)) < TOL


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
        assert float((rows.max(-1) - rows[np.arange(6), served]).max()) < 1e-5
        for li in range(13):
            assert _close(state.ssm.state[li][b], states[li][0][0]) < TOL


@pytest.fixture(scope="module")
def sound(ref, small):
    """One seeded sequence, the reference's logits on it and the Mamba states
    its token scan leaves before the last token (the four-layer toy)."""
    ids, states = _ids(40, 2)[None], []
    hp = ref.hyper(small[0])
    ref.hidden(small[2], hp, ids[:, :-1], states=states)
    return ids, ref.logits(small[2], hp, ids)[0], states


@pytest.mark.parametrize("name", sorted(jamba_variants.VARIANTS))
def test_each_broken_variant_departs_from_the_reference(sound, small, name):
    """Each inner norm's scale, the convolution's bias, ``D``, a norm behind
    the gate, a rotation and an untied head each matter: the variant's
    logits leave the reference's by more than the sound program's ever do."""
    ids, want, states = sound
    vkw, vparams, patches = nemotron_variants.broken(
        name, small[1], small[2], jamba_variants.VARIANTS)
    vcfg = llama_mod.LlamaConfig(**vkw)

    @jax.jit
    def run(p):
        # One wave reads no stored state: ``state_bf16`` shows in what it
        # LEAVES (the check reads the served stream's row so).
        left = []
        x = llama_mod.forward_hidden(p, vcfg, ids, np.ones_like(ids), ssm_out=left)
        return llama_mod._head_logits(p, vcfg, x)[0], left[0].state[0]

    with nemotron_variants.patched(patches):
        got, state = run(vparams)
    # with ``ssm_out`` the last position's row is no forward pass's
    got, want = got[:-1], want[:-1]
    if name == "state_bf16":
        got, want = state, states[0][0]
    rms = float(jnp.sqrt(jnp.mean(jnp.square(got - want))))
    assert rms > 2 * TOL


@pytest.mark.parametrize("case", ["sound", "slow_element_off", "fast_element_off"])
def test_the_checks_state_limit_reads_the_slow_elements_of_the_streams_row(ref, case):
    """``served_state_error`` on a loop that holds five rows of two layers:
    the stream's row is found by distance, an error on a SLOW element (one
    that keeps its value over the answer) reads past the limit, the same
    error on a fast one does not move the slow reading."""
    import asyncio
    import types

    rng = np.random.default_rng(0)
    want = [rng.normal(size=(4, 6)).astype(np.float32) for _ in range(2)]
    kept = [np.where(np.arange(4)[:, None] == 0, -0.5, -40.0) * np.ones((4, 6))
            for _ in range(2)]
    rows = [np.stack([rng.normal(size=(4, 6)).astype(np.float32) for _ in range(5)])
            for _ in range(2)]
    for layer, w in zip(rows, want):
        layer[3] = w
    if case != "sound":
        rows[1][3, 0 if case == "slow_element_off" else 2, 1] += 0.2
    loop = types.SimpleNamespace(
        idle=lambda: True, _state=types.SimpleNamespace(ssm=types.SimpleNamespace(
            state=[jnp.asarray(r) for r in rows])))
    svc = types.SimpleNamespace(batcher=types.SimpleNamespace(_cdl=loop))
    out = asyncio.run(ref.served_state_error(svc, want, kept))
    assert out["state_row"] == [3, 3] and out["state_slow_elements"] == [6, 6]
    worst = max(out["state_slow_rel_err"])
    if case == "slow_element_off":
        assert worst > ref.STATE_SLOW_REL
    else:
        assert worst == 0.0
        assert (max(out["state_rel_err"]) > 0) == (case == "fast_element_off")


# ---------------------------------------------------------------------------
# (iii) the configuration


@pytest.mark.parametrize("bad,needle", [
    ({"ssm_dt_rank": 0}, "a 'mamba' layer needs ssm_heads"),
    ({"ssm_head_dim": 2}, "ssm_head_dim 1 and ssm_groups 1"),
    ({"layer_types": ["mamba"] * 14}, "one attention layer"),
    ({"layer_types": ["attention"] * 14, "ssm_heads": 0, "ssm_head_dim": 0,
      "ssm_groups": 0, "ssm_state": 0}, "ssm_dt_rank=8 needs a 'mamba' layer"),
    ({"layer_types": ["attention"] * 14, "ssm_dt_rank": 0},
     "need an 'M' or a 'mamba' layer"),
    ({"layer_types": ["mamba", "conv"] + ["full"] * 12},
     "'window', 'full', 'linear' or 'mamba'"),
])
def test_a_config_that_does_not_add_up_is_refused(kw, bad, needle):
    with pytest.raises(ValueError, match=needle):
        llama_mod.LlamaConfig(**{**kw, **bad})


def test_one_table_names_every_recurrent_kind(cfg):
    """``RECURRENT`` is what ``LayerKind.recurrent``, ``recurrent_shapes`` and
    ``_recurrent_block`` read: a kind in it keeps a state row, no other."""
    assert set(llama_mod.RECURRENT) == {"mamba2", "gdn", "mamba1"}
    for mixer in (*llama_mod.RECURRENT, "gqa", "mla", None):
        kind = llama_mod.LayerKind(0, False, False, 8, mixer=mixer)
        assert kind.recurrent == (mixer in llama_mod.RECURRENT)
    assert llama_mod._recurrent_block(cfg, 0) is llama_mod._mamba1_block
    assert llama_mod.RECURRENT["mamba1"].shapes(cfg) == cfg.recurrent_shapes(0)


@pytest.mark.parametrize("name,fused", [
    ("jamba2-3b-d28", True), ("nemotron3-super-ep4-d11", True),
    ("gigachat35-ep16-d5", True), ("mistral-7b-d8", False),
    ("trinity-mini-d5", False)])
def test_scan_fused_is_the_gate_the_scans_apply(name, fused):
    """``LlamaConfig.scan_fused`` (what ``ssm_scan_fused_tokens_total`` counts
    by) at the benchmark's configurations: the three recurrences' served
    widths pass their kernels' shape gates (``RECURRENT[kind].fits``) once
    the decode step runs its kernels; a configuration with no recurrent
    layer, and any with the kernels off, is not fused."""
    config = bench_spec.load_json(f"{bench_spec.HERE}/configs/{name}.json")
    kwargs = json.loads(bench_spec.service_env(config)["LLAMA_CONFIG"])
    for kernels in (False, True):
        c = llama_mod.LlamaConfig(**kwargs, pallas_decode=kernels, eos_id=2, pad_id=0)
        assert c.scan_fused == (fused and kernels)


def _svc(monkeypatch, kw, **knobs):
    from mlmicroservicetemplate_tpu.utils.config import ServiceConfig

    over = {k: v for k, v in kw.items()
            if k not in ("eos_id", "pad_id", "pallas_interpret")}
    over.update(num_layers=4, layer_types=SMALL_TYPES)
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
    assert c.layer_types == ("mamba", "full", "mamba", "mamba")
    assert c.tie_embeddings and c.nope_on_full and c.ssm_dt_rank == 8
    assert "lm_head" not in bundle.params
    assert not getattr(bundle.tokenizer, "add_bos", False)
    ids = _ids(20, 9, vocab=290)[None]
    got = jax.jit(bundle.logits_fn)(bundle.params, ids, np.ones_like(ids))
    want = ref.logits(bundle.params, ref.hyper({**config, **SMALL}), ids)
    assert _close(got, want) < TOL


@pytest.mark.parametrize("knobs,needle", [
    ({"paged_kv": False}, "PAGED_KV=0 is not supported for a llama config with Mamba-1"),
    ({"spec_decode": "ngram"}, "SPEC_DECODE is not supported"),
    ({"quant_kv": "int8"}, "QUANT_KV is not supported"),
    ({"prefix_cache": True}, "PREFIX_CACHE is not supported"),
    ({"prompt_prefix": "w5 w6"}, "PROMPT_PREFIX is not supported"),
    ({"kv_host_budget_mb": 64.0},
     "KV_HOST_BUDGET_MB is not supported.*Mamba-1.*rebuilt by recompute"),
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


def test_a_tied_head_alone_is_refused_under_tp(monkeypatch):
    """``tie_embeddings`` on an otherwise plain config: no TP spec shards a
    table read twice, so the boot says so."""
    from mlmicroservicetemplate_tpu.models.registry import build_model
    from mlmicroservicetemplate_tpu.utils.config import ServiceConfig

    monkeypatch.setenv("LLAMA_CONFIG", json.dumps({
        "vocab_size": 300, "d_model": 32, "num_heads": 4, "num_kv_heads": 2,
        "num_layers": 2, "d_ff": 48, "tie_embeddings": True}))
    with pytest.raises(ValueError, match="TP=2 is not supported.*a tied head"):
        build_model(ServiceConfig(device="cpu", model_name="llama", warmup=False,
                                  seq_buckets=(16,), max_decode_len=8, tp=2))
