"""The Trinity (AFMoE) block through ``models/llama.py`` — a per-layer
pattern (window | full attention x dense | expert FFN), a head_dim of
its own, sigmoid routing with a selection bias, a scale and a shared
expert, per-head q/k-norm, sandwich norms, an attention gate, no
positional encoding on full layers, a scaled embedding — held to the
benchmark's plain reference (``cellbench/references/trinity.py``) at a
toy size on the CPU in float32: 6 layers of which 2 dense, pattern
(w, w, w, f, w, w), window 8, 8 experts top-2 + 1 shared, head_dim 24 on
d_model 64 / 4 heads.

TOL: model and reference both compute in float32 and differ in the
order of sums only (a grouped matmul against a masked loop over experts,
a masked softmax against a band): measured 3e-6 on logits of size ~0.7.
Every one of the seven broken rules of ``tools/trinity_variants.py``
moves a logit by 20 x TOL or more at this size (each shown failing
below), so 2e-4 separates them with room both ways.
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
from tools import trinity_variants

TOL = 2e-4
W = 8  # the toy's window
# The toy by its PUBLISHED names: laid over the benchmark's configuration
# file, so that the file's own LLAMA_CONFIG mapping is what builds it.
TOY = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=24,
    intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=6,
    num_dense_layers=2, num_experts=8, num_experts_per_tok=2, sliding_window=W,
    vocab_size=128, max_position_embeddings=128,
)


@pytest.fixture(scope="module")
def config():
    real = bench_spec.load_json(bench_spec.HERE + "/configs/trinity-mini-d5.json")
    return {**real, **TOY}


@pytest.fixture(scope="module")
def ref():
    return bench_spec.load_module(
        bench_spec.HERE + "/references/trinity.py", "cellbench_reference_trinity")


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


def test_the_toy_has_every_kind_of_layer(cfg, params):
    kinds = [cfg.layer_kind(li) for li in range(cfg.num_layers)]
    assert [bool(k.window) for k in kinds] == [True, True, True, False, True, True]
    assert [k.rope for k in kinds] == [True, True, True, False, True, True]
    assert [k.experts for k in kinds] == [False, False, True, True, True, True]
    assert [k.d_ff for k in kinds] == [96, 96, 32, 32, 32, 32]
    assert cfg.expert_layers == (2, 3, 4, 5)
    assert cfg.head_dim == 24 != cfg.d_model // cfg.num_heads and cfg.q_dim == 96
    a, m = params["layers"][2]["attn"], params["layers"][2]["mlp"]
    assert a["q"]["kernel"].shape == (64, 96) and a["o"]["kernel"].shape == (96, 64)
    assert a["gate"]["kernel"].shape == (64, 96) and a["q_norm"]["scale"].shape == (24,)
    assert m["router_bias"].shape == (8,) and float(jnp.abs(m["router_bias"]).max()) > 0
    assert m["shared"]["gate"]["kernel"].shape == (64, 32)
    assert params["layers"][0]["mlp"]["gate"]["kernel"].shape == (64, 96)
    assert "router" not in params["layers"][1]["mlp"]


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

    shape = (nb, bs, cfg.num_kv_heads * cfg.head_dim)
    t_w = nb // rows
    return PagedState(
        cache_k=[jnp.zeros(shape) for _ in range(cfg.num_layers)],
        cache_v=[jnp.zeros(shape) for _ in range(cfg.num_layers)],
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
    together -> (logits [B, steps, V], tokens)."""
    rows = len(prompts)
    state, table = _prefill(params, cfg, prompts, steps, chunk, bs, t_w)
    lens = np.asarray([len(p) for p in prompts])
    valid = (np.arange(t_w * bs)[None] < (lens - 1)[:, None]).astype(np.int32)
    state = state._replace(
        key_valid=jnp.asarray(valid), write_idx=jnp.asarray(lens - 1),
        last_token=jnp.asarray([p[-1] for p in prompts]))
    seen, toks = _Logits(monkeypatch), []
    for _ in range(steps):
        state, (tok, counts) = llama_mod._paged_decode_step(params, cfg, state, table)
        toks.append(np.asarray(tok))
        np.testing.assert_array_equal(  # [expert layers, E]: k a live row a layer
            np.asarray(counts.sum(axis=1)),
            [rows * cfg.experts_per_token] * len(cfg.expert_layers))
    return jnp.stack(seen.seen, axis=1), toks


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
    """Logits after a prefill wave; and token by token through the paged
    cache after a chunked paged prefill whose chunk boundary (5) falls
    inside a window (8), with the kernel's view and with the gathered
    path, contexts below, at and past the window in one batch."""
    if path == "wave":
        ids = np.stack([_ids(21, 1), _ids(21, 2)])
        mask = np.ones_like(ids)
        mask[1, 6:] = 0  # a row shorter than the window, right-padded
        got = llama_mod.lm_logits(params, cfg, jnp.asarray(ids), jnp.asarray(mask))
        want = ref.logits(params, ref.hyper(config), ids)
        assert _close(got[0], want[0]) < TOL and _close(got[1, :6], want[1, :6]) < TOL
        return
    kcfg = dataclasses.replace(cfg, pallas_decode=path == "kernel")
    prompts = [_ids(n, 10 + n) for n in (3, 6, 8, 19)]  # below, below, AT, past W
    got, toks = _serve(params, kcfg, prompts, 4, 5, monkeypatch)
    assert _close(got, _teacher_forced(ref, config, params, prompts, toks)) < TOL


def test_the_prompt_window_kernel_is_the_xla_window(cfg, params, monkeypatch):
    """``paged_prefill_chunk`` through the prompt-window kernel
    (``cfg.pallas_decode``: GQA heads as rows of a q tile, window layers over their band's blocks)
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


# ---------------------------------------------------------------------------
# (ii) each broken rule lands outside the tolerance


@pytest.mark.parametrize("name", sorted(trinity_variants.VARIANTS))
def test_each_broken_variant_departs_from_the_reference(
        ref, config, kw, params, name):
    ids = _ids(24, 3)[None]
    want = ref.logits(params, ref.hyper(config), ids)
    vkw, vparams = trinity_variants.VARIANTS[name](kw, params)
    got = llama_mod.lm_logits(vparams, llama_mod.LlamaConfig(**vkw),
                              jnp.asarray(ids), jnp.ones_like(ids))
    assert _close(got, want) > 20 * TOL, name
    # and what the chip run prints of it says so too
    x = ref.hidden(params, ref.hyper(config), ids)[0]
    r = trinity_variants.readings(ref, params, x, got[0], tail=8)
    sound = trinity_variants.readings(
        ref, params, x,
        llama_mod.lm_logits(params, llama_mod.LlamaConfig(**kw), jnp.asarray(ids),
                            jnp.ones_like(ids))[0], tail=8)
    assert sound["logit_rms_err"] < 1e-5 and sound["worst_margin"] == 0.0
    assert r["logit_rms_err"] > 100 * sound["logit_rms_err"]


# ---------------------------------------------------------------------------
# (iii) the table view reads exactly the window's keys


@pytest.mark.parametrize("t", [0, 5, W - 1, W, W + 3, 15, 16, 31, 47])
def test_view_reads_exactly_the_windows_keys(t):
    """Keys t-W+1..t (those that exist), for t below, at and past W and
    at block boundaries (bs 4): through the view's table entries and
    valid bits, whatever else the row's table and key_valid hold."""
    bs, t_w = 4, 12
    table = jnp.asarray([100 + np.arange(t_w), 200 + np.arange(t_w)], jnp.int32)
    valid = np.ones((2, t_w * bs), np.int32)  # stale bits past t: the view clears none it need not
    valid[:, t + 1:] = 0
    ts = jnp.asarray([t, max(t - 1, 0)])
    valid[1, max(t - 1, 0) + 1:] = 0
    vt, vv = llama_mod.window_view(table, jnp.asarray(valid), ts, W, bs)
    tw = llama_mod.window_view_blocks(W, bs, t_w)
    assert tw == 8 and vt.shape == (2, tw) and vv.shape == (2, tw * bs)
    for row, tr in enumerate(np.asarray(ts)):
        seen = set()
        for j in np.flatnonzero(np.asarray(vv[row])):
            block = int(vt[row, j // bs]) - 100 * (row + 1)
            seen.add(block * bs + int(j) % bs)
        assert seen == set(range(max(tr - W + 1, 0), tr + 1)), (row, tr)
    # consecutive entries of the row's own table
    d = np.diff(np.asarray(vt), axis=1)
    assert (d == 1).all()


def test_a_table_no_wider_than_the_view_is_walked_whole():
    table = jnp.arange(6, dtype=jnp.int32)[None]
    valid = (jnp.arange(24)[None] <= 20).astype(jnp.int32)
    vt, vv = llama_mod.window_view(table, valid, jnp.asarray([20]), W, 4)
    assert vt is table and list(np.flatnonzero(np.asarray(vv[0]))) == list(range(13, 21))
    assert llama_mod.window_view_blocks(2048, 16, 392) == 136  # 129 blocks, in eights
    assert llama_mod.window_view_blocks(2048, 16, 44) == 44


def test_the_step_hands_window_layers_the_view_and_full_layers_the_table(
        cfg, params, monkeypatch):
    """Table widths the paged attention is called with, a layer at a
    time: Tw for (w, w, w, -, w, w), T for the full layer."""
    widths = []
    real = llama_mod._paged_cache_attention

    def spy(c, q, ck, cv, table, key_valid, bs):
        widths.append((table.shape[1], key_valid.shape[1]))
        return real(c, q, ck, cv, table, key_valid, bs)

    monkeypatch.setattr(llama_mod, "_paged_cache_attention", spy)
    prompts = [_ids(19, 4)]
    _serve(params, cfg, prompts, 1, 5, monkeypatch, bs=2, t_w=16)
    tw = llama_mod.window_view_blocks(W, 2, 16)
    assert tw == 8
    assert widths == [(tw, 2 * tw)] * 3 + [(16, 32)] + [(tw, 2 * tw)] * 2


# ---------------------------------------------------------------------------
# (iv) what does not carry the window refuses at boot


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
    assert c.layer_types == ("window",) * 3 + ("full",) + ("window",) * 2
    assert c.window == W and c.router_score == "sigmoid" and c.qk_norm == "head"
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
    ({"prompt_prefix": "w5 w6", "paged_kv": False}, "is not supported for a llama config with window"),
    ({"tp": 2}, "TP=2 is not supported"),
    ({"quantize": "int8"}, "QUANTIZE=int8 is not supported"),
])
def test_registry_refuses_what_does_not_carry_the_window(
        monkeypatch, kw, knobs, needle):
    from mlmicroservicetemplate_tpu.models.registry import build_model

    with pytest.raises(ValueError, match=needle):
        build_model(_svc(monkeypatch, kw, **knobs))


@pytest.mark.parametrize("bad,needle", [
    ({"layer_types": ("window", "full")}, "layer_types must name each"),
    ({"layer_types": ("full",) * 6}, "a window needs layers"),
    ({"window": 0}, "window layers need a window"),
    ({"d_ff_dense": 0}, "num_dense_layers needs d_ff_dense"),
    ({"num_dense_layers": 6}, "leaves no expert layer"),
    ({"router_score": "tanh"}, "router_score"),
    ({"qk_norm": "row"}, "qk_norm"),
])
def test_a_pattern_that_does_not_add_up_is_refused(kw, bad, needle):
    with pytest.raises(ValueError, match=needle):
        llama_mod.LlamaConfig(**{**kw, **bad})


# ---------------------------------------------------------------------------
# (v) a config with none of the new fields set is what it was


@pytest.mark.parametrize("name,over,digest", [
    ("mistral", dict(num_kv_heads=2, d_ff=96),
     "812a6ab6971a9f71db19f12a5926b5378e15bef087dd31f33e78e8c7b09d8964"),
    ("olmoe", dict(num_kv_heads=4, d_ff=32, num_experts=8, experts_per_token=2,
                   qk_norm=True),
     "ff212db2630c6ce4bb59d4ccb53b16da5abd484ca5eba127df9cdda2873b979e"),
])
def test_old_configs_build_the_trees_they_built(name, over, digest):
    """Leaf names, shapes and draws of a Mistral-like and an OLMoE-like
    tree, bit for bit what the tree before the pattern built (digests
    taken from commit 2175848)."""
    c = llama_mod.LlamaConfig(vocab_size=97, d_model=64, num_heads=4, num_layers=3,
                              max_position=64, **over)
    assert c.head_dim == 16 and c.layer_types == () and not c.window
    assert all(c.layer_kind(li) == llama_mod.LayerKind(
        0, True, bool(c.num_experts), c.d_ff) for li in range(3))
    p = llama_mod.init_params(jax.random.PRNGKey(0), c, dtype=jnp.bfloat16)
    h = hashlib.sha256()
    for k, v in jax.tree_util.tree_flatten_with_path(p)[0]:
        h.update(jax.tree_util.keystr(k).encode())
        h.update(np.asarray(v.astype(jnp.float32)).tobytes())
    assert h.hexdigest() == digest
