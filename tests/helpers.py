"""Tiny-config ModelBundle builders for fast engine/scheduler/API tests.

Mirrors the registry builders but with small architectures so CPU tests
stay quick; the golden tests cover full-size fidelity.
"""

from __future__ import annotations

import contextlib
import functools

import numpy as np

from mlmicroservicetemplate_tpu.models import bert as bert_mod
from mlmicroservicetemplate_tpu.models import resnet as resnet_mod
from mlmicroservicetemplate_tpu.models import t5 as t5_mod
from mlmicroservicetemplate_tpu.models.registry import (
    KIND_IMAGE,
    KIND_SEQ2SEQ,
    KIND_TEXT,
    ModelBundle,
)
from mlmicroservicetemplate_tpu.models.tokenizer import build_tokenizer
from mlmicroservicetemplate_tpu.runtime.device import default_policy
from mlmicroservicetemplate_tpu.scheduler.policy import Arrival

TINY_RESNET = functools.partial(
    resnet_mod.ResNetConfig,
    embedding_size=8,
    hidden_sizes=(8, 16, 16, 32),
    depths=(1, 1, 1, 1),
    num_labels=10,
    image_size=32,
)
TINY_BERT = functools.partial(
    bert_mod.BertConfig,
    vocab_size=512,
    hidden_size=32,
    num_layers=2,
    num_heads=2,
    intermediate_size=64,
    max_position=128,
    num_labels=3,
)
TINY_T5 = functools.partial(
    t5_mod.T5Config,
    vocab_size=384,
    d_model=32,
    d_kv=8,
    num_heads=2,
    d_ff=64,
    num_layers=2,
)


def tiny_resnet_bundle(seed: int = 0) -> ModelBundle:
    import jax

    cfg = TINY_RESNET()
    policy = default_policy("cpu")
    params = resnet_mod.init_params(jax.random.PRNGKey(seed), cfg=cfg)

    def forward(p, images):
        from mlmicroservicetemplate_tpu.models.preprocess import normalize_imagenet

        x = normalize_imagenet(images)
        return resnet_mod.apply(p, cfg, x.astype(policy.compute_jnp))

    return ModelBundle(
        name="resnet50", kind=KIND_IMAGE, cfg=cfg, params=params, policy=policy,
        tokenizer=None, labels=None, forward=forward, image_size=cfg.image_size,
    )


def tiny_bert_bundle(seed: int = 0) -> ModelBundle:
    import jax

    cfg = TINY_BERT()
    policy = default_policy("cpu")
    params = bert_mod.init_params(jax.random.PRNGKey(seed), cfg=cfg)

    def forward(p, input_ids, attention_mask):
        return bert_mod.classify(
            p, cfg, input_ids, attention_mask, dtype=policy.compute_jnp
        )

    return ModelBundle(
        name="bert-base", kind=KIND_TEXT, cfg=cfg, params=params, policy=policy,
        tokenizer=build_tokenizer(None, for_t5=False), labels=["a", "b", "c"],
        forward=forward,
    )


def tiny_t5_bundle(seed: int = 0) -> ModelBundle:
    import jax

    cfg = TINY_T5()
    policy = default_policy("cpu")
    params = t5_mod.init_params(jax.random.PRNGKey(seed), cfg=cfg)
    # Untie the LM head with fresh random weights: tied heads + random
    # init argmax-lock onto the start token (self-correlation of the
    # residual stream), which would make generation tests trivially
    # all-pad.  A random untied head yields diverse token sequences.
    import jax.numpy as jnp

    params["lm_head"] = {
        "kernel": jax.random.normal(
            jax.random.PRNGKey(seed + 99), (cfg.d_model, cfg.vocab_size), jnp.float32
        )
    }

    def encode_fn(p, input_ids, attention_mask):
        return t5_mod.encode(p, cfg, input_ids, attention_mask, dtype=policy.compute_jnp)

    def init_state_fn(p, enc_out, enc_mask, max_len: int, sample=None):
        return t5_mod.init_decode_state(p, cfg, enc_out, enc_mask, max_len, sample=sample)

    def generate_chunk_fn(p, state, n_steps: int, sample: bool = False):
        return t5_mod.generate_chunk(p, cfg, state, n_steps, sample)

    from mlmicroservicetemplate_tpu.models import spec as spec_mod

    def init_spec_fn(state, input_ids, attention_mask, prefix_ids=None):
        return t5_mod.init_spec_state(state, input_ids, attention_mask)

    def spec_chunk_fn(p, spec_state, n_verify: int, spec_k: int,
                      sample: bool = False):
        return spec_mod.spec_chunk(
            p, spec_state, n_verify, spec_k, 2,
            lambda pp, st, toks: t5_mod.multi_step(pp, cfg, st, toks),
            cfg.eos_id, cfg.pad_id, sample,
        )

    return ModelBundle(
        name="t5-small", kind=KIND_SEQ2SEQ, cfg=cfg, params=params, policy=policy,
        tokenizer=build_tokenizer(None, for_t5=True), labels=None, forward=None,
        encode_fn=encode_fn, init_state_fn=init_state_fn,
        generate_chunk_fn=generate_chunk_fn,
        init_spec_fn=init_spec_fn, spec_chunk_fn=spec_chunk_fn,
    )


TINY_GPT = dict(
    vocab_size=300, d_model=32, num_heads=2, num_layers=2, d_ff=64,
    max_position=256, eos_id=257, pad_id=257,
)
TINY_LLAMA = dict(
    vocab_size=300, d_model=32, num_heads=4, num_kv_heads=2, num_layers=2,
    d_ff=64, max_position=256, eos_id=257, pad_id=257,
)


def tiny_gpt_bundle(seed: int = 0, **cfg_overrides) -> ModelBundle:
    """Tiny decoder-only bundle with the full fn surface the engine
    serves (contiguous chunk + paged chunk), for loop/scheduler tests.
    ``cfg_overrides`` land on GPTConfig (e.g. ``pallas_decode=True,
    pallas_interpret=True`` for the autotuner smokes)."""
    import jax

    from mlmicroservicetemplate_tpu.models import gpt as gpt_mod
    from mlmicroservicetemplate_tpu.models.tokenizer import ByteTokenizer

    cfg = gpt_mod.GPTConfig(**{**TINY_GPT, **cfg_overrides})
    params = gpt_mod.init_params(jax.random.PRNGKey(seed), cfg)
    return ModelBundle(
        name="gpt2", kind=KIND_SEQ2SEQ, cfg=cfg, params=params,
        policy=default_policy("cpu"), tokenizer=ByteTokenizer(add_eos=True),
        labels=None, forward=None,
        encode_fn=lambda p, i, m: i,
        init_state_fn=lambda p, i, m, ml, sample=None: gpt_mod.init_decode_state(
            p, cfg, i, m, ml, sample=sample
        ),
        generate_chunk_fn=lambda p, s, n, sample=False: gpt_mod.generate_chunk(
            p, cfg, s, n, sample
        ),
        paged_chunk_fn=lambda p, s, t, n, sample=False: gpt_mod.generate_chunk_paged(
            p, cfg, s, t, n, sample
        ),
        empty_state_fn=lambda p, b, s, ml: gpt_mod.empty_decode_state(
            p, cfg, b, s, ml
        ),
        prefill_chunk_fn=lambda p, st, i, m, start: gpt_mod.prefill_chunk(
            p, cfg, st, i, m, start
        ),
        paged_prefill_chunk_fn=(
            lambda p, st, tr, i, m, starts: gpt_mod.paged_prefill_chunk(
                p, cfg, st, tr, i, m, starts
            )
        ),
        supports_prefix=True,
    )


def tiny_llama_bundle(seed: int = 0, kv_quant: bool = False,
                      **cfg_overrides) -> ModelBundle:
    import jax

    from mlmicroservicetemplate_tpu.models import llama as llama_mod
    from mlmicroservicetemplate_tpu.models.tokenizer import ByteTokenizer

    cfg = llama_mod.LlamaConfig(
        **{**TINY_LLAMA, "kv_quant": kv_quant, **cfg_overrides}
    )
    params = llama_mod.init_params(jax.random.PRNGKey(seed), cfg)
    return ModelBundle(
        name="llama", kind=KIND_SEQ2SEQ, cfg=cfg, params=params,
        policy=default_policy("cpu"), tokenizer=ByteTokenizer(add_eos=True),
        labels=None, forward=None,
        encode_fn=lambda p, i, m: i,
        init_state_fn=lambda p, i, m, ml, sample=None: llama_mod.init_decode_state(
            p, cfg, i, m, ml, sample=sample
        ),
        generate_chunk_fn=lambda p, s, n, sample=False: llama_mod.generate_chunk(
            p, cfg, s, n, sample
        ),
        paged_chunk_fn=lambda p, s, t, n, sample=False: llama_mod.generate_chunk_paged(
            p, cfg, s, t, n, sample
        ),
        empty_state_fn=lambda p, b, s, ml: llama_mod.empty_decode_state(
            p, cfg, b, s, ml
        ),
        prefill_chunk_fn=lambda p, st, i, m, start: llama_mod.prefill_chunk(
            p, cfg, st, i, m, start
        ),
        paged_prefill_chunk_fn=(
            lambda p, st, tr, i, m, starts: llama_mod.paged_prefill_chunk(
                p, cfg, st, tr, i, m, starts
            )
        ),
        supports_prefix=True,
    )


def rand_image(seed: int = 0, size: int = 32) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, 255, (size, size, 3), dtype=np.uint8)


def text_feats(tokenizer, text: str, max_len: int = 128) -> dict:
    ids, mask = tokenizer.encode(text, max_len)
    n = int(mask.sum())
    return {"input_ids": ids[:n], "length": np.int32(n)}


@contextlib.contextmanager
def one_wave(cdl, cap_s: float = 5.0):
    """Submit a burst to an idle decode loop the way the API does:
    announced on the loop's queue (``scheduler.policy.Arrival``) until
    its streams are put, so the loop admits it as ONE wave.  A loop
    holds a wave no longer than a wave of its rung last took; a test's
    loop has timed none (or, on this CPU, one of a millisecond), so
    every rung is given ``cap_s``."""
    cdl._wave_seconds = dict.fromkeys(cdl._wave_rungs, cap_s)
    with Arrival([cdl.queue]):
        yield


def expert_rungs_at_toy_size(monkeypatch, tile: int = 8) -> list:
    """Let the expert block's ladder of row counts (``ops/moe.row_rungs``)
    engage at a toy's few hundred assignments — rows by ``tile``, a rung for
    ``tile`` rows left out — and record every EAGER ``expert_ffn`` call:
    returns the list that receives ``(rungs, rows ran)``, the rung chosen
    by the call's own held count as the device chooses it."""
    from mlmicroservicetemplate_tpu.ops import moe

    monkeypatch.setattr(moe, "ROW_TILE", tile)
    monkeypatch.setattr(moe, "LADDER_MIN_SKIP", tile)
    calls, real = [], moe.expert_ffn

    def keep(h, mlp, k, *args, expert_first=0, **kw):
        out, counts = real(h, mlp, k, *args, expert_first=expert_first, **kw)
        held = mlp["up"]["kernel"].shape[0]
        rungs = moe.row_rungs(h.shape[0] * k, held, counts.shape[0])
        here = int(counts[expert_first:expert_first + held].sum())
        calls.append((rungs, rungs[int(moe.rung_index(here, rungs))]))
        return out, counts

    monkeypatch.setattr(moe, "expert_ffn", keep)
    return calls


def expert_row_kernels_at_toy_size(monkeypatch, rows: int = 128,
                                   row_bytes: int = 512) -> None:
    """Let the expert block's two DMA kernels (``ops/moe.row_kernels_fit``)
    take a toy's calls: ``rows`` assignment rows of ``row_bytes`` are
    enough, where the rule as shipped wants a prompt dispatch's thousands
    of rows of 8 KB."""
    from mlmicroservicetemplate_tpu.ops import moe

    monkeypatch.setattr(moe, "ROW_KERNELS_MIN_ROWS", rows)
    monkeypatch.setattr(moe, "ROW_KERNELS_MIN_ROW_BYTES", row_bytes)


def latent_window_both_ways(cfg, layer, li: int, start: int, n_valid: int,
                            c: int = 8, k_len: int = 24, seed: int = 5):
    """A latent layer's prompt window of ``c`` queries from ``start``
    (``n_valid`` real tokens, then pad) over a row of ``k_len`` table keys
    whose first ``start + c`` are written: ``(got, want)`` [c, H, v] —
    ``_mla_expanded_attention``'s window branch through the prompt-window
    kernel, and ``prefill_attention_ref`` over keys and values expanded the
    plain way (the wave branch's einsums; a head's key = its nope dims
    beside the token's one rotary key)."""
    import jax
    import jax.numpy as jnp

    from mlmicroservicetemplate_tpu.models import llama as llama_mod
    from mlmicroservicetemplate_tpu.ops.prefill_attention import prefill_attention_ref

    n = start + c
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, n, cfg.d_model)) * 0.5
    cos, sin = llama_mod._rope_tables(cfg, jnp.arange(n, dtype=jnp.int32), jnp.float32)
    (qn, qr), latent, _, _ = llama_mod._qkv_rope(
        cfg, layer, None, li, x, cos[None, :, None, :], sin[None, :, None, :])
    rows = jnp.zeros((1, k_len, cfg.latent_lanes)).at[:, :n].set(latent)
    chunk_mask = (jnp.arange(c) < n_valid).astype(jnp.int32)
    got = llama_mod._mla_expanded_attention(
        cfg, layer, (qn[:, start:], qr[:, start:]), rows, None,
        (0, start, chunk_mask))[0]
    a, r = layer["attn"], cfg.kv_lora_rank
    lat, kr = rows[0, :, :r], rows[0, :, r:cfg.latent_dim]
    kn = jnp.einsum("kr,hnr->khn", lat, a["k_b"]["kernel"])
    v = jnp.einsum("kr,hrv->khv", lat, a["v_b"]["kernel"])
    k = jnp.concatenate(
        [kn, jnp.broadcast_to(kr[:, None], kn.shape[:2] + kr.shape[-1:])], axis=-1)
    want = prefill_attention_ref(
        jnp.concatenate([qn[0, start:], qr[0, start:]], axis=-1), k, v, 0, start,
        chunk_mask, scale=cfg.attn_scale)
    return got, want
