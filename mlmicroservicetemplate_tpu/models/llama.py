"""Llama-family decoder (RoPE + GQA + SwiGLU), pure-JAX, KV-cached.

Model-family breadth beyond the reference's zoo (SURVEY.md §2 serves
ResNet/BERT/T5; round 2 added GPT-2): this is the modern-decoder
member — the architecture family (Llama/Mistral/TinyLlama/Qwen-style)
a 2026 user actually brings to a serving template.  Servable as
``MODEL_NAME=llama`` through the SAME machinery as GPT-2: the
encode/init/generate_chunk trio, fused prefill+first-chunk dispatch,
continuous batching, per-request sampling, TP sharding.

Architecture: pre-norm RMSNorm blocks, rotary position embeddings
(HF rotate-half convention), grouped-query attention (num_kv_heads <
num_heads; K/V cached at KV width and broadcast to query heads at
attention time), SwiGLU MLP (down(silu(gate)·up)), no biases anywhere,
untied LM head.  Two published variations of the block are config
fields, off by default: a sparse expert FFN in place of the MLP
(``num_experts``; ops/moe.py — OLMoE-1B-7B: 64 experts, top-8) and an
RMSNorm on q and k before RoPE (``qk_norm``).

Decode reuses ``gpt.GPTState`` verbatim — the per-row
(write_idx/key_valid/pos/rng) state contract is what the continuous
batching loop and the engine already speak.  RoPE is applied BEFORE
caching K (the standard layout), so cached keys never need re-rotation;
each row rotates its new K/Q at its OWN position.

Checkpoint mapping: ``convert/hf_maps.llama_state_to_pytree`` (HF
``model.layers.i.self_attn.{q,k,v,o}_proj`` etc., nn.Linear [out,in]
weights transposed to [in,out]).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from . import lora
from .common import (
    Params,
    dense,
    dense_init,
    embed,
    kv_quantize,
    lm_head_logits,
    merge_heads,
    mha_attention,
    mha_attention_kv8,
    normal_init,
    rmsnorm,
    rmsnorm_init,
)
from .gpt import GPTState


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    # Defaults = TinyLlama-1.1B (the smallest real Llama-family
    # checkpoint people serve); tests use tiny overrides.
    vocab_size: int = 32000
    d_model: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 4
    num_layers: int = 22
    d_ff: int = 5632
    max_position: int = 2048
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    bos_id: int = 1
    eos_id: int = 2
    pad_id: int = 0
    # int8 KV cache (QUANT_KV=int8): K/V stored as per-token-per-head
    # int8 + f32 scales, dequantized by scale factoring inside the
    # attention matmuls (common.mha_attention_kv8) — halves the KV
    # HBM term of batched long-context decode.  Generation is NOT
    # bit-identical to the bf16 cache (quantization is lossy); the
    # knob ships measured (the pre-round BASELINE record (removed in PR 22))
    # and default-off.
    kv_quant: bool = False
    # Pallas decode attention (USE_PALLAS_DECODE=1): the single-token
    # decode step's cache attention runs as one kernel gridded over
    # (batch, KV head) — the cache crosses HBM once per KV HEAD
    # instead of once per query head (no materialized GQA repeat), and
    # under kv_quant the payload crosses at int8 width with in-kernel
    # dequant (ops/attention.decode_attention).  Numerics: f32 scores/
    # softmax like the jnp path (verified equal in tests/test_ops.py);
    # serving-only, no VJP.
    pallas_decode: bool = False
    # Tensor-parallel width of the serving placement (registry sets it
    # from the TP knob; 1 = default, builds no mesh anywhere).  Static
    # so kernel call sites decide shard_map wrapping at trace time and
    # the autotuner keys TP entries apart (parallel/tpserve.py).
    tp: int = 1
    # Kernel-variant pin (ops/paged_attention.Variant grammar, e.g.
    # "b4-hb"): "" = resolve through the autotuner's tuning table at
    # trace time (ops/autotune.lookup — the measured winner for this
    # decode shape, or the default kernel when nothing is tuned).
    # Registry plumbs PALLAS_VARIANT here; docs/kernel_tuning.md.
    pallas_variant: str = ""
    # Run Pallas kernels in interpret mode (CPU serving/CI; TPU runs
    # compiled Mosaic).  Registry plumbs PALLAS_INTERPRET.
    pallas_interpret: bool = False
    # Sparse expert FFN (OLMoE-style; 0 = today's dense SwiGLU block):
    # ``num_experts`` experts of width ``d_ff`` each, every token runs
    # its ``experts_per_token`` most probable ones (router softmax over
    # ALL experts in f32; weights renormalised over the chosen ones
    # only under ``norm_topk_prob``).  ``_mlp_block`` is the one seam.
    num_experts: int = 0
    experts_per_token: int = 0
    norm_topk_prob: bool = False
    # Learned-scale RMSNorm over the WHOLE q and k projections, before
    # the head split and RoPE (OLMoE / OLMo-2); ``_qkv_rope``.
    qk_norm: bool = False
    # Prompts start with the tokenizer's BOS (the Llama / Mistral
    # convention; registry._build_llama applies it to the tokenizer).
    # OLMoE's tokenizer has no BOS: a token every stream shares at
    # position 0 is also what collapses a seeded random router onto a
    # few experts (PERF.md section 6, PR 27).
    add_bos: bool = True

    def __post_init__(self):
        if self.num_experts and not (
            0 < self.experts_per_token <= self.num_experts
        ):
            raise ValueError(
                f"experts_per_token={self.experts_per_token} must lie in "
                f"1..num_experts={self.num_experts}"
            )

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads

    @property
    def n_rep(self) -> int:
        return self.num_heads // self.num_kv_heads


# ---------------------------------------------------------------------------
# init


def init_params(key, cfg: LlamaConfig = LlamaConfig(), dtype=None) -> Params:
    """Seeded random tree.  Every leaf is drawn in float32 and cast to
    ``dtype`` AT ONCE (None keeps float32), so the boot peak is the
    serving-dtype tree plus one float32 leaf — not a whole float32 tree
    cast afterwards (6 bytes a parameter; OLMoE at 8 layers would not
    boot).  Same keys, same draws: bit-identical to cast-after."""

    def cast(tree):
        if dtype is None:
            return tree
        return jax.tree.map(lambda a: a.astype(dtype), tree)

    def lin(k, d_in, d_out):
        return cast(dense_init(k, d_in, d_out, bias=False, std=0.02))

    def norm_scale(k, n):
        return cast({"scale": 1.0 + normal_init(k, (n,), std=0.25)})

    def experts(k, shape):
        # The key ``dense_init`` would draw a [d_in, d_out] kernel from.
        return {"kernel": cast(normal_init(jax.random.split(k)[0], shape, std=0.02))}

    keys = jax.random.split(key, cfg.num_layers + 2)
    d, kv_dim = cfg.d_model, cfg.num_kv_heads * cfg.head_dim
    e, w = cfg.num_experts, cfg.d_ff
    params: Params = {
        "embed": {"embedding": cast(normal_init(keys[0], (cfg.vocab_size, d), std=0.02))},
        "layers": [],
        "final_ln": cast(rmsnorm_init(d)),
        "lm_head": {"kernel": cast(normal_init(keys[1], (d, cfg.vocab_size), std=0.02))},
    }
    for i in range(cfg.num_layers):
        k = jax.random.split(keys[2 + i], 7)
        attn = {
            "q": lin(k[0], d, d),
            "k": lin(k[1], d, kv_dim),
            "v": lin(k[2], d, kv_dim),
            "o": lin(k[3], d, d),
        }
        if cfg.qk_norm:
            # Learned scales have no reason to be 1: drawn about it, so a
            # served path that drops the norm departs from one that has it.
            attn["q_norm"] = norm_scale(jax.random.fold_in(keys[2 + i], 8), d)
            attn["k_norm"] = norm_scale(jax.random.fold_in(keys[2 + i], 9), kv_dim)
        if e:
            mlp = {
                "router": lin(jax.random.fold_in(keys[2 + i], 7), d, e),
                "gate": experts(k[4], (e, d, w)),
                "up": experts(k[5], (e, d, w)),
                "down": experts(k[6], (e, w, d)),
            }
        else:
            mlp = {
                "gate": lin(k[4], d, w),
                "up": lin(k[5], d, w),
                "down": lin(k[6], w, d),
            }
        params["layers"].append(
            {
                "attn_ln": cast(rmsnorm_init(d)),
                "attn": attn,
                "mlp_ln": cast(rmsnorm_init(d)),
                "mlp": mlp,
            }
        )
    return params


# ---------------------------------------------------------------------------
# rotary embeddings (HF rotate-half convention)


def _rope_tables(cfg: LlamaConfig, positions: jax.Array, dtype):
    """cos/sin [..., head_dim] for integer positions [...]."""
    half = cfg.head_dim // 2
    inv_freq = 1.0 / (
        cfg.rope_theta
        ** (jnp.arange(0, half, dtype=jnp.float32) * 2.0 / cfg.head_dim)
    )
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [..., half]
    emb = jnp.concatenate([angles, angles], axis=-1)  # [..., head_dim]
    return jnp.cos(emb).astype(dtype), jnp.sin(emb).astype(dtype)


def _rotate_half(x: jax.Array) -> jax.Array:
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [B, S, H, D]; cos/sin broadcastable to [B, S, 1, D]."""
    return x * cos + _rotate_half(x) * sin


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """[B, S, KVH, D] -> [B, S, KVH*n_rep, D] (GQA broadcast)."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(
        x[:, :, :, None, :], (b, s, h, n_rep, d)
    ).reshape(b, s, h * n_rep, d)


def _split(x: jax.Array, n_heads: int) -> jax.Array:
    b, s, d = x.shape
    return x.reshape(b, s, n_heads, d // n_heads)


def _mlp_block(cfg: "LlamaConfig", layer, x, valid, tally=None):
    """Pre-norm FFN block with its residual, under the ``mlp`` scope
    (the device trace's name for it in every step kind): the dense
    SwiGLU, or with ``cfg.num_experts`` the sparse expert FFN
    (ops/moe.py).  ``valid`` [B, S] marks the rows that are neither
    padding nor finished — only they get expert work; ``tally`` (a
    list) receives the layer's [E] count of their assignments."""
    with jax.named_scope("mlp"):
        h = rmsnorm(layer["mlp_ln"], x, eps=cfg.rms_eps)
        m = layer["mlp"]
        if cfg.num_experts:
            from ..ops.moe import expert_ffn

            b, s, d = x.shape
            out, counts = expert_ffn(
                h.reshape(b * s, d), m, cfg.experts_per_token,
                cfg.norm_topk_prob, jnp.broadcast_to(valid, (b, s)).reshape(-1),
                interpret=cfg.pallas_interpret,
            )
            if tally is not None:
                tally.append(counts)
            return x + out.reshape(b, s, d)
        return x + dense(
            m["down"], jax.nn.silu(dense(m["gate"], h)) * dense(m["up"], h)
        )


def _select_next(params: Params, cfg: "LlamaConfig", state, x_last,
                 sample: bool):
    """Final-normed hidden rows → (next token, sample params, done,
    tokens): the ``lm_head`` and ``sample`` scopes of a decode step."""
    rows = jnp.arange(state.last_token.shape[0])
    with jax.named_scope("lm_head"):
        logits = lm_head_logits(
            x_last, params["lm_head"]["kernel"], transposed=False
        )
    with jax.named_scope("sample"):
        if sample:
            from .sampling import select_token

            next_tok, sp = select_token(logits, state.sample)
        else:
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            sp = state.sample
        next_tok = jnp.where(state.done, jnp.int32(cfg.pad_id), next_tok)
        done = state.done | (next_tok == cfg.eos_id)
        tokens = state.tokens.at[rows, state.pos].set(next_tok, mode="drop")
    return next_tok, sp, done, tokens


def _aproj(a, ad, name: str, li: int, x):
    """One attention projection (+ per-row LoRA delta when serving a
    ``__adapters__`` overlay; models/lora.py)."""
    return lora.apply(ad, name, li, x, dense(a[name], x))


def _qkv_rope(cfg: "LlamaConfig", layer, ad, li: int, x, cos, sin):
    """Rotated q [.., H, Dh] and k, and v [.., KVH, Dh] of one layer from
    the residual stream x [B, S, D] — the ``qkv_rope`` scope of every
    step kind.  Under ``cfg.qk_norm`` q and k pass a learned-scale
    RMSNorm over the whole projection before the head split."""
    a = layer["attn"]
    with jax.named_scope("qkv_rope"):
        h = rmsnorm(layer["attn_ln"], x, eps=cfg.rms_eps)
        q = _aproj(a, ad, "q", li, h)
        k = _aproj(a, ad, "k", li, h)
        if cfg.qk_norm:
            q = rmsnorm(a["q_norm"], q, eps=cfg.rms_eps)
            k = rmsnorm(a["k_norm"], k, eps=cfg.rms_eps)
        q = _apply_rope(_split(q, cfg.num_heads), cos, sin)
        k = _apply_rope(_split(k, cfg.num_kv_heads), cos, sin)
        v = _split(_aproj(a, ad, "v", li, h), cfg.num_kv_heads)
    return q, k, v


# ---------------------------------------------------------------------------
# prefill


def _prefix_entry_len(entry) -> int:
    """Token count of one prefix K/V entry — dense [1, P, KVH, D] or
    quantized (int8 payload, scale) tuple."""
    return entry[0].shape[1] if isinstance(entry, tuple) else entry.shape[1]


def _dequant_prefix(entry, dtype):
    """Dense view of a prefix K/V entry for the prefill-side concat.
    Quantized entries pay an int8→dtype multiply over P tokens ONCE per
    prefill — the cache-resident copy stays int8."""
    if isinstance(entry, tuple):
        q8, sc = entry
        return q8.astype(dtype) * sc.astype(dtype)
    return entry.astype(dtype)


def _quant_prefix_entry(entry, dtype):
    """(int8, scale-in-``dtype``) form of a prefix K/V entry for the
    quantized cache: already-quantized entries pass through EXACTLY
    (no requantization loss — capture under kv_quant slices the int8
    cache rows themselves); dense entries quantize with the cache's own
    per-token-per-head scheme."""
    if isinstance(entry, tuple):
        q8, sc = entry
        return q8, sc.astype(dtype)
    q8, sc = kv_quantize(entry)
    return q8, sc.astype(dtype)


def quantize_prefix_kv(pkv: dict) -> dict:
    """Quantize a dense ``compute_prefix_kv`` pytree to the (int8,
    scale) entry form the kv_quant cache absorbs — used by the registry
    to store a global PROMPT_PREFIX at cache width (per-request capture
    under kv_quant produces this form natively)."""
    return {
        "k": [tuple(kv_quantize(k)) for k in pkv["k"]],
        "v": [tuple(kv_quantize(v)) for v in pkv["v"]],
    }


def forward_hidden(
    params: Params,
    cfg: LlamaConfig,
    input_ids: jax.Array,  # [B, S]
    attention_mask: jax.Array,  # [B, S]
    dtype=jnp.float32,
    collect_kv: bool = False,
    prefix_kv=None,  # optional list[(k,v)] of [1, P, KVH, D] cached prefix
):
    """Hidden states [B, S, D] (+ per-layer ROTATED prompt K / V).

    With ``prefix_kv`` the batch is the SUFFIX of a shared cached
    prompt prefix: tokens take rotary positions P.., queries attend to
    the (already rotated) prefix K/V plus the causal suffix — prefill
    cost is O(S), not O(P+S)."""
    b, s = input_ids.shape
    p_len = 0 if prefix_kv is None else _prefix_entry_len(prefix_kv[0][0])
    with jax.named_scope("embed"):
        x = embed(params["embed"], input_ids, dtype)
    pos = jnp.arange(p_len, p_len + s, dtype=jnp.int32)
    cos, sin = _rope_tables(cfg, pos, dtype)  # [S, D_h]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    causal = jnp.tril(jnp.ones((s, s), bool))
    mask = causal[None, None] & (attention_mask[:, None, None, :] != 0)
    if p_len:
        pre = jnp.ones((1, 1, s, p_len), bool)  # prefix fully visible
        mask = jnp.concatenate(
            [jnp.broadcast_to(pre, (b, 1, s, p_len)), mask], axis=-1
        )
    ad = lora.adapter_tables(params)
    kv = []
    for li, layer in enumerate(params["layers"]):
        a = layer["attn"]
        q, k, v = _qkv_rope(cfg, layer, ad, li, x, cos, sin)
        if collect_kv:
            kv.append((k, v))
        with jax.named_scope("attn"):
            if p_len:
                pk = _dequant_prefix(prefix_kv[li][0], k.dtype)
                pv = _dequant_prefix(prefix_kv[li][1], v.dtype)
                k = jnp.concatenate(
                    [jnp.broadcast_to(pk, (b,) + pk.shape[1:]), k], axis=1
                )
                v = jnp.concatenate(
                    [jnp.broadcast_to(pv, (b,) + pv.shape[1:]), v], axis=1
                )
            ctx = mha_attention(
                q, _repeat_kv(k, cfg.n_rep), _repeat_kv(v, cfg.n_rep), mask=mask
            )
        with jax.named_scope("attn_out"):
            x = x + _aproj(a, ad, "o", li, merge_heads(ctx))
        x = _mlp_block(cfg, layer, x, attention_mask != 0)
    x = rmsnorm(params["final_ln"], x, eps=cfg.rms_eps)
    return (x, kv) if collect_kv else x


def compute_prefix_kv(params: Params, cfg: LlamaConfig, prefix_ids, dtype=jnp.float32):
    """Per-layer ROTATED K/V of a shared prompt prefix — computed once
    at startup, carried in params under ``__prefix__`` (see gpt.py)."""
    ids = jnp.asarray(prefix_ids, jnp.int32).reshape(1, -1)
    _, kv = forward_hidden(
        params, cfg, ids, jnp.ones_like(ids), dtype, collect_kv=True
    )
    return {"k": [k for k, _ in kv], "v": [v for _, v in kv]}


def lm_logits(
    params: Params, cfg: LlamaConfig, input_ids, attention_mask, dtype=jnp.float32
) -> jax.Array:
    """[B, S, V] next-token logits (the non-generative forward)."""
    x = forward_hidden(params, cfg, input_ids, attention_mask, dtype)
    return lm_head_logits(x, params["lm_head"]["kernel"], transposed=False)


# ---------------------------------------------------------------------------
# incremental decode (state layout shared with gpt.GPTState)


def init_decode_state(
    params: Params,
    cfg: LlamaConfig,
    input_ids: jax.Array,  # [B, S] right-padded
    attention_mask: jax.Array,  # [B, S]
    max_len: int,
    dtype=jnp.float32,
    sample=None,
) -> GPTState:
    from .sampling import greedy_params

    b, s = input_ids.shape
    pre = params.get("__prefix__") if isinstance(params, dict) else None
    p_len = _prefix_entry_len(pre["k"][0]) if pre is not None else 0
    prefix_kv = list(zip(pre["k"], pre["v"])) if pre is not None else None
    total = p_len + s + max_len
    _, kv = forward_hidden(
        params, cfg, input_ids, attention_mask, dtype,
        collect_kv=True, prefix_kv=prefix_kv,
    )
    cache_k, cache_v = [], []
    with jax.named_scope("kv_write"):
        for li, (k, v) in enumerate(kv):
            if cfg.kv_quant:
                # Scales stored in the COMPUTE dtype: the decode step
                # recovers its working dtype from the state (the int8
                # payload can't carry it), and mha_attention_kv8 upcasts
                # scales into the f32 logits anyway.  Prefix rows (global
                # PROMPT_PREFIX or a per-request cache hit) land as int8 +
                # scale too — already-quantized entries copy bit-exact,
                # dense ones quantize with the cache's own scheme — so the
                # whole slab stays uniform for the fused decode kernel.
                shape = (b, total, cfg.num_kv_heads, cfg.head_dim)
                k8, ks = kv_quantize(k)
                v8, vs = kv_quantize(v)
                ck8 = jnp.zeros(shape, jnp.int8)
                cks = jnp.ones(shape[:3] + (1,), dtype)
                cv8 = jnp.zeros(shape, jnp.int8)
                cvs = jnp.ones(shape[:3] + (1,), dtype)
                if p_len:
                    pk8, pks = _quant_prefix_entry(prefix_kv[li][0], dtype)
                    pv8, pvs = _quant_prefix_entry(prefix_kv[li][1], dtype)
                    ck8 = ck8.at[:, :p_len].set(pk8)
                    cks = cks.at[:, :p_len].set(pks)
                    cv8 = cv8.at[:, :p_len].set(pv8)
                    cvs = cvs.at[:, :p_len].set(pvs)
                ck8 = ck8.at[:, p_len : p_len + s].set(k8)
                cks = cks.at[:, p_len : p_len + s].set(ks.astype(dtype))
                cv8 = cv8.at[:, p_len : p_len + s].set(v8)
                cvs = cvs.at[:, p_len : p_len + s].set(vs.astype(dtype))
                cache_k.append((ck8, cks))
                cache_v.append((cv8, cvs))
                continue
            ck = jnp.zeros((b, total, cfg.num_kv_heads, cfg.head_dim), k.dtype)
            cv = ck
            if p_len:
                pk, pv = prefix_kv[li]
                ck = ck.at[:, :p_len].set(pk.astype(ck.dtype))
                cv = cv.at[:, :p_len].set(pv.astype(cv.dtype))
            cache_k.append(ck.at[:, p_len : p_len + s].set(k))
            cache_v.append(cv.at[:, p_len : p_len + s].set(v))
    lengths = attention_mask.sum(axis=-1).astype(jnp.int32)
    key_valid = jnp.zeros((b, total), jnp.int32)
    if p_len:
        key_valid = key_valid.at[:, :p_len].set(1)
    key_valid = key_valid.at[:, p_len : p_len + s].set(
        attention_mask.astype(jnp.int32)
    )
    rows = jnp.arange(b)
    last_tok = input_ids[rows, jnp.maximum(lengths - 1, 0)]
    return GPTState(
        cache_k=cache_k,
        cache_v=cache_v,
        key_valid=key_valid,
        write_idx=p_len + jnp.maximum(lengths - 1, 0),
        pos=jnp.zeros((b,), jnp.int32),
        last_token=last_tok.astype(jnp.int32),
        done=lengths == 0,
        tokens=jnp.full((b, max_len), cfg.pad_id, jnp.int32),
        sample=sample if sample is not None else greedy_params(b),
    )


def _cache_dtype(state: GPTState):
    entry = state.cache_k[0]
    return entry[1].dtype if isinstance(entry, tuple) else entry.dtype


def _write_kv(cache, rows_idx, pos_idx, k_new, dtype):
    """Scatter new K (or V) into a dense or (int8, scale) cache entry."""
    if isinstance(cache, tuple):
        q8, sc = kv_quantize(k_new)
        return (
            cache[0].at[rows_idx, pos_idx].set(q8, mode="drop"),
            cache[1].at[rows_idx, pos_idx].set(sc.astype(dtype), mode="drop"),
        )
    return cache.at[rows_idx, pos_idx].set(k_new, mode="drop")


def _cache_attention(cfg: LlamaConfig, q, ck, cv, mask):
    """Attention over a dense or int8-quantized KV cache (GQA repeat
    applies to payloads and scales alike).  With ``cfg.pallas_decode``
    the single-query step runs the fused decode kernel instead: no
    materialized GQA repeat, int8 payloads dequantized in-kernel."""
    if cfg.pallas_decode and q.shape[1] == 1:
        from ..ops import autotune
        from ..ops.attention import decode_attention

        m2 = mask[:, 0, 0, :]  # [B, 1, 1, T] -> [B, T]
        quant = isinstance(ck, tuple)
        kslab = ck[0] if quant else ck
        vkey = cfg.pallas_variant or autotune.lookup(
            "decode", b=q.shape[0], kvh=kslab.shape[2],
            n_rep=q.shape[2] // kslab.shape[2], d=q.shape[3],
            block_size=0, t=kslab.shape[1], dtype=str(q.dtype), quant=quant,
            tp=cfg.tp,
        )
        if quant:
            ctx = decode_attention(
                q[:, 0], ck[0], cv[0], m2, k_scale=ck[1], v_scale=cv[1],
                interpret=cfg.pallas_interpret, variant=vkey, tp=cfg.tp,
            )
        else:
            ctx = decode_attention(q[:, 0], ck, cv, m2,
                                   interpret=cfg.pallas_interpret,
                                   variant=vkey, tp=cfg.tp)
        return ctx[:, None]  # [B, 1, H, D]
    if isinstance(ck, tuple):
        return mha_attention_kv8(
            q,
            _repeat_kv(ck[0], cfg.n_rep), _repeat_kv(ck[1], cfg.n_rep),
            _repeat_kv(cv[0], cfg.n_rep), _repeat_kv(cv[1], cfg.n_rep),
            mask=mask,
        )
    return mha_attention(
        q, _repeat_kv(ck, cfg.n_rep), _repeat_kv(cv, cfg.n_rep), mask=mask
    )


def _decode_step(params: Params, cfg: LlamaConfig, state: GPTState, sample: bool = False):
    dtype = _cache_dtype(state)
    b = state.last_token.shape[0]
    rows = jnp.arange(b)
    t = state.write_idx  # [B] per-row position
    with jax.named_scope("embed"):
        x = embed(params["embed"], state.last_token[:, None], dtype)  # [B,1,D]
    # Per-row rotary tables at each row's own position (clamped for
    # long-dead continuous-batching rows whose writes drop anyway).
    cos, sin = _rope_tables(cfg, jnp.minimum(t, cfg.max_position - 1), dtype)
    cos, sin = cos[:, None, None, :], sin[:, None, None, :]  # [B,1,1,D_h]
    key_valid = state.key_valid.at[rows, t].set(1, mode="drop")
    attn_mask = (key_valid != 0)[:, None, None, :]

    ad = lora.adapter_tables(params)
    new_k, new_v = [], []
    for li, layer in enumerate(params["layers"]):
        a = layer["attn"]
        q, k1, v1 = _qkv_rope(cfg, layer, ad, li, x, cos, sin)
        with jax.named_scope("kv_write"):
            ck = _write_kv(state.cache_k[li], rows, t, k1[:, 0], dtype)
            cv = _write_kv(state.cache_v[li], rows, t, v1[:, 0], dtype)
        new_k.append(ck)
        new_v.append(cv)
        with jax.named_scope("attn"):
            ctx = _cache_attention(cfg, q, ck, cv, attn_mask)
        with jax.named_scope("attn_out"):
            x = x + _aproj(a, ad, "o", li, merge_heads(ctx))
        x = _mlp_block(cfg, layer, x, ~state.done[:, None])
    x = rmsnorm(params["final_ln"], x, eps=cfg.rms_eps)
    next_tok, sp, done, tokens = _select_next(params, cfg, state, x[:, 0], sample)
    return (
        GPTState(
            cache_k=new_k,
            cache_v=new_v,
            key_valid=key_valid,
            write_idx=t + 1,
            pos=state.pos + 1,
            last_token=next_tok,
            done=done,
            tokens=tokens,
            sample=sp,
        ),
        next_tok,
    )


def multi_step(
    params: Params, cfg: LlamaConfig, state: GPTState, tokens: jax.Array
) -> tuple[list, list, jax.Array]:
    """Window forward for speculative verification (models/spec.py):
    D tokens per row at positions write_idx.., one pass — the llama
    variant of ``gpt.multi_step`` (per-row rotary tables at each
    window position, GQA-width cache writes).  key_valid updates are
    acceptance's job (spec.verify_step)."""
    dtype = _cache_dtype(state)
    b, d_w = tokens.shape
    rows = jnp.arange(b)[:, None]  # [B, 1]
    t = state.write_idx  # [B]
    pos_w = t[:, None] + jnp.arange(d_w)[None]  # [B, D]
    x = embed(params["embed"], tokens, dtype)  # [B, D, Dm]
    cos, sin = _rope_tables(
        cfg, jnp.minimum(pos_w, cfg.max_position - 1), dtype
    )  # [B, D, Dh]
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    total = state.key_valid.shape[1]
    pos_k = jnp.arange(total)[None, None]
    base_valid = (state.key_valid != 0)[:, None, :]
    in_window = (pos_k >= t[:, None, None]) & (pos_k <= pos_w[:, :, None])
    mask = (base_valid | in_window)[:, None]  # [B, 1, D, total]

    ad = lora.adapter_tables(params)
    new_k, new_v = [], []
    for li, layer in enumerate(params["layers"]):
        a = layer["attn"]
        q, k1, v1 = _qkv_rope(cfg, layer, ad, li, x, cos, sin)
        ck = _write_kv(state.cache_k[li], rows, pos_w, k1, dtype)
        cv = _write_kv(state.cache_v[li], rows, pos_w, v1, dtype)
        new_k.append(ck)
        new_v.append(cv)
        ctx = _cache_attention(cfg, q, ck, cv, mask)
        x = x + _aproj(a, ad, "o", li, merge_heads(ctx))
        x = _mlp_block(cfg, layer, x, ~state.done[:, None])
    x = rmsnorm(params["final_ln"], x, eps=cfg.rms_eps)
    logits = lm_head_logits(x, params["lm_head"]["kernel"], transposed=False)
    return new_k, new_v, logits  # [B, D, V]


def generate_chunk(
    params: Params, cfg: LlamaConfig, state: GPTState, n_steps: int, sample: bool = False
) -> tuple[GPTState, jax.Array]:
    """``n_steps`` decode steps in one compiled scan — the engine's
    chunk contract (static ``sample`` picks argmax vs sampling path)."""

    def step(s, _):
        return _decode_step(params, cfg, s, sample)

    state, toks = jax.lax.scan(step, state, None, length=n_steps)
    return state, jnp.transpose(toks)


def generate_window(
    params: Params, cfg: LlamaConfig, state: GPTState, n_steps: int,
    max_chunks: int, sample: bool = False,
):
    """Fused decode window (DECODE_WINDOW): up to ``max_chunks`` chunk
    scans in ONE dispatch with on-device EOS early exit — the llama
    twin of ``gpt.generate_window`` (int8 KV cache entries ride the
    while_loop carry as (payload, scale) tuples unchanged)."""
    from .window import decode_window

    return decode_window(
        lambda s: generate_chunk(params, cfg, s, n_steps, sample),
        state, n_steps, max_chunks, cfg.pad_id,
    )


def greedy_generate(
    params: Params,
    cfg: LlamaConfig,
    input_ids: jax.Array,
    attention_mask: jax.Array,
    max_len: int,
    dtype=jnp.float32,
) -> jax.Array:
    """Prefill + full decode scan, single dispatch → [B, max_len]."""
    state = init_decode_state(params, cfg, input_ids, attention_mask, max_len, dtype)
    state, _ = generate_chunk(params, cfg, state, max_len)
    return state.tokens


# ---------------------------------------------------------------------------
# block-paged decode (PAGED_KV=1) — gpt.PagedState layout at GQA width,
# composed with the int8 KV cache ((payload, scale) pool pairs).


def _paged_write_kv(cache, table, t, val, bs: int, dtype):
    """Scatter one new K (or V) row per batch row through the block
    table, into a dense pool or an (int8 payload, scale) pool pair —
    the paged mirror of ``_write_kv`` (same quantization, so paged
    int8 decode stays bit-identical to the contiguous int8 cache)."""
    from .gpt import paged_write_token

    if isinstance(cache, tuple):
        q8, sc = kv_quantize(val)
        return (
            paged_write_token(cache[0], table, t, q8, bs),
            paged_write_token(cache[1], table, t, sc.astype(dtype), bs),
        )
    return paged_write_token(cache, table, t, val, bs)


def _paged_cache_attention(cfg: LlamaConfig, q, ck, cv, table, key_valid,
                           bs: int):
    """Attention over the paged pool.  With ``cfg.pallas_decode`` the
    single-query step runs the fused paged kernel — each program DMAs
    exactly the row's live blocks, int8 payloads dequantize in VMEM.
    Otherwise the row's blocks gather to a dense view and run the
    contiguous path's exact math (token identity by construction)."""
    if cfg.pallas_decode and q.shape[1] == 1:
        from ..ops import autotune
        from ..ops.paged_attention import paged_decode_attention

        quant = isinstance(ck, tuple)
        vkey = cfg.pallas_variant or autotune.lookup(
            "paged_decode", b=q.shape[0], kvh=cfg.num_kv_heads,
            n_rep=cfg.n_rep, d=q.shape[3],
            block_size=bs, t=table.shape[1], dtype=str(q.dtype), quant=quant,
            tp=cfg.tp,
        )
        if quant:
            ctx = paged_decode_attention(
                q[:, 0], ck[0], cv[0], table, key_valid, bs,
                k_scale=ck[1], v_scale=cv[1],
                interpret=cfg.pallas_interpret, variant=vkey, tp=cfg.tp,
            )
        else:
            ctx = paged_decode_attention(q[:, 0], ck, cv, table, key_valid,
                                         bs, interpret=cfg.pallas_interpret,
                                         variant=vkey, tp=cfg.tp)
        return ctx[:, None]
    return _gathered_attention(
        cfg, q, ck, cv, table, bs, (key_valid != 0)[:, None, None, :]
    )


def _gathered_attention(cfg: LlamaConfig, q, ck, cv, table, bs: int, mask):
    """The XLA path over the pool: gather the rows' blocks, unmerge
    ``(KVH, D)`` on the gathered view (never on the pool) and run the
    contiguous path's attention."""
    from ..ops.paged_attention import gather_pages

    def dense(pool, last):
        return _repeat_kv(
            gather_pages(pool, table, bs, (cfg.num_kv_heads, last)), cfg.n_rep
        )

    d = cfg.head_dim
    if isinstance(ck, tuple):
        return mha_attention_kv8(
            q, dense(ck[0], d), dense(ck[1], 1), dense(cv[0], d),
            dense(cv[1], 1), mask=mask,
        )
    return mha_attention(q, dense(ck, d), dense(cv, d), mask=mask)


def _paged_decode_step(params: Params, cfg: LlamaConfig, state, table,
                       sample: bool = False):
    """One paged decode step: ``_decode_step`` with cache reads/writes
    resolved through the block table (RoPE, GQA, sampling and EOS
    logic unchanged — physical layout is the only difference).  With
    experts the step's second output is ``(next_tok, counts)``: the
    [L, E] assignments of the rows still decoding, a row a layer."""
    from .gpt import PagedState

    entry = state.cache_k[0]
    dtype = entry[1].dtype if isinstance(entry, tuple) else entry.dtype
    bs = entry[0].shape[1] if isinstance(entry, tuple) else entry.shape[1]
    b = state.last_token.shape[0]
    rows = jnp.arange(b)
    t = state.write_idx
    with jax.named_scope("embed"):
        x = embed(params["embed"], state.last_token[:, None], dtype)
    cos, sin = _rope_tables(cfg, jnp.minimum(t, cfg.max_position - 1), dtype)
    cos, sin = cos[:, None, None, :], sin[:, None, None, :]
    key_valid = state.key_valid.at[rows, t].set(1, mode="drop")

    ad = lora.adapter_tables(params)
    new_k, new_v, moe_tally = [], [], []
    for li, layer in enumerate(params["layers"]):
        a = layer["attn"]
        q, k1, v1 = _qkv_rope(cfg, layer, ad, li, x, cos, sin)
        with jax.named_scope("kv_write"):
            ck = _paged_write_kv(state.cache_k[li], table, t, k1[:, 0], bs, dtype)
            cv = _paged_write_kv(state.cache_v[li], table, t, v1[:, 0], bs, dtype)
        new_k.append(ck)
        new_v.append(cv)
        with jax.named_scope("attn"):
            ctx = _paged_cache_attention(cfg, q, ck, cv, table, key_valid, bs)
        with jax.named_scope("attn_out"):
            x = x + _aproj(a, ad, "o", li, merge_heads(ctx))
        x = _mlp_block(cfg, layer, x, ~state.done[:, None], moe_tally)
    x = rmsnorm(params["final_ln"], x, eps=cfg.rms_eps)
    next_tok, sp, done, tokens = _select_next(params, cfg, state, x[:, 0], sample)
    return (
        PagedState(
            cache_k=new_k, cache_v=new_v, key_valid=key_valid,
            write_idx=t + 1, pos=state.pos + 1, last_token=next_tok,
            done=done, tokens=tokens, sample=sp,
        ),
        (next_tok, jnp.stack(moe_tally)) if moe_tally else next_tok,
    )


def generate_chunk_paged(params: Params, cfg: LlamaConfig, state, table,
                         n_steps: int, sample: bool = False):
    """``n_steps`` paged decode steps in one compiled scan ->
    (state, tokens [B, n_steps]); with experts the second output is
    ``(tokens, counts)``, counts the [L, E] int32 assignments of the
    chunk, a row a layer (each sums to steps x live rows x k; a grouped
    matmul's load is one layer's) — it rides the tokens' fetch."""

    def step(s, _):
        return _paged_decode_step(params, cfg, s, table, sample)

    state, out = jax.lax.scan(step, state, None, length=n_steps)
    if cfg.num_experts:
        toks, counts = out
        return state, (jnp.transpose(toks), jnp.sum(counts, axis=0))
    return state, jnp.transpose(out)


def generate_window_paged(params: Params, cfg: LlamaConfig, state, table,
                          n_steps: int, max_chunks: int,
                          sample: bool = False):
    """Paged fused decode window over a constant block table (blocks
    for all ``max_chunks`` chunks are pre-provisioned by the engine;
    the ledger reconciles at the window boundary)."""
    from .window import decode_window

    def chunk(s):
        s, out = generate_chunk_paged(params, cfg, s, table, n_steps, sample)
        return s, (out[0] if cfg.num_experts else out)  # a window counts nothing

    return decode_window(chunk, state, n_steps, max_chunks, cfg.pad_id)


# ---------------------------------------------------------------------------
# chunked prefill (PREFILL_CHUNK) — gpt.py's window contract at GQA
# width, composed with the int8 KV cache.


def empty_decode_state(
    params: Params,
    cfg: LlamaConfig,
    batch: int,
    s_total: int,
    max_len: int,
    dtype=jnp.float32,
) -> GPTState:
    """All-zero decode state for chunked prefill (see
    ``gpt.empty_decode_state``); under ``kv_quant`` the cache entries
    are (int8 payload, scale) pairs mirroring ``init_decode_state``'s
    zero/ones init, so per-window quantized writes land in the exact
    slab layout monolithic prefill would have produced."""
    from .sampling import greedy_params

    total = s_total + max_len
    shape = (batch, total, cfg.num_kv_heads, cfg.head_dim)
    if cfg.kv_quant:
        cache_k = [
            (jnp.zeros(shape, jnp.int8), jnp.ones(shape[:3] + (1,), dtype))
            for _ in params["layers"]
        ]
        cache_v = [
            (jnp.zeros(shape, jnp.int8), jnp.ones(shape[:3] + (1,), dtype))
            for _ in params["layers"]
        ]
    else:
        cache_k = [jnp.zeros(shape, dtype) for _ in params["layers"]]
        cache_v = list(cache_k)
    return GPTState(
        cache_k=cache_k,
        cache_v=cache_v,
        key_valid=jnp.zeros((batch, total), jnp.int32),
        write_idx=jnp.zeros((batch,), jnp.int32),
        pos=jnp.zeros((batch,), jnp.int32),
        last_token=jnp.zeros((batch,), jnp.int32),
        done=jnp.ones((batch,), bool),
        tokens=jnp.full((batch, max_len), cfg.pad_id, jnp.int32),
        sample=greedy_params(batch),
    )


def prefill_chunk(
    params: Params,
    cfg: LlamaConfig,
    state: GPTState,
    chunk_ids: jax.Array,  # [B, C]
    chunk_mask: jax.Array,  # [B, C]
    start,
    dtype=jnp.float32,
) -> GPTState:
    """One prompt window into the contiguous cache (see
    ``gpt.prefill_chunk``): RoPE at each absolute window position, GQA
    cache writes (quantized per token-head under ``kv_quant`` — the
    same per-token scheme as monolithic prefill, so window grouping
    never changes the stored bytes)."""
    from .gpt import _window_mask

    b, c = chunk_ids.shape
    rows = jnp.arange(b)[:, None]
    pos_w = jnp.broadcast_to(start + jnp.arange(c)[None, :], (b, c))
    x = embed(params["embed"], chunk_ids, dtype)
    cos, sin = _rope_tables(
        cfg, jnp.minimum(pos_w, cfg.max_position - 1), dtype
    )  # [B, C, Dh]
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    mask = _window_mask(state.key_valid != 0, chunk_mask, start)

    ad = lora.adapter_tables(params)
    new_k, new_v = [], []
    for li, layer in enumerate(params["layers"]):
        a = layer["attn"]
        q, k1, v1 = _qkv_rope(cfg, layer, ad, li, x, cos, sin)
        ck = _write_kv(state.cache_k[li], rows, pos_w, k1, dtype)
        cv = _write_kv(state.cache_v[li], rows, pos_w, v1, dtype)
        new_k.append(ck)
        new_v.append(cv)
        ctx = _cache_attention(cfg, q, ck, cv, mask)
        x = x + _aproj(a, ad, "o", li, merge_heads(ctx))
        x = _mlp_block(cfg, layer, x, chunk_mask != 0)
    key_valid = state.key_valid.at[rows, pos_w].set(
        chunk_mask.astype(jnp.int32), mode="drop"
    )
    return state._replace(cache_k=new_k, cache_v=new_v, key_valid=key_valid)


def _paged_scatter_entry(cache, table_row, vals, bs: int, start, dtype):
    """Scatter one window's K (or V) rows [C, KVH, D] through the
    table into a dense pool or an (int8, scale) pool pair."""
    from ..ops.paged_attention import scatter_pages

    if isinstance(cache, tuple):
        q8, sc = kv_quantize(vals)
        return (
            scatter_pages(cache[0], table_row, q8, bs, start=start),
            scatter_pages(cache[1], table_row, sc.astype(dtype), bs, start=start),
        )
    return scatter_pages(cache, table_row, vals, bs, start=start)


def paged_prefill_chunk(
    params: Params,
    cfg: LlamaConfig,
    state,  # gpt.PagedState
    table_row: jax.Array,
    chunk_ids: jax.Array,  # [1, C]
    chunk_mask: jax.Array,
    start,
    dtype=jnp.float32,
):
    """One prompt window straight into pool blocks (see
    ``gpt.paged_prefill_chunk``), at GQA width and composed with the
    int8 pool pairs."""
    from .gpt import _window_mask

    b, c = chunk_ids.shape  # b == 1
    entry = state.cache_k[0]
    bs = entry[0].shape[1] if isinstance(entry, tuple) else entry.shape[1]
    pos_w = jnp.broadcast_to(start + jnp.arange(c)[None, :], (b, c))
    x = embed(params["embed"], chunk_ids, dtype)
    cos, sin = _rope_tables(cfg, jnp.minimum(pos_w, cfg.max_position - 1), dtype)
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    total = table_row.shape[0] * bs
    base_valid = jnp.broadcast_to(jnp.arange(total)[None, :] < start, (b, total))
    mask = _window_mask(base_valid, chunk_mask, start)

    ad = lora.adapter_tables(params)
    new_k, new_v = [], []
    for li, layer in enumerate(params["layers"]):
        a = layer["attn"]
        q, k1, v1 = _qkv_rope(cfg, layer, ad, li, x, cos, sin)
        ck = _paged_scatter_entry(state.cache_k[li], table_row, k1[0], bs, start, dtype)
        cv = _paged_scatter_entry(state.cache_v[li], table_row, v1[0], bs, start, dtype)
        new_k.append(ck)
        new_v.append(cv)
        ctx = _gathered_attention(cfg, q, ck, cv, table_row[None], bs, mask)
        x = x + _aproj(a, ad, "o", li, merge_heads(ctx))
        x = _mlp_block(cfg, layer, x, chunk_mask != 0)
    return state._replace(cache_k=new_k, cache_v=new_v)


def init_paged_state(
    params: Params,
    cfg: LlamaConfig,
    input_ids: jax.Array,
    attention_mask: jax.Array,
    max_len: int,
    table: jax.Array,  # [B, T] block ids covering the prompt width
    num_blocks: int,
    block_size: int,
    dtype=jnp.float32,
    sample=None,
):
    """Prefill straight into pool blocks (int8 pools under kv_quant,
    same per-token scales as the contiguous cache).  Paged mode has no
    global ``__prefix__`` overlay (build_model rejects the combo) —
    per-request prefixes share BLOCKS instead."""
    from ..ops.paged_attention import scatter_pages
    from .gpt import PagedState
    from .sampling import greedy_params

    b, s = input_ids.shape
    t_w = table.shape[1]
    _, kv = forward_hidden(
        params, cfg, input_ids, attention_mask, dtype, collect_kv=True
    )
    cache_k, cache_v = [], []
    # ops/paged_attention's layout rule: payload [NB, BS, KVH*D],
    # scales [NB, BS, KVH].
    shape = (num_blocks, block_size, cfg.num_kv_heads * cfg.head_dim)
    sc_shape = (num_blocks, block_size, cfg.num_kv_heads)
    for k, v in kv:
        if cfg.kv_quant:
            k8, ks = kv_quantize(k)
            v8, vs = kv_quantize(v)
            ck8 = jnp.zeros(shape, jnp.int8)
            cks = jnp.ones(sc_shape, dtype)
            cv8 = jnp.zeros(shape, jnp.int8)
            cvs = jnp.ones(sc_shape, dtype)
            for row in range(b):
                ck8 = scatter_pages(ck8, table[row], k8[row], block_size)
                cks = scatter_pages(cks, table[row], ks[row].astype(dtype), block_size)
                cv8 = scatter_pages(cv8, table[row], v8[row], block_size)
                cvs = scatter_pages(cvs, table[row], vs[row].astype(dtype), block_size)
            cache_k.append((ck8, cks))
            cache_v.append((cv8, cvs))
            continue
        ck = jnp.zeros(shape, k.dtype)
        cv = jnp.zeros(shape, v.dtype)
        for row in range(b):
            ck = scatter_pages(ck, table[row], k[row], block_size)
            cv = scatter_pages(cv, table[row], v[row], block_size)
        cache_k.append(ck)
        cache_v.append(cv)
    lengths = attention_mask.sum(axis=-1).astype(jnp.int32)
    key_valid = jnp.zeros((b, t_w * block_size), jnp.int32)
    key_valid = key_valid.at[:, :s].set(attention_mask.astype(jnp.int32))
    rows = jnp.arange(b)
    last_tok = input_ids[rows, jnp.maximum(lengths - 1, 0)]
    return PagedState(
        cache_k=cache_k,
        cache_v=cache_v,
        key_valid=key_valid,
        write_idx=jnp.maximum(lengths - 1, 0),
        pos=jnp.zeros((b,), jnp.int32),
        last_token=last_tok.astype(jnp.int32),
        done=lengths == 0,
        tokens=jnp.full((b, max_len), cfg.pad_id, jnp.int32),
        sample=sample if sample is not None else greedy_params(b),
    )
