"""Decoder-only causal LM (GPT-2 family), pure-JAX, KV-cached decode.

Model-family breadth beyond the reference's three configs (SURVEY.md §2
serves ResNet/BERT/T5): the template contract is "bring a model, get
the serving stack" — this is the decoder-only member, servable as
``MODEL_NAME=gpt2`` with streaming generation through the SAME engine
machinery as T5 (encode/init/generate_chunk trio, single-dispatch
chunked scans, early EOS exit).

Architecture (GPT-2): learned positions, pre-LN blocks, GELU MLP,
causal attention, tied LM head, final LN.

TPU-first decode design: the prompt is prefilled in ONE forward (K/V
for all prompt positions written into static [B, S+max_decode, H, D]
caches), then generation runs as ``lax.scan`` chunks with per-row write
indices — right-padded prompts of different lengths decode correctly in
one batch because each row embeds/attends at its own position, with a
key-validity mask instead of a shared causal frontier.

The first decode step recomputes the last prompt position (its cache
write is bit-identical to prefill's), which buys a uniform step
function with no special first-token path.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from . import lora
from .common import (
    Params,
    dense,
    dense_init,
    embed,
    layernorm,
    layernorm_init,
    merge_heads,
    mha_attention,
    normal_init,
    split_heads,
)


def gelu_new(x: jax.Array) -> jax.Array:
    # GPT-2 uses the tanh-approximated GELU ("gelu_new" in HF), not the
    # erf form BERT uses — checkpoint fidelity depends on matching it.
    return jax.nn.gelu(x, approximate=True)


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    d_model: int = 768
    num_heads: int = 12
    num_layers: int = 12
    d_ff: int = 3072
    max_position: int = 1024
    ln_eps: float = 1e-5
    eos_id: int = 50256
    pad_id: int = 50256  # GPT-2 has no pad token; eos doubles as pad
    # Fused Pallas decode over the paged pool (ops/paged_attention):
    # one grid program per (row, block-group) DMAs exactly the row's
    # live blocks — no gather_pages materialization.  GPT is MHA
    # (kvh == num_heads, n_rep == 1), so this is the no-GQA corner of
    # the same kernel llama serves; token-identical to the gather path
    # (tests/test_pallas_autotune.py).  Serving-only, no VJP.
    pallas_decode: bool = False
    # Variant pin / interpret-mode toggle — same contract as
    # LlamaConfig (docs/kernel_tuning.md); "" resolves through the
    # autotuner tuning table at trace time.
    pallas_variant: str = ""
    pallas_interpret: bool = False
    # Tensor-parallel width of the serving placement (registry sets it
    # from the TP knob; 1 = default, builds no mesh anywhere).  Static
    # so kernel call sites decide shard_map wrapping at trace time and
    # the autotuner keys TP entries apart (parallel/tpserve.py).
    tp: int = 1

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads


# ---------------------------------------------------------------------------
# init


def init_params(key, cfg: GPTConfig = GPTConfig()) -> Params:
    keys = jax.random.split(key, cfg.num_layers + 2)
    d = cfg.d_model
    params: Params = {
        "wte": {"embedding": normal_init(keys[0], (cfg.vocab_size, d), std=0.02)},
        "wpe": {"embedding": normal_init(keys[1], (cfg.max_position, d), std=0.01)},
        "layers": [],
        "final_ln": layernorm_init(d),
    }
    for i in range(cfg.num_layers):
        k = jax.random.split(keys[2 + i], 4)
        params["layers"].append(
            {
                "ln1": layernorm_init(d),
                "attn": {
                    "qkv": dense_init(k[0], d, 3 * d, std=0.02),
                    "out": dense_init(k[1], d, d, std=0.02),
                },
                "ln2": layernorm_init(d),
                "mlp": {
                    "up": dense_init(k[2], d, cfg.d_ff, std=0.02),
                    "down": dense_init(k[3], cfg.d_ff, d, std=0.02),
                },
            }
        )
    return params


def _qkv(p, cfg: GPTConfig, x, ad=None, li=0):
    qkv = lora.apply(ad, "qkv", li, x, dense(p["qkv"], x))
    q, k, v = jnp.split(qkv, 3, axis=-1)
    return (split_heads(t, cfg.num_heads) for t in (q, k, v))


def _attn_out(p, x, ad=None, li=0):
    """Attention output projection (+ per-row LoRA delta when serving
    a ``__adapters__`` overlay; models/lora.py)."""
    return lora.apply(ad, "out", li, x, dense(p["out"], x))


def _logits(params: Params, cfg: GPTConfig, x) -> jax.Array:
    """Tied LM head; logits in f32 for exact argmax.  Quantized tables
    go through the scale-factored matmul (``common.lm_head_logits``) so
    no full-precision copy of wte is ever materialized in the scan."""
    from .common import lm_head_logits

    return lm_head_logits(x, params["wte"]["embedding"], transposed=True)


# ---------------------------------------------------------------------------
# prefill (full prompt forward)


def forward_hidden(
    params: Params,
    cfg: GPTConfig,
    input_ids: jax.Array,  # [B, S]
    attention_mask: jax.Array,  # [B, S]
    dtype=jnp.float32,
    collect_kv: bool = False,
    prefix_kv=None,  # optional list[(k,v)] of [1, P, H, D] cached prefix
):
    """Hidden states [B, S, D] (+ per-layer prompt K/V when collecting).

    With ``prefix_kv`` the batch is the SUFFIX of a shared cached
    prompt prefix (prompt-prefix caching): tokens embed at positions
    P.., every query attends to the whole prefix plus its causal
    suffix context, and only suffix K/V is computed — prefill cost is
    O(S), not O(P+S).
    """
    b, s = input_ids.shape
    p_len = 0 if prefix_kv is None else prefix_kv[0][0].shape[1]
    x = embed(params["wte"], input_ids, dtype)
    pos = jnp.arange(p_len, p_len + s, dtype=jnp.int32)
    x = x + embed(params["wpe"], pos, dtype)[None]
    causal = jnp.tril(jnp.ones((s, s), bool))
    mask = causal[None, None] & (attention_mask[:, None, None, :] != 0)
    if p_len:
        pre = jnp.ones((1, 1, s, p_len), bool)  # prefix fully visible
        mask = jnp.concatenate([jnp.broadcast_to(pre, (b, 1, s, p_len)), mask], axis=-1)
    ad = lora.adapter_tables(params)
    kv = []
    for li, layer in enumerate(params["layers"]):
        h = layernorm(layer["ln1"], x, eps=cfg.ln_eps)
        q, k, v = _qkv(layer["attn"], cfg, h, ad, li)
        if collect_kv:
            kv.append((k, v))
        if p_len:
            pk, pv = prefix_kv[li]
            k = jnp.concatenate([jnp.broadcast_to(pk.astype(k.dtype), (b,) + pk.shape[1:]), k], axis=1)
            v = jnp.concatenate([jnp.broadcast_to(pv.astype(v.dtype), (b,) + pv.shape[1:]), v], axis=1)
        ctx = mha_attention(q, k, v, mask=mask)
        x = x + _attn_out(layer["attn"], merge_heads(ctx), ad, li)
        h = layernorm(layer["ln2"], x, eps=cfg.ln_eps)
        x = x + dense(layer["mlp"]["down"], gelu_new(dense(layer["mlp"]["up"], h)))
    x = layernorm(params["final_ln"], x, eps=cfg.ln_eps)
    return (x, kv) if collect_kv else x


def compute_prefix_kv(params: Params, cfg: GPTConfig, prefix_ids, dtype=jnp.float32):
    """Per-layer K/V of a shared prompt prefix ([1, P] ids) — computed
    ONCE at startup and carried in the params pytree under
    ``__prefix__`` so placement/sharding/jit treat it like weights."""
    ids = jnp.asarray(prefix_ids, jnp.int32).reshape(1, -1)
    _, kv = forward_hidden(
        params, cfg, ids, jnp.ones_like(ids), dtype, collect_kv=True
    )
    return {"k": [k for k, _ in kv], "v": [v for _, v in kv]}


def lm_logits(
    params: Params, cfg: GPTConfig, input_ids, attention_mask, dtype=jnp.float32
) -> jax.Array:
    """[B, S, V] next-token logits (the non-generative forward)."""
    return _logits(params, cfg, forward_hidden(params, cfg, input_ids, attention_mask, dtype))


# ---------------------------------------------------------------------------
# incremental decode


class GPTState(NamedTuple):
    """Static-shape decode state; caches span prompt + decode budget.

    EVERY field is per-row (leading dim B): rows decode independently,
    which is what lets a continuous-batching loop insert a freshly
    prefilled request into slot i while other rows are mid-generation
    (``engine/streams.py``).
    """

    cache_k: Any  # per layer [B, S+Tmax, H, D]
    cache_v: Any
    key_valid: jax.Array  # [B, S+Tmax] int32 — 1 where cache rows are real
    write_idx: jax.Array  # [B] int32 — position the NEXT step processes
    pos: jax.Array  # [B] int32 — decode steps taken per row
    last_token: jax.Array  # [B] int32 — token the next step embeds
    done: jax.Array  # [B] bool
    tokens: jax.Array  # [B, Tmax] generated tokens (pad-filled)
    sample: Any  # sampling.SampleParams, all [B]-shaped
    # Recurrent state beside the cache (llama.SsmState; a config with
    # Mamba layers): no leaf, and no trace in a lowered program, otherwise.
    ssm: Any = ()


def init_decode_state(
    params: Params,
    cfg: GPTConfig,
    input_ids: jax.Array,  # [B, S] right-padded
    attention_mask: jax.Array,  # [B, S]
    max_len: int,
    dtype=jnp.float32,
    sample=None,  # SampleParams [B] or None (greedy)
) -> GPTState:
    from .sampling import greedy_params

    b, s = input_ids.shape
    pre = params.get("__prefix__") if isinstance(params, dict) else None
    p_len = pre["k"][0].shape[1] if pre is not None else 0
    prefix_kv = list(zip(pre["k"], pre["v"])) if pre is not None else None
    total = p_len + s + max_len
    _, kv = forward_hidden(
        params, cfg, input_ids, attention_mask, dtype,
        collect_kv=True, prefix_kv=prefix_kv,
    )
    cache_k, cache_v = [], []
    for li, (k, v) in enumerate(kv):
        ck = jnp.zeros((b, total, cfg.num_heads, cfg.head_dim), k.dtype)
        cv = ck
        if p_len:
            pk, pv = prefix_kv[li]
            ck = ck.at[:, :p_len].set(pk.astype(ck.dtype))
            cv = cv.at[:, :p_len].set(pv.astype(cv.dtype))
        cache_k.append(ck.at[:, p_len : p_len + s].set(k))
        cache_v.append(cv.at[:, p_len : p_len + s].set(v))
    lengths = attention_mask.sum(axis=-1).astype(jnp.int32)  # [B]
    key_valid = jnp.zeros((b, total), jnp.int32)
    if p_len:
        key_valid = key_valid.at[:, :p_len].set(1)
    key_valid = key_valid.at[:, p_len : p_len + s].set(
        attention_mask.astype(jnp.int32)
    )
    rows = jnp.arange(b)
    # The first step re-processes the last prompt token at its own
    # position (identical K/V overwrite), producing the first generated
    # token's logits — one uniform step fn, no prefill/decode seam.
    last_tok = input_ids[rows, jnp.maximum(lengths - 1, 0)]
    return GPTState(
        cache_k=cache_k,
        cache_v=cache_v,
        key_valid=key_valid,
        write_idx=p_len + jnp.maximum(lengths - 1, 0),
        pos=jnp.zeros((b,), jnp.int32),
        last_token=last_tok.astype(jnp.int32),
        done=lengths == 0,  # fully-pad rows never generate
        tokens=jnp.full((b, max_len), cfg.pad_id, jnp.int32),
        sample=sample if sample is not None else greedy_params(b),
    )


def _decode_step(params: Params, cfg: GPTConfig, state: GPTState, sample: bool = False):
    dtype = state.cache_k[0].dtype
    b = state.last_token.shape[0]
    rows = jnp.arange(b)
    t = state.write_idx  # [B] per-row position
    x = embed(params["wte"], state.last_token[:, None], dtype)  # [B,1,D]
    # Long-dead rows (continuous batching: slot freed, not yet reused)
    # keep stepping; clamp their position lookup and DROP their writes
    # so they never corrupt in-range cache entries.
    x = x + embed(params["wpe"], jnp.minimum(t, cfg.max_position - 1), dtype)[:, None]
    key_valid = state.key_valid.at[rows, t].set(1, mode="drop")
    attn_mask = (key_valid != 0)[:, None, None, :]  # [B,1,1,total]

    ad = lora.adapter_tables(params)
    new_k, new_v = [], []
    for li, layer in enumerate(params["layers"]):
        h = layernorm(layer["ln1"], x, eps=cfg.ln_eps)
        q, k1, v1 = _qkv(layer["attn"], cfg, h, ad, li)  # [B,1,H,D]
        ck = state.cache_k[li].at[rows, t].set(k1[:, 0], mode="drop")
        cv = state.cache_v[li].at[rows, t].set(v1[:, 0], mode="drop")
        new_k.append(ck)
        new_v.append(cv)
        ctx = mha_attention(q, ck, cv, mask=attn_mask)
        x = x + _attn_out(layer["attn"], merge_heads(ctx), ad, li)
        h = layernorm(layer["ln2"], x, eps=cfg.ln_eps)
        x = x + dense(layer["mlp"]["down"], gelu_new(dense(layer["mlp"]["up"], h)))
    x = layernorm(params["final_ln"], x, eps=cfg.ln_eps)
    logits = _logits(params, cfg, x[:, 0])  # [B, V]

    if sample:
        from .sampling import select_token

        next_tok, sp = select_token(logits, state.sample)
    else:
        next_tok, sp = jnp.argmax(logits, axis=-1).astype(jnp.int32), state.sample
    next_tok = jnp.where(state.done, jnp.int32(cfg.pad_id), next_tok)
    done = state.done | (next_tok == cfg.eos_id)
    tokens = state.tokens.at[rows, state.pos].set(next_tok, mode="drop")
    new_state = GPTState(
        cache_k=new_k,
        cache_v=new_v,
        key_valid=key_valid,
        write_idx=t + 1,
        pos=state.pos + 1,
        last_token=next_tok,
        done=done,
        tokens=tokens,
        sample=sp,
    )
    return new_state, next_tok


def multi_step(
    params: Params, cfg: GPTConfig, state: GPTState, tokens: jax.Array
) -> tuple[list, list, jax.Array]:
    """Window forward for speculative verification (models/spec.py):
    process D tokens per row at positions write_idx..write_idx+D-1 in
    ONE pass.  Writes K/V for every window position (cache rows beyond
    the buffer drop), attends each query to the valid cache PLUS its
    causal in-window prefix, and returns (new_k, new_v, logits
    [B, D, V]).  key_valid is NOT updated here — acceptance decides
    which window positions become real (spec.verify_step)."""
    dtype = state.cache_k[0].dtype
    b, d_w = tokens.shape
    rows = jnp.arange(b)[:, None]  # [B, 1]
    t = state.write_idx  # [B]
    pos_w = t[:, None] + jnp.arange(d_w)[None]  # [B, D]
    x = embed(params["wte"], tokens, dtype)  # [B, D, Dm]
    x = x + embed(params["wpe"], jnp.minimum(pos_w, cfg.max_position - 1), dtype)
    total = state.key_valid.shape[1]
    pos_k = jnp.arange(total)[None, None]  # [1, 1, total]
    base_valid = (state.key_valid != 0)[:, None, :]  # [B, 1, total]
    in_window = (pos_k >= t[:, None, None]) & (pos_k <= pos_w[:, :, None])
    mask = (base_valid | in_window)[:, None]  # [B, 1, D, total]

    ad = lora.adapter_tables(params)
    new_k, new_v = [], []
    for li, layer in enumerate(params["layers"]):
        h = layernorm(layer["ln1"], x, eps=cfg.ln_eps)
        q, k1, v1 = _qkv(layer["attn"], cfg, h, ad, li)  # [B, D, H, Dh]
        ck = state.cache_k[li].at[rows, pos_w].set(k1, mode="drop")
        cv = state.cache_v[li].at[rows, pos_w].set(v1, mode="drop")
        new_k.append(ck)
        new_v.append(cv)
        ctx = mha_attention(q, ck, cv, mask=mask)
        x = x + _attn_out(layer["attn"], merge_heads(ctx), ad, li)
        h = layernorm(layer["ln2"], x, eps=cfg.ln_eps)
        x = x + dense(layer["mlp"]["down"], gelu_new(dense(layer["mlp"]["up"], h)))
    x = layernorm(params["final_ln"], x, eps=cfg.ln_eps)
    return new_k, new_v, _logits(params, cfg, x)  # [B, D, V]


def generate_chunk(
    params: Params, cfg: GPTConfig, state: GPTState, n_steps: int, sample: bool = False
) -> tuple[GPTState, jax.Array]:
    """``n_steps`` decode steps in one compiled scan; returns
    (state, [B, n_steps] tokens) — the engine's chunk contract.
    ``sample`` is STATIC: False compiles the argmax fast path (no
    [B, V] sort per step), True the per-row sampling path."""

    def step(s, _):
        return _decode_step(params, cfg, s, sample)

    state, toks = jax.lax.scan(step, state, None, length=n_steps)
    return state, jnp.transpose(toks)


def greedy_generate(
    params: Params,
    cfg: GPTConfig,
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
# block-paged decode (PAGED_KV=1; engine/kv_blocks.py owns the tables)


class PagedState(NamedTuple):
    """Decode state over a block-paged KV pool (``PAGED_KV=1``).

    Identical to ``GPTState`` except the caches: instead of per-row
    contiguous ``[B, W, H, D]`` slabs, K/V live in pools of
    ``block_size``-token blocks ``[NB, BS, H*D]`` (a token's dims
    merged: ops/paged_attention's layout rule) shared by every
    row, and logical position ``p`` of row ``b`` resolves through a
    host-owned block table (``table[b, p // BS]``) that rides into
    each dispatch as a traced argument — NOT part of this state, so
    the host can grow/free blocks between dispatches without touching
    device buffers.  All non-cache fields keep their per-row GPTState
    semantics, which is what keeps paged decode token-identical to the
    contiguous layout: positions, masks and sampling never change,
    only where a KV row physically lives."""

    cache_k: Any  # per layer [NB, BS, H*D] pool ((int8, [NB, BS, H] scale) under QUANT_KV)
    cache_v: Any
    key_valid: jax.Array  # [B, W] int32 over LOGICAL positions (W = T*BS)
    write_idx: jax.Array  # [B]
    pos: jax.Array  # [B]
    last_token: jax.Array  # [B]
    done: jax.Array  # [B]
    tokens: jax.Array  # [B, Tmax]
    sample: Any
    # llama.SsmState with its own R rows (rows are streams, not slots:
    # ``row`` [B] names each slot's), or nothing: see GPTState.ssm.
    ssm: Any = ()


def _paged_dest(table: jax.Array, t: jax.Array, bs: int, nb: int) -> jax.Array:
    """Flat pool index of logical position ``t`` per row; out-of-table
    positions (long-dead rows) and sentinel table entries both resolve
    out of range so ``.at[].set(mode="drop")`` drops them."""
    bidx = t // bs
    blk = jnp.take_along_axis(
        table, jnp.minimum(bidx, table.shape[1] - 1)[:, None], axis=1
    )[:, 0]
    blk = jnp.where(bidx < table.shape[1], blk, nb)
    return blk * bs + t % bs


def paged_write_token(pool, table, t, val, bs: int):
    """Scatter one new K (or V) row per batch row ``[B, ...]`` into a
    pool ``[NB, BS, C]`` through the table."""
    from ..ops.paged_attention import scatter_rows

    return scatter_rows(pool, _paged_dest(table, t, bs, pool.shape[0]), val)


def _paged_decode_step(
    params: Params, cfg: GPTConfig, state: PagedState, table: jax.Array,
    sample: bool = False,
):
    """One decode step reading/writing K/V through the block table;
    everything else is ``_decode_step`` verbatim — same positions,
    same mask semantics, same logits — so greedy outputs are
    token-identical to the contiguous path."""
    from ..ops.paged_attention import gather_pages

    dtype = state.cache_k[0].dtype
    bs = state.cache_k[0].shape[1]
    b = state.last_token.shape[0]
    rows = jnp.arange(b)
    t = state.write_idx
    x = embed(params["wte"], state.last_token[:, None], dtype)
    x = x + embed(params["wpe"], jnp.minimum(t, cfg.max_position - 1), dtype)[:, None]
    key_valid = state.key_valid.at[rows, t].set(1, mode="drop")
    attn_mask = (key_valid != 0)[:, None, None, :]

    ad = lora.adapter_tables(params)
    new_k, new_v = [], []
    for li, layer in enumerate(params["layers"]):
        h = layernorm(layer["ln1"], x, eps=cfg.ln_eps)
        q, k1, v1 = _qkv(layer["attn"], cfg, h, ad, li)
        ck = paged_write_token(state.cache_k[li], table, t, k1[:, 0], bs)
        cv = paged_write_token(state.cache_v[li], table, t, v1[:, 0], bs)
        new_k.append(ck)
        new_v.append(cv)
        if cfg.pallas_decode:
            from ..ops import autotune
            from ..ops.paged_attention import paged_decode_attention

            vkey = cfg.pallas_variant or autotune.lookup(
                "paged_decode", b=b, kvh=cfg.num_heads, n_rep=1,
                d=q.shape[3], block_size=bs, t=table.shape[1],
                dtype=str(q.dtype), quant=False, tp=cfg.tp,
            )
            ctx = paged_decode_attention(
                q[:, 0], ck, cv, table, key_valid, bs,
                interpret=cfg.pallas_interpret, variant=vkey, tp=cfg.tp,
            )[:, None]
        else:
            tail = (cfg.num_heads, cfg.head_dim)
            kd = gather_pages(ck, table, bs, tail)
            vd = gather_pages(cv, table, bs, tail)
            ctx = mha_attention(q, kd, vd, mask=attn_mask)
        x = x + _attn_out(layer["attn"], merge_heads(ctx), ad, li)
        h = layernorm(layer["ln2"], x, eps=cfg.ln_eps)
        x = x + dense(layer["mlp"]["down"], gelu_new(dense(layer["mlp"]["up"], h)))
    x = layernorm(params["final_ln"], x, eps=cfg.ln_eps)
    logits = _logits(params, cfg, x[:, 0])

    if sample:
        from .sampling import select_token

        next_tok, sp = select_token(logits, state.sample)
    else:
        next_tok, sp = jnp.argmax(logits, axis=-1).astype(jnp.int32), state.sample
    next_tok = jnp.where(state.done, jnp.int32(cfg.pad_id), next_tok)
    done = state.done | (next_tok == cfg.eos_id)
    tokens = state.tokens.at[rows, state.pos].set(next_tok, mode="drop")
    return (
        PagedState(
            cache_k=new_k, cache_v=new_v, key_valid=key_valid,
            write_idx=t + 1, pos=state.pos + 1, last_token=next_tok,
            done=done, tokens=tokens, sample=sp,
        ),
        next_tok,
    )


def generate_chunk_paged(
    params: Params, cfg: GPTConfig, state: PagedState, table: jax.Array,
    n_steps: int, sample: bool = False,
) -> tuple[PagedState, jax.Array]:
    """``n_steps`` paged decode steps in one compiled scan (the
    engine's chunk contract, plus the traced block table)."""

    def step(s, _):
        return _paged_decode_step(params, cfg, s, table, sample)

    state, toks = jax.lax.scan(step, state, None, length=n_steps)
    return state, jnp.transpose(toks)


# ---------------------------------------------------------------------------
# chunked prefill (PREFILL_CHUNK; engine/streams.py drives the windows)


def empty_decode_state(
    params: Params,
    cfg: GPTConfig,
    batch: int,
    s_total: int,
    max_len: int,
    dtype=jnp.float32,
) -> GPTState:
    """All-zero decode state sized for a chunked prefill: caches span
    ``s_total`` prompt positions plus the decode budget, every row
    born done.  ``prefill_chunk`` fills the prompt region window by
    window; the continuous loop flips the row live (write_idx /
    last_token / done / sample) once the prompt is exhausted, at which
    point the state is positionally what ``init_decode_state`` would
    have produced for the same prompt."""
    from .sampling import greedy_params

    total = s_total + max_len
    cache = [
        jnp.zeros((batch, total, cfg.num_heads, cfg.head_dim), dtype)
        for _ in params["layers"]
    ]
    return GPTState(
        cache_k=cache,
        cache_v=list(cache),
        key_valid=jnp.zeros((batch, total), jnp.int32),
        write_idx=jnp.zeros((batch,), jnp.int32),
        pos=jnp.zeros((batch,), jnp.int32),
        last_token=jnp.zeros((batch,), jnp.int32),
        done=jnp.ones((batch,), bool),
        tokens=jnp.full((batch, max_len), cfg.pad_id, jnp.int32),
        sample=greedy_params(batch),
    )


def _window_mask(base_valid: jax.Array, chunk_mask: jax.Array, start):
    """[B, 1, C, total] attention mask for one prefill window: every
    already-valid cache position (``base_valid`` [B, total] bool —
    previous windows, or an adopted/seeded prefix) plus the causal,
    pad-gated in-window prefix.  ``start`` is traced (a scalar, or
    [B, 1]: each row's own), so one executable serves every window of a
    prompt."""
    b, c = chunk_mask.shape
    total = base_valid.shape[1]
    pos_k = jnp.arange(total)[None, :]  # [1, total]
    off = pos_k - start  # key offset into the window
    in_win = (off >= 0) & (off < c)
    wvalid = jnp.take_along_axis(
        chunk_mask.astype(jnp.int32),
        jnp.clip(jnp.broadcast_to(off, (b, total)), 0, c - 1),
        axis=1,
    )
    win_keys = in_win & (wvalid != 0)  # [B, total]
    causal = off[:, None, :] <= jnp.arange(c)[None, :, None]  # [1, C, total]
    return (base_valid[:, None, :] | (win_keys[:, None, :] & causal))[:, None]


def prefill_chunk(
    params: Params,
    cfg: GPTConfig,
    state: GPTState,
    chunk_ids: jax.Array,  # [B, C] window of the prompt, right-padded
    chunk_mask: jax.Array,  # [B, C]
    start,  # traced scalar: absolute position of chunk_ids[:, 0]
    dtype=jnp.float32,
) -> GPTState:
    """Consume one prompt window [start, start+C) into the decode
    state: K/V written at absolute positions, ``key_valid`` extended,
    each window query attending to the whole already-prefilled prefix
    plus its causal in-window context — token-identical to the
    monolithic prompt forward, one bounded dispatch at a time.  The
    last window's pad tail writes junk K/V past the prompt (exactly
    like monolithic prefill's bucket padding): ``key_valid`` never
    marks it, and decode overwrites each position in the same step
    that validates it."""
    b, c = chunk_ids.shape
    rows = jnp.arange(b)[:, None]
    pos_w = jnp.broadcast_to(start + jnp.arange(c)[None, :], (b, c))
    x = embed(params["wte"], chunk_ids, dtype)
    x = x + embed(params["wpe"], jnp.minimum(pos_w, cfg.max_position - 1), dtype)
    mask = _window_mask(state.key_valid != 0, chunk_mask, start)

    ad = lora.adapter_tables(params)
    new_k, new_v = [], []
    for li, layer in enumerate(params["layers"]):
        h = layernorm(layer["ln1"], x, eps=cfg.ln_eps)
        q, k1, v1 = _qkv(layer["attn"], cfg, h, ad, li)  # [B, C, H, D]
        ck = state.cache_k[li].at[rows, pos_w].set(k1, mode="drop")
        cv = state.cache_v[li].at[rows, pos_w].set(v1, mode="drop")
        new_k.append(ck)
        new_v.append(cv)
        ctx = mha_attention(q, ck, cv, mask=mask)
        x = x + _attn_out(layer["attn"], merge_heads(ctx), ad, li)
        h = layernorm(layer["ln2"], x, eps=cfg.ln_eps)
        x = x + dense(layer["mlp"]["down"], gelu_new(dense(layer["mlp"]["up"], h)))
    key_valid = state.key_valid.at[rows, pos_w].set(
        chunk_mask.astype(jnp.int32), mode="drop"
    )
    return state._replace(cache_k=new_k, cache_v=new_v, key_valid=key_valid)


def paged_prefill_chunk(
    params: Params,
    cfg: GPTConfig,
    state: PagedState,
    table_rows: jax.Array,  # [B, T] each stream's block table (sentinel-padded)
    chunk_ids: jax.Array,  # [B, C]
    chunk_mask: jax.Array,  # [B, C]
    starts: jax.Array,  # [B]
    dtype=jnp.float32,
) -> PagedState:
    """One prompt window each of ``B`` different streams written
    straight into their pool blocks (PREFILL_CHUNK × PAGED_KV): a row's
    K/V scatter through its own block table at absolute positions;
    attention reads back through a dense gather of each stream's own
    blocks (adopted CoW prefix blocks included, so a prefix-cache hit
    suffix-prefills in chunks with no KV copy).  Only the pool leaves
    change — the slot rows' logical fields belong to OTHER streams and
    are untouched; a stream's row fields land at handoff
    (engine/streams.py).  A row's valid keys are exactly the positions
    below its ``starts`` entry: the prompt is contiguous from 0, so no
    per-row key_valid is needed mid-prefill."""
    from ..ops.paged_attention import gather_pages, scatter_pages

    b, c = chunk_ids.shape
    bs = state.cache_k[0].shape[1]
    first = starts[:, None]  # [B, 1]: each row's own start
    pos_w = first + jnp.arange(c)[None, :]
    x = embed(params["wte"], chunk_ids, dtype)
    x = x + embed(params["wpe"], jnp.minimum(pos_w, cfg.max_position - 1), dtype)
    total = table_rows.shape[1] * bs
    mask = _window_mask(jnp.arange(total)[None, :] < first, chunk_mask, first)

    ad = lora.adapter_tables(params)
    new_k, new_v = [], []
    tail = (cfg.num_heads, cfg.head_dim)
    for li, layer in enumerate(params["layers"]):
        h = layernorm(layer["ln1"], x, eps=cfg.ln_eps)
        q, k1, v1 = _qkv(layer["attn"], cfg, h, ad, li)
        ck, cv = state.cache_k[li], state.cache_v[li]
        for r in range(b):
            ck = scatter_pages(ck, table_rows[r], k1[r], bs, start=starts[r])
            cv = scatter_pages(cv, table_rows[r], v1[r], bs, start=starts[r])
        new_k.append(ck)
        new_v.append(cv)
        kd = gather_pages(ck, table_rows, bs, tail)
        vd = gather_pages(cv, table_rows, bs, tail)
        ctx = mha_attention(q, kd, vd, mask=mask)
        x = x + _attn_out(layer["attn"], merge_heads(ctx), ad, li)
        h = layernorm(layer["ln2"], x, eps=cfg.ln_eps)
        x = x + dense(layer["mlp"]["down"], gelu_new(dense(layer["mlp"]["up"], h)))
    return state._replace(cache_k=new_k, cache_v=new_v)


def init_paged_state(
    params: Params,
    cfg: GPTConfig,
    input_ids: jax.Array,  # [B, S] right-padded
    attention_mask: jax.Array,
    max_len: int,
    table: jax.Array,  # [B, T] block ids covering S (+ growth later)
    num_blocks: int,
    block_size: int,
    dtype=jnp.float32,
    sample=None,
) -> PagedState:
    """Prefill straight into pool blocks: the prompt forward's K/V
    scatter through the table instead of filling a contiguous slab."""
    from ..ops.paged_attention import scatter_pages
    from .sampling import greedy_params

    b, s = input_ids.shape
    t_w = table.shape[1]
    _, kv = forward_hidden(
        params, cfg, input_ids, attention_mask, dtype, collect_kv=True
    )
    cache_k, cache_v = [], []
    for k, v in kv:
        shape = (num_blocks, block_size, cfg.num_heads * cfg.head_dim)
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
