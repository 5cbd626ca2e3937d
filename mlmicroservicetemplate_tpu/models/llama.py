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
attention time), SwiGLU MLP (down(silu(gate)·up)), no biases anywhere, an
LM head of its own (``tie_embeddings``: the embedding table read again,
transposed).  Two published variations of the block are config
fields, off by default: a sparse expert FFN in place of the MLP
(``num_experts``; ops/moe.py — OLMoE-1B-7B: 64 experts, top-8) and an
RMSNorm on q and k before RoPE (``qk_norm``).  Later variations, each a
config field whose default leaves the block as it was, are documented on
``LlamaConfig``: per-layer patterns and window layers, latent attention, three
recurrences with state rows beside the pool, and a cross-decoder whose layers
read what an earlier layer of the same step produced (``layer_types`` "cross"
/ "gmu", differential attention, window rings).

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

import contextlib
import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from . import lora
from .common import (
    Params,
    dense,
    dense_init,
    embed,
    kv_quantize,
    layernorm,
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
    qk_norm: bool | str = False
    # Prompts start with the tokenizer's BOS (the Llama / Mistral
    # convention; registry._build_llama applies it to the tokenizer).
    # OLMoE's tokenizer has no BOS: a token every stream shares at
    # position 0 is also what collapses a seeded random router onto a
    # few experts (PERF.md section 6, PR 27).
    add_bos: bool = True
    # A per-layer pattern (Trinity / AFMoE-style; every field's default is
    # the one-kind block above).  ``layer_kind(li)`` is the one place that
    # answers "what is layer li"; every step kind asks it.
    #   attention: ``layer_types`` names each layer "window" or "full"
    #   (HF's "sliding_attention" / "full_attention" are accepted; empty =
    #   all full); a window layer's query at position i sees keys
    #   i-window+1..i.
    layer_types: tuple = ()
    window: int = 0
    #   FFN: the first ``num_dense_layers`` layers are dense SwiGLU of width
    #   ``d_ff_dense`` whatever ``num_experts`` says; the others follow it.
    num_dense_layers: int = 0
    d_ff_dense: int = 0
    # Width of one head where it is a size of its own (0 = d_model //
    # num_heads): q is then num_heads * head_dim wide, not d_model.
    head_dim: int = 0
    # The router (ops/moe.py): ``router_score`` softmax | sigmoid over all
    # experts in f32; ``router_bias`` adds a per-expert bias to the scores
    # for the SELECTION only; the chosen scores (renormalised under
    # ``norm_topk_prob``) are multiplied by ``route_scale``;
    # ``num_shared_experts`` experts of width ``d_ff`` run on every token.
    num_shared_experts: int = 0
    router_score: str = "softmax"
    route_scale: float = 1.0
    router_bias: bool = False
    # Block variants: a learned-scale RMSNorm AFTER the attention and after
    # the FFN as well as before (``sandwich_norm``); a sigmoid gate
    # ``sigmoid(y W_g)`` on the attention output before ``W_o``
    # (``attn_gate``); ``qk_norm="head"`` = the q/k RMSNorm per head over
    # ``head_dim`` (True = OLMoE's, over the whole projection); no
    # positional encoding on full-attention layers (``nope_on_full``); the
    # embedding scaled by sqrt(d_model) (``mup_embed``).
    sandwich_norm: bool = False
    attn_gate: bool = False
    nope_on_full: bool = False
    mup_embed: bool = False
    # The attention kind: "gqa" (everything above) | "mla", multi-head
    # latent attention (DeepSeek-V2): q through a ``q_lora_rank``-wide
    # bottleneck with its own RMSNorm; K and V through ONE
    # ``kv_lora_rank``-wide latent a token with its own RMSNorm plus one
    # ``qk_rope_head_dim``-wide rotary key shared by every head; heads of
    # ``qk_nope_head_dim + qk_rope_head_dim`` for scores (``head_dim`` is
    # set to that) and ``v_head_dim`` for values.  The cache holds the
    # latent row [c ; k_rope], once a token a layer, no heads axis and no
    # V pool (``latent_lanes``); the decode step is absorbed, prefill
    # expanded (``_mla_*`` below).  ``num_kv_heads`` is not read.
    attention: str = "gqa"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Rotary scaling: None, or YaRN's keys as published (``type`` "yarn",
    # ``factor``, ``original_max_position_embeddings``, ``beta_fast``,
    # ``beta_slow``, ``mscale``, ``mscale_all_dim``): blended frequencies
    # in ``_rope_tables``, ``mscale(all_dim)^2`` in the softmax scale.
    # A dict is stored as its sorted items (the config is hashed).
    rope_scaling: Any = None
    # The router's group limit (ops/moe.group_limited): ``n_group`` groups
    # of consecutive experts, the ``topk_group`` best kept (0/1 = none).
    n_group: int = 0
    topk_group: int = 0
    # A chip's share of the experts: the tree holds ``experts_held``
    # experts from ``expert_first`` on (0 = all, as ever); the router
    # stays ``num_experts`` wide and selects over all of them.
    experts_held: int = 0
    expert_first: int = 0
    # Layers that are a mixer OR an FFN alone (Nemotron-H style):
    # ``layer_pattern`` is one letter a layer, the published string cut to
    # ``num_layers`` — "M" a Mamba-2 mixer (``ssm_heads`` heads of
    # ``ssm_head_dim``, ``ssm_groups`` groups of B and C, ``ssm_state``
    # wide, a causal depthwise convolution over ``ssm_conv`` taps, the scan
    # in chunks of ``ssm_chunk``; ops/ssm.py), "*" an attention
    # (``attention``; rotated unless ``nope_on_full``), "E" the expert FFN.
    # Each is pre-norm with its own residual.  Only "*" layers have a cache
    # entry, only "M" layers a recurrent state (``SsmState``), only "E"
    # layers a row of the routing tally.  Empty = every layer is attention
    # then FFN, as ever.
    layer_pattern: str = ""
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 0
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # The expert block's shape (ops/moe.py): ``expert_act`` "silu" (gated:
    # gate, up, down) | "relu2" (non-gated ``down(relu(up x)^2)``, no gate
    # stack), the shared expert alike; ``moe_latent`` > 0 puts the routed
    # experts in a latent that wide between a down- and an up-projection
    # the layer shares; ``d_ff_shared`` the shared expert's width where it
    # is a size of its own (0 = ``num_shared_experts * d_ff``).
    expert_act: str = "silu"
    moe_latent: int = 0
    d_ff_shared: int = 0
    # A Gated-DeltaNet mixer (ops/ssm.py; GigaChat3.5-style) on the layers
    # ``layer_types`` names "linear" (HF's "linear_attention"): such a layer
    # is that mixer THEN its FFN (dense or experts, as ``num_dense_layers``
    # says), the other layers ``attention`` then theirs.  ``gdn_key_heads``
    # heads of ``gdn_key_dim`` for q and k, ``gdn_value_heads`` (a multiple)
    # of ``gdn_value_dim`` for v and the gate z, a causal depthwise
    # convolution of ``gdn_conv`` taps without bias over [q | k | v], the
    # output gate ``gdn_gate_scale * sigmoid(z)`` (the published
    # ``linear_sigmoid_gate_scale``).  Its state — a [Dv, Dk] float32 matrix
    # a value head and the taps — lives in the same state rows as Mamba's
    # (``SsmState``).
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_key_dim: int = 0
    gdn_value_dim: int = 0
    gdn_conv: int = 4
    gdn_gate_scale: float = 2.0
    # Every SwiGLU (dense, routed, shared) clamped where > 0:
    # ``silu(min(gate, limit)) * clip(up, -limit, limit)``.
    swiglu_limit: float = 0.0
    # The learned scale of the block's norms (pre, post, final): 0 = the
    # leaf itself; w > 0 = ``w * sigmoid(leaf)`` (a zero-centred gated
    # norm: a leaf of 0 scales by w / 2), the leaves then drawn about 0.
    norm_gate_weight: float = 0.0
    # A Mamba-1 mixer (ops/ssm.py; Jamba-style) on the layers ``layer_types``
    # names "mamba": such a layer is that mixer THEN its FFN, the others
    # ``attention`` ("attention" is accepted for "full") then theirs.  It
    # reads ``ssm_heads`` as its CHANNELS (``ssm_head_dim`` 1 and
    # ``ssm_groups`` 1: a decay a channel and a state, one B and C for all),
    # ``ssm_state``, ``ssm_conv`` and ``ssm_dt_rank``: the width of the
    # low-rank step ``[dt | B | C] = x W_x``, each of the three through an
    # RMSNorm of its own, ``Delta = softplus(dt W_dt + b_dt)``.  Its state —
    # ``[ssm_state, channels]`` float32 and the taps — lives in the same
    # state rows as the other two recurrences' (``SsmState``).
    ssm_dt_rank: int = 0
    # The LM head is the embedding table, transposed: no ``lm_head`` leaf.
    tie_embeddings: bool = False
    # A Mamba-2 mixer THEN its FFN (Granite-4.0-H style) on the layers
    # ``layer_types`` names "mamba2" — the ``layer_pattern`` "M" mixer
    # (``ssm_heads`` .. ``ssm_chunk``, the same leaves, block and state rows)
    # with the layer's FFN behind it, the others ``attention`` then theirs.
    # A name of its own: the published ``layer_types`` of that family says
    # "mamba", which here is Jamba's Mamba-1; a configuration file
    # translates.
    # Four published scalars (Granite's; each default leaves the block as it
    # is, bit for bit): the embedded rows times ``embedding_multiplier``;
    # ``attention_multiplier`` the softmax scale in place of
    # ``head_dim^-1/2`` (0 = that); every sub-block's output times
    # ``residual_multiplier`` before its residual add; the logits divided by
    # ``logits_scaling``.
    embedding_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # A self-decoder / cross-decoder pair (SambaY; Phi-4-mini-flash): after
    # the self-decoder's layers, ``layer_types`` names layers that OWN nothing
    # and read what an earlier layer of the same step produced — "cross": an
    # attention with a query and an output projection alone, its keys and
    # values the pool of the last "full" layer before the first of them
    # (``kv_layer``), read after that layer's write of the same step; "gmu": a
    # Gated Memory Unit ``W_out(silu(W_in u) * m)``, ``m`` the scan output
    # (with the D skip, before the gate) of the last "mamba" layer before the
    # first of them (``memory_layer``) at the same positions.  Every layer
    # from the first of them on (``cross_from``) is one of the two, so none
    # carries anything from one position to the next: a prompt window runs the
    # layers before ``cross_from`` alone (its last position's logits are the
    # first decode step's, as ever).
    # ``attention="diff"`` is differential attention: the query heads pair up
    # adjacent (q1, q2), the KV heads likewise (k1, k2) and (v1, v2), a query
    # pair j reads KV pair ``j // (pairs a KV pair)``; ``softmax(q1 k1^T)
    # [v1 | v2] - lambda softmax(q2 k2^T) [v1 | v2]``, ``lambda`` from four
    # learned vectors a layer and the layer's depth, an RMSNorm over the
    # ``2 head_dim`` of a pair's output, times ``1 - lambda_init``.  The
    # kernels see it as grouped-query attention over ``num_kv_heads / 2`` KV
    # heads ``2 head_dim`` wide — a cached token's [k1 | k2] and [v1 | v2] as
    # they lie — with each query placed in its key's half of the lanes
    # (``_diff_place``): every cached byte crosses HBM once a layer a step.
    # ``norm`` "rms" | "layer": the block norms (pre, final) as LayerNorm with
    # scale AND bias; ``attn_bias``: biases on q, k, v and o;
    # ``nope_on_window``: no rotation on window layers either;
    # ``ssm_inner_norms``: a Mamba-1 layer's three inner RMSNorms (Jamba's;
    # false = the plain Mamba-1 block).
    norm: str = "rms"
    attn_bias: bool = False
    nope_on_window: bool = False
    ssm_inner_norms: bool = True
    # Keys a window layer's store holds a stream, where it is a RING of the
    # stream's own beside its state row and not blocks of the paged pool (0:
    # the pool, every block behind the window kept): the key at position p
    # lies at ``p % window_ring`` of the stream's row, so the store stops
    # growing whatever the context.  A multiple of the pool's block size that
    # holds a prompt window's view (``window - 1 + PREFILL_CHUNK`` keys and a
    # block; the registry checks it against the environment).
    window_ring: int = 0

    def __post_init__(self):
        if self.num_experts and not (
            0 < self.experts_per_token <= self.num_experts
        ):
            raise ValueError(
                f"experts_per_token={self.experts_per_token} must lie in "
                f"1..num_experts={self.num_experts}"
            )
        if self.attention not in ("gqa", "mla", "diff"):
            raise ValueError(
                f"attention={self.attention!r} ('gqa', 'mla', 'diff')")
        if self.norm not in ("rms", "layer"):
            raise ValueError(f"norm={self.norm!r} ('rms', 'layer')")
        if self.diff and (self.num_heads % 2 or self.num_kv_heads % 2
                          or self.num_heads % self.num_kv_heads):
            raise ValueError(
                "attention='diff' pairs heads: num_heads and num_kv_heads must "
                f"be even, got {self.num_heads} / {self.num_kv_heads}")
        mla_dims = (self.q_lora_rank, self.kv_lora_rank, self.qk_nope_head_dim,
                    self.qk_rope_head_dim, self.v_head_dim)
        if self.mla:
            if not all(d > 0 for d in mla_dims) or self.qk_rope_head_dim % 2:
                raise ValueError(
                    "attention='mla' needs q_lora_rank, kv_lora_rank, "
                    "qk_nope_head_dim, qk_rope_head_dim (even) and v_head_dim"
                    f", got {mla_dims}")
            object.__setattr__(
                self, "head_dim", self.qk_nope_head_dim + self.qk_rope_head_dim)
        elif any(mla_dims):
            raise ValueError(
                f"latent-attention sizes {mla_dims} need attention='mla'")
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(
                self, "rope_scaling", tuple(sorted(self.rope_scaling.items())))
        if self.rope_scaling is not None:
            y = dict(self.rope_scaling)
            need = {"type", "factor", "original_max_position_embeddings",
                    "beta_fast", "beta_slow", "mscale", "mscale_all_dim"}
            if y.get("type") != "yarn" or set(y) != need:
                raise ValueError(
                    f"rope_scaling must be None or YaRN's keys {sorted(need)}, "
                    f"got {y}")
            if not self.mla:
                raise ValueError(
                    "rope_scaling (YaRN) is carried by attention='mla' only: "
                    "its mscale^2 lives in that path's softmax scale")
        if self.n_group > 1:
            per = self.num_experts // self.n_group
            if (self.num_experts % self.n_group
                    or not 0 < self.topk_group <= self.n_group
                    or self.experts_per_token > self.topk_group * per):
                raise ValueError(
                    f"n_group={self.n_group} / topk_group={self.topk_group}: "
                    f"groups must divide num_experts={self.num_experts} and "
                    "the kept groups must hold experts_per_token experts")
        if self.experts_held or self.expert_first:
            if not (0 < self.experts_held
                    and 0 <= self.expert_first
                    and self.expert_first + self.experts_held <= self.num_experts):
                raise ValueError(
                    f"experts_held={self.experts_held} from expert_first="
                    f"{self.expert_first} must lie within num_experts="
                    f"{self.num_experts}")
        if not self.head_dim:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        hf = {"sliding_attention": "window", "full_attention": "full",
              "linear_attention": "linear", "attention": "full"}
        # A published pattern cut in depth: its first num_layers entries.
        types = tuple(hf.get(t, t) for t in self.layer_types)[: self.num_layers]
        object.__setattr__(self, "layer_types", types)
        if types and (len(types) != self.num_layers
                      or set(types) - {"window", "full", "linear", "mamba",
                                       "mamba2", "cross", "gmu"}):
            raise ValueError(
                f"layer_types must name each of the {self.num_layers} layers "
                f"'window', 'full', 'linear' or 'mamba' (Mamba-1; 'mamba2' a "
                f"Mamba-2 mixer then its FFN; 'cross' / 'gmu' a cross-decoder's "
                f"layers), got {types}"
            )
        if self.cross_from < len(types):
            before, after = types[: self.cross_from], types[self.cross_from:]
            if (set(after) - {"cross", "gmu"} or self.mla
                    or ("cross" in after and "full" not in before)
                    or ("gmu" in after and "mamba" not in before)):
                raise ValueError(
                    "a 'cross' layer needs a 'full' layer before the first "
                    "cross-decoder layer (the pool it reads), a 'gmu' layer a "
                    "'mamba' layer there (the memory it gates), every layer "
                    "from the first of them on is 'cross' or 'gmu', and the "
                    f"attention is not 'mla', got {types}")
        if self.window_ring and (not self.window
                                 or self.window_ring < self.window):
            raise ValueError(
                f"window_ring={self.window_ring} needs window layers and holds "
                f"at least their window={self.window}")
        gdn_dims = (self.gdn_key_heads, self.gdn_value_heads, self.gdn_key_dim,
                    self.gdn_value_dim)
        if "linear" in types:
            if (not all(d > 0 for d in gdn_dims) or self.gdn_conv < 2
                    or self.gdn_value_heads % self.gdn_key_heads
                    or set(types) == {"linear"}):
                raise ValueError(
                    "a 'linear' layer needs gdn_key_heads, gdn_value_heads (a "
                    "multiple), gdn_key_dim and gdn_value_dim, and the pattern "
                    f"one attention layer (the paged pool's), got {gdn_dims}")
        elif any(gdn_dims):
            raise ValueError(
                f"Gated-DeltaNet sizes {gdn_dims} need a 'linear' layer")
        if ("window" in types) != bool(self.window):
            raise ValueError(
                f"window={self.window} and layer_types={types}: a window "
                "needs layers that use it, and window layers need a window"
            )
        if self.num_dense_layers and not self.d_ff_dense:
            raise ValueError("num_dense_layers needs d_ff_dense")
        if self.num_experts and self.num_dense_layers >= self.num_layers:
            raise ValueError("num_dense_layers leaves no expert layer")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(f"router_score={self.router_score!r}")
        if self.qk_norm not in (False, True, "head"):
            raise ValueError(f"qk_norm={self.qk_norm!r} (False, True, 'head')")
        if self.expert_act not in ("silu", "relu2"):
            raise ValueError(f"expert_act={self.expert_act!r} ('silu', 'relu2')")
        pattern = self.layer_pattern[: self.num_layers]
        object.__setattr__(self, "layer_pattern", pattern)
        ssm_dims = (self.ssm_heads, self.ssm_head_dim, self.ssm_groups,
                    self.ssm_state)
        if pattern:
            if (len(pattern) != self.num_layers or set(pattern) - set("M*E")
                    or "*" not in pattern):
                raise ValueError(
                    f"layer_pattern must name each of the {self.num_layers} "
                    "layers 'M' (Mamba-2), '*' (attention) or 'E' (experts) "
                    f"with at least one '*' (the paged pool's layer), got "
                    f"{pattern!r}")
            if types or self.num_dense_layers or self.mla:
                raise ValueError(
                    "layer_pattern stands instead of layer_types / "
                    "num_dense_layers, over attention='gqa'")
            if "E" in pattern and not self.num_experts:
                raise ValueError("an 'E' layer needs num_experts")
        if "M" in pattern or "mamba2" in types:
            if (not all(d > 0 for d in ssm_dims) or self.ssm_conv < 2
                    or self.ssm_heads % self.ssm_groups
                    or self.ssm_inner % self.ssm_groups):
                what = "an 'M'" if "M" in pattern else "a 'mamba2'"
                raise ValueError(
                    f"{what} layer needs ssm_heads (a multiple of ssm_groups), "
                    f"ssm_head_dim, ssm_groups and ssm_state, got {ssm_dims}")
        if "mamba2" in types and (
                set(types) == {"mamba2"} or "mamba" in types or self.mla):
            raise ValueError(
                "'mamba2' layers need attention='gqa', the pattern one "
                "attention layer (the paged pool's) and no 'mamba' (Mamba-1) "
                f"layer beside them, got {types}")
        if "mamba" in types:
            if (self.ssm_heads <= 0 or self.ssm_state <= 0 or self.ssm_conv < 2
                    or self.ssm_dt_rank <= 0 or self.ssm_head_dim != 1
                    or self.ssm_groups != 1 or set(types) == {"mamba"}
                    or self.mla):
                raise ValueError(
                    "a 'mamba' layer needs ssm_heads (its channels), ssm_state "
                    "and ssm_dt_rank, ssm_head_dim 1 and ssm_groups 1 (Mamba-1 "
                    "has neither heads nor groups), attention='gqa' and the "
                    f"pattern one attention layer (the paged pool's), got "
                    f"{ssm_dims} / ssm_dt_rank={self.ssm_dt_rank}")
        elif self.ssm_dt_rank:
            raise ValueError(
                f"ssm_dt_rank={self.ssm_dt_rank} needs a 'mamba' layer")
        if ("M" not in pattern and not {"mamba", "mamba2"} & set(types)
                and any(ssm_dims)):
            raise ValueError(
                f"Mamba sizes {ssm_dims} need an 'M' or a 'mamba' layer (or "
                "layer_types 'mamba2')")
        if (self.embedding_multiplier <= 0 or self.attention_multiplier < 0
                or self.residual_multiplier <= 0 or self.logits_scaling <= 0):
            raise ValueError(
                "embedding_multiplier, residual_multiplier and logits_scaling "
                "must be positive (1 = none) and attention_multiplier "
                "non-negative (0 = head_dim^-1/2)")

    @property
    def diff(self) -> bool:
        return self.attention == "diff"

    @property
    def kv_groups(self) -> int:
        """KV heads as the attention kernels see them: under ``diff`` a pair
        (k1, k2) is one head ``2 head_dim`` wide."""
        return self.num_kv_heads // 2 if self.diff else self.num_kv_heads

    @property
    def kv_tail(self) -> tuple:
        """A cached token's dims unmerged, as the attention reads them."""
        return (self.kv_groups, self.head_dim * self.num_kv_heads // self.kv_groups)

    @property
    def n_rep(self) -> int:
        return self.num_heads // self.kv_groups

    @property
    def layer_counts(self) -> dict:
        """How many layers ``layer_types`` names of each kind."""
        return {t: self.layer_types.count(t) for t in dict.fromkeys(self.layer_types)}

    @property
    def cross_from(self) -> int:
        """The first cross-decoder layer ('cross' / 'gmu'); ``num_layers``
        where there is none."""
        return next((i for i, t in enumerate(self.layer_types)
                     if t in ("cross", "gmu")), self.num_layers)

    def _last_before_cross(self, name: str) -> int:
        return max((i for i, t in enumerate(self.layer_types[: self.cross_from])
                    if t == name), default=-1)

    @property
    def kv_layer(self) -> int:
        """The layer whose pool the 'cross' layers read (-1: no such layer)."""
        return self._last_before_cross("full") if "cross" in self.layer_types else -1

    @property
    def memory_layer(self) -> int:
        """The Mamba layer whose scan output the 'gmu' layers gate (-1)."""
        return self._last_before_cross("mamba") if "gmu" in self.layer_types else -1

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def mla(self) -> bool:
        return self.attention == "mla"

    @property
    def rope_dim(self) -> int:
        """Width of what is rotated: a whole head, or MLA's rotary part."""
        return self.qk_rope_head_dim if self.mla else self.head_dim

    @property
    def o_dim(self) -> int:
        """Input width of ``W_o``: the heads' VALUES merged."""
        return self.num_heads * (self.v_head_dim if self.mla else self.head_dim)

    @property
    def latent_dim(self) -> int:
        """Values of one cached latent row [c ; k_rope] (0: no latent)."""
        return self.kv_lora_rank + self.qk_rope_head_dim if self.mla else 0

    @property
    def latent_lanes(self) -> int:
        """Width of the latent POOL: ``latent_dim`` rounded up to whole
        128-lane tiles, zeros past the values.  The chip's compiler lays a
        576-wide minor dim out 640 wide in HBM anyway and Mosaic refuses to
        slice a block out of it at 576 ("Slice shape along dimension 2
        must be aligned to tiling (128)": compiled for a described v5e,
        PR 33), so the pad costs no byte and is what lets the kernel copy
        a block where it lies."""
        return -(-self.latent_dim // 128) * 128

    @property
    def attn_scale(self) -> float:
        """The softmax scale: ``head_dim^-1/2`` — ``attention_multiplier``
        where the model states one — and, under YaRN, ``mscale(factor,
        mscale_all_dim)^2`` (DeepSeek-V2: 1.2608^2)."""
        scale = self.attention_multiplier or self.head_dim ** -0.5
        if self.rope_scaling is not None:
            y = dict(self.rope_scaling)
            scale *= yarn_mscale(y["factor"], y["mscale_all_dim"]) ** 2
        return scale

    @property
    def gqa_scale(self) -> float | None:
        """What the GQA paths hand their kernels and ``mha_attention`` as
        ``scale``: None — each one's own ``head_dim^-1/2``, the program as
        it ever was — unless the model states an ``attention_multiplier``."""
        return self.attention_multiplier or (
            self.head_dim ** -0.5 if self.diff else None)  # not the view's width

    @property
    def held(self) -> int:
        """Experts the tree holds (all of them unless ``experts_held``)."""
        return self.experts_held or self.num_experts

    @property
    def ssm_inner(self) -> int:
        """Width of a Mamba layer's inner stream (its heads merged)."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """What the convolution runs over: [x ; B ; C]."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def gdn_conv_dim(self) -> int:
        """What a Gated-DeltaNet layer's convolution runs over: [q | k | v]."""
        return (2 * self.gdn_key_heads * self.gdn_key_dim
                + self.gdn_value_heads * self.gdn_value_dim)

    @property
    def cache_layers(self) -> tuple:
        """The layers with a cache entry (a pool under the block table): those
        whose mixer is an attention that owns its keys there, in order."""
        return tuple(li for li in range(self.num_layers)
                     if self.layer_kind(li).store == "table")

    @property
    def own_layers(self) -> tuple:
        """The attention layers that own their keys and values, pool or ring,
        in order: an entry each of a contiguous cache and of
        ``forward_hidden``'s collected keys."""
        return tuple(li for li in range(self.num_layers)
                     if self.layer_kind(li).store in ("table", "ring"))

    @property
    def pool_entries(self) -> tuple:
        """Which entries of a contiguous cache (``own_layers``' order) are
        pools under the block table: what the paged loop shapes its pools
        from (every one, unless window layers keep rings)."""
        return tuple(i for i, li in enumerate(self.own_layers)
                     if li in self.cache_layers)

    @property
    def ring_layers(self) -> tuple:
        """The window layers whose keys lie in a ring a stream beside its
        state row (``window_ring``), in order."""
        return tuple(li for li in range(self.num_layers)
                     if self.layer_kind(li).store == "ring")

    @property
    def state_rows(self) -> bool:
        """Whether a stream holds a row beside the pool (``SsmState``)."""
        return bool(self.recurrent_layers or self.ring_layers)

    @property
    def window_row_bytes(self) -> int:
        """Bytes of window keys and values one stream's rings hold (0: none)."""
        return (len(self.ring_layers) * self.window_ring
                * 2 * self.num_kv_heads * self.head_dim * 2)

    @property
    def recurrent_layers(self) -> tuple:
        """The layers whose mixer keeps a state row (``SsmState``), in order."""
        return tuple(li for li in range(self.num_layers)
                     if self.layer_kind(li).recurrent)

    def recurrent_shapes(self, li: int) -> tuple:
        """``(taps, state)``: what one row of recurrent layer ``li`` holds —
        the convolution's taps [K-1, channels] (the cache's two-byte dtype)
        and the state (float32): Mamba-2's [H, P, N], Gated DeltaNet's
        [Hv, Dv, Dk], Mamba-1's [N, channels] (``RECURRENT``)."""
        return RECURRENT[self.layer_kind(li).mixer].shapes(self)

    @property
    def scan_fused(self) -> bool:
        """Whether a prompt window's or wave's scan of the recurrent layers
        takes its fused kernel (``ops/ssm.py``): the test the scans
        themselves apply — the decode step runs its kernels and the shapes
        are whole tiles of the chip (``RECURRENT``)."""
        from ..ops import ssm

        kinds = {self.layer_kind(li).mixer for li in self.recurrent_layers}
        return bool(kinds) and self.pallas_decode and all(
            RECURRENT[k].fits(ssm, self) for k in kinds)

    @property
    def ssm_row_bytes(self) -> int:
        """Bytes of recurrent state one stream holds (0: no recurrent
        layer): a layer's float32 state and its K-1 convolution taps in the
        cache's two-byte dtype."""
        import math

        return sum(
            math.prod(state) * 4 + math.prod(taps) * 2
            for taps, state in map(self.recurrent_shapes, self.recurrent_layers))

    def layer_kind(self, li: int) -> "LayerKind":
        if self.layer_pattern:
            c = self.layer_pattern[li]
            return LayerKind(
                window=0, rope=c == "*" and not self.nope_on_full,
                experts=c == "E", d_ff=self.d_ff if c == "E" else 0,
                mixer={"*": self.attention, "M": "mamba2"}.get(c),
                ffn=c == "E", store="table" if c == "*" else "",
            )
        kind = self.layer_types[li] if self.layer_types else "full"
        window = self.window if kind == "window" else 0
        dense = li < self.num_dense_layers
        attends = kind in ("window", "full", "cross")
        return LayerKind(
            window=window,
            rope=attends and not (
                self.nope_on_window if window else self.nope_on_full),
            experts=bool(self.num_experts) and not dense,
            d_ff=self.d_ff_dense if dense else self.d_ff,
            mixer={"linear": "gdn", "mamba": "mamba1", "mamba2": "mamba2",
                   "gmu": "gmu"}.get(kind, self.attention),
            store=("" if not attends else "shared" if kind == "cross"
                   else "ring" if window and self.window_ring else "table"),
        )

    @property
    def expert_layers(self) -> tuple:
        return tuple(li for li in range(self.num_layers)
                     if self.layer_kind(li).experts)


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """What one layer is: ``window`` keys a query sees (0 = all before
    it), whether q and k are rotated, its FFN (``experts``: the
    sparse expert block of experts ``d_ff`` wide; else a dense SwiGLU of
    width ``d_ff``) and its ``mixer``: an attention ("gqa" | "mla" | "diff";
    ``store`` says whose keys it reads and whether it writes any), a
    recurrence (a key of ``RECURRENT``), "gmu" (a Gated Memory Unit: no
    state, no cache) or None.  Under a ``layer_pattern`` a layer is ONE
    sub-block alone: a mixer, or — ``ffn`` — the FFN."""

    window: int
    rope: bool
    experts: bool
    d_ff: int
    mixer: str | None = "gqa"
    ffn: bool = True
    # Where an attention layer's keys and values lie: "table" its own pool
    # under the block table, "ring" its own ring a stream (``window_ring``),
    # "shared" nowhere of its own — a 'cross' layer reads ``cfg.kv_layer``'s
    # pool and writes none; "" for a layer that attends to nothing.
    store: str = "table"

    @property
    def attention(self) -> str:
        """The attention kind of a layer that attends, else ""."""
        return self.mixer if self.mixer in ("gqa", "mla", "diff") else ""

    @property
    def recurrent(self) -> bool:
        """Whether the mixer keeps a state row: the one place that asks."""
        return self.mixer in RECURRENT


class SsmState(NamedTuple):
    """The recurrent state of a config with Mamba layers, a decode
    state's ``ssm`` field: per Mamba layer the convolution's taps ``conv``
    [R, K-1, conv_dim] and the state ``state`` [R, H, P, N] float32, R
    rows.  A wave's or the contiguous slab's state has a row a batch row.
    The paged loop's has its own R (the streams it can hold, prompts in
    prefill among them) and ``row`` [B] names each slot's row: a stream
    takes a row when its prompt's first window runs and keeps it until it
    ends, so going live moves no state."""

    conv: Any
    state: Any
    row: jax.Array
    # A window layer's ring (``cfg.window_ring``; empty without one): its
    # keys and its values [R, window_ring, KVH*D] a ring layer, the key at
    # position p at ``p % window_ring`` of the stream's row.
    ring_k: Any = ()
    ring_v: Any = ()

    @property
    def leaves(self) -> dict:
        """The per-row leaves by field (everything but ``row``): what an
        insert copies and a rebuild zeroes, a row a stream."""
        return {f: getattr(self, f) for f in self._fields if f != "row"}


def zero_ssm(cfg: "LlamaConfig", rows: int, dtype):
    """``rows`` zeroed state rows, ``row`` the identity (``()``, a decode
    state's empty default, for a config without state rows)."""
    shapes = [cfg.recurrent_shapes(li) for li in cfg.recurrent_layers]
    if not cfg.state_rows:
        return ()
    zeros = functools.cache(lambda tail, dt: jnp.zeros((rows,) + tail, dt))
    ring = (cfg.window_ring, cfg.num_kv_heads * cfg.head_dim)
    rings = [zeros(ring, dtype) for _ in cfg.ring_layers]
    return SsmState(  # layers of one shape share one zeros, as ever
        [zeros(taps, dtype) for taps, _ in shapes],
        [zeros(state, jnp.float32) for _, state in shapes],
        jnp.arange(rows, dtype=jnp.int32), rings, list(rings))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: ``0.1 * mscale * ln(factor) + 1``
    (1 for a factor of at most 1)."""
    import math

    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


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
        # Waited for, leaf by leaf: dispatch runs far ahead of the device,
        # and every float32 draw still in flight holds its buffer (twelve
        # expert stacks of 1.07 GB each at Trinity's widths).
        return jax.block_until_ready(
            jax.tree.map(lambda a: a.astype(dtype), tree))

    def lin(k, d_in, d_out):
        return cast(dense_init(k, d_in, d_out, bias=False, std=0.02))

    def norm_scale(k, n):
        return cast({"scale": 1.0 + normal_init(k, (n,), std=0.25)})

    def block_norm(k, n, learned=False):
        """A pre, post or final norm's leaf.  Under ``norm_gate_weight`` it
        is drawn about 0 (the scale is then about half the weight) so that
        a dropped or misread scale shows; else ones, or — ``learned``, the
        post-norms — about 1."""
        if cfg.norm_gate_weight:
            return cast({"scale": normal_init(k, (n,), std=0.25)})
        if cfg.norm == "layer":
            # Scale and bias drawn off 1 and 0, so that either dropped shows.
            return cast({"scale": 1.0 + normal_init(k, (n,), std=0.1),
                         "bias": normal_init(jax.random.fold_in(k, 1), (n,), std=0.1)})
        return norm_scale(k, n) if learned else cast(rmsnorm_init(n))

    def alin(k, d_in, d_out, bias_std):
        """An attention projection: under ``cfg.attn_bias`` with a bias large
        enough beside its output that dropping it shows."""
        p = lin(k, d_in, d_out)
        if cfg.attn_bias:
            p["bias"] = cast(normal_init(jax.random.fold_in(k, 1), (d_out,),
                                         std=bias_std))
        return p

    def experts(k, shape):
        # The key ``dense_init`` would draw a [d_in, d_out] kernel from.
        return {"kernel": cast(normal_init(jax.random.split(k)[0], shape, std=0.02))}

    keys = jax.random.split(key, cfg.num_layers + 2)
    d, qd, kv_dim = cfg.d_model, cfg.q_dim, cfg.num_kv_heads * cfg.head_dim
    e, held = cfg.num_experts, cfg.held
    params: Params = {
        "embed": {"embedding": cast(normal_init(keys[0], (cfg.vocab_size, d), std=0.02))},
        "layers": [],
        "final_ln": block_norm(jax.random.fold_in(key, 3), d),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "kernel": cast(normal_init(keys[1], (d, cfg.vocab_size), std=0.02))}
    for i in range(cfg.num_layers):
        lk = keys[2 + i]
        k = jax.random.split(lk, 7)
        kind, w = cfg.layer_kind(i), cfg.layer_kind(i).d_ff

        def extra(n):  # leaves newer than the 7-way split: their own keys
            return jax.random.fold_in(lk, n)

        if kind.mixer == "mamba2" and not kind.ffn:
            params["layers"].append(_init_mamba(cfg, extra, lin, norm_scale, cast))
            continue
        if not kind.attention:
            attn = None
        elif cfg.mla:
            # W_UKV [r, H x (nope + v)] is drawn whole and split ONCE, here,
            # into the two operands the absorbed step contracts against:
            # k_b [H, nope, r] (q_nope -> the latent's space) and v_b
            # [H, r, v] (the latent's space -> values).  No step re-lays it.
            r, dn, dv = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.v_head_dim
            hn = cfg.num_heads
            kv_b = lin(k[2], r, hn * (dn + dv))["kernel"].reshape(r, hn, dn + dv)
            attn = {
                "q_a": lin(k[0], d, cfg.q_lora_rank),
                "q_a_norm": norm_scale(extra(17), cfg.q_lora_rank),
                "q_b": lin(extra(18), cfg.q_lora_rank, qd),
                "kv_a": lin(k[1], d, cfg.latent_dim),
                "kv_a_norm": norm_scale(extra(19), r),
                "k_b": {"kernel": cast(jnp.transpose(kv_b[:, :, :dn], (1, 2, 0)))},
                "v_b": {"kernel": cast(jnp.transpose(kv_b[:, :, dn:], (1, 0, 2)))},
                "o": lin(k[3], cfg.o_dim, d),
            }
        else:
            attn = {"q": alin(k[0], d, qd, 0.3), "o": alin(k[3], qd, d, 0.1)}
            if kind.store != "shared":  # a 'cross' layer has no k and no v
                attn.update(k=alin(k[1], d, kv_dim, 0.3),
                            v=alin(k[2], d, kv_dim, 0.3))
        if cfg.diff and attn is not None:
            # lambda's four vectors (float32, std 0.2: the learned part moves
            # lambda by about a third either way of lambda_init) and the
            # sub-norm's scale about 1: a dropped piece shows.
            attn["lambda"] = normal_init(extra(49), (4, cfg.head_dim), std=0.2)
            attn["subln"] = norm_scale(extra(50), 2 * cfg.head_dim)
        if cfg.qk_norm and attn is not None:
            # Learned scales have no reason to be 1: drawn about it, so a
            # served path that drops the norm departs from one that has it.
            per_head = cfg.qk_norm == "head"
            attn["q_norm"] = norm_scale(extra(8), cfg.head_dim if per_head else qd)
            attn["k_norm"] = norm_scale(extra(9), cfg.head_dim if per_head else kv_dim)
        if cfg.attn_gate and attn is not None:
            attn["gate"] = lin(extra(10), d, cfg.o_dim)
        gated = cfg.expert_act == "silu"
        if not kind.ffn:
            mlp = None
        elif kind.experts:
            dl = cfg.moe_latent or d  # the width the routed experts see
            mlp = {
                "router": lin(extra(7), d, e),  # the published width
                "up": experts(k[5], (held, dl, w)),
                "down": experts(k[6], (held, w, dl)),
            }
            if gated:
                mlp["gate"] = experts(k[4], (held, dl, w))
            if cfg.moe_latent:
                mlp["latent_down"] = lin(extra(20), d, dl)
                mlp["latent_up"] = lin(extra(21), dl, d)
            if cfg.router_bias:
                # The buffer of aux-loss-free balancing: large enough
                # beside sigmoid scores that dropping it moves the choice.
                mlp["router_bias"] = cast(normal_init(extra(13), (e,), std=0.05))
            if cfg.num_shared_experts:
                ws = cfg.d_ff_shared or cfg.num_shared_experts * w
                mlp["shared"] = {
                    "up": lin(extra(15), d, ws),
                    "down": lin(extra(16), ws, d),
                }
                if gated:
                    mlp["shared"]["gate"] = lin(extra(14), d, ws)
        else:
            mlp = {
                "gate": lin(k[4], d, w),
                "up": lin(k[5], d, w),
                "down": lin(k[6], w, d),
            }
        layer = {}
        if attn is not None:
            layer.update(attn_ln=block_norm(extra(29), d), attn=attn)
        if kind.mixer == "gdn":
            layer.update(gdn_ln=block_norm(extra(29), d),
                         gdn=_init_gdn(cfg, extra, lin, cast))
        if kind.mixer == "mamba1":
            layer.update(ssm_ln=block_norm(extra(29), d),
                         ssm=_init_mamba1(cfg, extra, lin, norm_scale, cast))
        if kind.mixer == "mamba2":  # then its FFN: layer_types "mamba2"
            layer.update(_init_mamba(cfg, extra, lin, norm_scale, cast))
        if kind.mixer == "gmu":
            layer.update(gmu_ln=block_norm(extra(29), d),
                         gmu={"in": lin(extra(51), d, cfg.ssm_inner),
                              "out": lin(extra(52), cfg.ssm_inner, d)})
        if mlp is not None:
            layer.update(mlp_ln=block_norm(extra(30), d), mlp=mlp)
        if cfg.sandwich_norm:
            post = "gdn_post_ln" if kind.mixer == "gdn" else "attn_post_ln"
            layer[post] = block_norm(extra(11), d, learned=True)
            layer["mlp_post_ln"] = block_norm(extra(12), d, learned=True)
        if cfg.swiglu_limit and mlp is not None:
            # Every SwiGLU's gate 8x wider and its down 8x narrower (exact
            # in any float dtype): the clamp binds on a share of every
            # FFN's hidden units, so a dropped clamp shows, and no expert's
            # output is larger than its neighbours'.
            for ffn in (mlp, mlp.get("shared")):
                if ffn is not None and "gate" in ffn:
                    ffn["gate"]["kernel"] = ffn["gate"]["kernel"] * 8
                    ffn["down"]["kernel"] = ffn["down"]["kernel"] / 8
        params["layers"].append(layer)
    return params


def _init_gdn(cfg: LlamaConfig, extra, lin, cast) -> dict:
    """One Gated-DeltaNet mixer's leaves: ``qkvz`` [q | k | v | z] and
    ``ba`` [b | a] wide, the convolution's taps (no bias) normal 0.4,
    ``A_log`` / ``dt_bias`` drawn as Mamba's (``_init_mamba``: a head's
    decay a token between about e^-1.6 and e^-0.001), the output norm's
    zero-centred scale (``1 + w``) normal 0.25."""
    d, hv = cfg.d_model, cfg.gdn_value_heads
    inner = hv * cfg.gdn_value_dim
    return {
        "qkvz": lin(extra(31), d, cfg.gdn_conv_dim + inner),
        "ba": lin(extra(32), d, 2 * hv),
        "conv": {"kernel": cast(normal_init(
            extra(34), (cfg.gdn_conv, cfg.gdn_conv_dim), std=0.4))},
        **_decay_leaves(extra(33), extra(35), hv),
        "norm": cast({"scale": normal_init(extra(36), (cfg.gdn_value_dim,), std=0.25)}),
        "out": lin(extra(37), inner, d),
    }


def _decay_leaves(k_step, k_a, heads: int) -> dict:
    """A recurrent layer's per-head decay scalars, float32 whatever the
    tree's dtype: ``dt_bias`` the inverse softplus of a step log-uniform in
    [0.001, 0.1], ``A_log`` with ``exp(A_log)`` uniform in [1, 16]."""
    step = jnp.exp(jax.random.uniform(
        k_step, (heads,), minval=jnp.log(0.001), maxval=jnp.log(0.1)))
    return {"dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.log(jax.random.uniform(
                k_a, (heads,), minval=1.0, maxval=16.0))}


def _init_mamba(cfg: LlamaConfig, extra, lin, norm_scale, cast) -> dict:
    """One Mamba-2 layer's leaves.  ``in`` is [z | x B C | dt] wide.  Drawn
    so that each rule of the block shows in the output when broken:
    ``A_log`` with ``-exp(A_log)`` uniform in [-16, -1]; ``dt_bias`` the
    inverse softplus of a step log-uniform in [0.001, 0.1] (what the
    published ``time_step_min / max`` initialise; they clamp nothing);
    ``D`` ones; the convolution's taps normal 0.4 and its bias normal 0.5
    beside inputs of about unit size; the gated norm's scale about 1."""
    d, inner, h = cfg.d_model, cfg.ssm_inner, cfg.ssm_heads
    return {
        "ssm_ln": cast(rmsnorm_init(d)),
        "ssm": {
            "in": lin(extra(22), d, inner + cfg.ssm_conv_dim + h),
            "conv": {
                "kernel": cast(normal_init(
                    extra(23), (cfg.ssm_conv, cfg.ssm_conv_dim), std=0.4)),
                "bias": cast(normal_init(extra(24), (cfg.ssm_conv_dim,), std=0.5)),
            },
            **_decay_leaves(extra(25), extra(26), h),
            "D": jnp.ones((h,), jnp.float32),
            "norm": norm_scale(extra(27), inner),
            "out": lin(extra(28), inner, d),
        },
    }


def _init_mamba1(cfg: LlamaConfig, extra, lin, norm_scale, cast) -> dict:
    """One Mamba-1 mixer's leaves: ``in`` [x | z] wide, the convolution over
    x alone (taps normal 0.4, bias normal 0.5), ``x_proj`` [dt | B | C] wide
    with an RMSNorm scale each (about 1), ``dt_proj`` (std ``rank^-1/2``,
    its bias the inverse softplus of a step log-uniform in [0.001, 0.1],
    float32), ``A_log`` = log(1..N) a channel (the family's; stored [N,
    channels] as the state lies) and ``D`` about 1, both float32."""
    d, ch, n, r = cfg.d_model, cfg.ssm_inner, cfg.ssm_state, cfg.ssm_dt_rank
    step = jnp.exp(jax.random.uniform(
        extra(43), (ch,), minval=jnp.log(0.001), maxval=jnp.log(0.1)))
    norms = {"dt_norm": norm_scale(extra(45), r),
             "b_norm": norm_scale(extra(46), n),
             "c_norm": norm_scale(extra(47), n)} if cfg.ssm_inner_norms else {}
    return {
        "in": lin(extra(38), d, 2 * ch),
        "conv": {
            "kernel": cast(normal_init(extra(39), (cfg.ssm_conv, ch), std=0.4)),
            "bias": cast(normal_init(extra(40), (ch,), std=0.5)),
        },
        "x_proj": lin(extra(41), ch, r + 2 * n),
        **norms,
        "dt_proj": {
            "kernel": cast(normal_init(extra(42), (r, ch), std=r ** -0.5)),
            "bias": step + jnp.log(-jnp.expm1(-step)),
        },
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1.0, n + 1.0))[:, None], (n, ch)),
        "D": 1.0 + normal_init(extra(44), (ch,), std=0.25),
        "out": lin(extra(48), ch, d),
    }


# ---------------------------------------------------------------------------
# rotary embeddings (HF rotate-half convention)


def _rope_tables(cfg: LlamaConfig, positions: jax.Array, dtype):
    """cos/sin [..., rope_dim] for integer positions [...].  Under
    ``cfg.rope_scaling`` (YaRN) the frequencies are blended: dimension
    pair ``d`` keeps ``theta^(-2d/D)`` where its wavelength makes more
    than ``beta_fast`` turns in the original context, takes it divided by
    ``factor`` where it makes fewer than ``beta_slow``, a linear ramp
    between; cos and sin carry ``mscale / mscale_all_dim``'s ratio."""
    dim = cfg.rope_dim
    half = dim // 2
    inv_freq = 1.0 / (
        cfg.rope_theta
        ** (jnp.arange(0, half, dtype=jnp.float32) * 2.0 / dim)
    )
    amp = None
    if cfg.rope_scaling is not None:
        import math

        y = dict(cfg.rope_scaling)

        def pair_of(turns):  # the pair whose wavelength makes ``turns`` turns
            return (dim * math.log(y["original_max_position_embeddings"]
                                   / (turns * 2 * math.pi))
                    / (2 * math.log(cfg.rope_theta)))

        lo = max(math.floor(pair_of(y["beta_fast"])), 0)
        hi = min(math.ceil(pair_of(y["beta_slow"])), dim - 1)
        ramp = jnp.clip(
            (jnp.arange(half, dtype=jnp.float32) - lo) / max(hi - lo, 0.001),
            0.0, 1.0)
        inv_freq = inv_freq / y["factor"] * ramp + inv_freq * (1.0 - ramp)
        ratio = (yarn_mscale(y["factor"], y["mscale"])
                 / yarn_mscale(y["factor"], y["mscale_all_dim"]))
        amp = None if ratio == 1.0 else ratio
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [..., half]
    emb = jnp.concatenate([angles, angles], axis=-1)  # [..., rope_dim]
    cos, sin = jnp.cos(emb), jnp.sin(emb)
    if amp is not None:
        cos, sin = cos * amp, sin * amp
    return cos.astype(dtype), sin.astype(dtype)


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


def _embed(params: Params, cfg: "LlamaConfig", ids, dtype):
    """Token rows of the embedding table (scaled by sqrt(d_model) under
    ``cfg.mup_embed``) — the ``embed`` scope of every step kind."""
    with jax.named_scope("embed"):
        x = embed(params["embed"], ids, dtype)
        if cfg.mup_embed:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        if cfg.embedding_multiplier != 1.0:
            x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return x


def _residual(cfg: "LlamaConfig", x, out):
    """``x + out``: a sub-block's residual add, ``out`` times
    ``cfg.residual_multiplier`` where the model states one — the product and
    the sum in float32, rounded once (0.22 is no bfloat16 number)."""
    if cfg.residual_multiplier == 1.0:
        return x + out
    return (x.astype(jnp.float32) + out.astype(jnp.float32)
            * cfg.residual_multiplier).astype(x.dtype)


def _norm(cfg: "LlamaConfig", p, x):
    """A block norm (pre, post or final): RMSNorm with its learned scale —
    the leaf itself, or under ``cfg.norm_gate_weight`` that weight times
    the leaf's sigmoid."""
    if cfg.norm_gate_weight:
        p = {"scale": cfg.norm_gate_weight * jax.nn.sigmoid(
            p["scale"].astype(jnp.float32))}
    if cfg.norm == "layer":  # LayerNorm: mean taken out, scale and bias
        return layernorm(p, x, eps=cfg.rms_eps)
    return rmsnorm(p, x, eps=cfg.rms_eps)


def _swiglu_gate(cfg: "LlamaConfig", gate):
    """``silu(gate)`` of a SwiGLU, the gate clamped from above under
    ``cfg.swiglu_limit`` (``_swiglu_up`` clamps the other factor)."""
    return jax.nn.silu(
        jnp.minimum(gate, cfg.swiglu_limit) if cfg.swiglu_limit else gate)


def _swiglu_up(cfg: "LlamaConfig", up):
    lim = cfg.swiglu_limit
    return jnp.clip(up, -lim, lim) if lim else up


def _mlp_block(cfg: "LlamaConfig", layer, li: int, x, valid, tally=None):
    """Pre-norm FFN block of layer ``li`` with its residual, under the
    ``mlp`` scope (the device trace's name for it in every step kind):
    the dense SwiGLU, or on an expert layer (``cfg.layer_kind``) the
    sparse expert FFN (ops/moe.py); under ``cfg.sandwich_norm`` the
    block's output is normed again before the residual.  ``valid``
    [B, S] marks the rows that are neither padding nor finished — only
    they get expert work; ``tally`` (a list) receives an expert layer's
    [E] count of their assignments."""
    with jax.named_scope("mlp"):
        h = _norm(cfg, layer["mlp_ln"], x)
        m = layer["mlp"]
        if cfg.layer_kind(li).experts:
            from ..ops.moe import expert_ffn

            b, s, d = x.shape
            out, counts = expert_ffn(
                h.reshape(b * s, d), m, cfg.experts_per_token,
                cfg.norm_topk_prob, jnp.broadcast_to(valid, (b, s)).reshape(-1),
                interpret=cfg.pallas_interpret, score=cfg.router_score,
                route_scale=cfg.route_scale, n_group=cfg.n_group,
                topk_group=cfg.topk_group, expert_first=cfg.expert_first,
                act=cfg.expert_act, limit=cfg.swiglu_limit,
            )
            if tally is not None:
                tally.append(counts)
            out = out.reshape(b, s, d)
        else:
            out = dense(
                m["down"], _swiglu_gate(cfg, dense(m["gate"], h))
                * _swiglu_up(cfg, dense(m["up"], h))
            )
        if cfg.sandwich_norm:
            out = _norm(cfg, layer["mlp_post_ln"], out)
        return _residual(cfg, x, out)


def _head_logits(params: Params, cfg: "LlamaConfig", x):
    """Float32 logits of final-normed rows: through ``lm_head``, or under
    ``cfg.tie_embeddings`` through the embedding table, transposed; divided
    by ``cfg.logits_scaling`` where the model states one."""
    if cfg.tie_embeddings:
        logits = lm_head_logits(x, params["embed"]["embedding"], transposed=True)
    else:
        logits = lm_head_logits(x, params["lm_head"]["kernel"], transposed=False)
    return logits if cfg.logits_scaling == 1.0 else logits / cfg.logits_scaling


def _select_next(params: Params, cfg: "LlamaConfig", state, x_last,
                 sample: bool):
    """Final-normed hidden rows → (next token, sample params, done,
    tokens): the ``lm_head`` and ``sample`` scopes of a decode step."""
    rows = jnp.arange(state.last_token.shape[0])
    with jax.named_scope("lm_head"):
        logits = _head_logits(params, cfg, x_last)
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


def _attn_gate(cfg: "LlamaConfig", a, ad, li: int, h):
    """The attention output's gate ``sigmoid(h W_g)`` [.., o_dim], over the
    merged heads elementwise (None without ``cfg.attn_gate``): GQA's and
    the latent attention's alike; ``_attn_out`` applies it."""
    return jax.nn.sigmoid(_aproj(a, ad, "gate", li, h)) if cfg.attn_gate else None


def _qkv_rope(cfg: "LlamaConfig", layer, ad, li: int, x, cos, sin):
    """q [.., H, Dh], k and v [.., KVH, Dh] of layer ``li`` from the
    residual stream x [B, S, D], and the attention output's gate
    [.., H*Dh] (None without ``cfg.attn_gate``) — the ``qkv_rope`` scope
    of every step kind.  ``cfg.qk_norm``: a learned-scale RMSNorm on q
    and k before RoPE — True over the whole projection before the head
    split (OLMoE), "head" per head over Dh (Trinity).  q and k are
    rotated unless the layer's kind says not (``cfg.nope_on_full``)."""
    a = layer["attn"]
    if cfg.mla:
        return _mla_qkv(cfg, layer, x, cos, sin, ad, li)
    kind = cfg.layer_kind(li)
    with jax.named_scope("qkv_rope"):
        h = _norm(cfg, layer["attn_ln"], x)
        q = _aproj(a, ad, "q", li, h)
        if kind.store == "shared":  # a 'cross' layer: a query alone
            q = _split(q, cfg.num_heads)
            if kind.rope:
                q = _apply_rope(q, cos, sin)
            return (_diff_place(q) if cfg.diff else q, None, None,
                    _attn_gate(cfg, a, ad, li, h))
        k = _aproj(a, ad, "k", li, h)
        if cfg.qk_norm is True:
            q = rmsnorm(a["q_norm"], q, eps=cfg.rms_eps)
            k = rmsnorm(a["k_norm"], k, eps=cfg.rms_eps)
        q, k = _split(q, cfg.num_heads), _split(k, cfg.num_kv_heads)
        if cfg.qk_norm == "head":
            q = rmsnorm(a["q_norm"], q, eps=cfg.rms_eps)
            k = rmsnorm(a["k_norm"], k, eps=cfg.rms_eps)
        if kind.rope:
            q, k = _apply_rope(q, cos, sin), _apply_rope(k, cos, sin)
        v = _split(_aproj(a, ad, "v", li, h), cfg.num_kv_heads)
        if cfg.diff:  # [k1 | k2] and [v1 | v2] as the cache holds them
            q, k, v = _diff_place(q), _split(merge_heads(k), cfg.kv_groups), \
                _split(merge_heads(v), cfg.kv_groups)
        g = _attn_gate(cfg, a, ad, li, h)
    return q, k, v, g


def _diff_place(q):
    """Differential attention's queries [.., H, Dh] as the kernels take them,
    [.., H, 2 Dh]: a pair's first head (even) in the lanes of its KV pair's
    k1, the second in k2's, zeros in the other half — ``q' . [k1 | k2]`` is
    then ``q . k1`` or ``q . k2``, and plain grouped-query attention over the
    pairs weighs the 2 Dh-wide value [v1 | v2] with each head's own softmax."""
    z = jnp.zeros_like(q)
    odd = (jnp.arange(q.shape[-2]) % 2 == 1)[:, None]
    return jnp.where(odd, jnp.concatenate([z, q], axis=-1),
                     jnp.concatenate([q, z], axis=-1))


def diff_lambda_init(li: int) -> float:
    """Differential attention's ``lambda_init`` at depth ``li``."""
    import math

    return 0.8 - 0.6 * math.exp(-0.3 * li)


def _diff_combine(cfg: "LlamaConfig", a, li: int, ctx):
    """A pair's two attentions [.., H, 2 Dh] -> its output [.., H / 2, 2 Dh]
    — the ``attn_diff_combine`` scope: ``o1 - lambda o2``, ``lambda =
    exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, an RMSNorm over the
    pair's 2 Dh with its learned scale, times ``1 - lambda_init``; float32."""
    with jax.named_scope("attn_diff_combine"):
        f32 = jnp.float32
        init = diff_lambda_init(li)
        lam = _diff_lambda(a["lambda"].astype(f32), init)
        c = ctx.astype(f32).reshape(
            ctx.shape[:-2] + (ctx.shape[-2] // 2, 2, ctx.shape[-1]))
        o = c[..., 0, :] - lam * c[..., 1, :]
        o = _diff_subnorm(a["subln"], o, cfg.rms_eps) * (1.0 - init)
        return o.astype(ctx.dtype)


def _diff_lambda(lv, init: float):
    """``exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init`` of a layer's four
    vectors ``lv`` [4, Dh], float32."""
    return jnp.exp(jnp.sum(lv[0] * lv[1])) - jnp.exp(jnp.sum(lv[2] * lv[3])) + init


def _diff_subnorm(p, o, eps: float):
    """The pair's sub-norm: an RMSNorm over its 2 Dh with a learned scale."""
    return rmsnorm(p, o, eps=eps)


def _gmu_memory(y, z):
    """What the memory layer hands the Gated Memory Units: the scan's output
    with the D skip, BEFORE its own gate ``silu(z)`` (which is not read)."""
    return y


def _kv_source(cfg: "LlamaConfig") -> int:
    """The layer whose keys and values a 'cross' layer reads."""
    return cfg.kv_layer


# ---------------------------------------------------------------------------
# multi-head latent attention (``cfg.attention == "mla"``; DeepSeek-V2)
#
#   c_q = RMSNorm(y W_DQ)        [qn_h ; qr_h] = split_h(c_q W_UQ)   qr rotated
#   [c ; kr] = y W_DKV           c = RMSNorm(c)    kr rotated: ONE rotary key
#                                a token, shared by every head
#   the cache holds [c ; kr] (``latent_dim`` values in ``latent_lanes``
#   lanes), after norm and rotation, once a token a layer.
#   expanded (prefill):  [kn_h ; v_h] = split_h(c W_UKV)
#       s_hij = (qn_hi . kn_hj + qr_hi . kr_j) * attn_scale
#   absorbed (decode; the same numbers):  ql_hi = qn_hi W_UK_h^T
#       s_hij = (ql_hi . c_j + qr_hi . kr_j) * attn_scale
#       ol_hi = sum_j softmax(s)_hij c_j      a_hi = ol_hi W_UV_h
#   ``k_b`` [H, nope, r] = W_UK, ``v_b`` [H, r, v] = W_UV: W_UKV split once
#   at init (``init_params``).

#: Heads a block of a prefill WAVE's expanded attention holds keys, values
#: and float32 scores for: 128 heads x 1024 queries x 6272 keys would be
#: 3.3 GB.  A prompt window's scores stay in the kernel's VMEM: its blocks
#: answer to another need (``MLA_WINDOW_BYTES``).
MLA_HEAD_BLOCK = 16

#: Bytes of expanded keys, values, padded queries and output ONE static
#: head block of a prompt window's attention may hold in HBM: DeepSeek-V2's
#: heads of 256 + 128 lanes, 2048 queries over 6272 keys in bfloat16, are
#: 6.4 MB each — 16 heads a block, 8 blocks a layer, 0.1 GB of transients
#: where all 128 at once are 0.8.  The value is measured, not reckoned
#: (PERF.md section 6, PR 64; one window alone on a v5e, ms at start 0 /
#: 4096): 1 block 62.6 / 84.7, 2 62.9 / 85.1, 4 63.0 / 85.2, 8 61.9 / 84.0,
#: 16 61.7 / 83.9 — the kernel fetches its key tiles 8 % faster from rows
#: 8 KB wide than from rows 64 KB wide; in the cell 8 blocks read
#: ``tbt_p99_ms`` 1.3 ms under one block's and ``setup_s`` 3 s over it.
MLA_WINDOW_BYTES = 128 << 20


def _mla_qkv(cfg: "LlamaConfig", layer, x, cos, sin, ad=None, li: int = 0):
    """``((qn [.., H, nope], qr [.., H, rope]), latent [.., lanes], None,
    gate)`` of an MLA layer from the residual stream x [B, S, D] — its
    ``qkv_rope`` scope: ``mla_q`` (down, norm, up, rotary) and ``mla_kv``
    (down, norm, rotary; the row the cache holds, zero past
    ``latent_dim``); the output's gate as ``_attn_gate`` has it."""
    a, r = layer["attn"], cfg.kv_lora_rank
    with jax.named_scope("qkv_rope"):
        h = _norm(cfg, layer["attn_ln"], x)
        with jax.named_scope("mla_q"):
            cq = rmsnorm(a["q_a_norm"], dense(a["q_a"], h), eps=cfg.rms_eps)
            q = _split(dense(a["q_b"], cq), cfg.num_heads)
            qn = q[..., : cfg.qk_nope_head_dim]
            qr = _apply_rope(q[..., cfg.qk_nope_head_dim:], cos, sin)
        with jax.named_scope("mla_kv"):
            ckr = dense(a["kv_a"], h)
            c = rmsnorm(a["kv_a_norm"], ckr[..., :r], eps=cfg.rms_eps)
            kr = _apply_rope(ckr[..., None, r:], cos, sin)[..., 0, :]
            latent = _latent_row(cfg, c, kr)
        g = _attn_gate(cfg, a, ad, li, h)
    return (qn, qr), latent, None, g


def _latent_row(cfg: "LlamaConfig", c, kr):
    """[c ; kr] padded with zeros to the pool's ``latent_lanes``: a cached
    row, or the absorbed q that scores it."""
    pad = cfg.latent_lanes - cfg.latent_dim
    zeros = [jnp.zeros(c.shape[:-1] + (pad,), c.dtype)] if pad else []
    return jnp.concatenate([c, kr] + zeros, axis=-1)


def _mla_expanded_attention(cfg: "LlamaConfig", layer, q, latent, mask,
                            window_at=None):
    """Expanded attention of q = (qn [B, Sq, H, nope], qr [B, Sq, H, rope])
    over the keys ``latent`` [B, Sk, lanes] -> [B, Sq, H, v]: the prefill
    form, keys and values made again from the latents (``mla_expand``).
    Two branches that share the latent's split and nothing else:

    - a prefill WAVE (``mask`` [B, 1, Sq, Sk], ``window_at`` None; the
      check prompts' and the unary path's forward too): XLA's attention
      under the mask, ``MLA_HEAD_BLOCK`` heads at a time inside
      ``lax.map`` — a block expands its heads' keys and values, scores
      them in float32 and weighs its values; the blocks are what keeps the
      float32 scores of all heads out of HBM;
    - one prompt WINDOW (``window_at`` = ``(kpos0, start, chunk_mask
      [C])``, B = 1, no ``mask``): ``_mla_window_attention`` — the heads'
      keys and values each out of one matmul in the layout the
      prompt-window kernel reads, in a few static head blocks, no loop."""
    if window_at is not None:
        return _mla_window_attention(cfg, layer, q, latent, window_at)
    a, r = layer["attn"], cfg.kv_lora_rank
    qn, qr = q
    b, sq, hn, dn = qn.shape
    hb = min(hn, MLA_HEAD_BLOCK)
    nblk = hn // hb
    c, kr = latent[..., :r], latent[..., r:cfg.latent_dim]
    f32 = jnp.float32

    def blocks(x, axis):  # the heads axis -> [nblk, ..., hb, ...], leading
        shape = x.shape[:axis] + (nblk, hb) + x.shape[axis + 1:]
        return jnp.moveaxis(x.reshape(shape), axis, 0)

    def block(args):
        qn_h, qr_h, kb, vb = args
        with jax.named_scope("mla_expand"):
            kn = jnp.einsum("bkr,hnr->bkhn", c, kb.astype(c.dtype))
            v = jnp.einsum("bkr,hrv->bkhv", c, vb.astype(c.dtype))
        s = (jnp.einsum("bqhn,bkhn->bhqk", qn_h, kn, preferred_element_type=f32)
             + jnp.einsum("bqhd,bkd->bhqk", qr_h, kr, preferred_element_type=f32)
             ) * cfg.attn_scale
        s = jnp.where(mask, s, f32(-1e9))
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhv->bqhv", p, v)

    out = jax.lax.map(block, (
        blocks(qn, 2), blocks(qr, 2),
        blocks(a["k_b"]["kernel"], 0), blocks(a["v_b"]["kernel"], 0)))
    # [nblk, B, Sq, hb, v] -> [B, Sq, H, v]
    return jnp.moveaxis(out, 0, 2).reshape(b, sq, hn, cfg.v_head_dim)


def mla_window_head_blocks(heads: int, c: int, k_len: int, dk: int, dv: int,
                           itemsize: int) -> int:
    """Static head blocks a prompt window's expanded attention goes in:
    the FEWEST (a divisor of ``heads``) whose keys ``[k_len, hb * dk]``,
    values ``[k_len, hb * dv]``, padded queries ``[c, hb * dk]`` and
    output ``[c, hb * dv]`` together stay within ``MLA_WINDOW_BYTES`` — a
    rule of the shapes: 8 blocks of 16 heads at DeepSeek-V2's served
    widths (102 MB a block), one wherever a layer's heads fit whole."""
    a_head = (k_len + c) * (dk + dv) * itemsize
    return next((n for n in range(1, heads) if heads % n == 0
                 and heads // n * a_head <= MLA_WINDOW_BYTES), heads)


def _mla_window_attention(cfg: "LlamaConfig", layer, q, latent, window_at):
    """One prompt window's expanded attention, q = (qn [1, C, H, nope],
    qr [1, C, H, rope]) over the row's latents [1, K, lanes] as the pool
    holds them ([c | kr | zeros]) -> [1, C, H, v], through the
    prompt-window kernel with the expanded heads as its KV heads.

    Keys and values are each WRITTEN ONCE, 2-D, lane-dense and row-major,
    by the matmul whose output the kernel reads (``mla_expand``): ``k``
    ``[K, hb * dk]`` = the latent rows times an expansion matrix (held
    transposed, ``[hb * dk, lanes]``) whose rows ``h * dk .. + nope`` are
    W_UK's head h, the next ``rope`` an identity from the rotary lanes, the
    rest zeros up to ``dk`` = a multiple of 128 lanes (192 -> 256: the MXU
    contracts 256 in the passes 192 take) — products with 1 and 0 are
    exact under the float32 accumulator, so a head's key is its nope dims
    beside the token's one rotary key, as the wave branch scores them;
    ``v`` ``[K, hb * v]`` = c times W_UV.  The layout is STATED
    (``with_layout_constraint``): left to itself the chip's compiler
    writes both products key-minor and copies them (PERF.md section 6,
    PR 64).  No ``kn`` that is read back, no ``[K, hb, lanes]`` array
    (tiled over (head, lane) on the chip: merging its trailing dims relays
    every byte out), no per-head concatenate, no ``lax.map`` and no stack
    to transpose out of: q goes in ``[C, hb, dk]`` (nope | rotary | zero
    lanes, one pad), the output comes back ``[C, hb, v]``.  The matrices
    are made from ``k_b`` / ``v_b`` as they are.  The heads go in
    ``mla_window_head_blocks`` static blocks (a Python loop), outputs
    joined on the heads axis — ``[C, H, v]`` as ``merge_heads`` wants it."""
    from jax.experimental.layout import Layout, with_layout_constraint

    from ..ops.prefill_attention import prefill_attention

    a, r = layer["attn"], cfg.kv_lora_rank
    qn, qr = q[0][0], q[1][0]
    c, hn, dn = qn.shape
    dr, dv = qr.shape[-1], cfg.v_head_dim
    rows = latent[0]
    k_len, lanes = rows.shape
    dt = rows.dtype
    dk = dn + dr + -(dn + dr) % 128
    hb = hn // mla_window_head_blocks(hn, c, k_len, dk, dv, dt.itemsize)
    row_major = Layout(major_to_minor=(0, 1))
    # A head's lanes nope .. nope + rope <- the rotary lanes of a latent row.
    rot = jnp.zeros((dk, lanes), dt).at[
        dn + jnp.arange(dr), r + jnp.arange(dr)].set(1)
    out = []
    for lo in range(0, hn, hb):
        with jax.named_scope("mla_expand"):
            w_k = jnp.pad(a["k_b"]["kernel"][lo:lo + hb].astype(dt),
                          ((0, 0), (0, dk - dn), (0, lanes - r))) + rot
            w_v = jnp.swapaxes(a["v_b"]["kernel"][lo:lo + hb].astype(dt), 1, 2)
            k = with_layout_constraint(jnp.einsum(
                "kl,ml->km", rows, w_k.reshape(hb * dk, lanes)), row_major)
            v = with_layout_constraint(jnp.einsum(
                "kr,mr->km", rows[:, :r], w_v.reshape(hb * dv, r)), row_major)
        qh = jnp.pad(
            jnp.concatenate([qn[:, lo:lo + hb], qr[:, lo:lo + hb]], axis=-1),
            ((0, 0), (0, 0), (0, dk - dn - dr)))
        out.append(prefill_attention(
            qh, k.reshape(k_len, hb, dk), v.reshape(k_len, hb, dv), *window_at,
            scale=cfg.attn_scale, interpret=cfg.pallas_interpret))
    return (out[0] if len(out) == 1 else jnp.concatenate(out, axis=1))[None]


def _mla_decode_attention(cfg: "LlamaConfig", layer, q, pool, table,
                          key_valid, bs: int):
    """The absorbed decode step's attention, q = (qn, qr) [B, 1, H, .]
    over the latent pool -> [B, 1, H, v]: ``mla_absorb`` (qn through
    W_UK into the latent's space: q is then [B, H, lanes] as the cache
    lies), ``attn_latent`` (the Pallas latent kernel — each cached row
    read once, keys = all its lanes, values = its first ``kv_lora_rank``
    — or, without ``cfg.pallas_decode``, the same sums over the rows'
    gathered blocks in XLA) and ``mla_unabsorb`` (through W_UV).
    ``table`` None: ``pool`` is a contiguous latent slab [B, T, lanes]
    (the unary path's ``_decode_step``), attended in XLA as it lies."""
    a, r = layer["attn"], cfg.kv_lora_rank
    qn, qr = q[0][:, 0], q[1][:, 0]
    with jax.named_scope("mla_absorb"):
        ql = jnp.einsum("bhn,hnr->bhr", qn, a["k_b"]["kernel"].astype(qn.dtype))
        q_lat = _latent_row(cfg, ql, qr)
    with jax.named_scope("attn_latent"):
        if cfg.pallas_decode and table is not None:
            from ..ops import autotune
            from ..ops.paged_attention import latent_decode_attention

            vkey = cfg.pallas_variant or autotune.lookup(
                "latent_decode", b=q_lat.shape[0], kvh=1, n_rep=cfg.num_heads,
                d=cfg.latent_lanes, block_size=bs, t=table.shape[1],
                dtype=str(q_lat.dtype), quant=False, tp=cfg.tp,
            )
            ol = latent_decode_attention(
                q_lat, pool, table, key_valid, bs, r, cfg.attn_scale,
                interpret=cfg.pallas_interpret, variant=vkey)
        else:
            from ..ops.paged_attention import gather_pages

            keys = pool if table is None else gather_pages(pool, table, bs)
            s = jnp.einsum("bhc,btc->bht", q_lat, keys,
                           preferred_element_type=jnp.float32) * cfg.attn_scale
            s = jnp.where((key_valid != 0)[:, None, :], s, jnp.float32(-1e9))
            p = jax.nn.softmax(s, axis=-1).astype(keys.dtype)
            ol = jnp.einsum("bht,btr->bhr", p, keys[..., :r])
    with jax.named_scope("mla_unabsorb"):
        ctx = jnp.einsum("bhr,hrv->bhv", ol, a["v_b"]["kernel"].astype(ol.dtype))
    return ctx[:, None]


def _attn_out(cfg: "LlamaConfig", layer, ad, li: int, x, ctx, g):
    """The attention block's end, with its residual — the ``attn_out``
    scope of every step kind: heads merged, gated (``g`` from
    ``_qkv_rope``), projected by ``W_o`` and, under
    ``cfg.sandwich_norm``, normed again before the residual."""
    with jax.named_scope("attn_out"):
        if cfg.diff:
            ctx = _diff_combine(cfg, layer["attn"], li, ctx)
        y = merge_heads(ctx)
        if g is not None:
            y = y * g
        y = _aproj(layer["attn"], ad, "o", li, y)
        if cfg.sandwich_norm:
            y = _norm(cfg, layer["attn_post_ln"], y)
        return _residual(cfg, x, y)


def _attn_scope(cfg: "LlamaConfig", li: int):
    """``attn`` and, for a config with a layer pattern, the layer's kind
    inside it (``attn_window`` / ``attn_full``): the scope a layer's
    attention runs under in every step kind — ``attn_cross`` alone for a
    layer that reads another layer's pool, apart from the owner's ``attn``."""
    stack = contextlib.ExitStack()
    kind = cfg.layer_kind(li)
    if kind.store == "shared":  # a reader of ``cfg.kv_layer``'s pool
        stack.enter_context(jax.named_scope("attn_cross"))
        return stack
    stack.enter_context(jax.named_scope("attn"))
    if cfg.layer_types or cfg.layer_pattern:
        stack.enter_context(jax.named_scope(
            "attn_window" if kind.window else "attn_full"))
    return stack


def _band(q_pos, k_pos, window: int):
    """[..., Q, K] bool: key position within ``window`` keys of the
    query's (itself included); the causal side is the caller's mask."""
    return q_pos[..., :, None] - k_pos[..., None, :] < window


def _ssm_delta(dt, bias):
    """A Mamba head's step: ``softplus(dt + dt_bias)``, positive."""
    return jax.nn.softplus(dt + bias)


def _ssm_gate_norm(y, z, scale, groups: int, eps: float):
    """``RMSNorm_group(y * silu(z)) * scale`` on [..., inner] float32: the
    gate FIRST, then a norm over each group's share of the width."""
    shape = y.shape
    y = (y * jax.nn.silu(z)).reshape(*shape[:-1], groups, shape[-1] // groups)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + eps)
    return y.reshape(shape) * scale


def _mamba_block(cfg: "LlamaConfig", layer, x, conv, s, mask=None, live=None):
    """Pre-norm Mamba-2 mixer with its residual, under the ``ssm`` scope:
    x [B, L, D] from each row's taps ``conv`` [B, K-1, conv_dim] and state
    ``s`` [B, H, P, N] -> (x + out, conv', s').  ``mask`` [B, L] (1 on a
    prefix of real tokens): a window or a wave, the chunked scan;
    ``live`` [B] instead (L = 1): the decode step's one-token update, a
    row that is not live neither moving its state nor reading it wrong
    (ops/ssm.py).  ``y = RMSNorm_group(y * silu(z))``: the gate first,
    then a norm over each group's share of the inner width."""
    from ..ops import ssm

    m = layer["ssm"]
    b, length = x.shape[:2]
    inner, cd = cfg.ssm_inner, cfg.ssm_conv_dim
    hn, g, n = cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_state
    f32 = jnp.float32
    with jax.named_scope("ssm"):
        u = rmsnorm(layer["ssm_ln"], x, eps=cfg.rms_eps)
        with jax.named_scope("ssm_in_proj"):
            zxd = dense(m["in"], u)
            z, xbc, dt = zxd[..., :inner], zxd[..., inner:inner + cd], zxd[..., inner + cd:]
        w, bias = m["conv"]["kernel"], m["conv"]["bias"]
        with jax.named_scope("ssm_conv"):
            if live is None:
                xbc, conv = ssm.conv_scan(xbc, conv, w, bias, mask)
            else:
                y1, conv = ssm.conv_step(xbc[:, 0], conv, w, bias, live)
                xbc = y1[:, None]
        dt = _ssm_delta(dt.astype(f32), m["dt_bias"].astype(f32))
        a = -jnp.exp(m["A_log"].astype(f32))
        if live is None:
            # x, B and C where the convolution left them: the kernel reads
            # them through block index maps, no heads-major copy.
            with jax.named_scope("ssm_scan"):
                y, s = ssm.ssm_scan(
                    xbc, dt, a, m["D"], s, mask, groups=g, state=n,
                    chunk=cfg.ssm_chunk, kernel=cfg.pallas_decode,
                    interpret=cfg.pallas_interpret)
        else:
            xs, bm, cm = ssm.split_xbc(xbc, hn, g, n)
            with jax.named_scope("ssm_step"):
                y, s = ssm.ssm_step(xs[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0],
                                    m["D"], s, live)
                y = y[:, None]
        with jax.named_scope("ssm_gate_norm"):
            y = _ssm_gate_norm(
                y.reshape(b, length, inner), z.astype(f32),
                m["norm"]["scale"].astype(f32), g, cfg.rms_eps).astype(x.dtype)
        with jax.named_scope("ssm_out_proj"):
            return _residual(cfg, x, dense(m["out"], y)), conv, s


def _gdn_block(cfg: "LlamaConfig", layer, x, conv, s, mask=None, live=None):
    """Pre-norm Gated-DeltaNet mixer with its residual, under the ``gdn``
    scope: x [B, L, D] from each row's taps ``conv`` [B, K-1, channels] and
    state ``s`` [B, Hv, Dv, Dk] -> (x + out, conv', s'); ``mask`` / ``live``
    as ``_mamba_block`` has them.  q and k are L2-normalised a head (q
    scaled by ``Dk^-1/2``), value head h reads key head ``h // (Hv / Hk)``;
    ``y = RMSNorm_Dv(o; 1 + w) * gate_scale * sigmoid(z)``: the norm first,
    then the gate."""
    from ..ops import ssm

    m = layer["gdn"]
    b, length = x.shape[:2]
    hk, hv, dk, dv = (cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim,
                      cfg.gdn_value_dim)
    cd, f32 = cfg.gdn_conv_dim, jnp.float32
    with jax.named_scope("gdn"):
        u = _norm(cfg, layer["gdn_ln"], x)
        with jax.named_scope("gdn_in_proj"):
            qkvz = dense(m["qkvz"], u)
            qkv, z = qkvz[..., :cd], qkvz[..., cd:]
            ba = dense(m["ba"], u).astype(f32)
        w = m["conv"]["kernel"]
        with jax.named_scope("gdn_conv"):
            if live is None:
                qkv, conv = ssm.conv_scan(qkv, conv, w, None, mask)
            else:
                y1, conv = ssm.conv_step(qkv[:, 0], conv, w, None, live)
                qkv = y1[:, None]
        scan = "gdn_scan" if live is None else "gdn_step"
        with jax.named_scope(scan):
            beta = jax.nn.sigmoid(ba[..., :hv])
            g = -jnp.exp(m["A_log"].astype(f32)) * jax.nn.softplus(
                ba[..., hv:] + m["dt_bias"].astype(f32))
            if live is None:
                # q, k and v where the convolution left them: the kernel
                # reads them through block index maps and normalises a KEY
                # head's q and k once, no float32 copy a value head.
                o, s = ssm.gdn_scan(
                    qkv, g, beta, s, mask, kernel=cfg.pallas_decode,
                    interpret=cfg.pallas_interpret)
            else:
                o, s = ssm.gdn_step(*ssm.gdn_heads(qkv[:, 0], hk, hv, dk),
                                    g[:, 0], beta[:, 0], s, live)
                o = o[:, None]
        with jax.named_scope("gdn_gate_norm"):
            o = rmsnorm({"scale": 1.0 + m["norm"]["scale"].astype(f32)}, o,
                        eps=cfg.rms_eps)
            y = o.reshape(b, length, hv * dv) * (
                cfg.gdn_gate_scale * jax.nn.sigmoid(z.astype(f32)))
        with jax.named_scope("gdn_out_proj"):
            out = dense(m["out"], y.astype(x.dtype))
            if cfg.sandwich_norm:
                out = _norm(cfg, layer["gdn_post_ln"], out)
            return _residual(cfg, x, out), conv, s


def _mamba1_gate(y, z):
    """``y * silu(z)``, float32: Mamba-1's gate has NO norm behind it."""
    return y * jax.nn.silu(z)


def _mamba1_block(cfg: "LlamaConfig", layer, x, conv, s, mask=None, live=None,
                  memory: list | None = None):
    """Pre-norm Mamba-1 mixer with its residual, under the ``ssm`` scope
    (Mamba-2's name: the two never share a model): x [B, L, D] from each
    row's taps ``conv`` [B, K-1, channels] and state ``s`` [B, N, channels]
    -> (x + out, conv', s'); ``mask`` / ``live`` as ``_mamba_block`` has
    them.  The convolution runs over x alone; ``[dt | B | C] = x W_x`` —
    ``ssm_x_proj``, each of the three through its own RMSNorm under
    ``cfg.ssm_inner_norms`` —; ``Delta = softplus(dt W_dt + b_dt)`` in float32
    — ``ssm_dt_proj`` —; ``y * silu(z)`` with NO norm — ``ssm_gate``.  A list
    given as ``memory`` receives ``y`` [B, L, channels] float32, the scan's
    output with the D skip BEFORE the gate: what a Gated Memory Unit gates."""
    from ..ops import ssm

    m = layer["ssm"]
    ch, n, r = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_dt_rank
    f32 = jnp.float32
    with jax.named_scope("ssm"):
        u = _norm(cfg, layer["ssm_ln"], x)
        with jax.named_scope("ssm_in_proj"):
            xz = dense(m["in"], u)
            xs, z = xz[..., :ch], xz[..., ch:]
        w, bias = m["conv"]["kernel"], m["conv"]["bias"]
        with jax.named_scope("ssm_conv"):
            if live is None:
                xs, conv = ssm.conv_scan(xs, conv, w, bias, mask)
            else:
                y1, conv = ssm.conv_step(xs[:, 0], conv, w, bias, live)
                xs = y1[:, None]
        with jax.named_scope("ssm_x_proj"):
            dbc = dense(m["x_proj"], xs).astype(f32)  # the norms' outputs stay float32
            if cfg.ssm_inner_norms:
                dt = rmsnorm(m["dt_norm"], dbc[..., :r], eps=cfg.rms_eps)
                bm = rmsnorm(m["b_norm"], dbc[..., r:r + n], eps=cfg.rms_eps)
                cm = rmsnorm(m["c_norm"], dbc[..., r + n:], eps=cfg.rms_eps)
            else:
                dt, bm, cm = dbc[..., :r], dbc[..., r:r + n], dbc[..., r + n:]
        with jax.named_scope("ssm_dt_proj"):
            delta = jax.nn.softplus(
                jnp.einsum("blr,rc->blc", dt.astype(x.dtype),
                           m["dt_proj"]["kernel"].astype(x.dtype),
                           preferred_element_type=f32)
                + m["dt_proj"]["bias"].astype(f32))
        a = -jnp.exp(m["A_log"].astype(f32))
        if live is None:
            with jax.named_scope("ssm_scan"):
                y, s = ssm.mamba1_scan(
                    xs, delta, a, bm, cm, m["D"], s, mask,
                    kernel=cfg.pallas_decode, interpret=cfg.pallas_interpret)
        else:
            with jax.named_scope("ssm_step"):
                y, s = ssm.mamba1_step(xs[:, 0], delta[:, 0], a, bm[:, 0],
                                       cm[:, 0], m["D"], s, live)
                y = y[:, None]
        if memory is not None:
            memory.append(_gmu_memory(y, z.astype(f32)))
        with jax.named_scope("ssm_gate"):
            y = _mamba1_gate(y, z.astype(f32)).astype(x.dtype)
        with jax.named_scope("ssm_out_proj"):
            return _residual(cfg, x, dense(m["out"], y)), conv, s


def _gmu_block(cfg: "LlamaConfig", layer, x, m):
    """Pre-norm Gated Memory Unit with its residual, under the ``gmu`` scope:
    ``x + W_out(silu(W_in u) * m)``, ``m`` [B, L, channels] float32 the
    memory layer's scan output at the same positions (``_mamba1_block``'s
    ``memory``); the gate's product in float32, rounded once.  No state, no
    cache, no convolution."""
    g = layer["gmu"]
    with jax.named_scope("gmu"):
        u = _norm(cfg, layer["gmu_ln"], x)
        with jax.named_scope("gmu_in_proj"):
            gate = dense(g["in"], u)
        y = (jax.nn.silu(gate.astype(jnp.float32)) * m).astype(x.dtype)
        with jax.named_scope("gmu_out_proj"):
            return _residual(cfg, x, dense(g["out"], y))


class Recurrence(NamedTuple):
    """A recurrent mixer kind: ``shapes(cfg) -> (taps, state)`` (what one
    state row of such a layer holds, ``LlamaConfig.recurrent_shapes``),
    ``block(cfg, layer, x, conv, s, mask=, live=) -> (x, conv', s')``, the
    name its boot refusals give it and ``fits(ssm, cfg)``: the shape gate
    ``ops/ssm`` puts before its prompt scan's fused kernel
    (``LlamaConfig.scan_fused``)."""

    shapes: Any
    block: Any
    name: str
    fits: Any


#: Every mixer that keeps a state row, by ``LayerKind.mixer``: the ONE table
#: ``LayerKind.recurrent``, ``LlamaConfig.recurrent_shapes``,
#: ``_recurrent_block`` and the registry's refusals read.
RECURRENT = {
    "mamba2": Recurrence(
        lambda c: ((c.ssm_conv - 1, c.ssm_conv_dim),
                   (c.ssm_heads, c.ssm_head_dim, c.ssm_state)),
        _mamba_block, "Mamba-2 layers (layer_pattern 'M' / layer_types 'mamba2')",
        lambda ssm, c: ssm._kernel_fits(
            c.ssm_heads, c.ssm_head_dim, c.ssm_groups, c.ssm_state, c.ssm_chunk,
            c.pallas_interpret)),
    "gdn": Recurrence(
        lambda c: ((c.gdn_conv - 1, c.gdn_conv_dim),
                   (c.gdn_value_heads, c.gdn_value_dim, c.gdn_key_dim)),
        _gdn_block, "Gated-DeltaNet layers (layer_types 'linear')",
        lambda ssm, c: ssm._gdn_kernel_fits(
            c.gdn_key_heads, c.gdn_value_heads, c.gdn_key_dim, c.gdn_value_dim,
            ssm.GDN_CHUNK, c.pallas_interpret)),
    "mamba1": Recurrence(
        lambda c: ((c.ssm_conv - 1, c.ssm_inner), (c.ssm_state, c.ssm_inner)),
        _mamba1_block, "Mamba-1 layers (layer_types 'mamba')",
        lambda ssm, c: ssm._mamba1_kernel_fits(
            c.ssm_inner, c.ssm_state, c.pallas_interpret)),
}


def _recurrent_block(cfg: "LlamaConfig", li: int):
    """The block of recurrent layer ``li`` (``RECURRENT``)."""
    return RECURRENT[cfg.layer_kind(li).mixer].block


def _layers(params: Params, cfg: "LlamaConfig", x, attend, recur, valid,
            tally=None, memory=(), self_only: bool = False):
    """x through every layer, each running the sub-blocks its kind has
    (``cfg.layer_kind``): its mixer — ``recur(li, layer, x)`` (a recurrent
    layer), ``attend(li, layer, x)``, the step kind's own closures over its
    cache and state, or a Gated Memory Unit on ``memory[0]`` (what the
    memory layer's ``recur`` left there: ``_ssm_walker``), each with its
    residual — then its FFN (``_mlp_block``; ``valid()`` gives its rows' mask,
    asked for where an FFN runs).  The one walk every step kind makes;
    ``self_only`` stops it before the cross-decoder (``cfg.cross_from``): a
    step that reads no logit — a prompt window, a wave's prefill — owes those
    layers nothing, for they leave nothing behind."""
    upto = cfg.cross_from if self_only else cfg.num_layers
    for li, layer in enumerate(params["layers"][:upto]):
        kind = cfg.layer_kind(li)
        if kind.recurrent:
            x = recur(li, layer, x)
        elif kind.attention:
            x = attend(li, layer, x)
        elif kind.mixer == "gmu":
            x = _gmu_block(cfg, layer, x, memory[0])
        if kind.ffn:
            x = _mlp_block(cfg, layer, li, x, valid(), tally)
    return x


def _ssm_walker(cfg: "LlamaConfig", ssm, run, memory=None, lift=None):
    """``(recur, done)``: ``recur(li, layer, x)`` runs the next recurrent
    layer through ``run(block, layer, x, conv, s) -> (x, conv', s')`` —
    ``block`` that layer's ``_recurrent_block`` — on the layer's entries of
    ``ssm``; ``done()`` is ``ssm`` with what the layers left (``()`` for a
    config without any).  A list given as ``memory`` receives what
    ``cfg.memory_layer``'s block hands the Gated Memory Units (through
    ``lift``, where the step runs its blocks on other rows than its own)."""
    convs, states = [], []

    def recur(li, layer, x):
        i = len(convs)
        block, got = _recurrent_block(cfg, li), []
        if memory is not None and li == cfg.memory_layer:
            block = functools.partial(block, memory=got)
        x, conv, s = run(block, layer, x, ssm.conv[i], ssm.state[i])
        if got:
            memory.append(lift(got[0]) if lift else got[0])
        convs.append(conv)
        states.append(s)
        return x

    def done():
        return ssm._replace(conv=convs, state=states) if convs else ssm

    return recur, done


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
    ssm_out: list | None = None,
):
    """Hidden states [B, S, D] (+ per-layer ROTATED prompt K / V, an entry
    a layer that owns its keys — ``cfg.own_layers``: a 'cross' layer has
    none; with ``collect_kv`` the pass stops before the cross-decoder,
    ``_layers``' ``self_only``, and the hidden states are no forward
    pass's).  A list given as ``ssm_out`` receives the
    ``SsmState`` (B rows) a decode state starts from: what the Mamba
    layers' scans from zeros leave BEFORE each row's last real token —
    the first decode step embeds that token again (``write_idx`` is its
    position), which a cache entry bears and a recurrence does not.  The
    hidden states at that one position are then no forward pass's (its
    keys are written again by that step): a call that reads them gives
    no ``ssm_out``.

    With ``prefix_kv`` the batch is the SUFFIX of a shared cached
    prompt prefix: tokens take rotary positions P.., queries attend to
    the (already rotated) prefix K/V plus the causal suffix — prefill
    cost is O(S), not O(P+S)."""
    b, s = input_ids.shape
    p_len = 0 if prefix_kv is None else _prefix_entry_len(prefix_kv[0][0])
    x = _embed(params, cfg, input_ids, dtype)
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
    band = mask & _band(pos, jnp.arange(p_len + s), cfg.window) if cfg.window else None
    ad = lora.adapter_tables(params)
    kv, shared = [], []

    def attend(li, layer, x):
        q, k, v, g = _qkv_rope(cfg, layer, ad, li, x, cos, sin)
        if cfg.layer_kind(li).store == "shared":
            k, v = shared[0]  # the kv layer's keys, of this same pass
        elif collect_kv:
            kv.append((k, v))
        if li == _kv_source(cfg):
            shared.append((k, v))
        with _attn_scope(cfg, li):
            if cfg.mla:  # k is the window's latent rows: expanded attention
                ctx = _mla_expanded_attention(cfg, layer, q, k, mask)
            else:
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
                    q, _repeat_kv(k, cfg.n_rep), _repeat_kv(v, cfg.n_rep),
                    mask=band if cfg.layer_kind(li).window else mask,
                    scale=cfg.gqa_scale,
                )
        return _attn_out(cfg, layer, ad, li, x, ctx, g)

    ssm_mask = attention_mask
    if ssm_out is not None and cfg.recurrent_layers:
        ssm_mask = attention_mask * (
            jnp.arange(s)[None, :] < attention_mask.sum(axis=-1, keepdims=True) - 1)
    memory: list = []
    recur, ssm_done = _ssm_walker(
        cfg, zero_ssm(cfg, b, dtype),
        lambda block, layer, x, conv, st: block(
            cfg, layer, x, conv, st, mask=ssm_mask), memory)
    # A pass that collects the cache reads no logit: the cross-decoder's
    # layers leave nothing in a cache or a state, so it does not run them.
    x = _layers(params, cfg, x, attend, recur, lambda: attention_mask != 0,
                memory=memory, self_only=collect_kv)
    if ssm_out is not None:
        ssm_out.append(ssm_done())
    x = _norm(cfg, params["final_ln"], x)
    return (x, kv) if collect_kv else x


def _with_rings(cfg: LlamaConfig, ssm, rings):
    """``ssm`` with a wave's window keys and values ``rings`` ([B, S, ..] a
    ring layer, positions 0..S-1) laid into its rings' first S places."""
    if not rings:
        return ssm
    s = rings[0][0].shape[1]
    if s > cfg.window_ring:
        raise ValueError(
            f"a wave of {s} positions does not fit a window ring of "
            f"{cfg.window_ring}: longer prompts prefill in windows")

    def lay(dst, src):  # [B, ring, C] <- [B, S, KVH, D]
        return dst.at[:, :s].set(merge_heads(src).astype(dst.dtype))

    return ssm._replace(
        ring_k=[lay(d, k) for d, (k, _) in zip(ssm.ring_k, rings)],
        ring_v=[lay(d, v) for d, (_, v) in zip(ssm.ring_v, rings)])


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
    return _head_logits(params, cfg, x)


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
    ssm_out: list = []
    _, kv = forward_hidden(
        params, cfg, input_ids, attention_mask, dtype,
        collect_kv=True, prefix_kv=prefix_kv, ssm_out=ssm_out,
    )
    cache_k, cache_v = [], []
    with jax.named_scope("kv_write"):
        for li, (k, v) in enumerate(kv):
            if cfg.mla:  # one latent slab a layer, no V (the paged insert's source)
                ck = jnp.zeros((b, total, cfg.latent_lanes), k.dtype)
                cache_k.append(ck.at[:, :s].set(k))
                continue
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
            ck = jnp.zeros((b, total) + cfg.kv_tail, k.dtype)
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
        # (a ring layer's keys lie in the slab whole; the rings ride along
        # zeroed, a row a batch row: the paged loop's template shapes its
        # state rows from them)
        ssm=ssm_out[0],
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
                scale=cfg.gqa_scale,
            )
        else:
            ctx = decode_attention(q[:, 0], ck, cv, m2,
                                   interpret=cfg.pallas_interpret,
                                   variant=vkey, tp=cfg.tp, scale=cfg.gqa_scale)
        return ctx[:, None]  # [B, 1, H, D]
    if isinstance(ck, tuple):
        return mha_attention_kv8(
            q,
            _repeat_kv(ck[0], cfg.n_rep), _repeat_kv(ck[1], cfg.n_rep),
            _repeat_kv(cv[0], cfg.n_rep), _repeat_kv(cv[1], cfg.n_rep),
            mask=mask, scale=cfg.gqa_scale,
        )
    return mha_attention(
        q, _repeat_kv(ck, cfg.n_rep), _repeat_kv(cv, cfg.n_rep), mask=mask,
        scale=cfg.gqa_scale,
    )


def _decode_step(params: Params, cfg: LlamaConfig, state: GPTState, sample: bool = False):
    dtype = _cache_dtype(state)
    b = state.last_token.shape[0]
    rows = jnp.arange(b)
    t = state.write_idx  # [B] per-row position
    x = _embed(params, cfg, state.last_token[:, None], dtype)  # [B,1,D]
    # Per-row rotary tables at each row's own position (clamped for
    # long-dead continuous-batching rows whose writes drop anyway).
    cos, sin = _rope_tables(cfg, jnp.minimum(t, cfg.max_position - 1), dtype)
    cos, sin = cos[:, None, None, :], sin[:, None, None, :]  # [B,1,1,D_h]
    key_valid = state.key_valid.at[rows, t].set(1, mode="drop")
    attn_mask = (key_valid != 0)[:, None, None, :]

    ad = lora.adapter_tables(params)
    new_k, new_v = [], []
    total = key_valid.shape[1]
    banded = bool(cfg.window) and total > cfg.window  # a slab past the window
    if banded:
        band = attn_mask & _band(
            t[:, None], jnp.arange(total)[None], cfg.window)[:, None]

    def attend(li, layer, x):
        kind = cfg.layer_kind(li)
        q, k1, v1, g = _qkv_rope(cfg, layer, ad, li, x, cos, sin)
        if kind.store == "shared":  # the kv layer's entry, after its write
            ai = cfg.own_layers.index(_kv_source(cfg))
            ck, cv = new_k[ai], new_v[ai]
        else:
            ai = len(new_k)  # the layer's cache entry: one an owning layer
            with jax.named_scope("kv_write"):
                ck = _write_kv(state.cache_k[ai], rows, t, k1[:, 0], dtype)
                if not cfg.mla:
                    cv = _write_kv(state.cache_v[ai], rows, t, v1[:, 0], dtype)
                    new_v.append(cv)
            new_k.append(ck)
        with jax.named_scope(
                "attn_cross" if kind.store == "shared" else "attn"):
            if cfg.mla:  # the latent slab, absorbed, in XLA (unary requests)
                ctx = _mla_decode_attention(cfg, layer, q, ck, None, key_valid, 0)
            else:
                ctx = _cache_attention(
                    cfg, q, ck, cv, band if banded and kind.window else attn_mask)
        return _attn_out(cfg, layer, ad, li, x, ctx, g)

    memory: list = []
    recur, ssm_done = _ssm_walker(
        cfg, state.ssm, lambda block, layer, x, conv, st: block(
            cfg, layer, x, conv, st, live=~state.done), memory)
    x = _layers(params, cfg, x, attend, recur, lambda: ~state.done[:, None],
                memory=memory)
    x = _norm(cfg, params["final_ln"], x)
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
            ssm=ssm_done(),
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
    x = _embed(params, cfg, tokens, dtype)  # [B, D, Dm]
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
        q, k1, v1, g = _qkv_rope(cfg, layer, ad, li, x, cos, sin)
        ck = _write_kv(state.cache_k[li], rows, pos_w, k1, dtype)
        cv = _write_kv(state.cache_v[li], rows, pos_w, v1, dtype)
        new_k.append(ck)
        new_v.append(cv)
        ctx = _cache_attention(cfg, q, ck, cv, mask)
        x = _attn_out(cfg, layer, ad, li, x, ctx, g)
        x = _mlp_block(cfg, layer, li, x, ~state.done[:, None])
    x = _norm(cfg, params["final_ln"], x)
    logits = _head_logits(params, cfg, x)
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
            "paged_decode", b=q.shape[0], kvh=cfg.kv_groups,
            n_rep=cfg.n_rep, d=q.shape[3],
            block_size=bs, t=table.shape[1], dtype=str(q.dtype), quant=quant,
            tp=cfg.tp,
        )
        if quant:
            ctx = paged_decode_attention(
                q[:, 0], ck[0], cv[0], table, key_valid, bs,
                k_scale=ck[1], v_scale=cv[1], scale=cfg.gqa_scale,
                interpret=cfg.pallas_interpret, variant=vkey, tp=cfg.tp,
            )
        else:
            ctx = paged_decode_attention(q[:, 0], ck, cv, table, key_valid,
                                         bs, scale=cfg.gqa_scale,
                                         interpret=cfg.pallas_interpret,
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
            gather_pages(pool, table, bs, (cfg.kv_groups, last)), cfg.n_rep
        )

    d = cfg.kv_tail[1]
    if isinstance(ck, tuple):
        return mha_attention_kv8(
            q, dense(ck[0], d), dense(ck[1], 1), dense(cv[0], d),
            dense(cv[1], 1), mask=mask, scale=cfg.gqa_scale,
        )
    return mha_attention(q, dense(ck, d), dense(cv, d), mask=mask,
                         scale=cfg.gqa_scale)


#: A window layer's table view is a multiple of this many blocks wide, so
#: that every block fold the autotuner enumerates divides it.
VIEW_BLOCK_MULTIPLE = 8


def window_view_blocks(window: int, bs: int, t_width: int) -> int:
    """Table entries a window layer's view holds: the blocks ``window``
    consecutive keys can lie in (129 at 2048 keys in blocks of 16),
    rounded up; ``t_width`` (no view: the layer walks the whole table,
    masked) where the table is no wider than that."""
    span = (window - 1 + bs - 1) // bs + 1
    tw = -(-span // VIEW_BLOCK_MULTIPLE) * VIEW_BLOCK_MULTIPLE
    return tw if tw < t_width else t_width


def window_view(table, key_valid, t, window: int, bs: int, ring=None):
    """What a window layer attends over in a decode step whose newest
    key lies at position ``t`` [B]: ``(table [B, Tw], key_valid
    [B, Tw*bs])`` — per row the ``Tw`` consecutive table ENTRIES from the
    block that holds key ``t-window+1`` on (the pool is never touched),
    and the matching slice of ``key_valid`` with every key older than
    ``t-window+1`` cleared.  The kernel and the gathered path take it in
    place of the whole table: a row's live range lies within ``Tw/K``
    programs, and its mask and table row are ``Tw`` wide, not ``T``.
    ``ring`` = ``(row [B], ring blocks, rows)``: the window layers' keys lie
    in rings (``cfg.window_ring``), and the view's entries are the same
    logical blocks' ids there (``ring_blocks``), ``table`` giving only its
    width."""
    t_width = table.shape[1]
    tw = window_view_blocks(window, bs, t_width)
    lo = jnp.maximum(t - window + 1, 0)  # oldest key in the window
    first = jnp.minimum(lo // bs, t_width - tw)  # [B] first block of the view
    pos = first[:, None] * bs + jnp.arange(tw * bs)[None, :]  # key positions

    def rows(a, start, n):  # a row's n consecutive entries from its own start
        return jax.vmap(
            lambda r, s: jax.lax.dynamic_slice_in_dim(r, s, n))(a, start)

    valid = rows(key_valid, first * bs, tw * bs) * (pos >= lo[:, None])
    if ring is not None:  # the view's blocks lie in the rows' rings
        row, rb, n_rows = ring
        return ring_blocks(row, first, tw, rb, n_rows), valid
    if tw == t_width:
        return table, valid
    return rows(table, first, tw), valid


def ring_blocks(row, first, n: int, rb: int, n_rows: int):
    """The ``n`` consecutive logical blocks from ``first`` [...] of the rings
    of state rows ``row`` [...] as block ids of the rings' pool view
    ``[n_rows * rb, BS, C]``: logical block j of row r lies at ``r * rb + j %
    rb``.  A row past the last (a dead slot's, a filler's) gives ids past the
    pool: no block — a write there drops, the kernel reads nothing."""
    row, first = jnp.asarray(row), jnp.asarray(first)
    return row[..., None] * rb + (first[..., None] + jnp.arange(n)) % rb


def _ring_write(ssm, ri: int, dest, k_rows, v_rows, bs: int):
    """K and V rows ``[N, ...]`` at flat positions ``dest`` [N] (``row *
    window_ring + position % window_ring``; out of range drops) of ring layer
    ``ri``'s rings -> the two rings as the kernels read them: pools of ``[rows
    * window_ring / bs, bs, C]`` blocks (a re-view, written in place)."""
    from ..ops.paged_attention import scatter_rows

    def put(ring, rows):
        return scatter_rows(ring.reshape((-1, bs) + ring.shape[2:]), dest, rows)

    return put(ssm.ring_k[ri], k_rows), put(ssm.ring_v[ri], v_rows)


def _rings_done(ssm, ring_k, ring_v):
    """``ssm`` with the pools ``_ring_write`` left a ring layer, a row a
    stream again (``ssm`` itself where no layer keeps a ring)."""
    if not ring_k:
        return ssm
    shape = ssm.ring_k[0].shape
    return ssm._replace(ring_k=[r.reshape(shape) for r in ring_k],
                        ring_v=[r.reshape(shape) for r in ring_v])


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
    x = _embed(params, cfg, state.last_token[:, None], dtype)
    cos, sin = _rope_tables(cfg, jnp.minimum(t, cfg.max_position - 1), dtype)
    cos, sin = cos[:, None, None, :], sin[:, None, None, :]
    key_valid = state.key_valid.at[rows, t].set(1, mode="drop")
    full = (table, key_valid)
    ring = None
    if cfg.ring_layers:
        # A slot's ring is its state row's; a slot that is not live (done,
        # or freed: ``_paged_ssm_step``'s rule) points past the last row, so
        # its stale ``row`` can touch no ring given to another stream.
        n_rows, rlen = state.ssm.ring_k[0].shape[:2]
        live = ~state.done & (table[:, 0] < entry.shape[0])
        ring_row = jnp.where(live, state.ssm.row, n_rows)
        ring = (ring_row, rlen // bs, n_rows)
        ring_dest = ring_row * rlen + t % rlen  # this step's key, a slot
    if cfg.window:
        # Once a step, shared by the window layers.
        with jax.named_scope("attn"), jax.named_scope("attn_window_view"):
            view = window_view(table, key_valid, t, cfg.window, bs, ring)

    ad = lora.adapter_tables(params)
    new_k, new_v, moe_tally = [], [], []
    ring_k, ring_v = [], []

    def attend(li, layer, x):
        kind = cfg.layer_kind(li)
        q, k1, v1, g = _qkv_rope(cfg, layer, ad, li, x, cos, sin)
        if kind.store == "shared":
            # The kv layer's pool AFTER its write of this step: nothing of
            # its own to write, nothing held.
            ai = cfg.cache_layers.index(_kv_source(cfg))
            ck, cv = new_k[ai], new_v[ai]
        elif kind.store == "ring":
            with jax.named_scope("kv_write"):
                ck, cv = _ring_write(state.ssm, len(ring_k), ring_dest,
                                     k1[:, 0], v1[:, 0], bs)
            ring_k.append(ck)
            ring_v.append(cv)
        else:
            ai = len(new_k)  # the layer's pool: one a layer that owns one
            with jax.named_scope("kv_write"):
                ck = _paged_write_kv(state.cache_k[ai], table, t, k1[:, 0], bs, dtype)
                if not cfg.mla:  # a latent row is written once: there is no V pool
                    cv = _paged_write_kv(state.cache_v[ai], table, t, v1[:, 0], bs, dtype)
                    new_v.append(cv)
            new_k.append(ck)
        with _attn_scope(cfg, li):
            if cfg.mla:
                ctx = _mla_decode_attention(cfg, layer, q, ck, *full, bs)
            else:
                ctx = _paged_cache_attention(
                    cfg, q, ck, cv, *(view if kind.window else full), bs
                )
        return _attn_out(cfg, layer, ad, li, x, ctx, g)

    memory: list = []
    run, lift = (_paged_ssm_step(cfg, state, table) if cfg.recurrent_layers
                 else (None, None))
    recur, ssm_done = _ssm_walker(cfg, state.ssm, run, memory, lift)
    x = _layers(params, cfg, x, attend, recur, lambda: ~state.done[:, None],
                moe_tally, memory)
    x = _norm(cfg, params["final_ln"], x)
    next_tok, sp, done, tokens = _select_next(params, cfg, state, x[:, 0], sample)
    return (
        PagedState(
            cache_k=new_k, cache_v=new_v, key_valid=key_valid,
            write_idx=t + 1, pos=state.pos + 1, last_token=next_tok,
            done=done, tokens=tokens, sample=sp,
            ssm=_rings_done(ssm_done(), ring_k, ring_v),
        ),
        (next_tok, jnp.stack(moe_tally)) if moe_tally else next_tok,
    )


def _paged_ssm_step(cfg: LlamaConfig, state, table):
    """A paged decode step's recurrent layer, ``(run, lift)`` of
    ``_ssm_walker``.  The
    state rows stay where they lie and are updated in place, ALL ``R`` of
    them under a mask: the step's small per-slot rows (the residual
    stream) are gathered to their state rows and the layer's output back
    to the slots — a stream's 4 MB a layer is never gathered.  A slot is
    live while it decodes (``done`` false) and holds blocks (its table row
    is not the sentinel: the host clears it when the stream ends, so a
    freed slot's stale ``row`` can touch no row given to another prompt);
    the rows of dead slots, of prompts in prefill and the free ones do not
    move."""
    b = state.done.shape[0]
    nb = jax.tree.leaves(state.cache_k)[0].shape[0]
    n_rows = state.ssm.state[0].shape[0]
    live = ~state.done & (table[:, 0] < nb)
    slot_of = jnp.full((n_rows,), b, jnp.int32).at[
        jnp.where(live, state.ssm.row, n_rows)].set(
            jnp.arange(b, dtype=jnp.int32), mode="drop")
    live_r = slot_of < b
    from_slot = jnp.minimum(slot_of, b - 1)
    to_slot = jnp.minimum(state.ssm.row, n_rows - 1)

    def run(block, layer, x, conv, st):
        y, conv, st = block(
            cfg, layer, jnp.take(x, from_slot, axis=0), conv, st, live=live_r)
        return jnp.where(
            live[:, None, None], jnp.take(y, to_slot, axis=0), x), conv, st

    def lift(m):  # the memory layer's scan output, state rows -> slots
        return jnp.take(m, to_slot, axis=0)

    return run, lift


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
    shape = (batch, total) + cfg.kv_tail
    cached = cfg.own_layers
    if cfg.kv_quant:
        cache_k = [
            (jnp.zeros(shape, jnp.int8), jnp.ones(shape[:3] + (1,), dtype))
            for _ in cached
        ]
        cache_v = [
            (jnp.zeros(shape, jnp.int8), jnp.ones(shape[:3] + (1,), dtype))
            for _ in cached
        ]
    else:
        cache_k = [jnp.zeros(shape, dtype) for _ in cached]
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
        ssm=zero_ssm(cfg, batch, dtype),
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
    x = _embed(params, cfg, chunk_ids, dtype)
    cos, sin = _rope_tables(
        cfg, jnp.minimum(pos_w, cfg.max_position - 1), dtype
    )  # [B, C, Dh]
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    mask = _window_mask(state.key_valid != 0, chunk_mask, start)

    ad = lora.adapter_tables(params)
    new_k, new_v = [], []

    def attend(li, layer, x):
        ai = len(new_k)
        q, k1, v1, g = _qkv_rope(cfg, layer, ad, li, x, cos, sin)
        ck = _write_kv(state.cache_k[ai], rows, pos_w, k1, dtype)
        cv = _write_kv(state.cache_v[ai], rows, pos_w, v1, dtype)
        new_k.append(ck)
        new_v.append(cv)
        ctx = _cache_attention(cfg, q, ck, cv, mask)
        return _attn_out(cfg, layer, ad, li, x, ctx, g)

    if cfg.recurrent_layers:
        # registry refuses PAGED_KV=0 for it: this window has no way to
        # leave a prompt's last token out of the state (forward_hidden).
        raise NotImplementedError(
            "recurrent layers prefill in windows through paged_prefill_chunk only")
    _layers(params, cfg, x, attend, None, lambda: chunk_mask != 0)
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


def _prefill_mask(kpos, chunk_mask, start, window: int):
    """[B, 1, C, K] attention mask of one prompt window over the keys at
    positions ``kpos`` [K]: every position before the window (``start``,
    traced: earlier windows or an adopted prefix) plus the causal,
    pad-gated part of the window itself — ``gpt._window_mask`` for keys
    that are a slice of the row — and, with ``window``, only the
    ``window`` keys up to each query's own."""
    b, c = chunk_mask.shape
    off = kpos[None, :] - start  # [1, K] key offset into the window
    wvalid = jnp.take_along_axis(
        chunk_mask.astype(jnp.int32),
        jnp.clip(jnp.broadcast_to(off, (b, kpos.shape[0])), 0, c - 1), axis=1,
    )
    win_keys = (off >= 0) & (off < c) & (wvalid != 0)  # [B, K]
    causal = off[:, None, :] <= jnp.arange(c)[None, :, None]  # [1, C, K]
    mask = (off < 0)[:, None, :] | (win_keys[:, None, :] & causal)
    if window:
        mask &= jnp.arange(c)[None, :, None] - off[:, None, :] < window
    return mask[:, None]


def prefill_key_blocks(c: int, t_w: int, bs: int, start, window: int):
    """``(first, n)``: the table entries a prompt window of ``c`` queries
    from position ``start`` attends over — a full layer the row's whole
    table (the kernel's key loop stops at the last live key tile), a
    window layer the ``n`` entries (static) that can hold the
    ``c + window - 1`` keys ending with the window's last query.
    ``start`` traced (the step) or an int (the host's counters)."""
    if not window:
        return 0, t_w
    n = min(t_w, -(-(c + window - 1) // bs) + 1)
    lo = (start - window + 1) // bs
    if isinstance(start, jax.Array):
        return jnp.clip(lo, 0, t_w - n), n
    return min(max(int(lo), 0), t_w - n), n


def prefill_tile_counts(cfg: LlamaConfig, c: int, t_w: int, bs: int,
                        start: int, n_valid: int) -> tuple[int, int]:
    """``(live, total)`` (q tile, key tile) pairs of the prompt-window
    kernel for one window of ``n_valid`` real tokens at ``start``, a KV
    head of every layer together: what ``paged_prefill_chunk`` hands
    ``ops/prefill_attention`` (``(0, 0)`` where the window runs in XLA)."""
    from ..ops.prefill_attention import count_live_tiles, tile_sizes

    if not cfg.pallas_decode or cfg.kv_quant:
        return 0, 0
    live = total = 0
    for li in cfg.own_layers:  # a layer with no keys of its own: no tile
        window = cfg.layer_kind(li).window
        first, n = prefill_key_blocks(c, t_w, bs, start, window)
        tq, tk = tile_sizes(c, 1 if cfg.mla else cfg.n_rep, n * bs)
        lv, tot = count_live_tiles(
            start, n_valid, c, first * bs, n * bs, window, tq, tk)
        live, total = live + lv, total + tot
    return live, total


def paged_prefill_chunk(
    params: Params,
    cfg: LlamaConfig,
    state,  # gpt.PagedState
    table_rows: jax.Array,  # [B, T]
    chunk_ids: jax.Array,  # [B, C]
    chunk_mask: jax.Array,  # [B, C]
    starts: jax.Array,  # [B]
    dtype=jnp.float32,
    ssm_rows: jax.Array | None = None,  # [B, 2] each prompt's (state row, tokens to fold)
    tally: list | None = None,  # receives each expert layer's [E] counts
):
    """One prompt window each of ``B`` different prompts straight into
    pool blocks (see ``gpt.paged_prefill_chunk``), at GQA width and
    composed with the int8 pool pairs.  What is a matmul over rows — the
    embedding, the projections, the FFN (on an expert layer the router
    and the grouped matmuls: the experts stream once for all ``B x C``
    rows) — runs once over the batch, RoPE at each row's own ``starts``;
    what belongs to one prompt — the scatter through its table and the
    attention — runs once a row, in a static loop inside the one
    executable.  A row's queries attend over that row's gathered keys
    (``prefill_key_blocks``) through the prompt-window kernel
    (``ops/prefill_attention``: tile by tile, the scores never in HBM, a
    tile no query sees never run) wherever the decode step runs its
    kernels (``cfg.pallas_decode``); ``starts`` stays traced and one
    executable a batch width serves every window of every prompt.
    Without the kernels, and over an int8 pool, the same keys in XLA
    under ``_prefill_mask`` ([H, C, K] float32 scores: the tests'
    reference, no served path on the chip).  With Mamba layers each row's
    scan continues the state its prompt's earlier windows left in row
    ``ssm_rows[r, 0]`` of ``state.ssm`` — from zeros where ``starts[r]`` is
    0, whatever the row held: a finished stream's state cannot leak — and
    writes it back there, having folded in the window's first
    ``ssm_rows[r, 1]`` tokens: all of them, but for a prompt's LAST window,
    which leaves the prompt's last token to the first decode step
    (``forward_hidden``'s rule).  A filled-up row (no token; its row index
    past the last row) reads a clamped row and writes none.  A window layer
    whose store is a ring (``cfg.window_ring``) writes its keys into row
    ``ssm_rows[r, 0]``'s ring at ``position % window_ring`` and attends over
    the same logical blocks there (``ring_blocks``); a cross-decoder's layers
    do not run (``_layers``' ``self_only``): a window reads no logit."""
    from ..ops.paged_attention import gather_pages

    b, c = chunk_ids.shape
    entry = state.cache_k[0]
    bs = entry[0].shape[1] if isinstance(entry, tuple) else entry.shape[1]
    pos_w = starts[:, None] + jnp.arange(c)[None, :]
    x = _embed(params, cfg, chunk_ids, dtype)
    cos, sin = _rope_tables(cfg, jnp.minimum(pos_w, cfg.max_position - 1), dtype)
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    t_w = table_rows.shape[1]
    kernel = cfg.pallas_decode and not isinstance(entry, tuple)

    rlen = cfg.window_ring
    if cfg.ring_layers:
        n_rows = state.ssm.ring_k[0].shape[0]
        # Every row's window, a position each, in its prompt's ring (a
        # filler's row lies past the last: its writes drop).
        ring_dest = (ssm_rows[:, :1] * rlen + pos_w % rlen).reshape(-1)

    def attend(layer, r: int, q, ck, cv, window: int, ring: bool):
        """Row ``r``'s window: q [1, C, ...] over its own table."""
        start = starts[r]
        first, n = prefill_key_blocks(c, t_w, bs, start, window)
        if ring:  # the same logical blocks, where the prompt's ring has them
            rows = ring_blocks(jnp.minimum(ssm_rows[r, 0], n_rows - 1), first,
                               n, rlen // bs, n_rows)[None]
        else:
            rows = jax.lax.dynamic_slice_in_dim(table_rows[r], first, n)[None]
        window_at = (first * bs, start, chunk_mask[r])
        mask = None if kernel else _prefill_mask(
            first * bs + jnp.arange(n * bs), chunk_mask[r:r + 1], start, window)
        if cfg.mla:
            # The row's latents, earlier windows' and this one's, as the
            # pool holds them, expanded again here: 0.017 GFLOP a key a
            # layer against 196 kFLOP more a query-key pair scored
            # absorbed — at 1024 queries a window expansion is the cheaper
            # by 6x (PERF.md section 6, PR 33).
            return _mla_expanded_attention(
                cfg, layer, q, gather_pages(ck, rows, bs), mask,
                window_at if kernel else None)
        if not kernel:
            return _gathered_attention(cfg, q, ck, cv, rows, bs, mask)
        from ..ops.prefill_attention import prefill_attention

        tail = cfg.kv_tail
        return prefill_attention(
            q[0], gather_pages(ck, rows, bs, tail)[0],
            gather_pages(cv, rows, bs, tail)[0], *window_at, window=window,
            scale=cfg.gqa_scale, interpret=cfg.pallas_interpret)[None]

    ad = lora.adapter_tables(params)
    new_k, new_v = [], []
    ring_k, ring_v = [], []

    def attend_rows(li, layer, x):
        q, k1, v1, g = _qkv_rope(cfg, layer, ad, li, x, cos, sin)
        ring = cfg.layer_kind(li).store == "ring"
        # Every row's keys land before any row attends: the pool is
        # written in place, then only read.
        if ring:
            with jax.named_scope("kv_write"):
                ck, cv = _ring_write(
                    state.ssm, len(ring_k), ring_dest,
                    k1.reshape((b * c,) + k1.shape[2:]),
                    v1.reshape((b * c,) + v1.shape[2:]), bs)
            ring_k.append(ck)
            ring_v.append(cv)
        else:
            ai = len(new_k)
            ck, cv = state.cache_k[ai], None if cfg.mla else state.cache_v[ai]
            with jax.named_scope("kv_write"):
                for r in range(b):
                    ck = _paged_scatter_entry(ck, table_rows[r], k1[r], bs, starts[r], dtype)
                    if not cfg.mla:
                        cv = _paged_scatter_entry(cv, table_rows[r], v1[r], bs, starts[r], dtype)
            new_k.append(ck)
            if not cfg.mla:
                new_v.append(cv)
        with _attn_scope(cfg, li):
            window = cfg.layer_kind(li).window
            ctx = [attend(layer, r, jax.tree.map(lambda a: a[r:r + 1], q), ck, cv,
                          window, ring)
                   for r in range(b)]
            ctx = ctx[0] if b == 1 else jnp.concatenate(ctx, axis=0)
        return _attn_out(cfg, layer, ad, li, x, ctx, g)

    def scan(block, layer, x, conv, st):
        at, fold = ssm_rows[:, 0], ssm_rows[:, 1]

        def rows_of(a):  # each prompt's row; zeros for a first window
            first = (starts == 0).reshape((b,) + (1,) * (a.ndim - 1))
            return jnp.where(first, 0, jnp.take(a, at, axis=0, mode="clip"))

        x, conv1, st1 = block(
            cfg, layer, x, rows_of(conv), rows_of(st),
            mask=jnp.arange(c)[None, :] < fold[:, None])
        return (x, conv.at[at].set(conv1, mode="drop"),
                st.at[at].set(st1, mode="drop"))

    recur, ssm_done = _ssm_walker(cfg, state.ssm, scan)
    # A window reads no logit (the prompt's last token is the first decode
    # step's): the cross-decoder's layers, which leave nothing behind, do
    # not run — a prompt costs them nothing, whatever its length.
    _layers(params, cfg, x, attend_rows, recur, lambda: chunk_mask != 0,
            tally, self_only=True)
    return state._replace(cache_k=new_k, cache_v=new_v,
                          ssm=_rings_done(ssm_done(), ring_k, ring_v))


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
    ssm_out: list = []
    _, kv = forward_hidden(
        params, cfg, input_ids, attention_mask, dtype, collect_kv=True,
        ssm_out=ssm_out,
    )
    cache_k, cache_v = [], []
    # ops/paged_attention's layout rule: payload [NB, BS, KVH*D],
    # scales [NB, BS, KVH].
    shape = (num_blocks, block_size, cfg.num_kv_heads * cfg.head_dim)
    sc_shape = (num_blocks, block_size, cfg.num_kv_heads)
    # A ring layer's keys go to the rows' rings, not to a pool.
    rings = [e for li, e in zip(cfg.own_layers, kv) if li in cfg.ring_layers]
    kv = [e for li, e in zip(cfg.own_layers, kv) if li not in cfg.ring_layers]
    for k, v in kv:
        if cfg.mla:  # one latent pool a layer
            ck = jnp.zeros((num_blocks, block_size, cfg.latent_lanes), k.dtype)
            for row in range(b):
                ck = scatter_pages(ck, table[row], k[row], block_size)
            cache_k.append(ck)
            continue
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
        ssm=_with_rings(cfg, ssm_out[0], rings),
    )
