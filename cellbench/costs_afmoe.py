"""Operations and bytes of an AFMoE-style decoder (Trinity): a per-layer
pattern of window and full attention, leading dense layers, then expert
layers of ``num_experts`` routed experts (``num_experts_per_tok`` a
token) plus ``num_shared_experts`` that every token runs, a gated
attention whose ``head_dim`` is a key of its own — computed from the
configuration file's published sizes, never from the program's own
counters.  KV bytes are WINDOW-AWARE: a sliding layer reads
``min(context, sliding_window)`` keys a stream, so that a window
layer's kernel is not credited with keys it never read."""

from __future__ import annotations

from cellbench.costs import BF16


def sizes(c: dict) -> dict:
    heads, kvh, hd = (int(c["num_attention_heads"]),
                      int(c["num_key_value_heads"]), int(c["head_dim"]))
    layers = int(c["num_hidden_layers"])
    sliding = [t == "sliding_attention" for t in c["layer_types"][:layers]]
    return {"d": int(c["hidden_size"]), "hd": hd, "heads": heads,
            "q": heads * hd, "kv": kvh * hd,
            "w_dense": int(c["intermediate_size"]),
            "w": int(c["moe_intermediate_size"]),
            "e": int(c["num_experts"]), "k": int(c["num_experts_per_tok"]),
            "shared": int(c["num_shared_experts"]), "layers": layers,
            "dense_layers": int(c["num_dense_layers"]),
            "expert_layers": layers - int(c["num_dense_layers"]),
            "window_layers": sum(sliding), "full_layers": layers - sum(sliding),
            "window": int(c["sliding_window"]), "v": int(c["vocab_size"])}


def attention_params(c: dict) -> dict:
    """q and the output gate [d, heads x head_dim], k and v [d, kv], o
    [heads x head_dim, d]; the two pre-norms, the two post-norms and the
    per-head q/k-norm scales."""
    z = sizes(c)
    return {"projections": 3 * z["d"] * z["q"] + 2 * z["d"] * z["kv"],
            "norms": 4 * z["d"] + 2 * z["hd"]}


def layer_params(c: dict) -> dict:
    """A dense layer and an expert layer, counted apart."""
    z, a = sizes(c), attention_params(c)
    attn = a["projections"] + a["norms"]
    one_expert = 3 * z["d"] * z["w"]
    router = z["d"] * z["e"] + z["e"]  # and the selection bias
    shared = z["shared"] * one_expert
    return {"attention": a["projections"], "norms": a["norms"],
            "dense_ffn": 3 * z["d"] * z["w_dense"], "one_expert": one_expert,
            "router": router, "shared": shared, "experts": z["e"] * one_expert,
            "dense_layer": attn + 3 * z["d"] * z["w_dense"],
            "expert_layer": attn + router + shared + z["e"] * one_expert}


def decoder_params(c: dict) -> dict:
    z, lp = sizes(c), layer_params(c)
    layers = (z["dense_layers"] * lp["dense_layer"]
              + z["expert_layers"] * lp["expert_layer"])
    head = 0 if c.get("tie_word_embeddings") else z["d"] * z["v"]
    return {"layers": layers, "embedding": z["d"] * z["v"], "head": head,
            "final_norm": z["d"],
            "total": layers + z["d"] * z["v"] + head + z["d"]}


def kv_bytes_per_token_layer(c: dict) -> int:
    """K and V of one token in one layer."""
    return 2 * sizes(c)["kv"] * BF16


def experts_hit(c: dict, batch: float, experts: float | None = None) -> float:
    """Distinct routed experts a layer touches in a step of ``batch``
    tokens, EXPECTED UNDER UNIFORM ROUTING of independent tokens over
    ``experts`` experts (default: all ``num_experts``, the assumption it
    is): ``e * (1 - (1 - k/e) ** batch)`` — 111.8 of 128 at 32 tokens of
    top-8.  The reference's check reports what a 32-row step of its own
    hits (``routing``)."""
    z = sizes(c)
    e = float(experts or z["e"])
    return e * (1.0 - (1.0 - z["k"] / e) ** batch)


def experts_streamed(c: dict, batch: float) -> float:
    """The experts whose weights a step STREAMS, for the byte counts: the
    seeded router with its selection bias is less even than uniform (the
    reference's own routing of a 32-row step hits 98.5 of 128, not
    111.8), so bytes counted under uniform routing credit the grouped
    matmul with weights it never read (a roofline share of 101 % in a
    traced run, my chip run, PR 31).  The configuration file states the
    number of equally likely experts that reproduces the reference's
    reading (``routing_effective_experts``: 107.5 gives 98.5 at 32
    rows); without the key routing is taken as uniform."""
    return experts_hit(c, batch, c.get("routing_effective_experts"))


def kv_read_bytes(c: dict, live_tokens: float, window_tokens: float) -> float:
    """Bytes of KV a step reads: a full layer every live token, a
    sliding layer ``window_tokens`` = the sum over streams of
    ``min(context, sliding_window)``."""
    z = sizes(c)
    return kv_bytes_per_token_layer(c) * (
        z["full_layers"] * live_tokens + z["window_layers"] * window_tokens)


def decode_step(c: dict, batch: float, live_tokens: float,
                window_tokens: float) -> dict:
    """One decode step of ``batch`` streams holding ``live_tokens`` tokens
    of context together, ``window_tokens`` of them inside a sliding
    layer's window.  Bytes: every attention, norm, dense-FFN, router,
    shared-expert and head weight crosses HBM once, of the routed experts
    only those HIT, the embedding table gives one row a stream, the KV is
    read window-aware and one token a stream a layer is written."""
    z, lp, p = sizes(c), layer_params(c), decoder_params(c)
    dense = (z["layers"] * (lp["attention"] + lp["norms"])
             + z["dense_layers"] * lp["dense_ffn"]
             + z["expert_layers"] * (lp["router"] + lp["shared"]))
    hit = z["expert_layers"] * experts_streamed(c, batch) * lp["one_expert"]
    weights = (dense + p["head"] + p["final_norm"] + hit) * BF16 + (
        batch * z["d"] * BF16)
    kv = kv_read_bytes(c, live_tokens, window_tokens) + (
        kv_bytes_per_token_layer(c) * z["layers"] * batch)
    expert_flops = 2.0 * z["expert_layers"] * z["k"] * lp["one_expert"] * batch
    attn_flops = 4.0 * z["heads"] * z["hd"] * (
        z["full_layers"] * live_tokens + z["window_layers"] * window_tokens)
    flops = 2.0 * (dense + p["head"]) * batch + expert_flops + attn_flops
    return {"bytes": weights + kv, "weight_bytes": weights, "kv_bytes": kv,
            "flops": flops, "expert_bytes": hit * BF16,
            "expert_flops": expert_flops, "experts_hit": experts_streamed(c, batch)}


def expert_matmuls(c: dict, batch: float) -> dict:
    """The grouped matmuls of one step alone (the ``moe_experts`` scope:
    the routed experts, not the shared one): the hit experts' weights and
    the assignments' activations in and out (bf16), ``k`` experts'
    multiply-adds a token."""
    z, step = sizes(c), decode_step(c, batch, 0.0, 0.0)
    rows = z["expert_layers"] * batch * z["k"]
    activations = rows * (2 * z["d"] + 3 * z["w"]) * BF16  # in, gate, up, act, out
    return {"bytes": step["expert_bytes"] + activations,
            "flops": step["expert_flops"]}
