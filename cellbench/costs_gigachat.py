"""Operations and bytes of a GigaChat3.5-style decoder — layers that are a
Gated-DeltaNet mixer or (``layer_types`` "full") a gated latent attention,
each followed by a dense FFN (the first ``first_k_dense_replace``) or an
expert FFN of which this chip HOLDS ``n_routed_experts`` of the router's
``router_experts`` plus one shared expert — computed from the configuration
file's published sizes, never from the program's own counters.  The latent
layer's parts are ``cellbench/costs_mla.py``'s at this file's 64 heads.

A stream's state is two things: the latent layers cache ONE row a token
(``latent_bytes_per_token``), and every DeltaNet layer holds a fixed
``[value heads, Dv, Dk]`` float32 matrix state plus ``conv - 1`` taps of
the convolution's 16384 channels (``state_bytes_per_stream``): at the
published sizes 4.19 MB a layer, which a decode step reads AND writes for
EVERY state row, live or not — the step updates the rows where they lie
under a mask (``models/llama._paged_ssm_step``) — so ``gdn_step`` counts
all ``MAX_STREAMS`` rows and its ceiling is what a step over live rows
alone would move: live / rows."""

from __future__ import annotations

from cellbench import costs_mla
from cellbench.costs import BF16

F32 = 4


def sizes(c: dict) -> dict:
    layers = int(c["num_hidden_layers"])
    kinds = list(c["layer_types"])[:layers]
    dense = int(c["first_k_dense_replace"])
    hk, hv = int(c["linear_num_key_heads"]), int(c["linear_num_value_heads"])
    dk, dv = int(c["linear_key_head_dim"]), int(c["linear_value_head_dim"])
    return {"d": int(c["hidden_size"]), "layers": layers,
            "g_layers": kinds.count("linear"), "a_layers": kinds.count("full"),
            "dense_layers": dense, "expert_layers": layers - dense,
            "hk": hk, "hv": hv, "dk": dk, "dv": dv,
            "conv_dim": 2 * hk * dk + hv * dv, "inner": hv * dv,
            "conv_k": int(c["linear_conv_kernel_dim"]),
            "q_rank": int(c["q_lora_rank"]), "kv_rank": int(c["kv_lora_rank"]),
            "rows": int(c["env"]["MAX_STREAMS"]),
            "w_dense": int(c["intermediate_size"]),
            "w": int(c["moe_intermediate_size"]),
            "held": int(c["n_routed_experts"]),
            "router": int(c.get("router_experts", c["n_routed_experts"])),
            "k": int(c["num_experts_per_tok"]),
            "shared": int(c["n_shared_experts"]), "v": int(c["vocab_size"])}


def layer_params(c: dict) -> dict:
    """The parts a layer is made of; every layer has two pre- and two
    post-norm scales (``norms``)."""
    z = sizes(c)
    d = z["d"]
    gdn = (d * (z["conv_dim"] + z["inner"])  # W_qkvz
           + d * 2 * z["hv"]  # W_ba
           + z["conv_k"] * z["conv_dim"]  # taps, no bias
           + 2 * z["hv"] + z["dv"]  # A_log, dt_bias, the output norm
           + z["inner"] * d)  # W_out
    a = costs_mla.attention_params(c)
    latent = (a["projections"] + z["q_rank"] + z["kv_rank"]  # the inner norms
              + (d * a_heads(c) if c.get("gated_attention") else 0))
    one_expert = 3 * d * z["w"]
    return {"gdn": gdn, "latent": latent, "norms": 4 * d,
            "dense_ffn": 3 * d * z["w_dense"], "one_expert": one_expert,
            "router": d * z["router"] + z["router"],
            "shared": z["shared"] * one_expert,
            "experts": z["held"] * one_expert}


def a_heads(c: dict) -> int:
    """Width of the latent attention's merged values (and of its gate)."""
    return int(c["num_attention_heads"]) * int(c["v_head_dim"])


def decoder_params(c: dict) -> dict:
    z, lp = sizes(c), layer_params(c)
    dense = (z["g_layers"] * lp["gdn"] + z["a_layers"] * lp["latent"]
             + z["layers"] * lp["norms"] + z["dense_layers"] * lp["dense_ffn"]
             + z["expert_layers"] * (lp["router"] + lp["shared"]))
    layers = dense + z["expert_layers"] * lp["experts"]
    head = 0 if c.get("tie_word_embeddings") else z["d"] * z["v"]
    return {"dense": dense, "layers": layers, "embedding": z["d"] * z["v"],
            "head": head, "final_norm": z["d"],
            "total": layers + z["d"] * z["v"] + head + z["d"]}


def latent_bytes_per_token(c: dict) -> int:
    """One cached row a LATENT layer: the latent and the rotary key."""
    return sizes(c)["a_layers"] * costs_mla.latent_bytes_per_token_layer(c)


def state_bytes_per_stream(c: dict) -> int:
    """A stream's recurrent state over the DeltaNet layers: the float32
    matrices and the convolution's taps (bf16)."""
    z = sizes(c)
    return z["g_layers"] * (z["hv"] * z["dv"] * z["dk"] * F32
                            + (z["conv_k"] - 1) * z["conv_dim"] * BF16)


def gdn_step(c: dict, batch: float) -> dict:
    """The one-token delta rule of one step, all DeltaNet layers.  Bytes:
    EVERY state row (``MAX_STREAMS``: the step updates them where they lie,
    under a mask) read once and written once.  Operations, the ``batch``
    live rows': decay, S k, the rank-one update and S q — 7 an element.
    (The ``gdn_step`` scope's time also holds the rows' q / k
    normalisation and gates: 32 x 16384 bf16 values a layer beside 268 MB
    of state, counted at no byte.)"""
    z = sizes(c)
    per_row = z["g_layers"] * z["hv"] * z["dv"] * z["dk"]
    return {"bytes": 2.0 * z["rows"] * per_row * F32,
            "live_bytes": 2.0 * batch * per_row * F32,
            "flops": 7.0 * batch * per_row}


def gdn_projections(c: dict, tokens: float) -> dict:
    """``W_qkvz``, ``W_ba`` and ``W_out`` of every DeltaNet layer over
    ``tokens`` rows (a step's live streams, or a dispatch's positions): the
    weights once, the rows in and out (bf16), a multiply-add a weight a row."""
    z = sizes(c)
    per_layer = z["d"] * (z["conv_dim"] + z["inner"] + 2 * z["hv"]) + z["inner"] * z["d"]
    rows = tokens * (2 * z["d"] + z["conv_dim"] + 2 * z["inner"] + 2 * z["hv"])
    return {"bytes": z["g_layers"] * (per_layer + rows) * BF16,
            "flops": 2.0 * z["g_layers"] * per_layer * tokens}


#: ``ops/ssm.py``'s ``GDN_CHUNK`` and ``INVERSE_BLOCK`` (this file computes
#: from the configuration file and its own constants, never from the program).
SCAN_CHUNK, INVERSE_BLOCK = 64, 16


def gdn_scan(c: dict, rows: float, tokens: float) -> dict:
    """The chunked scan of one window dispatch, all DeltaNet layers:
    ``tokens`` positions over ``rows`` prompts, counted as what a fused
    kernel MUST move and do (the XLA form moves more: q and k repeated a
    value head in float32, every ``[Q, Q]`` matrix through HBM).  Bytes, a
    token a layer: the convolution's output [q | k | v] where it lies (a
    KEY head's q and k once, bf16), the two gates a value head (float32)
    in, o out (bf16); each row's state in and out (float32).  Operations, a
    chunk of Q tokens of one value head: K K^T and Q K^T (2 x 2 Q^2 Dk);
    the triangular inverse as the program runs it — forward substitution
    in diagonal blocks of b = 16 (2 b (b - 1) Q), then pairs of inverted
    blocks merged upwards, two b^3 matmuls a pair a level (2 Q b^2 at b =
    16, 32, ...); T against [b V | b e^G K] (2 Q^2 (Dv + Dk)); the chunk's
    U, its outputs and the carried state (Kc S^T, Q S^T, U^T K: 3 x 2 Q Dk
    Dv; (Q K^T) U: 2 Q^2 Dv).  The normalisation of q and k, the decays
    and the gates run under the same scope and are counted at no
    operation.  At the published sizes the bytes bind (0.87 ms a
    three-window dispatch against 0.73 ms of operations at the peaks)."""
    z = sizes(c)
    q, hv, dk, dv = SCAN_CHUNK, z["hv"], z["dk"], z["dv"]
    b, inverse = INVERSE_BLOCK, 2.0 * INVERSE_BLOCK * (INVERSE_BLOCK - 1) * q
    while b < q:
        inverse += 2.0 * q * b * b
        b *= 2
    per_chunk = hv * (4.0 * q * q * dk + inverse
                      + 2.0 * q * q * (dv + dk) + 6.0 * q * dk * dv
                      + 2.0 * q * q * dv)
    per_token = z["conv_dim"] * BF16 + 2 * hv * F32 + z["inner"] * BF16
    return {"flops": z["g_layers"] * tokens / q * per_chunk,
            "bytes": z["g_layers"] * (tokens * per_token
                                      + rows * 2.0 * hv * dv * dk * F32)}


def latent_kernel(c: dict, batch: float, live_tokens: float) -> dict:
    """The latent decode kernel of one step over the LATENT layers
    (``costs_mla.latent_kernel`` counts every layer of its decoder)."""
    z = sizes(c)
    heads, lat = int(c["num_attention_heads"]), costs_mla.sizes(c)["latent"]
    per_layer = (costs_mla.latent_bytes_per_token_layer(c) * live_tokens
                 + batch * heads * (lat + z["kv_rank"]) * BF16)
    return {"bytes": z["a_layers"] * per_layer,
            "flops": z["a_layers"] * costs_mla.latent_flops_per_key_layer(c)
            * live_tokens}


def experts_streamed(c: dict, batch: float) -> float:
    """Distinct HELD experts a layer touches in a step of ``batch`` tokens:
    ``costs_mla.experts_streamed``'s rule (from the file's
    ``routing_held_experts_hit``, the reference's own routing, where it
    states one; else uniform: 10.2 of 16 at 32 rows of top-8 of 256)."""
    return costs_mla.experts_streamed(c, batch)


def decode_step(c: dict, batch: float, live_tokens: float) -> dict:
    """One decode step of ``batch`` live streams holding ``live_tokens``
    tokens of context together.  Bytes: every mixer, norm, dense-FFN,
    router, shared-expert and head weight crosses HBM once, of the held
    experts only those HIT; the embedding gives one row a stream; every
    state row is read and written (``gdn_step``) and each live row's taps;
    each live latent row is read once and one row a stream written."""
    z, lp, p = sizes(c), layer_params(c), decoder_params(c)
    hit = z["expert_layers"] * experts_streamed(c, batch) * lp["one_expert"]
    weights = (p["dense"] + p["head"] + p["final_norm"] + hit) * BF16 + (
        batch * z["d"] * BF16)
    step = gdn_step(c, batch)
    taps = 2.0 * batch * z["g_layers"] * (z["conv_k"] - 1) * z["conv_dim"] * BF16
    kernel = latent_kernel(c, batch, live_tokens)
    kv = kernel["bytes"] + latent_bytes_per_token(c) * batch
    expert_flops = (2.0 * z["expert_layers"] * z["k"] * costs_mla.held_share(c)
                    * lp["one_expert"] * batch)
    flops = (2.0 * (p["dense"] + p["head"]) * batch + expert_flops
             + kernel["flops"] + step["flops"])
    return {"bytes": weights + step["bytes"] + taps + kv, "weight_bytes": weights,
            "state_bytes": step["bytes"] + taps, "kv_bytes": kv, "flops": flops,
            "expert_bytes": hit * BF16, "expert_flops": expert_flops,
            "experts_hit": experts_streamed(c, batch)}


def expert_matmuls(c: dict, batch: float) -> dict:
    """The grouped matmuls of one step alone (the ``moe_experts`` scope:
    the held routed experts): the hit experts' weights and the
    assignments' activations in and out (bf16; the sort gathers every
    assignment's row, held or not)."""
    z, step = sizes(c), decode_step(c, batch, 0.0)
    rows = z["expert_layers"] * batch * z["k"]
    activations = rows * (2 * z["d"] + 3 * z["w"]) * BF16
    return {"bytes": step["expert_bytes"] + activations,
            "flops": step["expert_flops"]}
