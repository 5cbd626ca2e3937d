"""Operations and bytes of a GraniteMoeHybrid-style decoder — every layer a
Mamba-2 mixer or a GQA attention (``layer_types`` cut to
``num_hidden_layers``) THEN an expert block: gated experts on the full
hidden width, of which this chip HOLDS ``num_local_experts`` of the router's
``router_experts``, plus a shared expert; a tied head — computed from the
configuration file's published sizes, never from the program's own counters.

A stream's state is two things: ONE attention layer a period caches keys
and values a token (``kv_bytes_per_token``), and every Mamba layer holds a
fixed ``[heads, head_dim, state]`` float32 state plus ``mamba_d_conv - 1``
taps of the convolution's width (``state_bytes_per_stream``), read AND
written by every decode step of a live stream: at the published sizes
4.19 MB a layer each way, nine layers, against 4 KB a token of KV."""

from __future__ import annotations

from cellbench.costs import BF16

F32 = 4


def sizes(c: dict) -> dict:
    layers = int(c["num_hidden_layers"])
    kinds = [str(t) for t in c["layer_types"]][:layers]
    d, heads = int(c["hidden_size"]), int(c["num_attention_heads"])
    mh, mp = int(c["mamba_n_heads"]), int(c["mamba_d_head"])
    g, n = int(c["mamba_n_groups"]), int(c["mamba_d_state"])
    return {"d": d, "layers": layers,
            "m_layers": kinds.count("mamba"), "a_layers": kinds.count("attention"),
            "heads": heads, "kv_heads": int(c["num_key_value_heads"]),
            "hd": d // heads,
            "mh": mh, "mp": mp, "g": g, "n": n, "inner": mh * mp,
            "conv_dim": mh * mp + 2 * g * n, "conv_k": int(c["mamba_d_conv"]),
            "chunk": int(c["scan_chunk"]),
            "w": int(c["intermediate_size"]),
            "w_shared": int(c["shared_intermediate_size"]),
            "held": int(c["num_local_experts"]),
            "router": int(c.get("router_experts", c["num_local_experts"])),
            "k": int(c["num_experts_per_tok"]), "v": int(c["vocab_size"])}


def layer_params(c: dict) -> dict:
    """A layer of each kind OUTSIDE its routed experts (its mixer, its two
    norms, the router and the shared expert), one expert, and the parts the
    step costs them by."""
    z = sizes(c)
    d = z["d"]
    mixer = (d * (2 * z["inner"] + 2 * z["g"] * z["n"] + z["mh"])  # in: z | xBC | dt
             + z["conv_k"] * z["conv_dim"] + z["conv_dim"]  # taps and bias
             + 3 * z["mh"]  # dt_bias, A_log, D
             + z["inner"]  # the gated norm's scale
             + z["inner"] * d)  # out
    attention = 2 * d * z["heads"] * z["hd"] + 2 * d * z["kv_heads"] * z["hd"]
    shared = 3 * d * z["w_shared"]  # gated: gate, up, down
    block = d * z["router"] + shared + 2 * d  # router, shared expert, two pre-norms
    one_expert = 3 * d * z["w"]
    return {"mamba_mixer": mixer, "attention_mixer": attention,
            "shared": shared, "ffn_dense": block,
            "mamba_dense": mixer + block, "attention_dense": attention + block,
            "one_expert": one_expert,
            "mamba_layer": mixer + block + z["held"] * one_expert,
            "attention_layer": attention + block + z["held"] * one_expert}


def decoder_params(c: dict) -> dict:
    z, lp = sizes(c), layer_params(c)
    layers = (z["m_layers"] * lp["mamba_layer"] + z["a_layers"] * lp["attention_layer"])
    head = 0 if c.get("tie_word_embeddings") else z["d"] * z["v"]
    return {"layers": layers, "embedding": z["d"] * z["v"], "head": head,
            "final_norm": z["d"],
            "total": layers + z["d"] * z["v"] + head + z["d"]}


def kv_bytes_per_token(c: dict) -> int:
    """K and V of one token over the ATTENTION layers."""
    z = sizes(c)
    return 2 * z["a_layers"] * z["kv_heads"] * z["hd"] * BF16


def state_bytes_per_stream(c: dict) -> int:
    """A stream's recurrent state over the Mamba layers: the float32
    state and the convolution's taps (bf16)."""
    z = sizes(c)
    return z["m_layers"] * (z["mh"] * z["mp"] * z["n"] * F32
                            + (z["conv_k"] - 1) * z["conv_dim"] * BF16)


def held_share(c: dict) -> float:
    z = sizes(c)
    return z["held"] / z["router"]


def experts_streamed(c: dict, batch: float) -> float:
    """Distinct HELD experts a layer touches in a step of ``batch`` tokens
    (``costs_nemotron.experts_streamed``'s rule: from the configuration's
    ``routing_held_experts_hit`` — what a ``MAX_STREAMS``-row step of the
    reference's own routing hits — where it states one, else uniform:
    35.7 of 36 at 32 rows of top-10 of 72)."""
    z = sizes(c)
    q = z["k"] / z["router"]
    hit, rows = c.get("routing_held_experts_hit"), float(c["env"]["MAX_STREAMS"])
    if hit:
        q = 1.0 - (1.0 - min(float(hit), z["held"] - 1e-9) / z["held"]) ** (1.0 / rows)
    return z["held"] * (1.0 - (1.0 - q) ** batch)


def ssm_step(c: dict, batch: float) -> dict:
    """The one-token state update of one step, all Mamba layers: each live
    row's state read once and written once; a decay, an outer product and
    a contraction an element (6 operations)."""
    z = sizes(c)
    elements = z["m_layers"] * batch * z["mh"] * z["mp"] * z["n"]
    return {"bytes": 2.0 * elements * F32, "flops": 6.0 * elements}


def ssm_scan(c: dict, rows: float, tokens: float) -> dict:
    """The chunked scan of one window dispatch, all Mamba layers:
    ``tokens`` positions over ``rows`` prompts.  A chunk of Q tokens:
    ``C B^T`` ONCE A GROUP (2 Q^2 N G: the kernel computes it again a block
    of heads, which is its own cost and none of the work's), the masked
    decay matrix times it against the inputs (2 Q^2 P H), what the chunk
    leaves and what it reads of the carried state (2 x 2 Q P N H).  Bytes:
    x, B, C (bf16) and the steps (float32) in, y (float32) out, each row's
    state in and out."""
    z = sizes(c)
    q, h, p, n, g = z["chunk"], z["mh"], z["mp"], z["n"], z["g"]
    per_chunk = 2.0 * q * q * (n * g + p * h) + 4.0 * q * p * n * h
    per_token = (z["conv_dim"] * BF16 + h * F32 + z["inner"] * F32)
    return {"flops": z["m_layers"] * tokens / q * per_chunk,
            "bytes": z["m_layers"] * (tokens * per_token
                                      + rows * 2.0 * h * p * n * F32)}


def attention_kernel(c: dict, batch: float, live_tokens: float) -> dict:
    """The paged decode kernel of one step over the attention layers: each
    live key and value read once, q in and the context out a stream."""
    z = sizes(c)
    return {"bytes": kv_bytes_per_token(c) * live_tokens
            + z["a_layers"] * 2.0 * batch * z["heads"] * z["hd"] * BF16,
            "flops": 4.0 * z["a_layers"] * z["heads"] * z["hd"] * live_tokens}


def decode_step(c: dict, batch: float, live_tokens: float) -> dict:
    """One decode step of ``batch`` live streams holding ``live_tokens``
    tokens of context together.  Bytes: every mixer, router, shared-expert
    and norm weight crosses HBM once, the tied table once (as the head; as
    the embedding it gives one row a stream), of the held experts only those
    HIT; each live stream's recurrent state is read and written, its keys
    and values read once and one token written."""
    z, lp, p = sizes(c), layer_params(c), decoder_params(c)
    dense = z["m_layers"] * lp["mamba_dense"] + z["a_layers"] * lp["attention_dense"]
    table = z["d"] * z["v"]  # the head's read of the tied table
    hit = z["layers"] * experts_streamed(c, batch) * lp["one_expert"]
    weights = (dense + table + p["final_norm"] + hit) * BF16 + batch * z["d"] * BF16
    state = 2.0 * state_bytes_per_stream(c) * batch
    kernel = attention_kernel(c, batch, live_tokens)
    kv = kernel["bytes"] + kv_bytes_per_token(c) * batch
    expert_flops = (2.0 * z["layers"] * z["k"] * held_share(c)
                    * lp["one_expert"] * batch)
    flops = (2.0 * (dense + table) * batch + expert_flops + kernel["flops"]
             + ssm_step(c, batch)["flops"])
    return {"bytes": weights + state + kv, "weight_bytes": weights,
            "state_bytes": state, "kv_bytes": kv, "flops": flops,
            "expert_bytes": hit * BF16, "expert_flops": expert_flops,
            "experts_hit": experts_streamed(c, batch)}


def expert_matmuls(c: dict, batch: float) -> dict:
    """The grouped matmuls of one step alone (the ``moe_experts`` scope: the
    held routed experts on the full hidden width): the hit experts' weights
    and the assignments' activations in and out (bf16; the sort gathers
    every assignment's row, held or not: gate and up read it, down writes
    one)."""
    z, step = sizes(c), decode_step(c, batch, 0.0)
    rows = z["layers"] * batch * z["k"]
    activations = rows * (2 * z["d"] + 3 * z["w"]) * BF16
    return {"bytes": step["expert_bytes"] + activations,
            "flops": step["expert_flops"]}
