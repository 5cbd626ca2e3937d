"""Operations and bytes of a DeepSeek-V2-style decoder: multi-head latent
attention (q through a ``q_lora_rank`` bottleneck; K and V through one
``kv_lora_rank`` latent a token plus one ``qk_rope_head_dim`` rotary key
every head shares), leading dense layers, then expert layers of which
this chip HOLDS ``n_routed_experts`` of the router's ``router_experts``,
plus ``n_shared_experts`` that every token runs — computed from the
configuration file's published sizes, never from the program's own
counters.

The cache is one latent row a token a layer, and the decode kernel reads
it ONCE a key: ``kv_lora_rank + qk_rope_head_dim`` values (the pool's pad
lanes to a whole tile are not counted: a kernel is not credited with
bytes that hold nothing), which every head scores over all of them and
weighs over the first ``kv_lora_rank``: ``heads x (2 x latent + 2 x
kv_lora_rank)`` operations a key — 278.5 kFLOP on 1152 B at the published
sizes, 242 FLOP/B against a v5e's ridge of 240."""

from __future__ import annotations

from cellbench.costs import BF16


def sizes(c: dict) -> dict:
    layers, dense = int(c["num_hidden_layers"]), int(c["first_k_dense_replace"])
    return {"d": int(c["hidden_size"]), "heads": int(c["num_attention_heads"]),
            "q_rank": int(c["q_lora_rank"]), "kv_rank": int(c["kv_lora_rank"]),
            "nope": int(c["qk_nope_head_dim"]), "rope": int(c["qk_rope_head_dim"]),
            "vd": int(c["v_head_dim"]),
            "latent": int(c["kv_lora_rank"]) + int(c["qk_rope_head_dim"]),
            "w_dense": int(c["intermediate_size"]),
            "w": int(c["moe_intermediate_size"]),
            "held": int(c["n_routed_experts"]),
            "router": int(c.get("router_experts", c["n_routed_experts"])),
            "k": int(c["num_experts_per_tok"]),
            "shared": int(c["n_shared_experts"]), "layers": layers,
            "dense_layers": dense, "expert_layers": layers - dense,
            "v": int(c["vocab_size"])}


def attention_params(c: dict) -> dict:
    """W_DQ [d, q_rank], W_UQ [q_rank, heads x (nope + rope)], W_DKV [d,
    latent], W_UKV [kv_rank, heads x (nope + v)], W_O [heads x v, d]; the
    two pre-norms and the two inner norms' scales."""
    z = sizes(c)
    h = z["heads"]
    return {"projections": z["d"] * z["q_rank"]
            + z["q_rank"] * h * (z["nope"] + z["rope"])
            + z["d"] * z["latent"] + z["kv_rank"] * h * (z["nope"] + z["vd"])
            + h * z["vd"] * z["d"],
            "norms": 2 * z["d"] + z["q_rank"] + z["kv_rank"]}


def layer_params(c: dict) -> dict:
    """A dense layer and an expert layer (at the HELD experts), apart."""
    z, a = sizes(c), attention_params(c)
    attn = a["projections"] + a["norms"]
    one_expert = 3 * z["d"] * z["w"]
    router = z["d"] * z["router"]
    shared = z["shared"] * one_expert
    return {"attention": a["projections"], "norms": a["norms"],
            "dense_ffn": 3 * z["d"] * z["w_dense"], "one_expert": one_expert,
            "router": router, "shared": shared, "experts": z["held"] * one_expert,
            "dense_layer": attn + 3 * z["d"] * z["w_dense"],
            "expert_layer": attn + router + shared + z["held"] * one_expert}


def decoder_params(c: dict) -> dict:
    z, lp = sizes(c), layer_params(c)
    layers = (z["dense_layers"] * lp["dense_layer"]
              + z["expert_layers"] * lp["expert_layer"])
    head = 0 if c.get("tie_word_embeddings") else z["d"] * z["v"]
    return {"layers": layers, "embedding": z["d"] * z["v"], "head": head,
            "final_norm": z["d"],
            "total": layers + z["d"] * z["v"] + head + z["d"]}


def latent_bytes_per_token_layer(c: dict) -> int:
    """One cached row: the latent and the rotary key, once."""
    return sizes(c)["latent"] * BF16


def latent_flops_per_key_layer(c: dict) -> float:
    """Every head scores a key over the whole row and weighs its first
    ``kv_rank`` values: a multiply-add each."""
    z = sizes(c)
    return 2.0 * z["heads"] * (z["latent"] + z["kv_rank"])


def held_share(c: dict) -> float:
    """The share of a token's assignments that lands on this chip, IF
    EVEN over the router's experts: held / published."""
    z = sizes(c)
    return z["held"] / z["router"]


def experts_streamed(c: dict, batch: float) -> float:
    """Distinct HELD experts a layer touches in a step of ``batch``
    tokens — whose weights the step streams.  A token chooses a given
    held expert with probability ``q``: ``held x (1 - (1 - q)^batch)``.
    Under uniform routing ``q = k / router_experts`` (28.2 of 40 at 32
    rows of top-6 of 160); the seeded router is not uniform, so where the
    configuration file states what a ``MAX_STREAMS``-row step of the
    REFERENCE's own routing hits (``routing_held_experts_hit``, from every
    run's check) ``q`` is the one that reproduces that reading, and the
    grouped matmul is not credited with weights it never read (PERF.md
    section 6, PR 31's lesson 7)."""
    z = sizes(c)
    q = z["k"] / z["router"]
    hit, rows = c.get("routing_held_experts_hit"), float(c["env"]["MAX_STREAMS"])
    if hit:
        q = 1.0 - (1.0 - min(float(hit), z["held"] - 1e-9) / z["held"]) ** (1.0 / rows)
    return z["held"] * (1.0 - (1.0 - q) ** batch)


def latent_kernel(c: dict, batch: float, live_tokens: float) -> dict:
    """The latent decode kernel of one step, all layers: each live cached
    row read once, q in and the weighed latent out a stream."""
    z = sizes(c)
    per_layer_bytes = (latent_bytes_per_token_layer(c) * live_tokens
                       + batch * z["heads"] * (z["latent"] + z["kv_rank"]) * BF16)
    return {"bytes": z["layers"] * per_layer_bytes,
            "flops": z["layers"] * latent_flops_per_key_layer(c) * live_tokens}


def decode_step(c: dict, batch: float, live_tokens: float) -> dict:
    """One absorbed decode step of ``batch`` streams holding
    ``live_tokens`` tokens of context together.  Bytes: every attention
    (W_UKV's two halves are the absorb and unabsorb operands), norm,
    dense-FFN, router, shared-expert and head weight crosses HBM once, of
    the held experts only those HIT, the embedding table gives one row a
    stream, each live latent row is read once a layer and one row a stream
    a layer is written."""
    z, lp, p = sizes(c), layer_params(c), decoder_params(c)
    dense = (z["layers"] * (lp["attention"] + lp["norms"])
             + z["dense_layers"] * lp["dense_ffn"]
             + z["expert_layers"] * (lp["router"] + lp["shared"]))
    hit = z["expert_layers"] * experts_streamed(c, batch) * lp["one_expert"]
    weights = (dense + p["head"] + p["final_norm"] + hit) * BF16 + (
        batch * z["d"] * BF16)
    kernel = latent_kernel(c, batch, live_tokens)
    kv = kernel["bytes"] + latent_bytes_per_token_layer(c) * z["layers"] * batch
    expert_flops = (2.0 * z["expert_layers"] * z["k"] * held_share(c)
                    * lp["one_expert"] * batch)
    flops = 2.0 * (dense + p["head"]) * batch + expert_flops + kernel["flops"]
    return {"bytes": weights + kv, "weight_bytes": weights, "kv_bytes": kv,
            "flops": flops, "expert_bytes": hit * BF16,
            "expert_flops": expert_flops, "attn_flops": kernel["flops"],
            "experts_hit": experts_streamed(c, batch)}


def expert_matmuls(c: dict, batch: float) -> dict:
    """The grouped matmuls of one step alone (the ``moe_experts`` scope:
    the held routed experts, not the shared ones): the hit experts'
    weights and the assignments' activations in and out (bf16; the sort
    gathers every assignment's row, held or not), ``k x held_share``
    experts' multiply-adds a token."""
    z, step = sizes(c), decode_step(c, batch, 0.0)
    rows = z["expert_layers"] * batch * z["k"]
    activations = rows * (2 * z["d"] + 3 * z["w"]) * BF16
    return {"bytes": step["expert_bytes"] + activations,
            "flops": step["expert_flops"]}
