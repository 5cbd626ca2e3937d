"""Operations and bytes of a decoder whose FFN is a sparse expert layer
(OLMoE-style: ``num_experts`` experts of width ``intermediate_size``,
``num_experts_per_tok`` of them a token, a ``[hidden, experts]`` router,
q/k-norm scales), computed from the configuration file's published
sizes — never from the program's own counters."""

from __future__ import annotations

from cellbench.costs import BF16, kv_bytes_per_token


def sizes(c: dict) -> dict:
    d = int(c["hidden_size"])
    heads, kvh = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    return {"d": d, "w": int(c["intermediate_size"]), "heads": heads,
            "kv": kvh * (d // heads), "hd": d // heads,
            "e": int(c["num_experts"]), "k": int(c["num_experts_per_tok"]),
            "layers": int(c["num_hidden_layers"]), "v": int(c["vocab_size"])}


def expert_layer_params(c: dict) -> dict:
    """One block: q, k, v, o; the q/k-norm and the two RMSNorm scales;
    the router; ``e`` experts of three ``d x w`` matrices each."""
    z = sizes(c)
    d, w, kv, e = z["d"], z["w"], z["kv"], z["e"]
    attention = 2 * d * d + 2 * d * kv
    norms = 2 * d + d + kv
    one_expert = 3 * d * w
    return {"attention": attention, "norms": norms, "router": d * e,
            "one_expert": one_expert, "experts": e * one_expert,
            "total": attention + norms + d * e + e * one_expert}


def decoder_params(c: dict) -> dict:
    z = sizes(c)
    layers = z["layers"] * expert_layer_params(c)["total"]
    head = 0 if c.get("tie_word_embeddings") else z["d"] * z["v"]
    return {"layers": layers, "embedding": z["d"] * z["v"], "head": head,
            "final_norm": z["d"],
            "total": layers + z["d"] * z["v"] + head + z["d"]}


def experts_hit(c: dict, batch: float) -> float:
    """Distinct experts a layer touches in a step of ``batch`` tokens,
    EXPECTED UNDER UNIFORM ROUTING of independent tokens (the assumption
    it is): ``e * (1 - (1 - k/e) ** batch)`` — 63.99 of 64 at 64 tokens
    of top-8.  A trained router is balanced by its loss; the seeded
    stand-in is held to the same by the reference's check, whose
    ``routing`` line reports what a 64-row step of its own hits
    (cellbench/references/olmoe.py)."""
    z = sizes(c)
    return z["e"] * (1.0 - (1.0 - z["k"] / z["e"]) ** batch)


def decode_step(c: dict, batch: float, live_tokens: float) -> dict:
    """One decode step of ``batch`` streams holding ``live_tokens`` tokens
    of context together.  Bytes: every attention, norm, router and head
    weight crosses HBM once, of the experts only those HIT (above), the
    embedding table gives one row a stream, the live KV is read once and
    one token a stream is written.  Operations: one multiply-add per
    stream for every dense weight, ``k`` experts' worth per stream, and
    the attention over the live context."""
    z, lp, p = sizes(c), expert_layer_params(c), decoder_params(c)
    dense = z["layers"] * (lp["attention"] + lp["norms"] + lp["router"])
    hit = z["layers"] * experts_hit(c, batch) * lp["one_expert"]
    expert_bytes = hit * BF16
    weights = (dense + p["head"] + p["final_norm"]) * BF16 + expert_bytes + (
        batch * z["d"] * BF16)
    kv = kv_bytes_per_token(c) * (live_tokens + batch)
    expert_flops = 2.0 * z["layers"] * z["k"] * lp["one_expert"] * batch
    attn_flops = 4.0 * z["layers"] * z["heads"] * z["hd"] * live_tokens
    flops = 2.0 * (dense + p["head"]) * batch + expert_flops + attn_flops
    return {"bytes": weights + kv, "weight_bytes": weights, "kv_bytes": kv,
            "flops": flops, "expert_bytes": expert_bytes,
            "expert_flops": expert_flops, "experts_hit": experts_hit(c, batch)}


def expert_matmuls(c: dict, batch: float) -> dict:
    """The grouped matmuls of one step alone: the hit experts' weights
    and the assignments' activations in and out (bf16), ``k`` experts'
    multiply-adds a token."""
    z, step = sizes(c), decode_step(c, batch, 0.0)
    rows = z["layers"] * batch * z["k"]
    activations = rows * (2 * z["d"] + 3 * z["w"]) * BF16  # in, gate, up, act, out
    return {"bytes": step["expert_bytes"] + activations,
            "flops": step["expert_flops"]}
