"""Operations and bytes a step needs, computed from the configuration
file's published sizes (never from the program's own counters)."""

from __future__ import annotations

BF16 = 2  # bytes; the configurations serve bf16 weights and KV


def decoder_layer_params(c: dict) -> int:
    """Parameters of one Mistral/Llama-style block: q, k, v, o, the
    gated MLP's three matrices and two RMSNorm scales."""
    d, ff = int(c["hidden_size"]), int(c["intermediate_size"])
    hd = d // int(c["num_attention_heads"])
    kv = int(c["num_key_value_heads"]) * hd
    return d * d * 2 + d * kv * 2 + 3 * d * ff + 2 * d


def decoder_params(c: dict) -> dict:
    d, v = int(c["hidden_size"]), int(c["vocab_size"])
    layers = int(c["num_hidden_layers"]) * decoder_layer_params(c)
    head = 0 if c.get("tie_word_embeddings") else d * v
    return {"layers": layers, "embedding": d * v, "head": head, "final_norm": d,
            "total": layers + d * v + head + d}


def kv_bytes_per_token(c: dict) -> int:
    """K and V of one token over every layer."""
    hd = int(c["hidden_size"]) // int(c["num_attention_heads"])
    return 2 * int(c["num_hidden_layers"]) * int(c["num_key_value_heads"]) * hd * BF16


def decode_step(c: dict, batch: float, live_tokens: float) -> dict:
    """One decode step of ``batch`` streams holding ``live_tokens``
    tokens of context together: every weight but the embedding table
    crosses HBM once (the table gives one row per stream), the live KV
    is read once and one token per stream is written; each weight does
    one multiply-add per stream."""
    p = decoder_params(c)
    d = int(c["hidden_size"])
    weights = (p["layers"] + p["head"] + p["final_norm"]) * BF16 + batch * d * BF16
    kv = kv_bytes_per_token(c) * (live_tokens + batch)
    hd = d // int(c["num_attention_heads"])
    attn_flops = 4.0 * int(c["num_hidden_layers"]) * int(
        c["num_attention_heads"]) * hd * live_tokens
    flops = 2.0 * (p["layers"] + p["head"]) * batch + attn_flops
    return {"bytes": weights + kv, "weight_bytes": weights, "kv_bytes": kv,
            "flops": flops}


def roofline_seconds(cost: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_mem = cost["bytes"] / peaks["hbm_bytes_per_s"]
    t_flop = cost["flops"] / peaks["bf16_flops_per_s"]
    return (t_mem, "hbm") if t_mem >= t_flop else (t_flop, "flops")
