"""Operations and bytes of a SambaY decoder as Phi-4-mini-flash-reasoning has
it — a self-decoder of Mamba-1 and window-attention layers ending in ONE
full-attention layer, a cross-decoder of Gated Memory Units and cross-attention
layers that read that one layer's keys and values, a dense SiLU-gated MLP
behind every mixer, differential attention, the head tied to the embedding —
computed from the configuration file's sizes and the mathematics, never from
the program's own counters or its implementation.

What a stream holds is three things (``stores``): ONE layer's keys and values a
token (``full_bytes_per_token``: (K + V) x 20 KV heads x 64 x 2 B = 5120 B),
which the full layer AND the seven cross layers read — eight reads of the same
bytes a step, none of them avoidable (a layer's queries differ); a window
layer's keys and values for at most ``sliding_window`` keys (what ANY store
must keep; the served ring keeps ``window_ring`` a stream); and a Mamba layer's
fixed ``[d_state, d_inner]`` float32 state plus taps.

Differential attention a layer a step, as the mathematics needs it: each live
key and each live value crosses HBM ONCE (a pair's two softmaxes come from one
pass over [k1 | k2], both weigh the same 128-wide [v1 | v2]); the family's own
four-call form would read every key twice and every value twice.  Operations:
a query head scores 64 dims a key and weighs 128 a value: 2 x (64 + 128) a
head a key.

The selective scan has no matmul form: ``ssm_scan`` / ``ssm_step`` give
``flops`` 0 (their rooflines bind on bytes) and carry ``vector_ops`` and
``exponentials`` beside it, as ``costs_jamba`` does (the same kernel at the
same shape; its ``sizes`` counts Mamba layers by Jamba's period and offset,
which this configuration has not)."""

from __future__ import annotations

from cellbench.costs import BF16

F32 = 4
SCAN_VECTOR_OPS = 7  # costs_jamba's count, a state element a token


def sizes(c: dict) -> dict:
    kinds = list(c["layers_block_type"])
    d, heads = int(c["hidden_size"]), int(c["num_attention_heads"])
    return {"d": d, "layers": len(kinds),
            "mamba": kinds.count("mamba"), "window": kinds.count("window"),
            "full": kinds.count("full"), "gmu": kinds.count("gmu"),
            "cross": kinds.count("cross"),
            "self_layers": min(i for i, k in enumerate(kinds) if k in ("gmu", "cross")),
            "heads": heads, "kv_heads": int(c["num_key_value_heads"]),
            "hd": d // heads, "win": int(c["sliding_window"]),
            "inner": int(c["mamba_d_inner"]), "n": int(c["mamba_d_state"]),
            "conv_k": int(c["mamba_d_conv"]), "r": int(c["mamba_dt_rank"]),
            "ff": int(c["intermediate_size"]), "v": int(c["vocab_size"]),
            "ring": int(c["window_ring"]), "rows": int(c["env"]["MAX_STREAMS"])}


def layer_params(c: dict) -> dict:
    """A mixer of each kind and the MLP, each with its LayerNorm (scale and
    bias)."""
    z = sizes(c)
    d, ch, n, r = z["d"], z["inner"], z["n"], z["r"]
    mamba_proj = d * 2 * ch + ch * (r + 2 * n) + r * ch + ch * d  # in, x, dt, out
    mamba = (mamba_proj + z["conv_k"] * ch + ch + ch  # taps, conv bias, dt bias
             + n * ch + ch + 2 * d)  # A_log, D, the pre-norm
    q = d * z["heads"] * z["hd"] + z["heads"] * z["hd"]
    kv = 2 * (d * z["kv_heads"] * z["hd"] + z["kv_heads"] * z["hd"])
    o = z["heads"] * z["hd"] * d + d
    diff = 4 * z["hd"] + 2 * z["hd"]  # lambda's vectors, the sub-norm
    attention = q + kv + o + diff + 2 * d
    cross = q + o + diff + 2 * d
    gmu = 2 * d * ch + 2 * d
    mlp = 3 * d * z["ff"] + 2 * d
    return {"mamba_mixer": mamba, "mamba_proj": mamba_proj, "attention_mixer": attention,
            "cross_mixer": cross, "gmu_mixer": gmu, "gmu_proj": 2 * d * ch, "mlp": mlp}


def decoder_params(c: dict) -> dict:
    z, lp = sizes(c), layer_params(c)
    self_layers = (z["mamba"] * lp["mamba_mixer"]
                   + (z["window"] + z["full"]) * lp["attention_mixer"]
                   + z["self_layers"] * lp["mlp"])
    cross_layers = (z["gmu"] * lp["gmu_mixer"] + z["cross"] * lp["cross_mixer"]
                    + (z["layers"] - z["self_layers"]) * lp["mlp"])
    emb = z["d"] * z["v"]
    return {"self_layers": self_layers, "cross_layers": cross_layers,
            "layers": self_layers + cross_layers, "embedding": emb, "head": 0,
            "final_norm": 2 * z["d"],
            "total": self_layers + cross_layers + emb + 2 * z["d"]}


def full_bytes_per_token(c: dict) -> int:
    """K and V of one token in the ONE pool (the full layer's)."""
    z = sizes(c)
    return 2 * z["full"] * z["kv_heads"] * z["hd"] * BF16


def window_bytes_per_token(c: dict) -> int:
    """K and V of one token over the window layers."""
    z = sizes(c)
    return 2 * z["window"] * z["kv_heads"] * z["hd"] * BF16


def state_bytes_per_stream(c: dict) -> int:
    z = sizes(c)
    return z["mamba"] * (z["n"] * z["inner"] * F32
                         + (z["conv_k"] - 1) * z["inner"] * BF16)


def stores(c: dict, tokens_per_stream: int) -> dict:
    """Bytes the three stores hold for ``MAX_STREAMS`` streams of
    ``tokens_per_stream`` tokens: the pool, the window rings as served
    (``window_ring`` keys a stream a layer), the state rows."""
    z = sizes(c)
    return {"pool": z["rows"] * tokens_per_stream * full_bytes_per_token(c),
            "window_store": z["rows"] * z["ring"] * window_bytes_per_token(c),
            "state": z["rows"] * state_bytes_per_stream(c)}


def _attention(z: dict, layers: int, batch: float, keys: float) -> dict:
    """``layers`` differential-attention reads of one step over ``keys`` live
    keys (all streams together): each key and value ONCE a layer; q in and
    the 40 x 128 context out a stream."""
    kv = 2 * z["kv_heads"] * z["hd"] * BF16
    return {"bytes": layers * (kv * keys
                               + batch * z["heads"] * (z["hd"] + 2 * z["hd"]) * BF16),
            "flops": layers * 2.0 * z["heads"] * (z["hd"] + 2 * z["hd"]) * keys}


def attention_full(c: dict, batch: float, live_tokens: float) -> dict:
    """The full layer AND the cross layers of one step: each reads every live
    key and value of the one pool once — eight reads of the same bytes."""
    z = sizes(c)
    return _attention(z, z["full"] + z["cross"], batch, live_tokens)


def attention_window(c: dict, batch: float, live_tokens: float) -> dict:
    """The window layers of one step: at most ``sliding_window`` keys a
    stream (``live_tokens / batch`` the mean context)."""
    z = sizes(c)
    per = min(live_tokens / batch, z["win"]) if batch else 0.0
    return _attention(z, z["window"], batch, per * batch)


def ssm_step(c: dict, batch: float) -> dict:
    """The one-token state update of one step, all Mamba layers: EVERY state
    row read and written (the step updates them where they lie, under a
    mask); ``live_bytes`` the live rows' alone."""
    z = sizes(c)
    per_row = z["mamba"] * z["n"] * z["inner"]
    return {"bytes": 2.0 * z["rows"] * per_row * F32,
            "live_bytes": 2.0 * batch * per_row * F32, "flops": 0.0,
            "vector_ops": SCAN_VECTOR_OPS * batch * per_row,
            "exponentials": batch * per_row}


def ssm_scan(c: dict, rows: float, tokens: float) -> dict:
    """The selective scan of one window dispatch, all Mamba layers
    (``costs_jamba.ssm_scan``'s count at this model's nine layers)."""
    z = sizes(c)
    ch, n = z["inner"], z["n"]
    per_token = ch * BF16 + ch * F32 + 2 * n * F32 + ch * F32
    elements = z["mamba"] * tokens * ch * n
    return {"bytes": z["mamba"] * (tokens * per_token + rows * 2.0 * n * ch * F32),
            "flops": 0.0, "vector_ops": SCAN_VECTOR_OPS * elements,
            "exponentials": elements}


def gmu(c: dict, tokens: float) -> dict:
    """The Gated Memory Units of one step over ``tokens`` rows: both
    projections' weights once, the rows in and out, the memory read (float32)
    once a unit."""
    z, lp = sizes(c), layer_params(c)
    rows = tokens * (2 * z["d"] + 2 * z["inner"]) * BF16 + tokens * z["inner"] * F32
    return {"bytes": z["gmu"] * (lp["gmu_proj"] * BF16 + rows),
            "flops": 2.0 * z["gmu"] * lp["gmu_proj"] * tokens}


def decode_step(c: dict, batch: float, live_tokens: float) -> dict:
    """One decode step of ``batch`` live streams holding ``live_tokens``
    tokens of context together.  Bytes: every layer's weights and the final
    norm once, the embedding table once more AS THE HEAD (tied: the read is
    not saved) beside one row a stream; each live stream's recurrent state
    and taps read and written; the one pool's keys and values read by EIGHT
    layers and one token written; the window layers' keys and one token
    each."""
    z, p = sizes(c), decoder_params(c)
    head = z["d"] * z["v"]
    weights = (p["layers"] + head + p["final_norm"]) * BF16 + batch * z["d"] * BF16
    state = 2.0 * state_bytes_per_stream(c) * batch
    full = attention_full(c, batch, live_tokens)
    window = attention_window(c, batch, live_tokens)
    kv = (full["bytes"] + window["bytes"]
          + (full_bytes_per_token(c) + window_bytes_per_token(c)) * batch)
    flops = 2.0 * (p["layers"] + head) * batch + full["flops"] + window["flops"]
    step = ssm_step(c, batch)
    return {"bytes": weights + state + kv, "weight_bytes": weights,
            "state_bytes": state, "kv_bytes": kv,
            "full_pool_read_bytes": full["bytes"], "window_read_bytes": window["bytes"],
            "flops": flops, "vector_ops": step["vector_ops"],
            "exponentials": step["exponentials"]}


def prefill_dispatch(c: dict, rows: float, tokens: float, cross_tokens: float = 0.0) -> dict:
    """One prompt dispatch of ``tokens`` positions over ``rows`` prompts, split
    as the architecture allows: ``self`` — layers 0 .. 17 on every position
    (weights once, a multiply-add a weight a position, the rows in and out a
    layer, the scan's bytes; attention's operations over a mean context are
    left out: under 2 % of the matrix work at these shapes) — and ``cross`` —
    the cross-decoder's layers on ``cross_tokens`` positions (a window reads
    no logit: 0), beside ``cross_all``: what running them on EVERY position
    would cost, the program this architecture exists not to be."""
    z, p = sizes(c), decoder_params(c)
    act = 2 * z["d"] * BF16  # a layer's rows in and out

    def part(params, layers, n):
        return {"bytes": params * BF16 + layers * n * act, "flops": 2.0 * params * n}

    self_part = part(p["self_layers"], z["self_layers"], tokens)
    scan = ssm_scan(c, rows, tokens)
    self_part["bytes"] += scan["bytes"]
    n_cross = z["layers"] - z["self_layers"]
    return {"self": self_part,
            "cross": part(p["cross_layers"], n_cross, cross_tokens) if cross_tokens
            else {"bytes": 0.0, "flops": 0.0},
            "cross_all": part(p["cross_layers"], n_cross, tokens)}
