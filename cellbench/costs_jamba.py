"""Operations and bytes of a Jamba-style decoder — every layer a mixer
(Mamba-1, or attention where ``l % attn_layer_period == attn_layer_offset``)
THEN a dense SiLU-gated MLP, the head tied to the embedding — computed from
the configuration file's published sizes and the mathematics, never from the
program's own counters or its implementation.

A stream's state is two things: the attention layers cache keys and values
a token (``kv_bytes_per_token``: 2 layers x (K + V) x ONE KV head x 128 x 2 B
= 1 KB), and every Mamba layer holds a fixed ``[d_state, d_inner]`` float32
state plus ``d_conv - 1`` taps of the inner width
(``state_bytes_per_stream``: 26 x 358 400 B = 9.32 MB), read AND written by
every decode step.

The selective scan has no matmul form (a decay for every channel AND state),
so its work is vector operations and exponentials, for which
``cellbench/peaks.json`` has no peak: ``ssm_scan`` / ``ssm_step`` give
``flops`` 0 (their rooflines bind on bytes) and carry ``vector_ops`` and
``exponentials`` beside it, for the benchmark issue that adds the peak they
divide by (PERF.md section 7)."""

from __future__ import annotations

from cellbench.costs import BF16

F32 = 4
#: Vector operations a state element a token: ``Delta A`` (1), the decay
#: times the old state (1), ``Delta x`` times ``B`` (1, ``Delta x`` itself
#: is once a channel), their sum (1), times ``C`` and into ``y`` (2), and the
#: ``D x`` / gate share rounded up (1); the exponential is counted apart.
SCAN_VECTOR_OPS = 7


def sizes(c: dict) -> dict:
    layers = int(c["num_hidden_layers"])
    period, offset = int(c["attn_layer_period"]), int(c["attn_layer_offset"])
    a_layers = sum(1 for li in range(layers) if li % period == offset)
    d, heads = int(c["hidden_size"]), int(c["num_attention_heads"])
    return {"d": d, "layers": layers, "a_layers": a_layers,
            "m_layers": layers - a_layers, "heads": heads,
            "kv_heads": int(c["num_key_value_heads"]), "hd": d // heads,
            "inner": int(c["mamba_expand"]) * d, "n": int(c["mamba_d_state"]),
            "conv_k": int(c["mamba_d_conv"]), "r": int(c["mamba_dt_rank"]),
            "ff": int(c["intermediate_size"]), "v": int(c["vocab_size"]),
            "rows": int(c["env"]["MAX_STREAMS"])}


def layer_params(c: dict) -> dict:
    """A mixer of each kind and the MLP (each with its pre-norm)."""
    z = sizes(c)
    d, ch, n, r = z["d"], z["inner"], z["n"], z["r"]
    mamba_proj = d * 2 * ch + ch * (r + 2 * n) + r * ch + ch * d  # in, x, dt, out
    mamba = (mamba_proj + z["conv_k"] * ch + ch  # taps and bias
             + ch  # dt_proj's bias
             + n * ch + ch  # A_log, D
             + r + 2 * n  # the three inner norms
             + d)  # the pre-norm
    attention = 2 * d * z["heads"] * z["hd"] + 2 * d * z["kv_heads"] * z["hd"] + d
    mlp = 3 * d * z["ff"] + d
    return {"mamba_mixer": mamba, "mamba_proj": mamba_proj,
            "attention_mixer": attention, "mlp": mlp,
            "mamba_layer": mamba + mlp, "attention_layer": attention + mlp}


def decoder_params(c: dict) -> dict:
    z, lp = sizes(c), layer_params(c)
    layers = z["m_layers"] * lp["mamba_layer"] + z["a_layers"] * lp["attention_layer"]
    head = 0 if c.get("tie_word_embeddings") else z["d"] * z["v"]
    return {"layers": layers, "embedding": z["d"] * z["v"], "head": head,
            "final_norm": z["d"],
            "total": layers + z["d"] * z["v"] + head + z["d"]}


def kv_bytes_per_token(c: dict) -> int:
    """K and V of one token over the ATTENTION layers."""
    z = sizes(c)
    return 2 * z["a_layers"] * z["kv_heads"] * z["hd"] * BF16


def state_bytes_per_stream(c: dict) -> int:
    """A stream's recurrent state over the Mamba layers: the float32 state
    and the convolution's taps (bf16)."""
    z = sizes(c)
    return z["m_layers"] * (z["n"] * z["inner"] * F32
                            + (z["conv_k"] - 1) * z["inner"] * BF16)


def ssm_step(c: dict, batch: float) -> dict:
    """The one-token state update of one step, all Mamba layers.  Bytes:
    EVERY state row (``MAX_STREAMS``: the step updates them where they lie,
    under a mask) read once and written once; ``live_bytes`` the ``batch``
    live rows' alone, the ceiling a step over live rows would set.  Work,
    the live rows': ``SCAN_VECTOR_OPS`` vector operations and one
    exponential a state element."""
    z = sizes(c)
    per_row = z["m_layers"] * z["n"] * z["inner"]
    return {"bytes": 2.0 * z["rows"] * per_row * F32,
            "live_bytes": 2.0 * batch * per_row * F32, "flops": 0.0,
            "vector_ops": SCAN_VECTOR_OPS * batch * per_row,
            "exponentials": batch * per_row}


def ssm_scan(c: dict, rows: float, tokens: float) -> dict:
    """The selective scan of one window dispatch, all Mamba layers:
    ``tokens`` positions over ``rows`` prompts — what ANY implementation
    must move and do.  Bytes: x (bf16), ``Delta`` (float32), B and C
    (float32) read and y (float32) written a position, each row's state in
    and out once.  Work: ``SCAN_VECTOR_OPS`` vector operations and one
    exponential a state element a position (no matmul: ``flops`` 0)."""
    z = sizes(c)
    ch, n = z["inner"], z["n"]
    per_token = ch * BF16 + ch * F32 + 2 * n * F32 + ch * F32
    elements = z["m_layers"] * tokens * ch * n
    return {"bytes": z["m_layers"] * (tokens * per_token + rows * 2.0 * n * ch * F32),
            "flops": 0.0, "vector_ops": SCAN_VECTOR_OPS * elements,
            "exponentials": elements}


def mamba_projections(c: dict, tokens: float) -> dict:
    """``W_in``, ``W_x``, ``W_dt`` and ``W_out`` of every Mamba layer over
    ``tokens`` rows (a step's live streams, or a dispatch's positions): the
    weights once, the rows in and out (bf16), a multiply-add a weight a row."""
    z, lp = sizes(c), layer_params(c)
    rows = tokens * (2 * z["d"] + 4 * z["inner"] + 2 * (z["r"] + 2 * z["n"]))
    return {"bytes": z["m_layers"] * (lp["mamba_proj"] + rows) * BF16,
            "flops": 2.0 * z["m_layers"] * lp["mamba_proj"] * tokens}


def mlp(c: dict, tokens: float) -> dict:
    """The dense MLP of every layer over ``tokens`` rows."""
    z = sizes(c)
    per_layer = 3 * z["d"] * z["ff"]
    rows = tokens * (2 * z["d"] + 3 * z["ff"])
    return {"bytes": z["layers"] * (per_layer + rows) * BF16,
            "flops": 2.0 * z["layers"] * per_layer * tokens}


def attention_kernel(c: dict, batch: float, live_tokens: float) -> dict:
    """The paged decode kernel of one step over the attention layers: each
    live key and value read once (ONE KV head: 512 B a token a layer), q in
    and the context out a stream at 20 heads."""
    z = sizes(c)
    return {"bytes": kv_bytes_per_token(c) * live_tokens
            + z["a_layers"] * 2.0 * batch * z["heads"] * z["hd"] * BF16,
            "flops": 4.0 * z["a_layers"] * z["heads"] * z["hd"] * live_tokens}


def decode_step(c: dict, batch: float, live_tokens: float) -> dict:
    """One decode step of ``batch`` live streams holding ``live_tokens``
    tokens of context together.  Bytes: every layer's weights and the final
    norm cross HBM once, and the embedding table once more AS THE HEAD (it
    is tied: ``decoder_params``' ``head`` is 0, the read is not) beside one
    row a stream; each live stream's recurrent state and taps are read and
    written (the least a step could move: ``ssm_step`` has what this one
    moves), its keys and values read once and one token written."""
    z, p = sizes(c), decoder_params(c)
    head = z["d"] * z["v"]
    weights = (p["layers"] + head + p["final_norm"]) * BF16 + batch * z["d"] * BF16
    state = 2.0 * state_bytes_per_stream(c) * batch
    kernel = attention_kernel(c, batch, live_tokens)
    kv = kernel["bytes"] + kv_bytes_per_token(c) * batch
    flops = 2.0 * (p["layers"] + head) * batch + kernel["flops"]
    step = ssm_step(c, batch)
    return {"bytes": weights + state + kv, "weight_bytes": weights,
            "state_bytes": state, "kv_bytes": kv, "flops": flops,
            "vector_ops": step["vector_ops"], "exponentials": step["exponentials"]}
