"""Plain reference for the SambaY architecture as Phi-4-mini-flash-reasoning
has it (a self-decoder of Mamba-1 and sliding-window attention layers ending
in ONE full-attention layer, then a cross-decoder of Gated Memory Units and
cross-attention layers that read that one layer's keys and values;
differential attention; LayerNorm; a tied head), and the check that holds the
served path to it.

Forward pass in straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``, written from the keys of the
model's ``config.json`` and the family's model code (``modeling_phi4flash``).
``LN(x; w, b) = (x - mean x) / sqrt(var x + 1e-5) * w + b``.  Every layer
``l`` of 32, pre-norm, two sub-blocks each with its residual:

    x <- x + mix_l(LN_1(x))        x <- x + mlp(LN_2(x))

final ``LN``; logits ``= x E^T`` with ``E`` the embedding (tied, no bias).

  mix_l: l even and l <= 16 -> Mamba (9); l odd and l <= 15 -> window
  attention, sliding_window 512 (8); l = 17 -> full attention (1); l even and
  l >= 18 -> Gated Memory Unit (7); l odd and l >= 19 -> cross-attention (7).
  mlp(u) = W_down (silu(W_gate u) * (W_up u)), 2560 -> 10240 -> 2560, no bias
  (the published W_1 = [gate | up] is the program's two kernels side by side).
  No position embedding and no rotation anywhere: the Mamba layers carry order.

  Mamba (Mamba-1, arXiv:2312.00752, NO inner norm), u = LN_1(x):
    [x | z] = u W_in                                    5120 | 5120, no bias
    x_t   = silu( b_c + sum_{j<4} w_j * x_{t-3+j} )     depthwise, causal (zeros before the sequence)
    [dt | B | C] = x W_x                                160 | 16 | 16, no bias, no norm
    D_t   = softplus( dt W_dt + b_dt )                  [5120], float32
    A     = -exp(A_log)                                 [16, 5120] as stored (a state a row)
    h_t[n, c] = exp(D_t[c] A[n, c]) h_{t-1}[n, c] + D_t[c] B_t[n] x_t[c]     float32, h_-1 = 0
    y_t[c]    = sum_n h_t[n, c] C_t[n] + D[c] x_t[c]
    mix   = (y * silu(z)) W_out                         5120 -> 2560, no bias
  Layer 16 (the last Mamba layer) also hands on  m = y : the scan's output
  with the D skip, BEFORE the gate, a position.

  Gated Memory Unit, u = LN_1(x):   mix = ( silu(u W_in^g) * m ) W_out^g,
    2560 -> 5120 -> 2560, no bias, m layer 16's at the same position.

  Differential attention (window, full, cross), u = LN_1(x):
    q = u W_q + b_q (40 heads of 64)   k, v = u W_k + b_k, u W_v + b_v (20 of 64)
    a cross layer has q alone and takes k, v from layer 17.
    heads pair up adjacent: (q1_j, q2_j) = (q_2j, q_2j+1), j < 20; (k1_g, k2_g)
    and (v1_g, v2_g) likewise, g < 10; query pair j reads KV pair g = j // 2.
    A(q, k) = softmax(q k^T / 8) under the causal mask (window layers: the
    query itself and the 511 keys before it).
    o_j = A(q1_j, k1_g) [v1_g | v2_g] - lambda A(q2_j, k2_g) [v1_g | v2_g]     128 wide
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
    lambda_init = 0.8 - 0.6 exp(-0.3 l)
    o_j <- RMSNorm_128(o_j; w_sub, eps 1e-5) * (1 - lambda_init)
    mix = concat_j(o_j) W_o + b_o

**Departures from the published code, each for a reason.**  The family's
``flash_attn`` is called four times a layer ((q1,k1,v1), (q1,k1,v2),
(q2,k2,v1), (q2,k2,v2)) and the halves concatenated: here the same four
products as explicit score matrices, a block of queries at a time (a
4400 x 4400 x 20 float32 score tensor is 1.5 GB).  The recurrence is a scan
over tokens, one at a time, from a zero state — not the published CUDA
selective scan, not the program's chunked loop: the three must agree.  No
cache, no kernel, no table, no batching: one sequence at a time, every layer
at EVERY position — which is what proves the served path's split (a prompt
window runs the self-decoder alone; the cross-decoder runs where a logit is
read) exact and not an approximation.

Assumed (the configuration file lists each with its reason): the layer order
(the model code's rule from ``mb_per_layer`` 2 and ``num_hidden_layers`` 32);
the pairing by adjacency; the ``lambda_init`` formula; the Mamba sizes
(``d_state`` 16, ``d_conv`` 4, ``expand`` 2, ``dt_rank`` 160: the family's
defaults, ``config.json`` has no key); the window's inclusive edge; that cross
layers keep their own ``lambda`` and sub-norm; head_dim 64 = 2560 / 40;
float32 state.  Weights are the service's seeded random init read leaf by
leaf.

The check (``jamba.py``'s, for this block): ``N_PROMPTS`` seeded prompts of
2200-4200 tokens are served greedily AT ONCE through the normal HTTP stream
path — a boundary's dispatch holds ``PREFILL_CHUNK`` windows of DIFFERENT
prompts, each continuing its own state row and its own window rings; the
prompt-window kernel over the differential pairs with the window's band, then
decode through the one-token state update, the rings and the ONE paged pool
that eight layers read — and then one more ALONE with an answer of
``check_state_tokens`` tokens.  Each served sequence is teacher-forced through
this reference, and every served token's REFERENCE logit must lie within
``MARGIN`` of the reference's top logit at that position, ``TOP1_SHARE`` of
them its argmax.  Beside the tokens:

- the program's own logits (``bundle.logits_fn``, its prefill-wave forward,
  every layer at every position) on the first ``logit_check_tokens`` tokens of
  the first sequence must lie within ``LOGIT_RMS`` (rms) of the reference's;
- the recurrent STATE the loop holds for the lone stream when it has ended
  (``served_state_error``) must lie within ``STATE_SLOW_REL`` (relative rms
  over a layer's SLOW state elements, the worst of the first ``STATE_LAYERS``
  Mamba layers) of the state this reference's token scan reaches on the same
  tokens: what shows a state kept in less than the float32 the configuration
  states.
"""

from __future__ import annotations

import json
import math
import random

# Reference logits have a standard deviation of about 1.0 here.  Each limit
# lies between chip readings at the published widths (my chip runs, PR 63;
# PERF.md section 4 has the table): the served path's, and the same program
# with one rule of the block broken (tools/phi4flash_variants.py: the
# program's own prefill-wave forward — every layer at every position — on one
# seeded sequence of 2560 tokens; margin and top-1 over its last 64
# positions).  The weights are PRNGKey(0)'s and the prompts CHECK_SEED's, so
# the wave forward's reading repeats to the last digit from run to run; the
# SERVED tokens move with the kernel variant the tuner picked at that boot
# (two boots read margin 0.118 / 0.143 and top-1 92.8 / 90.1 %).
#
#                                  logit rms   worst margin   top-1
#   served path (check), 2 boots    0.040067    0.118 0.143    92.8 90.1 %  (of 304)
#     (its rms on the check's first 2048 / 1024 tokens: 0.040156 / 0.040860;
#      the configuration's logit_check_tokens is 1024 since the check's
#      full-logit forward peaked at 16.26 GB of the chip's memory)
#   sound, prefill wave             0.039871    0.097          85.9 %
#   scores and softmax in bf16      0.040535    0.051          85.9 %   NOT separable: see below
#   state stored in bf16            as sound: judged on the state, see below
#   cross reads a WINDOW's keys     0.141568    0.317          67.2 %
#   m taken after the gate          0.252037    0.790          43.8 %
#   lambda's learned part dropped   0.581050    1.723          23.4 %
#   no lambda (no subtraction)      0.699854    2.153           7.8 %
#   rotated q and k                 0.811928    2.667           7.8 %
#   Jamba's inner Mamba norms       0.917571    5.740           1.6 %
#   no sub-norm                     1.061015    4.972           0.0 %
#   float8_e4m3 weights             1.328207    6.156           0.0 %
#   D dropped                       1.386276    6.759           0.0 %
#
# Every broken rule but the two dtype rows fails the rms limit, most all
# three.  **bfloat16 scores are not separable here**: 32 layers of bfloat16
# activations read 0.0399 and the same with bfloat16 scores 0.0405 — 1.7 %,
# less than a re-ordering of sums moves a margin; a limit between the two would
# have no room on either side, so none is set.  What holds the scores' dtype
# is tier-1's CPU test of both kernels over the lane-placed pairs against the
# four plain attentions at 2e-6 (tests/test_phi4flash_block.py: float32
# operands, where bfloat16 scores miss by three orders).
# Worst margin: far from both readings, since it moves with the ORDER of sums
# (0.118 / 0.143 on two boots): the geometric middle of the largest sound
# reading and lambda's dropped learned part (the nearest rule whose margin
# alone would have to speak), sqrt(0.143 x 1.723).
MARGIN = 0.5
# Share of served tokens that must BE the reference's argmax (sound 85.9 -
# 92.8 %; a cross layer on a window's keys 67.2 %, the next 43.8 %).
TOP1_SHARE = 0.75
# rms of (program - reference) logits over the logit check's positions: the
# geometric middle of the served 0.0401 and the nearest broken rule's 0.1416
# (a cross layer reading a window layer's keys).
LOGIT_RMS = 0.0753
# Relative rms of (the loop's state row - the reference's state) after the
# lone stream's prompt and answer, over a Mamba layer's SLOW state elements,
# the worst of the FIRST ``STATE_LAYERS`` Mamba layers (``jamba.py`` has the
# reasoning: a slow element keeps every rounding of its own storage and
# averages its inputs' away; the first layers' residual stream lies nearest
# the reference's).  Sound, two boots: 0.04 0.13-0.15 0.67-0.68 0.46-0.56 %
# in the first four layers and 0.45-0.94 % in the other five; stored in
# bfloat16 (``tools/phi4flash_variants.py --served state_bf16``: a rounding a
# decode step, 240 of them) 3.89 3.89 3.17 3.23 % there and 2.5-3.9 % below.
# The geometric middle of the largest sound reading and the least bfloat16
# one among the first four: sqrt(0.0068 x 0.0317).  Every layer's reading is
# reported.
STATE_SLOW_REL = 0.0147
STATE_LAYERS = 4
SLOW_LOG_KEEP = -2.0
N_PROMPTS = 4  # served at once: PREFILL_BUDGET / PREFILL_CHUNK + 1
SERVE_TOKENS = 16
QUERY_BLOCK = 128  # queries a block of the attention holds scores for
HEAD_CHUNKS = 8  # the head is applied (and upcast) a slice of the vocabulary at a time


def layer_kinds(config: dict) -> list[str]:
    """The family's rule (``mb_per_layer`` 2): the first half and one more
    layer alternate Mamba and attention, the last of those attention layers
    full, the others windowed; behind it Gated Memory Units and
    cross-attention alternate."""
    n = int(config["num_hidden_layers"])
    half = n // 2
    kinds = []
    for li in range(n):
        if li % 2 == 0:
            kinds.append("mamba" if li <= half else "gmu")
        elif li < half + 1:
            kinds.append("window")
        else:
            kinds.append("full" if li == half + 1 else "cross")
    return kinds


def hyper(config: dict) -> dict:
    """The sizes the forward pass needs, by their published names (the Mamba
    sizes by the configuration file's ``assumed`` keys)."""
    hidden = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    kinds = list(config.get("layers_block_type") or layer_kinds(config))
    return {
        "kinds": kinds,
        "hidden": hidden, "heads": heads,
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": hidden // heads,
        "window": int(config["sliding_window"]),
        "inner": int(config["mamba_d_inner"]),
        "state": int(config["mamba_d_state"]),
        "conv": int(config["mamba_d_conv"]),
        "dt_rank": int(config["mamba_dt_rank"]),
        "eps": float(config["layer_norm_eps"]),
        "memory_layer": max(i for i, k in enumerate(kinds) if k == "mamba"),
        "kv_layer": kinds.index("full"),
    }


def lambda_init(depth: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


def _layernorm(x, scale, bias, eps):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _rmsnorm(x, scale, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * scale


def attention(q, k, v, scale: float, window: int = 0):
    """softmax(q k^T * scale) v on q [S, H, D], k [S, H, D], v [S, H, Dv]
    under the causal mask (``window``: the query itself and the ``window -
    1`` keys before it); a block of queries at a time against every key."""
    import jax
    import jax.numpy as jnp

    s, h, _ = q.shape
    n_blocks = -(-s // QUERY_BLOCK)
    qp = jnp.pad(q, ((0, n_blocks * QUERY_BLOCK - s), (0, 0), (0, 0)))
    kpos = jnp.arange(s)

    def block(i):
        qs = jax.lax.dynamic_slice_in_dim(qp, i * QUERY_BLOCK, QUERY_BLOCK, axis=0)
        qpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        allowed = kpos[None, :] <= qpos[:, None]
        if window:
            allowed &= qpos[:, None] - kpos[None, :] < window
        scores = jnp.einsum("qhd,khd->hqk", qs, k) * scale
        scores = jnp.where(allowed[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(block, jnp.arange(n_blocks))  # [n, qb, H, Dv]
    return out.reshape(n_blocks * QUERY_BLOCK, h, v.shape[-1])[:s]


def mamba(u, w: dict, hp: dict, tail: int = 0):
    """The Mamba-1 mixer on u [S, D] (normed), the recurrence one token at a
    time from a zero state.  -> (out [S, D], y [S, C] the scan's output with
    the D skip BEFORE the gate, the state h [N, C] after the last row, the sum
    of the steps ``D_t`` [C] over the last ``tail`` rows)."""
    import jax
    import jax.numpy as jnp

    s = u.shape[0]
    ch, n, k, r = hp["inner"], hp["state"], hp["conv"], hp["dt_rank"]
    xz = u @ w["in"]
    x, z = xz[:, :ch], xz[:, ch:]
    padded = jnp.concatenate([jnp.zeros((k - 1, ch)), x], axis=0)
    x = jax.nn.silu(w["conv_b"] + sum(
        w["conv_w"][j] * padded[j:j + s] for j in range(k)))
    dbc = x @ w["x_proj"]
    dt, bm, cm = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]  # no norm
    delta = jax.nn.softplus(dt @ w["dt_proj"] + w["dt_bias"])  # [S, C]
    a = -jnp.exp(w["A_log"])  # [N, C]

    def step(h, t):
        x_t, b_t, c_t, d_t = t
        h = jnp.exp(d_t[None, :] * a) * h + (d_t * x_t)[None, :] * b_t[:, None]
        return h, jnp.sum(h * c_t[:, None], axis=0) + w["D"] * x_t

    last, y = jax.lax.scan(step, jnp.zeros((n, ch)), (x, bm, cm, delta))
    kept = jnp.sum(delta[s - tail:], axis=0) if tail else jnp.zeros((ch,))
    return (y * jax.nn.silu(z)) @ w["out"], y, last, kept


def diff_attention(u, w: dict, hp: dict, lam0, window: int, kv=None):
    """Differential attention on u [S, D]: -> (out [S, D], (k, v) [S, KVH, D]
    of this layer); ``lam0`` the layer's ``lambda_init``.  ``kv`` given (a
    cross layer): q alone is projected, keys and values are the ones handed
    in."""
    import jax.numpy as jnp

    s = u.shape[0]
    h, kvh, d = hp["heads"], hp["kv_heads"], hp["head_dim"]
    q = (u @ w["q"] + w["q_b"]).reshape(s, h // 2, 2, d)
    if kv is None:
        kv = ((u @ w["k"] + w["k_b"]).reshape(s, kvh, d),
              (u @ w["v"] + w["v_b"]).reshape(s, kvh, d))
    k, v = (x.reshape(s, kvh // 2, 2, d) for x in kv)
    rep = (h // 2) // (kvh // 2)  # query pairs a KV pair

    def each(x):  # [S, KVH/2, D] -> a row a query pair
        return jnp.repeat(x, rep, axis=1)

    q1, q2 = q[:, :, 0], q[:, :, 1]
    k1, k2, v1, v2 = each(k[:, :, 0]), each(k[:, :, 1]), each(v[:, :, 0]), each(v[:, :, 1])
    scale = d ** -0.5
    # the family's four calls: (q1,k1,v1) (q1,k1,v2) (q2,k2,v1) (q2,k2,v2)
    a1 = jnp.concatenate([attention(q1, k1, v1, scale, window),
                          attention(q1, k1, v2, scale, window)], axis=-1)
    a2 = jnp.concatenate([attention(q2, k2, v1, scale, window),
                          attention(q2, k2, v2, scale, window)], axis=-1)
    lv = w["lambda"]  # [4, D]: lq1, lk1, lq2, lk2
    lam = jnp.exp(jnp.sum(lv[0] * lv[1])) - jnp.exp(jnp.sum(lv[2] * lv[3])) + lam0
    o = _rmsnorm(a1 - lam * a2, w["subln"], hp["eps"]) * (1.0 - lam0)  # [S, H/2, 2D]
    return o.reshape(s, h * d) @ w["o"] + w["o_b"], kv


def mlp(u, w: dict):
    import jax

    return (jax.nn.silu(u @ w["gate"]) * (u @ w["up"])) @ w["down"]


def layer(x, w: dict, hp: dict, kind: str, role: str, lam0, carried: dict,
          tail: int = 0):
    """One layer on x [S, D] (one sequence): its mixer, then its MLP.
    ``carried`` holds what earlier layers of the same pass handed on: ``m``
    (the memory layer's y: the layer whose ``role`` is "memory") and ``kv``
    (the keys and values of the full layer whose ``role`` is "kv").  ->
    (x, carried, what a Mamba layer leaves beside it: (state [N, C] after the
    last row, the steps' sum over the last ``tail`` rows [C]); else None)."""
    u = _layernorm(x, w["ln"], w["ln_b"], hp["eps"])
    left = None
    if kind == "mamba":
        f, y, last, kept = mamba(u, w, hp, tail)
        left = (last, kept)
        if role == "memory":
            carried = {**carried, "m": y}
    elif kind == "gmu":
        import jax

        f = (jax.nn.silu(u @ w["in"]) * carried["m"]) @ w["out"]
    elif kind == "cross":
        f, _ = diff_attention(u, w, hp, lam0, 0, carried["kv"])
    else:
        f, kv = diff_attention(u, w, hp, lam0,
                               hp["window"] if kind == "window" else 0)
        if role == "kv":
            carried = {**carried, "kv": kv}
    x = x + f
    x = x + mlp(_layernorm(x, w["mlp_ln"], w["mlp_ln_b"], hp["eps"]), w)
    return x, carried, left


def layer_weights(p: dict, kind: str) -> dict:
    """One layer of the service's tree upcast to float32."""
    import jax.numpy as jnp

    f = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    out = {"mlp_ln": f(p["mlp_ln"]["scale"]), "mlp_ln_b": f(p["mlp_ln"]["bias"]),
           **{n: f(p["mlp"][n]["kernel"]) for n in ("gate", "up", "down")}}
    if kind == "mamba":
        m = p["ssm"]
        return {**out, "ln": f(p["ssm_ln"]["scale"]), "ln_b": f(p["ssm_ln"]["bias"]),
                "in": f(m["in"]["kernel"]),
                "conv_w": f(m["conv"]["kernel"]), "conv_b": f(m["conv"]["bias"]),
                "x_proj": f(m["x_proj"]["kernel"]),
                "dt_proj": f(m["dt_proj"]["kernel"]), "dt_bias": f(m["dt_proj"]["bias"]),
                "A_log": f(m["A_log"]), "D": f(m["D"]), "out": f(m["out"]["kernel"])}
    if kind == "gmu":
        g = p["gmu"]
        return {**out, "ln": f(p["gmu_ln"]["scale"]), "ln_b": f(p["gmu_ln"]["bias"]),
                "in": f(g["in"]["kernel"]), "out": f(g["out"]["kernel"])}
    a = p["attn"]
    names = ("q", "o") if kind == "cross" else ("q", "k", "v", "o")
    return {**out, "ln": f(p["attn_ln"]["scale"]), "ln_b": f(p["attn_ln"]["bias"]),
            **{n: f(a[n]["kernel"]) for n in names},
            **{n + "_b": f(a[n]["bias"]) for n in names},
            "lambda": f(a["lambda"]), "subln": f(a["subln"]["scale"])}


def hidden(params: dict, hp: dict, ids, states: list | None = None,
           tail: int = 0):
    """ids [B, S] int32 -> the final-normed hidden states [B, S, D],
    float32, one sequence at a time, every layer at every position.  A list
    given as ``states`` receives each MAMBA layer's (state [B, N, C] after
    ALL S tokens, so no padding; the steps' sum over the last ``tail`` tokens
    [B, C])."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    # lambda_init rides as a number, so a kind compiles once, not a layer
    step = jax.jit(
        lambda x, w, carried, lam0, kind, role: layer(
            x, w, hp, kind, role, lam0, carried, tail),
        static_argnums=(4, 5))
    roles = {hp["memory_layer"]: "memory", hp["kv_layer"]: "kv"}
    ids = np.asarray(ids)
    kept: dict[int, list] = {}
    with jax.default_matmul_precision("highest"):
        xs = [jnp.take(jnp.asarray(params["embed"]["embedding"]), row, axis=0)
              .astype(jnp.float32) for row in ids]
        carried = [{} for _ in xs]
        for li, (p, kind) in enumerate(zip(params["layers"], hp["kinds"])):
            w = layer_weights(p, kind)
            for b in range(len(xs)):
                xs[b], carried[b], left = step(
                    xs[b], w, carried[b], jnp.float32(lambda_init(li)), kind,
                    roles.get(li, ""))
                if states is not None and kind == "mamba":
                    kept.setdefault(li, []).append(jax.tree.map(np.asarray, left))
            del w
        scale = jnp.asarray(params["final_ln"]["scale"], jnp.float32)
        bias = jnp.asarray(params["final_ln"]["bias"], jnp.float32)
        out = jnp.stack([_layernorm(x, scale, bias, hp["eps"]) for x in xs])
    if states is not None:
        states.extend(tuple(np.stack(part) for part in zip(*v))
                      for _, v in sorted(kept.items()))
    return out


def _table_slices(params: dict):
    """The tied head: the embedding table [V, D], a slice of the vocabulary
    at a time, upcast to float32."""
    import jax.numpy as jnp

    table = params["embed"]["embedding"]
    step = -(-table.shape[0] // HEAD_CHUNKS)
    for c in range(0, table.shape[0], step):
        yield c, c + step, jnp.asarray(table[c: c + step], jnp.float32)


def head_logits(params: dict, x):
    """x [..., D] final-normed rows -> float32 logits [..., V] = x E^T."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        parts = [x @ e.T for _, _, e in _table_slices(params)]
    return jnp.concatenate(parts, axis=-1)


def logits(params: dict, hp: dict, ids):
    """ids [B, S] int32 -> float32 logits [B, S, V]."""
    return head_logits(params, hidden(params, hp, ids))


def compare(ref_rows, served: list[list[int]]) -> dict:
    """Margins of the served tokens under teacher-forced reference
    logits: ``ref_rows[b][j]`` [V] is the reference's row at the position
    that predicts served token j of sequence b."""
    import numpy as np

    margins, top1 = [], 0
    for rows, toks in zip(ref_rows, served):
        for row, tok in zip(np.asarray(rows), toks):
            margins.append(float(row.max() - row[tok]))
            top1 += int(int(row.argmax()) == tok)
    total = max(len(margins), 1)
    worst = max(margins) if margins else float("inf")
    return {
        "tokens": len(margins), "worst_margin": worst,
        "mean_margin": sum(margins) / total, "top1_share": top1 / total,
        "margin_limit": MARGIN, "top1_limit": TOP1_SHARE,
        "correct": bool(margins) and worst <= MARGIN
        and top1 / total >= TOP1_SHARE,
    }


def logit_rms_error(params: dict, ref_hidden, got_logits) -> float:
    """Root mean square of (program - reference) over logits [N, V]: the
    reference's rows are ``ref_hidden`` [N, D] through the tied head, a
    slice of the vocabulary at a time."""
    import jax
    import jax.numpy as jnp

    sq, v = 0.0, params["embed"]["embedding"].shape[0]
    with jax.default_matmul_precision("highest"):
        for lo, hi, e in _table_slices(params):
            diff = jnp.asarray(got_logits[:, lo:hi], jnp.float32) - ref_hidden @ e.T
            sq += float(jnp.sum(diff * diff))
    return (sq / (ref_hidden.shape[0] * v)) ** 0.5


async def _serve(svc, text: str, max_tokens: int) -> list[int]:
    """One greedy stream over HTTP -> its token ids (RuntimeError: the status)."""
    toks: list[int] = []
    async with svc.http.post("/predict", json={
            "text": text, "stream": True, "max_tokens": max_tokens}) as r:
        if r.status != 200:
            raise RuntimeError(f"HTTP {r.status}")
        async for line in r.content:
            msg = json.loads(line) if line.strip() else {}
            toks += [int(w[1:]) for w in msg.get("delta", "").split()
                     if w[1:].isdigit()]
    return toks


async def served_state_error(svc, want: list, kept: list) -> dict:
    """The recurrent state the LOOP holds for the stream that just ended
    against ``want`` (a Mamba layer each, [N, C]: the reference's state after
    the same tokens); ``kept`` [N, C] a layer: the log of what an element
    keeps over the answer's decode steps.  Per layer the relative rms distance
    of the nearest of the loop's state rows — a stream's row is the host's to
    choose, so the nearest is taken and every layer must name the same one —
    over the whole state and over the SLOW elements alone (``SLOW_LOG_KEEP``).
    Read once nothing is admitted or in flight."""
    import asyncio

    import jax
    import jax.numpy as jnp
    import numpy as np

    loop = svc.batcher._cdl
    while not loop.idle():
        await asyncio.sleep(0.01)

    @jax.jit
    def distance(rows, one, slow):  # [R, N, C], [N, C], [N, C] bool
        sq = jnp.square(rows - one[None])
        return (jnp.sum(sq, axis=(1, 2)), jnp.sum(sq * slow[None], axis=(1, 2)),
                jnp.sum(jnp.square(one)), jnp.sum(jnp.square(one) * slow))

    out = {"state_rel_err": [], "state_slow_rel_err": [],
           "state_slow_elements": [], "state_row": []}
    for have, one, keep in zip(loop._state.ssm.state, want, kept):
        slow = np.asarray(keep) >= SLOW_LOG_KEEP
        d, ds, w, ws = (np.asarray(x, np.float64) for x in distance(
            have, jnp.asarray(one), jnp.asarray(slow)))
        row = int(np.argmin(d))
        out["state_row"].append(row)
        out["state_rel_err"].append(float(np.sqrt(d[row] / w)))
        out["state_slow_elements"].append(int(slow.sum()))
        # a layer with no slow element reads as far off as a wrong row: the
        # limit must not pass a layer it cannot see
        out["state_slow_rel_err"].append(
            float(np.sqrt(ds[row] / ws)) if slow.any() and ws > 0 else 1.0)
    return out


async def check(svc, config: dict, seed: int) -> dict:
    """Serve seeded prompts through the normal path and hold them to
    the reference.  ``svc`` is the harness's running service."""
    import asyncio

    import jax
    import numpy as np

    trail = {}

    def peak(stage: str) -> None:  # the high-water mark is monotonic
        stats = jax.devices()[0].memory_stats() or {}
        trail[stage] = stats.get("peak_bytes_in_use")

    rng = random.Random(seed)
    vocab = int(config["vocab_size"])
    # N_PROMPTS at once, then one alone with a long answer (the last).
    lens = [rng.randrange(*config["check_prompt_tokens"])
            for _ in range(N_PROMPTS + 1)]
    state_tokens = int(config["check_state_tokens"])
    chunk = int(svc.cfg.stream_chunk_tokens)
    if state_tokens % chunk:
        # the loop runs whole chunks: past the answer the state would have
        # absorbed tokens no one was sent
        raise RuntimeError(f"check_state_tokens {state_tokens}: not a multiple "
                           f"of the {chunk}-token decode chunk")
    peak("before")
    texts = [" ".join(f"w{rng.randrange(3, vocab)}" for _ in range(n))
             for n in lens]
    try:
        served = list(await asyncio.gather(
            *(_serve(svc, t, SERVE_TOKENS) for t in texts[:-1])))
        served.append(await _serve(svc, texts[-1], state_tokens))
    except RuntimeError as e:
        return {"correct": False, "error": str(e)}
    prompts = []
    for text in texts:
        ids, mask = svc.bundle.tokenizer.encode(text, 8192)
        prompts.append([int(t) for t in ids[: int(mask.sum())]])
    hp = hyper(config)
    params = svc.engine.params
    peak("served")
    if any(len(s) == 0 for s in served) or len(served[-1]) != state_tokens:
        return {"correct": False, "error": "a stream came back short",
                "served_tokens": [len(s) for s in served]}
    # The lone stream's state has absorbed its prompt and every served
    # token but the last (which no step was fed): the reference scans
    # exactly those, unpadded, and its rows predict all the served tokens.
    alone = np.asarray([prompts[-1] + served[-1][:-1]], np.int32)
    want_states: list = []
    ref_alone = hidden(params, hp, alone, states=want_states, tail=state_tokens)
    a_rows = [-np.exp(np.asarray(params["layers"][li]["ssm"]["A_log"], np.float32))
              for li, kind in enumerate(hp["kinds"]) if kind == "mamba"]
    state = await served_state_error(
        svc, [s[0] for s, _ in want_states],
        [a * k[0][None, :] for a, (_, k) in zip(a_rows, want_states)])
    del want_states
    width = max(len(p) + len(s) for p, s in zip(prompts[:-1], served))
    batch = np.zeros((N_PROMPTS, width), np.int32)  # right pad: causal, so inert
    for b, (p, s) in enumerate(zip(prompts, served[:-1])):
        batch[b, : len(p) + len(s)] = p + s
    ref_hidden = hidden(params, hp, batch)
    jax.block_until_ready(ref_hidden)
    peak("reference")
    # position p_len - 1 + j predicts served token j
    ref_rows = [head_logits(params, h[len(p) - 1: len(p) - 1 + len(s)])
                for h, p, s in zip([*ref_hidden, ref_alone[0]], prompts, served)]
    out = compare(ref_rows, served)
    out["prompt_tokens"] = [len(p) for p in prompts]
    out["served_tokens"] = [len(s) for s in served]
    out.update(state)
    out["state_slow_limit"] = STATE_SLOW_REL
    out["state_slow_layers"] = STATE_LAYERS
    out["correct"] = (
        out["correct"]
        and max(state["state_slow_rel_err"][:STATE_LAYERS]) <= STATE_SLOW_REL
        and len(set(state["state_row"])) == 1)
    del ref_alone
    # The program's own logits (its prefill-wave forward, every layer at
    # every position) on the head of the first sequence.
    n = min(int(config.get("logit_check_tokens", width)),
            len(prompts[0]) + len(served[0]))
    got = jax.jit(lambda p, i, m: svc.bundle.logits_fn(p, i, m)[0])(
        params, batch[:1, :n], np.ones((1, n), np.int32))
    jax.block_until_ready(got)
    peak("program_logits")
    out["logit_check_tokens"] = n
    out["logit_rms_err"] = logit_rms_error(params, ref_hidden[0, :n], got)
    peak("logit_rms")
    out["logit_rms_limit"] = LOGIT_RMS
    out["correct"] = out["correct"] and out["logit_rms_err"] <= LOGIT_RMS
    out["memory_peak_bytes_after"] = trail
    return out
