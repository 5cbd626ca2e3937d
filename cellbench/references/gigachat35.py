"""Plain reference for the GigaChat3.5 architecture as
ai-sage/GigaChat3.5-432B-A28B has it (three Gated-DeltaNet linear-attention
layers to one gated latent-attention layer, each followed by a dense or an
expert FFN, sandwich norms), and the check that holds the served path to it.

Forward pass in straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``, written from the keys of the
model's ``config.json``.  ``x`` the residual stream, ``u`` a normed input.

  N(x; w) = x / sqrt(mean(x^2) + 1e-6) * 2 sigmoid(w)      norm_type ZeroCenteredGatedNorm,
                                                           layernorm_gating_weight 2  (ASSUMED: the
                                                           other reading is 1 + w; w = 0 gives 1 in both)
  every layer (layernorm_type pre_post):
    x <- x + N_post( mix_l( N_pre(x) ) )                   mix_l: DeltaNet, or latent attention on
    x <- x + N_post'( ffn_l( N_pre'(x) ) )                 the layers in full_attention_layers
  logits = N(x_L) W_head                                   untied

  Gated DeltaNet (arXiv:2412.06464; 32 key heads, 64 value heads of 128, conv 4):
    [q | k | v | z] = u W_qkvz          4096 | 4096 | 8192 | 8192        [b | a] = u W_ba     64 | 64
    [q | k | v]_t = silu( sum_{j<4} w_j * [q | k | v]_{t-3+j} )    depthwise, causal, no bias (zeros before the sequence)
    value head h of 64 reads key head h // 2:
    q_t, k_t <- q_t / |q_t|, k_t / |k_t|  (eps 1e-6 under the root)   q_t <- q_t * 128^-1/2
    b_t = sigmoid(b_t)        a_t = exp( -exp(A_log_h) * softplus(a_t + dt_bias_h) )
    S_t = a_t S_{t-1} + b_t ( v_t - a_t S_{t-1} k_t ) k_t^T          S [128, 128] FLOAT32, S_-1 = 0
    o_t = S_t q_t
    y_t = N_128(o_t; 1 + w_o) * 2 sigmoid(z_t)            linear_gating_type gated_rmsnorm_sigmoid_zero_centered,
    mix = merge(y) W_out                 8192 -> 7168      linear_sigmoid_gate_scale 2
  Latent attention (DeepSeek-V2's, at 64 heads): q through a 1536-wide bottleneck with its RMSNorm,
    K and V through ONE 512-wide latent a token with its RMSNorm plus one 64-wide rotary key shared
    by every head; heads of 128 + 64 for scores, 128 for values; YaRN (factor 8 over 32768, theta
    100000), softmax scale 192^-1/2 (0.1 ln 8 + 1)^2; the inner norms' scales are the leaves
    themselves.  gated_attention: ctx <- ctx * sigmoid(u W_g), W_g 7168 -> 64 x 128, elementwise
    over the merged heads, before W_o.
  FFN: layers < first_k_dense_replace dense SwiGLU 18432; the others
    s = sigmoid_f32(u W_r)  [256];  e_1..e_8 = top8(s + b)  (n_group 1: no group limit)
    w_j = 2.5 s[e_j] / sum_j s[e_j]
    f = sum_j w_j Expert_{e_j}(u) + Shared(u)              SwiGLU 2048 each; the shared one unweighted
  every SwiGLU (swiglu_limit 10):  down( silu(min(g, 10)) * clip(up, -10, 10) )

**The recurrence is a scan over tokens**, one token at a time, not the
chunked (UT-transform) form the program runs for windows and waves: the two
must agree.  Attention is always EXPANDED (the served decode step is the
absorbed form).  No cache, no kernel, no sort, no table, no grouped matmul,
no batching: one sequence at a time, a full causal mask a block of queries
at a time, the expert sum a loop over the HELD experts with a plain
per-expert mask, each expert upcast on its own.

**One chip's share** (the configuration's cut, the same in program and
reference): the router is 256 wide and the top-8 runs over all 256; this
chip holds experts ``expert_first .. expert_first + n_routed_experts - 1``
(16); what a token's experts on the other chips would add is left out —
nothing stands in for the absent chips or their exchange.  ``experts`` takes
``held`` / ``first``, so the sixteen shares of a layer can be summed (the
shared expert counted once: ``shared=False`` on the others).  The vocabulary
is the configuration's ``vocab_size`` rows (16 032).

Assumed (the configuration file lists each with its reason): the norm's
scale ``2 sigmoid(w)``; the DeltaNet layout above (the one whose key names
the config uses) with chunk 64 in the program; the rotary pairing
(``rope_interleave`` is a fixed permutation of W_qb's and W_kva's rotary
columns: with seeded weights either convention is the same function;
program and reference rotate halves); the attention gate elementwise, not
a head; sigmoid router scores with a selection bias (``scoring_func`` is not
in the config; with ``routed_scaling_factor`` and ``norm_topk_prob`` it is
the only reading under which both keys act); the clamp's form; float32
state; the two multi-token-prediction modules are not served.  Weights are
the service's seeded random init read leaf by leaf.

The check is Nemotron's (``nemotron_h.check``): ``N_PROMPTS`` seeded prompts
of 2200-4200 tokens served greedily AT ONCE through the normal HTTP stream
path (a boundary's dispatch holds ``PREFILL_CHUNK`` windows of DIFFERENT
prompts, each continuing its own state row; then decode through the
one-token delta rule, the latent pool and the absorbed kernel), then one
more ALONE with an answer of ``check_state_tokens`` tokens.  Served tokens
under teacher-forced reference logits (``MARGIN``, ``TOP1_SHARE``), the
program's own prefill-wave logits (``LOGIT_RMS``), and the state rows the
loop holds for the lone stream against this reference's token scan
(``STATE_FIRST_REL`` on the first DeltaNet layer, ``STATE_SLOW_REL`` on the
worst, over a layer's slow heads).
"""

from __future__ import annotations

import json
import math
import random

# Reference logits have a standard deviation of about 1.0 here.  Each limit
# lies between chip readings at the published widths (my chip runs, PR 47,
# the review round; PERF.md section 4 has the table) of the cell's own check
# on the SERVED path — prompt windows, then the decode step: what the cell's
# traffic runs — with the sound program in place and with one rule of the
# block broken (``tools/gigachat_variants.py --served NAME``).  The wave
# column is the program's own prefill-wave forward on one seeded sequence of
# 2560 tokens (margin and top-1 over its last 64 positions): a path the
# traffic never takes, read for the variants that were not served.  The
# weights are PRNGKey(0)'s and the prompts CHECK_SEED's, so a reading
# repeats from run to run to three digits; it moves when the program's
# arithmetic does.
#
#                          served: rms  margin  top-1      wave: rms  margin  top-1
#   sound                     0.0699   0.500   94.1 %       0.0637   0.041   98.4 %
#   state stored in bf16      0.0699   0.399   92.4 %       as sound: judged on the state
#   route scale 2.5 -> 1      0.2057   0.646   77.0 %       0.2109   0.837   73.4 %
#   attention gate dropped    0.3324   1.160   67.4 %       0.3309   1.142   54.7 %
#   clamp dropped             0.9480   4.246   17.4 %       0.9487   4.469   18.8 %
#   no renormalisation                                      0.8568   2.947   20.3 %
#   1 + w for 2 sigmoid(w)                                  1.0419   2.899   20.3 %
#   post-norms dropped                                      1.1817   4.588   12.5 %
#   beta = 1                                                1.3328   4.852    4.7 %
#   float8_e4m3 weights                                     1.9484   8.130    0.0 %
#
# The init draws every SwiGLU's gate eight times wider and its down eight
# times narrower, so the clamp binds on about a fifth of every FFN's hidden
# units and no expert is larger than its neighbours (``models/llama.
# init_params``): a dropped clamp is the loudest patch, not the quietest.
# Every broken variant but the state's fails the rms limit (the geometric
# middle of the sound 0.0699 and the nearest variant's 0.2057) and the
# top-1 limit (between the two sound-token programs' 94.1 / 92.4 % and
# the nearest variant's 77.0 %); float8, the nearest precision below the
# bf16 the configuration states, fails all three.  The margin is the worst
# of 304 tokens, an extreme value: its limit is the geometric middle of the
# sound 0.500 and the dropped gate's 1.160, so it holds the gate, the clamp
# and everything louder, and leaves the route scale (0.646) to the other
# two limits.
MARGIN = 0.76
# Share of served tokens that must BE the reference's argmax.
TOP1_SHARE = 0.85
# rms of (program - reference) logits over the logit check's positions.
LOGIT_RMS = 0.12
# Relative rms of (the loop's state row - the reference's state) after the
# lone stream's prompt and answer, over a DeltaNet layer's SLOW heads (a
# head whose state keeps more than e^-2 of itself over the answer's 240
# decode steps: 1 / 4 / 8 / 3 of a layer's 64 here).  Two limits, because
# two things are read.  (i) The FIRST DeltaNet layer's input is the
# embedding alone, so its distance is the recurrence's own arithmetic and
# storage: 0.46-0.49 % in every sound-state run (six, over three draws of
# the weights), and 1.27 % with the state rows stored in bfloat16 (every
# scan's and every decode step's state rounded as it is handed back:
# ``--served state_bf16``; 1.38 % on the earlier draw) while its tokens and
# logits read as the sound program's: ``STATE_FIRST_REL``, the geometric
# middle, alone fails it.  (ii) A deeper layer's state carries the bf16
# ACTIVATIONS' distance through every expert layer above it, and that is
# not a steady number: the sound program reads 1.45 / 5.01 / 6.84 % on
# layers 2-4 (2.40 % and 3.65 % on layer 4 with two other draws of the
# weights), the bf16-state program 2.20 / 3.37 / 4.22 % — LOWER on the
# last two, its greedy answer being another sequence.  So the worst layer is
# held only against what a fault upstream or in the carry does to it: the
# dropped clamp reads 52.6 / 72.1 / 80.8 % there, route scale 1 14.2 %;
# ``STATE_SLOW_REL`` is the geometric middle of 6.84 % and 52.6 %.
STATE_FIRST_REL = 0.008
STATE_SLOW_REL = 0.19
SLOW_LOG_KEEP = -2.0
N_PROMPTS = 4  # served at once: PREFILL_BUDGET / PREFILL_CHUNK + 1
SERVE_TOKENS = 16
QUERY_BLOCK = 128  # queries a block of the attention holds scores for
HEAD_CHUNKS = 4  # the head is applied (and upcast) a slice of the vocabulary at a time


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def hyper(config: dict) -> dict:
    """The sizes the forward pass needs, by their published names."""
    layers = int(config["num_hidden_layers"])
    ys = config["rope_scaling"]
    return {
        "kinds": tuple(config["layer_types"])[:layers],  # "linear" | "full"
        "dense_layers": int(config["first_k_dense_replace"]),
        "hidden": int(config["hidden_size"]),
        "eps": float(config["rms_norm_eps"]),
        "norm_weight": float(config["layernorm_gating_weight"]),
        "limit": float(config["swiglu_limit"]),
        "heads": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "v": int(config["v_head_dim"]),
        "theta": float(config["rope_theta"]),
        "yarn": dict(ys) if ys else None,
        "gated_attention": bool(config["gated_attention"]),
        "k_heads": int(config["linear_num_key_heads"]),
        "v_heads": int(config["linear_num_value_heads"]),
        "k_dim": int(config["linear_key_head_dim"]),
        "v_dim": int(config["linear_value_head_dim"]),
        "conv": int(config["linear_conv_kernel_dim"]),
        "o_eps": float(config["linear_attn_o_norm_eps"]),
        "gate_scale": float(config["linear_sigmoid_gate_scale"]),
        "top_k": int(config["num_experts_per_tok"]),
        "norm_topk": bool(config["norm_topk_prob"]),
        "route_scale": float(config["routed_scaling_factor"]),
        "router_experts": int(config["router_experts"]),  # the published 256
        "held": int(config["n_routed_experts"]),  # this chip's share
        "first": int(config.get("expert_first", 0)),
    }


def _rms(x, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps))


def norm(x, w, hp: dict):
    """N(x; w): the zero-centred gated norm, scale ``2 sigmoid(w)``."""
    import jax

    return _rms(x, hp["eps"]) * (hp["norm_weight"] * jax.nn.sigmoid(w))


def swiglu(g, u, hp: dict):
    import jax
    import jax.numpy as jnp

    lim = hp["limit"]
    return jax.nn.silu(jnp.minimum(g, lim)) * jnp.clip(u, -lim, lim)


def inv_freq(hp: dict):
    """The rotary frequencies of the ``rope`` dims, YaRN-blended."""
    import jax.numpy as jnp

    d = hp["rope"]
    inv = 1.0 / (hp["theta"] ** (jnp.arange(0, d // 2, dtype=jnp.float32) * 2.0 / d))
    y = hp["yarn"]
    if not y:
        return inv, 1.0

    def pair_of(turns):
        return (d * math.log(y["original_max_position_embeddings"]
                             / (turns * 2 * math.pi)) / (2 * math.log(hp["theta"])))

    lo = max(math.floor(pair_of(y["beta_fast"])), 0)
    hi = min(math.ceil(pair_of(y["beta_slow"])), d - 1)
    r = 1.0 - jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - lo)
                       / max(hi - lo, 0.001), 0.0, 1.0)
    amp = yarn_mscale(y["factor"], y["mscale"]) / yarn_mscale(
        y["factor"], y["mscale_all_dim"])
    return inv / y["factor"] * (1.0 - r) + inv * r, amp


def _rope(x, hp: dict):
    """x [S, H, D]; rotate-half convention, positions 0..S-1."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv, amp = inv_freq(hp)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :] * amp
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :] * amp
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(q, k, v, scale: float):
    """softmax(q k^T * scale) v on q, k [S, H, Dk], v [S, H, Dv] under the
    full causal mask; a block of queries at a time against every key."""
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
        scores = jnp.einsum("qhd,khd->hqk", qs, k) * scale
        scores = jnp.where(allowed[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(block, jnp.arange(n_blocks))  # [n, qb, H, Dv]
    return out.reshape(n_blocks * QUERY_BLOCK, h, v.shape[-1])[:s]


def delta_rule(q, k, v, log_a, beta, s0=None):
    """The gated delta rule one token at a time: q, k [S, H, Dk] (unit
    length, q scaled), v [S, H, Dv], ``log_a`` / ``beta`` [S, H] -> (o
    [S, H, Dv], the state [H, Dv, Dk] after the last row)."""
    import jax
    import jax.numpy as jnp

    def step(state, t):
        q_t, k_t, v_t, a_t, b_t = t
        kept = a_t[:, None, None] * state
        u_t = b_t[:, None] * (v_t - jnp.sum(kept * k_t[:, None, :], axis=-1))
        state = kept + u_t[:, :, None] * k_t[:, None, :]
        return state, jnp.sum(state * q_t[:, None, :], axis=-1)

    if s0 is None:
        s0 = jnp.zeros((q.shape[1], v.shape[2], q.shape[2]), jnp.float32)
    last, o = jax.lax.scan(step, s0, (q, k, v, jnp.exp(log_a), beta))
    return o, last


def deltanet(u, w: dict, hp: dict):
    """The Gated-DeltaNet mixer on u [S, D] (normed), from a zero state
    (right padding is inert for the OUTPUT: causal).  -> (out [S, D], the
    state S [Hv, Dv, Dk] after the last row, log a_t [S, Hv])."""
    import jax
    import jax.numpy as jnp

    s = u.shape[0]
    hk, hv, dk, dv, kc = (hp["k_heads"], hp["v_heads"], hp["k_dim"],
                          hp["v_dim"], hp["conv"])
    qkvz, ba = u @ w["qkvz"], u @ w["ba"]
    cd = 2 * hk * dk + hv * dv
    qkv, z = qkvz[:, :cd], qkvz[:, cd:]
    padded = jnp.concatenate([jnp.zeros((kc - 1, cd)), qkv], axis=0)
    qkv = jax.nn.silu(sum(w["conv_w"][j] * padded[j:j + s] for j in range(kc)))

    def unit(t):
        t = t.reshape(s, hk, dk)
        t = t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)
        return jnp.repeat(t, hv // hk, axis=1)

    q = unit(qkv[:, :hk * dk]) * dk ** -0.5
    k = unit(qkv[:, hk * dk:2 * hk * dk])
    v = qkv[:, 2 * hk * dk:].reshape(s, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    log_a = -jnp.exp(w["A_log"]) * jax.nn.softplus(ba[:, hv:] + w["dt_bias"])
    o, last = delta_rule(q, k, v, log_a, beta)
    y = _rms(o, hp["o_eps"]) * (1.0 + w["o_norm"])
    y = y.reshape(s, hv * dv) * (hp["gate_scale"] * jax.nn.sigmoid(z))
    return y @ w["out"], last, log_a


def softmax_scale(hp: dict) -> float:
    scale = (hp["nope"] + hp["rope"]) ** -0.5
    if hp["yarn"]:
        scale *= yarn_mscale(hp["yarn"]["factor"], hp["yarn"]["mscale_all_dim"]) ** 2
    return scale


def latent_attention(u, w: dict, hp: dict):
    """The gated latent attention on u [S, D] (normed), expanded."""
    import jax
    import jax.numpy as jnp

    s = u.shape[0]
    h, dn, dr = hp["heads"], hp["nope"], hp["rope"]
    cq = _rms(u @ w["q_a"], hp["eps"]) * w["q_a_norm"]
    q = (cq @ w["q_b"]).reshape(s, h, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], hp)], axis=-1)
    ckr = u @ w["kv_a"]
    c = _rms(ckr[:, : hp["kv_rank"]], hp["eps"]) * w["kv_a_norm"]
    kr = _rope(ckr[:, None, hp["kv_rank"]:], hp)  # [S, 1, rope]: one key a token
    kn = jnp.einsum("sr,hnr->shn", c, w["k_b"])
    v = jnp.einsum("sr,hrv->shv", c, w["v_b"])
    k = jnp.concatenate([kn, jnp.broadcast_to(kr, (s, h, dr))], axis=-1)
    ctx = attention(q, k, v, softmax_scale(hp)).reshape(s, h * hp["v"])
    if hp["gated_attention"]:
        ctx = ctx * jax.nn.sigmoid(u @ w["attn_gate"])
    return ctx @ w["o"]


def select(sc, bias, hp: dict):
    """Router scores sc [S, E] -> (chosen experts [S, k], their weights):
    the k largest of ``sc + bias``, weights the scores themselves,
    renormalised over the chosen under ``norm_topk``, times the scale."""
    import jax
    import jax.numpy as jnp

    _, ek = jax.lax.top_k(sc + bias, hp["top_k"])
    wk = jnp.take_along_axis(sc, ek, axis=-1)
    if hp["norm_topk"]:
        wk = wk / jnp.sum(wk, axis=-1, keepdims=True)
    return ek, wk * hp["route_scale"]


def experts(u, w: dict, hp: dict, first: int | None = None, shared: bool = True):
    """The expert sum on u [S, D]: the float32 router over ALL published
    experts, then every HELD expert in turn (the stacks of ``w``: experts
    ``first`` on, ``hp["first"]`` by default), masked to the tokens that
    chose it; the shared expert on all of them unless ``shared`` is False
    (a share other than the one that counts it).  Also returns the chosen
    experts [S, k] (published ids)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    ek, wk = select(jax.nn.sigmoid(u @ w["router"]), w["router_bias"], hp)

    def one(acc, ew):
        e, g, up, d = ew  # one held expert's matrices, upcast here
        weight = jnp.sum(jnp.where(ek == e, wk, 0.0), axis=-1)  # [S]
        y = swiglu(u @ g.astype(f32), u @ up.astype(f32), hp) @ d.astype(f32)
        return acc + weight[:, None] * y, None

    ids = (hp["first"] if first is None else first) + jnp.arange(w["gate"].shape[0])
    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (ids, w["gate"], w["up"], w["down"]))
    if shared:
        out = out + swiglu(u @ w["s_gate"], u @ w["s_up"], hp) @ w["s_down"]
    return out, ek


def layer(x, w: dict, hp: dict, kind: str, dense: bool):
    """One layer on x [S, D] (one sequence).  -> (x, (a DeltaNet layer's
    (state [Hv, Dv, Dk] after the last row, log decay [S, Hv]) or None, an
    expert layer's chosen experts [S, k] or None))."""
    u = norm(x, w["mix_ln"], hp)
    if kind == "linear":
        f, last, log_a = deltanet(u, w, hp)
        left = (last, log_a)
    else:
        f, left = latent_attention(u, w, hp), None
    x = x + norm(f, w["mix_post_ln"], hp)
    u = norm(x, w["mlp_ln"], hp)
    if dense:
        f, chosen = swiglu(u @ w["gate"], u @ w["up"], hp) @ w["down"], None
    else:
        f, chosen = experts(u, w, hp)
    return x + norm(f, w["mlp_post_ln"], hp), (left, chosen)


def layer_weights(p: dict, kind: str, dense: bool) -> dict:
    """One layer of the service's tree upcast to float32 — but for an
    expert layer's stacked gate / up / down, which stay as they are
    stored: ``experts`` upcasts one expert at a time."""
    import jax.numpy as jnp

    f = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    m = p["mlp"]
    w = {"mlp_ln": f(p["mlp_ln"]["scale"]), "mlp_post_ln": f(p["mlp_post_ln"]["scale"])}
    if kind == "linear":
        g = p["gdn"]
        w.update(mix_ln=f(p["gdn_ln"]["scale"]), mix_post_ln=f(p["gdn_post_ln"]["scale"]),
                 qkvz=f(g["qkvz"]["kernel"]), ba=f(g["ba"]["kernel"]),
                 conv_w=f(g["conv"]["kernel"]), dt_bias=f(g["dt_bias"]),
                 A_log=f(g["A_log"]), o_norm=f(g["norm"]["scale"]),
                 out=f(g["out"]["kernel"]))
    else:
        a = p["attn"]
        w.update(mix_ln=f(p["attn_ln"]["scale"]),
                 mix_post_ln=f(p["attn_post_ln"]["scale"]),
                 q_a_norm=f(a["q_a_norm"]["scale"]), kv_a_norm=f(a["kv_a_norm"]["scale"]),
                 **{n: f(a[n]["kernel"]) for n in ("q_a", "q_b", "kv_a", "k_b", "v_b", "o")})
        if "gate" in a:
            w["attn_gate"] = f(a["gate"]["kernel"])
    if dense:
        w.update({n: f(m[n]["kernel"]) for n in ("gate", "up", "down")})
        return w
    sh = m["shared"]
    w.update({n: jnp.asarray(m[n]["kernel"]) for n in ("gate", "up", "down")})
    w.update(router=f(m["router"]["kernel"]), router_bias=f(m["router_bias"]),
             s_gate=f(sh["gate"]["kernel"]), s_up=f(sh["up"]["kernel"]),
             s_down=f(sh["down"]["kernel"]))
    return w


def hidden(params: dict, hp: dict, ids, chosen: list | None = None,
           states: list | None = None):
    """ids [B, S] int32 -> the final-normed hidden states [B, S, D],
    float32, one sequence at a time.  A list given as ``chosen`` receives
    each EXPERT layer's chosen experts [B, S, k] (padding positions
    included: the caller knows the lengths); one given as ``states`` each
    DELTANET layer's (state [B, Hv, Dv, Dk] after ALL S tokens, so no
    padding; log decay [B, S, Hv])."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    step = jax.jit(lambda x, w, kind, dense: layer(x, w, hp, kind, dense),
                   static_argnums=(2, 3))
    ids = np.asarray(ids)
    kept_e: dict[int, list] = {}
    kept_s: dict[int, list] = {}
    with jax.default_matmul_precision("highest"):
        xs = [jnp.take(jnp.asarray(params["embed"]["embedding"]), row, axis=0)
              .astype(jnp.float32) for row in ids]
        for li, (p, kind) in enumerate(zip(params["layers"], hp["kinds"])):
            dense = li < hp["dense_layers"]
            w = layer_weights(p, kind, dense)
            for b in range(len(xs)):
                xs[b], (left, picked) = step(xs[b], w, kind, dense)
                if chosen is not None and picked is not None:
                    kept_e.setdefault(li, []).append(np.asarray(picked))
                if states is not None and left is not None:
                    kept_s.setdefault(li, []).append(jax.tree.map(np.asarray, left))
            del w
        scale = jnp.asarray(params["final_ln"]["scale"], jnp.float32)
        out = jnp.stack([norm(x, scale, hp) for x in xs])
    if chosen is not None:
        chosen.extend(np.stack(v) for _, v in sorted(kept_e.items()))
    if states is not None:
        states.extend(tuple(np.stack(part) for part in zip(*v))
                      for _, v in sorted(kept_s.items()))
    return out


def head_logits(params: dict, x):
    """x [..., D] final-normed rows -> float32 logits [..., V], the head
    upcast a slice of the vocabulary at a time."""
    import jax
    import jax.numpy as jnp

    kernel = params["lm_head"]["kernel"]
    v = kernel.shape[1]
    step = -(-v // HEAD_CHUNKS)
    with jax.default_matmul_precision("highest"):
        parts = [x @ jnp.asarray(kernel[:, c: c + step], jnp.float32)
                 for c in range(0, v, step)]
    return jnp.concatenate(parts, axis=-1)


def logits(params: dict, hp: dict, ids, chosen: list | None = None,
           head: bool = True):
    """ids [B, S] int32 -> float32 logits [B, S, V] (``head=False``: the
    final-normed hidden states, a pass made for the routing alone)."""
    x = hidden(params, hp, ids, chosen)
    return head_logits(params, x) if head else x


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
    """Root mean square of (program - reference) over logits [N, V]:
    the reference's rows are ``ref_hidden`` [N, D] through the head, a
    slice of the vocabulary at a time."""
    import jax
    import jax.numpy as jnp

    kernel = params["lm_head"]["kernel"]
    v = kernel.shape[1]
    step = -(-v // HEAD_CHUNKS)
    sq = 0.0
    with jax.default_matmul_precision("highest"):
        for c in range(0, v, step):
            ref = ref_hidden @ jnp.asarray(kernel[:, c: c + step], jnp.float32)
            diff = jnp.asarray(got_logits[:, c: c + step], jnp.float32) - ref
            sq += float(jnp.sum(diff * diff))
    return (sq / (ref_hidden.shape[0] * v)) ** 0.5


def routing(chosen: list, lens: list[int], hp: dict) -> dict:
    """What one decode step over these rows routes, an expert layer at a
    time: each row's LAST real position is one of the step's tokens.
    ``held_experts_hit``: distinct experts OF THIS CHIP'S SHARE a layer
    touches, a mean over the expert layers (what a step streams; the cost
    model counts the experts' bytes from this line; 32 rows x 8 over 256
    expect 10.2 of 16 if even); ``held_share``: the share of assignments
    that land on this chip (held / published = 6.25 % if even);
    ``tokens_none_here``: the share of tokens with no expert on this chip
    (59 % if even); ``busiest_held_share``: the share of rows whose top-k
    holds a layer's most chosen held expert, the worst layer."""
    import numpy as np

    rows = np.arange(len(lens))
    lo, hi = hp["first"], hp["first"] + hp["held"]
    last = [np.asarray(c)[rows, np.asarray(lens) - 1] for c in chosen]  # [B, k]
    here = [(a >= lo) & (a < hi) for a in last]
    hit = [len(np.unique(a[m])) for a, m in zip(last, here)]
    busiest = [np.bincount(a[m] - lo, minlength=hp["held"]).max() / len(lens)
               for a, m in zip(last, here)]
    return {"rows": len(lens),
            "held_experts_hit": sum(hit) / len(hit),
            "held_experts_hit_least": min(hit),
            "held_share": float(np.mean([m.mean() for m in here])),
            "tokens_none_here": float(np.mean([(~m.any(axis=1)).mean() for m in here])),
            "busiest_held_share": float(max(busiest))}


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
    against ``want`` (a DeltaNet layer each, [Hv, Dv, Dk]: the reference's
    state after the same tokens); ``kept`` [Hv] a layer: the log of what a
    head's state keeps over the answer's decode steps.  Per layer the
    relative rms distance of the nearest of the loop's state rows — a
    stream's row is the host's to choose, so the nearest is taken and
    every layer must name the same one — over the whole state and over the
    SLOW heads alone (``SLOW_LOG_KEEP``).  Read once nothing is admitted or
    in flight: the state is the loop thread's while it runs."""
    import asyncio

    import jax
    import jax.numpy as jnp
    import numpy as np

    loop = svc.batcher._cdl
    while not loop.idle():
        await asyncio.sleep(0.01)

    @jax.jit
    def distance(rows, one):  # [R, H, Dv, Dk], [H, Dv, Dk] -> a head: [R, H], [H]
        return (jnp.sum(jnp.square(rows - one[None]), axis=(2, 3)),
                jnp.sum(jnp.square(one), axis=(1, 2)))

    out = {"state_rel_err": [], "state_slow_rel_err": [], "state_slow_heads": [],
           "state_row": []}
    for have, one, keep in zip(loop._state.ssm.state, want, kept):
        d, w = (np.asarray(x, np.float64) for x in distance(have, jnp.asarray(one)))
        row = int(np.argmin(d.sum(axis=1)))
        slow = np.asarray(keep) >= SLOW_LOG_KEEP
        out["state_row"].append(row)
        out["state_rel_err"].append(float(np.sqrt(d[row].sum() / w.sum())))
        out["state_slow_heads"].append(int(slow.sum()))
        # a layer with no slow head reads as far off as a wrong row: the
        # limit must not pass a layer it cannot see
        out["state_slow_rel_err"].append(
            float(np.sqrt(d[row][slow].sum() / w[slow].sum())) if slow.any() else 1.0)
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
    ref_alone = hidden(params, hp, alone, states=want_states)
    state = await served_state_error(
        svc, [s[0] for s, _ in want_states],
        [a[0, -state_tokens:].sum(axis=0) for _, a in want_states])
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
    out["state_first_limit"], out["state_slow_limit"] = STATE_FIRST_REL, STATE_SLOW_REL
    out["correct"] = (out["correct"]
                      and state["state_slow_rel_err"][0] <= STATE_FIRST_REL
                      and max(state["state_slow_rel_err"]) <= STATE_SLOW_REL
                      and len(set(state["state_row"])) == 1)
    del ref_alone
    # The program's own logits (its prefill-wave forward) on the head of
    # the first sequence.
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
    del ref_hidden, ref_rows, got
    # The reference's own routing of one step's worth of rows (as many as
    # the service has slots), reported beside the verdict and never part
    # of it: what the cost functions count the streamed experts from.
    n_rows = int(config["env"]["MAX_STREAMS"])
    lo, hi = config["routing_prompt_tokens"]
    r_lens = [rng.randrange(lo, hi) for _ in range(n_rows)]
    r_ids = np.zeros((n_rows, max(r_lens)), np.int32)
    for b, k in enumerate(r_lens):
        r_ids[b, :k] = [rng.randrange(3, vocab) for _ in range(k)]
    chosen: list = []
    hidden(params, hp, r_ids, chosen)
    out["routing"] = routing(chosen, r_lens, hp)
    peak("routing")
    out["memory_peak_bytes_after"] = trail
    return out
