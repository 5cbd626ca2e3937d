"""Plain reference for the DeepSeek-V2 architecture (multi-head latent
attention + group-limited expert routing), and the check that holds the
served path to it.

Forward pass in straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``, written from the keys of
deepseek-ai/DeepSeek-V2's ``config.json`` and the family's model code
(``modeling_deepseek``).  ``x`` the residual stream, position ``i``, head
``h`` of 128; ``RoPE_y`` = rotary on 64 dims with YaRN's frequencies:

    y        = RMSNorm(x)
    c_q      = RMSNorm_1536( y W_DQ )                       W_DQ [5120, 1536]
    [qn_h ; qr_h] = split_h( c_q W_UQ )                     W_UQ [1536, 128 x 192]; qn 128, qr 64
    qr_h     = RoPE_y(qr_h, i)
    [c ; kr] = y W_DKV                                      W_DKV [5120, 512 + 64]
    c        = RMSNorm_512(c)      kr = RoPE_y(kr, i)       ONE rotary key a token, shared by every head
    [kn_h ; v_h] = split_h( c W_UKV )                       W_UKV [512, 128 x (128 + 128)]
    s_hij    = ( qn_hi . kn_hj + qr_hi . kr_j ) * 192^-1/2 * m^2 ,   j <= i ;   m = 0.1 * 0.707 * ln 40 + 1 = 1.2608
    a_hi     = sum_j softmax_j(s_hij) v_hj
    h        = x + concat_h(a_h) W_O                        W_O [128 x 128, 5120]
    z        = RMSNorm(h)
    layer 0:      f = ( silu(z G) * (z U) ) D               width 12288
    layers >= 1:  p   = softmax_f32( z W_r )                [160]
                  g_k = max_{e in group k} p_e              8 groups of 20 consecutive experts
                  keep the 3 groups of largest g; e_1..e_6 = top6 of p over their 60 experts
                  w_j = 16 * p[e_j]                         norm_topk_prob false, routed_scaling_factor 16
                  f   = sum_j w_j Expert_{e_j}(z) + Shared(z)   Expert: SwiGLU of width 1536; Shared: one SwiGLU of width 2 x 1536
    out      = h + f
    logits   = RMSNorm(x_L) W_head                          untied

**YaRN**: ``inv_freq_d = theta^(-2d/64)``; ``inv_freq'_d = inv_freq_d / 40
* (1 - r_d) + inv_freq_d * r_d`` with ``r_d = 1 - clip((d - lo) / (hi -
lo), 0, 1)``, ``lo`` / ``hi`` the dimension pairs whose wavelength makes
``beta_fast`` = 32 / ``beta_slow`` = 1 turns in 4096 positions (floor /
ceil, clipped to 0..63 as the model code does); cos and sin are multiplied
by ``mscale(40, 0.707) / mscale(40, 0.707)`` = 1.

**Expanded attention, always** — keys and values of every head are made
from the latent (``c W_UKV``) and scored at width 192; the served decode
step is the ABSORBED form (``qn W_UK^T`` against the cached latent), so
the check also tests the absorption.  No cache, no kernel, no sort, no
table, no grouped matmul: a full causal mask, a block of queries at a
time, one sequence at a time; the expert sum is a loop over the HELD
experts with a plain per-expert mask, each expert upcast on its own.

**One chip's share** (the configuration's cut, the same in program and
reference): the router is 160 wide and the selection runs over all 160
with the group limit; this chip holds experts ``expert_first ..
expert_first + experts_held - 1`` (40: groups 0 and 1); what a token's
experts on the other chips would add is left out — nothing stands in for
the absent chips or their exchange.  The vocabulary is the configuration's
``vocab_size`` rows (25 600): a smaller vocabulary, logits over it.

Assumed (model code, not keys of ``config.json``; the configuration file
lists each): the three inner RMSNorms' placement as above; the rotary
pairing — the model code de-interleaves ``qr`` / ``kr`` and rotates halves;
with seeded weights that is a column permutation of ``W_UQ`` / ``W_DKV``,
so program and reference both rotate halves of the 64 dims as they lie;
``seq_aux`` / aux losses unused; bf16 weights and cache with float32 router
scores; the piece tokenizer with no BOS.  Departures, each noted: (1) the
model code fills the scores of experts outside the kept groups with 0
before the top-6 (so does this file; a softmax score is never 0, so no
masked expert can be chosen while the kept groups hold 6); (2) weights are
the service's seeded random init read leaf by leaf — ``W_UKV`` lies there
split once into ``k_b`` [H, 128, 512] (= W_UK) and ``v_b`` [H, 512, 128]
(= W_UV), and the expansion ``c W_UKV`` is the two einsums over them; (3)
the loop runs every held expert on every token and masks.

The check is ``references/trinity.py``'s: seeded prompts PAST 2048 TOKENS
are served greedily through the normal HTTP stream path (chunked paged
prefill in ``PREFILL_CHUNK`` windows — expanded attention over the row's
latents —, then decode through the latent pool, the absorbed step and the
Pallas latent kernel); the served sequence is teacher-forced through this
reference, and every served token's REFERENCE logit must lie within
``MARGIN`` of the reference's top logit at that position, ``TOP1_SHARE`` of
them its argmax.  Beside the tokens, the program's own logits
(``bundle.logits_fn``, its prefill-wave forward) on the first
``logit_check_tokens`` tokens of the first sequence must lie within
``LOGIT_RMS`` (rms) of the reference's.
"""

from __future__ import annotations

import json
import math
import random

# Reference logits have a standard deviation of about 0.9 here; the top of
# 25600 sits near 3.9.  Each limit lies between chip readings at the
# published widths (my chip runs, PR 33; PERF.md section 4 has the table):
# the served path's, and the same program with one rule of the block broken
# (tools/deepseek_variants.py: the program's own prefill forward on one
# seeded sequence of 2560 tokens; margin and top-1 over its last 64
# positions).  The weights are PRNGKey(0)'s and the prompts CHECK_SEED's, so
# a reading repeats to the last digit from run to run; it moves when the
# program's arithmetic does.
#
# What sets the sound program's distance: as on Trinity, bf16 rounding moves
# a DISCRETE choice — the 6th and 7th expert of a token, or its 3rd and 4th
# group — and a routed expert weighs 16 p ~ 0.1-0.3 with no norm after it;
# hence rms 0.12, and a worst margin that wanders with the rounding order
# (0.135 through the served path's chunked prefill and absorbed decode,
# 1.19 through the prefill wave's one pass) while the rms does not
# (0.1213 / 0.1217).  The margin limit is the served path's; the wave is
# held to the rms alone.
#
#                         logit rms   worst margin   top-1
#   served path (check)    0.1213       0.135        95.3 %   (61 of 64)
#   sound, prefill wave    0.1217       1.193        89.1 %
#   no group limit         0.4171       1.689        35.9 %
#   route scale 16 -> 1    0.8581       4.330        18.8 %
#   renormalised weights   1.1629       6.753         4.7 %
#   no norm on c           1.2332       4.914         4.7 %
#   no mscale^2 in scale   1.2516       4.661         6.3 %
#   no norm on c_q         1.4025       6.769         0.0 %
#   plain RoPE for YaRN    1.5847       7.650         0.0 %
#   float8_e4m3 weights    1.7978       8.352         0.0 %
#
# Every broken variant fails all three limits.  (A ninth, values read from
# all of a cached row's lanes, lives in the decode kernel alone:
# tests/test_deepseek_block.py.)
MARGIN = 0.9
# Share of served tokens that must BE the reference's argmax.
TOP1_SHARE = 0.65
# rms of (program - reference) logits over the logit check's positions.
LOGIT_RMS = 0.22
N_PROMPTS = 4
SERVE_TOKENS = 16
QUERY_BLOCK = 128  # queries a block of the attention holds scores for
HEAD_CHUNKS = 4  # the head is applied (and upcast) a slice of the vocabulary at a time


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def hyper(config: dict) -> dict:
    """The sizes the forward pass needs, by their published names."""
    ys = config["rope_scaling"]
    return {
        "hidden": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "v": int(config["v_head_dim"]),
        "theta": float(config["rope_theta"]),
        "yarn": dict(ys) if ys else None,
        "eps": float(config["rms_norm_eps"]),
        "top_k": int(config["num_experts_per_tok"]),
        "n_group": int(config["n_group"]),
        "topk_group": int(config["topk_group"]),
        "norm_topk": bool(config["norm_topk_prob"]),
        "route_scale": float(config["routed_scaling_factor"]),
        "router_experts": int(config["router_experts"]),  # the published 160
        "held": int(config["n_routed_experts"]),  # this chip's share
        "first": int(config.get("expert_first", 0)),
        "dense_layers": int(config["first_k_dense_replace"]),
    }


def softmax_scale(hp: dict) -> float:
    scale = (hp["nope"] + hp["rope"]) ** -0.5
    if hp["yarn"]:
        scale *= yarn_mscale(hp["yarn"]["factor"], hp["yarn"]["mscale_all_dim"]) ** 2
    return scale


def _rmsnorm(x, scale, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * scale


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


def select(p, hp: dict):
    """Router scores p [S, E] -> (chosen experts [S, k], their weights):
    the group limit (a group's score the max of its experts'; experts
    outside the ``topk_group`` best groups score 0), the top-k among what
    is left, weights the scores as they are times ``route_scale``
    (renormalised instead under ``norm_topk``, as the model code has it)."""
    import jax
    import jax.numpy as jnp

    s, e = p.shape
    sel = p
    if hp["n_group"] > 1:
        g = jnp.max(p.reshape(s, hp["n_group"], -1), axis=-1)
        _, gi = jax.lax.top_k(g, hp["topk_group"])
        keep = jnp.zeros((s, hp["n_group"]), bool).at[
            jnp.arange(s)[:, None], gi].set(True)
        sel = jnp.where(jnp.repeat(keep, e // hp["n_group"], axis=1), p, 0.0)
    _, ek = jax.lax.top_k(sel, hp["top_k"])
    wk = jnp.take_along_axis(p, ek, axis=-1)
    if hp["norm_topk"]:
        wk = wk / jnp.sum(wk, axis=-1, keepdims=True)
    return ek, wk * hp["route_scale"]


def experts(z, w: dict, hp: dict):
    """The expert sum on z [S, D]: the float32 router over ALL published
    experts, the group-limited top-k, then every HELD expert in turn,
    masked to the tokens that chose it, and the shared expert on all of
    them.  Also returns the chosen experts [S, k] (published ids)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    ek, wk = select(jax.nn.softmax(z @ w["router"], axis=-1), hp)

    def one(acc, ew):
        e, g, u, d = ew  # one held expert's matrices, upcast here
        weight = jnp.sum(jnp.where(ek == e, wk, 0.0), axis=-1)  # [S]
        y = (jax.nn.silu(z @ g.astype(f32)) * (z @ u.astype(f32))) @ d.astype(f32)
        return acc + weight[:, None] * y, None

    ids = hp["first"] + jnp.arange(w["gate"].shape[0])
    out, _ = jax.lax.scan(one, jnp.zeros_like(z),
                          (ids, w["gate"], w["up"], w["down"]))
    shared = (jax.nn.silu(z @ w["s_gate"]) * (z @ w["s_up"])) @ w["s_down"]
    return out + shared, ek


def layer(x, w: dict, hp: dict, dense: bool):
    """One decoder block on x [S, D] (one sequence).  -> (x, the layer's
    chosen experts [S, k], or None for a dense layer)."""
    import jax
    import jax.numpy as jnp

    s = x.shape[0]
    h, dn, dr = hp["heads"], hp["nope"], hp["rope"]
    y = _rmsnorm(x, w["attn_ln"], hp["eps"])
    cq = _rmsnorm(y @ w["q_a"], w["q_a_norm"], hp["eps"])
    q = (cq @ w["q_b"]).reshape(s, h, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], hp)], axis=-1)
    ckr = y @ w["kv_a"]
    c = _rmsnorm(ckr[:, : hp["kv_rank"]], w["kv_a_norm"], hp["eps"])
    kr = _rope(ckr[:, None, hp["kv_rank"]:], hp)  # [S, 1, rope]: one key a token
    kn = jnp.einsum("sr,hnr->shn", c, w["k_b"])  # c W_UKV, the key half
    v = jnp.einsum("sr,hrv->shv", c, w["v_b"])  # and the value half
    k = jnp.concatenate([kn, jnp.broadcast_to(kr, (s, h, dr))], axis=-1)
    a = attention(q, k, v, softmax_scale(hp)).reshape(s, h * hp["v"])
    x = x + a @ w["o"]
    z = _rmsnorm(x, w["mlp_ln"], hp["eps"])
    if dense:
        f, chosen = (jax.nn.silu(z @ w["gate"]) * (z @ w["up"])) @ w["down"], None
    else:
        f, chosen = experts(z, w, hp)
    return x + f, chosen


def layer_weights(p: dict, dense: bool) -> dict:
    """One layer of the service's tree upcast to float32 — but for an
    expert layer's stacked gate / up / down, which stay as they are
    stored: ``experts`` upcasts one expert at a time."""
    import jax.numpy as jnp

    f = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    a, m = p["attn"], p["mlp"]
    w = {
        "attn_ln": f(p["attn_ln"]["scale"]), "mlp_ln": f(p["mlp_ln"]["scale"]),
        "q_a": f(a["q_a"]["kernel"]), "q_a_norm": f(a["q_a_norm"]["scale"]),
        "q_b": f(a["q_b"]["kernel"]), "kv_a": f(a["kv_a"]["kernel"]),
        "kv_a_norm": f(a["kv_a_norm"]["scale"]),
        "k_b": f(a["k_b"]["kernel"]), "v_b": f(a["v_b"]["kernel"]),
        "o": f(a["o"]["kernel"]),
    }
    if dense:
        w.update({n: f(m[n]["kernel"]) for n in ("gate", "up", "down")})
        return w
    sh = m["shared"]
    w.update({n: jnp.asarray(m[n]["kernel"]) for n in ("gate", "up", "down")})
    w.update(router=f(m["router"]["kernel"]),
             s_gate=f(sh["gate"]["kernel"]), s_up=f(sh["up"]["kernel"]),
             s_down=f(sh["down"]["kernel"]))
    return w


def hidden(params: dict, hp: dict, ids, chosen: list | None = None):
    """ids [B, S] int32 -> the final-normed hidden states [B, S, D],
    float32, one sequence at a time (128 heads of 192: a sequence's q and
    expanded k are 0.4 GB each at 4200 tokens).  A list given as
    ``chosen`` receives each EXPERT layer's chosen experts [B, S, k]
    (padding positions included: the caller knows the lengths)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    step = jax.jit(lambda x, w, dense: layer(x, w, hp, dense), static_argnums=(2,))
    ids = np.asarray(ids)
    picks_by_layer: dict[int, list] = {}
    with jax.default_matmul_precision("highest"):
        xs = [jnp.take(jnp.asarray(params["embed"]["embedding"]), row, axis=0)
              .astype(jnp.float32) for row in ids]
        for li, p in enumerate(params["layers"]):
            dense = li < hp["dense_layers"]
            w = layer_weights(p, dense)
            for b in range(len(xs)):
                xs[b], picks = step(xs[b], w, dense)
                if chosen is not None and picks is not None:
                    picks_by_layer.setdefault(li, []).append(np.asarray(picks))
            del w
        scale = jnp.asarray(params["final_ln"]["scale"], jnp.float32)
        out = jnp.stack([_rmsnorm(x, scale, hp["eps"]) for x in xs])
    if chosen is not None:
        chosen.extend(np.stack(v) for _, v in sorted(picks_by_layer.items()))
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
    model counts the experts' bytes from this line); ``held_share``: the
    share of assignments that land on this chip (held / published = 25 %
    if even); ``tokens_none_here``: the share of tokens with no expert on
    this chip (35.7 % if a token's 3 kept groups were uniform over 8:
    C(6,3) / C(8,3)); ``busiest_held_share``: the share of rows whose
    top-k holds a layer's most chosen held expert, the worst layer."""
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


async def check(svc, config: dict, seed: int) -> dict:
    """Serve seeded prompts through the normal path and hold them to
    the reference.  ``svc`` is the harness's running service."""
    import jax
    import numpy as np

    trail = {}

    def peak(stage: str) -> None:  # the high-water mark is monotonic
        stats = jax.devices()[0].memory_stats() or {}
        trail[stage] = stats.get("peak_bytes_in_use")

    rng = random.Random(seed)
    vocab = int(config["vocab_size"])
    lens = [rng.randrange(*config["check_prompt_tokens"]) for _ in range(N_PROMPTS)]
    peak("before")
    texts = [" ".join(f"w{rng.randrange(3, vocab)}" for _ in range(n))
             for n in lens]
    served, prompts = [], []
    for text in texts:
        toks: list[int] = []
        async with svc.http.post("/predict", json={
                "text": text, "stream": True, "max_tokens": SERVE_TOKENS}) as r:
            if r.status != 200:
                return {"correct": False, "error": f"HTTP {r.status}"}
            async for line in r.content:
                msg = json.loads(line) if line.strip() else {}
                toks += [int(w[1:]) for w in msg.get("delta", "").split()
                         if w[1:].isdigit()]
        ids, mask = svc.bundle.tokenizer.encode(text, 8192)
        prompts.append([int(t) for t in ids[: int(mask.sum())]])
        served.append(toks)
    width = max(len(p) + len(s) for p, s in zip(prompts, served))
    batch = np.zeros((len(prompts), width), np.int32)  # right pad: causal, so inert
    for b, (p, s) in enumerate(zip(prompts, served)):
        batch[b, : len(p) + len(s)] = p + s
    hp = hyper(config)
    params = svc.engine.params
    peak("served")
    ref_hidden = hidden(params, hp, batch)
    jax.block_until_ready(ref_hidden)
    peak("reference")
    # position p_len - 1 + j predicts served token j
    ref_rows = [head_logits(params, ref_hidden[b, len(p) - 1: len(p) - 1 + len(s)])
                for b, (p, s) in enumerate(zip(prompts, served))]
    out = compare(ref_rows, served)
    out["prompt_tokens"] = [len(p) for p in prompts]
    out["served_tokens"] = [len(s) for s in served]
    if any(len(s) == 0 for s in served):
        out["correct"] = False
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
