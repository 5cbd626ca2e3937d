"""Plain reference for the Mistral-7B architecture, and the check that
holds the served path to it.

Forward pass in straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``, written from the published
description (arXiv:2310.06825 and the HF config): token embedding,
pre-norm blocks of RMSNorm -> grouped-query attention (32 query heads
over 8 KV heads, head size 128, rotate-half RoPE, causal) -> residual,
RMSNorm -> SiLU-gated MLP -> residual, final RMSNorm, untied LM head.
No cache, no kernel, no batching tricks.

Departures, each noted: (1) the sliding window (4096) is not applied —
no context in the benchmark's traffic reaches it, so it never binds;
(2) weights are the service's seeded random init read leaf by leaf and
upcast ONE LAYER AT A TIME (a whole float32 copy does not fit beside
the service); (3) the tokenizer is the benchmark's synthetic piece
table, not Mistral's.

The check: a few seeded prompts are served greedily through the normal
HTTP stream path (prefill, then decode through the paged cache and the
Pallas kernel); the served sequence is teacher-forced through this
reference, and every served token's REFERENCE logit must lie within
``MARGIN`` of the reference's top logit at that position.  Token
equality is the wrong test: with random weights the logits are nearly
flat and bf16 flips near-ties (PR 22 saw ~4 % of steps).
"""

from __future__ import annotations

import json
import random

# Reference logits have a standard deviation of about 1.3 here (unit-rms
# hidden state times a 0.02-std head over 4096 inputs) and the top of
# 32000 sits near 5.  bf16 weights, activations and KV through 8 layers
# move a logit by a few hundredths; a token served from a wrong mask,
# a dropped term, or a cache kept at int8 moves it by tenths to whole
# units and lands far from the top.  See PERF.md for what the chip read.
MARGIN = 0.35
# Share of served tokens that must BE the reference's argmax.
TOP1_SHARE = 0.80
N_PROMPTS = 4
SERVE_TOKENS = 16


def hyper(config: dict) -> dict:
    """The sizes the forward pass needs, by their published names."""
    return {
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["hidden_size"]) // int(config["num_attention_heads"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
    }


def _rmsnorm(x, scale, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * scale


def _rope(x, theta):
    """x [B, S, H, D]; HF rotate-half convention, positions 0..S-1."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d // 2, dtype=jnp.float32) * 2.0 / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def layer(x, w: dict, hp: dict):
    """One decoder block on x [B, S, D]; ``w`` holds float32 weights."""
    import jax
    import jax.numpy as jnp

    b, s, _ = x.shape
    h, kvh, d = hp["heads"], hp["kv_heads"], hp["head_dim"]
    y = _rmsnorm(x, w["attn_ln"], hp["eps"])
    q = _rope((y @ w["q"]).reshape(b, s, h, d), hp["theta"])
    k = _rope((y @ w["k"]).reshape(b, s, kvh, d), hp["theta"])
    v = (y @ w["v"]).reshape(b, s, kvh, d)
    k = jnp.repeat(k, h // kvh, axis=2)  # query head i reads KV head i // (h/kvh)
    v = jnp.repeat(v, h // kvh, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + ctx.reshape(b, s, h * d) @ w["o"]
    y = _rmsnorm(x, w["mlp_ln"], hp["eps"])
    return x + (jax.nn.silu(y @ w["gate"]) * (y @ w["up"])) @ w["down"]


def layer_weights(p: dict) -> dict:
    """One layer of the service's tree, upcast to float32."""
    import jax.numpy as jnp

    f = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    return {
        "attn_ln": f(p["attn_ln"]["scale"]), "mlp_ln": f(p["mlp_ln"]["scale"]),
        "q": f(p["attn"]["q"]["kernel"]), "k": f(p["attn"]["k"]["kernel"]),
        "v": f(p["attn"]["v"]["kernel"]), "o": f(p["attn"]["o"]["kernel"]),
        "gate": f(p["mlp"]["gate"]["kernel"]), "up": f(p["mlp"]["up"]["kernel"]),
        "down": f(p["mlp"]["down"]["kernel"]),
    }


def logits(params: dict, hp: dict, ids):
    """ids [B, S] int32 -> float32 logits [B, S, V]."""
    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda x, w: layer(x, w, hp))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(jnp.asarray(params["embed"]["embedding"]), ids, axis=0)
        x = x.astype(jnp.float32)
        for p in params["layers"]:
            x = step(x, layer_weights(p))
        x = _rmsnorm(x, jnp.asarray(params["final_ln"]["scale"], jnp.float32),
                     hp["eps"])
        return x @ jnp.asarray(params["lm_head"]["kernel"], jnp.float32)


def compare(ref_logits, prompt_lens: list[int], served: list[list[int]]) -> dict:
    """Margins of the served tokens under teacher-forced reference
    logits [B, S, V]: position ``p_len - 1 + j`` predicts served token j."""
    import numpy as np

    ref = np.asarray(ref_logits)
    margins, top1 = [], 0
    for b, (n, toks) in enumerate(zip(prompt_lens, served)):
        for j, tok in enumerate(toks):
            row = ref[b, n - 1 + j]
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


async def check(svc, config: dict, seed: int) -> dict:
    """Serve seeded prompts through the normal path and hold them to
    the reference.  ``svc`` is the harness's running service."""
    import numpy as np

    rng = random.Random(seed)
    vocab = int(config["vocab_size"])
    lens = [rng.randrange(*config.get("check_prompt_tokens", [64, 257]))
            for _ in range(N_PROMPTS)]
    texts = [" ".join(f"w{rng.randrange(3, vocab)}" for _ in range(n - 1))
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
        ids, mask = svc.bundle.tokenizer.encode(text, 4096)
        prompts.append([int(t) for t in ids[: int(mask.sum())]])
        served.append(toks)
    width = max(len(p) + len(s) for p, s in zip(prompts, served))
    batch = np.zeros((len(prompts), width), np.int32)  # right pad: causal, so inert
    for b, (p, s) in enumerate(zip(prompts, served)):
        batch[b, : len(p) + len(s)] = p + s
    ref = logits(svc.engine.params, hyper(config), batch)
    out = compare(ref, [len(p) for p in prompts], served)
    out["prompt_tokens"] = [len(p) for p in prompts]
    out["served_tokens"] = [len(s) for s in served]
    if any(len(s) == 0 for s in served):
        out["correct"] = False
    return out
