"""Plain reference for BERT-base sequence classification, and the
check that holds ``/predict`` to it.

Forward pass in straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")`` from the published
description (arXiv:1810.04805 and the HF implementation): word +
learned position + token-type embeddings, LayerNorm (eps 1e-12), 12
post-LN encoder blocks (multi-head attention with a key padding mask,
erf-GELU MLP), tanh pooler over [CLS], linear classifier, softmax.
No kernel, no bucketing, no batching.

Departures: weights are the service's seeded random init (a 2-label
head, the repo's ``BertConfig.num_labels``); the tokenizer is the
repo's byte fallback, not WordPiece.

The check: seeded texts across both seq buckets go through ``/predict``
(batcher, bucketed jit, the fused attention kernel, bf16) and the
served class probabilities must lie within ``PROB_ATOL`` of the
reference's.
"""

from __future__ import annotations

import random

# bf16 weights and activations through 12 layers move a probability by
# about 2e-3 (PR 22 measured 0.00166 on the chip between two served
# paths); a wrong mask or a missing layer moves it by 1e-1.
PROB_ATOL = 2e-2
N_TEXTS = 8


def _ln(x, p, eps):
    import jax.numpy as jnp

    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * f32(p["scale"]) + f32(p["bias"])


def f32(a):
    import jax.numpy as jnp

    return jnp.asarray(a, jnp.float32)


def _dense(p, x):
    return x @ f32(p["kernel"]) + f32(p["bias"])


PAD_TO = 128  # texts are padded to a multiple of this: few shapes to compile


def _forward(params: dict, ids, n, heads: int, eps: float):
    """ids [S] right-padded, ``n`` real tokens -> class probabilities.
    Padded KEYS are masked out of every softmax; padded queries are
    computed and never read (the classifier reads position 0)."""
    import jax
    import jax.numpy as jnp

    s = ids.shape[0]
    keep = jnp.arange(s) < n
    e = params["embeddings"]
    x = (f32(e["word"]["embedding"])[ids]
         + f32(e["position"]["embedding"])[:s]
         + f32(e["token_type"]["embedding"])[0][None])
    x = _ln(x, e["ln"], eps)
    d = x.shape[-1] // heads
    for p in params["layers"]:
        a = p["attn"]
        q = _dense(a["q"], x).reshape(s, heads, d)
        k = _dense(a["k"], x).reshape(s, heads, d)
        v = _dense(a["v"], x).reshape(s, heads, d)
        sc = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
        sc = jnp.where(keep[None, None, :], sc, -jnp.inf)
        ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
        x = _ln(x + _dense(a["out"], ctx.reshape(s, heads * d)), a["ln"], eps)
        m = p["mlp"]
        h = _dense(m["down"], jax.nn.gelu(_dense(m["up"], x), approximate=False))
        x = _ln(x + h, m["ln"], eps)
    pooled = jnp.tanh(_dense(params["pooler"], x[0]))
    return jax.nn.softmax(_dense(params["classifier"], pooled))


def probs(params: dict, config: dict, ids):
    """ids [S] int32 (one unpadded text) -> class probabilities."""
    import functools

    import jax
    import numpy as np

    n = int(ids.shape[0])
    rows = params["embeddings"]["position"]["embedding"].shape[0]
    padded = np.zeros((min(-(-n // PAD_TO) * PAD_TO, rows),), np.int32)
    padded[:n] = np.asarray(ids)
    fn = jax.jit(functools.partial(
        _forward, heads=int(config["num_attention_heads"]),
        eps=float(config["layer_norm_eps"])))
    with jax.default_matmul_precision("highest"):
        return fn(params, padded, n)


async def check(svc, config: dict, seed: int) -> dict:
    import numpy as np

    from cellbench.traffic import prompt_text

    rng = random.Random(seed)
    buckets = sorted(int(b) for b in config["env"]["SEQ_BUCKETS"].split(","))
    lens = []
    lo = 8
    for b in buckets:  # texts that land in every seq bucket
        lens += [rng.randrange(lo, b + 1) for _ in range(N_TEXTS // len(buckets))]
        lo = b + 1
    worst, rows = 0.0, []
    for n in lens:
        text = prompt_text(n, config["prompt"], rng)
        async with svc.http.post("/predict", json={"text": text}) as r:
            if r.status != 200:
                return {"correct": False, "error": f"HTTP {r.status}"}
            got = (await r.json())["probs"]
        ids, mask = svc.bundle.tokenizer.encode(text, max(buckets))
        ids = np.asarray(ids[: int(mask.sum())], np.int32)
        ref = np.asarray(probs(svc.engine.params, config, ids))
        diff = float(np.max(np.abs(ref - np.asarray(got, np.float32))))
        worst = max(worst, diff)
        rows.append({"tokens": int(ids.shape[0]), "max_abs_diff": diff})
    return {"correct": bool(np.isfinite(worst)) and worst <= PROB_ATOL,
            "worst_abs_prob_diff": worst, "limit": PROB_ATOL, "texts": rows}
