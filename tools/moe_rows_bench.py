"""One expert layer's block on the chip, the two row shuffles by the DMA
kernels against XLA's form, at the shapes the expert cells' prompt
dispatches and waves run (docs/kernel_tuning.md's table):

    chiprun -- python3 -m tools.moe_rows_bench [--cells granite,dsv2] [--pieces]

Random weights and tokens from a seed; the router is left to send what it
sends (about an even share).  ``--pieces`` also times the two kernels and
XLA's two shuffles alone.  Prints one JSON line a shape.  Needs the chip:
times from a CPU mean nothing and the tool refuses to print them.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from mlmicroservicetemplate_tpu.ops import moe

#: cell -> (tokens a call, top-k, held, published, d_model, latent or 0,
#: expert width, activation)
SHAPES = {
    "granite-dispatch": (3072, 10, 36, 72, 4096, 0, 768, "silu"),
    "dsv2-window": (2048, 6, 40, 160, 5120, 0, 1536, "silu"),
    "nemotron-dispatch": (3072, 22, 128, 512, 4096, 1024, 2688, "relu2"),
    "gigachat-dispatch": (3072, 8, 16, 256, 7168, 0, 2048, "silu"),
    "trinity-dispatch": (3072, 8, 128, 128, 2048, 0, 1024, "silu"),
    "olmoe-wave": (8192, 8, 64, 64, 2048, 0, 1024, "silu"),
    "olmoe-wave64": (64, 8, 64, 64, 2048, 0, 1024, "silu"),
    "granite-step": (32, 10, 36, 72, 4096, 0, 768, "silu"),
}


def timed(fn, *args, reps: int = 20) -> float:
    """Milliseconds a call, the mean of ``reps`` after two warm-ups."""
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) * 1e3 / reps


def tree(key, held, pub, d, latent, w, act):
    bf, wide = jnp.bfloat16, latent or d
    ks = jax.random.split(key, 6)

    def stack(k_, *shape):
        return (jax.random.normal(k_, shape, jnp.float32) * 0.02).astype(bf)

    mlp = {"router": {"kernel": jax.random.normal(ks[0], (d, pub), jnp.float32) * 0.02},
           "up": {"kernel": stack(ks[1], held, wide, w)},
           "down": {"kernel": stack(ks[2], held, w, wide)}}
    if act == "silu":
        mlp["gate"] = {"kernel": stack(ks[3], held, wide, w)}
    if latent:
        mlp["latent_down"] = {"kernel": stack(ks[4], d, latent)}
        mlp["latent_up"] = {"kernel": stack(ks[5], latent, d)}
    return mlp


def layer(fit: bool, k: int, act: str):
    """A jitted ``expert_ffn`` traced with the rule forced to ``fit``."""
    def run(h, mlp, valid):
        keep = moe.row_kernels_fit
        moe.row_kernels_fit = lambda *a: fit
        try:
            return moe.expert_ffn(h, mlp, k, True, valid, act=act)[0]
        finally:
            moe.row_kernels_fit = keep
    return jax.jit(run)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(SHAPES))
    ap.add_argument("--pieces", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--same-tokens", action="store_true",
                    help="every token the same row, as a boot's warm-up sends")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("moe_rows_bench times the chip: no TPU here")
    for name in args.cells.split(","):
        t, k, held, pub, d, latent, w, act = SHAPES[name]
        key = jax.random.PRNGKey(args.seed)
        mlp = tree(key, held, pub, d, latent, w, act)
        h = jax.random.normal(jax.random.fold_in(key, 7), (t, d), jnp.float32).astype(jnp.bfloat16)
        if args.same_tokens:
            h = jnp.broadcast_to(h[:1], h.shape) + jnp.zeros_like(h)
        valid = jnp.ones((t,), bool)
        wide, n = latent or d, t * k
        line = {"shape": name, "rows": n, "row_bytes": wide * 2,
                "rule": moe.row_kernels_fit(n, wide, jnp.bfloat16)}
        outs = {}
        for label, fit in (("xla", False), ("kernels", True)):
            if fit and (n % moe.ROW_TILE or wide % moe.LANES):
                continue
            fn = layer(fit, k, act).lower(h, mlp, valid).compile()
            t0 = time.perf_counter()
            jax.block_until_ready(fn(h, mlp, valid))
            line[f"first_call_{label}_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
            line[f"layer_{label}_ms"] = round(timed(fn, h, mlp, valid), 4)
            outs[label] = fn(h, mlp, valid)
        if len(outs) == 2:
            line["max_abs_diff"] = float(jnp.max(jnp.abs(
                outs["xla"].astype(jnp.float32) - outs["kernels"].astype(jnp.float32))))
        if args.pieces and "kernels" in outs:
            n_live = jnp.int32(-(-n * held // pub))
            src = jax.random.randint(key, (n,), 0, t, jnp.int32)
            pos = jax.random.permutation(key, n).astype(jnp.int32).reshape(t, k)
            wts = jax.random.uniform(key, (t, k), jnp.float32)
            rows = h if not latent else h[:, :latent]
            ys = jax.random.normal(key, (n, wide), jnp.float32).astype(jnp.bfloat16)
            rung = moe.row_rungs(n, held, pub)[0]
            line["pieces_ms"] = {
                "sorted_rows": round(timed(moe.sorted_rows, rows, src, n_live), 4),
                "combine_rows": round(timed(moe.combine_rows, ys, pos, wts, n_live), 4),
                "row_slabs": round(timed(jax.jit(
                    lambda y, n_: moe._live_slabs(y, n_, False)), ys, n_live), 4),
                "xla_take_rung": round(timed(jax.jit(lambda r, s: jnp.pad(
                    jnp.take(r, s[:rung], axis=0), ((0, n - rung), (0, 0)))), rows, src), 4),
                "xla_combine_rung": round(timed(jax.jit(lambda y, p, w_: jnp.sum(
                    jnp.take(jnp.concatenate([y[:rung], jnp.zeros_like(y[:1])]),
                             jnp.where(p.T < n_live, p.T, rung).reshape(-1), axis=0
                             ).reshape(k, t, wide).astype(jnp.float32)
                    * w_.T[:, :, None], axis=0).astype(y.dtype)), ys, pos, wts), 4),
            }
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
