"""The seven ways to get the Trinity block wrong that its check must
catch, and a run of each against the plain reference.

    python3 -m tools.trinity_variants [--tokens 2560] [--rehearse FILE]

``VARIANTS`` maps a name to ``(LlamaConfig kwargs, params) -> (kwargs,
params)``: the program with one rule of the block broken.
``tests/test_trinity_block.py`` holds each to the reference at a toy size on
the CPU; run as a script on the chip, this builds the benchmark's
configuration (``cellbench/configs/trinity-mini-d5.json``, seeded
weights as the service draws them), runs the sound program and each
variant through ``llama.lm_logits`` on one seeded sequence longer than
the window, and prints, one JSON line each, what the cell's check would
read: the rms of (program - reference) logits over every position, and
over the LAST 64 positions (all past the window) the worst margin of
the program's own greedy token under the reference and the share of
them that are the reference's argmax.  The limits in
``cellbench/references/trinity.py`` lie between the sound reading and
these (PERF.md section 4 has the table).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def _without(params: dict, leaf: str) -> dict:
    """The tree with ``leaf`` taken out of every layer's ``mlp``."""
    layers = [{**p, "mlp": {k: v for k, v in p["mlp"].items() if k != leaf}}
              for p in params["layers"]]
    return {**params, "layers": layers}


def _float8(params: dict) -> dict:
    """Every leaf rounded to float8_e4m3 — the nearest precision below
    the configuration's bfloat16 — and back to its dtype."""
    import jax

    # reduce_precision, not a cast there and back: under jit the compiler
    # folds bf16 -> f8 -> bf16 away (my chip run, PR 31: the variant read
    # the sound program's figures to the last digit).
    return jax.tree.map(
        lambda a: jax.lax.reduce_precision(a, exponent_bits=4, mantissa_bits=3),
        params)


VARIANTS = {
    # the sliding layers attend over everything before them (RoPE kept)
    "window_ignored": lambda kw, p: ({**kw, "window": 1 << 30}, p),
    "rope_on_full_layer": lambda kw, p: ({**kw, "nope_on_full": False}, p),
    "shared_expert_dropped": lambda kw, p: (
        {**kw, "num_shared_experts": 0}, _without(p, "shared")),
    "route_scale_1": lambda kw, p: ({**kw, "route_scale": 1.0}, p),
    "softmax_for_sigmoid": lambda kw, p: ({**kw, "router_score": "softmax"}, p),
    "selection_bias_dropped": lambda kw, p: (
        {**kw, "router_bias": False}, _without(p, "router_bias")),
    "float8_weights": lambda kw, p: (kw, _float8(p)),
}


def readings(ref, params, ref_hidden, program_logits, tail: int = 64) -> dict:
    """What the check would read of ``program_logits`` [S, V] (the
    program's own) against the reference's final-normed hidden states
    ``ref_hidden`` [S, D] of the same sequence on the SOUND ``params``."""
    import jax.numpy as jnp
    import numpy as np

    rms = ref.logit_rms_error(params, ref_hidden, program_logits)
    want = np.asarray(ref.head_logits(params, ref_hidden[-tail:]))
    tok = np.asarray(jnp.argmax(program_logits[-tail:], axis=-1))
    margin = want.max(axis=-1) - want[np.arange(len(tok)), tok]
    return {"logit_rms_err": rms, "worst_margin": float(margin.max()),
            "mean_margin": float(margin.mean()),
            "top1_share": float((want.argmax(axis=-1) == tok).mean()),
            "positions": int(ref_hidden.shape[0]), "tail": int(len(tok))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tokens", type=int, default=2560)
    ap.add_argument("--seed", type=int, default=20240924)
    ap.add_argument("--rehearse", default=None,
                    help="a cellbench rehearsal file: tiny sizes, on the CPU")
    ap.add_argument("--only", default="", help="comma-separated variant names")
    a = ap.parse_args(argv)
    if a.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cellbench import run as bench_run
    from cellbench import spec
    from mlmicroservicetemplate_tpu.models import llama

    config = spec.load_json(os.path.join(spec.HERE, "configs", "trinity-mini-d5.json"))
    if a.rehearse:
        config = bench_run._merge(config, spec.load_json(a.rehearse)["config"])
    ref = spec.load_module(os.path.join(spec.HERE, "references", "trinity.py"),
                           "cellbench_reference_trinity")
    kw = json.loads(spec.service_env(config)["LLAMA_CONFIG"])
    kw["pallas_interpret"] = bool(a.rehearse)
    dtype = jnp.float32 if a.rehearse else jnp.bfloat16
    cfg = llama.LlamaConfig(**kw)
    params = llama.init_params(jax.random.PRNGKey(0), cfg, dtype=dtype)
    rng = np.random.default_rng(a.seed)
    ids = rng.integers(3, cfg.vocab_size, a.tokens).astype(np.int32)
    # the reference is always of the SOUND weights and rules
    x = ref.hidden(params, ref.hyper(config), ids[None])[0]
    print(json.dumps({"device": jax.devices()[0].device_kind, "tokens": a.tokens,
                      "window": cfg.window, "layer_types": cfg.layer_types}),
          flush=True)
    # what ``readings`` needs of the sound tree, kept when the tree goes
    head = {"lm_head": {"kernel": jnp.copy(params["lm_head"]["kernel"])}}
    for name, broken in {"sound": lambda k, p: (k, p), **VARIANTS}.items():
        if a.only and name not in a.only.split(","):
            continue
        if name == "float8_weights" and not a.rehearse:
            # last, and in place: two trees of 8.5 GB do not fit the chip
            vkw, vparams = kw, jax.jit(_float8, donate_argnums=0)(params)
            params = None
        else:
            vkw, vparams = broken(kw, params)
        vcfg = llama.LlamaConfig(**vkw)
        got = jax.jit(lambda p, i, c=vcfg: llama.lm_logits(
            p, c, i, jnp.ones_like(i), dtype=dtype))(vparams, jnp.asarray(ids)[None])[0]
        print(json.dumps({"variant": name, **readings(ref, head, x, got)}),
              flush=True)
        del got, vparams
    return 0


if __name__ == "__main__":
    sys.exit(main())
