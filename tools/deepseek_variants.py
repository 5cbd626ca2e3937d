"""Eight ways to get the DeepSeek-V2 block wrong that its check must
catch, and a run of each against the plain reference.

    python3 -m tools.deepseek_variants [--tokens 2560] [--rehearse FILE]

``VARIANTS`` maps a name to ``(LlamaConfig kwargs, params) -> (kwargs,
params[, patches])``: the program with one rule of the block broken —
by a config key, by the tree, or (``patches``: attribute -> replacement on
``models/llama.py``, in place for that variant's forward only) where the
rule is neither.  ``tests/test_deepseek_block.py`` holds each to the
reference at a toy size on the CPU; run as a script on the chip, this
builds the benchmark's configuration
(``cellbench/configs/deepseek-v2-ep4-d5.json``, seeded weights as the
service draws them), runs the sound program and each variant through
``llama.lm_logits`` on one seeded sequence, and prints, one JSON line
each, what the cell's check would read: the rms of (program - reference)
logits over every position, and over the LAST 64 positions the worst
margin of the program's own greedy token under the reference and the
share of them that are the reference's argmax.  The limits in
``cellbench/references/deepseek_v2.py`` lie between the sound reading and
these (PERF.md section 4 has the table).  A ninth way — values read from
all of a cached row's lanes, not its first ``kv_lora_rank`` — lives in
the decode kernel alone, which this forward does not run:
``tests/test_deepseek_block.py`` holds the kernel to it.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from tools.trinity_variants import _float8, readings  # noqa: E402  (one reading for every family)


def _yarn(kw: dict, **over) -> dict:
    return {**kw, "rope_scaling": {**dict(kw["rope_scaling"]), **over}}


def _no_norm_at(width: int) -> dict:
    """``llama.rmsnorm`` passing rows ``width`` wide through untouched:
    the inner norm of that width (c: kv_lora_rank, c_q: q_lora_rank; no
    other norm of the block is that wide) is dropped, scale and all."""
    from mlmicroservicetemplate_tpu.models import llama

    real = llama.rmsnorm

    def rmsnorm(p, x, eps=1e-6):
        return x if x.shape[-1] == width else real(p, x, eps=eps)

    return {"rmsnorm": rmsnorm}


VARIANTS = {
    # the softmax scale without YaRN's mscale^2 (cos / sin keep their ratio 1)
    "no_mscale_in_scale": lambda kw, p: (_yarn(kw, mscale=0.0, mscale_all_dim=0.0), p),
    # plain RoPE frequencies for YaRN's blend (no pair makes so few turns:
    # the ramp is 0 everywhere), the scale's mscale^2 kept
    "plain_rope_for_yarn": lambda kw, p: (_yarn(kw, beta_fast=1e-9, beta_slow=1e-9), p),
    "no_norm_on_c": lambda kw, p: (kw, p, _no_norm_at(kw["kv_lora_rank"])),
    "no_norm_on_cq": lambda kw, p: (kw, p, _no_norm_at(kw["q_lora_rank"])),
    "route_scale_1": lambda kw, p: ({**kw, "route_scale": 1.0}, p),
    # plain top-6 over all 160
    "no_group_limit": lambda kw, p: ({**kw, "n_group": 0, "topk_group": 0}, p),
    "renormalised_weights": lambda kw, p: ({**kw, "norm_topk_prob": True}, p),
    "float8_weights": lambda kw, p: (kw, _float8(p)),
}


@contextlib.contextmanager
def patched(patches: dict):
    """``models/llama.py`` with ``patches`` in place."""
    from mlmicroservicetemplate_tpu.models import llama

    saved = {k: getattr(llama, k) for k in patches}
    for k, v in patches.items():
        setattr(llama, k, v)
    try:
        yield
    finally:
        for k, v in saved.items():
            setattr(llama, k, v)


def broken(name: str, kw: dict, params: dict):
    """(kwargs, params, patches) of variant ``name``."""
    out = VARIANTS[name](kw, params)
    return out if len(out) == 3 else (*out, {})


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

    config = spec.load_json(
        os.path.join(spec.HERE, "configs", "deepseek-v2-ep4-d5.json"))
    if a.rehearse:
        config = bench_run._merge(config, spec.load_json(a.rehearse)["config"])
    ref = spec.load_module(os.path.join(spec.HERE, "references", "deepseek_v2.py"),
                           "cellbench_reference_deepseek_v2")
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
                      "attention": cfg.attention, "held": cfg.held,
                      "attn_scale": cfg.attn_scale}), flush=True)
    # what ``readings`` needs of the sound tree, kept when the tree goes
    head = {"lm_head": {"kernel": jnp.copy(params["lm_head"]["kernel"])}}
    for name in ["sound", *VARIANTS]:
        if a.only and name not in a.only.split(","):
            continue
        if name == "sound":
            vkw, vparams, patches = kw, params, {}
        elif name == "float8_weights" and not a.rehearse:
            # last, and in place: two trees of 10.3 GB do not fit the chip
            vkw, patches = kw, {}
            vparams = jax.jit(_float8, donate_argnums=0)(params)
            params = None
        else:
            vkw, vparams, patches = broken(name, kw, params)
        vcfg = llama.LlamaConfig(**vkw)
        with patched(patches):
            got = jax.jit(lambda p, i, c=vcfg: llama.lm_logits(
                p, c, i, jnp.ones_like(i), dtype=dtype))(
                    vparams, jnp.asarray(ids)[None])[0]
        print(json.dumps({"variant": name, **readings(ref, head, x, got)}),
              flush=True)
        del got, vparams
    return 0


if __name__ == "__main__":
    sys.exit(main())
