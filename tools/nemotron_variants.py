"""Ten ways to get the Nemotron-H block (Mamba-2 | attention | LatentMoE
layers) wrong that its check must catch, and a run of each against the
plain reference.

    python3 -m tools.nemotron_variants [--tokens 2560] [--rehearse FILE]
    python3 -m tools.nemotron_variants --served state_bf16 [--rehearse FILE]

``VARIANTS`` maps a name to ``(LlamaConfig kwargs, params) -> (kwargs,
params[, patches])``: the program with one rule of the block broken — by a
config key, by the tree, or (``patches``: ``"module.attribute"`` ->
replacement, in place for that variant's forward only) where the rule is
neither.  ``tests/test_nemotron_block.py`` holds each to the reference at
a toy size on the CPU; run as a script on the chip, this builds the
benchmark's configuration (``cellbench/configs/nemotron3-super-ep4-d11.json``,
seeded weights as the service draws them), runs the sound program and each
variant through ``llama.lm_logits`` (the prefill wave's forward: ONE
chunked scan over the whole sequence) on one seeded sequence, and prints,
one JSON line each, what the cell's check would read: the rms of (program
- reference) logits over every position, and over the LAST 64 positions
the worst margin of the program's own greedy token under the reference and
the share of them that are the reference's argmax.  The limits in
``cellbench/references/nemotron_h.py`` lie between the sound reading and
these (its table; PERF.md section 4).

``state_bf16`` is the state rows STORED in bfloat16: what a window's or a
wave's scan leaves and what every decode step leaves, rounded.  One wave
forward rounds nothing a logit reads, so that variant is judged where the
state is read: ``--served NAME`` boots the cell's own service
(``cellbench/service.py``) with the variant's patches, or its keyword
overrides of ``LLAMA_CONFIG``, in place and prints what the cell's ``check``
says of it — tokens, logits, and the loop's state row against the
reference's token scan: the served path's reading of a variant (a cell
whose traffic never runs the prefill wave is judged there).
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import importlib
import json
import os
import sys
from typing import NamedTuple

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from tools.trinity_variants import _float8, readings  # noqa: E402  (one reading for every family)

PKG = "mlmicroservicetemplate_tpu"
CELL = "nemotron3-super-ep4-d11.longdoc-closed"


class Family(NamedTuple):
    """What ``main`` runs the variants of: a cell of the benchmark, its
    configuration's reference and the table of broken rules (this file's,
    or another family's: ``tools/gigachat_variants.py``)."""

    cell: str
    reference: str
    variants: dict


def _ssm_leaf(params: dict, path: tuple, fn) -> dict:
    """The tree with ``fn`` applied to leaf ``path`` of every Mamba layer."""
    def one(layer):
        if "ssm" not in layer:
            return layer
        ssm = dict(layer["ssm"])
        if len(path) == 1:
            ssm[path[0]] = fn(ssm[path[0]])
        else:
            ssm[path[0]] = {**ssm[path[0]], path[1]: fn(ssm[path[0]][path[1]])}
        return {**layer, "ssm": ssm}

    return {**params, "layers": [one(layer) for layer in params["layers"]]}


def _norm_before_gate(y, z, scale, groups: int, eps: float):
    import jax
    import jax.numpy as jnp

    shape = y.shape
    y = y.reshape(*shape[:-1], groups, shape[-1] // groups)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + eps)
    return y.reshape(shape) * scale * jax.nn.silu(z)


def bf16_stored(*names: str) -> dict:
    """Patches for ``ops.ssm``'s ``names`` (a recurrence's scan and step)
    with the state they hand back rounded to bfloat16 (``reduce_precision``:
    under jit the compiler folds a cast there and back away — my chip run,
    PR 40)."""
    import jax

    from mlmicroservicetemplate_tpu.ops import ssm

    def rounded(fn):
        @functools.wraps(fn)
        def run(*args, **kw):
            y, state = fn(*args, **kw)
            return y, jax.lax.reduce_precision(state, 8, 7)

        return run

    return {f"ops.ssm.{name}": rounded(getattr(ssm, name)) for name in names}


def _relu(x):
    import jax

    return jax.nn.relu(x)


VARIANTS = {
    "D_dropped": lambda kw, p: (kw, _ssm_leaf(p, ("D",), lambda a: a * 0)),
    # Delta = dt + dt_bias: negative steps, a decay above 1
    "delta_without_softplus": lambda kw, p: (
        kw, p, {"models.llama._ssm_delta": lambda dt, bias: dt + bias}),
    "conv_bias_dropped": lambda kw, p: (
        kw, _ssm_leaf(p, ("conv", "bias"), lambda a: a * 0)),
    "norm_before_gate": lambda kw, p: (
        kw, p, {"models.llama._ssm_gate_norm": _norm_before_gate}),
    "relu_for_relu2": lambda kw, p: (kw, p, {"ops.moe._relu2": _relu}),
    "route_scale_1": lambda kw, p: ({**kw, "route_scale": 1.0}, p),
    "no_renormalisation": lambda kw, p: ({**kw, "norm_topk_prob": False}, p),
    "rotated_qk": lambda kw, p: ({**kw, "nope_on_full": False}, p),
    "state_bf16": lambda kw, p: (kw, p, bf16_stored("ssm_scan", "ssm_step")),
    "float8_weights": lambda kw, p: (kw, _float8(p)),
}


@contextlib.contextmanager
def patched(patches: dict):
    """The program with ``patches`` (``"module.attribute"`` -> value) in place."""
    saved = []
    for dotted, value in patches.items():
        mod_name, attr = dotted.rsplit(".", 1)
        mod = importlib.import_module(f"{PKG}.{mod_name}")
        saved.append((mod, attr, getattr(mod, attr)))
        setattr(mod, attr, value)
    try:
        yield
    finally:
        for mod, attr, value in saved:
            setattr(mod, attr, value)


def broken(name: str, kw: dict, params: dict, variants: dict | None = None):
    """(kwargs, params, patches) of variant ``name``."""
    out = (VARIANTS if variants is None else variants)[name](kw, params)
    return out if len(out) == 3 else (*out, {})


def served(name: str, rehearse: str | None, family: Family) -> int:
    """The cell's own check of a service built with variant ``name`` in
    place — patches, keyword overrides of the service's ``LLAMA_CONFIG``
    (the reference stays the published configuration's), or both; not one
    that edits the weights: one JSON line, the check's."""
    import asyncio

    from cellbench import run as bench_run
    from cellbench import spec
    from cellbench.service import Service

    cell = spec.resolve(family.cell, spec.REPO)
    if rehearse:
        cell.config = bench_run._merge(
            cell.config, spec.load_json(rehearse)["config"])
    kw, params, patches = {}, {"layers": []}, {}
    if name != "sound":
        vkw, vparams, patches = broken(name, kw, params, family.variants)
        if vparams is not params:
            raise SystemExit(f"--served {name}: not a variant that edits the weights")
        # the service is built broken on purpose: what it is held to follows
        cell.config = bench_run._merge(cell.config, {
            "env_json": {"LLAMA_CONFIG": vkw},
            "expect_cfg": {k: v for k, v in vkw.items()
                           if k in cell.config.get("expect_cfg", {})}})
    ref = spec.load_module(
        os.path.join(spec.HERE, "references", family.reference + ".py"),
        f"cellbench_reference_{family.reference}")
    work = os.path.join(spec.REPO, ".cellbench_work")
    os.makedirs(work, exist_ok=True)
    extra = {"DEVICE": "cpu" if rehearse else "tpu", "WARMUP": "1",
             "LOG_LEVEL": "WARNING"}

    async def run() -> dict:
        async with Service(cell.config, work, extra) as svc:
            return await ref.check(svc, cell.config, bench_run.CHECK_SEED)

    with patched(patches):
        out = asyncio.run(run())
    out.pop("memory_peak_bytes_after", None)
    print(json.dumps({"variant": name, "served": True, **out}), flush=True)
    return 0


def main(argv=None, family: Family | None = None) -> int:
    family = family or Family(CELL, "nemotron_h", VARIANTS)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tokens", type=int, default=2560)
    ap.add_argument("--seed", type=int, default=20240924)
    ap.add_argument("--rehearse", default=None,
                    help="a cellbench rehearsal file: tiny sizes, on the CPU")
    ap.add_argument("--only", default="", help="comma-separated variant names")
    ap.add_argument("--served", default=None, metavar="NAME",
                    help="the cell's check over a service with this variant "
                         "(or 'sound') in place")
    a = ap.parse_args(argv)
    if a.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if a.served:
        return served(a.served, a.rehearse, family)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cellbench import run as bench_run
    from cellbench import spec
    from mlmicroservicetemplate_tpu.models import llama

    config = spec.load_json(os.path.join(
        spec.HERE, "configs", family.cell.rsplit(".", 1)[0] + ".json"))
    if a.rehearse:
        config = bench_run._merge(config, spec.load_json(a.rehearse)["config"])
    ref = spec.load_module(
        os.path.join(spec.HERE, "references", family.reference + ".py"),
        f"cellbench_reference_{family.reference}")
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
                      "pattern": cfg.layer_pattern or list(cfg.layer_types),
                      "held": cfg.held}), flush=True)
    # what ``readings`` needs of the sound tree, kept when the tree goes
    at = "embed" if cfg.tie_embeddings else "lm_head"  # a tied head is the table
    head = {at: jax.tree.map(jnp.copy, params[at])}
    for name in ["sound", *family.variants]:
        if a.only and name not in a.only.split(","):
            continue
        if name == "sound":
            vkw, vparams, patches = kw, params, {}
        elif name == "float8_weights" and not a.rehearse:
            # last, and in place: two trees of 9.3 GB do not fit the chip
            vkw, patches = kw, {}
            vparams = jax.jit(_float8, donate_argnums=0)(params)
            params = None
        else:
            vkw, vparams, patches = broken(name, kw, params, family.variants)
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
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # a served run leaves the service's worker threads behind
