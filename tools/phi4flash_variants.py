"""Ways to get the SambaY block (Mamba-1 | window / full differential
attention | Gated Memory Units | cross-attention over ONE layer's keys, a
dense MLP behind each, LayerNorm, a tied head) wrong that its check must
catch, and a run of each against the plain reference.

    python3 -m tools.phi4flash_variants [--tokens 2560] [--rehearse FILE]
    python3 -m tools.phi4flash_variants --served state_bf16 [--rehearse FILE]

The table's form and the two ways to run it are ``tools/nemotron_variants``'
(its ``main`` runs this family's): the sound program and each variant
through ``llama.lm_logits`` — every layer at every position — on one seeded
sequence at the published widths of
``cellbench/configs/phi4-mini-flash-d32.json``, one JSON line each of what the
cell's check would read; ``--served NAME`` boots the cell's own service with
a variant that is patches or keyword overrides in place and prints its check
— the path the cell's traffic runs, and the only place a state stored in
bfloat16 shows (``state_slow_rel_err``).  The limits in
``cellbench/references/phi4flash.py`` lie between the sound reading and these
(its table; PERF.md section 4).
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from tools import nemotron_variants  # noqa: E402
from tools.nemotron_variants import _ssm_leaf  # noqa: E402
from tools.trinity_variants import _float8  # noqa: E402

CELL = "phi4-mini-flash-d32.longdoc-closed"


def _inner_norms(kw, p):
    """Jamba's three inner RMSNorms (unit scales) on a Mamba that has none."""
    import jax.numpy as jnp

    def one(layer):
        if "ssm" not in layer:
            return layer
        m = layer["ssm"]
        r, n = m["dt_proj"]["kernel"].shape[0], m["A_log"].shape[0]
        dt = m["in"]["kernel"].dtype
        return {**layer, "ssm": {
            **m, "dt_norm": {"scale": jnp.ones((r,), dt)},
            "b_norm": {"scale": jnp.ones((n,), dt)},
            "c_norm": {"scale": jnp.ones((n,), dt)}}}

    return ({**kw, "ssm_inner_norms": True},
            {**p, "layers": [one(layer) for layer in p["layers"]]})


def _gated_memory(y, z):
    """m taken AFTER the Mamba layer's own gate."""
    import jax

    return y * jax.nn.silu(z)


def _bf16_scores(q, k, v, mask=None, bias=None, scale=None):
    """``common.mha_attention`` with its scores and softmax in bfloat16."""
    import jax
    import jax.numpy as jnp

    bf = jnp.bfloat16
    logits = (jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(bf)
              * jnp.asarray(scale or q.shape[-1] ** -0.5, bf))
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.asarray(-1e9, bf))
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _last_window(cfg) -> int:
    """A 'cross' layer reading the last WINDOW layer's keys, not the full one's."""
    return max(i for i, t in enumerate(cfg.layer_types) if t == "window")


VARIANTS = {
    "no_lambda": lambda kw, p: (
        kw, p, {"models.llama._diff_lambda": lambda lv, init: 0.0}),
    "lambda_learned_part_dropped": lambda kw, p: (
        kw, p, {"models.llama._diff_lambda": lambda lv, init: init}),
    "no_subnorm": lambda kw, p: (
        kw, p, {"models.llama._diff_subnorm": lambda s, o, eps: o}),
    "rotated_qk": lambda kw, p: (
        {**kw, "nope_on_full": False, "nope_on_window": False}, p),
    "mamba_inner_norms": _inner_norms,
    "memory_after_gate": lambda kw, p: (
        kw, p, {"models.llama._gmu_memory": _gated_memory}),
    "cross_reads_window_keys": lambda kw, p: (
        kw, p, {"models.llama._kv_source": _last_window}),
    "scores_bf16": lambda kw, p: (
        kw, p, {"models.llama.mha_attention": _bf16_scores}),
    "D_dropped": lambda kw, p: (kw, _ssm_leaf(p, ("D",), lambda a: a * 0)),
    "state_bf16": lambda kw, p: (
        kw, p, nemotron_variants.bf16_stored("mamba1_scan", "mamba1_step")),
    "float8_weights": lambda kw, p: (kw, _float8(p)),
}


FAMILY = nemotron_variants.Family(CELL, "phi4flash", VARIANTS)


if __name__ == "__main__":
    code = nemotron_variants.main(family=FAMILY)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # a served run leaves the service's worker threads behind
