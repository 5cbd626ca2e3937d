"""Ways to get the Jamba block (Mamba-1 | multi-query attention, a dense MLP
behind each, a tied head) wrong that its check must catch, and a run of each
against the plain reference.

    python3 -m tools.jamba_variants [--tokens 2560] [--rehearse FILE]
    python3 -m tools.jamba_variants --served state_bf16 [--rehearse FILE]

The table's form and the two ways to run it are ``tools/nemotron_variants``'
(its ``main`` runs this family's): the sound program and each variant
through ``llama.lm_logits`` on one seeded sequence at the published widths
of ``cellbench/configs/jamba2-3b-d28.json``, one JSON line each of what the
cell's check would read; ``--served NAME`` boots the cell's own service with
a variant that is patches or keyword overrides in place and prints its
check — the path the cell's traffic runs, and the only place a state stored
in bfloat16 shows (``state_slow_rel_err``).  The limits in
``cellbench/references/jamba.py`` lie between the sound reading and these
(its table; PERF.md section 4).

An inner norm is broken by its learned SCALE (ones for the drawn leaf), not
dropped whole: without the norm ``dt`` keeps ``x_proj``'s raw size and the
check would read a different model's overflow, not a near miss.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from tools import nemotron_variants  # noqa: E402
from tools.nemotron_variants import _ssm_leaf  # noqa: E402
from tools.trinity_variants import _float8  # noqa: E402

CELL = "jamba2-3b-d28.longdoc-closed"


def _untied(kw, p):
    """A head of its own, drawn as an untied configuration draws it."""
    import jax

    from mlmicroservicetemplate_tpu.models.common import normal_init

    table = p["embed"]["embedding"]
    kernel = normal_init(jax.random.PRNGKey(1), table.shape[::-1], std=0.02)
    return ({**kw, "tie_embeddings": False},
            {**p, "lm_head": {"kernel": kernel.astype(table.dtype)}})


def _ones(a):
    import jax.numpy as jnp

    return jnp.ones_like(a)


def _normed_gate(y, z):
    """Mamba-2's gated norm where Mamba-1 has none."""
    import jax
    import jax.numpy as jnp

    y = y * jax.nn.silu(z)
    return y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + 1e-6)


VARIANTS = {
    "dt_norm_scale_dropped": lambda kw, p: (
        kw, _ssm_leaf(p, ("dt_norm", "scale"), _ones)),
    "b_norm_scale_dropped": lambda kw, p: (
        kw, _ssm_leaf(p, ("b_norm", "scale"), _ones)),
    "c_norm_scale_dropped": lambda kw, p: (
        kw, _ssm_leaf(p, ("c_norm", "scale"), _ones)),
    "conv_bias_dropped": lambda kw, p: (
        kw, _ssm_leaf(p, ("conv", "bias"), lambda a: a * 0)),
    "D_dropped": lambda kw, p: (kw, _ssm_leaf(p, ("D",), lambda a: a * 0)),
    "gate_normed": lambda kw, p: (kw, p, {"models.llama._mamba1_gate": _normed_gate}),
    "rotated_qk": lambda kw, p: ({**kw, "nope_on_full": False}, p),
    "untied_head": _untied,
    "state_bf16": lambda kw, p: (
        kw, p, nemotron_variants.bf16_stored("mamba1_scan", "mamba1_step")),
    "float8_weights": lambda kw, p: (kw, _float8(p)),
}


FAMILY = nemotron_variants.Family(CELL, "jamba", VARIANTS)


if __name__ == "__main__":
    code = nemotron_variants.main(family=FAMILY)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # a served run leaves the service's worker threads behind
