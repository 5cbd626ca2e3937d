"""Ways to get the GigaChat3.5 block (Gated-DeltaNet | gated latent
attention, each with a dense or expert FFN, sandwich norms) wrong that its
check must catch, and a run of each against the plain reference.

    python3 -m tools.gigachat_variants [--tokens 2560] [--rehearse FILE]
    python3 -m tools.gigachat_variants --served state_bf16 [--rehearse FILE]

The table's form and the two ways to run it are ``tools/nemotron_variants``'
(its ``main`` runs this family's): the sound program and each variant
through ``llama.lm_logits`` on one seeded sequence at the published widths
of ``cellbench/configs/gigachat35-ep16-d5.json``, one JSON line each of
what the cell's check would read; ``--served NAME`` boots the cell's own
service with a variant that is patches or keyword overrides in place and
prints its check — the path the cell's traffic runs (prompt windows, then
the decode step: ``SEQ_BUCKETS`` 128 keeps every prompt off the prefill
wave), and the only place a state stored in bfloat16 shows
(``state_slow_rel_err``).
The limits in ``cellbench/references/gigachat35.py`` lie between the sound
reading and these (its table; PERF.md section 4).
"""

from __future__ import annotations

import functools
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from tools import nemotron_variants  # noqa: E402
from tools.trinity_variants import _float8  # noqa: E402

CELL = "gigachat35-ep16-d5.longdoc-closed"


def _one_plus_w(cfg, p, x):
    """The norm's other reading: scale ``1 + w`` for ``2 sigmoid(w)``."""
    import jax.numpy as jnp

    from mlmicroservicetemplate_tpu.models.common import rmsnorm

    return rmsnorm({"scale": 1.0 + p["scale"].astype(jnp.float32)}, x,
                   eps=cfg.rms_eps)


def _beta_one() -> dict:
    import jax.numpy as jnp

    from mlmicroservicetemplate_tpu.ops import ssm

    def whole(fn, at):  # ``beta`` is argument ``at`` of both
        @functools.wraps(fn)
        def run(*args, **kw):
            return fn(*args[:at], jnp.ones_like(args[at]), *args[at + 1:], **kw)

        return run

    return {"ops.ssm.gdn_scan": whole(ssm.gdn_scan, 2),
            "ops.ssm.gdn_step": whole(ssm.gdn_step, 4)}


def _no_clamp() -> dict:
    """Every SwiGLU (dense, routed, shared) without its clamp.  Patches, not
    ``swiglu_limit`` 0: the init widens the gates under that key, and a
    service built without it would draw weights the clamp never binds on."""
    import jax

    from mlmicroservicetemplate_tpu.ops import moe

    clamped = moe.expert_ffn

    @functools.wraps(clamped)
    def unclamped(*args, **kw):
        return clamped(*args, **{**kw, "limit": 0.0})

    return {"ops.moe.expert_ffn": unclamped,
            "models.llama._swiglu_gate": lambda cfg, gate: jax.nn.silu(gate),
            "models.llama._swiglu_up": lambda cfg, up: up}


VARIANTS = {
    "clamp_dropped": lambda kw, p: (kw, p, _no_clamp()),
    # a patch, not the config key: a service built without the key draws no
    # gate leaf, and the reference (of the SOUND rules) needs it
    "attn_gate_dropped": lambda kw, p: (
        kw, p, {"models.llama._attn_gate": lambda *a: None}),
    "post_norm_dropped": lambda kw, p: ({**kw, "sandwich_norm": False}, p),
    "norm_one_plus_w": lambda kw, p: (kw, p, {"models.llama._norm": _one_plus_w}),
    # every token replaces ALL the state held for its key: beta = 1
    "delta_beta_1": lambda kw, p: (kw, p, _beta_one()),
    "route_scale_1": lambda kw, p: ({**kw, "route_scale": 1.0}, p),
    "no_renormalisation": lambda kw, p: ({**kw, "norm_topk_prob": False}, p),
    "state_bf16": lambda kw, p: (
        kw, p, nemotron_variants.bf16_stored("gdn_scan", "gdn_step")),
    "float8_weights": lambda kw, p: (kw, _float8(p)),
}

FAMILY = nemotron_variants.Family(CELL, "gigachat35", VARIANTS)


if __name__ == "__main__":
    code = nemotron_variants.main(family=FAMILY)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # a served run leaves the service's worker threads behind
