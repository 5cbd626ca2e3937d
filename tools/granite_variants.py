"""Ways to get the Granite-4.0-H block (a Mamba-2 mixer or an unrotated GQA
attention, THEN a 72-expert top-10 block with a shared expert, four scalar
multipliers, a tied head) wrong that its check must catch, and a run of each
against the plain reference.

    python3 -m tools.granite_variants [--tokens 2560] [--rehearse FILE]
    python3 -m tools.granite_variants --served state_bf16 [--rehearse FILE]

The table's form and the two ways to run it are ``tools/nemotron_variants``'
(its ``main`` runs this family's): the sound program and each variant
through ``llama.lm_logits`` on one seeded sequence at the published widths
of ``cellbench/configs/granite-4.0-h-small-ep2-d10.json``, one JSON line each
of what the cell's check would read; ``--served NAME`` boots the cell's own
service with a variant that is patches or keyword overrides in place and
prints its check — the path the cell's traffic runs, and the only place a
state stored in bfloat16 shows (``state_slow_rel_err``).  The limits in
``cellbench/references/granite_hybrid.py`` lie between the sound reading and
these (its table; PERF.md section 4).

``logits_scaling`` 16 and a 0.02 tied table make the logits small (a row's
standard deviation about 0.08): a variant is judged by the same three
limits as the served path, each stated against that spread.  One variant
moves no argmax by construction — a positive scalar on every logit
(``logits_scaling`` dropped) — and is the rms limit's alone.

``decay_bf16`` is the scan's DECAY in bfloat16: the running sums of ``A dt``
the chunk kernel (and the ``jax.numpy`` scan) exponentiates, rounded to
bfloat16 where the program computes them in float32 — a precision below the
one the configuration states for the recurrence, in the one place a wave
forward reads it.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from tools import nemotron_variants  # noqa: E402
from tools.nemotron_variants import _ssm_leaf  # noqa: E402
from tools.trinity_variants import _float8, _without  # noqa: E402

CELL = "granite-4.0-h-small-ep2-d10.longdoc-closed"


def _decay_bf16() -> dict:
    """``ops.ssm._chunk_sums`` with the running sums of ``A dt`` rounded to
    bfloat16 (``reduce_precision``: a cast there and back is folded away
    under jit): every decay of the scan, either form, is then a bfloat16
    number's exponential."""
    import jax

    from mlmicroservicetemplate_tpu.ops import ssm

    sums, call = ssm._chunk_sums, ssm._scan_kernel_call

    def rounded(dt, a, chunk):
        dts, cum = sums(dt, a, chunk)
        return dts, jax.lax.reduce_precision(cum, 8, 7)

    # the kernel's caller is a jit of its own: one with an empty cache, so
    # that it is traced with the rounded sums in place
    fresh = jax.jit(call.__wrapped__, static_argnames=("g", "n", "chunk", "interpret"))
    return {"ops.ssm._chunk_sums": rounded, "ops.ssm._scan_kernel_call": fresh}


def _norm_patch() -> dict:
    """Nemotron's gated norm (a norm over each of EIGHT groups' share of the
    inner width) where Granite has ONE group over all of it."""
    from mlmicroservicetemplate_tpu.models import llama

    sound = llama._ssm_gate_norm

    def eight(y, z, scale, groups: int, eps: float):
        return sound(y, z, scale, 8, eps)

    return {"models.llama._ssm_gate_norm": eight}


VARIANTS = {
    "embedding_multiplier_dropped": lambda kw, p: ({**kw, "embedding_multiplier": 1.0}, p),
    "attention_multiplier_dropped": lambda kw, p: ({**kw, "attention_multiplier": 0.0}, p),
    "residual_multiplier_dropped": lambda kw, p: ({**kw, "residual_multiplier": 1.0}, p),
    "logits_scaling_dropped": lambda kw, p: ({**kw, "logits_scaling": 1.0}, p),
    "conv_bias_dropped": lambda kw, p: (
        kw, _ssm_leaf(p, ("conv", "bias"), lambda a: a * 0)),
    "shared_expert_dropped": lambda kw, p: (
        {**kw, "num_shared_experts": 0, "d_ff_shared": 0}, _without(p, "shared")),
    "rotated_qk": lambda kw, p: ({**kw, "nope_on_full": False}, p),
    "eight_groups_norm": lambda kw, p: (kw, p, _norm_patch()),
    "decay_bf16": lambda kw, p: (kw, p, _decay_bf16()),
    "state_bf16": lambda kw, p: (
        kw, p, nemotron_variants.bf16_stored("ssm_scan", "ssm_step")),
    "float8_weights": lambda kw, p: (kw, _float8(p)),
}


FAMILY = nemotron_variants.Family(CELL, "granite_hybrid", VARIANTS)


if __name__ == "__main__":
    code = nemotron_variants.main(family=FAMILY)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # a served run leaves the service's worker threads behind
