"""int8 KV-cache A/B (QUANT_KV, VERDICT r3 item 7).

At the shapes where continuous batching pays (B=8, long context), KV
reads are the SECOND HBM-bandwidth term of the decode step after
weights: B=8, S=1024 llama-1.1B reads ~185 MB of bf16 KV per step
against 1.1 GB of int8 weights.  int8 KV halves that term; this
measures whether the saving survives the quantize/dequant work, per
the repo's "measure it or cut it" standard.

Two-scan differencing per config (the dispatch round-trip cancels); decode-step time
for dense vs int8 KV at several context lengths, on int8 weights
(where the KV share is largest — QUANTIZE=0 remeasures on bf16).

    MODEL_NAME=llama python benchmarks/kv_quant_ab.py
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

BATCH = int(os.environ.get("KV_BATCH", "8"))
CONTEXTS = tuple(
    int(x) for x in os.environ.get("KV_CONTEXTS", "512,1024,1792").split(",")
)


def step_ms(kv_quant: bool, s_len: int, pallas: bool = False) -> tuple[float, bool]:
    import jax

    from timing import chunked_time_per_step

    from mlmicroservicetemplate_tpu.engine import InferenceEngine
    from mlmicroservicetemplate_tpu.models.registry import build_model
    from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
    from mlmicroservicetemplate_tpu.utils.config import ServiceConfig

    # Explicit both ways: pallas_decode now AUTO-enables with kv_quant
    # on TPU, so the XLA baseline arm must force it OFF (popping the
    # env would silently measure Pallas-vs-Pallas).
    os.environ["USE_PALLAS_DECODE"] = "1" if pallas else "0"
    cfg = ServiceConfig(
        device=os.environ.get("DEVICE", "tpu"),
        model_name=os.environ.get("MODEL_NAME", "llama"),
        quantize=(os.environ.get("QUANTIZE", "int8") or None),
        quant_kv="int8" if kv_quant else None,
        warmup=False,
        batch_buckets=(BATCH,),
        seq_buckets=(s_len,),
        max_decode_len=32,
        stream_chunk_tokens=16,
        continuous_batching=False,
    )
    bundle = build_model(cfg)
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    rng = np.random.default_rng(0)
    feats = [
        {"input_ids": rng.integers(5, bundle.cfg.vocab_size, s_len).astype(np.int32),
         "length": np.int32(s_len)}
        for _ in range(BATCH)
    ]
    with eng._lock:
        ids, mask, _ = eng._collate_text(feats)
        sp, _ = eng._collate_sample(feats, ids.shape[0])
        ids, mask = eng.replicas.place_batch(ids, mask)
        state, _ = eng._start(
            eng.params, ids, mask, sp, eng.max_decode_len, eng.chunk_tokens, False
        )
        jax.block_until_ready(state.done)
    per, noisy = chunked_time_per_step(
        eng._gen_chunk, eng.params, state,
        iters=int(os.environ.get("CHUNK_ITERS", "48")),
    )
    return per * 1e3, noisy


def main() -> None:
    from mlmicroservicetemplate_tpu.runtime.device import apply_device_env

    apply_device_env(os.environ.get("DEVICE", "tpu").lower())
    rows = []
    # Pallas decode-attention columns (VERDICT r4 next #5): in-kernel
    # int8 dequant tests the hypothesis behind the measured XLA
    # kv-quant loss, and the dense kernel removes the GQA repeat.
    # KV_PALLAS=0 skips them.
    do_pallas = os.environ.get("KV_PALLAS", "1").lower() not in (
        "0", "false", "no"
    )
    for s_len in CONTEXTS:
        dense_ms, n1 = step_ms(False, s_len)
        q_ms, n2 = step_ms(True, s_len)
        row = {
            "context": s_len,
            "batch": BATCH,
            "dense_kv_step_ms": round(dense_ms, 3),
            "int8_kv_step_ms": round(q_ms, 3),
            "timing_noisy": bool(n1 or n2),
            "speedup": round(dense_ms / max(q_ms, 1e-9), 3),
        }
        if do_pallas:
            pd_ms, n3 = step_ms(False, s_len, pallas=True)
            pq_ms, n4 = step_ms(True, s_len, pallas=True)
            row.update({
                "dense_pallas_step_ms": round(pd_ms, 3),
                "int8_pallas_step_ms": round(pq_ms, 3),
                "pallas_dense_speedup": round(dense_ms / max(pd_ms, 1e-9), 3),
                "pallas_int8_vs_dense_xla": round(
                    dense_ms / max(pq_ms, 1e-9), 3
                ),
                "timing_noisy_pallas": bool(n3 or n4),
            })
        rows.append(row)
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({
        "model": os.environ.get("MODEL_NAME", "llama"),
        "weights": os.environ.get("QUANTIZE", "int8") or "bf16",
        "rows": rows,
    }))


if __name__ == "__main__":
    main()
