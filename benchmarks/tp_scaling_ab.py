"""Tensor-parallel decode-scaling A/B (TP serving, round 23).

Decode-step time for TP∈{1,2} × KV∈{dense,int8} through the
PRODUCTION engine path: the registry builds the `('replica','tp')`
placement from the `TP` knob, params shard Megatron-style, the KV
cache shards its heads axis, and decode attention runs under
`shard_map`.  Two-scan differencing per config (the dispatch round-trip cancels).

HONEST-NEGATIVE NOTE (pre-round CPU record, removed in PR 22): on CPU the virtual host
devices share ONE core, so TP=2 pays the collective + dispatch
overhead with zero added FLOP throughput — it measures SLOWER than
TP=1 by construction.  The CPU run is a correctness/overhead probe;
the throughput/MFU claim needs a four-chip run (not measured yet).

    MODEL_NAME=llama python benchmarks/tp_scaling_ab.py
    TP_AB=0 skips it in run_all.py.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# A TP=2 mesh needs ≥2 devices; on the host platform force the
# virtual-device split before the first jax import (no-op on TPU).
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )

import numpy as np  # noqa: E402

BATCH = int(os.environ.get("TP_BATCH", "4"))
CONTEXT = int(os.environ.get("TP_CONTEXT", "256"))
WIDTHS = tuple(
    int(x) for x in os.environ.get("TP_WIDTHS", "1,2").split(",")
)


def step_ms(tp: int, kv_quant: bool) -> tuple[float, bool]:
    import jax

    from timing import chunked_time_per_step

    from mlmicroservicetemplate_tpu.engine import InferenceEngine
    from mlmicroservicetemplate_tpu.models.registry import build_model
    from mlmicroservicetemplate_tpu.utils.config import ServiceConfig

    cfg = ServiceConfig(
        device=os.environ.get("DEVICE", "tpu"),
        model_name=os.environ.get("MODEL_NAME", "llama"),
        tp=tp,
        # Pin the replica axis so the A/B isolates TP width: without
        # this the TP=1 arm's REPLICAS=0 default data-parallels over
        # every visible device (8 here via the forced host split).
        replicas=1,
        quant_kv="int8" if kv_quant else None,
        warmup=False,
        batch_buckets=(BATCH,),
        seq_buckets=(CONTEXT,),
        max_decode_len=32,
        stream_chunk_tokens=16,
        continuous_batching=False,
    )
    bundle = build_model(cfg)
    # replicas=None: the registry's make_placement builds the TP mesh
    # (tp>1) or the plain single-device ReplicaSet (tp<=1) — the same
    # resolution order the server boot path uses.
    eng = InferenceEngine(bundle, cfg)
    rng = np.random.default_rng(0)
    feats = [
        {"input_ids": rng.integers(
            5, bundle.cfg.vocab_size, CONTEXT).astype(np.int32),
         "length": np.int32(CONTEXT)}
        for _ in range(BATCH)
    ]
    with eng._lock:
        ids, mask, _ = eng._collate_text(feats)
        sp, _ = eng._collate_sample(feats, ids.shape[0])
        ids, mask = eng.replicas.place_batch(ids, mask)
        state, _ = eng._start(
            eng.params, ids, mask, sp, eng.max_decode_len,
            eng.chunk_tokens, False,
        )
        jax.block_until_ready(state.done)
    per, noisy = chunked_time_per_step(
        eng._gen_chunk, eng.params, state,
        iters=int(os.environ.get("CHUNK_ITERS", "32")),
    )
    return per * 1e3, noisy


def main() -> None:
    from mlmicroservicetemplate_tpu.runtime.device import apply_device_env

    apply_device_env(os.environ.get("DEVICE", "tpu").lower())
    rows = []
    for kv_quant in (False, True):
        base_ms = None
        for tp in WIDTHS:
            ms, noisy = step_ms(tp, kv_quant)
            if base_ms is None:
                base_ms = ms
            rows.append({
                "tp": tp,
                "kv": "int8" if kv_quant else "dense",
                "batch": BATCH,
                "context": CONTEXT,
                "step_ms": round(ms, 3),
                "vs_tp1": round(base_ms / max(ms, 1e-9), 3),
                "timing_noisy": bool(noisy),
            })
            print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({
        "model": os.environ.get("MODEL_NAME", "llama"),
        "device": os.environ.get("DEVICE", "tpu"),
        "rows": rows,
    }))


if __name__ == "__main__":
    main()
