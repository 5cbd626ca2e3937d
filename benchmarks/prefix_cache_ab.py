"""Per-request prefix cache A/B (PREFIX_CACHE, VERDICT r3 item 4).

Measures the TTFT dispatch (fused prefill+first-chunk) device time for
a prompt whose first P tokens are cached vs the same prompt prefilled
in full — the per-request generalization of round 3's PROMPT_PREFIX
table (which measured 1.52× at llama-1.1B with a 768-token prefix).
Two-scan-length differencing (timing.py): the dispatch round-trip cancels exactly.

    MODEL_NAME=llama PREFIX_TOKENS=512 python benchmarks/prefix_cache_ab.py
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

PREFIX_TOKENS = int(os.environ.get("PREFIX_TOKENS", "512"))
SUFFIX_TOKENS = int(os.environ.get("SUFFIX_TOKENS", "16"))


def main() -> None:
    from mlmicroservicetemplate_tpu.engine import InferenceEngine
    from mlmicroservicetemplate_tpu.models.registry import build_model
    from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
    from mlmicroservicetemplate_tpu.runtime.device import apply_device_env
    from mlmicroservicetemplate_tpu.utils.config import ServiceConfig

    import jax

    from timing import device_time_per_call

    cfg = ServiceConfig(
        device=os.environ.get("DEVICE", "tpu"),
        model_name=os.environ.get("MODEL_NAME", "llama"),
        quantize=os.environ.get("QUANTIZE") or None,
        warmup=False,
        batch_buckets=(1,),
        seq_buckets=(32, PREFIX_TOKENS, PREFIX_TOKENS + 32),
        max_decode_len=16,
        stream_chunk_tokens=4,
        prefix_cache=True,
        continuous_batching=False,
    )
    apply_device_env(cfg.device, cfg.compile_cache_dir)
    bundle = build_model(cfg)
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    rng = np.random.default_rng(0)
    vocab = bundle.cfg.vocab_size
    ids = rng.integers(5, vocab, PREFIX_TOKENS + SUFFIX_TOKENS).astype(np.int32)
    feats = {"input_ids": ids, "length": np.int32(len(ids))}

    # Request 1: miss — donates tokens[:PREFIX_TOKENS] to the cache.
    for _ in eng.generate_stream(dict(feats)):
        pass
    m = eng.prefix_cache.match(ids, len(ids))
    assert m is not None and m[0] == PREFIX_TOKENS, eng.prefix_cache.stats()
    p_len, pkv = m

    # Collated shapes for both paths.
    sfeats = dict(feats, input_ids=ids[p_len:], length=np.int32(len(ids) - p_len))
    s_ids, s_mask, _ = eng._collate_text([sfeats])
    sp, _ = eng._collate_sample([sfeats], s_ids.shape[0])
    s_ids, s_mask = eng.replicas.place_batch(s_ids, s_mask)
    f_ids, f_mask, _ = eng._collate_text([feats])
    fsp, _ = eng._collate_sample([feats], f_ids.shape[0])
    f_ids, f_mask = eng.replicas.place_batch(f_ids, f_mask)

    def hit_fn(p, pk, i, mk):
        _, toks = eng.bundle.generate_chunk_fn(
            p, eng.bundle.init_state_fn(
                dict(p, __prefix__=pk), eng.bundle.encode_fn(
                    dict(p, __prefix__=pk), i, mk
                ), mk, eng.max_decode_len, sample=sp,
            ), eng.chunk_tokens, False,
        )
        return toks

    def miss_fn(p, i, mk):
        _, toks = eng.bundle.generate_chunk_fn(
            p, eng.bundle.init_state_fn(
                p, eng.bundle.encode_fn(p, i, mk), mk,
                eng.max_decode_len, sample=fsp,
            ), eng.chunk_tokens, False,
        )
        return toks

    iters = int(os.environ.get("SCAN_ITERS", "8"))
    hit_s, hit_noisy = device_time_per_call(
        hit_fn, (eng.params, pkv, s_ids, s_mask), carry_idx=2, iters=iters
    )
    miss_s, miss_noisy = device_time_per_call(
        miss_fn, (eng.params, f_ids, f_mask), carry_idx=1, iters=iters
    )
    print(json.dumps({
        "model": bundle.name,
        "quantize": cfg.quantize,
        "prefix_tokens": PREFIX_TOKENS,
        "suffix_tokens": SUFFIX_TOKENS,
        "ttft_dispatch_full_prefill_ms": round(miss_s * 1e3, 3),
        "ttft_dispatch_cached_prefix_ms": round(hit_s * 1e3, 3),
        "timing_noisy": bool(hit_noisy or miss_noisy),
        "speedup": round(miss_s / max(hit_s, 1e-12), 3),
        "cache": eng.prefix_cache.stats(),
    }))


if __name__ == "__main__":
    main()
