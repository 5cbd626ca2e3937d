"""Speculative-decoding A/B at real model scale (VERDICT r3 item 1).

Decode at B=1 is HBM-bound (the pre-round BASELINE record (removed in PR
22): llama-1.1B 2.58 ms/step bf16 ≈
the v5e wire), so the win decomposes exactly into two measurables:

- ``r`` — verify-step cost ratio: device seconds per spec verify step
  (a K+1-token window forward) over seconds per normal decode step.
  Weight streaming dominates at 1.1B, so r ≈ 1 is the hypothesis: one
  window forward streams the weights once, same as one step.
- ``alpha`` — tokens emitted per verify step on given traffic
  (acceptance + the free bonus token; 1.0 = nothing accepted).

tokens/s speedup = alpha / r.  Both are measured here (two-scan
differencing for r — the dispatch round-trip cancels), plus a wall-clock
generate_stream A/B through the full engine path (fewer dispatches per
token also saves dispatch round-trips, which the ratio alone doesn't show).

Traffic cases for alpha:
- ``cyclic``  — natural greedy repetition: random-init decoders (like
  real LLMs) often lock into short cycles; once generation repeats,
  prompt-lookup drafts from the generated history and acceptance
  approaches K+1.  This is the summarization/extraction/code-edit
  regime where output reuses earlier spans.
- ``adversarial`` — prompts drawn uniformly at random: essentially no
  n-gram ever recurs, alpha ≈ 1, and the measured slowdown (r > 1
  share) is the honest worst case.

Usage: MODEL_NAME=llama|gpt2 [QUANTIZE=int8] [SPEC_K=8] python
benchmarks/spec_ab.py
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from timing import chunked_time_per_step  # noqa: E402


def make_engine(spec: bool):
    from mlmicroservicetemplate_tpu.engine import InferenceEngine
    from mlmicroservicetemplate_tpu.models.registry import build_model
    from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
    from mlmicroservicetemplate_tpu.runtime.device import apply_device_env
    from mlmicroservicetemplate_tpu.utils.config import ServiceConfig

    cfg = ServiceConfig(
        device=os.environ.get("DEVICE", "tpu"),
        model_name=os.environ.get("MODEL_NAME", "llama"),
        quantize=os.environ.get("QUANTIZE") or None,
        warmup=False,
        batch_buckets=(1,),
        seq_buckets=(64, 256),
        max_decode_len=int(os.environ.get("DECODE_LEN", "128")),
        stream_chunk_tokens=int(os.environ.get("CHUNK", "16")),
        spec_decode="ngram" if spec else None,
        spec_k=int(os.environ.get("SPEC_K", "8")),
        continuous_batching=False,
    )
    apply_device_env(cfg.device, cfg.compile_cache_dir)
    bundle = build_model(cfg)
    return InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1))), cfg


def state_from_prompt(eng, ids_np):
    import jax

    feats = {"input_ids": ids_np, "length": np.int32(len(ids_np))}
    with eng._lock:
        ids, mask, _ = eng._collate_text([feats])
        sp, _ = eng._collate_sample([feats], ids.shape[0])
        ids, mask = eng.replicas.place_batch(ids, mask)
        state, _ = eng._start(
            eng.params, ids, mask, sp, eng.max_decode_len, eng.chunk_tokens, False
        )
        jax.block_until_ready(state.done)
    return feats, ids, mask, sp, state


def measure_alpha(eng, ids_np, budget) -> tuple[float, int]:
    """Drive the real spec stream; returns (tokens/verify-step, total)."""
    n_steps = 0
    total = 0
    feats = {"input_ids": ids_np, "length": np.int32(len(ids_np)),
             "max_tokens": budget}
    for chunk in eng.generate_stream(feats):
        total += int(chunk.size)
        n_steps += eng.chunk_tokens  # n_verify per dispatch
    return total / max(1, n_steps), total


def wall_tokens_s(eng, ids_np, budget, reps: int = 3, **extra) -> float:
    best = 0.0
    for _ in range(reps):
        feats = {"input_ids": ids_np, "length": np.int32(len(ids_np)),
                 "max_tokens": budget, **extra}
        t0 = time.perf_counter()
        n = sum(int(c.size) for c in eng.generate_stream(feats))
        dt = time.perf_counter() - t0
        best = max(best, n / dt)
    return best


def main() -> None:
    import jax

    spec_k = int(os.environ.get("SPEC_K", "8"))
    budget = int(os.environ.get("DECODE_LEN", "128"))
    rng = np.random.default_rng(0)

    eng_spec, cfg = make_engine(spec=True)
    eng_norm, _ = make_engine(spec=False)
    bundle = eng_spec.bundle
    vocab = bundle.cfg.vocab_size

    # Prompts: cyclic (short tiled n-gram cycle) and adversarial
    # (uniform random ids) at the same length.
    p_len = 48
    cycle = rng.integers(5, vocab, 4)
    ids_cyc = np.tile(cycle, p_len // 4 + 1)[:p_len].astype(np.int32)
    ids_adv = rng.integers(5, vocab, p_len).astype(np.int32)

    # -- r: per-step device cost, normal vs verify (differencing) -----
    _, _, _, _, state = state_from_prompt(eng_norm, ids_cyc)
    step_s, step_noisy = chunked_time_per_step(
        eng_norm._gen_chunk, eng_norm.params, state,
        iters=int(os.environ.get("CHUNK_ITERS", "48")),
    )

    feats, ids, mask, sp, state2 = state_from_prompt(eng_spec, ids_cyc)
    # Family-generic: the bundle's own init_spec_fn builds the history
    # (encoder-prefixed for T5, GPTState layout for decoder-only).
    ss = bundle.init_spec_fn(state2, ids, mask)
    spec_fn = jax.jit(
        lambda p, s, n: bundle.spec_chunk_fn(p, s, n, spec_k)[:2],
        static_argnums=2,
    )
    verify_s, verify_noisy = chunked_time_per_step(
        spec_fn, eng_spec.params, ss,
        iters=int(os.environ.get("CHUNK_ITERS", "48")),
    )
    r = verify_s / max(step_s, 1e-12)

    # -- alpha on both traffic shapes ---------------------------------
    alpha_cyc, total_cyc = measure_alpha(eng_spec, ids_cyc, budget)
    alpha_adv, total_adv = measure_alpha(eng_spec, ids_adv, budget)

    # -- end-to-end wall tokens/s through generate_stream -------------
    wall = {
        "spec_cyclic": wall_tokens_s(eng_spec, ids_cyc, budget),
        "norm_cyclic": wall_tokens_s(eng_norm, ids_cyc, budget),
        "spec_adversarial": wall_tokens_s(eng_spec, ids_adv, budget),
        "norm_adversarial": wall_tokens_s(eng_norm, ids_adv, budget),
    }
    # Sampled traffic (rejection-sampling acceptance, SPEC_SAMPLED):
    # same seeded request both sides; outputs differ in tokens (same
    # distribution), the wall ratio is the measurement.
    samp = dict(temperature=0.8, seed=7)
    wall["spec_sampled_cyclic"] = wall_tokens_s(
        eng_spec, ids_cyc, budget, **samp
    )
    wall["norm_sampled_cyclic"] = wall_tokens_s(
        eng_norm, ids_cyc, budget, **samp
    )

    out = {
        "model": bundle.name,
        "quantize": cfg.quantize,
        "spec_k": spec_k,
        "step_ms": round(step_s * 1e3, 4),
        "verify_step_ms": round(verify_s * 1e3, 4),
        "timing_noisy": bool(step_noisy or verify_noisy),
        "cost_ratio_r": round(r, 3),
        "alpha_cyclic": round(alpha_cyc, 3),
        "alpha_adversarial": round(alpha_adv, 3),
        "device_speedup_cyclic": round(alpha_cyc / r, 3),
        "device_speedup_adversarial": round(alpha_adv / r, 3),
        "wall_tokens_s": {k: round(v, 1) for k, v in wall.items()},
        "wall_speedup_cyclic": round(
            wall["spec_cyclic"] / max(wall["norm_cyclic"], 1e-9), 3
        ),
        "wall_speedup_adversarial": round(
            wall["spec_adversarial"] / max(wall["norm_adversarial"], 1e-9), 3
        ),
        "wall_speedup_sampled_cyclic": round(
            wall["spec_sampled_cyclic"]
            / max(wall["norm_sampled_cyclic"], 1e-9), 3
        ),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
