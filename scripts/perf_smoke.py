#!/usr/bin/env python
"""PERF_SMOKE: structural perf-counter regression gate (r20 satellite).

Runs a deterministic tiny workload — three sequential greedy streams
through a paged continuous decode loop (tiny gpt, chain depth 1,
DECODE_WINDOW=1) — and diffs the STRUCTURAL counters against the
committed ``benchmarks/perf_baseline.json``:

- ``chunk_dispatches`` / ``prefill_dispatches``: exact (the dispatch
  arithmetic is deterministic — one admission + ceil(remaining/chunk)
  chunk dispatches per stream);
- ``xla_compiles_serving``: exact 0 (warm covers every serving shape;
  a request-path compile is THE classic silent regression);
- ``host_syncs_per_token``: ceiling (delivery may combine fetches, so
  the count can only legitimately go DOWN);
- ``swap_fallbacks`` / ``perf_pending_dispatches``: exact 0 (a leaked
  pending submit means a fetch seam stopped sampling);
- ``prep_staged``: floor (the double-buffer must keep staging);
- ``autotune_variants_swept`` / ``autotune_installs``: exact (r21 —
  the workload runs with PALLAS_AUTOTUNE on and the interpret-mode
  kernel path enabled; the measured sweep must enumerate the same
  candidate set and install exactly one winner, and the serve-time
  compile pin above proves the tuned executable came out of the
  warm-time ExecutableCache install, not a request-path trace).

Wall-clock appears nowhere — the gate is CPU-noise-immune by
construction.  ``PERF_SMOKE_UPDATE=1`` rewrites the baseline (do this
deliberately, in the PR that changes the structure, with the why in
the commit).  Every run also appends a row to ``benchmarks/counter_ledger.jsonl``
(repo-root ``PERF_LEDGER.jsonl`` is the driver's chip ledger, not ours).

Usage (scripts/check.sh runs it after LINT):
    JAX_PLATFORMS=cpu python scripts/perf_smoke.py
"""

from __future__ import annotations

import asyncio
import json
import os
import sys

_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _root)
sys.path.insert(0, os.path.join(_root, "tests"))
sys.path.insert(0, os.path.join(_root, "benchmarks"))

BASELINE_PATH = os.path.join(_root, "benchmarks", "perf_baseline.json")

# The TP=2 workload needs a multi-device mesh; outside pytest the
# conftest's virtual-device flag is absent, so set it here (it only
# affects the host platform — a real TPU run is untouched).  Must
# happen before the first jax import.
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )

#: counter -> (comparator, tolerance).  "eq" = exact, "le" = current
#: must not exceed baseline*(1+tol), "ge" = must not fall below
#: baseline*(1-tol).
RULES = {
    "tokens": ("eq", 0.0),
    "chunk_dispatches": ("eq", 0.0),
    "prefill_dispatches": ("eq", 0.0),
    "xla_compiles_serving": ("eq", 0.0),
    "swap_fallbacks": ("eq", 0.0),
    "perf_pending_dispatches": ("eq", 0.0),
    "host_syncs_per_token": ("le", 0.10),
    "prep_staged": ("ge", 0.34),
    "autotune_variants_swept": ("eq", 0.0),
    "autotune_installs": ("eq", 0.0),
    # r23 tensor-parallel structural counters: the same dispatch
    # arithmetic must hold with the KV pool sharded over a TP=2 mesh,
    # and serving after warm stays zero-compile (the TP executables
    # key separately — a placement-fingerprint regression shows up
    # here as a request-path compile).
    "tp_tokens": ("eq", 0.0),
    "tp_chunk_dispatches": ("eq", 0.0),
    "tp_prefill_dispatches": ("eq", 0.0),
    "tp_xla_compiles_serving": ("eq", 0.0),
}


def run_workload() -> dict:
    import numpy as np

    from helpers import tiny_gpt_bundle
    from mlmicroservicetemplate_tpu.engine import InferenceEngine
    from mlmicroservicetemplate_tpu.engine.streams import ContinuousDecodeLoop
    from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
    from mlmicroservicetemplate_tpu.ops import autotune
    from mlmicroservicetemplate_tpu.runtime.compile_cache import CompileWindow
    from mlmicroservicetemplate_tpu.utils.config import ServiceConfig
    from perf_ledger import append_row, structural_counters

    cfg = ServiceConfig(
        device="cpu", warmup=False, batch_buckets=(1, 2),
        seq_buckets=(8, 16), max_decode_len=16, stream_chunk_tokens=4,
        max_streams=2, stream_pipeline=1, paged_kv=True, kv_block_size=4,
        # r21: the autotuner sweep runs at warm time (interpret-mode
        # kernels — this gate runs on CPU) so its structural counters
        # are pinned alongside the dispatch arithmetic.
        pallas_autotune=True, pallas_interpret=True,
    )
    autotune.clear()
    bundle = tiny_gpt_bundle(pallas_decode=True, pallas_interpret=True)
    engine = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    cdl = ContinuousDecodeLoop(engine, cfg)
    cdl.warm()

    async def one_stream(seed: int):
        feats = {
            "input_ids": np.arange(1, 9, dtype=np.int32) + seed,
            "length": np.int32(8),
            "max_tokens": 16,
        }
        out = []
        async for chunk in cdl.submit_stream(feats):
            out.extend(chunk.tolist())
        return out

    async def drive():
        for i in range(3):
            toks = await one_stream(i)
            assert len(toks) == 16, f"stream {i} produced {len(toks)} tokens"

    with CompileWindow() as w:
        asyncio.run(drive())
    # Let the loop quiesce so in-flight entries deliver and the
    # occupancy pending queue drains before counting.
    import time

    for _ in range(100):
        if cdl.idle() and not cdl._inflight_chunks:
            break
        time.sleep(0.02)
    counters = structural_counters(engine, cdl)
    counters["xla_compiles_serving"] = w.compiles
    cdl.stop()
    append_row("perf_smoke tiny-gpt paged", counters)
    return counters


def run_tp_workload() -> dict:
    """The same tiny paged workload at TP=2 over the virtual host
    devices (no Pallas — the jnp path under shard_map is the TP
    production path on CPU CI).  Counters land under a ``tp_``
    prefix."""
    import numpy as np

    from helpers import tiny_gpt_bundle
    from mlmicroservicetemplate_tpu.engine import InferenceEngine
    from mlmicroservicetemplate_tpu.engine.streams import ContinuousDecodeLoop
    from mlmicroservicetemplate_tpu.parallel import (
        TensorParallelSet,
        make_replica_tp_mesh,
    )
    from mlmicroservicetemplate_tpu.parallel.tp import gpt_param_spec
    from mlmicroservicetemplate_tpu.runtime.compile_cache import CompileWindow
    from mlmicroservicetemplate_tpu.utils.config import ServiceConfig
    from perf_ledger import append_row, structural_counters

    cfg = ServiceConfig(
        device="cpu", warmup=False, batch_buckets=(1, 2),
        seq_buckets=(8, 16), max_decode_len=16, stream_chunk_tokens=4,
        max_streams=2, stream_pipeline=1, paged_kv=True, kv_block_size=4,
    )
    bundle = tiny_gpt_bundle(tp=2)
    engine = InferenceEngine(
        bundle, cfg,
        TensorParallelSet(make_replica_tp_mesh(tp=2, replicas=1),
                          gpt_param_spec(bundle.cfg)),
    )
    cdl = ContinuousDecodeLoop(engine, cfg)
    cdl.warm()

    async def drive():
        for i in range(2):
            feats = {
                "input_ids": np.arange(1, 9, dtype=np.int32) + i,
                "length": np.int32(8),
                "max_tokens": 16,
            }
            out = []
            async for chunk in cdl.submit_stream(feats):
                out.extend(chunk.tolist())
            assert len(out) == 16, f"tp stream {i} produced {len(out)} tokens"

    with CompileWindow() as w:
        asyncio.run(drive())
    import time

    for _ in range(100):
        if cdl.idle() and not cdl._inflight_chunks:
            break
        time.sleep(0.02)
    counters = structural_counters(engine, cdl)
    counters["xla_compiles_serving"] = w.compiles
    cdl.stop()
    append_row("perf_smoke tiny-gpt paged tp2", counters)
    return {f"tp_{k}": v for k, v in counters.items()}


def compare(current: dict, baseline: dict) -> list[str]:
    failures = []
    for key, (cmp_, tol) in RULES.items():
        base = baseline.get(key)
        cur = current.get(key)
        if base is None or cur is None:
            failures.append(f"{key}: missing (baseline={base}, current={cur})")
            continue
        if cmp_ == "eq" and cur != base:
            failures.append(f"{key}: {cur} != baseline {base}")
        elif cmp_ == "le" and cur > base * (1 + tol):
            failures.append(
                f"{key}: {cur} > baseline {base} (+{tol:.0%} allowed)"
            )
        elif cmp_ == "ge" and cur < base * (1 - tol):
            failures.append(
                f"{key}: {cur} < baseline {base} (-{tol:.0%} allowed)"
            )
    return failures


def main() -> int:
    counters = run_workload()
    counters.update(run_tp_workload())
    flat = {k: v for k, v in counters.items() if k in RULES}
    if os.environ.get("PERF_SMOKE_UPDATE", "").lower() in ("1", "true", "yes"):
        with open(BASELINE_PATH, "w") as f:
            json.dump(flat, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"perf baseline rewritten: {json.dumps(flat, sort_keys=True)}")
        return 0
    if not os.path.exists(BASELINE_PATH):
        print(
            f"no committed baseline at {BASELINE_PATH}; run with "
            "PERF_SMOKE_UPDATE=1 to create it"
        )
        return 1
    with open(BASELINE_PATH) as f:
        baseline = json.load(f)
    failures = compare(flat, baseline)
    print(f"perf smoke counters: {json.dumps(flat, sort_keys=True)}")
    if failures:
        print("PERF_SMOKE REGRESSION:")
        for line in failures:
            print(f"  - {line}")
        return 1
    print("perf smoke: structural counters within baseline")
    return 0


if __name__ == "__main__":
    sys.exit(main())
