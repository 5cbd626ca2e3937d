"""Structural counters of a tiny paged run: what a `perf_opt` change
breaks without any test of behaviour noticing.

One deterministic workload — three sequential greedy streams of 16
tokens through a paged continuous decode loop (tiny GPT, block size 4,
chain depth 1, the kernel autotuner on with interpret-mode kernels) —
and the same loop at ``TP=2`` over the virtual host devices (two
streams, the jnp path under ``shard_map``).  No clock is read: the
counts are the dispatch arithmetic, so they are the same on any box.

- ``chunk_dispatches`` / ``prefill_dispatches``: one admission and
  ceil(remaining / chunk) chunk dispatches a stream;
- ``xla_compiles_serving``: warm covers every serving shape, so a
  compile on the request path is a regression (at ``TP=2`` it is what a
  placement key that stopped telling meshes apart looks like);
- ``host_syncs_per_token``: a ceiling — delivery may combine fetches,
  so the count can only legitimately go down;
- ``prep_staged``: a floor — the double-buffered host prep keeps
  staging;
- ``autotune_variants_swept`` / ``autotune_installs``: the warm-time
  sweep enumerates the same candidates and installs one winner; with
  zero serving compiles, the tuned executable came out of that install.

A change that alters the structure on purpose changes ``EXPECTED``
here, in the same PR.
"""

from __future__ import annotations

import asyncio
import time

import numpy as np
import pytest

from mlmicroservicetemplate_tpu.engine import InferenceEngine
from mlmicroservicetemplate_tpu.engine.streams import ContinuousDecodeLoop
from mlmicroservicetemplate_tpu.ops import autotune
from mlmicroservicetemplate_tpu.parallel import (
    ReplicaSet,
    TensorParallelSet,
    make_mesh,
    make_replica_tp_mesh,
)
from mlmicroservicetemplate_tpu.parallel.tp import gpt_param_spec
from mlmicroservicetemplate_tpu.runtime.compile_cache import CompileWindow
from mlmicroservicetemplate_tpu.utils.config import ServiceConfig

from helpers import tiny_gpt_bundle

#: counter -> (comparator, expected, tolerance): "eq" exact, "le" at
#: most expected * (1 + tolerance), "ge" at least expected * (1 - tolerance).
EXPECTED = {
    "tokens": ("eq", 48, 0.0),
    "chunk_dispatches": ("eq", 9, 0.0),
    "prefill_dispatches": ("eq", 3, 0.0),
    "xla_compiles_serving": ("eq", 0, 0.0),
    "swap_fallbacks": ("eq", 0, 0.0),
    "autotune_variants_swept": ("eq", 8, 0.0),
    "autotune_installs": ("eq", 1, 0.0),
    "host_syncs_per_token": ("le", 0.4375, 0.10),
    "prep_staged": ("ge", 6, 0.34),
    "tp_tokens": ("eq", 32, 0.0),
    "tp_chunk_dispatches": ("eq", 6, 0.0),
    "tp_prefill_dispatches": ("eq", 2, 0.0),
    "tp_xla_compiles_serving": ("eq", 0, 0.0),
}


def _cfg(**kw) -> ServiceConfig:
    return ServiceConfig(
        device="cpu", warmup=False, batch_buckets=(1, 2),
        seq_buckets=(8, 16), max_decode_len=16, stream_chunk_tokens=4,
        max_streams=2, stream_pipeline=1, paged_kv=True, kv_block_size=4,
        **kw,
    )


def _serve(engine, cfg, n_streams: int) -> dict:
    """Warm a loop, serve ``n_streams`` one after another, count."""
    cdl = ContinuousDecodeLoop(engine, cfg)
    cdl.warm()

    async def drive():
        for i in range(n_streams):
            feats = {
                "input_ids": np.arange(1, 9, dtype=np.int32) + i,
                "length": np.int32(8),
                "max_tokens": 16,
            }
            got = 0
            async for chunk in cdl.submit_stream(feats):
                got += len(chunk)
            assert got == 16, f"stream {i} produced {got} tokens"

    try:
        with CompileWindow() as window:
            asyncio.run(drive())
        # In-flight entries deliver before anything is counted.
        for _ in range(100):
            if cdl.idle() and not cdl._inflight_chunks:
                break
            time.sleep(0.02)
    finally:
        cdl.stop()
    sites = {s: a["count"] for s, a in engine.dispatch_attribution().items()}
    syncs = sites.get("chunk", 0) + sites.get("fetch", 0)
    return {
        "tokens": cdl.tokens_emitted,
        "chunk_dispatches": cdl.chunk_dispatches,
        "prefill_dispatches": cdl.prefill_dispatches,
        "xla_compiles_serving": window.compiles,
        "swap_fallbacks": cdl.swap_fallbacks,
        "host_syncs_per_token": round(syncs / cdl.tokens_emitted, 4),
        "prep_staged": cdl.prep_staged,
    }


@pytest.fixture(scope="module")
def counters(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        # A tuning table of its own: a table found on disk would turn
        # the sweep into a lookup.
        mp.setenv("PALLAS_TUNE_TABLE", str(
            tmp_path_factory.mktemp("tune") / "pallas_tune.json"))
        autotune.clear()
        try:
            cfg = _cfg(pallas_autotune=True, pallas_interpret=True)
            bundle = tiny_gpt_bundle(pallas_decode=True, pallas_interpret=True)
            out = _serve(
                InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1))), cfg, 3)
            tuned = autotune.stats()["counts"]
            out["autotune_variants_swept"] = tuned["timed"]
            out["autotune_installs"] = tuned["installs"]
        finally:
            autotune.clear()
        cfg = _cfg()
        bundle = tiny_gpt_bundle(tp=2)
        placement = TensorParallelSet(
            make_replica_tp_mesh(tp=2, replicas=1), gpt_param_spec(bundle.cfg))
        tp = _serve(InferenceEngine(bundle, cfg, placement), cfg, 2)
        out.update({f"tp_{k}": v for k, v in tp.items()})
    return out


@pytest.mark.parametrize("name", list(EXPECTED))
def test_structural_counter(counters, name):
    how, want, tol = EXPECTED[name]
    got = counters[name]
    if how == "eq":
        assert got == want
    elif how == "le":
        assert got <= want * (1 + tol)
    else:
        assert got >= want * (1 - tol)
