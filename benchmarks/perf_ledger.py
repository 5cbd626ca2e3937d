"""Perf-regression ledger: structural counters, not wall-clock.

Every the pre-round BASELINE record (removed in PR 22) round since r12
carries the same caveat — CPU
wall-clock numbers on the contended 1-vCPU box are weather, not
signal.  What IS stable there is the *structure* of the work: host
syncs per generated token, XLA compiles paid during serving, staged
host-prep hit rate, swap fallbacks, dispatch counts per site.  Those
counters regress when a change breaks a lever (a fused window that
stops fusing, a cache that stops sharing, a prep stage that stops
hitting) and they are immune to box noise by construction.

Two consumers:

- ``benchmarks/run_all.py`` appends one JSONL row per measured config
  to ``benchmarks/counter_ledger.jsonl`` (env ``PERF_LEDGER`` overrides the path,
  ``PERF_LEDGER=0`` disables) — the longitudinal record each
  the pre-round BASELINE record (removed in PR 22) round can diff against the last;
- ``scripts/perf_smoke.py`` (the ``PERF_SMOKE`` stage in
  ``scripts/check.sh``) runs a deterministic tiny workload and FAILS
  on regression against the committed ``benchmarks/perf_baseline.json``.
"""

from __future__ import annotations

import json
import os
import time


def default_path() -> str | None:
    """The ledger file path, or None when disabled (PERF_LEDGER=0)."""
    v = os.environ.get("PERF_LEDGER", "")
    if v.lower() in ("0", "false", "no"):
        return None
    if v:
        return v
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(root, "benchmarks", "counter_ledger.jsonl")


def structural_counters(engine, cdl=None) -> dict:
    """The noise-immune counter set for one served workload."""
    attrs = engine.dispatch_attribution() if hasattr(
        engine, "dispatch_attribution"
    ) else {}
    counts = {site: a["count"] for site, a in attrs.items()}
    syncs = counts.get("chunk", 0) + counts.get("fetch", 0)
    tokens = getattr(cdl, "tokens_emitted", 0) if cdl is not None else 0
    out = {
        "dispatch_counts": counts,
        "host_syncs": syncs,
        "tokens": tokens,
        "host_syncs_per_token": round(syncs / tokens, 4) if tokens else None,
    }
    if cdl is not None:
        out.update(
            chunk_dispatches=cdl.chunk_dispatches,
            prefill_dispatches=cdl.prefill_dispatches,
            window_dispatches=getattr(cdl, "window_dispatches", 0),
            prep_staged=getattr(cdl, "prep_staged", 0),
            prep_hits=getattr(cdl, "prep_hits", 0),
            prep_misses=getattr(cdl, "prep_misses", 0),
            swap_fallbacks=getattr(cdl, "swap_fallbacks", 0),
            preemptions=getattr(cdl, "preemptions", 0),
        )
    try:
        from mlmicroservicetemplate_tpu.runtime.compile_cache import (
            cache_stats,
            compile_counters,
        )

        out["xla_compiles_total"] = compile_counters()["count"]
        out["executable_cache"] = cache_stats()
    except Exception:
        pass
    perf = getattr(engine, "perf", None)
    if perf is not None:
        snap = perf.snapshot()
        out["modeled_flops_total"] = snap.get("modeled_flops_total", 0.0)
        out["perf_pending_dispatches"] = snap.get("pending_dispatches", 0)
    try:
        from mlmicroservicetemplate_tpu.ops import autotune

        counts = autotune.stats()["counts"]
        if any(counts.values()):
            out["autotune_variants_swept"] = counts["timed"]
            out["autotune_installs"] = counts["installs"]
            out["autotune"] = counts
    except Exception:
        pass
    return out


def append_row(config: str, counters: dict, path: str | None = None,
               extra: dict | None = None) -> None:
    """Append one ledger row; never raises into the caller (a ledger
    write failure must not sink a benchmark run)."""
    path = path if path is not None else default_path()
    if path is None:
        return
    row = {
        "ts": round(time.time(), 3),
        "config": config,
        **(extra or {}),
        **counters,
    }
    try:
        with open(path, "a") as f:
            f.write(json.dumps(row, sort_keys=True) + "\n")
    except OSError as e:
        print(f"perf ledger append failed: {e}")
