"""Serving-side tensor-parallel helpers (ROADMAP item 1).

``parallel/tp.py`` owns the Megatron layout rules (column-parallel
q/k/v + mlp-up, row-parallel attn-out + mlp-down) as PartitionSpec
pytrees; ``parallel/mesh.py`` owns the placement objects.  This module
is the small trace-time surface the REST of the serving stack needs:

- ``serving_tp_mesh(tp, replicas, group)`` — the cached
  ``('replica','tp')`` mesh placements are built over: the first
  ``tp`` visible devices by default, or the ``group`` of global device
  ids a multi-chip fleet carved for one replica (replica 1 on devices
  (2,3), …).  The default (prefix) group normalizes to the original
  cache key, so single-group serving stays byte-identical.  The
  ops-level ``shard_map`` wrapper (``ops/paged_attention.
  tp_shard_attention``) does NOT reconstruct this mesh: it traces
  against the abstract ('replica','tp') mesh, because jit shares one
  trace of a model fn across every replica's wrapper and concrete
  devices baked into it would pin all groups to the first tracer's
  chips.  ``use_trace_group`` (the thread-local the executable proxies
  set) only steers a group-less ``serving_tp_mesh`` call.
- ``device_group(placement)`` — a placement's global device-id tuple
  (None for single-device and default-prefix placements), the value
  the executable proxies feed ``use_trace_group``.
- ``kv_head_spec(paged)`` — the one KV-cache layout rule: every cache
  leaf (contiguous ``[B, S, H, D]`` slab and its ``[B, S, H, 1]``
  scales; pool leaf ``[NB, BS, H*D]`` and its ``[NB, BS, H]`` scales)
  shards its HEADS axis (axis 2) over 'tp'.  A pool's merged axis is
  head-major, so shard ``i`` of ``tp`` holds heads ``i*H/tp ..``
  whole (``H % tp == 0``, which the registry demands).  Block ids,
  tables, free-lists and refcounts never see a device axis — the pool
  stays one logical pool with one ledger.
- ``placement_fingerprint(placement)`` — a short stable string naming
  the mesh topology + param layout, mixed into the executable-cache
  and autotuner keys so TP executables can never alias single-device
  (or differently-laid-out) ones.

TP=1 (the default) calls NONE of this: no mesh object is built
anywhere, pinned by ``tests/test_tp_serving.py``.
"""

from __future__ import annotations

import threading

_MESH_CACHE: dict = {}
_LOCK = threading.Lock()

# Thread-local device group for trace-time mesh reconstruction.  The
# fleet's executable proxies (runtime/compile_cache._GroupPinned)
# set this around every call/lower so model-fn shard_maps traced on a
# non-prefix replica rebuild the mesh over THAT replica's devices.
# Thread-local (not a plain global) because the watchdog runs dispatches
# on fresh daemon threads and two replicas may trace concurrently.
_TRACE_GROUP = threading.local()


def current_trace_group():
    """The device-id tuple the current thread is tracing for, or None
    (default prefix placement)."""
    return getattr(_TRACE_GROUP, "group", None)


class use_trace_group:
    """Context manager pinning ``current_trace_group()`` for this
    thread.  ``use_trace_group(None)`` is a no-op (keeps the hot
    single-group path free of save/restore churn)."""

    __slots__ = ("_group", "_prev")

    def __init__(self, group):
        self._group = tuple(group) if group else None
        self._prev = None

    def __enter__(self):
        if self._group is not None:
            self._prev = getattr(_TRACE_GROUP, "group", None)
            _TRACE_GROUP.group = self._group
        return self

    def __exit__(self, *exc):
        if self._group is not None:
            _TRACE_GROUP.group = self._prev
        return False


def _normalize_group(group, need: int):
    """Collapse the default-prefix group to None so prefix placements
    keep the original (tp, replicas) cache key and mesh object."""
    if group is None:
        return None
    group = tuple(int(g) for g in group)
    if group == tuple(range(need)):
        return None
    return group


def serving_tp_mesh(tp: int, replicas: int = 1, group=None):
    """Cached ``('replica','tp')`` mesh over ``replicas*tp`` devices —
    bit-identical (compares/hashes equal) to the engine placement's
    mesh, so a ``shard_map`` traced against it composes with operands
    committed by ``TensorParallelSet``.

    ``group`` names the global device ids to build over (defaults to
    the current thread's trace group, else the visible-device prefix).
    The prefix group normalizes away so single-group serving reuses the
    exact pre-multichip mesh objects and cache keys."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    need = int(tp) * int(replicas)
    if group is None:
        group = current_trace_group()
    group = _normalize_group(group, need)
    key = (int(tp), int(replicas)) if group is None else (
        int(tp), int(replicas), group)
    with _LOCK:
        mesh = _MESH_CACHE.get(key)
        if mesh is None:
            devs = jax.devices()
            if group is not None and len(group) != need:
                raise ValueError(
                    f"device group {group} has {len(group)} devices, "
                    f"TP={tp} x replicas={replicas} needs {need}"
                )
            if need > len(devs) or (
                group is not None and max(group) >= len(devs)
            ):
                raise ValueError(
                    f"TP={tp} x replicas={replicas} needs {need} devices, "
                    f"only {len(devs)} visible"
                )
            picked = devs[:need] if group is None else [
                devs[i] for i in group]
            mesh = Mesh(
                np.array(picked).reshape(int(replicas), int(tp)),
                ("replica", "tp"),
            )
            _MESH_CACHE[key] = mesh
    return mesh


def device_group(placement):
    """Global device-id tuple of a TP placement, for trace-group
    pinning.  None for single-device placements, for plain DP meshes
    (no ``param_spec`` — they never reconstruct a serving mesh), and
    for the default prefix group (normalized so pre-multichip cache
    keys stay byte-identical)."""
    try:
        mesh = getattr(placement, "mesh", None)
        if mesh is None or getattr(placement, "param_spec", None) is None:
            return None
        ids = tuple(int(d.id) for d in mesh.devices.flat)
    except Exception:
        return None
    if len(ids) <= 1:
        return None
    return _normalize_group(ids, len(ids))


def kv_head_spec(paged: bool):
    """PartitionSpec for one KV-cache leaf: heads axis (2) over 'tp',
    whatever trails it (a slab's D, nothing on a pool leaf).

    Contiguous slabs additionally shard their batch axis (0) over
    'replica'; pool leaves must NOT (axis 0 is the block id space —
    device-agnostic by contract, and PAGED_KV pins REPLICAS=1)."""
    from jax.sharding import PartitionSpec as P

    return P(None if paged else "replica", None, "tp")


def placement_fingerprint(placement) -> str:
    """Stable short name of a placement's mesh topology + param layout
    for cache keying.  "" for plain single-mesh replica placements
    (keeps every pre-TP cache/autotune key byte-identical)."""
    mesh = getattr(placement, "mesh", None)
    if mesh is None:
        return ""
    try:
        axes = ",".join(f"{a}{int(n)}" for a, n in mesh.shape.items())
    except Exception:
        return ""
    spec = getattr(placement, "param_spec", None)
    if spec is None and axes in ("replica1", ""):
        return ""  # degenerate 1-device DP mesh == no placement axis
    tag = type(placement).__name__
    if spec is not None:
        import hashlib

        import jax
        from jax.sharding import PartitionSpec

        leaves = jax.tree.leaves(
            spec, is_leaf=lambda x: isinstance(x, PartitionSpec)
        )
        digest = hashlib.sha1(
            "|".join(str(s) for s in leaves).encode()
        ).hexdigest()[:10]
        return f"{tag}({axes})#{digest}"
    return f"{tag}({axes})"


def collective_probe(mesh, d_model: int, dtype="float32") -> dict:
    """Measured ICI collective latency over the serving mesh, per op —
    feeds ``tp_collective_seconds{op}`` at warm time (the serve path
    cannot separate collective from compute inside one executable, so
    the series reports a calibrated per-op probe, re-measured at every
    warm; docs/tensor-parallel.md documents the semantics)."""
    import time

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    tp = int(mesh.shape.get("tp", 1))
    if tp <= 1:
        return {}
    x = jnp.ones((max(1, d_model // tp), max(8, d_model)), dtype)
    xs = jax.device_put(x, NamedSharding(mesh, P("tp", None)))

    # check_vma=False: the static replication checker cannot infer
    # out-replication over 'tp' for these one-op bodies on a 2-D mesh;
    # the probe is a timing harness, not a correctness surface.
    psum = jax.jit(jax.shard_map(
        lambda v: jax.lax.psum(v, "tp"), mesh=mesh,
        in_specs=P("tp", None), out_specs=P(None, None),
        check_vma=False,
    ))
    gather = jax.jit(jax.shard_map(
        lambda v: jax.lax.all_gather(v, "tp", axis=0, tiled=True),
        mesh=mesh, in_specs=P("tp", None), out_specs=P(None, None),
        check_vma=False,
    ))
    out = {}
    for op, fn in (("all_reduce", psum), ("all_gather", gather)):
        jax.block_until_ready(fn(xs))  # compile + warm outside the clock
        t0 = time.perf_counter()
        for _ in range(3):
            jax.block_until_ready(fn(xs))
        out[op] = (time.perf_counter() - t0) / 3.0
    return out
