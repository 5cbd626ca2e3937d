"""Mesh construction + replica sharding for data-parallel serving.

Capability parity: the reference serves multi-accelerator by wrapping
the model in ``torch.nn.DataParallel`` — weights replicated per GPU via
NCCL broadcast, inputs scattered, outputs gathered (SURVEY.md §3.4).
Here the same contract is expressed as shardings on a 1-D device mesh:

- params:  ``NamedSharding(mesh, P())``        — replicated on every core
- batch:   ``NamedSharding(mesh, P("replica"))`` — leading axis split

A jitted forward whose inputs carry these shardings compiles to one SPMD
executable per shape bucket; XLA inserts the ICI collectives.  The
degenerate 1-core mesh works identically (SURVEY.md §7.2 L0), so the
single-chip and multi-chip serving paths are the same code.
"""

from __future__ import annotations

import logging

import numpy as np

log = logging.getLogger(__name__)


def _make_1d_mesh(axis: str, n_devices: int, devices, knob: str):
    """1-D mesh over the first ``n_devices`` visible devices (0 = all)."""
    import jax
    from jax.sharding import Mesh

    devs = list(devices if devices is not None else jax.devices())
    if n_devices:
        if n_devices > len(devs):
            raise ValueError(
                f"{knob}={n_devices} but only {len(devs)} devices visible"
            )
        devs = devs[:n_devices]
    log.info("%s mesh over %d device(s): %s", axis, len(devs), devs)
    return Mesh(np.array(devs), (axis,))


def make_mesh(n_replicas: int = 0, devices=None):
    """``('replica',)`` mesh for data-parallel serving."""
    return _make_1d_mesh("replica", n_replicas, devices, "REPLICAS")


class ReplicaSet:
    """Owns the mesh and the two shardings of DP serving.

    The engine asks it to (a) place params replicated, (b) place batch
    arrays sharded on the leading axis, and (c) report the padding
    multiple (batch sizes must divide evenly across replicas).
    """

    def __init__(self, mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.mesh = mesh
        self.param_sharding = NamedSharding(mesh, P())
        self.batch_sharding = NamedSharding(mesh, self._batch_spec())

    def _batch_spec(self):
        from jax.sharding import PartitionSpec as P

        return P("replica")

    @property
    def n_replicas(self) -> int:
        """Batch data-parallel width (what batch sizes must divide by)."""
        return self.mesh.devices.size

    @property
    def n_devices(self) -> int:
        """Total devices in the serving mesh (all axes)."""
        return self.mesh.devices.size

    def place_params(self, params):
        """Replicate a param pytree onto every core (the NCCL-broadcast
        equivalent; a single host→HBM transfer per core, done once)."""
        import jax

        return jax.device_put(params, self.param_sharding)

    def place_batch(self, *arrays):
        """Commit batch arrays with the leading axis sharded over
        replicas.  jit then propagates these shardings through the
        computation — no explicit in_shardings needed."""
        import jax

        if jax.process_count() > 1:
            # Host-local numpy cannot device_put onto non-addressable
            # devices; multi-host SERVING additionally needs every
            # process to enter the SPMD computation in lockstep (a
            # driver pattern this single-controller HTTP path does not
            # implement).  The multi-host bootstrap currently serves
            # the training/collective machinery — fail loudly here.
            raise NotImplementedError(
                "multi-process serving data-path is not implemented: the "
                "HTTP batcher is single-controller; run one serving "
                "process per host (REPLICAS over local devices) or use "
                "the train-step path for cross-host meshes"
            )
        placed = tuple(jax.device_put(a, self.batch_sharding) for a in arrays)
        return placed if len(placed) != 1 else placed[0]

    def pad_multiple(self) -> int:
        return self.n_replicas

    def seq_multiple(self) -> int:
        """Divisibility the SEQ bucket must honor (1 = unconstrained).
        Part of the placement contract the engine collates against."""
        return 1

    def place_decode_state(self, state, paged: bool = False):
        """Commit a host-built decode slot state (contiguous or paged)
        with this placement's shardings.  DP placements shard only the
        slot axis; TP placements additionally shard every KV-cache
        leaf's heads axis over 'tp' (override below)."""
        import jax

        return jax.device_put(state, self.batch_sharding)


def make_sp_mesh(n_devices: int = 0, devices=None):
    """``('sp',)`` mesh for sequence-parallel (ring attention) serving."""
    return _make_1d_mesh("sp", n_devices, devices, "SP")


def _make_2d_mesh(second_axis: str, width: int, replicas: int = 0, devices=None):
    """``('replica', <axis>)`` mesh: batch over rows, width over columns.

    replicas=0 = every remaining visible device (len(devices) // width).
    """
    import jax
    from jax.sharding import Mesh

    if width < 1:
        raise ValueError(f"{second_axis} width must be >= 1, got {width}")
    devs = list(devices if devices is not None else jax.devices())
    if replicas == 0:
        replicas = max(1, len(devs) // width)
    need = replicas * width
    if need > len(devs):
        raise ValueError(
            f"replicas={replicas} x {second_axis}={width} needs {need} "
            f"devices, only {len(devs)} visible"
        )
    grid = np.array(devs[:need]).reshape(replicas, width)
    log.info(
        "('replica', '%s') mesh %dx%d over %d device(s)",
        second_axis, replicas, width, need,
    )
    return Mesh(grid, ("replica", second_axis))


def make_replica_tp_mesh(tp: int, replicas: int = 0, devices=None):
    """``('replica', 'tp')`` serving mesh: Megatron-sharded params over
    'tp', batch data-parallel over 'replica'."""
    return _make_2d_mesh("tp", tp, replicas, devices)


def make_replica_sp_mesh(sp: int, replicas: int = 0, devices=None):
    """``('replica', 'sp')`` mesh: long-context ring attention over 'sp'
    WITH the batch axis data-parallel over 'replica' (round-2 verdict:
    a 1-D sp mesh left the batch axis idle on every device)."""
    return _make_2d_mesh("sp", sp, replicas, devices)


class TensorParallelSet(ReplicaSet):
    """Engine placement for tensor-parallel serving.

    Params are sharded per a Megatron-style PartitionSpec pytree
    (``parallel/tp.py``: column-parallel q/k/v + mlp-up, row-parallel
    attn-out + mlp-down, vocab-sharded embeddings) over the mesh's
    'tp' axis; batch arrays shard their leading axis over 'replica'.
    jit propagates both, and XLA inserts the ICI collectives
    (all-reduce after row-parallel matmuls) — serving-side Megatron
    with the compiler owning the comm.
    """

    def __init__(self, mesh, param_spec):
        self.param_spec = param_spec
        super().__init__(mesh)

    def _batch_spec(self):
        from jax.sharding import PartitionSpec as P

        return P("replica")

    @property
    def n_replicas(self) -> int:
        return int(self.mesh.shape["replica"])

    @property
    def tp_width(self) -> int:
        return int(self.mesh.shape["tp"])

    def place_params(self, params):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        # Top-level subtrees the spec doesn't describe (e.g. a cached
        # prompt-prefix KV attached after the spec was built) replicate
        # — always correct, just not tp-sharded.  ``may_alias``: a leaf
        # already resident with a compatible layout (a fleet spawn
        # re-placing the donor's sharded params, a supervised rebuild
        # re-placing its own) reuses the buffer instead of copying —
        # placement cost scales with what MOVED, not with model size.
        spec = dict(self.param_spec)
        for key in params:
            if key not in spec:
                spec[key] = jax.tree.map(lambda _: P(), params[key])
        return jax.tree.map(
            lambda p, s: jax.device_put(
                p, NamedSharding(self.mesh, s), may_alias=True
            ),
            params, spec,
        )

    def place_decode_state(self, state, paged: bool = False):
        """KV-cache leaves shard their heads axis over 'tp' (pool
        leaves ``[NB, BS, H*D]`` — the merged axis splits on head
        boundaries — and contiguous slabs ``[B, S, H, D]`` alike:
        parallel/tpserve.kv_head_spec); every other field
        keeps the DP slot sharding.  Spec slot states shard their
        ``base`` the same way (the drafting history has no head axis).
        One logical pool, per-shard buffers: block ids, tables and the
        free-list/refcount ledger never see the mesh."""
        import jax
        from jax.sharding import NamedSharding

        from .tpserve import kv_head_spec

        def kv_shard(x):
            # Heads axis must split evenly (registry validates real TP
            # configs — KV heads % tp, which is what makes a pool's
            # merged H*D axis split between heads; duck-typed test
            # states just replicate).
            if (getattr(x, "ndim", 0) >= 3
                    and x.shape[2] % self.tp_width == 0):
                return NamedSharding(self.mesh, kv_head_spec(paged))
            return self.batch_sharding

        def shardings(st):
            tree = jax.tree.map(lambda _: self.batch_sharding, st)
            if hasattr(st, "base"):  # SpecState wrapper
                return tree._replace(base=shardings(st.base))
            if hasattr(st, "cache_k"):
                tree = tree._replace(
                    cache_k=jax.tree.map(kv_shard, st.cache_k),
                    cache_v=jax.tree.map(kv_shard, st.cache_v),
                )
            return tree

        return jax.device_put(state, shardings(state))

    def pad_multiple(self) -> int:
        return self.n_replicas


class SeqParallelSet(ReplicaSet):
    """Engine placement for sequence-parallel (long-context) serving.

    Same contract as ``ReplicaSet`` but the SEQUENCE axis (axis 1 of
    [B, S] batch arrays) is sharded over the mesh's 'sp' axis — the
    layout ring attention consumes (``parallel/ring.py``): each device
    holds its local Q and K/V blocks; K/V blocks rotate over ICI via
    ppermute.

    Works on a 1-D ``('sp',)`` mesh (batch replicated) or a 2-D
    ``('replica', 'sp')`` mesh (batch data-parallel over 'replica' so
    the batch axis no longer idles — ``make_replica_sp_mesh``).
    """

    @property
    def _has_replica(self) -> bool:
        return "replica" in self.mesh.axis_names

    def _batch_spec(self):
        from jax.sharding import PartitionSpec as P

        return P("replica" if self._has_replica else None, "sp")

    @property
    def n_replicas(self) -> int:
        return int(self.mesh.shape["replica"]) if self._has_replica else 1

    def pad_multiple(self) -> int:
        # Batch divisibility comes from the replica axis (1 on a pure
        # sp mesh); the SEQ bucket must divide by the sp width.
        return self.n_replicas

    def seq_multiple(self) -> int:
        return int(self.mesh.shape["sp"])
