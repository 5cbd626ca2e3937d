"""InferenceEngine: bucketed static-shape jit dispatch over a replica mesh.

Replaces the reference's ``InferenceWorker.run_batch()`` hot loop
(SURVEY.md §3.2).  Core TPU-native ideas:

- **Shape buckets**: XLA compiles one executable per input shape, so
  dynamic traffic is padded up to a small set of static (batch, seq)
  buckets; every bucket can be AOT-warmed at startup so compilation
  never lands on the request path (SURVEY.md §7.4.1).
- **Replica mesh**: batches are committed with the leading axis sharded
  over the ``('replica',)`` mesh; params live replicated.  jit
  propagates these shardings, XLA emits the ICI scatter/gather — the
  DataParallel equivalent with the compiler owning the collectives.
- **Single-dispatch decode**: T5 generation is a ``lax.scan`` of K
  decode steps per dispatch (K = ``stream_chunk_tokens`` when streaming,
  the full budget otherwise), with static-shape KV caches.
"""

from __future__ import annotations

import functools
import logging
import math
import os
import threading
import time
from typing import Any, Iterator

import numpy as np

from ..models.registry import KIND_IMAGE, KIND_SEQ2SEQ, KIND_TEXT, ModelBundle
from ..parallel import ReplicaSet, make_mesh
from ..utils import locktrace, metrics, tracing
from .faults import guard_donation

log = logging.getLogger(__name__)


def bucket_for(n: int, buckets: tuple[int, ...], multiple: int = 1) -> int:
    """Smallest bucket ≥ max(n, multiple) that is a multiple of
    ``multiple``; falls back to the padded max bucket."""
    lo = max(n, multiple)
    for b in sorted(buckets):
        if b >= lo and b % multiple == 0:
            return b
    return int(math.ceil(max(buckets + (lo,)) / multiple)) * multiple



def chunk_with_done(chunk_fn, done_of=lambda state: state.done):
    """``chunk_fn(params, state, ...) -> (state', *outs)`` with
    ``state'``'s done flags as one more output: a buffer of its own that
    outlives ``state'`` when the next dispatch donates it (a pipelined
    caller fetches a chunk's ``done`` after dispatching the next).  Keeps
    ``chunk_fn``'s name — the executable's (``jit_<fn>``) in traces."""

    @functools.wraps(chunk_fn)
    def inner(params, state, *args):
        out = chunk_fn(params, state, *args)
        return (*out, done_of(out[0]))

    return inner


class InferenceEngine:
    """Owns jitted executables + on-device params for one ModelBundle."""

    def __init__(self, bundle: ModelBundle, cfg, replicas: ReplicaSet | None = None,
                 replica_id: int = 0, donor_params=None):
        import jax

        self.bundle = bundle
        self.cfg = cfg
        # Fleet identity (engine/fleet.py): which data-parallel replica
        # this engine is.  0 (default) = the single-engine path —
        # unscoped FAULT_SPEC rules behave exactly as before, and
        # ``rN:``-scoped rules let a chaos schedule kill one replica
        # while the others stay clean.
        self.replica_id = int(replica_id)
        # Fault tolerance (engine/faults.py): a deterministic injector
        # around the dispatch boundaries (FAULT_SPEC; None = off, zero
        # overhead) and a watchdog (deadline + transient retry) every
        # guarded dispatch runs under.  A malformed FAULT_SPEC fails
        # HERE — at startup, before readiness — not on the Nth dispatch.
        from .faults import FaultInjector, Watchdog

        # Observability (utils/tracing.py): TRACE=1 installs the
        # process span tracer (never torn down here — a second engine
        # without the knob must not disable the first's tracing); the
        # flight recorder rides on the engine regardless so the loop's
        # last iterations are always available for a fault post-mortem.
        if getattr(cfg, "trace", False) and tracing.tracer() is None:
            tracing.configure(True, int(getattr(cfg, "trace_ring", 4096)))
        self.flight = tracing.FlightRecorder(
            int(getattr(cfg, "flight_ring", 256))
        )
        # An executable compiled or loaded after readiness becomes a
        # ``compile`` event here, with its name (runtime/compile_cache).
        from ..runtime.compile_cache import report_to

        report_to(self.flight)
        # Per-site host-dispatch accounting (always on — two clock
        # reads per dispatch): {site: [count, host_seconds]}, served
        # by /debug/engine; device time per site is the profiler trace's.
        self.dispatch_stats: dict[str, list] = {}
        self._dispatch_stats_lock = threading.Lock()
        self.faults = FaultInjector.from_spec(
            getattr(cfg, "fault_spec", None),
            int(getattr(cfg, "fault_seed", 0) or 0),
            replica=self.replica_id,
        )
        self.watchdog = Watchdog(
            bundle.name,
            timeout_s=float(getattr(cfg, "dispatch_timeout_s", 0.0) or 0.0),
            retries=int(getattr(cfg, "dispatch_retries", 2)),
            backoff_s=float(getattr(cfg, "dispatch_backoff_s", 0.05)),
            injector=self.faults,
            recorder=self.flight,
        )
        if replicas is not None:
            self.replicas = replicas
        elif bundle.make_placement is not None:
            self.replicas = bundle.make_placement()
        else:
            self.replicas = ReplicaSet(make_mesh(getattr(cfg, "replicas", 0)))
        # Param placement: the boot path uploads the bundle's host
        # pytree once; fleet scale-ups pass ``donor_params`` — a live
        # replica's already-placed device arrays — so a spawned engine
        # pays a device-side broadcast (alias on the single-device
        # fleet, ICI copy across devices) instead of a fresh host→HBM
        # upload or a checkpoint reload (λScale; docs/autoscaling.md).
        # ``params_source`` is the observability/test pin for that.
        if donor_params is not None:
            from ..runtime.distributed import broadcast_params

            self.params, moved = broadcast_params(donor_params, self.replicas)
            # Honest transport label: "donor-ici" only when bytes
            # actually crossed devices; same-placement spawns alias and
            # say so (satellite of ISSUE 19 — the old flat "donor" let
            # an alias masquerade as a copy).
            self.params_source = "donor-ici" if moved else "donor-alias"
            if moved:
                metrics.FLEET_PARAM_BROADCAST.labels(bundle.name).inc(moved)
        else:
            self.params = self.replicas.place_params(bundle.params)
            self.params_source = "host"
        # TP observability: probe the serving mesh's collective cost
        # once at warm (tp_collective_seconds{op}) — a step change in
        # the gauge flags ICI vs host-hop placement drift.  Skipped
        # when warmup is off so tiny test engines stay cheap.
        if (getattr(self.replicas, "tp_width", 1) > 1
                and getattr(cfg, "warmup", True)):
            try:
                from ..parallel.tpserve import collective_probe

                d_model = int(
                    getattr(bundle.cfg, "d_model", 0)
                    or getattr(bundle.cfg, "hidden_size", 0) or 256
                )
                probe = collective_probe(self.replicas.mesh, d_model)
                for op, sec in probe.items():
                    metrics.TP_COLLECTIVE_SECONDS.labels(
                        bundle.name, op
                    ).set(sec)
            except Exception:
                # Observability only — never blocks boot, but a silent
                # pass here once hid a probe bug for a whole round.
                log.warning("TP collective probe failed", exc_info=True)
        self.batch_buckets = tuple(sorted(cfg.batch_buckets))
        self.seq_buckets = tuple(sorted(cfg.seq_buckets))
        # Decode budget rounded up to a whole number of stream chunks so
        # chunked and full generation share KV-cache shapes.
        chunk = max(1, int(getattr(cfg, "stream_chunk_tokens", 4)))
        self.chunk_tokens = chunk
        self.max_decode_len = int(
            math.ceil(getattr(cfg, "max_decode_len", 64) / chunk) * chunk
        )
        # Bounded dispatch pipelining: jitted calls are thread-safe, and
        # overlapping a few batches in flight hides the host<->device
        # round-trip (how much depends on the host's dispatch latency;
        # not measured on the attached chip yet).  The semaphore caps on-device
        # memory and queueing.
        self._lock = threading.Semaphore(
            max(1, int(getattr(cfg, "pipeline_depth", 4)))
        )

        if bundle.kind == KIND_SEQ2SEQ:
            # static: n_steps, sample-path flag.  The state is DONATED,
            # like every state an executable replaces (engine/streams.py
            # has the rule): the chunk writes its KV rows in place
            # instead of copying the caches, and leaves it does not touch
            # (T5's encoder output) alias through.  The callers pipeline
            # dispatches, so what they fetch later — tokens, ``done`` —
            # are outputs of their own (``chunk_with_done``), never
            # leaves of a state the next call consumes.
            # Every wrapper below routes through the process-level
            # ExecutableCache (runtime/compile_cache.py): a second
            # engine over the SAME bundle + placement (fleet spawns,
            # supervised rebuilds) shares the first's jitted wrappers
            # and performs zero XLA compiles at warm.
            self._gen_chunk = self._shared_jit(
                "gen_chunk",
                lambda: jax.jit(
                    tracing.scoped(
                        "decode_chunk",
                        chunk_with_done(bundle.generate_chunk_fn),
                    ),
                    static_argnums=(2, 3), donate_argnums=(1,),
                ),
            )

            # encode + cache init + first decode chunk fused into ONE
            # executable: time-to-first-token pays a single device
            # round-trip instead of three (encode / init / chunk each
            # cost a full dispatch round-trip otherwise).  ``sp`` is the per-row
            # SampleParams pytree; ``sample`` statically picks the
            # argmax fast path vs the sampling path.
            def start(p, ids, mask, sp, max_len: int, n_steps: int, sample: bool):
                enc = bundle.encode_fn(p, ids, mask)
                state = bundle.init_state_fn(p, enc, mask, max_len, sample=sp)
                return bundle.generate_chunk_fn(p, state, n_steps, sample)

            start = tracing.scoped("prefill_wave", start)
            self._start = self._shared_jit(
                "start", lambda: jax.jit(start, static_argnums=(4, 5, 6))
            )

            # Non-streaming generate: encode + init + a done-aware
            # while_loop of chunk scans, still ONE dispatch.  An
            # all-EOS batch exits at the next chunk boundary instead of
            # paying the full max_decode_len scan on the device.
            def full(p, ids, mask, sp, budgets, max_len: int, chunk: int,
                     sample: bool):
                import jax.numpy as jnp
                from jax import lax

                enc = bundle.encode_fn(p, ids, mask)
                state = bundle.init_state_fn(p, enc, mask, max_len, sample=sp)
                # Bucket-padding rows (all-zero mask) never emit EOS, so
                # they must count as done from the start or the early
                # exit could never fire on any padded batch.
                state = state._replace(done=state.done | (mask.sum(axis=-1) == 0))

                def cond(s):
                    import jax.numpy as jnp

                    # pos is per-row; all rows start together here, so
                    # any() == lockstep progress.
                    return jnp.logical_and((s.pos < max_len).any(), ~s.done.all())

                def body(s):
                    s, _ = bundle.generate_chunk_fn(p, s, chunk, sample)
                    # Per-row max_tokens: a capped row counts as done so
                    # an all-capped batch exits at the chunk boundary
                    # instead of paying the full budget on the device.
                    return s._replace(done=s.done | (s.pos >= budgets))

                state = lax.while_loop(cond, body, state)
                return state.tokens, state.pos.max()

            self._full = self._shared_jit(
                "full", lambda: jax.jit(full, static_argnums=(5, 6, 7))
            )

            # Speculative decoding (SPEC_DECODE=ngram, models/spec.py):
            # greedy streams draft spec_k tokens by prompt-lookup and
            # verify them in one forward — the only lever past the
            # HBM ceiling at batch=1.  Two executables: a fused
            # prefill + history-build + first spec chunk (TTFT = one
            # round-trip, like _start), and the follow-up spec chunk.
            self.spec_enabled = (
                getattr(cfg, "spec_decode", None) == "ngram"
                and bundle.spec_chunk_fn is not None
            )
            self.spec_k = int(getattr(cfg, "spec_k", 8))
            # Rejection-sampling acceptance extends speculation to
            # temperature>0 traffic (distribution-identical; see
            # models/spec._sampled_emission and the SPEC_SAMPLED knob).
            self.spec_sampled = self.spec_enabled and bool(
                getattr(cfg, "spec_sampled", True)
            )
            if self.spec_enabled:
                def spec_start(p, ids, mask, sp, max_len: int,
                               n_verify: int, spec_k: int,
                               sample: bool = False):
                    enc = bundle.encode_fn(p, ids, mask)
                    state = bundle.init_state_fn(p, enc, mask, max_len, sample=sp)
                    ss = bundle.init_spec_fn(state, ids, mask)
                    return bundle.spec_chunk_fn(p, ss, n_verify, spec_k, sample)

                self._spec_start = self._shared_jit(
                    "spec_start",
                    lambda: jax.jit(spec_start, static_argnums=(4, 5, 6, 7)),
                )
                self._spec_chunk = self._shared_jit(
                    "spec_chunk",
                    lambda: jax.jit(
                        chunk_with_done(
                            bundle.spec_chunk_fn, lambda ss: ss.base.done
                        ),
                        static_argnums=(2, 3, 4), donate_argnums=(1,),
                    ),
                )

                # Non-streaming greedy batches take the speculative
                # path too: ONE dispatch of a done-aware while_loop of
                # verify rounds — same accepted-token economics as the
                # streaming path, for /v1 clients that don't stream.
                def full_spec(p, ids, mask, sp, budgets, max_len: int,
                              spec_k: int, sample: bool = False):
                    from jax import lax

                    enc = bundle.encode_fn(p, ids, mask)
                    state = bundle.init_state_fn(p, enc, mask, max_len, sample=sp)
                    state = state._replace(
                        done=state.done | (mask.sum(axis=-1) == 0)
                    )
                    ss = bundle.init_spec_fn(state, ids, mask)

                    def cond(s):
                        return ~s.base.done.all()

                    def body(s):
                        import jax.numpy as jnp

                        s2, _, _ = bundle.spec_chunk_fn(p, s, 1, spec_k, sample)
                        # Budget-capped rows stop once they have
                        # OVERSHOT the cap (≥1 past it, like _full's
                        # chunk granularity): the host trims to
                        # max_tokens, and the extra token is what
                        # distinguishes finish_reason "length" from a
                        # model that genuinely stopped at the cap.
                        # +1 (not +spec_k): every round emits ≥1
                        # token, so one round past the cap suffices.
                        caps = jnp.minimum(budgets + 1, max_len)
                        return s2._replace(
                            base=s2.base._replace(
                                done=s2.base.done | (s2.base.pos >= caps)
                            )
                        )

                    ss = lax.while_loop(cond, body, ss)
                    return ss.base.tokens, ss.base.pos.max()

                self._full_spec = self._shared_jit(
                    "full_spec",
                    lambda: jax.jit(full_spec, static_argnums=(5, 6, 7)),
                )

            # Block-paged KV (PAGED_KV=1, decoder families): the
            # continuous loop's KV lives in a pool of KV_BLOCK_SIZE-
            # token blocks (engine/kv_blocks.py) instead of per-slot
            # contiguous slabs; the pool is sized from KV_BUDGET_MB
            # (or MAX_STREAMS × worst case when no budget is set) and
            # is the single source of truth for committed KV bytes.
            self.paged_kv = bool(
                getattr(cfg, "paged_kv", False)
                and bundle.paged_chunk_fn is not None
            )
            self.kv_block_size = int(getattr(cfg, "kv_block_size", 16))
            self.kv_pool = None
            if self.paged_kv:
                from .kv_blocks import BlockPool, blocks_for

                if self.replicas.pad_multiple() != 1:
                    raise ValueError(
                        "PAGED_KV requires a single-replica placement "
                        "(the block pool has no batch axis to shard)"
                    )
                bb = self.kv_block_bytes()
                budget = int(
                    float(getattr(cfg, "kv_budget_mb", 0.0) or 0.0) * 1e6
                )
                if budget:
                    num = max(1, budget // bb)
                else:
                    worst = blocks_for(
                        max(self.seq_buckets) + self.max_decode_len,
                        self.kv_block_size,
                    )
                    num = max(1, int(getattr(cfg, "max_streams", 8))) * worst
                self.kv_pool = BlockPool(num, bb)

            # Host-RAM KV tier (KV_HOST_BUDGET_MB; docs/kv-tiering.md):
            # checkpointed streams swap their blocks out to pinned host
            # buffers instead of recomputing, and evicted prefix-cache
            # entries demote there instead of dying.  The tier object
            # survives reset_device_state (host RAM outlives a device
            # rebuild — that is the point) and, in a fleet, is shared
            # by every replica (engine/fleet.py re-points it).
            self.kv_host = None
            host_mb = float(getattr(cfg, "kv_host_budget_mb", 0.0) or 0.0)
            if host_mb > 0:
                if not getattr(cfg, "paged_kv", False):
                    raise ValueError(
                        "KV_HOST_BUDGET_MB requires PAGED_KV=1 (the host "
                        "tier swaps paged blocks; the contiguous layout "
                        "has no block identity to swap)"
                    )
                if self.paged_kv:
                    from .kv_blocks import KVHostTier

                    self.kv_host = KVHostTier(host_mb, self.kv_block_bytes())
            # Disk KV tier below host RAM (KV_DISK_BUDGET_MB;
            # runtime/durability.py): cold host blocks demote to memmap
            # files under JOURNAL_DIR/kv_disk instead of dying, and
            # stream checkpoints write through so their resume KV
            # outlives the process.  Fleet-shared like kv_host.
            self.kv_disk = None
            disk_mb = float(getattr(cfg, "kv_disk_budget_mb", 0.0) or 0.0)
            if disk_mb > 0:
                jdir = getattr(cfg, "journal_dir", None)
                if not jdir:
                    raise ValueError(
                        "KV_DISK_BUDGET_MB requires JOURNAL_DIR (the "
                        "disk tier persists under the journal directory)"
                    )
                if self.kv_host is None:
                    raise ValueError(
                        "KV_DISK_BUDGET_MB requires PAGED_KV=1 and "
                        "KV_HOST_BUDGET_MB>0 (the disk tier sits BELOW "
                        "the host-RAM tier in the offload hierarchy)"
                    )
                if int(getattr(self, "replica_id", 0)) == 0:
                    # Process-level registry: two engines over one
                    # JOURNAL_DIR (fleet rebuilds, probes) share the
                    # tier instead of racing its index.
                    from ..runtime.durability import get_disk_tier

                    self.kv_disk = get_disk_tier(
                        disk_mb, self.kv_block_bytes(),
                        os.path.join(jdir, "kv_disk"),
                    )
                    self.kv_disk.model = bundle.name
            # Write-ahead stream journal (JOURNAL_DIR; the Batcher
            # constructs ONE per process and attaches it here; fleet
            # replicas share it like kv_host).  None = no journaling,
            # every hook in the serving path short-circuits.
            self.journal = None
            # Prefix demotions queued by on_evict for the decode loop to
            # gather at its next chunk boundary (the eviction itself
            # must not dispatch: it can run under the cache lock).
            self._host_demote_pending: list = []
            self._host_demote_on = True

            # Chunked prefill (PREFILL_CHUNK>0, decoder families;
            # docs/chunked-prefill.md): the continuous loop splits
            # prompts into PREFILL_CHUNK-token windows interleaved
            # with decode chunks (engine/streams.py owns the jitted
            # window executables).  Gated HERE — at startup, before
            # readiness — so an unsupported combination can never
            # silently serve monolithic.
            self.prefill_chunk = int(getattr(cfg, "prefill_chunk", 0) or 0)
            if self.prefill_chunk:
                if bundle.prefill_chunk_fn is None:
                    raise ValueError(
                        f"PREFILL_CHUNK is not supported for "
                        f"{bundle.name!r} (chunked prefill covers the "
                        "decoder families: gpt2, llama)"
                    )
                if self._global_prefix_len() > 0:
                    raise ValueError(
                        "PREFILL_CHUNK and PROMPT_PREFIX are mutually "
                        "exclusive (the global prefix overlay is seeded "
                        "by init_decode_state, which chunked prefill "
                        "bypasses); use PREFIX_CACHE=1"
                    )
                if self.paged_kv and self.prefill_chunk % self.kv_block_size:
                    raise ValueError(
                        f"PREFILL_CHUNK={self.prefill_chunk} must be a "
                        f"multiple of KV_BLOCK_SIZE={self.kv_block_size} "
                        "(block-aligned window boundaries keep per-chunk "
                        "block growth exact)"
                    )

            # Per-request prefix cache (PREFIX_CACHE=1, decoder
            # families without a global PROMPT_PREFIX): recurring
            # prompt prefixes — per-conversation system prompt +
            # history — donate their KV at prefill and later requests
            # prefill only their suffix.  The cached KV rides as a
            # TRACED argument (one executable per prefix-bucket ×
            # suffix-bucket pair), entering the model through the same
            # ``__prefix__`` overlay the global knob uses.
            self.prefix_cache = None
            if (
                getattr(cfg, "prefix_cache", False)
                and bundle.supports_prefix
                and not (
                    isinstance(bundle.params, dict)
                    and "__prefix__" in bundle.params
                )
            ):
                from .prefix_cache import PrefixCache

                # Paged mode stores block-ref pins, not KV copies:
                # eviction must release the cache's pool ref.
                on_evict = None
                if self.paged_kv:
                    def on_evict(entry, key=None):
                        from .kv_blocks import PagedPrefix

                        if not isinstance(entry, PagedPrefix):
                            return
                        # Host tier on: hand the pin to the decode loop
                        # for demotion (the block refs transfer with it
                        # — freed only after the device→host copy) so
                        # the prefix outlives device-budget pressure.
                        if (
                            self.kv_host is not None
                            and key is not None
                            and self._host_demote_on
                        ):
                            self._host_demote_pending.append((key, entry))
                            return
                        self.kv_pool.free(list(entry.block_ids))

                self.prefix_cache = PrefixCache(
                    self.seq_buckets,
                    float(getattr(cfg, "prefix_cache_mb", 256.0)),
                    on_evict=on_evict,
                )

                def start_prefixed(p, pkv, ids, mask, sp, max_len: int,
                                   n_steps: int, sample: bool):
                    p2 = dict(p, __prefix__=pkv)
                    enc = bundle.encode_fn(p2, ids, mask)
                    state = bundle.init_state_fn(p2, enc, mask, max_len, sample=sp)
                    return bundle.generate_chunk_fn(p2, state, n_steps, sample)

                self._start_prefixed = self._shared_jit(
                    "start_prefixed",
                    lambda: jax.jit(start_prefixed,
                                    static_argnums=(5, 6, 7)),
                )

                # Batched-wave variant: N same-(prefix-bucket,
                # suffix-bucket) cache hits prefill as ONE dispatch.
                # Each row's pkv rides in a tuple and stacks inside the
                # trace; the models' prefix broadcast is an identity
                # when the stacked batch dim equals the batch, so every
                # row attends to ITS OWN prefix.  One executable per
                # (prefix, suffix) pair and tuple length.
                def start_prefixed_wave(p, pkvs, ids, mask, sp,
                                        max_len: int, n_steps: int,
                                        sample: bool):
                    import jax.numpy as jnp

                    pkv = jax.tree.map(
                        lambda *xs: jnp.concatenate(xs, axis=0), *pkvs
                    )
                    p2 = dict(p, __prefix__=pkv)
                    enc = bundle.encode_fn(p2, ids, mask)
                    state = bundle.init_state_fn(p2, enc, mask, max_len, sample=sp)
                    return bundle.generate_chunk_fn(p2, state, n_steps, sample)

                self._start_prefixed_wave = self._shared_jit(
                    "start_prefixed_wave",
                    lambda: jax.jit(start_prefixed_wave,
                                    static_argnums=(5, 6, 7)),
                )
                self._slice_prefix: dict[int, Any] = {}

                # SPEC_DECODE × PREFIX_CACHE composition: the greedy
                # B=1 streams the speculative path serves are exactly
                # the traffic prefix caching targets, so the spec
                # start has a prefixed variant too — suffix-only
                # prefill, drafting history seeded with the FULL
                # prompt (prefix ids are the request's own tokens).
                if self.spec_enabled:
                    def spec_start_prefixed(p, pkv, pref_ids, ids, mask,
                                            sp, max_len: int,
                                            n_verify: int, spec_k: int,
                                            sample: bool = False):
                        p2 = dict(p, __prefix__=pkv)
                        enc = bundle.encode_fn(p2, ids, mask)
                        state = bundle.init_state_fn(
                            p2, enc, mask, max_len, sample=sp
                        )
                        ss = bundle.init_spec_fn(
                            state, ids, mask, prefix_ids=pref_ids
                        )
                        return bundle.spec_chunk_fn(
                            p2, ss, n_verify, spec_k, sample
                        )

                    self._spec_start_prefixed = self._shared_jit(
                        "spec_start_prefixed",
                        lambda: jax.jit(spec_start_prefixed,
                                        static_argnums=(6, 7, 8, 9)),
                    )
        else:
            self._forward = self._shared_jit(
                "forward", lambda: jax.jit(bundle.forward)
            )
            self.spec_enabled = False
            self.spec_sampled = False
            self.prefix_cache = None
            self.paged_kv = False
            self.kv_block_size = int(getattr(cfg, "kv_block_size", 16))
            self.kv_pool = None
            self.kv_host = None
            self.kv_disk = None
            self.journal = None
            self._host_demote_pending = []
            self._host_demote_on = True
            self.prefill_chunk = 0
        # Decode steps actually executed by the most recent non-streaming
        # seq2seq dispatch (early-exit observability; also in /metrics).
        self.last_decode_steps: int | None = None
        # Concurrent generate_stream count: the spec load gate must
        # hold on the LEGACY per-stream path too (CONTINUOUS_BATCHING=0
        # or oversized prompts) — without it, N concurrent streams all
        # run per-stream speculative loops serialized on the engine
        # lock, the under-load regression the gate exists to prevent.
        self._live_streams = 0
        self._live_streams_lock = threading.Lock()

    # ------------------------------------------------------------------
    # collation: list of per-item feature dicts -> padded device batch

    def _pad_multiple(self) -> int:
        return self.replicas.pad_multiple()

    def _collate_images(self, feats: list[dict]) -> tuple[np.ndarray, int]:
        n = len(feats)
        bsz = bucket_for(n, self.batch_buckets, self._pad_multiple())
        size = self.bundle.image_size
        # uint8 batch: 1/4 the host→device wire bytes of f32; the
        # normalize-to-f32 affine runs inside the jitted forward.
        out = np.zeros((bsz, size, size, 3), np.uint8)
        for i, f in enumerate(feats):
            out[i] = f["image"]
        return out, n

    def _collate_text(self, feats: list[dict]) -> tuple[np.ndarray, np.ndarray, int]:
        n = len(feats)
        bsz = bucket_for(n, self.batch_buckets, self._pad_multiple())
        max_len = max(int(f["length"]) for f in feats)
        # Sequence-parallel placements shard axis 1: the seq bucket must
        # divide by the mesh width (ReplicaSet reports 1).
        seq = bucket_for(max_len, self.seq_buckets, self.replicas.seq_multiple())
        ids = np.zeros((bsz, seq), np.int32)
        mask = np.zeros((bsz, seq), np.int32)
        for i, f in enumerate(feats):
            L = int(f["length"])
            ids[i, :L] = f["input_ids"][:L]
            mask[i, :L] = 1
        return ids, mask, n

    def _collate_sample(self, feats: list[dict], bsz: int):
        """Per-row SampleParams from request fields; bucket-pad rows are
        greedy.  Returns (SampleParams, sampled) — ``sampled`` picks the
        statically-compiled sampling executable only when some row
        actually needs it (the argmax path never pays the per-step
        [B, V] sort)."""
        import random

        from ..models.sampling import make_params

        temp = np.zeros(bsz, np.float32)
        top_k = np.zeros(bsz, np.int32)
        top_p = np.ones(bsz, np.float32)
        seed = np.zeros(bsz, np.uint32)
        sampled = False
        for i, f in enumerate(feats):
            t = float(f.get("temperature", 0.0))
            temp[i] = t
            if t > 0.0:
                sampled = True
                top_k[i] = int(f.get("top_k", 0))
                top_p[i] = float(f.get("top_p", 1.0))
                s = f.get("seed")
                # Unseeded sampled requests must differ from each other.
                # Mask defensively: np.uint32() raises OverflowError on
                # out-of-range ints (numpy 2.x), and one bad row must
                # not fail a shared batch.
                s = int(s) if s is not None else random.getrandbits(32)
                seed[i] = np.uint32(s & 0xFFFFFFFF)
        return make_params(seed, temp, top_k, top_p), sampled

    def budget_for(self, feats: dict) -> int:
        """One stream's token budget: request max_tokens clamped to the
        server decode budget (shared by both streaming paths)."""
        return min(
            int(feats.get("max_tokens", self.max_decode_len)), self.max_decode_len
        )

    def _kv_dims(self) -> tuple[int, int, int, int, bool]:
        """(layers, kv_heads, head_dim, elt_bytes, quant_int8) off the
        bundle config — the one place the admission estimate and the
        paged block ledger read model dims, so they can never drift."""
        cfg = self.bundle.cfg
        layers = int(getattr(cfg, "num_layers", 0) or 12)
        if hasattr(cfg, "cache_layers"):
            # Only the attention layers cache keys (a recurrent or an
            # FFN-only layer has no pool).
            layers = len(cfg.cache_layers)
        heads = int(
            getattr(cfg, "num_kv_heads", 0)
            or getattr(cfg, "num_heads", 0) or 12
        )
        head_dim = getattr(cfg, "d_kv", None) or getattr(
            cfg, "head_dim", None
        )
        if head_dim is None:
            d_model = int(getattr(cfg, "d_model", 0) or 768)
            n_attn = int(getattr(cfg, "num_heads", 0) or heads)
            head_dim = max(1, d_model // max(1, n_attn))
        quant = getattr(self.cfg, "quant_kv", None) == "int8"
        try:
            elt = np.dtype(self.bundle.policy.compute_jnp).itemsize
        except Exception:
            elt = 2
        return layers, heads, int(head_dim), int(elt), quant

    def _global_prefix_len(self) -> int:
        """Token rows a global PROMPT_PREFIX occupies in EVERY stream's
        cache (0 without one)."""
        pre = (
            self.bundle.params.get("__prefix__")
            if isinstance(self.bundle.params, dict) else None
        )
        if pre is None:
            return 0
        entry = pre["k"][0]
        return int(
            entry[0].shape[1] if isinstance(entry, tuple) else entry.shape[1]
        )

    def kv_token_bytes(self) -> int:
        """KV bytes one token position costs in this deployment."""
        from .kv_blocks import kv_token_bytes

        layers, heads, head_dim, elt, quant = self._kv_dims()
        latent = int(getattr(self.bundle.cfg, "latent_lanes", 0) or 0)
        return kv_token_bytes(layers, heads, head_dim, elt, quant, latent)

    def stream_fixed_bytes(self) -> int:
        """Bytes a stream holds whatever its length: a row of recurrent
        state (a model with Mamba layers) and its window layers' rings (a
        model whose window store is a ring: ``window_ring``); 0 otherwise."""
        cfg = self.bundle.cfg
        return int((getattr(cfg, "ssm_row_bytes", 0) or 0)
                   + (getattr(cfg, "window_row_bytes", 0) or 0))

    def kv_block_bytes(self) -> int:
        """Bytes one ``KV_BLOCK_SIZE``-token block costs (paged mode)."""
        return self.kv_token_bytes() * self.kv_block_size

    def kv_bytes_estimate(self, feats: dict) -> int:
        """Admission-time estimate of one request's KV-cache footprint
        in bytes: padded prompt bucket + server decode budget wide (a
        global PROMPT_PREFIX adds its rows — every stream's cache
        physically carries them), model dims off the bundle config,
        element width off the active QUANT_KV mode (int8 payload + one
        f32 scale per token-head vs the compute dtype).  Encoder-
        decoder families add the cross-attention cache over the
        encoder bucket.  Decoder-only causal LMs (gpt2/llama) register
        as KIND_SEQ2SEQ, so they take this path too — pinned by test,
        since a 0 here silently no-ops KV admission for the families
        that carry the composed decode levers.

        Deliberately a ceiling (collation pads up to buckets, the full
        decode budget is reserved even if the row EOSes early), so the
        scheduler's HBM budget fails SAFE — overcommit is refused at
        admission instead of discovered at slot-insert.  Paged mode
        replaces this ceiling with the exact block ledger
        (``kv_blocks_estimate``); the invariant the property test pins
        is ceiling ≥ blocks × block bytes."""
        if self.bundle.kind != KIND_SEQ2SEQ:
            return 0
        cfg = self.bundle.cfg
        s = bucket_for(
            max(int(feats.get("length", 0) or 0), 1),
            self.seq_buckets, self.replicas.seq_multiple(),
        )
        width = self._global_prefix_len() + s + self.max_decode_len
        per_tok = self.kv_token_bytes()
        # A stream's fixed bytes beside what grows a token at a time: its
        # row of recurrent state (0 without Mamba layers).
        total = width * per_tok + self.stream_fixed_bytes()
        if getattr(cfg, "d_kv", None) is not None:
            # Encoder-decoder: cross-attention K/V over the encoder seq.
            total += s * per_tok
        return int(total)

    def chunked_prefill_applies(self, length: int) -> bool:
        """Whether the continuous loop will prefill this prompt in
        PREFILL_CHUNK windows: enabled AND (longer than one window, or
        past the largest seq bucket — the monolithic wave path cannot
        serve those).  One predicate shared by the loop's routing and
        the admission ledger so the two can never drift."""
        return bool(self.prefill_chunk) and (
            int(length) > self.prefill_chunk
            or int(length) > max(self.seq_buckets)
        )

    def kv_blocks_estimate(self, feats: dict) -> tuple[int, int]:
        """Paged mode's exact ledger: (initial, worst) block counts for
        one stream.  ``initial`` covers the prompt bucket plus the
        fused first chunk — what admission charges up front; the loop
        grows block-by-block from there.  ``worst`` covers the
        request's own decode budget (max_tokens, chunk-rounded) — the
        can-never-fit rejection bound.

        Chunked prefill (PREFILL_CHUNK) shrinks ``initial`` to the
        FIRST prefill window: the loop allocates the rest of the
        prompt's blocks window-by-window as prefill proceeds, and a
        stream checkpointed mid-prefill re-reserves this same
        first-window footprint at resume — never the whole-prompt
        estimate (``kv_bytes_for_resume`` reads this)."""
        from .kv_blocks import blocks_for

        length = max(int(feats.get("length", 0) or 0), 1)
        s = bucket_for(
            length, self.seq_buckets, self.replicas.seq_multiple(),
        )
        budget = int(
            math.ceil(self.budget_for(feats) / self.chunk_tokens)
            * self.chunk_tokens
        )
        if self.chunked_prefill_applies(length):
            initial = blocks_for(
                min(length, self.prefill_chunk), self.kv_block_size
            )
            # Chunked streams grow off their EXACT length, not the
            # padded bucket (the windows write real positions only).
            worst = blocks_for(length + budget, self.kv_block_size)
        else:
            initial = blocks_for(s + self.chunk_tokens, self.kv_block_size)
            worst = blocks_for(s + budget, self.kv_block_size)
        return initial, max(initial, worst)

    def _collate_budget(self, feats: list[dict], bsz: int) -> np.ndarray:
        """Per-row budgets for the batched non-stream path; pad rows 0."""
        budgets = np.zeros(bsz, np.int32)
        for i, f in enumerate(feats):
            budgets[i] = self.budget_for(f)
        return budgets

    # ------------------------------------------------------------------
    # fault tolerance

    def _shared_jit(self, kind: str, build, statics: tuple = ()):
        """Route one jit-wrapper construction through the process-level
        ExecutableCache (runtime/compile_cache.py): engines over the
        same bundle + placement share wrappers, so fleet spawns and
        supervised rebuilds re-trace and re-compile nothing."""
        from ..runtime.compile_cache import shared_executable

        return shared_executable(
            kind, self.bundle, self.replicas, build, statics
        )

    def dispatch_guard(self, site: str, fn, donates=None):
        """Run one device-dispatch callable under the fault injector
        and the watchdog (deadline + transient retry).  Every guarded
        callable is a pure function of its inputs, so a retry on the
        same inputs is token-identical.  ``donates`` names the state a
        state -> state executable consumes (a state that is replaced is
        donated): an injected fault fires before ``fn`` and the retry
        finds the state live; a failure that left it consumed is raised
        as ``StateConsumedError`` — fatal, not retried — and the caller's
        rebuild path runs (engine/faults.guard_donation).

        Attribution: host submit→return time feeds
        ``dispatch_host_seconds{site}`` and the per-site stats
        ``/debug/engine`` serves, and the call is one
        ``dispatch:<site>`` phase (utils/tracing.py) — on the profiler's host plane whenever a
        session runs, in the TRACE=1 ring with ``host_ms``.  It never
        waits for the device: device time per site is read from the
        profiler's device trace, which shares the annotation's clock."""
        if locktrace.is_active():
            # LOCKTRACE=1: flag locks held across this dispatch (a
            # dispatch round-trip under a lock stalls every thread needing it).
            locktrace.note_dispatch(site)
        if donates is not None:
            fn = guard_donation(fn, donates)
        with tracing.phase(f"dispatch:{site}", cat="dispatch") as ph:
            t0 = time.perf_counter()
            out = self.watchdog.run(site, fn)
            t1 = time.perf_counter()
            self._note_dispatch(site, t1 - t0)
            ph.set(host_ms=round((t1 - t0) * 1e3, 3))
        return out

    def _note_dispatch(self, site: str, host_s: float) -> None:
        metrics.DISPATCH_HOST.labels(self.bundle.name, site).observe(host_s)
        with self._dispatch_stats_lock:
            st = self.dispatch_stats.setdefault(site, [0, 0.0])
            st[0] += 1
            st[1] += host_s

    def dispatch_attribution(self) -> dict:
        """Per-site dispatch accounting (``/debug/engine``):
        ``{site: {count, host_s, host_ms_avg}}``."""
        out = {}
        with self._dispatch_stats_lock:
            for site, (n, host) in sorted(self.dispatch_stats.items()):
                out[site] = {
                    "count": n,
                    "host_s": round(host, 4),
                    "host_ms_avg": round(host / n * 1e3, 3) if n else 0.0,
                }
        return out

    def fault_point(self, site: str) -> None:
        """Bare injection point for non-dispatch boundaries (e.g. the
        paged allocator's ``grow`` site, where an injected
        ``OutOfBlocks`` exercises the checkpoint-and-requeue path)."""
        if self.faults is not None:
            self.faults.fire(site)

    def reset_device_state(self) -> None:
        """Crash-recovery rebuild of everything living on the device:
        flush the prefix cache (its entries name buffers — or block
        ids — of the state being torn down), re-create the paged KV
        pool, re-place params.  Compiled executables survive (the
        process didn't die), so the rebuilt engine is warm: the first
        post-restart admission pays a device upload, not a compile.
        Caller (the decode loop's recovery path) owns dropping its own
        slot state and re-pointing at the fresh pool."""
        # Flush BEFORE swapping the pool: paged pins free through
        # on_evict into whatever ``kv_pool`` currently points at, and
        # those block ids belong to the OLD pool.  Demotion is
        # suspended — these pins name buffers of the state being torn
        # down, and any demotions still pending reference the OLD pool
        # too, so both free/die with it.  (Host-tier entries already
        # MATERIALIZED survive: host RAM outlives the rebuild.)
        self._host_demote_on = False
        try:
            if self.prefix_cache is not None:
                while self.prefix_cache.pop_lru() is not None:
                    pass
        finally:
            self._host_demote_on = True
        self._host_demote_pending = []
        if self.paged_kv and self.kv_pool is not None:
            from .kv_blocks import BlockPool

            self.kv_pool = BlockPool(
                self.kv_pool.num_blocks, self.kv_pool.block_bytes
            )
        self.params = self.replicas.place_params(self.bundle.params)

    # ------------------------------------------------------------------
    # dispatch

    def run_batch(self, feats: list[dict]) -> list[np.ndarray]:
        """Forward one formed batch; returns one f32/int row per item.

        Batches larger than the max bucket are split into sub-dispatches
        (the scheduler's ``max_batch`` normally prevents this).
        """
        import jax

        cap = max(self.batch_buckets)
        if len(feats) > cap:
            out: list[np.ndarray] = []
            for i in range(0, len(feats), cap):
                out.extend(self.run_batch(feats[i : i + cap]))
            return out

        with self._lock:
            if self.bundle.kind == KIND_IMAGE:
                images, n = self._collate_images(feats)
                batch = self.replicas.place_batch(images)
                logits = self._forward(self.params, batch)
            elif self.bundle.kind == KIND_TEXT:
                ids, mask, n = self._collate_text(feats)
                ids, mask = self.replicas.place_batch(ids, mask)
                logits = self._forward(self.params, ids, mask)
            else:  # seq2seq, non-streaming: ONE dispatch for encode +
                # init + done-aware chunked decode (early EOS exit);
                # all-greedy batches under SPEC_DECODE run verify
                # rounds instead of single-token steps.
                ids, mask, n = self._collate_text(feats)
                sp, sampled = self._collate_sample(feats, ids.shape[0])
                budgets = self._collate_budget(feats, ids.shape[0])
                ids, mask = self.replicas.place_batch(ids, mask)
                # Speculation is the LOW-CONCURRENCY lever (same gate
                # as stream routing): at large batches the
                # (spec_k+1)-wide verify window stops hiding under
                # weight streaming and low-acceptance traffic would
                # regress below the chunked scan.  Sampled rows ride
                # the same window via rejection-sampling acceptance
                # unless SPEC_SAMPLED=0 opted out.
                spec_batch = (
                    self.spec_enabled
                    and (not sampled or self.spec_sampled)
                    and n <= int(getattr(self.cfg, "spec_max_streams", 1))
                )
                if spec_batch:
                    tokens, steps = self._full_spec(
                        self.params, ids, mask, sp, budgets,
                        self.max_decode_len, self.spec_k, sampled,
                    )
                else:
                    tokens, steps = self._full(
                        self.params, ids, mask, sp, budgets,
                        self.max_decode_len, self.chunk_tokens, sampled,
                    )
                # tokens + step count in ONE transfer (each device_get
                # pays a full host<->device round-trip).
                rows, steps_np = jax.device_get((tokens, steps))
                rows = np.asarray(rows)
                self.last_decode_steps = int(steps_np)
                metrics.DECODE_STEPS.labels(self.bundle.name).observe(
                    self.last_decode_steps
                )
                return [rows[i] for i in range(n)]
            rows = np.asarray(jax.device_get(logits))
        return [rows[i] for i in range(n)]

    def _prefix_guard(self, length: int):
        """Static-shape guard for cache hits: the padded suffix bucket
        must keep positions inside the table AND the combined width
        inside the continuous loop's max-bucket slots."""
        s_max = max(self.seq_buckets)
        max_pos = int(getattr(self.bundle.cfg, "max_position", 1 << 30))

        def usable(p_len: int) -> bool:
            s_suf = bucket_for(
                max(length - p_len, 1), self.seq_buckets,
                self.replicas.seq_multiple(),
            )
            return (
                p_len + s_suf <= s_max
                and p_len + s_suf + self.max_decode_len <= max_pos
            )

        return usable

    def start_fused(self, feats: dict, params=None):
        """Collate + fused prefill-and-first-chunk for ONE stream,
        through the per-request prefix cache when it hits.  Returns
        (state, toks, sampled).  Caller must hold ``self._lock``.

        ``params`` overrides the dispatch tree — the continuous loop
        passes the adapter-overlaid params (models/lora.py) so a B=1
        admission prefills through its LoRA delta; None = the base
        tree, bit-identical to the pre-adapter path.

        Cache-hit path: the prompt's longest cached prefix (exact
        token-hash match at a seq-bucket length P) rides in as KV and
        only the suffix prefills — O(S) not O(P+S), per request.
        Miss path: normal full prefill, after which the prompt DONATES
        its own prefix KV (a single jitted slice of cache rows 0..P —
        free compute, the prefill already produced it)."""
        if params is None:
            params = self.params
        row_ids = np.asarray(feats["input_ids"], np.int32)[: int(feats["length"])]
        length = int(feats["length"])
        usable = self._prefix_guard(length)
        # Paged mode: the cache holds block-ref pins owned by the
        # continuous loop's pool — this per-stream path (oversized
        # prompts, spec routing, CONTINUOUS_BATCHING=0) stays
        # contiguous and must neither consume nor pollute them.
        prefix_cache = None if self.paged_kv else self.prefix_cache
        if prefix_cache is not None:
            m = prefix_cache.match(row_ids, length, usable=usable)
            if m is not None:
                p_len, pkv = m
                sfeats = dict(
                    feats,
                    input_ids=row_ids[p_len:],
                    length=np.int32(length - p_len),
                )
                ids, mask, _ = self._collate_text([sfeats])
                sp, sampled = self._collate_sample([feats], ids.shape[0])
                ids, mask = self.replicas.place_batch(ids, mask)
                state, toks = self._start_prefixed(
                    params, pkv, ids, mask, sp,
                    self.max_decode_len, self.chunk_tokens, sampled,
                )
                # A growing conversation must keep donating: the hit
                # state's cache holds the full contiguous prefix+suffix
                # KV, so capture at the LARGEST bucket this prompt now
                # covers — otherwise turn N stays pinned to turn 1's
                # bucket and re-prefills an ever-growing suffix.
                p_ins = prefix_cache.bucket_for_insert(length)
                if (
                    p_ins is not None
                    and p_ins > p_len
                    and not prefix_cache.contains(row_ids, p_ins)
                ):
                    prefix_cache.insert(
                        row_ids, p_ins, self._capture_prefix(state, p_ins)
                    )
                return state, toks, sampled
        ids, mask, _ = self._collate_text([feats])
        sp, sampled = self._collate_sample([feats], ids.shape[0])
        ids, mask = self.replicas.place_batch(ids, mask)
        state, toks = self._start(
            params, ids, mask, sp,
            self.max_decode_len, self.chunk_tokens, sampled,
        )
        if prefix_cache is not None:
            p_ins = prefix_cache.bucket_for_insert(length)
            if p_ins is not None and not prefix_cache.contains(
                row_ids, p_ins
            ):
                prefix_cache.insert(
                    row_ids, p_ins, self._capture_prefix(state, p_ins)
                )
        return state, toks, sampled

    def _capture_prefix(self, state, p_len: int, row: int = 0):
        """Prefix KV from a fresh prefill's cache rows [0, p_len) of
        batch row ``row`` (traced — one executable per p_len even when
        donating from a batched wave state) — one jitted slice
        dispatch, shaped like compute_prefix_kv's pytree so
        ``__prefix__`` consumers take it unchanged."""
        import jax

        if p_len not in self._slice_prefix:
            from jax import lax

            def cut(c, r):
                # A kv_quant cache entry is an (int8 payload, scale)
                # tuple: slice both so the captured prefix stays int8 —
                # half the cache-budget bytes, and the EXACT rows a
                # later quantized init copies back bit-identically.
                if isinstance(c, tuple):
                    return tuple(
                        lax.dynamic_slice_in_dim(x, r, 1, axis=0)[:, :p_len]
                        for x in c
                    )
                return lax.dynamic_slice_in_dim(c, r, 1, axis=0)[:, :p_len]

            def slc(st, r):
                return {
                    "k": [cut(c, r) for c in st.cache_k],
                    "v": [cut(c, r) for c in st.cache_v],
                }

            self._slice_prefix[p_len] = self._shared_jit(
                "slice_prefix", lambda: jax.jit(slc), statics=(p_len,)
            )
        return self._slice_prefix[p_len](state, np.int32(row))

    def generate_stream(self, feats: dict) -> Iterator[np.ndarray]:
        """Streaming seq2seq for one request: yields int32 token chunks
        (``chunk_tokens`` per device dispatch; variable-size chunks of
        ≥ chunk_tokens on the speculative path) until EOS or budget."""
        import jax

        if self.bundle.kind != KIND_SEQ2SEQ:
            raise ValueError(f"{self.bundle.name} does not support streaming")
        with self._live_streams_lock:
            self._live_streams += 1
            # Spec load gate, held on THIS path too (the Batcher's gate
            # only covers its continuous-loop routing): speculate only
            # while the concurrent per-stream count (self included)
            # stays within spec_max_streams.
            spec_ok = self._live_streams <= int(
                getattr(self.cfg, "spec_max_streams", 1)
            )
        try:
            if (
                self.spec_enabled
                and spec_ok
                and (
                    float(feats.get("temperature", 0.0)) == 0.0
                    or self.spec_sampled
                )
            ):
                # Greedy streams verify by argmax identity; sampled
                # ones by rejection sampling (SPEC_SAMPLED=0 opts them
                # back out to the normal chunked path for cross-path
                # seed stability).
                yield from self._spec_stream(feats)
                return
            with self._lock:
                # First chunk fused with encode+init (and routed
                # through the per-request prefix cache): TTFT = one
                # round-trip.  Guarded like the continuous loop's
                # dispatches (r18): the per-stream path used to bypass
                # the watchdog/fault-injector entirely.
                state, toks, sampled = self.dispatch_guard(
                    "prefill", lambda: self.start_fused(feats)
                )
                # One transfer for tokens+done — each device_get pays a
                # full host<->device round-trip, so never fetch them
                # separately.
                toks_np, done_np = self.dispatch_guard(
                    "fetch", lambda: jax.device_get((toks, state.done))
                )
                chunk, done = toks_np[0], bool(done_np[0])
            # Request max_tokens bounds chunk spending, and the final
            # chunk trims to the exact budget — raw emission never
            # overshoots, so the per-stream path stays token-identical
            # to the continuous loop (which enforces the same cap) for
            # budgets that are not chunk multiples.
            budget = self.budget_for(feats)
            produced = self.chunk_tokens
            yield chunk[:budget]
            if done:
                return
            while produced < budget:
                with self._lock:
                    state, toks, done_d = self.dispatch_guard(
                        "chunk",
                        lambda: self._gen_chunk(
                            self.params, state, self.chunk_tokens, sampled
                        ),
                        donates=state,
                    )
                    toks_np, done_np = self.dispatch_guard(
                        "fetch",
                        lambda: jax.device_get((toks, done_d)),
                    )
                    chunk, done = toks_np[0], bool(done_np[0])
                yield chunk[: budget - produced]
                produced += self.chunk_tokens
                if done:
                    return
        finally:
            with self._live_streams_lock:
                self._live_streams -= 1

    def _spec_stream(self, feats: dict) -> Iterator[np.ndarray]:
        """Speculative streaming (greedy): each dispatch runs
        ``chunk_tokens`` draft→verify rounds, emitting between
        chunk_tokens and chunk_tokens·(spec_k+1) tokens — token
        sequence identical to the normal greedy path.  Composes with
        the per-request prefix cache: a hit prefills only the suffix
        AND seeds the drafting history with the full prompt; a miss
        donates its prefix like start_fused."""
        import jax

        from ..models.spec import flatten_emitted

        n_verify = self.chunk_tokens
        budget = self.budget_for(feats)
        row_ids = np.asarray(feats["input_ids"], np.int32)[: int(feats["length"])]
        length = int(feats["length"])
        # Static executable variant: rejection-sampling acceptance for
        # temperature>0 requests (generate_stream gated on spec_sampled).
        sampled = float(feats.get("temperature", 0.0)) > 0.0
        # Same paged-mode bypass as start_fused: block-ref pins belong
        # to the continuous loop's pool, not this contiguous path.
        prefix_cache = None if self.paged_kv else self.prefix_cache
        with self._lock:
            hit = None
            if prefix_cache is not None:
                hit = prefix_cache.match(
                    row_ids, length, usable=self._prefix_guard(length)
                )
            if hit is not None:
                p_len, pkv = hit
                sfeats = dict(
                    feats,
                    input_ids=row_ids[p_len:],
                    length=np.int32(length - p_len),
                )
                ids, mask, _ = self._collate_text([sfeats])
                sp, _ = self._collate_sample([feats], ids.shape[0])
                ids, mask = self.replicas.place_batch(ids, mask)
                ss, out, ns = self.dispatch_guard(
                    "prefill",
                    lambda: self._spec_start_prefixed(
                        self.params, pkv, row_ids[:p_len], ids, mask,
                        sp, self.max_decode_len, n_verify, self.spec_k,
                        sampled,
                    ),
                )
                # Growing conversations keep donating from the hit
                # path (same rule as start_fused): capture the largest
                # bucket this prompt now covers.
                p_ins = prefix_cache.bucket_for_insert(length)
                if (
                    p_ins is not None
                    and p_ins > p_len
                    and not prefix_cache.contains(row_ids, p_ins)
                ):
                    prefix_cache.insert(
                        row_ids, p_ins, self._capture_prefix(ss.base, p_ins)
                    )
            else:
                ids, mask, _ = self._collate_text([feats])
                sp, _ = self._collate_sample([feats], ids.shape[0])
                ids, mask = self.replicas.place_batch(ids, mask)
                ss, out, ns = self.dispatch_guard(
                    "prefill",
                    lambda: self._spec_start(
                        self.params, ids, mask, sp,
                        self.max_decode_len, n_verify, self.spec_k,
                        sampled,
                    ),
                )
                if prefix_cache is not None:
                    p_ins = prefix_cache.bucket_for_insert(length)
                    if p_ins is not None and not prefix_cache.contains(
                        row_ids, p_ins
                    ):
                        prefix_cache.insert(
                            row_ids, p_ins,
                            self._capture_prefix(ss.base, p_ins),
                        )
            out_np, ns_np, done_np = self.dispatch_guard(
                "fetch", lambda: jax.device_get((out, ns, ss.base.done))
            )
        chunk = flatten_emitted(out_np, ns_np, 0)
        metrics.SPEC_EMITTED.labels(self.bundle.name).observe(
            int(chunk.size) / max(1, n_verify)
        )
        # A verify round can overshoot the budget mid-chunk; trim so the
        # stream never emits past it (normal-path contract).
        chunk = chunk[:budget]
        produced = int(chunk.size)
        yield chunk
        done = bool(done_np[0])
        # Depth-1 chain pipelining (the continuous loop's trick): the
        # spec state chain is pure device-side, so chunk k+1 dispatches
        # BEFORE chunk k's tokens are fetched — the ~RTT-long fetch
        # overlaps the next chunk's compute.  At most one dispatched
        # chunk is wasted at the tail (EOS/budget), and the optimistic
        # dispatch is skipped once the budget could already be covered.
        # (The chunk donates ``ss``: what is fetched after the next
        # dispatch — ``out``, ``ns``, ``done_d`` — are outputs of their
        # own, and ``ss`` is only ever the newest state.)
        def spec_chunk():
            return self.dispatch_guard(
                "chunk",
                lambda: self._spec_chunk(
                    self.params, ss, n_verify, self.spec_k, sampled
                ),
                donates=ss,
            )

        ahead = None
        while not done and produced < budget:
            with self._lock:
                if ahead is None:
                    ahead = spec_chunk()
                ss, out, ns, done_d = ahead
                ahead = None
                if produced + n_verify < budget:  # ≥1 token per round
                    ahead = spec_chunk()
                    ss = ahead[0]
                for arr in (out, ns, done_d):
                    try:
                        arr.copy_to_host_async()
                    except Exception:
                        pass
                out_np, ns_np, done_np = self.dispatch_guard(
                    "fetch",
                    lambda: jax.device_get((out, ns, done_d)),
                )
            chunk = flatten_emitted(out_np, ns_np, 0)
            metrics.SPEC_EMITTED.labels(self.bundle.name).observe(
                int(chunk.size) / max(1, n_verify)
            )
            chunk = chunk[: budget - produced]
            produced += int(chunk.size)
            done = bool(done_np[0])
            if chunk.size:
                yield chunk

    # ------------------------------------------------------------------
    # warmup: AOT-compile every bucket so p99 never pays a compile

    def warmup(self) -> float:
        """Compile all (batch × seq) buckets + decode scans.  Returns
        seconds spent; call at startup, before readiness flips true."""
        import jax

        from ..runtime.compile_cache import note_warm_phase

        with tracing.boot_phase("boot/warm/engine") as ph:
            seconds = self._warmup_inner(jax)
        note_warm_phase(self.bundle.name, "engine", ph.seconds)
        return seconds

    def _warmup_inner(self, jax) -> float:
        t0 = time.monotonic()
        mult = self._pad_multiple()
        batch_buckets = [b for b in self.batch_buckets if b % mult == 0 and b >= mult]
        if not batch_buckets:
            batch_buckets = [bucket_for(1, self.batch_buckets, mult)]
        if self.bundle.kind == KIND_IMAGE:
            for b in batch_buckets:
                self.run_batch(
                    [{"image": np.zeros((self.bundle.image_size,) * 2 + (3,), np.uint8)}]
                    * b
                )
        elif self.bundle.kind == KIND_TEXT:
            for b in batch_buckets:
                for s in self.seq_buckets:
                    feats = [
                        {"input_ids": np.ones(s, np.int32), "length": np.int32(s)}
                    ] * b
                    self.run_batch(feats)
        else:
            # Sampled executables (static sample=True) are distinct XLA
            # programs; warm them too or the first temperature>0 request
            # pays a request-path compile.  WARMUP_SAMPLING=0 skips them
            # for greedy-only deployments (halves seq2seq warmup).
            warm_sampled = os.environ.get(
                "WARMUP_SAMPLING", "1"
            ).lower() not in ("0", "false", "no")
            sampled_variants = (False, True) if warm_sampled else (False,)
            for b in batch_buckets:
                for s in self.seq_buckets:
                    feats = [
                        {"input_ids": np.ones(s, np.int32), "length": np.int32(s)}
                    ] * b
                    self.run_batch(feats)
                    if warm_sampled:
                        sampled_feats = [
                            dict(f, temperature=1.0, seed=0) for f in feats
                        ]
                        self.run_batch(sampled_feats)
            # The streaming start + follow-up chunk executables compile
            # per encoder seq bucket (KV-cache/cross-attn shapes depend
            # on it).  Warm both DIRECTLY — going through
            # generate_stream would skip the follow-up chunk whenever
            # the dummy prompt hits EOS inside the first chunk.
            for s in self.seq_buckets:
                feats = {"input_ids": np.ones(s, np.int32), "length": np.int32(s)}
                for flag in sampled_variants:
                    with self._lock:
                        ids, mask, _ = self._collate_text([feats])
                        sp, _ = self._collate_sample([feats], ids.shape[0])
                        ids, mask = self.replicas.place_batch(ids, mask)
                        state, _ = self._start(
                            self.params, ids, mask, sp,
                            self.max_decode_len, self.chunk_tokens, flag,
                        )
                        state, toks, _ = self._gen_chunk(
                            self.params, state, self.chunk_tokens, flag
                        )
                        jax.device_get(toks)
                # Prefix-cache executables: capture slicers for every
                # (prompt-bucket, prefix-bucket) pair — misses at ANY
                # bucket donate on-path — plus the (prefix × suffix)
                # _start_prefixed grid in both greedy and (when warmed)
                # sampled variants, so no cache interaction ever
                # compiles on the request path.
                if self.prefix_cache is not None:
                    with self._lock:
                        ids, mask, _ = self._collate_text([feats])
                        sp, _ = self._collate_sample([feats], ids.shape[0])
                        ids, mask = self.replicas.place_batch(ids, mask)
                        template, _ = self._start(
                            self.params, ids, mask, sp,
                            self.max_decode_len, self.chunk_tokens, False,
                        )
                        for p_len in self.seq_buckets:
                            if p_len > s - 1:
                                continue
                            pkv = self._capture_prefix(template, p_len)
                            if s != max(self.seq_buckets):
                                # The _start_prefixed grid only needs
                                # warming once (pkv shapes depend on
                                # p_len alone); smaller prompt buckets
                                # just warm their capture slicer above.
                                continue
                            for s_suf in self.seq_buckets:
                                if p_len + s_suf > max(self.seq_buckets):
                                    continue
                                sfeats = {
                                    "input_ids": np.ones(s_suf, np.int32),
                                    "length": np.int32(s_suf),
                                }
                                sids, smask, _ = self._collate_text([sfeats])
                                ssp, _ = self._collate_sample(
                                    [sfeats], sids.shape[0]
                                )
                                sids, smask = self.replicas.place_batch(
                                    sids, smask
                                )
                                for flag in sampled_variants:
                                    st2, toks2 = self._start_prefixed(
                                        self.params, pkv, sids, smask, ssp,
                                        self.max_decode_len,
                                        self.chunk_tokens, flag,
                                    )
                                    jax.device_get(toks2)
                                # Hit-path donation slicers: a cache
                                # hit captures a LARGER prefix from its
                                # own (narrower) state — warm those
                                # state-shape variants too.
                                for p_ins in self.seq_buckets:
                                    if p_len < p_ins <= p_len + s_suf - 1:
                                        self._capture_prefix(st2, p_ins)
                                # Spec × prefix composition: the
                                # prefixed spec start + its follow-up
                                # spec chunk per (prefix, suffix) pair,
                                # in every served sample variant.
                                if self.spec_enabled:
                                    for sflag in (
                                        (False, True)
                                        if (warm_sampled and self.spec_sampled)
                                        else (False,)
                                    ):
                                        ss3, out3, _ = self._spec_start_prefixed(
                                            self.params, pkv,
                                            np.ones(p_len, np.int32), sids,
                                            smask, ssp, self.max_decode_len,
                                            self.chunk_tokens, self.spec_k,
                                            sflag,
                                        )
                                        ss3, out3, _, _ = self._spec_chunk(
                                            self.params, ss3,
                                            self.chunk_tokens, self.spec_k,
                                            sflag,
                                        )
                                        jax.device_get(out3)
                # Speculative start + follow-up chunk compile per seq
                # bucket too (history/cache shapes depend on it); the
                # rejection-sampling variants are distinct executables.
                if self.spec_enabled:
                    spec_variants = (
                        (False, True)
                        if (warm_sampled and self.spec_sampled)
                        else (False,)
                    )
                    with self._lock:
                        ids, mask, _ = self._collate_text([feats])
                        sp, _ = self._collate_sample([feats], ids.shape[0])
                        ids, mask = self.replicas.place_batch(ids, mask)
                        for sflag in spec_variants:
                            ss, out, ns = self._spec_start(
                                self.params, ids, mask, sp,
                                self.max_decode_len, self.chunk_tokens,
                                self.spec_k, sflag,
                            )
                            ss, out, ns, _ = self._spec_chunk(
                                self.params, ss, self.chunk_tokens,
                                self.spec_k, sflag,
                            )
                            jax.device_get(out)
                    # _full_spec warms explicitly at n=1 ONLY when the
                    # pad-multiple filter removed batch bucket 1 above
                    # (REPLICAS>1): otherwise no warmup batch routes
                    # through the spec while_loop and the first
                    # non-streaming greedy request would compile on the
                    # request path.  (At REPLICAS=1 the bucket loop
                    # already covered it — don't re-decode the budget.)
                    if 1 not in batch_buckets:
                        self.run_batch([dict(feats)])
                        if warm_sampled and self.spec_sampled:
                            self.run_batch(
                                [dict(feats, temperature=1.0, seed=0)]
                            )
        dt = time.monotonic() - t0
        log.info("warmup compiled %s buckets in %.1fs", self.bundle.name, dt)
        return dt
