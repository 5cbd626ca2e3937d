"""Continuous batching for generative streams.

The round-2 design ran every stream as its own batch=1 decode loop on a
dedicated worker — N concurrent gpt2/t5 streams paid N independent
chunk-dispatch sequences.  This module replaces that with the
reference's own core idea (the dynamic-batching queue, SURVEY.md §2)
applied to generation: ONE batched ``generate_chunk`` dispatch serves
every live stream, and new requests are admitted at chunk boundaries
into free rows ("slots") of the shared decode state.

Why the model layer already supports this: GPT/T5 decode states are
fully per-row (per-row ``pos``/``write_idx``/``key_valid``/``done``/
rng chains — models/gpt.py, models/t5.py), so row i can sit at decode
step 40 of a 300-token prompt while row j starts step 0 of a 16-token
one.  Admission is a compiled scatter: the freshly prefilled batch=1
state (one ``_start`` dispatch at the request's own prompt bucket —
TTFT unchanged) is zero-padded up to the slot shapes and written into
row i with ``dynamic_update_slice``.  Over the paged pool a prefill
wave's rows land in their slots through ONE such dispatch
(``paged_insert``, ``_insert_wave``), not one a row.

One rule for the decode state: **a state that is replaced is donated**.
Every executable that takes the batched state and returns its successor
(chunk, insert, handoff, chunked-prefill window, host-tier
scatter) donates it, so the compiler aliases each KV buffer's input to
its output and writes a row in place; without it every call copied all
of the caches in and out (PERF.md section 6, PR 30).  What follows from
it: nothing outside ``self._state`` holds a leaf of a state — whatever
the loop fetches after a later dispatch (tokens, ``done``) is an
output of its own; executables that only read the state
(prefix and swap gathers) do not donate; and a dispatch that fails after
its state was consumed is fatal, not retried (engine/faults.py).

Dispatch economics: with S streams live, tokens/dispatch goes from
``chunk`` to ``S × chunk`` — where a dispatch's round-trip dominates
a chunk's compute, aggregate tokens/s scales ~linearly with
concurrency instead of flat (pre-round record, removed in PR 22; to
be re-measured on the attached chip).

Greedy/sampled rows mix freely in one batch: the sampled executable
(static ``sample=True``) computes argmax for rows with temperature 0,
bit-identical to the greedy path; the loop picks the greedy executable
whenever NO live row samples, so the common case never pays the
per-step [B, V] sort.

A freed slot's row keeps stepping until reused — its writes clamp to
``mode="drop"`` in the models and its outputs are discarded, so this
costs compute but never correctness; ``insert`` overwrites the whole
row on reuse.  The cost is BOUNDED and measured (the pre-round BASELINE
record (removed in PR 22) round 3):
a full-width chunk costs chunk(B=n_slots)/chunk(B=live) of a
right-sized one — 1.4× at llama-bf16 and gpt2 when ONE stream owns
the loop, and at llama-int8 the batched chunk is outright cheaper per
token than B=1 (0.86 vs 1.39 ms/step: weight streaming amortizes
across rows, dead or alive).  Width-bucketed compaction (per-width
chunk executables + live-row gather + slot remap) was considered and
deliberately NOT built: where the inter-chunk cadence is dominated by
the dispatch round-trip the saving is invisible, the worst case
(B=1 greedy) routes to the speculative per-stream path anyway, and
operators can right-size statically with MAX_STREAMS (slot count
follows it).  Revisit if profiles on the attached chip show the chunk
compute on the critical path.
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
from typing import Any, AsyncIterator

import numpy as np

from ..utils import metrics, tracing
from . import warm
from .programs import LoopPrograms

log = logging.getLogger(__name__)

_END = object()



def prefetch_to_host(*arrays) -> None:
    """Best-effort async device→host copy start: the later blocking
    fetch finds the data (mostly) on this side of the wire.  Backends
    without async copies just pay the round-trip at fetch time.  An
    argument may be a pytree (an expert model's (tokens, counts))."""
    import jax

    for arr in jax.tree.leaves(arrays):
        try:
            arr.copy_to_host_async()
        except Exception:
            pass


# A batched prefill wave runs the smallest rung that holds it, not
# ``n_slots`` rows: lone, a small wave, or the slot count.  Every rung is
# one more start + insert executable per seq bucket to load at every boot
# (~2.6 s a pair on a v5e at Mistral-7B widths, PERF.md section 6, PR 26),
# so there is ONE rung between: a wave of up to 4 rows costs live streams
# about one decode chunk; a larger one still runs the slot count.
_SMALL_WAVE_ROWS = 4


def wave_rungs(n_slots: int, multiple: int = 1) -> tuple[int, ...]:
    """Row counts a prefill wave may run at: 1 and ``_SMALL_WAVE_ROWS``,
    each rounded up to the placement's pad ``multiple`` and kept where
    below ``n_slots``, then ``n_slots`` itself (already a multiple: the
    loop rounds it)."""
    below = {-(-r // multiple) * multiple for r in (1, _SMALL_WAVE_ROWS)}
    return tuple(sorted(r for r in below if r < n_slots)) + (n_slots,)


class StreamClosedError(Exception):
    """The decode loop is shutting down."""


class _Stream:
    """One client stream: thread-safe bridge loop-thread → event loop.

    Doubles as the deadline queue's scheduled item (``klass`` /
    ``deadline`` / ``started`` / ``_removed``) and, under preemption,
    as its own checkpoint: ``tokens`` records every token DELIVERED to
    the consumer, so a preempted stream can resume token-identically —
    either by re-prefilling prompt+delivered (decoder-only causal LMs)
    or by replaying the whole deterministic generation with the first
    ``skip`` tokens suppressed."""

    __slots__ = (
        "feats", "chunks", "loop", "cancelled", "produced", "released",
        "budget", "klass", "deadline", "started", "kv", "kv_held",
        "skip", "tokens", "preempted", "t_in", "_removed",
        "blocks", "s_base", "s_lo", "shared_ids", "swap",
        "rid", "t_queued", "t_reserved", "t_emit", "done_journaled",
        "tenant", "adapter_slot", "ssm_row",
    )

    # Admission-ledger marker: paged mode accounts streams via the
    # block pool, batch calls via the byte ledger (admission.fits).
    is_stream = True

    def __init__(self, feats: dict, loop: asyncio.AbstractEventLoop,
                 budget: int):
        self.feats = feats
        self.chunks: asyncio.Queue = asyncio.Queue()
        self.loop = loop
        self.cancelled = threading.Event()
        self.produced = 0
        self.released = False  # loop-thread-owned: exactly-once release
        # Token budget (request max_tokens clamped to the server's
        # decode budget): the loop stops spending chunks on this row
        # once reached; the API layer trims to the exact count.
        self.budget = budget
        # Scheduling fields (set by submit_stream from the admission
        # controller; defaults = the seed's behavior).
        self.klass = "interactive"
        self.deadline: float | None = None
        self.started = False
        self.kv = 0
        self.kv_held = False
        # Preemption checkpoint state.
        self.skip = 0
        self.tokens: list[int] = []
        self.preempted = 0
        self.t_in = time.monotonic()
        self._removed = False
        # Paged-KV bookkeeping (engine/kv_blocks.py): this stream's
        # block table, the collated width its prefill scattered
        # ([s_lo, s_base + chunk) — s_lo > 0 on CoW prefix hits), and
        # any donor block ids it shares by refcount.
        self.blocks = None
        self.s_base = 0
        self.s_lo = 0
        self.shared_ids: list[int] = []
        # Host KV tier (engine/kv_blocks.py KVHostTier): the SwapEntry
        # holding this stream's checkpointed KV host-side, set at
        # swap-out; a resume with a live entry prefetches it back
        # instead of re-prefilling (docs/kv-tiering.md).
        self.swap = None
        # Observability: the request id (span/log correlation key —
        # the API stamps it on the feats dict), when this stream was
        # last (re-)queued (queue-wait span start) and when its last
        # chunk was delivered (stream_tbt_seconds cadence); when it
        # last left the queue (stream_admit_seconds runs from there to
        # its first emit).
        self.rid = str(feats.get("request_id") or "")
        self.t_queued = self.t_in
        self.t_reserved = self.t_in
        self.t_emit = 0.0
        # Write-ahead terminal marker: the journal's ``done`` record
        # must land BEFORE the consumer can observe the stream's end
        # (_journal_done), and exactly once across the emit site and
        # the release path.
        self.done_journaled = False
        # Multi-tenancy (tenancy/): the tenant label rides the stream
        # so fair-share dequeue and per-tenant SLO attribution never
        # re-derive it, and the adapter pool slot (0 = base weights)
        # is refcount-held for the stream's whole lifetime — acquired
        # at submit/adopt, released exactly once in _release.
        self.tenant = str(feats.get("tenant") or "")
        self.adapter_slot = 0
        # Its row of the recurrent state (a model with Mamba layers): from
        # its prompt's first window or its wave's insert until its blocks
        # go back (_ssm_take / _ssm_give).
        self.ssm_row: int | None = None

    def emit(self, item: Any) -> None:
        try:
            self.loop.call_soon_threadsafe(self.chunks.put_nowait, item)
        except RuntimeError:
            # Event loop closed: consumer is gone, nothing to deliver.
            self.cancelled.set()


class _PrefillJob:
    """One stream mid-chunked-prefill (PREFILL_CHUNK): host bookkeeping
    for the prompt windows already consumed plus the KV carried
    forward between them — a detached B=1 state (contiguous mode) or
    the stream's own pool blocks + table row (paged mode, where the
    windows write straight into the shared pools).  ``ready`` flips
    once the prompt is exhausted; the job then waits only on a free
    slot for its handoff."""

    __slots__ = (
        "st", "ids", "L", "p_len", "consumed", "s_total",
        "state", "sb", "table_row", "ready", "t_in",
    )

    def __init__(self, st: _Stream, ids: np.ndarray, L: int):
        self.st = st
        self.ids = ids
        self.L = L
        self.p_len = 0  # adopted/seeded prefix length (cache hit)
        self.consumed = 0  # absolute positions prefilled so far
        self.s_total = 0  # contiguous state prompt width
        self.state = None  # contiguous: detached B=1 device state
        self.sb = None  # paged: StreamBlocks being grown
        self.table_row = None  # paged: np table row (sentinel-padded)
        self.ready = False
        self.t_in = time.monotonic()


class _SwapInJob:
    """One checkpointed stream mid-swap-resume: its freshly allocated
    device blocks being filled back from the host tier, a bounded
    number per loop iteration (``_advance_swapins``).  Once every
    block is copied, the stream goes live through the chunked-prefill
    handoff (the restored KV is exactly what a fresh prefill of the
    resume prompt would have written, so the handoff contract is
    identical).  Duck-type-compatible with ``_PrefillJob`` where the
    handoff/failure helpers are shared."""

    __slots__ = (
        "st", "ids", "L", "p_len", "sb", "table_row", "copied",
        "ready", "state", "t_in", "resume_at",
    )

    def __init__(self, st: _Stream, ids: np.ndarray, L: int):
        self.st = st
        self.ids = ids
        self.L = L
        self.p_len = 0  # no CoW adoption: swap blocks are private
        self.sb = None  # StreamBlocks being filled
        self.table_row = None
        self.copied = 0  # device blocks already restored
        self.ready = False
        self.state = None  # _drop_job_resources compatibility
        self.t_in = time.monotonic()
        # Token position the restored KV covers.  == L for a full
        # resume (handoff straight to decode); < L for a MID-PREFILL
        # checkpoint's partial-prompt KV — the job converts into a
        # chunked-prefill job continuing at this boundary once every
        # restored block is copied (``_swapin_to_prefill``).
        self.resume_at = L


class ContinuousDecodeLoop:
    """Slot-based batched decode over one InferenceEngine.

    Single owner thread runs: admit pending streams at chunk
    boundaries → one batched generate_chunk dispatch → route each
    row's tokens to its stream → free done slots.  With chunked
    prefill on (PREFILL_CHUNK), long prompts prefill as bounded
    windows interleaved BETWEEN decode chunks instead of as one
    monolithic dispatch in front of them — see ``_advance_prefill``.
    """

    def __init__(self, engine, cfg):
        self.engine = engine
        self.max_streams = max(1, int(getattr(cfg, "max_streams", 8)))
        # Slot caches are sized for the LARGEST seq bucket; longer
        # prompts (engine pads past the bucket list for them) cannot be
        # inserted — the Batcher routes those to the per-stream path.
        self.max_prompt = max(engine.seq_buckets)
        # SPEC_CONTINUOUS: the shared state carries per-row drafting
        # histories and the shared chunk runs draft→verify rounds
        # (models/spec.py), so every live stream keeps the accepted-
        # token multiplier — each round emits 1..spec_k+1 tokens per
        # row instead of exactly 1.  Composes with the per-request
        # prefix cache: hit admissions prefill through the prefixed
        # wave starts and are recast through ``init_spec_fn`` at
        # slot-insert time like any other admission (the hit state's
        # narrower cache pads up to the slot shapes; the drafting
        # history is host-built from the FULL prompt, prefix included).
        self.spec = bool(
            getattr(cfg, "spec_continuous", False)
            and getattr(engine, "spec_enabled", False)
        )
        if getattr(cfg, "spec_continuous", False) and not self.spec:
            raise ValueError(
                "SPEC_CONTINUOUS needs SPEC_DECODE=ngram on a spec-capable "
                "family"
            )
        # Decoder-only families place the prompt at [p_len, p_len+L) of
        # the history (a startup PROMPT_PREFIX occupies [0, p_len) with
        # unknown ids); encoder-decoders place the ENCODER ids at the
        # front (t5.init_spec_state layout, offset read off the widths).
        pre = (
            engine.bundle.params.get("__prefix__")
            if isinstance(engine.bundle.params, dict) else None
        )
        if pre is not None:
            entry = pre["k"][0]
            # kv_quant stores the global prefix as (int8, scale) tuples.
            self._p_len = (
                entry[0].shape[1] if isinstance(entry, tuple)
                else entry.shape[1]
            )
        else:
            self._p_len = 0
        self._hist_w: int | None = None  # set by _build_empty_state
        self._kv_w: int | None = None
        # Chunked prefill (PREFILL_CHUNK; docs/chunked-prefill.md):
        # prompts longer than one window prefill as PREFILL_CHUNK-token
        # dispatches interleaved with the decode chunks — a long prompt
        # stalls live streams for at most ONE window's compute per
        # iteration instead of its whole prefill.  The knob also lifts
        # the loop's prompt ceiling past the largest seq bucket (the
        # round-8 routing-bug class: oversized prompts now chunk here
        # instead of silently falling to the legacy per-stream path),
        # so the slot state is sized for ``max_prompt`` below.
        self.prefill_chunk = int(getattr(engine, "prefill_chunk", 0) or 0)
        self._prefilling: list[_PrefillJob] = []
        self.prefill_chunk_dispatches = 0
        self.prefill_stall_s = 0.0
        self.prefill_budget = 0
        if self.prefill_chunk:
            if self.spec:
                raise ValueError(
                    "PREFILL_CHUNK does not compose with SPEC_CONTINUOUS "
                    "(the spec slot insert rebuilds the drafting history "
                    "from a monolithic collated prompt)"
                )
            if self._p_len:
                raise ValueError(
                    "PREFILL_CHUNK and PROMPT_PREFIX are mutually "
                    "exclusive; use PREFIX_CACHE=1"
                )
            max_pos = int(getattr(engine.bundle.cfg, "max_position", 0) or 0)
            cap = (
                max_pos - engine.max_decode_len if max_pos else self.max_prompt
            )
            want = int(getattr(cfg, "prefill_max_prompt", 0) or 0) or cap
            self.max_prompt = max(self.max_prompt, min(want, cap))
            self.prefill_budget = (
                int(getattr(cfg, "prefill_budget", 0) or 0)
                or self.prefill_chunk
            )
            from ..scheduler.policy import PrefillPacer

            self._pacer = PrefillPacer(
                weight=int(getattr(cfg, "class_weight", 4))
            )
        # Slot count must divide over the replica mesh's batch axis.
        mult = engine.replicas.pad_multiple()
        self.n_slots = -(-self.max_streams // mult) * mult
        self._wave_rungs = wave_rungs(self.n_slots, mult)
        # Block-paged KV (PAGED_KV=1): per-layer KV pools shared by all
        # slots + a host-owned per-slot block table that rides into
        # every dispatch as a traced argument.  Insert scatters a
        # prefill state into freshly allocated blocks, decode grows
        # block-by-block at chunk boundaries, frees return blocks the
        # moment a stream ends, and prefix-cache hits ADOPT the
        # donor's prompt blocks by refcount (CoW sharing).  A freed
        # slot's table row is the SENTINEL id (== pool size): the dead
        # row's further writes resolve out of range and drop, so a
        # reallocated block can never be corrupted by its previous
        # tenant (the paged mirror of the contiguous mode="drop"
        # clamp).
        self.paged = bool(getattr(engine, "paged_kv", False))
        # Prompt windows one prefill dispatch holds at most: the windows
        # of different prompts a boundary's budget admits go out as ONE
        # batched paged_prefill_chunk (the contiguous slab: one job a
        # dispatch, each owns its state).  Follows from the two values
        # the deployment states; no option of its own.
        self._prefill_width = (
            -(-self.prefill_budget // self.prefill_chunk)
            if self.paged and self.prefill_chunk else 1
        )
        bcfg = getattr(engine.bundle, "cfg", None)
        # Recurrent state rows (``_ssm_take``): one a slot — admission keeps
        # the streams that can hold one (live or in prefill) to ``n_slots``.
        self._ssm_free = None
        self._ssm_fused = False  # the rows' prompt scans run a fused kernel
        if self.paged and getattr(bcfg, "state_rows", False):
            self._ssm_free = list(range(self.n_slots))[::-1]
            self._ssm_fused = bcfg.scan_fused
        # A cross-decoder (layers that own nothing and run only where a
        # logit is read): prompt positions are counted through each half.
        self._cross_decoder = (
            getattr(bcfg, "cross_from", 0) < getattr(bcfg, "num_layers", 0))
        # (ring layers, keys a ring holds) of window layers whose store is a
        # ring a stream beside its state row, or None.
        self._ring = (
            (len(bcfg.ring_layers), int(bcfg.window_ring))
            if getattr(bcfg, "ring_layers", ()) else None)
        # (window layers, window) of a per-layer pattern, or None.
        types = getattr(bcfg, "layer_types", ())
        self._window_layers = (
            (types.count("window"), int(bcfg.window))
            if "window" in types else None
        )
        # Layers that cache keys: the attention layers (a recurrent or an
        # FFN-only layer has no pool), every layer of a model without kinds.
        self._attn_layers = (
            sum(1 for li in range(bcfg.num_layers)
                if bcfg.layer_kind(li).attention)
            if hasattr(bcfg, "layer_kind")
            else int(getattr(bcfg, "num_layers", 0)))
        # Those whose cache is a latent row a token (all of them, or none).
        self._latent_layers = (
            self._attn_layers if getattr(bcfg, "latent_lanes", 0) else 0)
        # (first, held) of the experts this tree holds: a chip's share.
        self._experts_held = (
            int(getattr(bcfg, "expert_first", 0) or 0),
            int(getattr(bcfg, "held", 0) or 0),
        )
        # A share's prompt dispatches since the last chunk dispatch: each
        # one's (counts [L, E], tokens) on the device, fetched with that
        # chunk (``_note_dispatched``).
        self._moe_windows: list = []
        # kind -> [rows ran, rows skipped] of the expert block, and kind ->
        # held rows of calls whose shuffles took the DMA kernels (/status).
        self.moe_rows: dict = {}
        self.moe_rows_fused: dict = {}
        if self.paged:
            from .kv_blocks import blocks_for

            if self.spec:
                raise ValueError(
                    "PAGED_KV does not compose with SPEC_CONTINUOUS yet"
                )
            self.block_size = int(engine.kv_block_size)
            self.pool = engine.kv_pool
            self.nb_max = blocks_for(
                self.max_prompt + engine.max_decode_len, self.block_size
            )
            self._table = np.full(
                (self.n_slots, self.nb_max), self.pool.num_blocks, np.int32
            )
            self._kv_tails: list[tuple] = []  # set with the pools
            self._dispatched_steps: dict[int, int] = {}
        # The loop's executables (engine/programs.py), each built at its
        # first use; warm-up (engine/warm.py) walks them before serving.
        self.programs = LoopPrograms(
            engine, n_slots=self.n_slots, spec=self.spec,
            block_size=getattr(self, "block_size", 0),
            nb_max=getattr(self, "nb_max", 0),
            state_rows=self._ssm_free is not None, params_for=self._mp,
        )
        # Host-RAM KV tier (KV_HOST_BUDGET_MB; docs/kv-tiering.md):
        # checkpointed streams gather the blocks behind their resume
        # prompt device→host instead of freeing-and-recomputing, and
        # resume by prefetching them back — KV_PREFETCH_BLOCKS per
        # iteration while decode is live, unbounded on idle — through the
        # same interleave seam as chunked prefill.  The tier object lives
        # on the ENGINE (it survives reset_device_state; a fleet shares one).
        # Swap-resume jobs + swap-out copies pending materialization
        # (exist in contiguous mode too so the shared loop code never
        # branches on their presence; only paged loops populate them).
        self._swapping: list[_SwapInJob] = []
        self._swap_pending: list = []
        self._swap_hold = False  # device suspect (watchdog cut)
        self.swap_chunk_blocks = max(
            1, int(getattr(cfg, "kv_prefetch_blocks", 4) or 4)
        )
        self.swap_outs = 0
        self.swap_ins = 0
        self.swap_fallbacks = 0
        self.swap_out_bytes = 0
        self.swap_in_bytes = 0
        self.prefetch_blocks_total = 0
        self.prefetch_blocks_live = 0
        self.host_prefix_promotes = 0
        # Double-buffered host prep (``_stage_host_prep``;
        # docs/compilation.md): iteration N+1's paged growth pass and
        # table upload, staged while chunk N is in flight and consumed
        # only if nothing it derived from has moved since.
        self._staged_prep: dict | None = None
        self.prep_staged = 0
        self.prep_hits = 0
        self.prep_misses = 0
        self.tokens_emitted = 0
        # SLA scheduling (scheduler/policy.py): the old unbounded
        # handoff Queue + instant reject past max_streams is now a
        # BOUNDED deadline-aware wait queue — up to ``max_stream_queue``
        # streams wait (EDF within class, class-weighted across) beyond
        # the active slots; 0 keeps the historical instant-503 contract.
        from ..scheduler.policy import DeadlineQueue

        self.max_stream_queue = max(
            0, int(getattr(cfg, "max_stream_queue", 0))
        )
        self.queue = DeadlineQueue(
            self.max_streams + self.max_stream_queue,
            weight=int(getattr(cfg, "class_weight", 4)),
        )
        # Shared AdmissionController (set by the Batcher; None when the
        # loop is driven directly, e.g. in tests — defaults apply).
        self.admission = None
        # Multi-tenancy (tenancy/, set by the Batcher; both None when
        # TENANTS/ADAPTER_DIR are unset — the loop then builds and
        # dispatches exactly the pre-tenancy graphs).
        self.tenants = None   # tenancy.accounts.TenantRegistry
        self.adapters = None  # tenancy.adapters.AdapterPool
        # Interactive arrivals may preempt batch-class slot holders.
        self.preempt = bool(getattr(cfg, "preempt", True))
        self.preemptions = 0  # observability + test hook
        self._stream_ewma_s = 1.0
        # Latency EWMAs (scheduler/policy.ScalingGovernor signals, also
        # /status.fleet.scaling): time-to-first-chunk and inter-chunk
        # cadence, updated at delivery.  0.0 until the first sample.
        self.ttft_ewma_s = 0.0
        self.tbt_ewma_s = 0.0
        self.active: dict[int, _Stream] = {}
        self.sampled_slots: set[int] = set()
        self.free: list[int] = list(range(self.n_slots))
        self._state = None  # batched decode state (device), loop-thread-owned
        # Depth-D decode pipelining: the state chain is pure
        # device-side, so up to ``chain_depth`` chunk dispatches ride
        # in flight before the oldest is fetched — steady-state
        # inter-chunk cadence drops to ~max(RTT/D, chunk compute)
        # (the round-3 loop was fixed at depth 1, which is why it lost
        # to N overlapped legacy chains at a long dispatch round-trip).
        # Each entry: ((toks, done), {slot: stream at dispatch time}).
        # Snapshots keep late-arriving tokens from leaking into a
        # slot's next tenant.  Depth starts at the configured value
        # (min 1); STREAM_PIPELINE=0 means warm() auto-tunes it from
        # the measured RTT/chunk-compute ratio.
        self._inflight_chunks: list = []
        self.chain_depth = max(1, int(getattr(cfg, "stream_pipeline", 0) or 1))
        self._auto_depth = int(getattr(cfg, "stream_pipeline", 0) or 0) == 0
        self._admitted = 0  # event-loop-owned admission counter
        # Streams running OUTSIDE this loop (the Batcher's legacy
        # per-stream path for oversized prompts) count against the same
        # MAX_STREAMS total; the Batcher wires this to its own counter.
        self.external_active = lambda: 0
        # Crash recovery (engine/supervisor.py): when a Supervisor is
        # attached (the Batcher does, SUPERVISE=1 default), a fatal
        # dispatch fault or loop death checkpoints every live stream
        # via the delivered-token cursor, rebuilds the device state
        # (fresh KV pool, params re-placed, prefix cache flushed) and
        # requeues the checkpoints for token-identical resume — up to
        # the supervisor's restart budget.  None (direct construction,
        # tests, SUPERVISE=0) keeps the historical error-every-stream
        # behavior.
        self.supervisor = None
        # Fleet wiring (engine/fleet.py; all None/unset outside a
        # fleet — the single-replica path never touches them):
        # ``failover(streams, exc, cause)`` receives every live
        # stream's checkpoint when this loop dies (restart budget
        # spent, loop-thread death, or breaker eviction) instead of
        # error-terminating them; ``on_fault``/``on_ok`` feed the
        # replica's circuit breaker; ``request_evacuation`` asks the
        # loop to hand everything over at the next iteration top.
        self.replica_id = int(getattr(engine, "replica_id", 0))
        self.failover = None
        self.on_fault = None
        self.on_ok = None
        self.dead = False
        self._evacuate_req = threading.Event()
        self._evict_cause = "evicted"
        # A fatal fault detected off the loop's main try (e.g. during
        # a prefill whose streams were checkpoint-requeued in place):
        # raised at the next iteration top so the shared recovery path
        # runs with clean pending lists.
        self._fault_pending: BaseException | None = None
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._thread_lock = threading.Lock()
        # Idle admission (``_collect_burst``): seconds the last wave of
        # each rung took from its dispatch to its rows' first chunks —
        # the longest the loop holds a wave of that rung for requests
        # the server is still reading.  Warm-up seeds the lone rung with
        # a chunk's measured round trip; nothing measured = no wait.
        self._wave_seconds: dict[int, float] = {}
        # The widest gap between two arrivals of the last idle wave (0.0
        # where it was a lone row): what the next one's first row borrows.
        self._idle_gap_s = 0.0
        self.idle_wait_rows = 0     # rows idle admissions' waits added
        self.idle_waits_capped = 0  # waits that ended on the cap
        # Waves that met chunks in flight, the chunks delivered ahead
        # of those waves' fetches (``_deliver_ahead_of_wave``), and the
        # waves whose start went out ahead of their iteration's chunk.
        self.waves_behind_chunks = 0
        self.chunks_ahead_of_wave = 0
        self.waves_ahead_of_chunk = 0
        # Where this loop's thread spends its wall time, by phase, always
        # on (utils/tracing.LoopTable; /status.decode.loop_time).  The
        # idle admissions that waited at all, and for how long, are its
        # ``inside`` row ``idle_admit``.
        self.loop_time = tracing.LoopTable(engine.bundle.name)
        metrics.register_exporter(self.loop_time)
        # Admissions dispatched but not yet fetched/inserted; the loop's
        # failure handler must terminate these consumers too.
        self._pending_admissions: list = []
        # Streams popped off `pending` whose prefill has NOT yet been
        # dispatched this iteration: the failure handler must be able
        # to terminate them — a chunk-dispatch exception between the
        # pop and the dispatch would otherwise orphan their consumers
        # (blocked forever) and leak max_streams slots.
        self._pending_wave: list = []
        # Observability + test hooks: how many device dispatches this
        # loop has issued (the whole point is that chunk_dispatches
        # scales with the LONGEST stream, not the stream count).
        self.prefill_dispatches = 0
        self.chunk_dispatches = 0
        # Flight recorder (utils/tracing.py): the engine owns the ring;
        # duck-typed test engines without one record nowhere.  The
        # pacer's hold/grant decisions land in the same ring.
        self._flight = getattr(engine, "flight", None)
        if self.prefill_chunk:
            self._pacer.recorder = self._flight
        # SLO burn-rate tracker (r20; scheduler/policy.SLOTracker):
        # per-priority-class TTFT/TBT objectives from the SLO_* knobs
        # feed multi-window burn-rate gauges and the optional
        # SCALE_UP_SLO_BURN governor signal.  None when every
        # objective knob is 0 (the default) — zero new work on the
        # emit path, bit-identical behavior.  A fleet shares replica
        # 0's tracker (engine/fleet.py re-points it) so the burn rate
        # is fleet-wide by construction.
        from ..scheduler.policy import SLOTracker

        self.slo = SLOTracker.from_cfg(engine.bundle.name, cfg)
        metrics.CHAIN_DEPTH.labels(engine.bundle.name).set(self.chain_depth)

    # ------------------------------------------------------------------
    # event-loop side

    def submit_stream(self, feats: dict) -> AsyncIterator[np.ndarray]:
        """Admission-checked stream entry; mirrors Batcher.submit_stream.

        Sheds with ``QueueFullError`` once ``max_streams`` active plus
        ``max_stream_queue`` waiting streams exist — unless the
        newcomer outranks a waiter (lower class or later deadline),
        which is then shed in its place.  A queued stream whose
        deadline passes before its first dispatch fails with
        ``DeadlineExceededError`` (the API maps it to 504)."""
        from ..scheduler.policy import QueueFullError

        if self._stop.is_set():
            raise RuntimeError("decode loop is stopped")
        if (
            float(feats.get("temperature", 0.0) or 0.0) > 0.0
            and feats.get("seed") is None
        ):
            # Pin the sampling seed at admission: any checkpoint resume
            # (preemption, crash recovery) REPLAYS the generation, and
            # an unseeded row would draw a fresh seed at re-collate —
            # the replay would diverge from tokens already delivered.
            import random

            feats["seed"] = random.getrandbits(32)
        adm = self.admission
        st = _Stream(
            feats, asyncio.get_running_loop(), self.engine.budget_for(feats)
        )
        with tracing.phase("admission", cat="sched", rid=st.rid) as sp:
            if adm is not None:
                klass, deadline = adm.classify(feats)
                try:
                    klass, kv = adm.admit(feats, klass)
                except QueueFullError as e:
                    if e.retry_after_s is None:
                        e.retry_after_s = self._retry_after_s()
                    self._shed(e.reason, st.tenant)
                    raise
                st.klass, st.deadline, st.kv = klass, deadline, kv
                sp.set(klass=st.klass, kv=st.kv)
            total = self._admitted + int(self.external_active())
            if total >= self.max_streams + self.max_stream_queue:
                victim = self.queue.evict_for(st)
                if victim is None:
                    if adm is not None:
                        adm.release_lease(feats)
                    self._shed("queue_full", st.tenant)
                    raise QueueFullError(
                        f"{total} streams active >= max_streams="
                        f"{self.max_streams}+{self.max_stream_queue} queued",
                        retry_after_s=self._retry_after_s(),
                    )
                self._shed("queue_full", victim.tenant)
                self._finish(victim, QueueFullError(
                    "shed for higher-priority stream",
                    retry_after_s=self._retry_after_s(),
                ))
            if self.adapters is not None and feats.get("adapter_id"):
                # Pin the LoRA pool slot for the stream's lifetime —
                # AFTER quota/capacity (a shed must not churn the pool)
                # and BEFORE _admitted (no slot, no admission).  A full
                # pool sheds honestly rather than silently serving base.
                from ..tenancy.adapters import AdapterBusy

                try:
                    st.adapter_slot = self.adapters.acquire(
                        str(feats["adapter_id"])
                    )
                except AdapterBusy as e:
                    if adm is not None:
                        adm.release_lease(feats)
                    self._shed("adapter_pool", st.tenant)
                    raise QueueFullError(
                        str(e), reason="adapter_pool",
                        retry_after_s=e.retry_after_s,
                    ) from e
            self._admitted += 1
            # Write-ahead admission record (runtime/durability.py):
            # journaled BEFORE the stream can produce anything, so a
            # SIGKILL at any later point finds it at replay.  None
            # (JOURNAL_DIR unset) = the pre-durability path exactly.
            j = self._journal()
            if j is not None and st.rid:
                j.admit(st.rid, feats, st.klass, st.budget)
            st.t_queued = time.monotonic()
            # Where stream_api_seconds ends and stream_queue_wait_seconds
            # begins (api/app._open_stream closes the first token's sum).
            feats["t_queued"] = st.t_queued
            self.queue.put(st, force=True)  # bound enforced just above
        self._ensure_thread()
        return self._consumer_gen(st)

    def _consumer_gen(self, st: _Stream):
        """The event-loop side of one stream: drain its chunk queue
        until the terminal sentinel (shared by live admissions and
        journal-replay resumes)."""

        async def gen():
            try:
                while True:
                    item = await st.chunks.get()
                    if item is _END:
                        break
                    if isinstance(item, BaseException):
                        raise item
                    yield item
            finally:
                # Consumer gone (disconnect or full drain): the loop
                # thread frees the slot at the next chunk boundary.
                st.cancelled.set()

        return gen()

    def _dec_admitted(self) -> None:
        self._admitted -= 1

    # ------------------------------------------------------------------
    # loop-thread side

    def _ensure_thread(self) -> None:
        with self._thread_lock:
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._run, name="decode-loop", daemon=True
                )
                self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout=30)

    def _journal(self):
        """The process's write-ahead stream journal when durability is
        on (JOURNAL_DIR; runtime/durability.py); None otherwise.  Read
        through the engine on every call — a fleet shares ONE journal
        across replicas, and it survives engine rebuilds."""
        return getattr(self.engine, "journal", None)

    def _disk_tier(self):
        """The disk KV tier once its payload files are attached to the
        live pool leaf layout (paged mode only); None otherwise."""
        if not self.paged:
            return None
        d = getattr(self.engine, "kv_disk", None)
        if d is None or not d.enabled or d.pool.leaves is None:
            return None
        return d

    def _release(self, st: _Stream) -> None:
        """Exactly-once per stream (loop thread, or the event loop for
        a stream that never reached the loop thread)."""
        if not st.released:
            st.released = True
            # Terminal journal record: replay must not resume this
            # stream (delivered in full, errored, or cancelled).
            # Usually already written by _journal_done at the emit
            # site (write-ahead); this covers streams that end with
            # no terminal emission (consumer cancelled).
            self._journal_done(st)
            self._drop_swap(st, disk_too=True)  # terminal: no reader left
            if self.admission is not None:
                self.admission.release(st)
            if st.adapter_slot and self.adapters is not None:
                # Drop the LoRA pool refcount exactly once, at the same
                # terminal point as the quota lease — a preempted stream
                # keeps its slot across checkpoints (its resume decodes
                # through the same delta).
                self.adapters.release(st.adapter_slot)
                st.adapter_slot = 0
            dt = time.monotonic() - st.t_in
            tr = tracing.tracer()
            if tr is not None:
                # The whole stream's lifetime (submit → release), the
                # parent interval its queue-wait/prefill/decode spans
                # tile — the per-request view of where the time went.
                tr.add(
                    "stream", cat="sched", rid=st.rid, t0=st.t_in, dur=dt,
                    produced=st.produced, klass=st.klass,
                    preempted=st.preempted,
                )
            self._stream_ewma_s = 0.8 * self._stream_ewma_s + 0.2 * dt
            try:
                st.loop.call_soon_threadsafe(self._dec_admitted)
            except RuntimeError:
                # Loop closed (shutdown/test teardown): the counter dies
                # with the loop; decrement directly so a restarted
                # consumer-side view stays sane.
                self._admitted -= 1

    def _shed(self, reason: str, tenant: str = "") -> None:
        metrics.SHED.labels(self.engine.bundle.name, reason).inc()
        if self.tenants is not None and reason != "quota":
            # Per-tenant attribution (bounded label; "" → anon).  Quota
            # sheds are already attributed at the admission gate.
            self.tenants.note_shed(tenant, reason)
        if self._flight is not None:
            self._flight.event("shed", reason=reason)

    def _retry_after_s(self) -> float:
        est = (self._admitted + 1) * self._stream_ewma_s / max(
            1, self.max_streams
        )
        return min(60.0, max(1.0, est))

    def _fits(self, st: _Stream) -> bool:
        return self.admission is None or self.admission.fits(st)

    def _reserve(self, st: _Stream) -> None:
        # The stream just left the wait queue: its queue-wait interval
        # is [t_queued, now] (re-stamped on every checkpoint requeue,
        # so resumes get their own observation and span).
        st.t_reserved = time.monotonic()
        wait = max(0.0, st.t_reserved - st.t_queued)
        metrics.STREAM_QUEUE_WAIT.labels(self.engine.bundle.name).observe(wait)
        tr = tracing.tracer()
        if tr is not None:
            tr.add(
                "queue_wait", cat="sched", rid=st.rid, t0=st.t_queued,
                dur=wait, klass=st.klass, resumed=bool(st.started),
            )
        if self.admission is not None:
            self.admission.reserve(st)

    def _class_gauges(self) -> None:
        from ..scheduler.policy import BATCH, INTERACTIVE

        for klass in (INTERACTIVE, BATCH):
            metrics.CLASS_QUEUE_DEPTH.labels(
                self.engine.bundle.name, "stream", klass
            ).set(self.queue.waiting(klass))

    def _journal_done(self, st: _Stream) -> None:
        """WRITE-AHEAD terminal record, exactly once: the journal must
        learn a stream is over BEFORE the consumer can observe its end.
        The converse order loses the race a kill -9 runs against it —
        the client sees the stream finish, the journal still holds it
        incomplete, and restart replay resurrects (and re-runs) a
        stream its consumer already closed (graftlint: write-ahead)."""
        if st.done_journaled:
            return
        j = self._journal()
        if j is not None and st.rid:
            j.done(st.rid)
        st.done_journaled = True

    def _finish(self, st: _Stream, item: Any = _END) -> None:
        self._journal_done(st)
        st.emit(item)
        self._release(st)

    def _free_slot(self, slot: int) -> None:
        st = self.active.pop(slot, None)
        self.sampled_slots.discard(slot)
        self.free.append(slot)
        # Paged: blocks return to the pool the moment the stream ends
        # (early EOS, cancel, budget) — THE exact-ledger property; the
        # contiguous layout holds its reservation until slot release.
        self._release_blocks(slot, st)
        if st is not None:
            self._release(st)

    def _run(self) -> None:
        """Thread entry: the iteration loop, plus last-resort cleanup.
        If the loop body ever dies on something its per-iteration
        handler cannot catch (BaseException), every consumer still
        gets a terminal error instead of hanging forever — a dead
        loop thread must never strand its clients."""
        self.loop_time.bind()
        try:
            self._run_loop()
        except BaseException as e:  # pragma: no cover - defensive
            log.exception("decode loop thread died")
            self._abort_all(e)
            raise
        finally:
            self.loop_time.unbind()

    def _abort_all(self, exc: BaseException) -> None:
        """Terminal error to every queued, pending and active stream."""
        if self.failover is not None and not self.dead:
            # Fleet mode: even a loop-thread death hands its streams
            # over instead of stranding them (the "one wedged loop
            # takes down the listener" failure this layer removes).
            try:
                self._evacuate(exc, "loop_death")
                return
            except Exception:
                log.exception(
                    "failover evacuation failed; error-terminating"
                )
        for st, *_ in self._pending_admissions:
            self._finish(st, exc)
        self._pending_admissions = []
        for st in self._pending_wave:
            self._finish(st, exc)
        self._pending_wave = []
        for job in self._prefilling:
            self._drop_job_resources(job)
            self._finish(job.st, exc)
        self._prefilling = []
        for job in self._swapping:
            self._drop_job_resources(job)
            self._finish(job.st, exc)
        self._swapping = []
        for st in self.queue.drain_all():
            self._finish(st, exc)
        for slot in list(self.active):
            st = self.active.get(slot)
            if st is not None:
                self._journal_done(st)
                st.emit(exc)
            self._free_slot(slot)
        self._inflight_chunks.clear()
        # A revived thread (next submission) must never reuse a state
        # the dead one may have left half-mutated.
        self._state = None
        self.sampled_slots.clear()

    def _run_loop(self) -> None:
        log.info("continuous decode loop up: %d slots", self.n_slots)
        # Every stretch of an iteration runs under a phase (FLAT
        # siblings, utils/tracing.phase): what the loop's table reads as
        # ``unnamed`` is the glue between them.
        n_wave = 0
        while not self._stop.is_set():
            self.loop_time.lap(
                len(self.active), n_wave, len(self._inflight_chunks)
            )
            n_wave = 0
            try:
                # The fleet asked for this replica's streams (breaker
                # open past FLEET_EVICT_S): evacuate at this iteration
                # top — a clean boundary, nothing in flight is lost.
                if self._evacuate_req.is_set() and self.failover is not None:
                    with tracing.phase("loop/recover"):
                        self._evacuate(
                            StreamClosedError("replica evicted by the fleet"),
                            self._evict_cause,
                        )
                    continue
                # A fatal fault parked by the prefill path (its streams
                # already checkpoint-requeued): run the shared recovery
                # now, with clean pending lists.
                if self._fault_pending is not None:
                    e, self._fault_pending = self._fault_pending, None
                    raise e
                with tracing.phase("loop/housekeeping"):
                    # Stale waiters shed as fast 504s BEFORE any admission
                    # work — never prefill a request nobody is waiting for.
                    self._expire_queued()
                    # Host KV tier drains, at the chunk boundary: pending
                    # swap-out copies materialize into the host buffers
                    # (the async device→host transfers started at gather
                    # time have usually landed), and evicted prefix pins
                    # queued for demotion gather out.
                    self._drain_swapouts()
                    self._drain_demotions()
                # Already-landed in-flight results route NOW (paged):
                # EOS'd rows' blocks return to the pool before this
                # iteration's growth pass instead of after it, and the
                # freed slots open admission capacity below.
                self._deliver_ready()
                if (
                    not self.active
                    and not self._inflight_chunks
                    and not self._prefilling
                    and not self._swapping
                    and self.queue.qsize() == 0
                ):
                    # The wait's name is its cause: a request the API
                    # has read and not queued yet is the program's to
                    # wait for, an empty server is the clients'.
                    with tracing.phase(
                        "loop/await_api" if self.queue.expected()
                        else "loop/idle"
                    ):
                        st = self.queue.pop(timeout=0.05, fits=self._fits)
                    if st is None:
                        continue
                    with tracing.phase("loop/queue_pop"):
                        self._reserve(st)
                    wave = [st]
                else:
                    wave = []
                # Interactive work waiting with every slot busy: at
                # this chunk boundary, checkpoint batch-class slot
                # holders and re-queue them so the wave below can admit
                # the interactive arrivals instead of shedding them.
                if (
                    self.preempt
                    and not wave
                    and not self.free
                    and self.queue.waiting("interactive") > 0
                ):
                    with tracing.phase("loop/preempt"):
                        self._preempt_for_interactive()
                # Chunk boundary: admit everything that fits, as ONE
                # wave — N prefill dispatches queue on the device and a
                # single combined transfer fetches all their first
                # chunks, so a wave costs one round-trip, not N.
                # Streams mid-chunked-prefill count against the slot
                # bound too: they were admitted first and will need a
                # slot at handoff — later short prompts must not
                # strand them slot-less.
                with tracing.phase("loop/queue_pop"):
                    while (
                        len(wave) + len(self.active) + len(self._prefilling)
                        + len(self._swapping)
                        < self.n_slots
                    ):
                        st = self.queue.pop_nowait(fits=self._fits)
                        if st is None:
                            break
                        self._reserve(st)
                        wave.append(st)
                # With no work in flight a partial wave costs every
                # straggler a wave of its own: hold this one for the
                # requests the server is still reading.  At a chunk
                # boundary the work in flight gives them that window.
                if wave and not self.active and not self._inflight_chunks:
                    self._collect_burst(wave)
                n_wave = len(wave)
                with tracing.phase("loop/housekeeping"):
                    self._class_gauges()
                # Depth-D pipeline: keep up to chain_depth chunks in
                # flight — chunk k's ~RTT-long fetch overlaps later
                # chunks' dispatch + compute + async host copy, so the
                # steady-state cadence is ~max(RTT/D, chunk compute).
                # Dispatch is ALSO gated on remaining work: once every
                # active stream's budget is covered by chunks already
                # in flight, dispatching more only wastes device/link
                # bandwidth and delays completion detection.
                #
                # The order of an iteration is the chunk's HOST half
                # (paged: the live rows' growth pass and the table),
                # the wave's start, the chunk's device dispatch.  A
                # start reads the request's own inputs and the
                # parameters and returns a state of its own — nothing of
                # the decode state or the pool — and joins the batch by
                # the insert after its fetch, so the chunk holds the
                # rows it held wherever the start sits.  The device
                # runs what was in flight, the start, then the chunk: a
                # newcomer's first token does not wait out a chunk that
                # computes nothing of its, and a live stream's one gap
                # across the admission is start + chunk, with no host
                # round trip inside it.  (A wave that is all prompt
                # windows or swap-ins dispatches nothing and hands back
                # no admissions.)  The growth pass stays ahead of the
                # wave because the wave may take from the pool (a
                # swap-in's blocks, a promoted prefix): live streams
                # keep their claim on a dry pool and a resumed stream
                # waits for blocks, never the other way round.  The
                # wave is pending from before any of it, so a fault in
                # either half of the chunk finds its streams.
                dispatched = False
                self._pending_wave = wave
                live = bool(self.active) and self._work_remains()
                table = None
                if live and self.paged:
                    with tracing.phase("loop/chunk_prep"):
                        table = self._chunk_table()
                    live = table is not None  # a dry pool took every row
                t_admit = time.monotonic()
                t_wave = t_admit if wave and self.active else None
                n_ahead = len(self._inflight_chunks)
                if wave:
                    with tracing.phase("loop/wave_dispatch"):
                        self._pending_admissions = self._admit_dispatch(wave)
                self._pending_wave = []
                if live:
                    with tracing.phase("loop/chunk_dispatch"):
                        self._dispatch_chunk(table)
                    dispatched = True
                if self._pending_admissions:
                    if dispatched:
                        self.waves_ahead_of_chunk += 1
                        metrics.WAVES_AHEAD_OF_CHUNK.labels(
                            self.engine.bundle.name
                        ).inc()
                    rows = self._wave_rows(len(self._pending_admissions))
                    # The host reads in the device's order: the chunks
                    # dispatched before the start as they land, then the
                    # start; the chunk behind it stays in flight for
                    # this iteration's usual delivery below.
                    self._deliver_ahead_of_wave(n_ahead)
                    self._admit_complete(self._pending_admissions)
                    self._pending_admissions = []
                    self._wave_seconds[rows] = time.monotonic() - t_admit
                self._note_wave_stall(t_wave)
                # Chunked prefill rides BEHIND the decode dispatch and
                # the wave admission: live streams' next chunk is
                # already queued on the device, so a window here delays
                # decode cadence by at most its own compute.
                advanced = False
                if self._swapping:
                    with tracing.phase("loop/swap_advance"):
                        advanced = self._advance_swapins()
                advanced = self._advance_prefill() or advanced
                # Double-buffered host prep: with the chunk just
                # dispatched still in flight (its fetch below blocks
                # for ~RTT), stage the NEXT dispatch's growth plan +
                # table upload now — host prep rides the device's
                # compute window instead of the gap between dispatches.
                if dispatched:
                    with tracing.phase("loop/stage_prep"):
                        self._stage_host_prep()
                if len(self._inflight_chunks) > self.chain_depth:
                    self._deliver_oldest()
                elif self._inflight_chunks and not dispatched:
                    # Nothing left to dispatch: the whole in-flight
                    # chain drains in ONE combined fetch (a per-chunk
                    # fetch would pay ~one host<->device round-trip EACH on the
                    # stream tail — the dominant cost at short decode
                    # budgets).
                    self._deliver_all()
                elif (
                    not dispatched and not advanced and not wave
                    and not self.active
                ):
                    # Waiters exist but none fit the KV budget (no
                    # admission, no work in flight): poll, don't spin.
                    with tracing.phase("loop/poll"):
                        time.sleep(0.01)
                with tracing.phase("loop/housekeeping"):
                    self._record_iteration()
            except Exception as e:
                with tracing.phase("loop/recover"):
                    recovered = self._recover(e)
                if recovered:
                    continue
                if self.failover is not None:
                    # Fleet mode: instead of error-terminating, hand
                    # every live stream's checkpoint to a healthy
                    # replica for token-identical resume.  A lost
                    # device gets its own cause so the fleet can
                    # retire the chip from future placements.
                    from .faults import is_device_loss

                    if is_device_loss(e):
                        cause = "device_lost"
                    elif (self.supervisor is not None
                          and self.supervisor.failed):
                        cause = "budget"
                    else:
                        cause = "fault"
                    self._evacuate(e, cause)
                    continue
                log.exception("decode loop iteration failed")
                n_lost = 0
                for st, *_ in self._pending_admissions:
                    self._finish(st, e)
                    n_lost += 1
                self._pending_admissions = []
                for st in self._pending_wave:
                    self._finish(st, e)
                    n_lost += 1
                self._pending_wave = []
                for job in self._prefilling:
                    self._drop_job_resources(job)
                    self._finish(job.st, e)
                    n_lost += 1
                self._prefilling = []
                for job in self._swapping:
                    self._drop_job_resources(job)
                    self._finish(job.st, e)
                    n_lost += 1
                self._swapping = []
                for slot in list(self.active):
                    st = self.active.get(slot)
                    if st is not None:
                        self._journal_done(st)
                        st.emit(e)
                        n_lost += 1
                    self._free_slot(slot)
                if n_lost:
                    metrics.STREAMS_LOST.labels(
                        self.engine.bundle.name, str(self.replica_id),
                        "fault",
                    ).inc(n_lost)
                # A failed dispatch may have already consumed (donated)
                # the state buffers — rebuild lazily on next admission.
                self._state = None
                self._staged_prep = None
                self._inflight_chunks.clear()
                self.sampled_slots.clear()
                # Restart budget exhausted: the engine is declared
                # broken — stop the loop (the shutdown path below ends
                # every queued consumer) and leave /readyz permanently
                # unready via the supervisor's ``failed`` flag.
                if self.supervisor is not None and self.supervisor.failed:
                    self._stop.set()
        # Shutdown: end every remaining consumer cleanly.
        self._staged_prep = None  # slot frees below return every block
        self._drain_swapouts()  # free demotion refs; ledger stays exact
        if self.paged:
            # Demotions still queued on the engine never gather now:
            # return their device refs so the pool ledger drains.
            pending = getattr(self.engine, "_host_demote_pending", [])
            if pending:
                self.engine._host_demote_pending = []
                for _k, pp in pending:
                    self.pool.free(list(pp.block_ids))
        for job in self._prefilling:
            self._drop_job_resources(job)
            self._finish(job.st, StreamClosedError("server stopping"))
        self._prefilling = []
        for job in self._swapping:
            self._drop_job_resources(job)
            self._finish(job.st, StreamClosedError("server stopping"))
        self._swapping = []
        for st in self.queue.drain_all():
            self._finish(st, StreamClosedError("server stopping"))
        for slot in list(self.active):
            st = self.active.get(slot)
            if st is not None:
                self._journal_done(st)
                st.emit(StreamClosedError("server stopping"))
            self._free_slot(slot)

    def _burst_cap_s(self, k: int) -> float:
        """The longest an idle loop holds a wave of ``k`` rows for more:
        what the wave it would run now costs (``_wave_seconds``, the
        nearest rung seen where this one has not run yet).  Past that
        a second wave would have served the stragglers as soon."""
        seen = self._wave_seconds
        return seen.get(self._wave_rows(k)) or max(seen.values(), default=0.0)

    def _collect_burst(self, wave: list) -> None:
        """An idle loop's admission wave: keep popping while the wave
        has room and the queue holds a row or a request the server has
        read is still on its way to it (``DeadlineQueue.expected``: up
        where the API has parsed a body, down where the stream was put
        or the request failed), so a burst lands as ONE wave and not as
        a lone start with its stragglers behind it.  Where the server
        reads faster
        than its clients write, the count touches zero between two
        arrivals of one burst: the loop then stays for a quiet gap of
        twice the widest gap between the arrivals it already holds or
        of the last idle wave, whichever is wider (a burst pauses for
        longer late than early, and a lone row has no gaps of its own).
        After a burst the loop so expects another; after a lone request,
        or on a fresh loop, it expects none, finds nothing announced
        and goes at once.  Bounded by ``_burst_cap_s``."""
        name = self.engine.bundle.name
        t0 = time.monotonic()
        n0 = len(wave)
        waited = False
        ts = sorted(st.t_queued for st in wave)
        last = ts[-1]
        own = max((b - a for a, b in zip(ts, ts[1:])), default=0.0)
        st = None  # a row the blocking wait handed back, not yet taken
        while len(wave) < self.n_slots:
            with tracing.phase("loop/queue_pop"):
                while len(wave) < self.n_slots:
                    if st is None:
                        st = self.queue.pop_nowait(fits=self._fits)
                    if st is None:
                        break
                    self._reserve(st)
                    wave.append(st)
                    own = max(own, st.t_queued - last)
                    last = max(last, st.t_queued)
                    st = None
                now = time.monotonic()
                quiet = max(0.0, last + 2.0 * max(own, self._idle_gap_s) - now)
                left = t0 + self._burst_cap_s(len(wave)) - now
            if len(wave) >= self.n_slots or (
                not quiet and not self.queue.expected()
            ):
                break
            waited = True
            # Blocks under ``loop/await_api`` / ``loop/await_burst``, a
            # slice of the wait at a time, each named by its cause.
            st = self.queue.pop_expected(left, quiet, fits=self._fits)
            if st is None:
                break
        self._idle_gap_s = own
        if not waited:
            return
        capped = self.queue.expected() > 0 and len(wave) < self.n_slots
        self.loop_time.note("idle_admit", time.monotonic() - t0)
        self.idle_wait_rows += len(wave) - n0
        self.idle_waits_capped += capped
        metrics.IDLE_ADMIT_ROWS.labels(name).inc(len(wave) - n0)
        if capped:
            metrics.IDLE_ADMIT_CAPPED.labels(name).inc()

    def _record_iteration(self) -> None:
        """One flight-recorder frame per non-idle loop iteration: batch
        composition, slot occupancy, queue depths, KV pool state."""
        fl = self._flight
        if fl is None or not fl.size:
            return
        if not (
            self.active or self._prefilling or self._swapping
            or self._inflight_chunks
        ):
            return
        rec = dict(
            active=len(self.active),
            free_slots=len(self.free),
            queued=self.queue.qsize(),
            prefilling=len(self._prefilling),
            swapping=len(self._swapping),
            inflight_chunks=len(self._inflight_chunks),
            chunk_dispatches=self.chunk_dispatches,
            prefill_dispatches=self.prefill_dispatches,
            slots={
                str(slot): {
                    "rid": st.rid, "klass": st.klass,
                    "produced": st.produced, "budget": st.budget,
                }
                for slot, st in self.active.items()
            },
        )
        if self.paged:
            rec["pool_free_blocks"] = self.pool.free_blocks
            rec["pool_used_blocks"] = self.pool.used_blocks
        fl.record_iteration(**rec)

    def _expire_queued(self) -> None:
        """Fail every queued stream whose deadline passed while it
        waited — the consumer raises before any response bytes went
        out, so the API layer returns a real 504."""
        from ..scheduler.policy import DeadlineExceededError

        for st in self.queue.expire():
            self._shed("deadline", st.tenant)
            self._finish(st, DeadlineExceededError(
                "deadline passed while queued; stream shed before dispatch"
            ))

    # -- crash recovery ------------------------------------------------

    def _checkpoint_requeue(self, st: _Stream) -> bool:
        """Checkpoint one stream (delivered-token cursor) and requeue
        it through admission for token-identical resume; finished or
        cancelled streams just end.  Returns True when requeued."""
        if self.admission is not None:
            self.admission.release(st)
        if st.cancelled.is_set() or st.budget - st.produced <= 0:
            self._finish(st)
            return False
        self._requeue_preempted(st)
        return True

    def _fail_streams(self, streams: list[_Stream], exc: Exception) -> None:
        """Prefill-path failure delivery for ``streams`` (not yet in a
        slot).  A fatal DEVICE fault under a supervisor checkpoints
        and requeues them — nothing was delivered yet, so resume is a
        clean, token-identical restart — and arms an engine rebuild
        for the next iteration.  Anything else (a poisoned request, a
        per-wave shape bug) error-terminates just these consumers, so
        one bad request can never take the loop down."""
        from .faults import StateConsumedError, is_fatal_device

        if self.supervisor is not None and is_fatal_device(exc):
            for st in streams:
                self._checkpoint_requeue(st)
            self._fault_pending = exc
            return
        for st in streams:
            self._finish(st, exc)
        if isinstance(exc, StateConsumedError):
            # Unsupervised, but the decode state went with the failed
            # dispatch: the loop's handler ends what lived in it and
            # rebuilds lazily, at the next iteration top.
            self._fault_pending = exc

    def _fail_preactive(self, st: _Stream, exc: Exception) -> None:
        """An insert or handoff failed for a stream not yet in a slot:
        the failure is this consumer's alone — unless the dispatch had
        already consumed the batched state, which every live stream
        shares: that takes ``_fail_streams``' fatal route."""
        from .faults import StateConsumedError

        if isinstance(exc, StateConsumedError):
            self._fail_streams([st], exc)
        else:
            self._finish(st, exc)

    def _recover(self, exc: Exception) -> bool:
        """Supervised crash recovery, on the loop thread: checkpoint
        every pending and active stream via the delivered-token cursor
        (``produced`` advances only at delivery, so in-flight chunks
        that were never fetched are simply not part of any checkpoint
        — no token is ever dropped or re-sent), tear down and rebuild
        the device state, and requeue the checkpoints through
        admission.  Returns False — caller error-terminates everything
        — when no supervisor is attached or the restart budget is
        spent."""
        if self.on_fault is not None:
            # Feed the replica's circuit breaker (engine/fleet.py)
            # BEFORE deciding recoverability: consecutive faults open
            # the breaker even while the restart budget still grants.
            self.on_fault()
        if self.failover is not None:
            from .faults import is_device_loss

            if is_device_loss(exc):
                # A lost device cannot be rebuilt around: the in-place
                # restart would re-place params and KV pools onto the
                # SAME placement, whose dead shard kills every
                # collective.  Skip the supervisor ladder entirely —
                # the caller evacuates the whole group to survivors and
                # the fleet respawns it on healthy devices.
                return False
        sup = self.supervisor
        if sup is None or not sup.allow_restart():
            # Unrecoverable (no supervisor, or the budget is spent and
            # the supervisor just dumped): leave a post-mortem either
            # way — the caller error-terminates every stream next.
            if sup is None and self._flight is not None:
                self._flight.dump(
                    f"unsupervised loop fault: {type(exc).__name__}: {exc}"
                )
            return False
        eng = self.engine
        # Dump BEFORE the rebuild mutates the rings' subject: the
        # post-mortem must show the iterations that led here.
        if self._flight is not None:
            self._flight.dump(
                f"fatal fault, supervised restart {sup.restarts}/"
                f"{sup.max_restarts}: {type(exc).__name__}: {exc}"
            )
        log.warning(
            "decode loop fault (%s: %s); supervised engine restart %d/%d",
            type(exc).__name__, exc, sup.restarts, sup.max_restarts,
        )
        # Host KV tier: a fault injected BEFORE its dispatch (or raised
        # by a fetch) leaves the pre-fault pools addressable, so active
        # streams' resume KV can swap out during the checkpoint below.
        # Held in two cases, where the streams resume by recompute
        # instead: a watchdog cut (the device may be wedged and a gather
        # could hang too), and a dispatch that failed AFTER consuming
        # the state it donates — the pools went with it.
        from .faults import DispatchTimeoutError, is_consumed

        self._swap_hold = isinstance(
            exc, DispatchTimeoutError
        ) or is_consumed(self._state)
        # A staged host-prep plan names blocks of the pools being torn
        # down: discard it plain (each stream's checkpoint/release
        # below returns its WHOLE block list, staged grants included).
        self._staged_prep = None
        recovered = 0
        for st, *_ in self._pending_admissions:
            recovered += self._checkpoint_requeue(st)
        self._pending_admissions = []
        for st in self._pending_wave:
            recovered += self._checkpoint_requeue(st)
        self._pending_wave = []
        for job in self._prefilling:
            # Partial-prompt KV swaps out against the pre-fault pools
            # (skipped under _swap_hold) before the deref below.
            self._swap_out_job(job)
            if self.paged and job.sb is not None:
                # Deref into the OLD pool (discarded below) so the
                # StreamBlocks object can't double-free later.
                job.sb.release()
                job.sb = None
            job.state = None
            recovered += self._checkpoint_requeue(job.st)
        self._prefilling = []
        for job in self._swapping:
            # Mid-prefetch resume: drop the half-filled device blocks
            # (old pool) and requeue; the HOST copy survives the
            # rebuild, so the retry still swap-resumes.
            if job.sb is not None:
                job.sb.release()
                job.sb = None
            recovered += self._checkpoint_requeue(job.st)
        self._swapping = []
        for slot in list(self.active):
            st = self.active.pop(slot)
            # _checkpoint_for_resume swaps the resume KV to the host
            # tier (gather against the pre-fault pools) and derefs the
            # blocks into the OLD pool (discarded below).
            recovered += self._checkpoint_requeue(st)
            if self.paged and st.blocks is not None:
                # Finished/cancelled streams skip the checkpoint path:
                # plain deref into the old pool.
                st.blocks.release()
                st.blocks = None
        self._swap_hold = False
        self.sampled_slots.clear()
        self.free = list(range(self.n_slots))
        self._inflight_chunks.clear()
        # Materialize the swap-outs gathered above BEFORE the rebuild
        # discards the old pools (the gathered copies are their own
        # buffers, but the host write must happen while this thread
        # still owns them — nothing else drains during recovery).
        self._drain_swapouts()
        self._state = None
        # Device-side rebuild: fresh KV pool, params re-placed, prefix
        # cache flushed (compiled executables survive — the process is
        # alive — so the rebuilt engine is warm).
        eng.reset_device_state()
        if self.paged:
            self.pool = eng.kv_pool
            self._table = np.full(
                (self.n_slots, self.nb_max), self.pool.num_blocks, np.int32
            )
            self._dispatched_steps.clear()
        if self.admission is not None:
            self.admission.pool = eng.kv_pool
            self.admission.note_pool()
        metrics.ENGINE_RESTARTS.labels(eng.bundle.name).inc()
        if recovered:
            metrics.STREAMS_RECOVERED.labels(
                eng.bundle.name, str(self.replica_id), "restart"
            ).inc(recovered)
        log.info(
            "engine rebuilt; %d stream checkpoint(s) requeued for "
            "token-identical resume", recovered,
        )
        return True

    # -- fleet failover (engine/fleet.py) ------------------------------

    def request_evacuation(self, cause: str = "evicted") -> None:
        """Ask the loop to hand every live stream to the fleet at the
        next iteration top (breaker-eviction path; thread-safe)."""
        self._evict_cause = cause
        self._evacuate_req.set()

    def _inc_admitted(self) -> None:
        self._admitted += 1

    def adopt_stream(self, st: _Stream) -> None:
        """Failover entry: enqueue another replica's checkpointed
        stream here for token-identical resume.  The checkpoint is
        just feats + cursor (``_checkpoint_for_resume``), so adoption
        is ordinary re-admission: re-estimate the KV footprint against
        THIS replica's pool, count it against this loop's admission,
        queue it.  Called from the dead replica's loop thread."""
        from ..scheduler.policy import QueueFullError

        entry = getattr(st, "swap", None)
        if entry is not None and not self._is_disk_entry(entry):
            tier = self._host_tier()
            if (
                tier is None or tier.pool is None
                or entry.pool is not tier.pool or not entry.alive
            ):
                # The checkpoint's host copy lives in a tier this loop
                # cannot read (non-shared deployment) or died: fall
                # back to the recast/replay recompute resume.  (Disk-
                # tier entries — journal-replay resumes — defer to
                # ``_start_swapin``'s disk→host promotion instead.)
                self._drop_swap(st)
                self.swap_fallbacks += 1
                metrics.KV_SWAP_RESUMES.labels(
                    self.engine.bundle.name, "fallback"
                ).inc()
        if self.admission is not None:
            st.kv = self.admission.kv_bytes_for_resume(
                st.feats, swap_tokens=self._swap_tokens(st)
            )
        # Re-pin the LoRA adapter against THIS loop's pool: the slot
        # index harvested from the dead replica indexes a pool that no
        # longer exists.  Failure ends the stream honestly — resuming
        # an adapter stream through base weights would silently change
        # its tokens.
        st.adapter_slot = 0
        aid = str(st.feats.get("adapter_id") or "")
        if aid:
            err = None
            if self.adapters is None:
                err = QueueFullError(
                    f"adopting replica has no adapter pool for {aid!r}",
                    reason="adapter_pool", retry_after_s=1.0,
                )
            else:
                from ..tenancy.adapters import AdapterBusy

                try:
                    st.adapter_slot = self.adapters.acquire(aid)
                except (AdapterBusy, KeyError) as e:
                    retry = getattr(e, "retry_after_s", 1.0)
                    err = QueueFullError(
                        str(e), reason="adapter_pool", retry_after_s=retry,
                    )
            if err is not None:
                self._shed("adapter_pool", st.tenant)
                try:
                    st.loop.call_soon_threadsafe(self._inc_admitted)
                except RuntimeError:
                    self._admitted += 1
                self._finish(st, err)
                return
        try:
            st.loop.call_soon_threadsafe(self._inc_admitted)
        except RuntimeError:
            self._admitted += 1
        if self._flight is not None:
            self._flight.event(
                "adopt_stream", rid=st.rid, klass=st.klass,
                budget=st.budget, skip=st.skip,
            )
        st.t_queued = time.monotonic()
        self.queue.put(st, force=True)
        self._ensure_thread()

    def resume_stream(self, feats: dict, delivered: list[int]):
        """Journal-replay re-admission (runtime/durability.py): rebuild
        a crashed process's stream from its journaled admission record
        and delivered-token cursor, and re-admit it through the SAME
        checkpoint machinery in-process resumes use — greedy decoder-
        only streams recast (prompt+delivered re-prefill), everything
        else replays with the first ``len(delivered)`` tokens
        suppressed.  Event-loop side; returns the consumer generator
        (continuation tokens only — the journaled prefix is the
        reconnect endpoint's to serve), or None when nothing remains
        to resume (the stream had already delivered its budget)."""
        st = _Stream(
            feats, asyncio.get_running_loop(), self.engine.budget_for(feats)
        )
        adm = self.admission
        if adm is not None:
            klass, _deadline = adm.classify(feats)
            # Deliberately no deadline: the original one lapsed while
            # the process was down, and failing the resume on it would
            # turn a survived crash into a 504.
            st.klass = klass
        st.tokens = [int(t) for t in delivered]
        st.produced = len(st.tokens)
        # Re-establish the journal record when this journal has never
        # seen the rid (a normal restart replay compacted the admit in
        # already; an adopter handed a checkpoint out-of-band has not)
        # — the continuation's cursor records need a base to extend.
        j = self._journal()
        if j is not None and st.rid and st.rid not in j.streams:
            j.admit(st.rid, feats, st.klass, st.budget)
            j.tokens(st.rid, st.tokens)
        if not self._checkpoint_for_resume(st):
            return None
        # A disk-tier copy of the checkpoint's resume KV (write-through
        # spill from a previous life) rides the admission as the swap
        # entry; ``_start_swapin`` promotes it disk→host→device, and
        # every failure path lands on the recompute resume.
        d = getattr(self.engine, "kv_disk", None)
        if self.paged and d is not None and d.enabled and st.rid:
            entry = d.get(("stream", st.rid))
            if (
                entry is not None and entry.alive
                and entry.tokens <= int(st.feats["length"])
            ):
                st.swap = entry
        self.adopt_stream(st)
        return self._consumer_gen(st)

    def _harvest_checkpoint(self, st: _Stream) -> _Stream | None:
        """Checkpoint one stream for failover: release this replica's
        ledger hold and admission count (the adopter re-takes both),
        or end the stream if nothing remains to resume."""
        if self.admission is not None:
            self.admission.release(st)
        if st.adapter_slot and self.adapters is not None:
            # The corpse's LoRA slot ref: the adopter re-pins against
            # ITS pool, so this one must drain with the dead replica.
            self.adapters.release(st.adapter_slot)
            st.adapter_slot = 0
        if not self._checkpoint_for_resume(st):
            self._finish(st)
            return None
        try:
            st.loop.call_soon_threadsafe(self._dec_admitted)
        except RuntimeError:
            self._admitted -= 1
        return st

    def _evacuate(self, exc: BaseException, cause: str) -> None:
        """This replica is dead (restart budget spent, loop death, or
        breaker eviction): checkpoint EVERY pending and active stream
        at its delivered-token cursor, free every device resource the
        corpse holds (blocks, prefix pins — the pool ledger must drain
        to zero), stop the loop, and hand the checkpoints to the fleet
        for token-identical resume on a healthy replica.  A replica
        crash costs latency, never output."""
        self.dead = True
        self._stop.set()
        # Staged host prep dies with the corpse: the stream releases
        # below return every block, staged grants included.
        self._staged_prep = None
        harvested: list[_Stream] = []

        def h(st: _Stream) -> None:
            out = self._harvest_checkpoint(st)
            if out is not None:
                harvested.append(out)

        for st, *_ in self._pending_admissions:
            h(st)
        self._pending_admissions = []
        for st in self._pending_wave:
            h(st)
        self._pending_wave = []
        for job in self._prefilling:
            # Partial-prompt KV swaps to the (fleet-shared) host tier
            # first, then real frees — not the _recover deref: the
            # pool outlives this loop and its ledger must read zero.
            self._swap_out_job(job)
            self._drop_job_resources(job)
            h(job.st)
        self._prefilling = []
        for job in self._swapping:
            # Mid-prefetch resume: return the device blocks; the host
            # entry rides the checkpoint to the adopter (usable when
            # the fleet shares one tier, dropped otherwise).
            self._drop_job_resources(job)
            h(job.st)
        self._swapping = []
        for st in self.queue.drain_all():
            h(st)
        for slot in list(self.active):
            st = self.active.pop(slot)
            # Checkpoint FIRST: _checkpoint_for_resume swaps the
            # resume KV out to the (possibly fleet-shared) host tier
            # while the blocks still exist, then real-frees them.
            h(st)
            self._release_blocks(slot, st)
        self.sampled_slots.clear()
        self.free = list(range(self.n_slots))
        self._inflight_chunks.clear()
        self._state = None
        # Drop the dead replica's prefix-cache pins: nothing will ever
        # serve from them again, and they are the last refs keeping
        # pool blocks from draining to zero.
        eng = self.engine
        if self.paged:
            # Queued-but-ungathered demotions can never copy now:
            # return their device refs so the corpse's ledger drains.
            pending = getattr(eng, "_host_demote_pending", [])
            if pending:
                eng._host_demote_pending = []
                for _k, pp in pending:
                    self.pool.free(list(pp.block_ids))
        if self.paged and eng.prefix_cache is not None:
            # Demotion suspended: self._state is already dropped here,
            # so the pins' content is unreachable — plain frees.
            prev = getattr(eng, "_host_demote_on", True)
            eng._host_demote_on = False
            try:
                while eng.prefix_cache.pop_lru() is not None:
                    pass
            finally:
                eng._host_demote_on = prev
        # Materialize the harvested checkpoints' swap-outs NOW: the
        # adopter reads the host buffers, and this loop never drains
        # again.
        self._drain_swapouts()
        if self._flight is not None:
            self._flight.event(
                "failover", cause=cause, streams=len(harvested),
                replica=self.replica_id,
            )
            self._flight.dump(
                f"replica {self.replica_id} dead ({cause}): "
                f"{type(exc).__name__}: {exc}"
            )
        log.warning(
            "replica %d dead (%s): evacuating %d stream checkpoint(s) "
            "to the fleet", self.replica_id, cause, len(harvested),
        )
        if self.failover is not None:
            self.failover(harvested, exc, cause)
        else:  # defensive: no fleet attached — error-terminate
            for st in harvested:
                self._journal_done(st)
                st.emit(exc)

    # -- preemption ----------------------------------------------------

    def _preempt_for_interactive(self) -> None:
        """Interactive work is waiting and every slot is busy: evict
        batch-class slot holders (latest deadline first) at this chunk
        boundary.  The victim's checkpoint is its delivery cursor —
        the tokens the consumer already received — and it re-queues
        (``started``: exempt from expiry/eviction) for resumption when
        capacity returns; its consumer never sees the gap."""
        # Anti-thrash guard: while a checkpointed stream still waits to
        # resume, interactive arrivals rely on the class-weighted queue
        # instead of evicting MORE batch work — every preemption
        # discards that stream's in-flight compute, so unbounded
        # preemption under sustained overload melts total throughput
        # without helping the interactive class.
        if self.queue.waiting_started() > 0:
            return
        want = min(self.queue.waiting("interactive"), self.n_slots)
        victims = [
            (slot, st)
            for slot, st in self.active.items()
            if st.klass == "batch"
            and not st.cancelled.is_set()
            and st.preempted < 2  # a stream yields at most twice
        ]
        if not victims:
            return
        victims.sort(
            key=lambda e: (
                e[1].deadline if e[1].deadline is not None else float("inf")
            ),
            reverse=True,
        )
        n = 0
        for slot, st in victims:
            if n >= want or len(self.free) >= want:
                break
            self.active.pop(slot)
            self.sampled_slots.discard(slot)
            self.free.append(slot)
            if self.admission is not None:
                self.admission.release(st)
            # Checkpoint BEFORE the block release: the host KV tier
            # copies the resume prompt's blocks out inside
            # _checkpoint_for_resume while they still exist.
            self._requeue_preempted(st)
            self._release_blocks(slot, st)
            self.preemptions += 1
            metrics.PREEMPTIONS.labels(self.engine.bundle.name).inc()
            if self._flight is not None:
                self._flight.event("preempt", rid=st.rid, slot=slot)
            n += 1
        if n:
            # The vacated slots must go to the interactive waiters, not
            # straight back to the batch class we just preempted.
            self.queue.prefer_interactive()

    def _checkpoint_for_resume(self, st: _Stream) -> bool:
        """Prepare one stream's token-identical resume off its
        delivered-token cursor; False when there is nothing left to
        resume (finished or cancelled — the caller just ends it).

        Two token-identical resume strategies:
        - **Recast** (decoder-only causal LMs, greedy): the remaining
          generation from prompt+delivered IS the continuation, so the
          stream re-enters admission as a fresh prompt — riding the
          slot-recast machinery prefix-hit admissions already use, and
          often hitting the prefix cache the original prompt donated
          to.  O(delivered) re-prefill, no wasted decode.
        - **Replay** (everything else): re-run the whole deterministic
          generation and suppress the first ``skip`` tokens.  Costs
          recompute, works for any family (encoder-decoders cannot
          re-enter decoder history through admission).

        The checkpoint is engine-agnostic — the feats dict plus a
        cursor — which is exactly why a FLEET failover can hand it to
        a DIFFERENT replica's queue and still resume token-identically
        (engine/fleet.py)."""
        remaining = st.budget - st.produced
        if remaining <= 0 or st.cancelled.is_set():
            return False
        st.started = True
        st.preempted += 1
        # Journal the checkpoint-site cursor (runtime/durability.py):
        # every resume — preemption, dry pool, supervised recovery,
        # fleet evacuation — leaves its delivered-token cursor in the
        # write-ahead log, so a crash between checkpoint and resume
        # still replays to the exact same continuation point.
        j = self._journal()
        if j is not None and st.rid:
            j.checkpoint(st.rid)
        greedy = float(st.feats.get("temperature", 0.0)) == 0.0
        ids = np.asarray(st.feats["input_ids"], np.int32)[
            : int(st.feats["length"])
        ]
        new_len = int(ids.size) + len(st.tokens)
        if (
            greedy
            and getattr(self.engine.bundle, "supports_prefix", False)
            and st.skip == 0
            and new_len <= self.max_prompt
        ):
            st.feats = dict(
                st.feats,
                input_ids=np.concatenate(
                    [ids, np.asarray(st.tokens, np.int32)]
                ),
                length=np.int32(new_len),
            )
            st.budget = remaining
            st.tokens = []  # folded into the prompt above
        else:
            st.skip = len(st.tokens)
        st.produced = 0
        # Host KV tier (docs/kv-tiering.md): the feats above now spell
        # the RESUME prompt — prompt+delivered for the recast fold,
        # the original prompt for replay — and its KV occupies the
        # contiguous positions [0, length) of this stream's blocks.
        # Copy those blocks device→host BEFORE they free, so the
        # resume prefetches them back instead of re-prefilling; then
        # release (idempotent with any caller-side release).
        if self.paged and st.blocks is not None:
            self._swap_out(st)
            st.blocks.release()
        # A checkpointed stream holds NO ledger commitment while it
        # waits (its reservation was released above by the caller); its
        # recurrent state row goes back too — the resume's prefill of the
        # prompt above rebuilds the state by recompute.
        self._ssm_give(st)
        st.blocks = None
        st.shared_ids = []
        st.s_lo = st.s_base = 0
        return True

    def _requeue_preempted(self, st: _Stream) -> None:
        """Checkpoint + re-queue one preempted stream on THIS loop's
        own queue (see ``_checkpoint_for_resume`` for the resume
        strategies)."""
        if not self._checkpoint_for_resume(st):
            self._finish(st)
            return
        # Refresh the footprint the stream re-reserves at dequeue —
        # the recast path just FOLDED delivered tokens into the
        # prompt, so the stale admission-time estimate can undershoot
        # the new prompt bucket.  A host-swapped checkpoint is charged
        # its TRUE resume cost: the prefetch blocks, not the
        # first-window re-prefill it will never run.
        if self.admission is not None:
            st.kv = self.admission.kv_bytes_for_resume(
                st.feats, swap_tokens=self._swap_tokens(st)
            )
        if self._flight is not None:
            self._flight.event(
                "checkpoint_requeue", rid=st.rid, klass=st.klass,
                budget=st.budget, skip=st.skip, preempted=st.preempted,
            )
        st.t_queued = time.monotonic()
        self.queue.put(st, force=True)

    def _emit_tokens(self, st: _Stream, chunk) -> None:
        """Deliver one chunk to a stream: honor the replay-resume
        suppression cursor and record delivered tokens for any later
        preemption checkpoint."""
        arr = np.asarray(chunk)
        if st.skip:
            k = min(st.skip, int(arr.size))
            st.skip -= k
            arr = arr[k:]
        # Never emit past the budget: a resumed stream whose REMAINING
        # budget is not chunk-aligned would otherwise deliver the
        # chunk's overshoot tokens — tokens the uninterrupted run never
        # produced, breaking reconnect-level token identity (the API's
        # max_tokens trim cannot catch it: the journal records raw
        # emissions).
        room = st.budget - len(st.tokens)
        if int(arr.size) > room:
            arr = arr[: max(0, room)]
        if arr.size:
            st.tokens.extend(int(t) for t in arr.tolist())
            # WRITE-AHEAD cursor: the journal learns about these tokens
            # before the consumer can — so after a kill, the journaled
            # cursor always covers everything any client received, and
            # the reconnect path can dedup with zero double emission.
            j = self._journal()
            if j is not None and st.rid:
                j.tokens(st.rid, arr)
            if not st.t_emit:
                # The first chunk leaves the loop thread here: where
                # stream_admit_seconds ends and stream_handoff_seconds
                # begins, stamped before the consumer can see the chunk.
                t_first = st.feats["t_first_emit"] = time.monotonic()
            st.emit(arr)
            self.tokens_emitted += int(arr.size)
            metrics.TOKENS.labels(self.engine.bundle.name).inc(int(arr.size))
            # Inter-chunk delivery cadence (stream_tbt_seconds): the
            # gap since this stream's PREVIOUS chunk — the first chunk
            # is TTFT's business, not TBT's.
            now = time.monotonic()
            if st.t_emit:
                gap = now - st.t_emit
                metrics.TBT.labels(self.engine.bundle.name).observe(gap)
                self.tbt_ewma_s = (
                    gap if not self.tbt_ewma_s
                    else 0.8 * self.tbt_ewma_s + 0.2 * gap
                )
                if self.slo is not None:
                    self.slo.note("tbt", st.klass, gap)
                if self.tenants is not None:
                    self.tenants.note_latency(st.tenant, "tbt", st.klass, gap)
            else:
                # Reservation to first emit: the wave, its fetch, its
                # insert dispatch and this stream's place in the emit
                # order.
                metrics.STREAM_ADMIT.labels(self.engine.bundle.name).observe(
                    max(0.0, t_first - st.t_reserved)
                )
                tr = tracing.tracer()
                if tr is not None:
                    tr.add(
                        "admit", cat="sched", rid=st.rid, t0=st.t_reserved,
                        dur=t_first - st.t_reserved,
                    )
                ttft = now - st.t_in
                self.ttft_ewma_s = (
                    ttft if not self.ttft_ewma_s
                    else 0.8 * self.ttft_ewma_s + 0.2 * ttft
                )
                if self.slo is not None:
                    self.slo.note("ttft", st.klass, ttft)
                if self.tenants is not None:
                    self.tenants.note_latency(st.tenant, "ttft", st.klass, ttft)
            st.t_emit = now

    # -- adapter dispatch params ---------------------------------------

    def _slot_rows(self) -> list[int]:
        """Per-slot adapter index vector for a full-width decode
        dispatch: row ``i`` decodes through the adapter pinned by the
        stream active in slot ``i`` (0 = base / free slot)."""
        rows = [0] * self.n_slots
        for slot, st in self.active.items():
            if 0 <= slot < self.n_slots:
                rows[slot] = st.adapter_slot
        return rows

    def _mp(self, n: int | None = None, rows: list[int] | None = None):
        """The params tree for one dispatch.

        No adapter pool → the engine's base tree, the SAME object every
        call, so traced graphs and executable-cache keys are bit-
        identical to the pre-adapter build (the TENANTS-unset pin).
        With a pool: overlay the slot stacks with an explicit per-row
        adapter index vector (``rows``), an all-base vector of width
        ``n`` (warm paths, empty-state builds), or — neither given —
        the live per-slot vector (full-width decode dispatches).
        Slot contents change under install/evict; shapes never do, so
        serving never recompiles (CompileWindow-pinned)."""
        if self.adapters is None:
            return self.engine.params
        if rows is None:
            rows = [0] * int(n) if n is not None else self._slot_rows()
        return self.adapters.overlay(self.engine.params, rows)

    # -- admission -----------------------------------------------------

    def _wave_rows(self, k: int) -> int:
        """Rows a prefill wave of ``k`` streams runs: the smallest rung
        that holds it, so the start and insert executables it meets are
        the ones the warm grids compiled."""
        return next(r for r in self._wave_rungs if r >= k)

    def _pad_wave(self, feats_list: list[dict]) -> list[dict]:
        """The wave's rows plus zero-length pad rows up to its rung
        (they collate to all-zero masks: born-done rows that never
        insert)."""
        pad = {"input_ids": np.zeros(0, np.int32), "length": np.int32(0)}
        return feats_list + [pad] * (
            self._wave_rows(len(feats_list)) - len(feats_list)
        )

    def _note_wave_fill(self, real_tokens: int, rows: int, width: int) -> None:
        """One prefill executable just ran ``rows x width`` token
        positions for ``real_tokens`` prompt tokens (prefill_wave_fill:
        useful over attempted work; prefill_wave_rows: the rows)."""
        name = self.engine.bundle.name
        metrics.PREFILL_WAVE_FILL.labels(name).observe(
            real_tokens / max(1, rows * width)
        )
        metrics.PREFILL_WAVE_ROWS.labels(name).observe(rows)
        if self._ssm_free is not None:
            self._note_ssm_scan(rows * width, real_tokens)

    def _note_wave_stall(self, t_wave: float | None) -> None:
        """A monolithic wave (dispatch, fetch, emit + inserts) just
        held the loop thread since ``t_wave`` while streams were live:
        no decode chunk could be dispatched for that long (None = no
        wave, or nobody was live to stall)."""
        if t_wave is None:
            return
        dt = time.monotonic() - t_wave
        self.prefill_stall_s += dt
        metrics.PREFILL_STALL.labels(self.engine.bundle.name).inc(dt)

    def _admit_dispatch(self, wave: list[_Stream]) -> list:
        """Phase 1 of admission: queue the wave's prefill work on the
        device and start async host copies of the first chunks — NO
        blocking fetch here, so the caller can dispatch the live
        streams' next chunk BEHIND it before it waits on anything: the
        device goes from the start straight into that chunk.

        A multi-stream wave prefills as ONE batched ``_start`` dispatch
        (``_wave_rows`` rows, at the widest prompt bucket in the wave):
        each dispatch costs a host<->device round-trip, and a wave pays
        one dispatch + one fetch TOTAL, not per stream.  Under the
        per-request prefix cache, waves group by (prefix, suffix)
        bucket instead — one batched prefixed start per hit group, one
        shared full-prefill wave for the misses
        (``_admit_prefixed_locked``)."""
        eng = self.engine
        started: list[tuple] = []  # (st, state1, toks, sampled, row, ids, mask)
        ok: list[_Stream] = []
        for st in wave:
            if st.cancelled.is_set():
                self._release(st)
                continue
            if int(st.feats.get("length", 0)) > self.max_prompt:
                # Callers normally route oversized prompts to the
                # per-stream path; direct misuse gets a clean error.
                self._finish(st, ValueError(
                    f"prompt longer than the largest seq bucket "
                    f"({self.max_prompt}) cannot join the shared batch"
                ))
                continue
            if self.paged and getattr(st, "swap", None) is not None:
                # Host-swapped checkpoint: resume by prefetching the
                # host copy back block-by-block (zero re-prefill).
                # False = the copy died — fall through to the normal
                # recast/replay admission below.
                if self._start_swapin(st):
                    continue
            ok.append(st)
        if self.prefill_chunk:
            # Chunked routing: prompts longer than one window (or past
            # the largest bucket) become backlog jobs driven by
            # _advance_prefill; short prompts keep the monolithic wave
            # path (one fused dispatch per wave stays the cheaper shape
            # for them).
            chunked = [
                st for st in ok
                if eng.chunked_prefill_applies(int(st.feats["length"]))
            ]
            if chunked:
                ok = [st for st in ok if st not in chunked]
                for st in chunked:
                    self._start_prefill_job(st)
        if not ok:
            return started
        with eng._lock:
            if eng.prefix_cache is not None and (
                len(ok) > 1 or self.spec or self.paged
            ):
                # Grouped wave admission under the per-request prefix
                # cache: same-(prefix, suffix)-bucket hits batch into
                # one prefixed start each, misses share one full
                # prefill wave — a burst of N same-prefix chat
                # requests pays ~1 prefill dispatch, not N.  Spec mode
                # routes SOLO admissions here too (its insert needs the
                # collated ids/mask this path threads through, and the
                # hit/donate bookkeeping is identical either way).
                return self._admit_prefixed_locked(ok)
            if len(ok) == 1 and not self.spec:
                for st in ok:
                    try:
                        # Fused prefill+first-chunk at the request's
                        # own bucket (through the prefix cache when
                        # on) — TTFT = solo serving; the slot insert
                        # pads narrower states up to the slot shapes.
                        state1, toks, sampled = eng.dispatch_guard(
                            "prefill", lambda: eng.start_fused(
                                st.feats,
                                params=self._mp(rows=[st.adapter_slot]),
                            )
                        )
                    except Exception as e:
                        self._fail_streams([st], e)
                        continue
                    from .engine import bucket_for

                    # The request's own bucket (a contiguous prefix-
                    # cache hit ran only its suffix: counted as a miss).
                    L = max(int(st.feats["length"]), 1)
                    s_own = bucket_for(
                        L, eng.seq_buckets, eng.replicas.seq_multiple()
                    )
                    if self.paged:
                        st.s_lo = 0
                        st.s_base = s_own
                    self.prefill_dispatches += 1
                    self._note_wave_fill(L, 1, s_own)
                    self._note_prompt_positions(L, went_live=1)
                    prefetch_to_host(toks, state1.done)
                    started.append((st, state1, toks, sampled, 0, None, None))
                return started
            try:
                # Pad the wave to its rung: a small wave no longer
                # computes ``n_slots`` rows, and every wave size meets a
                # warmed (B, S) executable.  Spec mode admits solo
                # streams here too (the lowest rung), through the same
                # collated path: the insert needs the ids/mask to build
                # the row's spec base.
                feats_list = self._pad_wave([st.feats for st in ok])
                ids, mask, _ = eng._collate_text(feats_list)
                sp, sampled = eng._collate_sample(feats_list, ids.shape[0])
                ids, mask = eng.replicas.place_batch(ids, mask)
                wrows = [st.adapter_slot for st in ok]
                wrows += [0] * (int(ids.shape[0]) - len(wrows))
                wparams = self._mp(rows=wrows)
                state1, toks = eng.dispatch_guard(
                    "prefill",
                    lambda: eng._start(
                        wparams, ids, mask, sp,
                        eng.max_decode_len, eng.chunk_tokens, sampled,
                    ),
                )
            except Exception as e:
                self._fail_streams(ok, e)
                return started
            self.prefill_dispatches += 1
            self._note_wave_fill(
                sum(int(st.feats["length"]) for st in ok),
                int(ids.shape[0]), int(ids.shape[1]),
            )
            self._note_prompt_positions(
                sum(int(st.feats["length"]) for st in ok), went_live=len(ok))
            prefetch_to_host(toks, state1.done)
            for row, st in enumerate(ok):
                # Slot sampling is PER ROW, not the wave-level flag the
                # batched executable ran with: one sampled request in a
                # wave must not pin 7 greedy streams' future chunks to
                # the per-step [B, V] sort.
                row_sampled = float(st.feats.get("temperature", 0.0)) > 0.0
                if self.paged:
                    st.s_lo = 0
                    st.s_base = int(ids.shape[1])
                started.append((st, state1, toks, row_sampled, row, ids, mask))
        return started

    def _admit_prefixed_locked(self, ok: list[_Stream]) -> list:
        """Wave admission with the per-request prefix cache on (caller
        holds ``eng._lock``).  Each stream is matched ONCE (here —
        never re-matched downstream, keeping hit/miss stats and LRU
        recency exact): hits group by (prefix-bucket, suffix-bucket)
        and prefill as ONE ``_start_prefixed_wave`` dispatch per group
        (each row's cached KV stacks inside the trace; solo hits use
        the B=1 ``_start_prefixed``); misses share ONE full-prefill
        wave (solo misses at B=1) and donate their prefixes per row."""
        from .engine import bucket_for

        eng = self.engine
        started: list[tuple] = []
        groups: dict[tuple[int, int], list] = {}
        misses: list[tuple[_Stream, np.ndarray, int]] = []
        for st in ok:
            L = int(st.feats["length"])
            row_ids = np.asarray(st.feats["input_ids"], np.int32)[:L]
            m = eng.prefix_cache.match(
                row_ids, L, usable=eng._prefix_guard(L)
            )
            if m is None and self.paged:
                # Host tier: a prefix demoted under device-budget
                # pressure promotes back on match (lock already held).
                m = self._promote_host_prefix(
                    row_ids, L, eng._prefix_guard(L)
                )
            if m is None:
                misses.append((st, row_ids, L))
                continue
            p_len, pkv = m
            if self.paged:
                # Paged hit: the entry is a block-ref pin, not KV.  The
                # stream ADOPTS the donor's blocks (refcount, no copy)
                # and the suffix prefill attends over a dense gather of
                # them from the current pools.
                from .kv_blocks import PagedPrefix

                if self._state is None or not isinstance(pkv, PagedPrefix):
                    misses.append((st, row_ids, L))
                    continue
                st.shared_ids = list(pkv.block_ids)
                pkv = self._gather_prefix(p_len, pkv.block_ids)
            s_suf = bucket_for(
                max(L - p_len, 1), eng.seq_buckets,
                eng.replicas.seq_multiple(),
            )
            groups.setdefault((p_len, s_suf), []).append(
                (st, row_ids, L, p_len, pkv)
            )

        def collate_place(feats_list):
            ids, mask, _ = eng._collate_text(feats_list)
            sp, sampled = eng._collate_sample(feats_list, ids.shape[0])
            ids, mask = eng.replicas.place_batch(ids, mask)
            return ids, mask, sp, sampled

        def record(state1, toks, streams, ids, mask, real_tokens: int):
            # ``ids``/``mask`` are the COLLATED (suffix, for hits)
            # prompt arrays the spec insert feeds to init_spec_fn; the
            # plain insert ignores them.  ``real_tokens``: the prompt
            # (suffix) tokens this executable run prefilled.
            self.prefill_dispatches += 1
            self._note_wave_fill(
                real_tokens, int(ids.shape[0]), int(ids.shape[1])
            )
            self._note_prompt_positions(real_tokens, went_live=len(streams))
            prefetch_to_host(toks, state1.done)
            for row, st in enumerate(streams):
                row_sampled = float(st.feats.get("temperature", 0.0)) > 0.0
                started.append(
                    (st, state1, toks, row_sampled, row, ids, mask)
                )

        def donate(state1, row, row_ids, L, min_over: int | None):
            """Per-row prefix donation; ``min_over`` = only donate
            buckets strictly larger (the hit path's growing-
            conversation rule), None = any (miss path).  Paged mode
            donates BLOCK REFS instead, which only exist after the
            slot insert — ``_admit_complete`` handles it there."""
            if self.paged:
                return
            p_ins = eng.prefix_cache.bucket_for_insert(L)
            if (
                p_ins is not None
                and (min_over is None or p_ins > min_over)
                and not eng.prefix_cache.contains(row_ids, p_ins)
            ):
                eng.prefix_cache.insert(
                    row_ids, p_ins, eng._capture_prefix(state1, p_ins, row)
                )

        if misses:
            try:
                ids, mask, sp, sampled = collate_place(
                    self._pad_wave([st.feats for st, _, _ in misses])
                )
                mrows = [st.adapter_slot for st, _, _ in misses]
                mrows += [0] * (int(ids.shape[0]) - len(mrows))
                mparams = self._mp(rows=mrows)
                state1, toks = eng.dispatch_guard(
                    "prefill",
                    lambda: eng._start(
                        mparams, ids, mask, sp,
                        eng.max_decode_len, eng.chunk_tokens, sampled,
                    ),
                )
            except Exception as e:
                self._fail_streams([st for st, _, _ in misses], e)
            else:
                for row, (st, row_ids, L) in enumerate(misses):
                    if self.paged:
                        st.s_lo = 0
                        st.s_base = int(ids.shape[1])
                    donate(state1, row, row_ids, L, None)
                record(state1, toks, [st for st, _, _ in misses], ids, mask,
                       sum(L for _, _, L in misses))

        # Hit groups: one batched prefixed start per (prefix, suffix)
        # bucket pair; multi-member groups pad to their rung, like the
        # miss wave.
        for (p_len, s_suf), members in groups.items():
            suffix_feats = [
                dict(st.feats, input_ids=row_ids[p_len:],
                     length=np.int32(L - p_len))
                for st, row_ids, L, _, _ in members
            ]
            try:
                ids, mask, sp, sampled = collate_place(
                    self._pad_wave(suffix_feats)
                )
                hrows = [st.adapter_slot for st, *_ in members]
                hrows += [0] * (int(ids.shape[0]) - len(hrows))
                hparams = self._mp(rows=hrows)

                def start_hits():
                    if len(members) == 1:
                        return eng._start_prefixed(
                            hparams, members[0][4], ids, mask, sp,
                            eng.max_decode_len, eng.chunk_tokens, sampled,
                        )
                    pkvs = tuple(pkv for _, _, _, _, pkv in members)
                    pkvs = pkvs + (pkvs[0],) * (ids.shape[0] - len(pkvs))
                    return eng._start_prefixed_wave(
                        hparams, pkvs, ids, mask, sp,
                        eng.max_decode_len, eng.chunk_tokens, sampled,
                    )

                state1, toks = eng.dispatch_guard("prefill", start_hits)
            except Exception as e:
                self._fail_streams([st for st, *_ in members], e)
                continue
            for row, (st, row_ids, L, pl, _) in enumerate(members):
                if self.paged:
                    st.s_lo = pl
                    st.s_base = pl + int(ids.shape[1])
                # Growing conversations keep donating from the hit path
                # (start_fused's rule, applied per row).
                donate(state1, row, row_ids, L, pl)
            record(state1, toks, [st for st, *_ in members], ids, mask,
                   sum(L - pl for _, _, L, pl, _ in members))
        return started

    def _admit_complete(self, started: list) -> None:
        """Phase 2: one combined ``device_get`` fetches every admitted
        stream's first chunk + done flag (a wave costs ~one RTT, not
        N — batched waves share one (toks, done) pair, fetched once),
        then emit + insert into free slots.  The caller has delivered
        the chunks dispatched ahead of the wave first
        (``_deliver_ahead_of_wave``), so nothing that has landed waits
        behind this fetch; where streams are live, their next chunk is
        already queued BEHIND the start and stays in flight across the
        insert (its ``(toks, done)`` are its own outputs, copied to the
        host from dispatch, and it is routed by its own snapshot of the
        slots), so a live stream's gap across the admission is start +
        that chunk."""
        import jax

        if not started:
            return
        eng = self.engine
        with tracing.phase("loop/wave_complete"):
            uniq: dict[int, Any] = {}
            for _, state1, toks, _, _, _, _ in started:
                uniq.setdefault(id(toks), (toks, state1.done))
        with tracing.phase("loop/wave_fetch"), eng._lock:
            try:
                fetched = dict(zip(
                    uniq.keys(),
                    eng.dispatch_guard(
                        "fetch",
                        lambda: jax.device_get(list(uniq.values())),
                    ),
                ))
            except Exception as e:
                self._fail_streams([st for st, *_ in started], e)
                return
        with tracing.phase("loop/insert"):
            self._emit_and_insert(started, fetched)

    def _emit_and_insert(self, started: list, fetched: dict) -> None:
        """The wave's tail: each admitted stream's first chunk goes out
        and its prefill row lands in a free slot — paged, a wave's rows in
        ONE insert dispatch (``_insert_wave``; a boundary's lone start
        and each of its waves is a group of its own: another prefill
        state, another (s_lo, s_cut)); on the contiguous slab, a row at
        a time."""
        if not self.paged:
            self._emit_and_insert_slab(started, fetched)
            return
        waves: dict[int, list] = {}
        for entry in started:
            waves.setdefault(id(entry[1]), []).append(entry)
        for wave in waves.values():
            self._insert_wave(wave, fetched)

    def _insert_wave(self, wave: list, fetched: dict) -> None:
        """One prefill state's rows into their slots, in three passes:
        the host's part a row (done?, a slot, its blocks, its state
        row); ONE guarded ``paged_insert`` for every row that lands;
        then every row's first chunk goes out — the device scatters
        while the host emits — and each row is settled: live in its
        slot, finished, re-queued, or failed."""
        from .kv_blocks import OutOfBlocks

        eng = self.engine
        name = eng.bundle.name
        st0, state1, toks = wave[0][:3]
        toks_np, done_np = fetched[id(toks)]
        s_lo, s_cut = st0.s_lo, st0.s_base + eng.chunk_tokens
        bw = int(state1.done.shape[0])
        # A row that does not land (a pad row of the rung, a row done or
        # re-queued below) keeps the sentinel and the slot past the last:
        # every one of its writes drops.
        table_rows = np.full((bw, self.nb_max), self.pool.num_blocks, np.int32)
        slots = np.full(bw, self.n_slots, np.int32)
        ssm_rows = (
            None if self._ssm_free is None
            else np.full(bw, self.n_slots, np.int32)
        )
        landing: list[tuple] = []  # (st, slot, sb, sampled)
        settle: list[tuple] = []  # (st, row, what, arg), in the wave's order
        for st, _, _, sampled, row, _, _ in wave:
            st.produced = eng.chunk_tokens
            if bool(done_np[row]) or st.produced >= st.budget:
                settle.append((st, row, "done", None))
                continue
            if self._fault_pending is not None:
                # An earlier wave's insert took the batched state with
                # it: the rows behind it go the same way, uninserted.
                settle.append((st, row, "fault", self._fault_pending))
                continue
            try:
                slot, sb = self._reserve_slot(st, s_cut)
            except OutOfBlocks:
                settle.append((st, row, "dry", None))
                continue
            # Any failure here (empty-state build OOM, an injected
            # fault) must terminate THIS consumer and return the slot —
            # the _run handler only reaches streams in self.active.
            # graftlint: except(pre-active insert failure errors only this stream; the supervisor owns the next chunk dispatch)
            except Exception as e:
                settle.append((st, row, "error", e))
                continue
            table_rows[row, : len(sb.ids)] = sb.ids
            slots[row] = slot
            if ssm_rows is not None:
                ssm_rows[row] = st.ssm_row
            landing.append((st, slot, sb, sampled))
            settle.append((st, row, "live", landing[-1]))
        if landing:
            ssm_arg = () if ssm_rows is None else (ssm_rows,)
            try:
                with eng._lock:
                    self._state = eng.dispatch_guard(
                        "insert", lambda: self.programs.paged_insert_fn()(
                            self._state, state1, table_rows, slots,
                            s_lo, s_cut, *ssm_arg,
                        ),
                        donates=self._state,
                    )
                metrics.STREAM_INSERT_ROWS.labels(name).observe(len(landing))
            except BaseException as e:
                # The dispatch was every landing row's: each gives back
                # what it took.  The streams are not active yet, so the
                # failure ends these consumers only (a dead device
                # resurfaces at the next guarded chunk dispatch, which
                # the supervisor owns) — unless it consumed the batched
                # state: that takes the shared fatal route (requeue +
                # rebuild at the next iteration top).
                for st, slot, sb, _ in reversed(landing):
                    sb.release()
                    self._ssm_give(st)
                    self.free.append(slot)
                landing = []
                if not isinstance(e, Exception):
                    raise
                settle = [
                    (st, row, "error", e) if what == "live"
                    else (st, row, what, arg)
                    for st, row, what, arg in settle
                ]
        for st, row, _, _ in settle:
            self._emit_tokens(st, toks_np[row])
        for st, row, what, arg in settle:
            if what == "done":
                self._finish(st)
            elif what == "fault":
                self._fail_streams([st], arg)
            elif what == "error":
                self._fail_preactive(st, arg)
            elif what == "dry":
                # The fits() gate raced another reservation and the
                # pool is momentarily dry: checkpoint the first chunk
                # (already delivered) and re-queue — token-identical
                # resume when blocks free up, never a dropped stream.
                metrics.KV_GROWTH_STALLS.labels(name).inc()
                if self._flight is not None:
                    self._flight.event(
                        "kv_growth_stall", rid=st.rid, site="insert"
                    )
                if self.admission is not None:
                    self.admission.release(st)
                self._requeue_preempted(st)
            else:
                _, slot, sb, sampled = arg
                st.blocks = sb
                self._table[slot] = table_rows[row]
                self._dispatched_steps[slot] = eng.chunk_tokens
                self.active[slot] = st
                if sampled:
                    self.sampled_slots.add(slot)
                if eng.prefix_cache is not None:
                    self._donate_paged(st, slot)
        if landing and self.admission is not None:
            self.admission.note_pool()

    def _reserve_slot(self, st: _Stream, s_cut: int) -> tuple:
        """The host's part of a row's paged insert: a free slot, the
        stream's initial blocks (adopting CoW prefix blocks first) and
        its state row.  Raises ``OutOfBlocks`` (after trying to reclaim
        prefix pins), or whatever else failed, with nothing leaked."""
        from .kv_blocks import StreamBlocks

        if self._state is None:
            self._build_empty_state()
        slot = self.free.pop()
        sb = StreamBlocks(self.pool, self.block_size)
        try:
            self.engine.fault_point("grow")
            if st.shared_ids:
                sb.adopt(st.shared_ids)
            self._reclaim_then_ensure(sb, s_cut)
            self._ssm_take(st)
        except BaseException:
            sb.release()
            self._ssm_give(st)
            self.free.append(slot)
            raise
        return slot, sb

    def _emit_and_insert_slab(self, started: list, fetched: dict) -> None:
        """The contiguous slab's tail, a row at a time: the stream's
        first chunk goes out, then its prefill row is written into a
        free slot."""
        eng = self.engine
        for st, state1, toks, sampled, row, ids, mask in started:
            toks_np, done_np = fetched[id(toks)]
            st.produced = eng.chunk_tokens
            self._emit_tokens(st, toks_np[row])
            if bool(done_np[row]) or st.produced >= st.budget:
                self._finish(st)
                continue
            if self._fault_pending is not None:
                # An earlier row's insert took the batched state with
                # it: the rows behind it go the same way, uninserted.
                self._fail_streams([st], self._fault_pending)
                continue
            # Any failure from here (empty-state build OOM, insert
            # compile) must terminate THIS consumer and return the slot
            # — the _run handler only reaches streams in self.active.
            slot = None
            try:
                if self._state is None:
                    self._build_empty_state()
                slot = self.free.pop()
                with eng._lock:
                    if self.spec:
                        hist_row = self._hist_row(st.feats, toks_np[row])
                        self._state = eng.dispatch_guard(
                            "insert", lambda: self.programs.insert_fn()(
                                self._state, state1, ids, mask, hist_row,
                                np.int32(slot), np.int32(row),
                            ),
                            donates=self._state,
                        )
                    else:
                        self._state = eng.dispatch_guard(
                            "insert", lambda: self.programs.insert_fn()(
                                self._state, state1, np.int32(slot),
                                np.int32(row),
                            ),
                            donates=self._state,
                        )
            # The stream is not active yet: an insert failure ends this
            # consumer only; a dead device resurfaces at the next
            # guarded chunk dispatch, which the supervisor owns.  An
            # insert that consumed the batched state takes the shared
            # fatal route (requeue + rebuild at the next iteration top).
            # graftlint: except(pre-active insert failure errors only this stream; the supervisor owns the next chunk dispatch)
            except Exception as e:
                if slot is not None:
                    self.free.append(slot)
                self._fail_preactive(st, e)
                continue
            self.active[slot] = st
            if sampled:
                self.sampled_slots.add(slot)

    # -- chunked prefill (PREFILL_CHUNK) -------------------------------

    def prefill_backlog_tokens(self) -> int:
        """Prompt tokens admitted but not yet prefilled (observability;
        read from other threads as a snapshot)."""
        return sum(max(0, j.L - j.consumed) for j in list(self._prefilling))

    def _chunked_prefix_usable(self, L: int):
        """Static-shape guard for prefix-cache hits on the CHUNKED
        path: the seeded prefix + suffix windows must fit the slot
        width and the model's position table."""
        eng = self.engine
        max_pos = int(getattr(eng.bundle.cfg, "max_position", 1 << 30))

        def usable(p_len: int) -> bool:
            if L + eng.max_decode_len > max_pos:
                return False
            if self.paged:
                # Pins are block-aligned by the build gate; defensive.
                return p_len % self.block_size == 0 and p_len < L
            from .engine import bucket_for

            s_suf = bucket_for(
                max(L - p_len, 1), eng.seq_buckets,
                eng.replicas.seq_multiple(),
            )
            return p_len + s_suf <= self.max_prompt

        return usable

    # -- recurrent state rows (a model with Mamba layers) ---------------
    # The decode state holds ``n_slots`` rows of recurrent state a Mamba
    # layer (models/llama.SsmState).  Rows are streams', not slots': a
    # stream takes one beside its blocks — at its prompt's first window,
    # or at its wave's insert, before it has a slot — and gives it back
    # with them.  There are as many rows as slots and no more, because a
    # row's holder is live or in ``_prefilling`` and the admission loop
    # keeps wave + active + prefilling + swapping <= n_slots; every decode
    # step reads and writes every row, so a spare one would cost.  The row
    # index rides into each window dispatch beside the block tables, and
    # into the insert / handoff that points a slot at it; the decode step
    # needs no host word (it reads liveness off the table it already
    # gets).  ``_ssm_free`` is None for every other model: no row, no
    # argument, no counter.

    def _ssm_take(self, st: _Stream) -> None:
        if self._ssm_free is None or st.ssm_row is not None:
            return
        st.ssm_row = self._ssm_free.pop()
        if st.started:  # checkpointed earlier: this prefill rebuilds its state
            metrics.SSM_STATE_RECOMPUTES.labels(self.engine.bundle.name).inc()
        self._note_ssm_rows()

    def _ssm_give(self, st: _Stream) -> None:
        if self._ssm_free is None or st.ssm_row is None:
            return
        self._ssm_free.append(st.ssm_row)
        st.ssm_row = None
        self._note_ssm_rows()

    def _note_ssm_rows(self) -> None:
        if self._ssm_free is None:
            return
        name = self.engine.bundle.name
        held = self.n_slots - len(self._ssm_free)
        live = sum(1 for s in self.active.values() if s.ssm_row is not None)
        for state, n in (("live", live), ("prefill", held - live),
                         ("free", len(self._ssm_free))):
            metrics.SSM_STATE_ROWS.labels(name, state).set(n)
        bcfg = self.engine.bundle.cfg
        metrics.SSM_STATE_BYTES.labels(name).set(held * bcfg.ssm_row_bytes)
        if self._ring is not None:
            metrics.KV_WINDOW_STORE_BYTES.labels(name).set(
                held * bcfg.window_row_bytes)

    def _note_prompt_positions(self, real: int, went_live: int = 0) -> None:
        """Prompt positions a dispatch ran: ``real`` through the
        self-decoder (a window's or a wave's real tokens), ``went_live``
        through the cross-decoder — a prompt's LAST position, which the
        first decode step of a stream that just went live (a handoff, or a
        wave's own first chunk) runs through every layer; no window and no
        wave's prefill runs a cross-decoder layer (models/llama._layers'
        ``self_only``).  Counted for a model with a
        cross-decoder alone."""
        if not self._cross_decoder:
            return
        name = self.engine.bundle.name
        if real:
            metrics.PREFILL_SELF_POSITIONS.labels(name).inc(real)
        if went_live:
            metrics.PREFILL_CROSS_POSITIONS.labels(name).inc(went_live)

    def _note_ring_keys(self, overwritten: int) -> None:
        """Window keys the rings overwrote (a ring layer each): positions
        written at or past ``window_ring``, each landing on the key
        ``window_ring`` before it — what a table would have kept behind the
        window."""
        if overwritten:
            metrics.KV_WINDOW_KEYS_OVERWRITTEN.labels(
                self.engine.bundle.name).inc(overwritten * self._ring[0])

    def _note_ssm_scan(self, scanned: int, real: int) -> None:
        name = self.engine.bundle.name
        metrics.SSM_SCAN_TOKENS.labels(name).inc(scanned)
        metrics.SSM_SCAN_MASKED.labels(name).inc(scanned - real)
        if self._ssm_fused:
            metrics.SSM_SCAN_FUSED.labels(name).inc(scanned)

    def _ssm_window_args(self, rows: int, jobs=(), ends=()) -> tuple:
        """The trailing argument of a window dispatch: ``[rows, 2]`` —
        each prompt's state row and how many of the window's tokens fold
        into it (all, but for a prompt's last window, which leaves the
        prompt's last token to the first decode step); a filled-up row,
        and warm-up, name the row past the last and fold nothing."""
        if self._ssm_free is None:
            return ()
        arg = np.zeros((rows, 2), np.int32)
        arg[:, 0] = self.n_slots
        for r, (job, end) in enumerate(zip(jobs, ends)):
            arg[r] = (job.st.ssm_row, end - job.consumed - (end == job.L))
        return (arg,)

    def _ssm_row_arg(self, st: _Stream | None = None) -> tuple:
        """The trailing argument of an insert or a handoff: the stream's
        state row (warm-up: the row past the last)."""
        if self._ssm_free is None:
            return ()
        return (np.int32(self.n_slots if st is None else st.ssm_row),)

    def _start_prefill_job(self, st: _Stream) -> None:
        """Create one chunked-prefill backlog job: match the prefix
        cache ONCE (hits adopt donor blocks / seed cached KV and
        suffix-prefill in windows), allocate the paged table or the
        detached contiguous state, and queue it for
        ``_advance_prefill``."""
        from .kv_blocks import PagedPrefix, StreamBlocks

        eng = self.engine
        L = int(st.feats["length"])
        ids = np.asarray(st.feats["input_ids"], np.int32)[:L]
        # TTFT admission-mode label: the API layer reads this off the
        # SAME feats dict it submitted (set before any recast copies).
        st.feats["prefill_mode"] = "chunked"
        job = _PrefillJob(st, ids, L)
        try:
            p_len, pkv = 0, None
            if eng.prefix_cache is not None:
                m = eng.prefix_cache.match(
                    ids, L, usable=self._chunked_prefix_usable(L)
                )
                if m is None and self.paged:
                    # Host tier: promote a demoted prefix back before
                    # settling for a cold chunked prefill.
                    with eng._lock:
                        m = self._promote_host_prefix(
                            ids, L, self._chunked_prefix_usable(L)
                        )
                if m is not None:
                    p_len, pkv = m
                    if self.paged:
                        if isinstance(pkv, PagedPrefix):
                            st.shared_ids = list(pkv.block_ids)
                            pkv = None
                        else:
                            p_len, pkv = 0, None
            job.p_len = p_len
            job.consumed = p_len
            if self.paged:
                if self._state is None:
                    self._build_empty_state()
                st.s_lo = p_len
                # Exact-growth base: the windows write REAL positions
                # only, so decode growth runs off L, not the padded
                # bucket — the ledger stays within one block of the
                # live token count.
                st.s_base = L
                job.sb = StreamBlocks(self.pool, self.block_size)
                if st.shared_ids:
                    job.sb.adopt(st.shared_ids)
                job.table_row = np.full(
                    self.nb_max, self.pool.num_blocks, np.int32
                )
                job.table_row[: len(job.sb.ids)] = job.sb.ids
                self._ssm_take(st)
            else:
                from .engine import bucket_for

                s_suf = bucket_for(
                    max(L - p_len, 1), eng.seq_buckets,
                    eng.replicas.seq_multiple(),
                )
                job.s_total = p_len + s_suf
                with eng._lock:
                    # graftlint: unguarded(detached empty-state template build — no stream tokens flow; failures classify via the caller's _fail_streams, and guarding would renumber the pinned prefill_chunk schedules)
                    job.state = self.programs.empty_prefill_fn()(
                        self._mp(rows=[st.adapter_slot]), 1, job.s_total,
                        eng.max_decode_len,
                    )
                    if p_len:
                        job.state = self.programs.seed_prefix_fn(p_len)(
                            job.state, pkv
                        )
        except Exception as e:
            self._drop_job_resources(job)
            self._fail_streams([st], e)
            return
        self._prefilling.append(job)

    def _drop_job_resources(self, job: _PrefillJob) -> None:
        """Return a job's KV (paged blocks / the detached state)."""
        if job.sb is not None:
            job.sb.release()
            job.sb = None
            if self.admission is not None:
                self.admission.note_pool()
        self._ssm_give(job.st)
        job.state = None

    def _checkpoint_job(self, job: _PrefillJob) -> bool:
        """Mid-prefill checkpoint: nothing was delivered yet, so resume
        is a clean token-identical restart through admission.  With a
        host tier, the partial-prompt KV swaps out FIRST — the resume
        prefetches it back and re-prefills only the remaining windows
        (``_swap_out_job``).  Blocks release NOW — a waiting checkpoint
        holds ZERO ledger commitment and re-reserves only its prefetch
        (or first-window) footprint at dequeue
        (``kv_bytes_for_resume``), never the whole-prompt estimate."""
        self._swap_out_job(job)
        self._drop_job_resources(job)
        return self._checkpoint_requeue(job.st)

    def _fail_prefill_job(self, job: _PrefillJob, exc: Exception) -> None:
        """Window-dispatch failure: release the job's KV, then the
        shared prefill failure policy (fatal device fault under a
        supervisor → checkpoint-requeue + engine rebuild at the next
        iteration top; anything else errors only this consumer)."""
        self._drop_job_resources(job)
        self._fail_streams([job.st], exc)

    def _stall_prefill_job(self, job: _PrefillJob) -> None:
        """Pool dry mid-prefill: checkpoint the job and re-queue it for a
        token-identical restart when blocks free up — the prefill mirror
        of _grow_for_dispatch's preemption."""
        metrics.KV_GROWTH_STALLS.labels(self.engine.bundle.name).inc()
        if self._flight is not None:
            self._flight.event(
                "kv_growth_stall", rid=job.st.rid, site="prefill"
            )
        self._prefilling.remove(job)
        self._checkpoint_job(job)

    def _dispatch_prefill_window(
        self, jobs: list[_PrefillJob]
    ) -> list[_PrefillJob]:
        """ONE dispatch for the next PREFILL_CHUNK window of each of
        ``jobs`` — windows of different prompts, ``[B, C]`` tokens: pad
        each prompt slice into its row, grow each job's block table to
        cover it (paged — the chunk-by-chunk allocation that replaces
        whole-prompt reservation; a job the pool has no blocks for is
        checkpointed and leaves the batch, the others go on), dispatch
        under the ``prefill_chunk`` fault site — ``B`` is 1 or
        ``_prefill_width``, fewer jobs filled up with masked rows.  The
        contiguous slab takes one job (each owns its detached state).
        Returns the jobs whose window ran; raises the dispatch's own
        failure, which is every job's of the batch."""
        import jax.numpy as jnp

        from .kv_blocks import OutOfBlocks

        eng = self.engine
        c = self.prefill_chunk

        def window_end(job):
            return min(job.consumed + c, job.L)

        if self.paged:
            grown = []
            with tracing.phase("loop/prefill_advance"):
                for job in jobs:
                    try:
                        # Fault-injection point, like decode growth: an
                        # injected OutOfBlocks exercises the mid-prefill
                        # checkpoint path.
                        eng.fault_point("grow")
                        self._reclaim_then_ensure(job.sb, window_end(job))
                    except OutOfBlocks:
                        self._stall_prefill_job(job)
                        continue
                    job.table_row[: len(job.sb.ids)] = job.sb.ids
                    grown.append(job)
            jobs = grown
            if not jobs:
                return jobs
        n = len(jobs)
        # Two executables whatever the budget: a window alone, or the
        # full width — a batch in between rides the full width with
        # masked rows (no token, no table entry: nothing written, no
        # expert row, no key tile), so set-up loads two, not one a width.
        rows = n if n == 1 else self._prefill_width
        ends = [window_end(job) for job in jobs]
        starts = np.zeros(rows, np.int32)
        starts[:n] = [job.consumed for job in jobs]
        with tracing.phase(
            "prefill_window", cat="engine",
            rid=jobs[0].st.rid if n == 1 else "",
            windows=n, streams=[job.st.rid for job in jobs],
            starts=starts[:n].tolist(), ends=ends, paged=self.paged,
        ):
            ids_w = np.zeros((rows, c), np.int32)
            mask_w = np.zeros((rows, c), np.int32)
            for r, (job, end) in enumerate(zip(jobs, ends)):
                ids_w[r, : end - job.consumed] = job.ids[job.consumed:end]
                mask_w[r, : end - job.consumed] = 1
            jparams = self._mp(
                rows=[job.st.adapter_slot for job in jobs] + [0] * (rows - n))
            if self.paged:
                if self._state is None:
                    self._build_empty_state()
                tables = np.full(
                    (rows, self.nb_max), self.pool.num_blocks, np.int32)
                tables[:n] = [job.table_row for job in jobs]
                with eng._lock:
                    out = eng.dispatch_guard(
                        "prefill_chunk",
                        lambda: self.programs.paged_prefill_fn()(
                            jparams, self._state, jnp.asarray(tables),
                            ids_w, mask_w, starts,
                            *self._ssm_window_args(rows, jobs, ends),
                        ),
                        donates=self._state,
                    )
                    if type(out) is tuple:
                        # A chip's share of the experts: the window's
                        # counts wait for the next chunk's fetch.
                        out, counts = out
                        prefetch_to_host(counts)
                        self._moe_windows.append(counts)
                    self._state = out
                if self.admission is not None:
                    self.admission.note_pool()
                if self._ssm_free is not None:
                    self._note_ssm_scan(rows * c, int(mask_w.sum()))
                self._note_prompt_positions(int(mask_w.sum()))
                if self._ring is not None:
                    self._note_ring_keys(
                        sum(max(end - max(job.consumed, self._ring[1]), 0)
                            for job, end in zip(jobs, ends)))
            else:
                (job,) = jobs
                with eng._lock:
                    job.state = eng.dispatch_guard(
                        "prefill_chunk",
                        lambda: self.programs.prefill_fn()(
                            jparams, job.state, ids_w, mask_w, starts[0]
                        ),
                        donates=job.state,
                    )
            name = eng.bundle.name
            for job, end in zip(jobs, ends):
                if self.paged:
                    self._note_prefill_tiles(job.consumed, end)
                job.consumed = end
            self.prefill_chunk_dispatches += n
            metrics.PREFILL_CHUNKS.labels(name).inc(n)
            # Both children exist from the first dispatch on: a share
            # of 0 reads 0, not nothing.
            metrics.PREFILL_WINDOWS_BATCHED.labels(name).inc(n if n > 1 else 0)
            metrics.PREFILL_WINDOWS_ALONE.labels(name).inc(n if n == 1 else 0)
        return jobs

    def _handoff_job(self, job: _PrefillJob) -> bool:
        """Prompt exhausted: flip the stream live in a slot — the
        normal slot-insert path's chunked twin.  The row starts at
        decode step 0 with ``write_idx = L-1`` (the first shared-chunk
        step re-embeds the last prompt token exactly like
        ``init_decode_state`` arranges), so its first tokens ride the
        next batched chunk.  Returns True when the stream went live."""
        st = job.st
        eng = self.engine
        if st.cancelled.is_set():
            self._drop_job_resources(job)
            self._release(st)
            return False
        slot = None
        try:
            if self._state is None:
                self._build_empty_state()
            slot = self.free.pop()
            sp, sampled = eng._collate_sample([st.feats], 1)
            last = np.asarray([job.ids[-1]], np.int32)
            w_idx = np.asarray([job.L - 1], np.int32)
            zero = np.zeros(1, np.int32)
            not_done = np.zeros(1, bool)
            if self.paged:
                kv_row = np.zeros(
                    (1, self.nb_max * self.block_size), np.int32
                )
                kv_row[0, : job.L] = 1
                toks_row = np.full(
                    (1, eng.max_decode_len),
                    int(getattr(eng.bundle.cfg, "pad_id", 0)), np.int32,
                )
                with eng._lock:
                    # ``handoff`` dispatch site: row surgery flipping a
                    # prefilled/swapped stream live — its own site so
                    # guarding it never renumbers the chunk/prefill
                    # schedules chaos tests pin.
                    self._state = eng.dispatch_guard(
                        "handoff",
                        lambda: self.programs.paged_handoff_fn()(
                            self._state, kv_row, w_idx, zero, last,
                            not_done, toks_row, sp, np.int32(slot),
                            *self._ssm_row_arg(st),
                        ),
                        donates=self._state,
                    )
                st.blocks = job.sb
                job.sb = None
                self._table[slot] = job.table_row
                self._dispatched_steps[slot] = 0
                if self.admission is not None:
                    self.admission.note_pool()
            else:
                final = job.state._replace(
                    write_idx=w_idx, pos=zero, last_token=last,
                    done=not_done, sample=sp,
                )
                with eng._lock:
                    self._state = eng.dispatch_guard(
                        "handoff",
                        lambda: self.programs.insert_fn()(
                            self._state, final, np.int32(slot), np.int32(0)
                        ),
                        donates=self._state,
                    )
        # The stream is not active yet, so there is no checkpoint to
        # classify-route; a dead device resurfaces at the next guarded
        # chunk dispatch, which the supervisor classifies and owns.
        # graftlint: except(pre-active handoff failure errors only this stream; no checkpoint exists to route)
        except Exception as e:
            if slot is not None:
                self.free.append(slot)
            self._drop_job_resources(job)
            self._fail_preactive(st, e)
            return False
        self.active[slot] = st
        self._note_ssm_rows()
        self._note_prompt_positions(0, went_live=1)
        if sampled:
            self.sampled_slots.add(slot)
        # Chunked streams donate like monolithic admissions do at
        # insert (growing-conversation rule included); non-fatal.
        if eng.prefix_cache is not None:
            try:
                if self.paged:
                    self._donate_paged(st, slot)
                else:
                    p_ins = eng.prefix_cache.bucket_for_insert(job.L)
                    if (
                        p_ins is not None
                        and (job.p_len == 0 or p_ins > job.p_len)
                        and not eng.prefix_cache.contains(job.ids, p_ins)
                    ):
                        with eng._lock:
                            eng.prefix_cache.insert(
                                job.ids, p_ins,
                                eng._capture_prefix(job.state, p_ins, 0),
                            )
            except Exception:
                log.exception("chunked prefix donation failed (non-fatal)")
        job.state = None
        return True

    def _advance_prefill(self) -> bool:
        """Interleave pending prefill windows BEHIND this iteration's
        decode dispatch: live streams pay at most ``prefill_budget``
        tokens of window compute per chunk boundary (the head-of-line
        bound this feature exists for), idle compute backfills the
        backlog unbounded, and the pacer starves batch-class prefill
        while interactive decode runs.  The windows chosen — one a job,
        of different prompts — go out together: ``[B, C]`` tokens in ONE
        paged dispatch, ``B`` up to what the budget admits
        (``_prefill_width``; past it, as when idle compute backfills,
        further dispatches of at most that width).  Returns True when any
        window dispatched or handoff completed (the loop must not
        sleep)."""
        if not self.prefill_chunk:
            return False
        eng = self.engine
        if not self._prefilling:
            metrics.PREFILL_BACKLOG.labels(eng.bundle.name).set(0)
            return False
        from ..scheduler.policy import INTERACTIVE, DeadlineExceededError

        # This function's own host work runs under ``loop/prefill_advance``,
        # closed around each window's ``prefill_window`` phase (which
        # ``_dispatch_prefill_window`` holds): top-level phases stay flat.
        advanced = False
        t0 = time.monotonic()
        live = bool(self.active)
        with tracing.phase("loop/prefill_advance"):
            interactive_live = any(
                s.klass == INTERACTIVE and not s.cancelled.is_set()
                for s in self.active.values()
            )
            # Stale/cancelled jobs drop before any device work.
            for job in list(self._prefilling):
                st = job.st
                if st.cancelled.is_set():
                    self._prefilling.remove(job)
                    self._drop_job_resources(job)
                    self._release(st)
                elif (
                    not st.started
                    and st.deadline is not None
                    and time.monotonic() > st.deadline
                ):
                    self._prefilling.remove(job)
                    self._drop_job_resources(job)
                    self._shed("deadline", st.tenant)
                    self._finish(st, DeadlineExceededError(
                        "deadline passed mid-prefill; stream shed before "
                        "its first token"
                    ))
            # Ready jobs (prompt exhausted) wait only on a free slot.
            for job in [j for j in self._prefilling if j.ready]:
                if not self.free:
                    break
                self._prefilling.remove(job)
                if self._handoff_job(job):
                    advanced = True
            budget = self.prefill_budget if live else (1 << 30)
            jobs = sorted(
                [j for j in self._prefilling if not j.ready],
                key=lambda j: (
                    0 if j.st.klass == INTERACTIVE else 1,
                    j.st.deadline if j.st.deadline is not None
                    else float("inf"),
                    j.t_in,
                ),
            )
            chosen = []
            for job in jobs:
                if budget <= 0:
                    break
                if live and not self._pacer.allow(
                    job.st.klass, interactive_live
                ):
                    continue
                chosen.append(job)
                budget -= self.prefill_chunk
        # The chosen windows go out together, ``_prefill_width`` a
        # dispatch: what a boundary's budget admits is one dispatch.
        width = self._prefill_width
        for i in range(0, len(chosen), width):
            batch = chosen[i:i + width]
            try:
                batch = self._dispatch_prefill_window(batch)
            except Exception as e:
                for job in batch:
                    if job in self._prefilling:  # not stalled at growth
                        self._prefilling.remove(job)
                        self._fail_prefill_job(job, e)
                if self._fault_pending is not None:
                    break  # shared recovery runs at the iteration top
                continue
            with tracing.phase("loop/prefill_advance"):
                for job in batch:
                    advanced = True
                    if job.consumed >= job.L:
                        job.ready = True
                        if self.free:
                            self._prefilling.remove(job)
                            self._handoff_job(job)
        with tracing.phase("loop/prefill_advance"):
            if live and advanced:
                # Host-observed decode-cadence delay: the time this chunk
                # boundary spent on prefill dispatches while streams were
                # live (the device-side window rides behind the decode
                # dispatch, so this bounds — not equals — the stall).
                dt = time.monotonic() - t0
                self.prefill_stall_s += dt
                metrics.PREFILL_STALL.labels(eng.bundle.name).inc(dt)
            metrics.PREFILL_BACKLOG.labels(eng.bundle.name).set(
                self.prefill_backlog_tokens()
            )
        return advanced

    def _build_empty_state(self) -> None:
        """All-slots-done decode state from a max-bucket prefill
        template (shapes/dtypes only; every row starts dead).  Spec
        mode wraps the template through the family's ``init_spec_fn``
        so the slot state carries key_valid/write_idx (the spec base
        contract) plus the [n_slots, hist_w] drafting history (-1 =
        invalid everywhere until a tenant's row is inserted)."""
        import jax

        eng = self.engine
        # Chunked prefill widens the admissible prompt ceiling past the
        # largest bucket; slots must hold the widest insertable state.
        # The paged state takes only a token's dims and the per-row
        # fields' shapes from the template (the pool and the table width
        # come from the ledger), so its template is one bucket wide: a
        # monolithic prefill of ``max_prompt`` tokens (6016 in the
        # long-document cell: a 4.6 GB score tensor) is never run.
        s_max = max(eng.seq_buckets) if self.paged else self.max_prompt
        feats = {"input_ids": np.ones(s_max, np.int32), "length": np.int32(s_max)}
        with eng._lock:
            ids, mask, sp = self.programs.placed_batch([feats])
            # graftlint: unguarded(all-dead template build carries no stream data; it rebuilds at recovery, where guarding would renumber every deterministic FAULT_SPEC schedule the chaos suites pin)
            template, _ = eng._start(
                self._mp(n=int(ids.shape[0])), ids, mask, sp,
                eng.max_decode_len, eng.chunk_tokens, False,
            )
            if self.spec:
                template = self.programs.init_spec_template_fn()(
                    template, ids, mask)
        if self.paged:
            self._build_empty_paged(template)
            return
        empty = jax.tree.map(
            lambda x: np.zeros((self.n_slots,) + tuple(x.shape[1:]), x.dtype),
            template,
        )
        if self.spec:
            from ..models.spec import SpecState

            self._hist_w = int(template.history.shape[1])
            self._kv_w = int(template.base.key_valid.shape[1])
            empty = SpecState(
                base=empty.base._replace(
                    done=np.ones((self.n_slots,), bool)
                ),
                history=np.full((self.n_slots, self._hist_w), -1, np.int32),
            )
        else:
            empty = empty._replace(done=np.ones((self.n_slots,), bool))
        # Dead rows: done=True masks every output; other fields are
        # don't-cares until insert overwrites the row.  device_put NOW:
        # leaving numpy leaves here would defer a multi-MB host→device
        # upload of the whole slot state into the first admission.
        # Placed with the mesh's NAMED sharding (batch axis over
        # replicas): a bare device_put commits SingleDeviceSharding,
        # and jit keys executables on sharding — every (empty-state ×
        # prefill-state) insert pair would then recompile on the first
        # real admission (seconds in a pre-round record) because
        # warm() only ever saw NamedSharding-carrying states.  Under a
        # TP placement the KV-cache leaves additionally commit with
        # their heads axis sharded over 'tp' (place_decode_state) —
        # the layout sharding propagation gives prefill outputs, so
        # insert pairs see matching shardings and nothing reshards.
        place = getattr(eng.replicas, "place_decode_state", None)
        if place is not None:
            self._state = place(empty)
        else:
            # graftlint: unguarded(pure placement of a host-built zero template with explicit sharding; retry-safe but carries no compute — a lost device surfaces at the next guarded dispatch)
            self._state = jax.device_put(empty, eng.replicas.batch_sharding)
        # graftlint: unguarded(same placement barrier as the device_put above)
        jax.block_until_ready(jax.tree.leaves(self._state)[0])

    def _build_empty_paged(self, template) -> None:
        """All-slots-dead paged state: per-layer pools of
        ``pool.num_blocks`` zeroed blocks (scale pools of ones under
        QUANT_KV, mirroring the contiguous init) + per-row logical
        fields at the slot count.  A rebuild (startup, warm reset,
        post-exception recovery) also flushes the prefix cache's
        block-ref pins — the pins name blocks of the POOL BUFFERS
        being replaced, so their content is gone."""
        import jax

        from ..models.gpt import PagedState

        eng = self.engine
        if eng.prefix_cache is not None:
            # Demotion suspended for this flush: the pins name buffers
            # of the pool being REPLACED, so a copy would read garbage.
            prev = getattr(eng, "_host_demote_on", True)
            eng._host_demote_on = False
            try:
                while eng.prefix_cache.pop_lru() is not None:
                    pass
            finally:
                eng._host_demote_on = prev
        bs = self.block_size
        nbp = self.pool.num_blocks
        # The template is a slab state: an entry a layer that owns keys.
        # Only those that are pools under the block table become pools (a
        # window layer's ring is a state row's: ``SsmState.ring_k``).
        entries = getattr(eng.bundle.cfg, "pool_entries", None)
        if entries is not None and len(entries) != len(template.cache_k):
            template = template._replace(
                cache_k=[template.cache_k[i] for i in entries],
                cache_v=[template.cache_v[i] for i in entries])
        # A token's dims as the contiguous prefill state carries them
        # ((KVH, D) payload, (KVH, 1) scale), in _host_leaf_specs'
        # leaf order: what a dense gather unmerges (_gather_prefix).
        self._kv_tails = [
            tuple(int(d) for d in x.shape[2:])
            for x in jax.tree.leaves((template.cache_k, template.cache_v))
        ]

        def pool_leaf(x, fill):
            # ops/paged_attention's layout rule: [NB, BS, C], a token's
            # trailing dims merged from allocation on.
            arr = np.zeros(
                (nbp, bs, int(np.prod(x.shape[2:], dtype=np.int64))), x.dtype
            )
            if fill:
                arr[...] = 1
            return arr

        def pool_entry(c):
            if isinstance(c, tuple):  # (int8 payload, scale)
                return (pool_leaf(c[0], False), pool_leaf(c[1], True))
            return pool_leaf(c, False)

        empty = PagedState(
            cache_k=[pool_entry(c) for c in template.cache_k],
            cache_v=[pool_entry(c) for c in template.cache_v],
            key_valid=np.zeros(
                (self.n_slots, self.nb_max * bs), np.int32
            ),
            write_idx=np.zeros((self.n_slots,), np.int32),
            pos=np.zeros((self.n_slots,), np.int32),
            last_token=np.zeros((self.n_slots,), np.int32),
            done=np.ones((self.n_slots,), bool),
            tokens=np.zeros(
                (self.n_slots,) + tuple(template.tokens.shape[1:]),
                template.tokens.dtype,
            ),
            sample=jax.tree.map(
                lambda x: np.zeros(
                    (self.n_slots,) + tuple(x.shape[1:]), x.dtype
                ),
                template.sample,
            ),
        )
        if self._ssm_free is not None:
            # Recurrent state: zeroed rows, every slot pointed past the
            # last.  The host's ledger of rows is not touched: a rebuild's
            # streams give theirs back as they are dropped, and a row's
            # content never outlives its holder (a first window starts
            # from zeros, an insert overwrites).
            t = template.ssm

            def rows(leaves):  # [Bw, ...] a layer -> zeroed [R, ...]
                return [np.zeros((self.n_slots,) + tuple(x.shape[1:]), x.dtype)
                        for x in leaves]

            empty = empty._replace(ssm=t._replace(
                row=np.full((self.n_slots,), self.n_slots, np.int32),
                **{f: rows(v) for f, v in t.leaves.items()},
            ))
            self._note_ssm_rows()
        # Pool leaves commit sharded over 'tp' on the merged heads axis
        # under a TP placement (one logical pool, per-shard buffers — block
        # ids and the ledger stay device-agnostic); everything else
        # keeps the slot sharding.
        place = getattr(eng.replicas, "place_decode_state", None)
        if place is not None:
            self._state = place(empty, paged=True)
        else:
            # graftlint: unguarded(pure placement of a host-built zero template with explicit sharding; retry-safe but carries no compute — a lost device surfaces at the next guarded dispatch)
            self._state = jax.device_put(empty, eng.replicas.batch_sharding)
        # graftlint: unguarded(same placement barrier as the device_put above)
        jax.block_until_ready(jax.tree.leaves(self._state)[0])
        # Host tier buffers build once the pool leaf shapes are known.
        tier = self._host_tier()
        if tier is not None:
            tier.ensure_pool(self._host_leaf_specs())
            self._note_host_gauges()
            # Disk rung below it (KV_DISK_BUDGET_MB): attach the memmap
            # payload files to the live leaf layout (a layout change
            # wipes stale state) and hook the host ledger's eviction
            # spill so cold host blocks demote instead of dying.
            disk = getattr(eng, "kv_disk", None)
            if (
                disk is not None and disk.enabled
                and tier.ledger is not None
            ):
                if disk.attach(self._host_leaf_specs()):
                    tier.ledger.spill = self._spill_host_entry
                    disk._note_gauges(eng.bundle.name)
                else:
                    log.warning(
                        "disk KV tier: leaf layout mismatch; tier "
                        "disabled for this process"
                    )

    def _hist_row(self, feats: dict, first_toks: np.ndarray) -> np.ndarray:
        """Host-built drafting-history row at the SLOT's width/layout
        (the single's device history has per-bucket width and, for
        encoder-decoders, a different decoder offset — padding it would
        misalign the layout, so the row is rebuilt from what the host
        already knows: prompt ids + the first chunk's tokens).

        Invariant target (models/spec.py): hist[hoff + p] == the token
        embedded at cache position p, -1 where no real token lives."""
        hw, kw = self._hist_w, self._kv_w
        hoff = hw - kw
        L = int(feats["length"])
        ids = np.asarray(feats["input_ids"], np.int32)[:L]
        row = np.full((1, hw), -1, np.int32)
        chunk = np.asarray(first_toks, np.int32)
        if hoff > 0:
            # Encoder-decoder: [encoder ids | decoder tokens].  Cache
            # position 0 embedded decoder_start; step-i tokens embed at
            # position i+1.  The LAST budget token is never embedded,
            # so clamp when the first chunk already fills the budget
            # (chunk_tokens == max_decode_len) — the stream finishes
            # before any lookup could use the clamped tail anyway.
            row[0, :L] = ids
            start_id = int(
                getattr(self.engine.bundle.cfg, "decoder_start_id", 0)
            )
            row[0, hoff] = start_id
            room = hw - (hoff + 1)
            row[0, hoff + 1 : hoff + 1 + min(chunk.size, room)] = chunk[:room]
        else:
            # Decoder-only: prompt at [p_len, p_len+L) (a startup
            # PROMPT_PREFIX owns [0, p_len) with unknown ids); step-i
            # tokens embed at cache position p_len + L + i.
            base = self._p_len + L
            row[0, self._p_len : base] = ids
            room = hw - base
            row[0, base : base + min(chunk.size, room)] = chunk[:room]
        return row

    def _gather_prefix(self, p_len: int, block_ids) -> Any:
        """Dense ``{"k": [...], "v": [...]}`` view of a pinned prefix's
        blocks, gathered from the CURRENT pools — what the prefixed
        start executables consume on a paged cache hit.  Caller holds
        ``eng._lock``; the blocks are write-once (streams never write
        positions below their prefix), so any pool version at or past
        the donor's insert reads the right rows."""
        import jax.numpy as jnp

        blocks = jnp.asarray(np.asarray(block_ids, np.int32))
        return self.programs.prefix_rows_fn(p_len, self._kv_tails)(
            self._state, blocks)

    def _donate_paged(self, st: _Stream, slot: int) -> None:
        """Paged prefix donation: pin the slot's prompt blocks by
        refcount (``_capture_prefix``'s CoW counterpart — no KV copy;
        eviction drops only the cache's ref, so sharers keep the
        blocks alive).  Hit streams keep donating at growing buckets
        (start_fused's rule); prefix buckets are block-aligned by the
        build_model gate, so a pin never covers a partial block."""
        from .kv_blocks import PagedPrefix

        eng = self.engine
        L = int(st.feats["length"])
        row_ids = np.asarray(st.feats["input_ids"], np.int32)[:L]
        p_ins = eng.prefix_cache.bucket_for_insert(L)
        if (
            p_ins is None
            or (st.s_lo > 0 and p_ins <= st.s_lo)
            or eng.prefix_cache.contains(row_ids, p_ins)
            or st.blocks is None
        ):
            return
        nb_pin = p_ins // self.block_size
        if nb_pin <= 0 or nb_pin > len(st.blocks.ids):
            return
        ids = list(st.blocks.ids[:nb_pin])
        self.pool.ref(ids)
        eng.prefix_cache.insert(
            row_ids, p_ins,
            PagedPrefix(p_ins, tuple(ids), p_ins * eng.kv_token_bytes()),
        )

    def _release_blocks(self, slot: int, st: _Stream | None) -> None:
        """Return a slot's blocks to the pool and point its table row
        at the sentinel so in-state writes of the dead row drop."""
        if not self.paged:
            return
        if st is not None and st.blocks is not None:
            st.blocks.release()
            st.blocks = None
        if st is not None:
            self._ssm_give(st)
        self._table[slot, :] = self.pool.num_blocks
        self._dispatched_steps.pop(slot, None)
        if self.admission is not None:
            self.admission.note_pool()

    def _reclaim_then_ensure(self, sb, n_tokens: int) -> None:
        """Grow ``sb`` to cover ``n_tokens``; when the pool runs dry,
        evict LRU prefix pins (the cheapest memory to give back —
        sharers keep their refs) until it fits or nothing is left to
        evict (re-raises ``OutOfBlocks``)."""
        from .kv_blocks import OutOfBlocks

        eng = self.engine
        while True:
            try:
                sb.ensure(n_tokens)
                return
            except OutOfBlocks:
                if (
                    eng.prefix_cache is None
                    or eng.prefix_cache.pop_lru() is None
                ):
                    raise

    # -- host KV tier (KV_HOST_BUDGET_MB; docs/kv-tiering.md) ----------

    def _host_tier(self):
        """The engine's KVHostTier when this loop can use it (paged
        mode, budget > 0); None otherwise.  Read through the engine on
        every call — a fleet re-points every replica at ONE shared
        tier, and the tier survives engine rebuilds."""
        if not self.paged:
            return None
        t = getattr(self.engine, "kv_host", None)
        return t if (t is not None and t.enabled) else None

    def _swap_tokens(self, st: _Stream) -> int | None:
        e = getattr(st, "swap", None)
        return e.tokens if (e is not None and e.alive) else None

    def _drop_swap(self, st: _Stream, disk_too: bool = False) -> None:
        """Release a stream's host-tier entry (terminal end, fallback,
        or a fresh swap-out superseding it).  Safe for entries of a
        foreign (non-shared) tier — the entry's own ledger frees it.
        ``disk_too`` (terminal end only) also drops the stream's disk-
        tier write-through copy: nothing will ever resume it again."""
        e = getattr(st, "swap", None)
        if e is not None:
            st.swap = None
            ledger = getattr(e, "ledger", None)
            if ledger is not None:
                ledger.release(e)
            self._note_host_gauges()
        if disk_too and st.rid:
            d = getattr(self.engine, "kv_disk", None)
            if d is not None and d.enabled:
                try:
                    d.release_key(("stream", st.rid))
                except Exception:  # pragma: no cover - defensive
                    log.exception("disk-tier release failed")

    def _note_host_gauges(self) -> None:
        tier = self._host_tier()
        if tier is None or tier.pool is None:
            return
        name = self.engine.bundle.name
        metrics.KV_HOST_POOL_BLOCKS.labels(name, "used").set(
            tier.pool.used_blocks
        )
        metrics.KV_HOST_POOL_BLOCKS.labels(name, "free").set(
            tier.pool.free_blocks
        )

    def _spill_host_entry(self, entry) -> None:
        """Host-ledger eviction hook (SwapLedger.spill): copy the
        victim's blocks to the disk tier before they die — host RAM
        stays the hot rung, disk the cold one.  Runs under the host
        ledger lock: numpy reads + memmap writes only, never device
        work."""
        disk = self._disk_tier()
        if disk is None or entry.key is None or entry.pool is None:
            return
        disk.put(
            entry.key, entry.tokens, entry.kind,
            entry.pool.read(entry.ids),
        )
        if self._flight is not None:
            self._flight.event(
                "disk_spill", kind=entry.kind, blocks=len(entry.ids)
            )

    def _host_leaf_specs(self):
        """Per-block (shape, dtype) of every KV pool leaf, in
        ``jax.tree.leaves((cache_k, cache_v))`` order — the ONE
        canonical flattening shared by the host buffers and the
        gather/scatter executables, so a block round-trips by id."""
        import jax

        leaves = jax.tree.leaves((self._state.cache_k, self._state.cache_v))
        return [(tuple(x.shape[1:]), x.dtype) for x in leaves]

    def _gather_to_pending(self, block_ids: list[int]):
        """Dispatch one padded gather of ``block_ids`` and start the
        async device→host copies; returns the gathered leaves (their
        own buffers — they outlive pool rebuilds).  Caller appends to
        ``_swap_pending`` for materialization at a chunk boundary."""
        import jax

        nb = len(block_ids)
        pad = 1 << max(0, nb - 1).bit_length()
        pids = np.asarray(
            list(block_ids) + [block_ids[-1]] * (pad - nb), np.int32
        )
        with self.engine._lock:
            # Guarded at the ``swap`` site: a wedged device link on the
            # gather dispatch hits the watchdog instead of stalling
            # the loop, and swap chaos schedules (swap:fatal@N) can
            # target tier traffic without renumbering chunk sites.
            leaves = self.engine.dispatch_guard(
                "swap",
                lambda: jax.tree.leaves(
                    self.programs.swap_gather_fn()(self._state, pids)
                ),
            )
        prefetch_to_host(*leaves)
        return leaves

    def _swap_out(self, st: _Stream) -> None:
        """Copy the blocks behind this stream's RESUME prompt (feats
        already rewritten by ``_checkpoint_for_resume``; its KV is the
        contiguous positions [0, length)) device→host."""
        if st.blocks is None:
            return
        self._swap_out_blocks(
            st, list(st.blocks.ids), int(st.feats.get("length", 0) or 0)
        )

    def _swap_out_job(self, job) -> None:
        """Mid-prefill checkpoint swap (the round-14 REMAINING item):
        the windows already consumed wrote real KV into the job's
        blocks — copy [0, consumed) device→host BEFORE the blocks
        release, so the resume prefetches them back and re-prefills
        only the windows the checkpoint never ran.  ``consumed`` is
        block-aligned by construction (prefix buckets and
        PREFILL_CHUNK are both multiples of KV_BLOCK_SIZE), checked
        anyway because the partial swap-in continues the prefill at
        exactly that boundary."""
        st = job.st
        cov = int(getattr(job, "consumed", 0) or 0)
        if (
            not self.paged or getattr(job, "sb", None) is None
            or cov <= 0 or cov % self.block_size != 0
        ):
            return
        self._swap_out_blocks(st, list(job.sb.ids), cov)

    def _swap_out_blocks(self, st: _Stream, block_ids: list[int],
                         cov: int) -> None:
        """Shared swap-out core: copy the first ``blocks_for(cov)`` of
        ``block_ids`` device→host as this stream's resume KV.  One
        gather dispatch here; the device→host wire time rides
        asynchronously and materializes at the next chunk boundary.
        Every failure path leaves the stream on the recompute resume —
        the swap is an optimization, never a correctness dependency."""
        from .kv_blocks import blocks_for

        tier = self._host_tier()
        eng = self.engine
        if (
            tier is None or self._state is None
            or self._swap_hold or st.cancelled.is_set()
        ):
            return
        self._drop_swap(st)  # supersede any stale earlier entry
        nb = blocks_for(cov, self.block_size)
        if nb <= 0 or nb > len(block_ids):
            return
        entry = None
        try:
            if not tier.ensure_pool(self._host_leaf_specs()):
                return
            # Keyed by request id so the disk tier's write-through copy
            # (and a restart's replay lookup) can find it.
            entry = tier.reserve(
                nb, cov, kind="stream",
                key=("stream", st.rid) if st.rid else None,
            )
            if entry is None:
                return  # host tier too small even after eviction
            leaves = self._gather_to_pending(list(block_ids[:nb]))
        except Exception:
            log.exception("KV swap-out failed; stream will recompute")
            if entry is not None:
                tier.release(entry)
            return
        self._swap_pending.append((entry, leaves, nb, None))
        st.swap = entry
        self.swap_outs += 1
        nbytes = nb * self.pool.block_bytes
        self.swap_out_bytes += nbytes
        metrics.KV_SWAP_BYTES.labels(eng.bundle.name, "out").inc(nbytes)
        if self._flight is not None:
            self._flight.event(
                "swap_out", rid=st.rid, tokens=cov, blocks=nb
            )
        self._note_host_gauges()

    def _drain_swapouts(self) -> None:
        """Materialize pending device→host copies into the host pool
        buffers.  Runs at the iteration top (the async copies started
        at gather time have usually landed — np.asarray is then a
        local read), before a device rebuild discards the old pools,
        and before an evacuation hands checkpoints to an adopter."""
        if not self._swap_pending:
            return
        pending, self._swap_pending = self._swap_pending, []
        disk = self._disk_tier()
        for entry, leaves, nb, free_ids in pending:
            try:
                if entry.alive:
                    # Materialization is a device→host fetch (the async
                    # copies usually landed; when they didn't, this
                    # blocks on the wire) — guarded at the swap site so
                    # a wedged link hits the watchdog, not the loop.
                    vals = self.engine.dispatch_guard(
                        "swap",
                        lambda: [np.asarray(x)[:nb] for x in leaves],
                    )
                    entry.pool.write(entry.ids, vals)
                    entry.ready = True
                    if (
                        disk is not None and entry.kind == "stream"
                        and entry.key is not None
                    ):
                        # Write-through to the disk rung: the resume KV
                        # now outlives the PROCESS — a post-restart
                        # journal replay prefetches it back instead of
                        # re-prefilling (runtime/durability.py).
                        try:
                            disk.put(
                                entry.key, entry.tokens, "stream", vals
                            )
                        except Exception:
                            log.exception(
                                "disk write-through failed (resume "
                                "still host-served)"
                            )
            # graftlint: except(every swap-out failure lands on the recompute resume — classification cannot change the outcome, the entry is released either way)
            except Exception:
                log.exception("KV swap materialize failed")
                ledger = getattr(entry, "ledger", None)
                if ledger is not None:
                    ledger.release(entry)
            finally:
                if free_ids:
                    # Demotion: the device refs transferred with the
                    # queue entry free once the copy is host-resident.
                    self.pool.free(free_ids)
                    if self.admission is not None:
                        self.admission.note_pool()
        self._note_host_gauges()

    def _drain_demotions(self) -> None:
        """Gather queued prefix-cache demotions (evicted PagedPrefix
        pins whose block refs transferred with the queue entry)
        device→host; the refs free at materialization.  A demotion
        that cannot land (tier full, state torn down, key already
        resident) frees its refs immediately — eviction still evicts."""
        eng = self.engine
        pending = getattr(eng, "_host_demote_pending", None)
        if not pending:
            return
        eng._host_demote_pending = []
        tier = self._host_tier()

        def give_back(ids):
            self.pool.free(ids)
            if self.admission is not None:
                self.admission.note_pool()

        for key, pp in pending:
            ids = list(pp.block_ids)
            nb = len(ids)
            entry = None
            if (
                tier is not None and self._state is not None and nb > 0
                and tier.ensure_pool(self._host_leaf_specs())
                and not tier.prefix_resident(key)
            ):
                entry = tier.reserve(nb, pp.p_len, kind="prefix", key=key)
            if entry is None:
                give_back(ids)
                continue
            try:
                leaves = self._gather_to_pending(ids)
            except Exception:
                log.exception("prefix demotion gather failed")
                tier.release(entry)
                give_back(ids)
                continue
            nbytes = nb * self.pool.block_bytes
            self.swap_out_bytes += nbytes
            metrics.KV_SWAP_BYTES.labels(eng.bundle.name, "out").inc(nbytes)
            if self._flight is not None:
                self._flight.event(
                    "prefix_demote", p_len=pp.p_len, blocks=nb
                )
            self._swap_pending.append((entry, leaves, nb, ids))

    def _host_to_device(self, entry, pos: int, dev_ids: list[int]) -> None:
        """Scatter ``len(dev_ids)`` host blocks (``entry.ids[pos:]``)
        into the device pools at ``dev_ids``.  Caller holds
        ``eng._lock``.  Padded to the fixed KV_PREFETCH_BLOCKS chunk
        (repeating the last block — an idempotent rewrite) so one
        executable serves every call."""
        n = len(dev_ids)
        K = self.swap_chunk_blocks
        vals = entry.pool.read(entry.ids[pos : pos + n])
        ids_p = np.asarray(
            list(dev_ids) + [dev_ids[-1]] * (K - n), np.int32
        )
        vals_p = [
            np.concatenate([v] + [v[-1:]] * (K - n), axis=0)
            if K > n else v
            for v in vals
        ]
        # Guarded swap-site dispatch: prefetch scatters get the same
        # watchdog/retry/attribution coverage as every other dispatch.
        self._state = self.engine.dispatch_guard(
            "swap", lambda: self.programs.swap_scatter_fn()(
                self._state, ids_p, vals_p),
            donates=self._state,
        )

    def _start_swapin(self, st: _Stream) -> bool:
        """Begin a host→device swap resume: allocate the device blocks
        the resume prompt needs up front (admission charged exactly
        them) and queue an incremental prefetch job; the stream goes
        live through the chunked handoff once every block is copied.
        Returns False ONLY when the host copy is unusable (evicted,
        never materialized) — the caller falls back to the recompute
        admission.  True = handled, including the requeue-on-dry-pool
        path."""
        from .kv_blocks import OutOfBlocks, StreamBlocks

        eng = self.engine
        entry = st.swap
        tier = self._host_tier()
        self._drain_swapouts()  # the entry may still be materializing
        L = int(st.feats["length"])
        if self._is_disk_entry(entry):
            # Disk rung: the checkpoint's KV survived a host eviction
            # or a whole process restart — promote it disk→host, then
            # the normal host→device prefetch path runs unchanged.
            # (The pool leaf layout the promotion validates against
            # only exists once the paged state is built — a restart's
            # first resume arrives before any admission built it.)
            if self._state is None:
                self._build_empty_state()
            entry = self._promote_disk_swap(st, entry)
            st.swap = entry
        # A restored MID-PREFILL checkpoint covers only the prompt
        # windows it had consumed: acceptable when the chunked-prefill
        # machinery can continue from that (block-aligned) boundary.
        partial_ok = (
            entry is not None and entry.tokens < L
            and bool(self.prefill_chunk) and entry.tokens > 0
            and entry.tokens % self.block_size == 0
        )
        if (
            entry is None or tier is None or tier.pool is None
            or entry.pool is not tier.pool or not entry.alive
            or not entry.ready
            or not (entry.tokens == L or partial_ok)
        ):
            self._drop_swap(st)
            self.swap_fallbacks += 1
            metrics.KV_SWAP_RESUMES.labels(
                eng.bundle.name, "fallback"
            ).inc()
            if self._flight is not None:
                self._flight.event("swap_fallback", rid=st.rid)
            return False
        ids = np.asarray(st.feats["input_ids"], np.int32)[:L]
        st.feats["prefill_mode"] = "swapped"
        job = _SwapInJob(st, ids, L)
        job.resume_at = int(entry.tokens)
        job.sb = StreamBlocks(self.pool, self.block_size)
        try:
            if self._state is None:
                self._build_empty_state()
            eng.fault_point("grow")
            # Allocate exactly the blocks the restored KV covers; a
            # partial resume grows the rest window-by-window as the
            # prefill continues.
            self._reclaim_then_ensure(job.sb, job.resume_at)
        except OutOfBlocks:
            # Device pool momentarily dry: requeue with the host entry
            # INTACT — the retry still swap-resumes once blocks free.
            job.sb.release()
            metrics.KV_GROWTH_STALLS.labels(eng.bundle.name).inc()
            if self._flight is not None:
                self._flight.event(
                    "kv_growth_stall", rid=st.rid, site="swapin"
                )
            if self.admission is not None:
                self.admission.release(st)
            self._requeue_preempted(st)
            return True
        except Exception as e:
            job.sb.release()
            self._fail_streams([st], e)
            return True
        st.s_lo = 0
        # Exact-growth base, like chunked prefill: the restored KV
        # covers real positions [0, L) only.
        st.s_base = L
        job.table_row = np.full(self.nb_max, self.pool.num_blocks, np.int32)
        job.table_row[: len(job.sb.ids)] = job.sb.ids
        self._swapping.append(job)
        if self.admission is not None:
            self.admission.note_pool()
        return True

    def _is_disk_entry(self, entry) -> bool:
        """Whether a swap entry belongs to the DISK tier (journal
        replay hands these out; ``_start_swapin`` promotes them)."""
        d = getattr(self.engine, "kv_disk", None)
        return (
            entry is not None and d is not None
            and getattr(entry, "ledger", None) is d.ledger
        )

    def _promote_disk_swap(self, st: _Stream, entry):
        """Disk→host promotion of a stream checkpoint's resume KV: a
        pure host-side copy (memmap read → host-pool write), after
        which the entry behaves exactly like a fresh swap-out.  None
        on any miss or pressure — the caller falls back through the
        normal fallback ladder (recompute resume)."""
        d = getattr(self.engine, "kv_disk", None)
        tier = self._host_tier()
        if (
            d is None or tier is None or entry is None
            or not entry.alive or not entry.ready
        ):
            return None
        try:
            specs = self._host_leaf_specs()
            if not d.attach(specs) or not tier.ensure_pool(specs):
                return None
            host = tier.reserve(
                len(entry.ids), entry.tokens, kind="stream",
                key=("stream", st.rid) if st.rid else None,
            )
            if host is None:
                return None
            tier.pool.write(host.ids, d.pool.read(entry.ids))
            host.ready = True
        except Exception:
            log.exception("disk→host KV promotion failed")
            return None
        d.promotes += 1
        nbytes = len(entry.ids) * self.pool.block_bytes
        self.swap_in_bytes += nbytes
        if self._flight is not None:
            self._flight.event(
                "disk_promote", rid=st.rid, tokens=entry.tokens,
                blocks=len(entry.ids),
            )
        self._note_host_gauges()
        return host

    def _swapin_to_prefill(self, job: _SwapInJob) -> None:
        """A fully-prefetched PARTIAL resume (mid-prefill checkpoint)
        becomes a chunked-prefill job continuing at the restored
        boundary: the restored blocks are bit-what the consumed
        windows wrote, so only the remaining windows re-prefill —
        the round-14 'mid-prefill checkpoints re-prefill from scratch'
        negative, closed."""
        st = job.st
        self._swapping.remove(job)
        pj = _PrefillJob(st, job.ids, job.L)
        pj.p_len = 0
        pj.consumed = job.resume_at
        pj.sb, job.sb = job.sb, None
        pj.table_row = job.table_row
        self.swap_ins += 1
        metrics.KV_SWAP_RESUMES.labels(
            self.engine.bundle.name, "swapped"
        ).inc()
        if self._flight is not None:
            self._flight.event(
                "swap_resume", rid=st.rid, tokens=job.resume_at,
                partial=True,
            )
        self._drop_swap(st)
        self._prefilling.append(pj)

    def _swap_handoff(self, job: _SwapInJob) -> bool:
        """Flip a fully-prefetched swap job live (the chunked-prefill
        handoff: w_idx = L-1, pos = 0 — the restored blocks are
        bit-what a fresh prefill of the resume prompt writes, so the
        continuation is token-identical)."""
        st = job.st
        tokens = st.swap.tokens if st.swap is not None else job.L
        ok = self._handoff_job(job)
        if ok:
            self.swap_ins += 1
            metrics.KV_SWAP_RESUMES.labels(
                self.engine.bundle.name, "swapped"
            ).inc()
            if self._flight is not None:
                self._flight.event(
                    "swap_resume", rid=st.rid, tokens=tokens
                )
            self._drop_swap(st)
        return ok

    def _advance_swapins(self) -> bool:
        """Copy queued swap-resume jobs' host blocks back into the
        device pools — ``KV_PREFETCH_BLOCKS`` per iteration while
        decode streams are live (idle backfill unbounded), riding the
        same interleave seam as chunked prefill so a resume never
        stalls live decode for more than one bounded copy.  Returns
        True when any copy or handoff happened (the loop must not
        sleep)."""
        if not self._swapping:
            return False
        from ..scheduler.policy import INTERACTIVE

        eng = self.engine
        advanced = False
        live = bool(self.active)
        for job in list(self._swapping):
            if job.st.cancelled.is_set():
                self._swapping.remove(job)
                self._drop_job_resources(job)
                self._release(job.st)
        for job in [j for j in self._swapping if j.ready]:
            if not self.free:
                break
            self._swapping.remove(job)
            if self._swap_handoff(job):
                advanced = True
        budget = self.swap_chunk_blocks if live else (1 << 30)
        jobs = sorted(
            [j for j in self._swapping if not j.ready],
            key=lambda j: (
                0 if j.st.klass == INTERACTIVE else 1, j.t_in,
            ),
        )
        for job in jobs:
            if budget <= 0:
                break
            entry = job.st.swap
            if entry is None or not entry.alive:
                # Evicted mid-prefetch (host pressure from newer
                # swap-outs): drop the half-filled blocks and requeue
                # on the recompute path.
                self._swapping.remove(job)
                self._drop_job_resources(job)
                self._drop_swap(job.st)
                self.swap_fallbacks += 1
                metrics.KV_SWAP_RESUMES.labels(
                    eng.bundle.name, "fallback"
                ).inc()
                if self.admission is not None:
                    self.admission.release(job.st)
                self._requeue_preempted(job.st)
                continue
            n = len(job.sb.ids)
            k = min(self.swap_chunk_blocks, n - job.copied, budget)
            if k > 0:
                try:
                    with eng._lock:
                        self._host_to_device(
                            entry, job.copied,
                            job.sb.ids[job.copied : job.copied + k],
                        )
                except Exception as e:
                    self._swapping.remove(job)
                    self._drop_job_resources(job)
                    self._fail_streams([job.st], e)
                    if self._fault_pending is not None:
                        break
                    continue
                job.copied += k
                budget -= k
                advanced = True
                nbytes = k * self.pool.block_bytes
                self.swap_in_bytes += nbytes
                self.prefetch_blocks_total += k
                if live:
                    self.prefetch_blocks_live += k
                metrics.KV_SWAP_BYTES.labels(
                    eng.bundle.name, "in"
                ).inc(nbytes)
            if job.copied >= n:
                if job.resume_at < job.L:
                    # Mid-prefill checkpoint fully restored: continue
                    # the prefill from the restored boundary instead
                    # of handing off to decode.
                    self._swapin_to_prefill(job)
                    advanced = True
                else:
                    job.ready = True
                    if self.free:
                        self._swapping.remove(job)
                        if self._swap_handoff(job):
                            advanced = True
        return advanced

    def _promote_host_prefix(self, row_ids, L: int, usable):
        """Host→device prefix promotion on a device-tier miss:
        allocate fresh blocks, copy the demoted entry's KV back,
        re-insert the pin — a CoW prefix hit that survived device-
        budget pressure.  Caller holds ``eng._lock`` (the copy
        dispatches).  None on any miss or pressure: promotion must
        never shed or fail a request."""
        from .kv_blocks import OutOfBlocks, PagedPrefix

        tier = self._host_tier()
        eng = self.engine
        if (
            tier is None or tier.ledger is None or self._state is None
            or eng.prefix_cache is None
        ):
            return None
        m = eng.prefix_cache.host_lookup(row_ids, L, tier, usable=usable)
        from_disk = False
        if m is None:
            # Disk rung: a prefix demoted out of host RAM under tier
            # pressure still promotes back — the copy source is the
            # memmap (entry.pool.read works on either tier).
            disk = self._disk_tier()
            if disk is not None:
                m = eng.prefix_cache.host_lookup(
                    row_ids, L, disk, usable=usable
                )
                from_disk = m is not None
        if m is None:
            return None
        p_len, entry = m
        nb = p_len // self.block_size
        if nb <= 0 or len(entry.ids) < nb or not entry.ready:
            return None
        try:
            ids = self.pool.alloc(nb)
        except OutOfBlocks:
            return None
        try:
            for i in range(0, nb, self.swap_chunk_blocks):
                self._host_to_device(
                    entry, i, ids[i : i + self.swap_chunk_blocks]
                )
        except Exception:
            log.exception("prefix promotion copy failed")
            self.pool.free(ids)
            return None
        pp = PagedPrefix(p_len, tuple(ids), p_len * eng.kv_token_bytes())
        # The alloc ref becomes the cache pin.  If the insert evicted
        # it straight back out (cache budget below one entry), treat
        # the match as a miss — the blocks freed through on_evict.
        eng.prefix_cache.insert(row_ids, p_len, pp)
        if not eng.prefix_cache.contains(row_ids, p_len):
            return None
        if self.admission is not None:
            self.admission.note_pool()
        self.host_prefix_promotes += 1
        if from_disk:
            d = self._disk_tier()
            if d is not None:
                d.promotes += 1
        nbytes = nb * self.pool.block_bytes
        self.swap_in_bytes += nbytes
        metrics.KV_SWAP_BYTES.labels(eng.bundle.name, "in").inc(nbytes)
        metrics.KV_HOST_PREFIX_HITS.labels(eng.bundle.name).inc()
        if self._flight is not None:
            self._flight.event(
                "prefix_promote", p_len=p_len, blocks=nb, disk=from_disk
            )
        return p_len, pp

    # -- decode --------------------------------------------------------

    def _work_remains(self) -> bool:
        """True while some active stream still needs tokens beyond
        what the in-flight dispatches will already deliver
        (``produced`` only advances at delivery, so count in-flight
        coverage — one chunk a dispatch)."""
        ahead = len(self._inflight_chunks) * self.engine.chunk_tokens
        return any(
            st.produced + ahead < st.budget for st in self.active.values()
        )

    def idle(self) -> bool:
        """True when NOTHING is admitted, queued, prefilling, swapping
        or in flight — the quiescence gate a drain-based fleet
        scale-down waits on before retiring this replica
        (engine/fleet.py): an idle loop can stop with zero checkpoints
        and zero evacuations.  Plain reads; safe from any thread."""
        return (
            not self.active
            and not self._inflight_chunks
            and not self._prefilling
            and not self._swapping
            and not self._pending_admissions
            and not self._pending_wave
            and self.queue.qsize() == 0
        )

    def interactive_load(self) -> tuple[bool, bool]:
        """(interactive decode live, interactive work waiting) — the
        class-pressure signal of the bulk-job ``BackfillGovernor``
        (scheduler/policy.py): live means an interactive stream
        occupies a slot; waiting means one sits in the deadline queue
        or mid-prefill/swap-in.  Safe to read from the event loop (all
        plain reads)."""
        from ..scheduler.policy import INTERACTIVE

        live = any(
            st.klass == INTERACTIVE and not st.cancelled.is_set()
            for st in self.active.values()
        )
        waiting = self.queue.waiting(INTERACTIVE) > 0 or any(
            j.st.klass == INTERACTIVE
            for jobs in (self._prefilling, self._swapping)
            for j in jobs
        )
        return live, waiting

    def _grow_for_dispatch(self) -> None:
        """Block-by-block growth at the dispatch boundary: every live
        row's table must cover the positions the NEXT chunk will write.
        A row whose growth finds the pool dry — after reclaiming
        prefix pins and delivering what is in flight
        (``_ensure_on_dry_pool``) — is checkpointed and re-queued
        (token-identical resume when blocks free), the paged equivalent
        of vLLM's preempt-on-OOM; admission's worst-case bound
        guarantees a stream running alone always fits, so this
        terminates."""
        from .kv_blocks import OutOfBlocks

        eng = self.engine
        chunk = eng.chunk_tokens
        grew = False
        for slot, st in list(self.active.items()):
            if self.active.get(slot) is not st:
                continue  # ended in a delivery a dry pool forced below
            if st.cancelled.is_set() or st.blocks is None:
                continue  # frees at the next delivery; writes drop
            steps = self._dispatched_steps.get(slot, 0) + chunk
            # Writes past the budget are never read (the row frees at
            # delivery); don't spend blocks on them.
            need = min(st.s_base + steps, st.s_base + st.budget)
            try:
                # Fault-injection point: a forced OutOfBlocks here
                # exercises the reclaim → checkpoint-and-requeue path.
                eng.fault_point("grow")
                fresh = st.blocks.ensure(need)
            except OutOfBlocks:
                fresh = self._ensure_on_dry_pool(slot, st, need)
                if fresh is None:
                    if self.active.get(slot) is not st:
                        continue  # its last chunk was in flight
                    metrics.KV_GROWTH_STALLS.labels(eng.bundle.name).inc()
                    if self._flight is not None:
                        self._flight.event(
                            "kv_growth_stall", rid=st.rid, site="grow"
                        )
                    self.active.pop(slot)
                    self.sampled_slots.discard(slot)
                    self.free.append(slot)
                    if self.admission is not None:
                        self.admission.release(st)
                    # Checkpoint (and host-tier swap-out) BEFORE the
                    # block release — the paged dry-pool reclaim no
                    # longer discards KV the device already computed.
                    self._requeue_preempted(st)
                    self._release_blocks(slot, st)
                    continue
            if fresh:
                n = len(st.blocks.ids)
                self._table[slot, :n] = st.blocks.ids
                grew = True
            self._dispatched_steps[slot] = steps
        if grew and self.admission is not None:
            self.admission.note_pool()

    def _ensure_on_dry_pool(self, slot: int, st: _Stream, need: int):
        """A live row's growth found the pool dry: reclaim prefix pins;
        still dry with chunks in flight, deliver them and try once more.
        A row checkpointed with its chunk in flight loses that chunk's
        tokens, and two rows that each need the pool's other half could
        then preempt each other in turn with neither advancing (a
        wave's insert meets the iteration's chunk in flight): delivered
        first, every row keeps what the device has computed for it, so
        each turn advances, and a row that ENDS in the delivery gives
        its blocks back.  The fresh block ids (the caller refreshes the
        table row), or None: the row ended there, or the pool is dry
        for good and the caller checkpoints it."""
        from .kv_blocks import OutOfBlocks

        try:
            self._reclaim_then_ensure(st.blocks, need)
        except OutOfBlocks:
            if not self._inflight_chunks:
                return None
            self._deliver_all()
            if self.active.get(slot) is not st:
                return None
            try:
                self._reclaim_then_ensure(st.blocks, need)
            except OutOfBlocks:
                return None
        return st.blocks.ids[-1:]

    # -- double-buffered host prep (docs/compilation.md) ----------------

    def _stage_host_prep(self) -> None:
        """Stage iteration N+1's host prep while N is in flight: run
        the paged growth pass (block grants + table assembly) NOW and
        start the table's host→device upload, so the next dispatch's
        host work collapses to a validity check.  Growth here is the
        SAME ``_grow_for_dispatch`` the inline path runs — a dry pool
        checkpoints exactly as it would one iteration later, and like
        it delivers what is in flight first (``_ensure_on_dry_pool``):
        there, and only there, staging blocks on a fetch inside the
        window it overlaps.  The upload runs
        under the ``prep`` dispatch site: measured in
        ``dispatch_host_seconds{site="prep"}``, watchdogged, and a
        chaos target (``rN:prep:fatal@K`` kills a replica mid-staging
        — the recovery/evacuation paths discard the staged plan)."""
        self._rollback_staged_prep()
        if not (self.paged and self.active):
            return
        if not self._work_remains():
            return
        eng = self.engine
        t0 = time.perf_counter()
        steps0 = dict(self._dispatched_steps)
        self._grow_for_dispatch()
        if not self.active:  # every row checkpointed on a dry pool
            return
        deltas = {
            slot: self._dispatched_steps.get(slot, 0) - steps0.get(slot, 0)
            for slot in self.active
        }
        table_np = self._table.copy()
        # Growth + table assembly host seconds land on the prep site
        # too (the guarded upload below notes its own share).
        eng._note_dispatch("prep", time.perf_counter() - t0)
        import jax.numpy as jnp

        with eng._lock:
            table_dev = eng.dispatch_guard(
                "prep", lambda: jnp.asarray(table_np)
            )
        self._staged_prep = {
            "tenants": dict(self.active),
            "steps": dict(self._dispatched_steps),
            "deltas": deltas,
            "table_np": table_np,
            "table": table_dev,
        }
        self.prep_staged += 1

    def _rollback_staged_prep(self) -> None:
        """Return a stale staged plan's grants: subtract each still-
        live tenant's staged step delta and trim the over-granted tail
        blocks.  Tenants that left the active set since staging
        released their whole block list already — nothing to return
        for them."""
        staged, self._staged_prep = self._staged_prep, None
        if staged is None:
            return
        trimmed = False
        for slot, st in staged["tenants"].items():
            if self.active.get(slot) is not st or st.blocks is None:
                continue
            delta = staged["deltas"].get(slot, 0)
            if not delta:
                continue
            steps = max(0, self._dispatched_steps.get(slot, 0) - delta)
            self._dispatched_steps[slot] = steps
            need = min(st.s_base + steps, st.s_base + st.budget)
            trimmed |= bool(st.blocks.trim(need))
            n = len(st.blocks.ids)
            self._table[slot, :n] = st.blocks.ids
            self._table[slot, n:] = self.pool.num_blocks
        if trimmed and self.admission is not None:
            self.admission.note_pool()

    def _consume_staged_prep(self):
        """The staged device table for this dispatch, or None.  Valid
        ONLY when the loop state still matches the staged snapshot
        bit-for-bit — same tenants (by identity), same dispatched-step
        cursors, same table bytes.  A mismatch rolls the staged grants
        back so the inline re-prep starts from the exact pre-staging
        state."""
        staged = self._staged_prep
        if staged is None:
            return None
        if (
            staged["tenants"] == dict(self.active)
            and staged["steps"] == dict(self._dispatched_steps)
            and np.array_equal(staged["table_np"], self._table)
        ):
            self._staged_prep = None
            self.prep_hits += 1
            return staged["table"]
        self.prep_misses += 1
        self._rollback_staged_prep()
        return None

    def _dispatch_chunk(self, table) -> None:
        eng = self.engine
        tr = tracing.tracer()
        if tr is None:
            self._dispatch_chunk_inner(eng, table)
            return
        # Ring only (TRACE=1): which requests rode this chunk.  The
        # profiler's trace names the interval ``loop/chunk_dispatch``.
        with tr.span(
            "decode_chunk", cat="engine", n_streams=len(self.active),
            streams=[st.rid for st in self.active.values()],
            paged=self.paged,
        ):
            self._dispatch_chunk_inner(eng, table)

    def _note_dispatched(self, entry) -> None:
        eng = self.engine
        self.chunk_dispatches += 1
        metrics.STREAM_BATCH.labels(eng.bundle.name).observe(len(self.active))
        if self.paged:
            self._note_table_blocks(eng.chunk_tokens)
            if self._window_layers:
                self._note_window_keys(eng.chunk_tokens)
            if self._ring is not None:
                # a chunk writes positions [last - steps, last) of a stream
                steps, rlen = eng.chunk_tokens, self._ring[1]
                lasts = [st.s_base + self._dispatched_steps.get(slot, steps)
                         for slot, st in self.active.items()]
                self._note_ring_keys(sum(
                    max(last - max(last - steps, rlen), 0) for last in lasts))
        if self._moe_windows:
            # Dispatched before this chunk, so done before it: their counts
            # ride its fetch and no fetch waits on a prompt dispatch.
            entry = ((*entry[0], self._moe_windows), entry[1])
            self._moe_windows = []
        self._inflight_chunks.append(entry)

    def _chunk_table(self):
        """The host half of a paged chunk dispatch: the live rows'
        growth and the device block table the chunk reads.  Double-
        buffered prep: the staged plan (growth already ran, table
        already uploading) is used when still valid; otherwise the
        inline pass.  None where every row was checkpointed on a dry
        pool: no chunk goes out.  ``_run_loop`` calls it once an
        iteration, ahead of the wave's dispatch, so a wave draws on the
        pool after the live rows have."""
        table = self._consume_staged_prep()
        if table is None:
            self._grow_for_dispatch()
            if not self.active:  # every row checkpointed, dry pool
                return None
            import jax.numpy as jnp

            with self.engine._lock:
                # A snapshot: the dispatch is asynchronous and the
                # next handoff, growth or release writes ``_table``
                # (the CPU backend aliases an aligned host buffer).
                table = jnp.asarray(self._table.copy())
        return table

    def _dispatch_chunk_inner(self, eng, table) -> None:
        if self.paged:
            use_sample = bool(self.sampled_slots)
            dparams = self._mp()
            with eng._lock:
                # ``done`` is an output of the chunk, as ``toks`` is:
                # the entry is fetched after later dispatches have
                # consumed this state.  With experts ``toks`` is
                # (tokens, counts): the chunk's [L, E] routing counts
                # ride the same fetch.
                self._state, toks, done = eng.dispatch_guard(
                    "chunk",
                    lambda: self.programs.paged_chunk_fn()(
                        dparams, self._state, table,
                        eng.chunk_tokens, use_sample,
                    ),
                    donates=self._state,
                )
                prefetch_to_host(toks, done)
            self._note_dispatched(((toks, done), dict(self.active)))
            return
        use_sample = bool(self.sampled_slots)
        # Spec mode stays on the base tree: adapters do not compose
        # with the draft→verify executable (the batcher rejects the
        # combination at boot).
        dparams = eng.params if self.spec else self._mp()
        with eng._lock:
            if self.spec:
                # One batched draft→verify chunk: every live row emits
                # chunk_tokens..chunk_tokens·(spec_k+1) tokens.
                self._state, out, ns, done = eng.dispatch_guard(
                    "chunk",
                    lambda: eng._spec_chunk(
                        eng.params, self._state, eng.chunk_tokens,
                        eng.spec_k, use_sample,
                    ),
                    donates=self._state,
                )
                prefetch_to_host(out, ns, done)
                entry = (((out, ns), done), dict(self.active))
            else:
                self._state, toks, done = eng.dispatch_guard(
                    "chunk",
                    lambda: eng._gen_chunk(
                        dparams, self._state, eng.chunk_tokens,
                        use_sample,
                    ),
                    donates=self._state,
                )
                # Start the host copies now so the fetch in
                # _deliver_oldest finds the data (mostly) already on
                # this side of the wire.
                prefetch_to_host(toks, done)
                entry = ((toks, done), dict(self.active))
        self._note_dispatched(entry)

    def _route_entry(self, fetched, snapshot) -> None:
        """Route one fetched in-flight entry: a chunk's (toks, done), and
        behind them the (counts, tokens) of a share's prompt dispatches
        since the chunk before."""
        toks_np, done_np, *windows = fetched
        if isinstance(toks_np, tuple) and not self.spec:
            toks_np, counts = toks_np
            self._note_moe(counts)
            self._note_moe_rows(
                "decode", counts, self.n_slots, self.engine.chunk_tokens)
        for counts, tokens in (windows[0] if windows else ()):
            self._note_moe_rows("prefill", counts, int(tokens))
        self._route_chunk(toks_np, done_np, snapshot)
        if self.on_ok is not None:
            # One successfully fetched-and-routed dispatch closes the
            # replica's breaker fault streak (engine/fleet.py).
            self.on_ok()

    def _note_prefill_tiles(self, start: int, end: int) -> None:
        """(Query tile, key tile) pairs of the paged prompt window just
        dispatched that the prompt-window kernel runs, and the rest of
        its queries x gathered keys rectangles that it never does — from
        the window's own numbers, the way the step lays them out
        (models/llama.prefill_tile_counts); nothing where the window
        runs in XLA."""
        bcfg = getattr(self.engine.bundle, "cfg", None)
        if not (getattr(bcfg, "pallas_decode", False)
                and hasattr(bcfg, "layer_kind")):
            return
        from ..models.llama import prefill_tile_counts

        live, total = prefill_tile_counts(
            bcfg, self.prefill_chunk, self.nb_max, self.block_size,
            start, end - start)
        name = self.engine.bundle.name
        metrics.PREFILL_KEY_TILES_LIVE.labels(name).inc(live)
        metrics.PREFILL_KEY_TILES_DEAD.labels(name).inc(total - live)

    def _note_window_keys(self, steps: int) -> None:
        """Keys the window layers read in the chunk just dispatched, and
        keys of live context behind their windows that they did not —
        from the host's own stream lengths, a step and a window layer at
        a time (a stream's step over ``n`` keys reads ``min(n, window)``
        of them through the view and leaves ``n - window`` behind)."""
        n_layers, window = self._window_layers
        read = behind = 0
        for slot, st in self.active.items():
            first = st.s_base + self._dispatched_steps.get(slot, steps) - steps
            for n in range(first, first + steps):
                read += min(n, window)
                behind += max(n - window, 0)
        name = self.engine.bundle.name
        metrics.KV_WINDOW_KEYS_READ.labels(name).inc(read * n_layers)
        metrics.KV_WINDOW_KEYS_BEHIND.labels(name).inc(behind * n_layers)

    def _note_table_blocks(self, steps: int) -> None:
        """Block-table entries of the chunk just dispatched, a step and an
        attention layer at a time: live = entries that hold a key the
        layer attends to (a step over ``n`` keys, its own included, reads
        blocks ``0 .. (n-1)//bs``; a window layer those from the block of
        key ``n - window`` on, through its view), dead = the rest of
        ``n_slots x`` the table's (or the view's) width — what the paged
        kernel's live bounds skip (ops/paged_attention.live_programs).
        From the host's own stream lengths, like ``_note_window_keys``;
        a stream's keys start at its prompt's length, not at ``s_base``,
        which is the collated bucket's width where a wave prefilled it
        (the table grows off that; the keys do not)."""
        bs, width = self.block_size, self.nb_max
        n_win, window = self._window_layers or (0, 0)
        n_full = self._attn_layers - n_win
        first = np.asarray([
            min(st.s_base, st.s_lo + int(st.feats.get("length", st.s_base)))
            + self._dispatched_steps.get(slot, steps) - steps
            for slot, st in self.active.items()
        ], np.int64)
        n = np.clip(first[:, None] + np.arange(steps), 1, width * bs)
        last = (n - 1) // bs
        full = int((last + 1).sum())
        win = int((last - np.maximum(n - window, 0) // bs + 1).sum())
        view = width
        if n_win:
            from ..models.llama import window_view_blocks

            view = window_view_blocks(window, bs, width)
        live = full * n_full + win * n_win
        total = self.n_slots * steps * (width * n_full + view * n_win)
        name = self.engine.bundle.name
        metrics.KV_TABLE_BLOCKS_LIVE.labels(name).inc(live)
        metrics.KV_TABLE_BLOCKS_DEAD.labels(name).inc(total - live)
        latent_layers = getattr(self, "_latent_layers", 0)
        if latent_layers:
            # A latent layer's step over n keys reads n cached rows, once.
            metrics.KV_LATENT_KEYS_READ.labels(name).inc(
                int(n.sum()) * latent_layers)

    def _note_moe(self, counts) -> None:
        """One delivered paged chunk's per-expert assignment counts
        ([L, E], a row an EXPERT layer — a dense layer of a per-layer
        pattern has none: models/llama.generate_chunk_paged) into
        the routing metrics.  Imbalance and experts hit are a LAYER's
        (a grouped matmul's load is one layer's), a mean over layers —
        over the experts this tree HOLDS (all of them, or a chip's share:
        the counts are over the published experts, so the assignments
        that landed elsewhere — what an expert-parallel exchange would
        carry — are counted apart)."""
        per_layer = counts.sum(axis=1)
        if int(per_layer.min()) <= 0:  # every row done: nothing was routed
            return
        name = self.engine.bundle.name
        first, n_held = getattr(self, "_experts_held", (0, 0))
        held = counts[:, first:first + n_held] if n_held else counts
        here = held.sum(axis=1)
        metrics.MOE_ASSIGNMENTS.labels(name).inc(int(per_layer.sum()))
        metrics.MOE_ASSIGNMENTS_HELD.labels(name).inc(int(here.sum()))
        metrics.MOE_ASSIGNMENTS_ABSENT.labels(name).inc(
            int(per_layer.sum() - here.sum()))
        metrics.MOE_EXPERTS_HIT.labels(name).set(
            float((held > 0).sum(axis=1).mean())
        )
        if int(here.min()) > 0:
            metrics.MOE_LOAD_IMBALANCE.labels(name).observe(
                float((held.max(axis=1) * held.shape[1] / here).mean())
            )

    def _note_moe_rows(self, kind: str, counts, tokens: int,
                       steps: int = 1) -> None:
        """Assignment rows the expert block ran, and rows of its calls it
        left out, in ``steps`` calls of ``tokens`` tokens an expert layer
        whose counts ([L, E], summed over the steps) just arrived: each
        call ran the rung of ``ops/moe.row_rungs`` that holds its held
        assignments — the rule and the index the device branches on (a
        window's counts leave out an expert FFN in the model's last
        layer, which a window never runs: models/registry.py).  A
        decode step's ladder has one rung at every slot count a cell
        runs; a chunk whose steps had several would be counted at its
        steps' mean held count."""
        from ..ops.moe import row_kernels_fit, row_rungs, rung_index

        bcfg = self.engine.bundle.cfg
        first, n_held = self._experts_held
        n = tokens * bcfg.experts_per_token
        rungs = row_rungs(n, n_held, bcfg.num_experts)
        held = counts[:, first:first + n_held].sum(axis=1) // steps
        ran = int(np.take(rungs, rung_index(held, rungs)).sum()) * steps
        skipped = n * steps * len(counts) - ran
        # The held rows themselves where the call's two shuffles took the
        # DMA kernels: the rule the traced program read, on the same shape.
        fused = int(held.sum()) * steps if row_kernels_fit(
            n, bcfg.moe_latent or bcfg.d_model,
            self.engine.bundle.policy.compute_jnp) else 0
        seen = self.moe_rows.setdefault(kind, [0, 0])
        seen[0] += ran
        seen[1] += skipped
        self.moe_rows_fused[kind] = self.moe_rows_fused.get(kind, 0) + fused
        name = self.engine.bundle.name
        metrics.MOE_ROWS.labels(name, kind, "ran").inc(ran)
        metrics.MOE_ROWS.labels(name, kind, "skipped").inc(skipped)
        metrics.MOE_ROWS_FUSED.labels(name, kind).inc(fused)

    def _deliver_oldest(self) -> None:
        import jax

        if not self._inflight_chunks:
            return
        fetchables, snapshot = self._inflight_chunks.pop(0)
        with tracing.phase("loop/deliver"):
            fetched = self.engine.dispatch_guard(
                "fetch", lambda: jax.device_get(fetchables)
            )
            self._route_entry(fetched, snapshot)

    def _deliver_ahead_of_wave(self, n_ahead: int) -> None:
        """A wave's start was just dispatched behind ``n_ahead`` chunks
        in flight (and ahead of its own iteration's chunk, the newest
        entry where one went out): those land before it — deliver them
        oldest first, EACH in a fetch of its own as it lands (one
        combined fetch would hold the oldest until the newest has
        landed), before the caller blocks on the wave's fetch.  The
        chunk dispatched behind the start is NOT read here: waiting for
        it would hold the newcomer's first token for a chunk that lands
        after it.  A delivery may end a stream and free its slot and
        blocks before the insert takes one.  A fault raised here leaves
        ``_pending_admissions`` set for ``_recover`` and the loop's
        handler, as one raised by the chunk's dispatch or around the
        wave's own insert does."""
        if not n_ahead:
            return
        name = self.engine.bundle.name
        self.waves_behind_chunks += 1
        metrics.WAVES_BEHIND_CHUNKS.labels(name).inc()
        for _ in range(n_ahead):
            self._deliver_oldest()
            self.chunks_ahead_of_wave += 1
            metrics.CHUNKS_AHEAD_OF_WAVE.labels(name).inc()

    def _deliver_all(self) -> None:
        """Drain every in-flight dispatch with ONE combined device_get."""
        import jax

        if not self._inflight_chunks:
            return
        entries = self._inflight_chunks
        self._inflight_chunks = []
        with tracing.phase("loop/deliver"):
            fetched = self.engine.dispatch_guard(
                "fetch",
                lambda: jax.device_get([f for f, _ in entries]),
            )
            for (_, snapshot), got in zip(entries, fetched):
                self._route_entry(got, snapshot)

    def _deliver_ready(self) -> None:
        """Opportunistic delivery of in-flight work whose buffers are
        ALREADY on this side of the wire (``is_ready`` — the async
        host copies started at dispatch): paged mode frees EOS'd rows'
        blocks at fetch time, BEFORE the next dispatch's
        growth pass would keep granting blocks to rows the device
        already finished.  Costs nothing when data is still in flight
        (no sync — the depth-D cadence is untouched)."""
        if not self.paged:
            return
        import jax

        while self._inflight_chunks:
            with tracing.phase("loop/housekeeping"):
                fetchables = self._inflight_chunks[0][0]
                try:
                    landed = all(
                        leaf.is_ready() for leaf in jax.tree.leaves(fetchables)
                    )
                except AttributeError:  # backend without is_ready probes
                    landed = False
            if not landed:
                return
            self._deliver_oldest()

    def _route_chunk(self, toks_np, done_np, snapshot) -> None:
        eng = self.engine
        for slot, st in snapshot.items():
            # The slot may have been freed (and possibly re-tenanted)
            # since this chunk dispatched — never emit stale rows.
            if self.active.get(slot) is not st:
                continue
            if st.cancelled.is_set():
                self._free_slot(slot)
                continue
            if self.spec:
                from ..models.spec import flatten_emitted

                out_np, ns_np = toks_np
                chunk = flatten_emitted(out_np, ns_np, slot)
                metrics.SPEC_EMITTED.labels(eng.bundle.name).observe(
                    int(chunk.size) / max(1, eng.chunk_tokens)
                )
                # A verify round can overshoot the budget mid-chunk;
                # trim so the stream never emits past it.
                chunk = chunk[: st.budget - st.produced]
                st.produced += int(chunk.size)
                self._emit_tokens(st, chunk)
            else:
                self._emit_tokens(st, toks_np[slot])
                st.produced += eng.chunk_tokens
            if bool(done_np[slot]) or st.produced >= st.budget:
                self._journal_done(st)
                st.emit(_END)
                self._free_slot(slot)

    # -- executables and warm-up (engine/programs.py, engine/warm.py) ---

    @property
    def kernel_variant(self) -> str:
        """The tuned Pallas decode kernel the paged chunk traced with."""
        return self.programs.kernel_variant

    def warm(self) -> None:
        """Compile the loop's executables off the request path."""
        warm.warm(self)

    def warm_spawn(self, donor=None) -> None:
        """``warm`` for a replica spawned beside a live ``donor`` loop."""
        warm.warm_spawn(self, donor)
