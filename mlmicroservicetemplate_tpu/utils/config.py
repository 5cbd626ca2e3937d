"""Env-var driven service configuration (12-factor), typed via pydantic.

Capability parity: the reference template configures itself entirely from
environment variables read at startup — device selection (the north-star
``DEVICE=tpu`` mode, BASELINE.json:5), model selection, ports, batching
knobs (``max_batch=32``, BASELINE.json:10), and the parent orchestration
server URL its registration client announces itself to (SURVEY.md §2).

This module must stay import-light: no jax, no torch.  Device selection
has to happen *before* jax is imported (see ``runtime.device``), so the
config object is plain data.
"""

from __future__ import annotations

import os

from pydantic import BaseModel, Field, field_validator, model_validator

_VALID_DEVICES = ("tpu", "cpu")


class ServiceConfig(BaseModel):
    """All knobs for one model-serving process."""

    # Device runtime (L0). "tpu" routes through the PJRT TPU plugin,
    # "cpu" forces JAX_PLATFORMS=cpu (useful for CI and local dev).
    device: str = Field(default="tpu")
    # Model zoo selection (L1).
    model_name: str = Field(default="resnet50")
    # Optional path to a converted checkpoint (orbax dir or .npz). When
    # unset, models run from deterministic random init (no network, no
    # HF hub in this environment — SURVEY.md §7.1).
    model_path: str | None = None
    # Optional tokenizer asset (vocab.txt for WordPiece / spm vocab). When
    # unset, text models fall back to the built-in byte-level tokenizer.
    tokenizer_path: str | None = None

    # Persistent XLA compilation cache directory (runtime/device.py,
    # docs/compilation.md): restarts and fleet spawns reuse compiled
    # executables from disk instead of re-paying warmup.  Unset =
    # device default (ON for DEVICE=tpu at ~/.cache/mlmst-xla-cache;
    # OFF on cpu — CPU compiles are fast and golden tests want cold
    # compiles).  A path enables it anywhere; "0"/"off" disables even
    # on tpu.  The same setting is also read from the
    # COMPILE_CACHE_DIR env var for pre-config callers.
    compile_cache_dir: str | None = None

    # HTTP surface (L4).
    host: str = "0.0.0.0"
    port: int = 8000

    # Dynamic batching (L3). max_batch mirrors the reference's knob
    # (BASELINE.json:10); batch_timeout_ms is the max-wait policy.
    max_batch: int = 32
    batch_timeout_ms: float = 3.0
    # Upper bound on queued requests before the server sheds load (503).
    max_queue: int = 1024
    # Batches allowed in flight on the device concurrently. Dispatch and
    # result-fetch round-trips overlap (XLA queues the work), so >1
    # hides host<->device transfer latency behind compute. The default
    # dates from a pre-round record (removed in PR 22: 4 -> 66.8 req/s,
    # 8 -> 83.0, 12 -> regression from thread thrash) and is to be
    # re-measured on the attached chip.
    pipeline_depth: int = 8

    # Static-shape buckets (L2). XLA compiles one executable per shape;
    # requests are padded up to the nearest bucket (SURVEY.md §7.4.1).
    batch_buckets: tuple[int, ...] = (1, 2, 4, 8, 16, 32)
    seq_buckets: tuple[int, ...] = (32, 64, 128, 256, 512)
    # Warm (AOT-compile) every bucket at startup so compilation never
    # lands on the request path. Disable for fast test startup.
    warmup: bool = True

    # Replica data-parallel serving (the NCCL-DataParallel equivalent).
    # 0 = use every visible device.
    replicas: int = 0
    # Sequence-parallel width for long-context models (bert-long): the
    # sequence axis shards over an ('sp',) mesh and attention runs as a
    # ppermute ring (parallel/ring.py). 0 = every visible device.
    # Combine with REPLICAS>=2 for a ('replica','sp') 2-D mesh (batch
    # data-parallel on top of sequence parallelism).
    sp: int = 0
    # Tensor-parallel width (bert-base / gpt2): params Megatron-sharded
    # over the 'tp' axis of a ('replica','tp') mesh (parallel/tp.py
    # specs), batch over 'replica'. 0 = off (pure replica DP).
    tp: int = 0

    # Seq2seq decoding (T5).
    max_decode_len: int = 64
    stream_chunk_tokens: int = 4
    # Concurrent streaming generations admitted before 503 shedding.
    max_streams: int = 8
    # Continuous batching: live streams share one batched decode
    # dispatch, new streams admitted at chunk boundaries
    # (engine/streams.py).  Off = round-2 per-stream workers.
    continuous_batching: bool = True
    # Chunk-chain pipelining depth for the continuous loop: how many
    # batched chunk dispatches ride in flight before the oldest is
    # fetched.  The state chain is pure device-side, so depth D cuts
    # the steady-state inter-chunk cadence to ~max(RTT/D, chunk
    # compute).  0 = auto: measured at warmup from dispatch RTT vs
    # per-chunk device time (a long round-trip picks ~RTT/compute,
    # a short one picks 1).
    stream_pipeline: int = 0

    # Parent orchestration-server registration (template parity:
    # the public template self-registers with a Photo Analysis Server on
    # startup, retrying until acked — SURVEY.md §1).
    server_url: str | None = None
    register_retry_s: float = 2.0
    register_max_tries: int = 30
    # Re-register every N seconds so a restarted parent re-learns this
    # service; 0 disables (register-once, template-parity behavior).
    register_heartbeat_s: float = 0.0

    # Weight-only quantization for serving: None (full precision) or
    # "int8" (per-channel symmetric; halves weight bytes per decode
    # step — the lever for HBM-bound small-batch generation).
    quantize: str | None = None
    # KV-cache quantization (llama family): "int8" stores K/V as
    # per-token-per-head int8 + scales, halving the SECOND bandwidth
    # term of batched long-context decode (weights being the first).
    # Lossy (not bit-identical to bf16-cache generation); measured in
    # the pre-round BASELINE record (removed in PR 22).  Composes with both
    # prefix knobs (round 6): cached
    # prefix rows are captured/attached as int8 + scale entries the
    # quantized cache absorbs directly.
    quant_kv: str | None = None

    # Speculative decoding for generative families (gpt2/llama/t5):
    # "ngram" drafts the next SPEC_K tokens by prompt-lookup (the last
    # SPEC_NGRAM generated tokens are matched against the prompt +
    # generation history — for T5, against the ENCODER input, where
    # summaries quote from) and verifies all of them in ONE forward —
    # the only lever past the HBM ceiling at batch=1, where each step
    # otherwise streams the full weights for one token.  Greedy output
    # is exactly the verify-forward's argmax at every position, so
    # output == non-speculative greedy.
    spec_decode: str | None = None
    # Draft length per verify step (tokens checked per forward).
    spec_k: int = 8
    # Match-pattern length for the n-gram lookup.
    spec_ngram: int = 2
    # Load gate: greedy streams route to the speculative per-stream
    # path only while FEWER than this many streams are active; beyond
    # it they join the shared continuous-batching loop instead (one
    # batched dispatch for all streams beats per-stream speculation
    # under concurrency — speculation is the B=1 latency lever).
    spec_max_streams: int = 1
    # Speculation inside the continuous-batching loop: the shared slot
    # state carries a per-row drafting history and the shared chunk
    # runs draft→verify rounds, so EVERY live stream keeps the
    # accepted-token multiplier instead of losing drafting beyond
    # spec_max_streams.  Costs a (spec_k+1)-wide window per row per
    # round — wins on quoting/repetitive traffic, can lose on
    # low-acceptance traffic at high width (not measured on this
    # chip: no cell yet).  Stacks with PREFIX_CACHE (round 6): hit
    # admissions recast through init_spec_fn at slot-insert time, so
    # prefix-hit streams join the spec slot batch.  With
    # SPEC_SAMPLED=0, sampled streams bypass the loop to the
    # per-stream chunked path so the strict seed contract holds.
    spec_continuous: bool = False
    # Rejection-sampling acceptance for temperature>0 requests (accept
    # draft_i with prob p(draft_i) under the filtered distribution;
    # resample the residual on reject): DISTRIBUTION-identical to
    # sequential sampling, but consumes randomness differently, so a
    # seeded request's exact tokens depend on which path served it
    # (each path is itself deterministic per seed).  SPEC_SAMPLED=0
    # restores strict cross-path seed reproducibility by routing all
    # sampled traffic to the normal chunked path.
    spec_sampled: bool = True

    # Shared prompt prefix (system prompt) for decoder models
    # (gpt2/llama): its KV is computed ONCE at startup and cached, so
    # every request's prefill pays only its own suffix (O(S) instead
    # of O(P+S)) and the prefix never counts against wire bytes.
    prompt_prefix: str | None = None
    # PER-REQUEST prefix caching (decoder families; the vLLM-class
    # generalization of PROMPT_PREFIX): KV of recurring token prefixes
    # — per-conversation system prompt + history — is captured from
    # each prefill and reused by any later request sharing it, matched
    # at request time by content hash at seq-bucket lengths.  Opt-in:
    # it compiles a (prefix-bucket × suffix-bucket) executable grid at
    # warmup, so restrict SEQ_BUCKETS for these deployments.
    # Mutually exclusive with PROMPT_PREFIX.
    prefix_cache: bool = False
    prefix_cache_mb: float = 256.0

    # SLA-aware request scheduling (scheduler/admission.py + policy.py).
    # Priority class for requests without an X-Priority header.
    priority_default: str = "interactive"
    # Default deadline for requests without X-Deadline-Ms, in ms; a
    # request still WAITING past its deadline sheds as a fast 504
    # before any device work.  0 = no default deadline.
    deadline_ms: float = 0.0
    # Weighted dequeue: interactive pops per batch pop while both
    # classes wait (batch never starves, interactive never waits more
    # than 1/weight extra).
    class_weight: int = 4
    # KV-footprint admission budget in MB: the cache bytes the admitted
    # working set may commit (estimated per request from prompt bucket,
    # decode budget, model dims and the QUANT_KV dtype).  Requests that
    # can never fit shed 503; transient overcommit down-classes
    # interactive work to batch.  0 disables the gate.
    kv_budget_mb: float = 0.0
    # Streams allowed to WAIT (deadline-queued) beyond max_streams
    # active; 0 restores the historical instant 503 past max_streams.
    max_stream_queue: int = 0
    # Block-paged KV cache (decoder families, continuous batching):
    # the shared decode loop's KV lives in a pool of KV_BLOCK_SIZE-token
    # blocks with per-slot block tables instead of per-slot contiguous
    # slabs.  Admission then charges a stream only its prompt blocks
    # plus the first chunk's block, grows block-by-block at chunk
    # boundaries, frees every block the moment the stream ends (early
    # EOS, cancel, preemption), and prefix-cache hits SHARE the donor's
    # prompt blocks by refcount instead of copying — which is what
    # turns KV_BUDGET_MB from a worst-case gate into live-token
    # occupancy (docs/kv-paging.md).  Default off = the seed layout.
    paged_kv: bool = False
    # Tokens per KV block in paged mode.  Unaligned seq buckets are
    # rounded UP to this grid at parse (_align_paged_seq_buckets) —
    # prefix sharing relies on bucket-aligned block boundaries.
    kv_block_size: int = 16
    # -- Pallas decode-kernel selection (docs/kernel_tuning.md) --------
    # Measured kernel-variant sweep at warmup (ops/autotune.py): every
    # feasible variant is verified against the jnp reference and timed
    # at the real serving shapes; the winner installs into the shared
    # ExecutableCache and persists in the tuning table, so replica
    # spawns/rebuilds inherit it with zero extra compiles.  Off =
    # default kernel everywhere (the seed behavior).
    pallas_autotune: bool = False
    # Pin one kernel variant fleet-wide (Variant grammar, e.g.
    # "b4-hb"); validated at boot.  None = autotuned-or-default.
    pallas_variant: str | None = None
    # Run Pallas kernels in interpret mode and lift the TPU backend
    # gate — CPU CI and the pallas_ab bench exercise the real kernel
    # path; never set this on a TPU deployment.
    pallas_interpret: bool = False
    # Contiguous-slab Pallas attention cutover: prompts at or under
    # this length run the single-block fused kernel (ops/attention.
    # use_pallas_attention); longer prompts take the XLA path.  Env is
    # read by ops/attention directly (config-less callers: unit
    # tests); this field validates it at boot.
    pallas_single_block_max_seq: int = 512
    # VMEM budget (MB) the decode-kernel fit gate AND the autotuner's
    # variant cost model filter against (ops/attention.
    # decode_kernel_fits, ops/autotune.paged_vmem_bytes).  ~16 MB/core
    # physical on v4/v5e; default leaves headroom for double-buffering.
    decode_kernel_vmem_budget_mb: int = 10
    # Host-RAM KV tier (docs/kv-tiering.md; requires PAGED_KV=1): MB of
    # host memory backing swapped-out KV.  Checkpointed streams
    # (preemption, dry-pool reclaim, supervised crash recovery, fleet
    # evacuation) copy the blocks behind their resume prompt
    # device→host instead of freeing-and-recomputing them, and resume
    # by prefetching the copies back — zero re-prefill chunks; evicted
    # prefix-cache entries demote here and promote back on a match, so
    # CoW prefix hits survive device-budget pressure.  0 (default) =
    # tier off: every checkpoint recomputes exactly as before
    # (bit-identical paths).
    kv_host_budget_mb: float = 0.0
    # Swap-in pacing: host→device block copies per loop iteration while
    # decode streams are live (idle backfill is unbounded) — the
    # communication-aware prefetch budget that keeps a resume from
    # stalling live decode (ChunkFlow, arXiv 2605.11335).
    kv_prefetch_blocks: int = 4
    # Durable serving (runtime/durability.py; docs/durability.md).
    # Directory for the crash-safe write-ahead stream journal: every
    # stream's admission record and delivered-token cursor append here
    # (length/CRC-framed JSONL) BEFORE tokens reach the client, and on
    # startup the server replays the journal and re-admits every
    # incomplete stream for token-identical resume after kill -9.
    # Clients reconnect via GET /v1/streams/{request_id}; unary
    # /predict retries dedup by X-Request-Id against journaled
    # results.  Unset (default) = no journal, every path bit-identical
    # to the pre-durability code.
    journal_dir: str | None = None
    # Journal fsync policy: "always" (fsync per record — survives
    # kernel/power crashes), "interval" (fsync at most every 50 ms),
    # "off" (OS page cache only — still survives a PROCESS kill, which
    # is the kill -9 contract; not a host crash).
    journal_fsync: str = "always"
    # Disk KV tier below the host-RAM tier (requires PAGED_KV=1,
    # KV_HOST_BUDGET_MB>0 and JOURNAL_DIR): cold host blocks (LRU-
    # evicted swaps, demoted prefixes) spill to memmap files under
    # JOURNAL_DIR/kv_disk instead of dying, and stream checkpoints
    # write through so their resume KV outlives the process.  0
    # (default) = no disk tier.
    kv_disk_budget_mb: float = 0.0
    # Bulk inference lane (jobs/; docs/bulk-inference.md): the
    # /v1/batches job API — thousands of JSONL prompt lines submitted
    # as ONE durable job whose manifest, per-line state and results
    # persist through the write-ahead journal machinery under
    # JOURNAL_DIR/jobs, so a kill -9 mid-job resumes from the last
    # completed line with exactly-once per-line results.  Lines run as
    # batch-class streams behind the deadline queue and pacer — pure
    # idle-compute backfill that interactive arrivals preempt at chunk
    # boundaries.  Requires JOURNAL_DIR and a generative model.  Off
    # (default) = no job code runs, serving paths bit-identical.
    jobs_enabled: bool = False
    # Per-job cap on lines in flight concurrently; the backfill
    # governor throttles below it while interactive work is live or
    # waiting (scheduler/policy.py).
    job_max_concurrent_lines: int = 4
    # Seconds a completed/cancelled job's results stay fetchable
    # before the store purges them; 0 = keep forever.
    job_result_ttl_s: float = 3600.0
    # Multi-tenant serving (tenancy/; docs/multi-tenancy.md).  Inline
    # tenant table: comma-separated "name=weight" (or bare "name",
    # weight 1) — each tenant's name doubles as its X-Api-Key.  Unset
    # AND TENANTS_FILE/ADAPTER_DIR unset (default) = no tenancy object
    # is constructed anywhere and every serving path is bit-identical
    # to the single-tenant server (pinned by tests/test_tenancy.py).
    tenants: str | None = None
    # Full tenant table: JSON file of spec objects with optional
    # "weight", "api_keys", "max_concurrency", "tokens_per_window",
    # "kv_mb" and "adapter" fields.  Both set = file wins for
    # duplicate names.  Garbage fails at boot, not request time.
    tenants_file: str | None = None
    # Fair-share weight for tenants without an explicit weight (and
    # for anonymous/keyless traffic).
    tenant_default_weight: float = 1.0
    # Sliding window in seconds for the per-tenant token-rate ledger
    # (tokens_per_window quotas count tokens admitted in the trailing
    # window; Retry-After = time until enough of the window drains).
    tenant_window_s: float = 60.0
    # Metric-label cardinality bound: the first K configured tenants
    # (declaration order) keep their names in the `tenant` label,
    # everything else exports as "other", keyless traffic as "anon" —
    # <= K+2 label values regardless of tenant-table size.
    tenant_metrics_topk: int = 8
    # LoRA adapter library directory: each <name>.npz under it (keys
    # "layers.{i}.{proj}.lora_a|lora_b", optional scalar "alpha")
    # becomes an adapter servable via the X-Adapter header — N tenants'
    # adapters decode as ONE batched dispatch over the shared base
    # weights (models/lora.py), routed through the SAME executables as
    # the base model (adapter install/evict never recompiles; pinned).
    # Unset (default) = no adapter code runs.  Rejected with
    # SPEC_DECODE/SPEC_CONTINUOUS (spec scoreboards assume base-model
    # logits).
    adapter_dir: str | None = None
    # Device-resident adapter slots (slot 0 is the pinned zero delta
    # serving base-model rows).  Adapters page host<->device through a
    # refcounted pool of this many slots; acquisition beyond capacity
    # sheds with reason="adapter_pool".
    adapter_slots: int = 8
    # Chunked prefill with prefill–decode interleaving
    # (docs/chunked-prefill.md): prompts longer than PREFILL_CHUNK
    # tokens prefill in PREFILL_CHUNK-token windows interleaved with
    # the continuous loop's decode chunks, so one long prompt never
    # stalls every live stream for its whole prefill.  Also lifts the
    # loop's prompt ceiling past the largest seq bucket (up to the
    # model's position budget) — oversized prompts chunk instead of
    # falling to the legacy per-stream path.  0 = off (the seed's
    # monolithic prefill).  Under PAGED_KV must be a multiple of
    # KV_BLOCK_SIZE; rejected for t5 / PROMPT_PREFIX / SPEC_CONTINUOUS.
    prefill_chunk: int = 0
    # Max prefill tokens interleaved per loop iteration while decode
    # streams are live (idle compute backfills unbounded).  0 = one
    # chunk (PREFILL_CHUNK) per iteration — decode cadence never waits
    # behind more than one window's compute.
    prefill_budget: int = 0
    # Prompt-length ceiling for chunked admission; 0 = auto (the
    # model's position budget: max_position - decode budget).  Bounds
    # the continuous loop's slot width (contiguous mode) / block-table
    # width (paged), so cap it when HBM is tight.
    prefill_max_prompt: int = 0
    # Interactive arrivals may preempt batch-class streams (checkpoint
    # the cursor, free the slot, re-queue for token-identical resume)
    # when every slot is busy.  Only reachable with MAX_STREAM_QUEUE>0.
    preempt: bool = True
    # Seconds the SIGTERM drain waits for in-flight work before exit.
    drain_grace_s: float = 30.0

    # Replica fleet (engine/fleet.py + scheduler/router.py): run this
    # many INDEPENDENT continuous decode loops — each with its own
    # engine, supervisor, watchdog, KV pool and prefix cache — behind
    # a health-gated router.  A dead replica's streams checkpoint at
    # their delivered-token cursor and resume token-identically on a
    # healthy replica.  1 (default) = the single-engine path, exactly.
    fleet_replicas: int = 1
    # Routing policy: "least" = health → least-loaded (committed KV
    # bytes + queue depth) → prefix affinity; "rr" = health-gated
    # round-robin (the A/B baseline).
    fleet_route: str = "least"
    # Consecutive dispatch faults that open a replica's circuit
    # breaker (routing avoids it; a half-open probe re-admits).
    fleet_breaker_n: int = 3
    # Seconds a breaker may sit open before the replica is evicted:
    # its streams failover to a healthy replica.  Half-open probes
    # start at half this interval.  Under elastic scaling this is ALSO
    # the rejoin delay: an evicted replica is rebuilt through the
    # scale-up path once it has been dead this long.
    fleet_evict_s: float = 10.0
    # Multi-chip fleet placement (docs/tensor-parallel.md +
    # docs/autoscaling.md): comma-separated per-replica TP widths, e.g.
    # "2,2,1" = two TP=2 groups plus one single-device spare, carved
    # DISJOINT from the visible device list (replica 0 keeps the base
    # engine's devices, so the first width must equal TP).  Unset
    # (default) with TP>1 carves one TP-wide group per replica; unset
    # with TP=1 keeps the shared single-device placement bit-identical
    # to the pre-multichip fleet.
    fleet_tp_groups: str | None = None

    # Elastic fleet (docs/autoscaling.md): live autoscaling bounds.
    # FLEET_REPLICAS becomes the INITIAL size; the ScalingGovernor
    # (scheduler/policy.py) moves the live count within
    # [FLEET_MIN_REPLICAS, FLEET_MAX_REPLICAS] off the router's own
    # load signals.  0 = same as FLEET_REPLICAS, and when BOTH bounds
    # collapse onto FLEET_REPLICAS the fleet is STATIC — no governor
    # thread, bit-identical to the pre-elastic code.
    fleet_min_replicas: int = 0
    fleet_max_replicas: int = 0
    # Scale-UP triggers (evaluated per governor tick, live < max):
    # waiting streams per live replica...
    scale_up_queue: float = 2.0
    # ...or committed-KV bytes as a fraction of the live fleet budget...
    scale_up_kv_frac: float = 0.85
    # ...or the decode loops' TTFT EWMA in ms (0 = signal off).
    scale_up_ttft_ms: float = 0.0
    # Minimum seconds between scale-up events (spin-up is cheap under
    # donor broadcast but each event still recompiles executables).
    scale_up_cooldown_s: float = 3.0
    # Scale-DOWN trigger: total load (active + queued streams) would
    # fit inside this fraction of the SURVIVORS' slots...
    scale_down_load: float = 0.25
    # ...sustained for this many seconds (the lull filter).
    scale_down_cooldown_s: float = 10.0
    # Governor tick period in seconds.
    scale_period_s: float = 0.5

    # Fault tolerance (engine/faults.py + engine/supervisor.py).
    # Deterministic fault-injection schedule wrapped around the
    # device-dispatch boundaries; off (None) = zero overhead.  Grammar
    # in engine/faults.py, e.g. "chunk:fatal@5;*:transient~0.05".
    fault_spec: str | None = None
    # Seed for rate-based (~) fault rules, so a chaos run replays.
    fault_seed: int = 0
    # Watchdog deadline per device dispatch in seconds; an overrun
    # raises DispatchTimeoutError (classified fatal → supervisor
    # rebuild) instead of stalling the decode loop forever.  0 = off
    # (the seed behavior; supervised deployments should set e.g. 60).
    dispatch_timeout_s: float = 0.0
    # Transient dispatch failures retried with capped exponential
    # backoff before the error escalates.
    dispatch_retries: int = 2
    dispatch_backoff_s: float = 0.05
    # Engine rebuilds the supervisor may spend (fatal fault / loop
    # death → checkpoint streams, rebuild device state, resume) before
    # /readyz goes permanently unready.
    engine_restarts_max: int = 3
    # Sliding restart window in seconds: the budget above counts only
    # restarts within the trailing window, so a long-lived engine is
    # not condemned by faults from hours ago.  0 (default) = the
    # historical lifetime cap.
    engine_restart_window_s: float = 0.0
    # Supervised crash recovery for the continuous decode loop; off
    # restores the seed's error-every-stream behavior on a fault.
    supervise: bool = True

    # Latency histogram bucket edges (comma-separated ascending
    # seconds) for the request/TTFT latency families in
    # utils/metrics.py; unset = the built-in defaults, which since r20
    # extend past 10 s (the r11 honest negative: stream TTFT/TBT p99
    # saturated the old 10 s top bucket on the 1-vCPU box).
    latency_buckets: str | None = None
    # SLO objectives per priority class, in ms; 0 disables that
    # objective.  Interactive-class time-to-first-token / inter-chunk
    # cadence...
    slo_ttft_ms: float = 0.0
    slo_tbt_ms: float = 0.0
    # ...and the batch-class pair (bulk/background traffic usually
    # carries a much looser objective, not none).
    slo_batch_ttft_ms: float = 0.0
    slo_batch_tbt_ms: float = 0.0
    # SLO attainment target: the burn-rate denominator is the error
    # budget (1 - SLO_TARGET); burn 1.0 = consuming it exactly at the
    # sustainable rate.
    slo_target: float = 0.99
    # Burn-rate windows in seconds, "fast,slow" (multi-window
    # alerting: fast reacts, slow filters blips).
    slo_windows_s: str = "60,600"
    # SLO-burn scale-up signal for the ScalingGovernor: scale up when
    # the worst fast-window burn rate reaches this threshold.  0
    # (default) = off — governor decisions bit-identical to pre-SLO
    # behavior (pinned).
    scale_up_slo_burn: float = 0.0

    # Observability.
    log_level: str = "INFO"
    # Log line shape: "text" (the classic formatter) or "json" (one
    # structured object per line, request_id-correlated with spans and
    # HTTP error bodies — utils/tracing.JsonLogFormatter).
    log_format: str = "text"
    # Request-level span tracing (utils/tracing.py): spans at the
    # request / admission / queue-wait / loop-phase / prefill-window /
    # decode-chunk / dispatch-site seams, kept in a ring and exported
    # as Chrome trace-event JSON at GET /debug/trace.  Off = no span
    # objects on the hot path.  On or off, the same phase names go to
    # the profiler's trace whenever a session runs, and nothing waits
    # for the device; see docs/observability.md.
    trace: bool = False
    # Completed spans kept in the trace ring.
    trace_ring: int = 4096
    # Engine flight recorder ring: loop iterations + scheduling/fault
    # events kept for GET /debug/engine and the automatic dump on
    # fatal faults.  0 disables recording (dump still answers, empty).
    flight_ring: int = 256
    # Directory for on-demand jax.profiler device traces
    # (POST /debug/profile); None = $PROFILE_DIR or /tmp/jax-trace.
    profile_dir: str | None = None

    # ------------------------------------------------------------------
    # r18 (graftlint knob-drift): every knob fails fast on garbage at
    # boot instead of surfacing as a serving-path error hours later.

    @field_validator("model_name")
    @classmethod
    def _check_model_name(cls, v: str) -> str:
        if not v.strip():
            raise ValueError("MODEL_NAME must be non-empty")
        return v

    @field_validator("host")
    @classmethod
    def _check_host(cls, v: str) -> str:
        if not v.strip():
            raise ValueError("HOST must be non-empty")
        return v

    @field_validator("port")
    @classmethod
    def _check_port(cls, v: int) -> int:
        if not (1 <= v <= 65535):
            raise ValueError("PORT must be in [1, 65535]")
        return v

    @field_validator("max_queue", "pipeline_depth", "max_decode_len",
                     "stream_chunk_tokens", "max_streams",
                     "register_max_tries")
    @classmethod
    def _check_pos_int(cls, v: int) -> int:
        if v < 1:
            raise ValueError(
                "MAX_QUEUE/PIPELINE_DEPTH/MAX_DECODE_LEN/"
                "STREAM_CHUNK_TOKENS/MAX_STREAMS/REGISTER_MAX_TRIES "
                "must be >= 1"
            )
        return v

    @field_validator("replicas", "sp", "tp", "stream_pipeline",
                     "max_stream_queue", "fault_seed", "spec_max_streams")
    @classmethod
    def _check_nonneg_knob_int(cls, v: int) -> int:
        if v < 0:
            raise ValueError(
                "REPLICAS/SP/TP/STREAM_PIPELINE/MAX_STREAM_QUEUE/"
                "FAULT_SEED/SPEC_MAX_STREAMS must be >= 0 (0 = auto/off)"
            )
        return v

    @field_validator("batch_timeout_ms", "register_retry_s",
                     "register_heartbeat_s", "prefix_cache_mb",
                     "deadline_ms", "kv_budget_mb", "drain_grace_s")
    @classmethod
    def _check_nonneg_knob_float(cls, v: float) -> float:
        if v < 0:
            raise ValueError(
                "BATCH_TIMEOUT_MS/REGISTER_RETRY_S/REGISTER_HEARTBEAT_S/"
                "PREFIX_CACHE_MB/DEADLINE_MS/KV_BUDGET_MB/DRAIN_GRACE_S "
                "must be >= 0"
            )
        return v

    @field_validator("batch_buckets", "seq_buckets")
    @classmethod
    def _check_buckets(cls, v: tuple[int, ...]) -> tuple[int, ...]:
        if not v:
            raise ValueError("BATCH_BUCKETS/SEQ_BUCKETS must be non-empty")
        if any(b < 1 for b in v):
            raise ValueError("bucket sizes must be >= 1")
        if list(v) != sorted(set(v)):
            raise ValueError(
                "BATCH_BUCKETS/SEQ_BUCKETS must be strictly ascending "
                f"(got {v})"
            )
        return v

    @field_validator("log_level")
    @classmethod
    def _check_log_level(cls, v: str) -> str:
        if v.upper() not in ("DEBUG", "INFO", "WARNING", "ERROR",
                             "CRITICAL"):
            raise ValueError(
                f"LOG_LEVEL must be a standard logging level, got {v!r}"
            )
        return v

    @field_validator("quantize")
    @classmethod
    def _check_quantize(cls, v: str | None) -> str | None:
        if v is not None:
            v = v.lower()
            if v in ("", "none", "0", "false"):
                return None
            if v != "int8":
                raise ValueError(f"QUANTIZE must be 'int8' or unset, got {v!r}")
        return v

    @field_validator("quant_kv")
    @classmethod
    def _check_quant_kv(cls, v: str | None) -> str | None:
        if v is not None:
            v = v.lower()
            if v in ("", "none", "0", "false"):
                return None
            if v != "int8":
                raise ValueError(f"QUANT_KV must be 'int8' or unset, got {v!r}")
        return v

    @field_validator("spec_decode")
    @classmethod
    def _check_spec(cls, v: str | None) -> str | None:
        if v is not None:
            v = v.lower()
            if v in ("", "none", "off", "0", "false"):
                return None
            if v != "ngram":
                raise ValueError(
                    f"SPEC_DECODE must be 'ngram' or unset, got {v!r}"
                )
        return v

    @field_validator("spec_k")
    @classmethod
    def _check_spec_k(cls, v: int) -> int:
        if not (1 <= v <= 64):
            raise ValueError("SPEC_K must be in [1, 64]")
        return v

    @field_validator("spec_ngram")
    @classmethod
    def _check_spec_ngram(cls, v: int) -> int:
        if not (1 <= v <= 8):
            raise ValueError("SPEC_NGRAM must be in [1, 8]")
        return v

    @field_validator("device")
    @classmethod
    def _check_device(cls, v: str) -> str:
        v = v.lower()
        if v not in _VALID_DEVICES:
            raise ValueError(f"DEVICE must be one of {_VALID_DEVICES}, got {v!r}")
        return v

    @field_validator("max_batch")
    @classmethod
    def _check_max_batch(cls, v: int) -> int:
        if v < 1:
            raise ValueError("MAX_BATCH must be >= 1")
        return v

    @field_validator("priority_default")
    @classmethod
    def _check_priority_default(cls, v: str) -> str:
        v = v.lower()
        if v not in ("interactive", "batch"):
            raise ValueError(
                f"PRIORITY_DEFAULT must be 'interactive' or 'batch', got {v!r}"
            )
        return v

    @field_validator("class_weight")
    @classmethod
    def _check_class_weight(cls, v: int) -> int:
        if v < 1:
            raise ValueError("CLASS_WEIGHT must be >= 1")
        return v

    @field_validator("kv_block_size")
    @classmethod
    def _check_kv_block_size(cls, v: int) -> int:
        if not (1 <= v <= 1024):
            raise ValueError("KV_BLOCK_SIZE must be in [1, 1024]")
        return v

    @field_validator("pallas_variant")
    @classmethod
    def _check_pallas_variant(cls, v: str | None) -> str | None:
        if v:
            from ..ops.paged_attention import parse_variant

            parse_variant(v)  # ValueError with the grammar on junk
        return v

    @field_validator("pallas_single_block_max_seq")
    @classmethod
    def _check_pallas_single_block(cls, v: int) -> int:
        if not (64 <= v <= 8192):
            raise ValueError(
                "PALLAS_SINGLE_BLOCK_MAX_SEQ must be in [64, 8192] "
                "(whole-slab kernel: one grid block per sequence)"
            )
        return v

    @field_validator("decode_kernel_vmem_budget_mb")
    @classmethod
    def _check_decode_vmem_budget(cls, v: int) -> int:
        if not (1 <= v <= 256):
            raise ValueError(
                "DECODE_KERNEL_VMEM_BUDGET_MB must be in [1, 256] MB"
            )
        return v

    @field_validator("prefill_chunk", "prefill_budget", "prefill_max_prompt")
    @classmethod
    def _check_prefill(cls, v: int) -> int:
        if v < 0:
            raise ValueError(
                "PREFILL_CHUNK/PREFILL_BUDGET/PREFILL_MAX_PROMPT must be >= 0"
            )
        return v

    @field_validator("kv_host_budget_mb")
    @classmethod
    def _check_kv_host_budget(cls, v: float) -> float:
        if v < 0:
            raise ValueError("KV_HOST_BUDGET_MB must be >= 0")
        return v

    @field_validator("kv_disk_budget_mb")
    @classmethod
    def _check_kv_disk_budget(cls, v: float) -> float:
        if v < 0:
            raise ValueError("KV_DISK_BUDGET_MB must be >= 0")
        return v

    @field_validator("journal_fsync")
    @classmethod
    def _check_journal_fsync(cls, v: str) -> str:
        v = v.lower()
        if v not in ("always", "interval", "off"):
            raise ValueError(
                f"JOURNAL_FSYNC must be 'always', 'interval' or 'off', "
                f"got {v!r}"
            )
        return v

    @field_validator("job_max_concurrent_lines")
    @classmethod
    def _check_job_lines(cls, v: int) -> int:
        if not (1 <= v <= 256):
            raise ValueError("JOB_MAX_CONCURRENT_LINES must be in [1, 256]")
        return v

    @field_validator("job_result_ttl_s")
    @classmethod
    def _check_job_ttl(cls, v: float) -> float:
        if v < 0:
            raise ValueError("JOB_RESULT_TTL_S must be >= 0")
        return v

    @field_validator("tenant_default_weight", "tenant_window_s")
    @classmethod
    def _check_tenant_pos_float(cls, v: float) -> float:
        if v <= 0:
            raise ValueError(
                "TENANT_DEFAULT_WEIGHT/TENANT_WINDOW_S must be > 0"
            )
        return v

    @field_validator("tenant_metrics_topk")
    @classmethod
    def _check_tenant_topk(cls, v: int) -> int:
        if not (1 <= v <= 64):
            raise ValueError("TENANT_METRICS_TOPK must be in [1, 64]")
        return v

    @field_validator("adapter_slots")
    @classmethod
    def _check_adapter_slots(cls, v: int) -> int:
        if not (1 <= v <= 256):
            raise ValueError("ADAPTER_SLOTS must be in [1, 256]")
        return v

    @model_validator(mode="after")
    def _check_tenant_table(self):
        # Boot-validate the tenant table so garbage TENANTS /
        # TENANTS_FILE fails here, not as request-time surprises.
        # Lazy import: tenancy is jax-free but pulls numpy/metrics.
        if self.tenants or self.tenants_file:
            from ..tenancy.accounts import parse_tenants

            parse_tenants(self.tenants, self.tenants_file)
        return self

    @field_validator("kv_prefetch_blocks")
    @classmethod
    def _check_kv_prefetch(cls, v: int) -> int:
        if not (1 <= v <= 4096):
            raise ValueError("KV_PREFETCH_BLOCKS must be in [1, 4096]")
        return v

    @field_validator("fleet_replicas")
    @classmethod
    def _check_fleet_replicas(cls, v: int) -> int:
        if not (1 <= v <= 64):
            raise ValueError("FLEET_REPLICAS must be in [1, 64]")
        return v

    @field_validator("fleet_route")
    @classmethod
    def _check_fleet_route(cls, v: str) -> str:
        v = v.lower()
        if v not in ("least", "rr"):
            raise ValueError(f"FLEET_ROUTE must be 'least' or 'rr', got {v!r}")
        return v

    @field_validator("fleet_breaker_n")
    @classmethod
    def _check_fleet_breaker_n(cls, v: int) -> int:
        if v < 1:
            raise ValueError("FLEET_BREAKER_N must be >= 1")
        return v

    @field_validator("fleet_evict_s", "engine_restart_window_s")
    @classmethod
    def _check_fleet_nonneg(cls, v: float) -> float:
        if v < 0:
            raise ValueError(
                "FLEET_EVICT_S/ENGINE_RESTART_WINDOW_S must be >= 0"
            )
        return v

    @field_validator("fleet_tp_groups")
    @classmethod
    def _check_fleet_tp_groups(cls, v: str | None) -> str | None:
        if v is None or not str(v).strip():
            return None
        try:
            widths = [int(w) for w in str(v).split(",")]
        except ValueError:
            raise ValueError(
                f"FLEET_TP_GROUPS must be comma-separated integer TP "
                f"widths (e.g. '2,2,1'), got {v!r}"
            ) from None
        if not widths or any(not (1 <= w <= 64) for w in widths):
            raise ValueError(
                "FLEET_TP_GROUPS widths must each be in [1, 64]"
            )
        return ",".join(str(w) for w in widths)

    @field_validator("fleet_min_replicas", "fleet_max_replicas")
    @classmethod
    def _check_fleet_bounds_range(cls, v: int) -> int:
        if not (0 <= v <= 64):
            raise ValueError(
                "FLEET_MIN/MAX_REPLICAS must be in [0, 64] (0 = "
                "FLEET_REPLICAS)"
            )
        return v

    @field_validator("scale_up_queue", "scale_up_cooldown_s",
                     "scale_down_cooldown_s", "scale_up_ttft_ms")
    @classmethod
    def _check_scale_nonneg(cls, v: float) -> float:
        if v < 0:
            raise ValueError("SCALE_UP/DOWN_* thresholds must be >= 0")
        return v

    @field_validator("scale_up_kv_frac", "scale_down_load")
    @classmethod
    def _check_scale_frac(cls, v: float) -> float:
        if not (0.0 <= v <= 1.0):
            raise ValueError(
                "SCALE_UP_KV_FRAC/SCALE_DOWN_LOAD must be in [0, 1]"
            )
        return v

    @field_validator("scale_period_s")
    @classmethod
    def _check_scale_period(cls, v: float) -> float:
        if v <= 0:
            raise ValueError("SCALE_PERIOD_S must be > 0")
        return v

    @model_validator(mode="after")
    def _check_fleet_elastic_bounds(self):
        n = self.fleet_replicas
        mn = self.fleet_min_replicas or n
        mx = self.fleet_max_replicas or n
        if not (mn <= n <= mx):
            raise ValueError(
                f"elastic fleet bounds must satisfy FLEET_MIN_REPLICAS "
                f"<= FLEET_REPLICAS <= FLEET_MAX_REPLICAS, got "
                f"{mn} <= {n} <= {mx}"
            )
        return self

    @model_validator(mode="after")
    def _check_tp_knob(self):
        # Tensor-parallel serving (TP>1; docs/tensor-parallel.md).
        # Composition limits fail at config parse, not first trace:
        # QUANTIZE's {'q8','scale'} weight subtrees have no TP layout
        # (same contract the registry enforces — "TP and QUANTIZE"),
        # and SP/TP compose via a 3-D mesh this engine doesn't build.
        if self.tp > 1:
            if self.quantize:
                raise ValueError(
                    "TP and QUANTIZE cannot combine (quantized leaves "
                    "are {'q8','scale'} subtrees the TP param spec "
                    "cannot shard); pick one"
                )
            if self.sp > 1:
                raise ValueError(
                    "TP and SP cannot combine (a ('replica','sp','tp') "
                    "mesh is not built); pick one parallelism axis"
                )
        return self

    @model_validator(mode="after")
    def _align_paged_seq_buckets(self):
        # PAGED_KV: block-align the bucket grid at BUILD time instead
        # of rejecting unaligned grids (prefix sharing and table-span
        # writes need block-aligned bucket boundaries).  Rounding UP
        # never shrinks an admissible prompt; collapsing duplicates
        # keeps the grid strictly ascending.  Aligned grids (the
        # default 16-multiples) pass through byte-identical.
        if self.paged_kv and self.kv_block_size > 1:
            bs = self.kv_block_size
            aligned = tuple(sorted({-(-b // bs) * bs
                                    for b in self.seq_buckets}))
            if aligned != self.seq_buckets:
                self.seq_buckets = aligned
        return self

    @field_validator("fault_spec")
    @classmethod
    def _check_fault_spec(cls, v: str | None) -> str | None:
        # Grammar validation happens at engine construction (still
        # startup, before readiness) — engine/faults.py cannot be
        # imported here because this module must stay jax-free.
        if v is not None and v.strip().lower() in ("", "none", "off", "0"):
            return None
        return v

    @field_validator("dispatch_timeout_s", "dispatch_backoff_s")
    @classmethod
    def _check_nonneg_float(cls, v: float) -> float:
        if v < 0:
            raise ValueError("dispatch timeout/backoff must be >= 0")
        return v

    @field_validator("dispatch_retries", "engine_restarts_max")
    @classmethod
    def _check_nonneg_int(cls, v: int) -> int:
        if v < 0:
            raise ValueError("DISPATCH_RETRIES/ENGINE_RESTARTS_MAX must be >= 0")
        return v

    @field_validator("log_format")
    @classmethod
    def _check_log_format(cls, v: str) -> str:
        v = v.lower()
        if v not in ("text", "json"):
            raise ValueError(f"LOG_FORMAT must be 'text' or 'json', got {v!r}")
        return v

    @field_validator("trace_ring", "flight_ring")
    @classmethod
    def _check_ring(cls, v: int) -> int:
        if v < 0:
            raise ValueError("TRACE_RING/FLIGHT_RING must be >= 0")
        return v

    @field_validator("slo_ttft_ms", "slo_tbt_ms",
                     "slo_batch_ttft_ms", "slo_batch_tbt_ms",
                     "scale_up_slo_burn")
    @classmethod
    def _check_slo_nonneg(cls, v: float) -> float:
        if v < 0:
            raise ValueError(
                "SLO_TTFT_MS/SLO_TBT_MS/SLO_BATCH_TTFT_MS/"
                "SLO_BATCH_TBT_MS/SCALE_UP_SLO_BURN must be >= 0 "
                "(0 = off)"
            )
        return v

    @field_validator("slo_target")
    @classmethod
    def _check_slo_target(cls, v: float) -> float:
        if not (0.0 < v < 1.0):
            raise ValueError(
                "SLO_TARGET must be in (0, 1) — the error budget is "
                "1 - SLO_TARGET"
            )
        return v

    @field_validator("slo_windows_s")
    @classmethod
    def _check_slo_windows(cls, v: str) -> str:
        try:
            parts = [float(x) for x in v.split(",") if x.strip()]
        except ValueError:
            raise ValueError(
                f"SLO_WINDOWS_S must be 'fast,slow' seconds, got {v!r}"
            )
        if len(parts) != 2 or parts[0] <= 0 or parts[0] >= parts[1]:
            raise ValueError(
                "SLO_WINDOWS_S must be two ascending positive durations "
                f"'fast,slow', got {v!r}"
            )
        return v

    @field_validator("latency_buckets")
    @classmethod
    def _check_latency_buckets(cls, v: str | None) -> str | None:
        if v is None or not v.strip():
            return None
        from . import metrics as _metrics

        if _metrics.parse_buckets(v) is None:
            raise ValueError(
                "LATENCY_BUCKETS must be comma-separated strictly "
                f"ascending positive seconds, got {v!r}"
            )
        return v


def _env(name: str, default: str | None = None) -> str | None:
    v = os.environ.get(name)
    return v if v not in (None, "") else default


def load_config(env: dict[str, str] | None = None) -> ServiceConfig:
    """Build a ServiceConfig from environment variables.

    Recognized variables (reference-parity names first):
      DEVICE, MODEL_NAME, MODEL_PATH, TOKENIZER_PATH, HOST, PORT,
      MAX_BATCH, BATCH_TIMEOUT_MS, MAX_QUEUE, REPLICAS, SP, TP,
      MAX_DECODE_LEN, SERVER_URL, WARMUP, LOG_LEVEL, PIPELINE_DEPTH,
      MAX_STREAMS, BATCH_BUCKETS, SEQ_BUCKETS, QUANTIZE,
      REGISTER_HEARTBEAT_S, CONTINUOUS_BATCHING, PROMPT_PREFIX,
      SPEC_DECODE, SPEC_K, SPEC_NGRAM, PRIORITY_DEFAULT, DEADLINE_MS,
      CLASS_WEIGHT, KV_BUDGET_MB, MAX_STREAM_QUEUE, PREEMPT,
      DRAIN_GRACE_S, PAGED_KV, KV_BLOCK_SIZE, KV_HOST_BUDGET_MB,
      KV_DISK_BUDGET_MB, JOURNAL_DIR, JOURNAL_FSYNC,
      KV_PREFETCH_BLOCKS, JOBS_ENABLED, JOB_MAX_CONCURRENT_LINES,
      JOB_RESULT_TTL_S, TENANTS, TENANTS_FILE, TENANT_DEFAULT_WEIGHT,
      TENANT_WINDOW_S, TENANT_METRICS_TOPK, ADAPTER_DIR,
      ADAPTER_SLOTS, PREFILL_CHUNK,
      PREFILL_BUDGET, PREFILL_MAX_PROMPT, FAULT_SPEC, FAULT_SEED,
      DISPATCH_TIMEOUT_S, DISPATCH_RETRIES, DISPATCH_BACKOFF_S,
      ENGINE_RESTARTS_MAX, ENGINE_RESTART_WINDOW_S, SUPERVISE,
      FLEET_REPLICAS, FLEET_ROUTE, FLEET_BREAKER_N, FLEET_EVICT_S,
      FLEET_TP_GROUPS,
      FLEET_MIN_REPLICAS, FLEET_MAX_REPLICAS, SCALE_UP_QUEUE,
      SCALE_UP_KV_FRAC, SCALE_UP_TTFT_MS, SCALE_UP_COOLDOWN_S,
      SCALE_DOWN_LOAD, SCALE_DOWN_COOLDOWN_S, SCALE_PERIOD_S,
      TRACE, TRACE_RING, FLIGHT_RING, PROFILE_DIR, LOG_FORMAT,
      COMPILE_CACHE_DIR,
      LATENCY_BUCKETS, SLO_TTFT_MS, SLO_TBT_MS, SLO_BATCH_TTFT_MS,
      SLO_BATCH_TBT_MS, SLO_TARGET, SLO_WINDOWS_S, SCALE_UP_SLO_BURN.
    """
    e = dict(os.environ)
    if env:
        e.update(env)

    def get(name: str, default: str | None = None) -> str | None:
        v = e.get(name)
        return v if v not in (None, "") else default

    kwargs: dict = {}
    mapping = {
        "device": "DEVICE",
        "model_name": "MODEL_NAME",
        "model_path": "MODEL_PATH",
        "tokenizer_path": "TOKENIZER_PATH",
        "host": "HOST",
        "server_url": "SERVER_URL",
        "log_level": "LOG_LEVEL",
        "quantize": "QUANTIZE",
        "quant_kv": "QUANT_KV",
        "prompt_prefix": "PROMPT_PREFIX",
        "spec_decode": "SPEC_DECODE",
        "priority_default": "PRIORITY_DEFAULT",
        "fleet_route": "FLEET_ROUTE",
        "fleet_tp_groups": "FLEET_TP_GROUPS",
        "fault_spec": "FAULT_SPEC",
        "log_format": "LOG_FORMAT",
        "profile_dir": "PROFILE_DIR",
        "journal_dir": "JOURNAL_DIR",
        "journal_fsync": "JOURNAL_FSYNC",
        "tenants": "TENANTS",
        "tenants_file": "TENANTS_FILE",
        "adapter_dir": "ADAPTER_DIR",
        "compile_cache_dir": "COMPILE_CACHE_DIR",
        "latency_buckets": "LATENCY_BUCKETS",
        "slo_windows_s": "SLO_WINDOWS_S",
        "pallas_variant": "PALLAS_VARIANT",
    }
    for field, var in mapping.items():
        v = get(var)
        if v is not None:
            kwargs[field] = v
    int_mapping = {
        "port": "PORT",
        "max_batch": "MAX_BATCH",
        "max_queue": "MAX_QUEUE",
        "replicas": "REPLICAS",
        "sp": "SP",
        "tp": "TP",
        "max_decode_len": "MAX_DECODE_LEN",
        "pipeline_depth": "PIPELINE_DEPTH",
        "max_streams": "MAX_STREAMS",
        "spec_k": "SPEC_K",
        "spec_ngram": "SPEC_NGRAM",
        "spec_max_streams": "SPEC_MAX_STREAMS",
        "stream_pipeline": "STREAM_PIPELINE",
        "class_weight": "CLASS_WEIGHT",
        "max_stream_queue": "MAX_STREAM_QUEUE",
        "kv_block_size": "KV_BLOCK_SIZE",
        "kv_prefetch_blocks": "KV_PREFETCH_BLOCKS",
        "job_max_concurrent_lines": "JOB_MAX_CONCURRENT_LINES",
        "tenant_metrics_topk": "TENANT_METRICS_TOPK",
        "adapter_slots": "ADAPTER_SLOTS",
        "prefill_chunk": "PREFILL_CHUNK",
        "prefill_budget": "PREFILL_BUDGET",
        "prefill_max_prompt": "PREFILL_MAX_PROMPT",
        "fleet_min_replicas": "FLEET_MIN_REPLICAS",
        "fleet_max_replicas": "FLEET_MAX_REPLICAS",
        "fault_seed": "FAULT_SEED",
        "dispatch_retries": "DISPATCH_RETRIES",
        "engine_restarts_max": "ENGINE_RESTARTS_MAX",
        "fleet_replicas": "FLEET_REPLICAS",
        "fleet_breaker_n": "FLEET_BREAKER_N",
        "trace_ring": "TRACE_RING",
        "flight_ring": "FLIGHT_RING",
        "pallas_single_block_max_seq": "PALLAS_SINGLE_BLOCK_MAX_SEQ",
        "decode_kernel_vmem_budget_mb": "DECODE_KERNEL_VMEM_BUDGET_MB",
    }
    for field, var in int_mapping.items():
        v = get(var)
        if v is not None:
            kwargs[field] = int(v)
    v = get("BATCH_TIMEOUT_MS")
    if v is not None:
        kwargs["batch_timeout_ms"] = float(v)
    v = get("REGISTER_HEARTBEAT_S")
    if v is not None:
        kwargs["register_heartbeat_s"] = float(v)
    for field, var in (
        ("deadline_ms", "DEADLINE_MS"),
        ("kv_budget_mb", "KV_BUDGET_MB"),
        ("kv_host_budget_mb", "KV_HOST_BUDGET_MB"),
        ("kv_disk_budget_mb", "KV_DISK_BUDGET_MB"),
        ("job_result_ttl_s", "JOB_RESULT_TTL_S"),
        ("tenant_default_weight", "TENANT_DEFAULT_WEIGHT"),
        ("tenant_window_s", "TENANT_WINDOW_S"),
        ("drain_grace_s", "DRAIN_GRACE_S"),
        ("dispatch_timeout_s", "DISPATCH_TIMEOUT_S"),
        ("dispatch_backoff_s", "DISPATCH_BACKOFF_S"),
        ("fleet_evict_s", "FLEET_EVICT_S"),
        ("scale_up_queue", "SCALE_UP_QUEUE"),
        ("scale_up_kv_frac", "SCALE_UP_KV_FRAC"),
        ("scale_up_ttft_ms", "SCALE_UP_TTFT_MS"),
        ("scale_up_cooldown_s", "SCALE_UP_COOLDOWN_S"),
        ("scale_down_load", "SCALE_DOWN_LOAD"),
        ("scale_down_cooldown_s", "SCALE_DOWN_COOLDOWN_S"),
        ("scale_period_s", "SCALE_PERIOD_S"),
        ("engine_restart_window_s", "ENGINE_RESTART_WINDOW_S"),
        ("slo_ttft_ms", "SLO_TTFT_MS"),
        ("slo_tbt_ms", "SLO_TBT_MS"),
        ("slo_batch_ttft_ms", "SLO_BATCH_TTFT_MS"),
        ("slo_batch_tbt_ms", "SLO_BATCH_TBT_MS"),
        ("slo_target", "SLO_TARGET"),
        ("scale_up_slo_burn", "SCALE_UP_SLO_BURN"),
    ):
        v = get(var)
        if v is not None:
            kwargs[field] = float(v)
    v = get("PREEMPT")
    if v is not None:
        kwargs["preempt"] = v.lower() not in ("0", "false", "no")
    v = get("PAGED_KV")
    if v is not None:
        kwargs["paged_kv"] = v.lower() not in ("0", "false", "no")
    v = get("PALLAS_AUTOTUNE")
    if v is not None:
        kwargs["pallas_autotune"] = v.lower() not in ("0", "false", "no")
    v = get("PALLAS_INTERPRET")
    if v is not None:
        kwargs["pallas_interpret"] = v.lower() not in ("0", "false", "no")
    v = get("JOBS_ENABLED")
    if v is not None:
        kwargs["jobs_enabled"] = v.lower() not in ("0", "false", "no")
    v = get("SUPERVISE")
    if v is not None:
        kwargs["supervise"] = v.lower() not in ("0", "false", "no")
    v = get("TRACE")
    if v is not None:
        kwargs["trace"] = v.lower() not in ("0", "false", "no")
    # Comma-separated bucket overrides, e.g. BATCH_BUCKETS=1,8,32 — used
    # to bound warmup compile time when only some shapes will be served.
    for field, var in (("batch_buckets", "BATCH_BUCKETS"), ("seq_buckets", "SEQ_BUCKETS")):
        v = get(var)
        if v is not None:
            buckets = tuple(int(x) for x in v.split(",") if x.strip())
            if not buckets:
                raise ValueError(f"{var}={v!r} parsed to no buckets")
            kwargs[field] = buckets
    v = get("WARMUP")
    if v is not None:
        kwargs["warmup"] = v.lower() not in ("0", "false", "no")
    v = get("CONTINUOUS_BATCHING")
    if v is not None:
        kwargs["continuous_batching"] = v.lower() not in ("0", "false", "no")
    v = get("PREFIX_CACHE")
    if v is not None:
        kwargs["prefix_cache"] = v.lower() not in ("0", "false", "no")
    v = get("SPEC_SAMPLED")
    if v is not None:
        kwargs["spec_sampled"] = v.lower() not in ("0", "false", "no")
    v = get("SPEC_CONTINUOUS")
    if v is not None:
        kwargs["spec_continuous"] = v.lower() not in ("0", "false", "no")
    v = get("PREFIX_CACHE_MB")
    if v is not None:
        kwargs["prefix_cache_mb"] = float(v)
    return ServiceConfig(**kwargs)
