"""Prometheus observability (SURVEY.md §5 "Metrics / logging").

The reference template's only introspection is its ``/status`` endpoint
and access logs; this module is the deliberate upgrade: request
count/latency histograms, batch-size distribution (the lever behind
req/s/chip), queue depth, and generated-token throughput, all exported
at ``GET /metrics``.

Kept import-safe without prometheus_client (stub fallback) so the core
serving path never gains a hard dependency.
"""

from __future__ import annotations

import os
import weakref

try:
    from prometheus_client import (
        CONTENT_TYPE_LATEST,
        Counter,
        Gauge,
        Histogram,
        generate_latest,
    )

    HAVE_PROM = True
except Exception:  # pragma: no cover - prometheus_client is installed here
    HAVE_PROM = False
    CONTENT_TYPE_LATEST = "text/plain"

    class _Noop:
        def labels(self, *a, **k):
            return self

        def inc(self, *a, **k):
            pass

        def observe(self, *a, **k):
            pass

        def set(self, *a, **k):
            pass

    def Counter(*a, **k):  # noqa: N802
        return _Noop()

    Gauge = Histogram = Counter

    def generate_latest():
        return b"# prometheus_client not installed\n"


# Latency histogram buckets (r20, the r11 honest negative closed):
# the defaults extend past 10 s — on the 1-vCPU CI box,
# stream_ttft/tbt p99 saturated the old 10 s top bucket and
# hist_pctile could only report "≥ 10 s".  The LATENCY_BUCKETS env
# knob overrides the whole set (comma-separated ascending seconds,
# validated strictly in ServiceConfig; parsed leniently here because
# metrics imports before config validation and a bad env var must
# not break `import metrics` for a test process).
_DEFAULT_LATENCY_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
    5.0, 10.0, 30.0, 60.0, 120.0,
)


def parse_buckets(spec: str | None) -> tuple[float, ...] | None:
    """Comma-separated ascending positive bucket edges, or None when
    unset/invalid (callers fall back to the defaults; ServiceConfig's
    validator is the strict gate that rejects garbage at boot)."""
    if not spec:
        return None
    try:
        vals = tuple(float(x) for x in spec.split(",") if x.strip())
    except ValueError:
        return None
    if not vals or any(v <= 0 for v in vals) or list(vals) != sorted(
        set(vals)
    ):
        return None
    return vals


_LATENCY_BUCKETS = (
    parse_buckets(os.environ.get("LATENCY_BUCKETS"))
    or _DEFAULT_LATENCY_BUCKETS
)

REQUESTS = Counter(
    "predict_requests_total", "Completed /predict requests", ["model", "status"]
)
LATENCY = Histogram(
    "predict_latency_seconds", "End-to-end /predict latency", ["model"],
    buckets=_LATENCY_BUCKETS,
)
QUEUE_WAIT = Histogram(
    "batch_queue_wait_seconds", "Time a request waits in the batching queue",
    ["model"], buckets=_LATENCY_BUCKETS,
)
DEVICE_TIME = Histogram(
    "device_batch_seconds", "Device time per dispatched batch", ["model"],
    buckets=_LATENCY_BUCKETS,
)
BATCH_SIZE = Histogram(
    "batch_size", "Items per dispatched batch", ["model"],
    buckets=(1, 2, 4, 8, 16, 32, 64),
)
QUEUE_DEPTH = Gauge("batch_queue_depth", "Requests currently queued", ["model"])
TOKENS = Counter("generated_tokens_total", "Seq2seq tokens generated", ["model"])
STREAM_BATCH = Histogram(
    "stream_batch_size",
    "Live streams served per continuous-batching chunk dispatch",
    ["model"], buckets=(1, 2, 4, 8, 16, 32, 64, 128),
)
STREAM_QUEUE_WAIT = Histogram(
    "stream_queue_wait_seconds",
    "Seconds a stream waited in the decode loop's queue: (re-)queued "
    "to the reservation that puts it in an admission wave (one "
    "observation per reservation, so a checkpoint resume counts again)",
    ["model"], buckets=_LATENCY_BUCKETS,
)
STREAM_ADMIT = Histogram(
    "stream_admit_seconds",
    "Seconds from a stream's reservation to its first emitted chunk: "
    "the prefill wave, its fetch, its insert dispatch and the stream's "
    "place in the wave's emit order",
    ["model"], buckets=_LATENCY_BUCKETS,
)
STREAM_API = Histogram(
    "stream_api_seconds",
    "Seconds from a streaming request's handler entry to its stream's "
    "first place in the decode loop's queue: the body, its parse, the "
    "executor's queue, the tokenizer, admission (first stage of "
    "stream_ttft_seconds = stream_api + stream_queue_wait + "
    "stream_admit + stream_handoff for a stream admitted once)",
    ["model"], buckets=_LATENCY_BUCKETS,
)
STREAM_HANDOFF = Histogram(
    "stream_handoff_seconds",
    "Seconds from the decode loop thread's first emit of a stream to "
    "the point where the API observes stream_ttft_seconds: the "
    "call_soon_threadsafe hop, the event loop's turn, detokenize (last "
    "stage of stream_ttft_seconds)",
    ["model"], buckets=_LATENCY_BUCKETS,
)
IDLE_ADMIT_ROWS = Counter(
    "idle_admit_rows_total",
    "Rows the idle-admission waits added to their waves beyond the "
    "rows that were there when the wait began",
    ["model"],
)
IDLE_ADMIT_CAPPED = Counter(
    "idle_admit_capped_total",
    "Idle-admission waits that ended on their cap (what the wave costs) "
    "with a request still announced",
    ["model"],
)
WAVES_BEHIND_CHUNKS = Counter(
    "stream_waves_behind_chunks_total",
    "Admission waves whose prefill was dispatched beside decode chunks "
    "in flight (a wave on an idle loop is not counted)",
    ["model"],
)
CHUNKS_AHEAD_OF_WAVE = Counter(
    "stream_chunks_ahead_of_wave_total",
    "In-flight decode chunks delivered, oldest first and each in a "
    "fetch of its own, ahead of a wave's fetch: over "
    "stream_waves_behind_chunks_total, the chunks a wave (about the "
    "chain depth where admissions meet live streams)",
    ["model"],
)
WAVES_AHEAD_OF_CHUNK = Counter(
    "stream_waves_ahead_of_chunk_total",
    "Admission waves whose start was dispatched ahead of their "
    "iteration's decode chunk, which stays in flight across the wave's "
    "fetch and insert (a wave on an idle loop, or one that is all "
    "prompt windows, is not counted)",
    ["model"],
)
PREFILL_WAVE_FILL = Histogram(
    "prefill_wave_fill",
    "Useful share of one prefill executable run: real prompt tokens "
    "of the wave / (rows x bucket length) the executable ran",
    ["model"],
    buckets=(0.01, 0.02, 0.05, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8,
             0.9, 1.0),
)
PREFILL_WAVE_ROWS = Histogram(
    "prefill_wave_rows",
    "Rows one prefill executable ran (a lone admission 1, a wave the "
    "smallest rung that holds it: 4 or the slot count)",
    ["model"], buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256),
)
STREAM_INSERT_ROWS = Histogram(
    "stream_insert_rows",
    "Rows one paged insert dispatch landed in their slots (a wave's rows "
    "that neither finished in their first chunk nor were re-queued)",
    ["model"], buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256),
)
MOE_LOAD_IMBALANCE = Histogram(
    "moe_load_imbalance",
    "Expert FFN: the busiest expert's share of a LAYER's assignments "
    "in one delivered paged decode chunk over the mean share (1.0 = "
    "even), summed over the chunk's steps, a mean over the layers",
    ["model"],
    buckets=(1.0, 1.1, 1.2, 1.35, 1.5, 1.75, 2.0, 2.5, 3.0, 4.0, 6.0, 8.0,
             16.0, 32.0, 64.0),
)
MOE_ASSIGNMENTS = Counter(
    "moe_assignments_total",
    "Expert FFN: (token, expert) assignments computed by delivered "
    "paged decode chunks (rows still decoding x layers x "
    "experts_per_token x steps)",
    ["model"],
)
MOE_ASSIGNMENTS_HELD = Counter(
    "moe_assignments_held_total",
    "Expert FFN: the assignments of moe_assignments_total that landed on "
    "an expert this tree holds (all of them unless the model holds a "
    "chip's share of its experts: LLAMA_CONFIG experts_held)",
    ["model"],
)
MOE_ASSIGNMENTS_ABSENT = Counter(
    "moe_assignments_absent_total",
    "Expert FFN: the assignments that landed on an expert held elsewhere "
    "— counted, computed nowhere: what an expert-parallel exchange would "
    "carry off this chip",
    ["model"],
)
MOE_ROWS = Counter(
    "moe_rows_total",
    "Expert FFN: assignment rows (tokens x experts_per_token, an expert "
    "layer a call) the row work around the grouped matmuls — gather, "
    "activation, mask, combine — ran over, and rows of its calls it "
    "skipped because this chip's held assignments fit a lower rung "
    "(ops/moe.row_rungs), by step kind: decode (delivered paged chunks) "
    "| prefill (prompt-window dispatches of a chip's share of the "
    "experts), from each call's own counts",
    ["model", "kind", "state"],
)
MOE_ROWS_FUSED = Counter(
    "moe_rows_fused_total",
    "Expert FFN: held assignment rows of the calls whose two row shuffles "
    "— rows into expert order, a token's rows back weighted and summed — "
    "took the DMA kernels (ops/moe.row_kernels_fit, a rule on the call's "
    "static shape; a call it leaves to XLA adds nothing), by step kind as "
    "moe_rows_total, from each call's own counts",
    ["model", "kind"],
)
SSM_STATE_BYTES = Gauge(
    "ssm_state_bytes",
    "Recurrent layers: bytes of recurrent state (a layer's float32 state "
    "and its convolution taps, every such layer) held by streams that "
    "have a state row — decoding or mid-prefill; a fixed size a stream, "
    "beside the paged KV that grows a token at a time",
    ["model"],
)
KV_WINDOW_STORE_BYTES = Gauge(
    "kv_window_store_bytes",
    "Window layers whose store is a ring a stream (LLAMA_CONFIG "
    "window_ring): bytes of window keys and values held by streams that "
    "have a state row — every ring layer's K and V ring; a fixed size a "
    "stream whatever its context, beside ssm_state_bytes (the recurrent "
    "rows) and the paged pool (kv_pool_blocks x the block's bytes: the "
    "layers that keep every key)",
    ["model"],
)
KV_WINDOW_KEYS_OVERWRITTEN = Counter(
    "kv_window_keys_overwritten_total",
    "Window layers whose store is a ring: keys a ring overwrote, a ring "
    "layer each — every position written at or past window_ring lands on "
    "the key window_ring before it (what a block table keeps allocated "
    "behind the window), from the host's own stream lengths, prompt "
    "windows and delivered decode chunks alike",
    ["model"],
)
PREFILL_SELF_POSITIONS = Counter(
    "prefill_self_positions_total",
    "A model with a cross-decoder (layer_types 'cross' / 'gmu'): real "
    "prompt positions run through the self-decoder's layers by prompt-"
    "window and prefill-wave dispatches",
    ["model"],
)
PREFILL_CROSS_POSITIONS = Counter(
    "prefill_cross_positions_total",
    "A model with a cross-decoder: prompt positions run through the "
    "cross-decoder's layers — one a prompt, its last, by the first decode "
    "step of the stream that went live; a window or a wave runs none (over "
    "prefill_self_positions_total: the split's share of a prompt)",
    ["model"],
)
SSM_STATE_ROWS = Gauge(
    "ssm_state_rows",
    "Recurrent layers: rows of the recurrent state by what holds them: "
    "live (a stream decoding in a slot), prefill (a prompt between its "
    "first window and going live), free",
    ["model", "state"],
)
SSM_SCAN_TOKENS = Counter(
    "ssm_scan_tokens_total",
    "Recurrent layers: token positions the chunked scan ran over in "
    "prompt-window and prefill-wave dispatches (rows x width, padding "
    "and filled-up rows included)",
    ["model"],
)
SSM_SCAN_MASKED = Counter(
    "ssm_scan_masked_tokens_total",
    "Recurrent layers: the positions of ssm_scan_tokens_total that were "
    "padding or a filled-up row of a batched dispatch: scanned, moving "
    "no state",
    ["model"],
)
SSM_SCAN_FUSED = Counter(
    "ssm_scan_fused_tokens_total",
    "Recurrent layers: the positions of ssm_scan_tokens_total whose scan "
    "took its fused kernel (ops/ssm.py: the decode step runs its kernels "
    "and the shapes are whole tiles of the chip; LlamaConfig.scan_fused): "
    "all of them or none, by the loaded configuration",
    ["model"],
)
SSM_STATE_RECOMPUTES = Counter(
    "ssm_state_recomputes_total",
    "Recurrent layers: recurrent states rebuilt by recomputing a "
    "stream's prompt and the tokens it had emitted (a preempted, "
    "failed-over or replayed stream taking a state row again: no tier "
    "carries a state row)",
    ["model"],
)
MOE_EXPERTS_HIT = Gauge(
    "moe_experts_hit",
    "Expert FFN: distinct experts of a layer (of those this tree holds) "
    "with at least one assignment in the last delivered paged decode "
    "chunk, a mean over the layers",
    ["model"],
)
KV_WINDOW_KEYS_READ = Counter(
    "kv_window_keys_read_total",
    "Window attention: keys the window layers of a per-layer pattern "
    "read in dispatched paged decode chunks (min(context, window) a "
    "stream a step a window layer; from the host's stream lengths)",
    ["model"],
)
KV_WINDOW_KEYS_BEHIND = Counter(
    "kv_window_keys_behind_total",
    "Window attention: keys of live context BEHIND the window that the "
    "window layers did not read (context - window a stream a step a "
    "window layer): what the table view saves over walking the table",
    ["model"],
)
KV_LATENT_KEYS_READ = Counter(
    "kv_latent_keys_read_total",
    "Paged decode over a latent cache (attention='mla'): cached latent "
    "rows read in dispatched paged decode chunks (a stream's context a "
    "step a layer, each row once; from the host's stream lengths)",
    ["model"],
)
KV_TABLE_BLOCKS_LIVE = Counter(
    "kv_table_blocks_live_total",
    "Paged decode: block-table entries that held a key an attention "
    "layer attended to in dispatched paged decode chunks (a stream a "
    "step a layer; through the table view for a window layer; from the "
    "host's stream lengths): what the paged kernel has to read",
    ["model"],
)
KV_TABLE_BLOCKS_DEAD = Counter(
    "kv_table_blocks_dead_total",
    "Paged decode: the other entries of the slots' tables (or views) in "
    "the same chunks — past a stream's last key, before a window, or a "
    "slot with no stream: dead / (live + dead) is the share of a walk "
    "of the table's width that the kernel's live bounds never run",
    ["model"],
)
PREFILL_KEY_TILES_LIVE = Counter(
    "prefill_key_tiles_live_total",
    "Chunked paged prefill through the prompt-window kernel: (query "
    "tile, key tile) pairs that held a key some query of the tile sees, "
    "a KV head of every layer of a dispatched window together (from the "
    "window's start, its real tokens and the layers' kinds): what the "
    "kernel's key loop runs",
    ["model"],
)
PREFILL_KEY_TILES_DEAD = Counter(
    "prefill_key_tiles_dead_total",
    "Chunked paged prefill: the other pairs of the same windows' "
    "queries x gathered keys rectangles — above the diagonal, behind a "
    "window layer's band, past the window's last real token: dead / "
    "(live + dead) is the share of the rectangle the kernel never runs",
    ["model"],
)
DECODE_STEPS = Histogram(
    "seq2seq_decode_steps",
    "Decode steps executed per non-streaming seq2seq dispatch "
    "(< max_decode_len when the whole batch hit EOS early)",
    ["model"], buckets=(4, 8, 16, 32, 64, 128, 256),
)
SPEC_EMITTED = Histogram(
    "spec_tokens_per_verify_step",
    "Speculative decoding: tokens emitted per verify step (1.0 = no "
    "draft accepted; the acceptance-rate observability surface)",
    ["model"], buckets=(1.0, 1.5, 2.0, 3.0, 4.0, 6.0, 9.0),
)
SHED = Counter(
    "requests_shed_total",
    "Load-shed requests by reason "
    "(queue_full | deadline | kv_budget | drain | degraded | "
    "fleet_down | quota | adapter_pool)",
    ["model", "reason"],
)
TTFT = Histogram(
    "stream_ttft_seconds",
    "Streaming time-to-first-token-chunk (submit to first event), by "
    "admission mode (chunked = PREFILL_CHUNK windows, monolithic = "
    "one fused prefill dispatch)",
    ["model", "mode"], buckets=_LATENCY_BUCKETS,
)
PREFILL_CHUNKS = Counter(
    "prefill_chunks_total",
    "Prompt windows dispatched by chunked prefill (PREFILL_CHUNK)",
    ["model"],
)
PREFILL_WINDOWS_BATCHED = Counter(
    "prefill_windows_batched_total",
    "Prompt windows that shared their prefill dispatch with at least one "
    "other prompt's window (PREFILL_BUDGET admits several PREFILL_CHUNK "
    "windows a chunk boundary: one batched dispatch, the experts and "
    "the other weights streamed once for all of them)",
    ["model"],
)
PREFILL_WINDOWS_ALONE = Counter(
    "prefill_windows_alone_total",
    "Prompt windows that were their prefill dispatch's only one: "
    "batched / (batched + alone) is how often the batched dispatch "
    "engages",
    ["model"],
)
PREFILL_STALL = Counter(
    "prefill_stall_seconds",
    "Host seconds in which the decode loop could dispatch no decode "
    "chunk while streams were live, because it was admitting: a "
    "monolithic prefill wave from its dispatch to the end of its "
    "emit/insert loop (the loop thread waits for the wave's fetch), "
    "plus the time spent dispatching PREFILL_CHUNK windows",
    ["model"],
)
PREFILL_BACKLOG = Gauge(
    "prefill_backlog_tokens",
    "Prompt tokens admitted but not yet prefilled (chunked backlog)",
    ["model"],
)
CLASS_QUEUE_DEPTH = Gauge(
    "sched_class_queue_depth",
    "Requests waiting in the deadline queue, by queue and priority class",
    ["model", "queue", "klass"],
)
PREEMPTIONS = Counter(
    "stream_preemptions_total",
    "Batch-class streams checkpointed and re-queued to admit "
    "interactive work",
    ["model"],
)
KV_COMMITTED = Gauge(
    "kv_committed_bytes",
    "KV-cache bytes currently committed against the admission budget, "
    "per fleet replica (replica 0 = the single-engine path)",
    ["model", "replica"],
)
KV_POOL_BLOCKS = Gauge(
    "kv_pool_blocks",
    "Paged-KV pool blocks by state (used includes prefix-cache pins), "
    "per fleet replica",
    ["model", "replica", "state"],
)
ENGINE_RESTARTS = Counter(
    "engine_restarts_total",
    "Supervised engine rebuilds after a fatal dispatch fault or decode "
    "loop death (streams checkpoint and resume token-identically)",
    ["model"],
)
DISPATCH_RETRIES = Counter(
    "dispatch_retries_total",
    "Transient dispatch failures retried under the watchdog, by "
    "exception type",
    ["model", "reason"],
)
DISPATCH_TIMEOUTS = Counter(
    "dispatch_timeouts_total",
    "Dispatches cut off by the DISPATCH_TIMEOUT_S watchdog deadline",
    ["model"],
)
STREAMS_RECOVERED = Counter(
    "streams_recovered_total",
    "Live streams checkpointed and resumed token-identically, by "
    "replica and cause (restart = same-engine rebuild, failover = "
    "re-routed to a healthy fleet replica)",
    ["model", "replica", "cause"],
)
STREAMS_LOST = Counter(
    "streams_lost_total",
    "Live streams error-terminated by an unrecoverable engine fault, "
    "by replica and cause (fault = no supervisor or budget spent, "
    "no_replica = every fleet replica was dead at failover)",
    ["model", "replica", "cause"],
)
FLEET_FAILOVERS = Counter(
    "fleet_failovers_total",
    "Replica evacuations: a replica died (restart budget spent, loop "
    "death, or breaker open past FLEET_EVICT_S) and its streams were "
    "re-routed for token-identical resume",
    ["model", "replica", "cause"],
)
FLEET_REPLICAS = Gauge(
    "fleet_replicas",
    "Fleet members by state: live (healthy-or-breaker-open, routable "
    "pool), draining (scale-down in progress — finishing or evacuating "
    "its streams), evicted (dead, awaiting rejoin), spawning (being "
    "built/warmed/probed; not yet admitted to routing)",
    ["model", "state"],
)
FLEET_SCALE_EVENTS = Counter(
    "fleet_scale_events_total",
    "Completed fleet scale events by direction and cause (up: queue | "
    "kv | ttft | slo | min | rejoin | manual, spawn_failed when the "
    "warm probe died, no_devices when no free device group could seat "
    "the spawn; down: idle | manual)",
    ["model", "dir", "cause"],
)
FLEET_SCALE_DURATION = Histogram(
    "fleet_scale_duration_seconds",
    "Wall time one scale event took (up: engine build + donor param "
    "broadcast + warm compile + probe dispatch; down: drain-or-"
    "evacuate + retire)",
    ["model", "dir"],
    buckets=(0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0),
)
FLEET_BREAKER = Gauge(
    "fleet_breaker_state",
    "Per-replica circuit breaker state: 0=closed (healthy), "
    "1=half-open (probing), 2=open (routing avoids it), 3=dead "
    "(evicted; streams failed over)",
    ["model", "replica"],
)
FLEET_PARAM_BROADCAST = Counter(
    "fleet_param_broadcast_bytes_total",
    "Real param bytes moved device-to-device by donor broadcasts at "
    "spawn (params_source=donor-ici). Same-placement spawns alias the "
    "donor's arrays and add ZERO here — the honest-transport ledger "
    "for multi-chip scale-up (docs/autoscaling.md)",
    ["model"],
)
FLEET_REPLICA_DEVICES = Gauge(
    "fleet_replica_devices",
    "Devices owned by each fleet replica's placement (TP group width; "
    "1 for single-device replicas; 0 once the replica is dead and its "
    "devices are released or retired)",
    ["model", "replica"],
)
CHAIN_DEPTH = Gauge(
    "stream_chain_depth",
    "Chunk-chain pipelining depth the continuous decode loop runs at "
    "(STREAM_PIPELINE; auto-tuned at warmup from measured dispatch RTT "
    "vs chunk compute when 0)",
    ["model"],
)
KV_HOST_POOL_BLOCKS = Gauge(
    "kv_host_pool_blocks",
    "Host-RAM KV tier blocks by state (KV_HOST_BUDGET_MB; used = "
    "swapped-out stream checkpoints + demoted prefix entries)",
    ["model", "state"],
)
KV_SWAP_BYTES = Counter(
    "kv_swap_bytes_total",
    "KV bytes moved across the device/host tier boundary, by direction "
    "(out = checkpoint swap-out + prefix demotion, in = resume "
    "prefetch + prefix promotion)",
    ["model", "dir"],
)
KV_SWAP_RESUMES = Counter(
    "kv_swap_resumes_total",
    "Checkpointed-stream resumes by outcome: swapped = KV prefetched "
    "from the host tier (zero re-prefill), fallback = host copy "
    "missing/evicted/foreign so the stream re-prefilled (recast or "
    "replay)",
    ["model", "outcome"],
)
KV_HOST_PREFIX_HITS = Counter(
    "kv_host_prefix_hits_total",
    "Prefix-cache matches served from the host tier: the entry was "
    "demoted under device-budget pressure and promoted back on match",
    ["model"],
)
JOURNAL_RECORDS = Counter(
    "journal_records_total",
    "Write-ahead stream-journal records appended, by kind (admit = "
    "stream admission, tokens = delivered-token cursor delta, done = "
    "terminal, result = unary /predict completion for X-Request-Id "
    "dedup)",
    ["model", "kind"],
)
JOURNAL_REPLAY = Counter(
    "journal_replay_streams_total",
    "Journaled streams processed at startup replay, by outcome "
    "(resumed = re-admitted for token-identical continuation, "
    "complete = already finished before the crash, failed = could not "
    "re-admit)",
    ["model", "outcome"],
)
JOBS_ACTIVE = Gauge(
    "jobs_active",
    "Bulk /v1/batches jobs with a live executor task (JOBS_ENABLED; "
    "their lines backfill idle compute as batch-class streams)",
    ["model"],
)
JOB_LINES = Counter(
    "job_lines_total",
    "Bulk job lines reaching a terminal state (completed = result "
    "journaled write-ahead to JOURNAL_DIR/jobs, failed = the error "
    "became the recorded result, cancelled = unfinished at job cancel)",
    ["model", "state"],
)
JOB_REPLAYS = Counter(
    "job_replays_total",
    "Jobs processed at startup replay, by outcome (resumed = "
    "re-admitted from the last completed line, complete = every line "
    "finished before the kill, failed = could not re-admit)",
    ["model", "outcome"],
)
KV_DISK_POOL_BLOCKS = Gauge(
    "kv_disk_pool_blocks",
    "Disk KV tier blocks by state (KV_DISK_BUDGET_MB; used = spilled "
    "stream checkpoints + demoted prefix entries persisted under "
    "JOURNAL_DIR/kv_disk)",
    ["model", "state"],
)
KV_GROWTH_STALLS = Counter(
    "kv_growth_stalls_total",
    "Paged-KV decode growth found the pool dry: the stream was "
    "checkpointed and re-queued (resumes when blocks free up)",
    ["model"],
)
# Sub-millisecond buckets: dispatch submit→return and inter-token
# cadence both sit well under 1 ms on direct-attached chips — the
# whole point of these two series is separating that regime from the
# regime of a ~100 ms dispatch round-trip (pre-round setting, to be re-measured).
# The fine set keeps its sub-ms resolution but no longer tops out at
# 10 s (the r11 honest negative: stream_tbt_seconds p99 saturated the
# top bucket on the 1-vCPU box and the scrape-side percentile could
# only answer "≥ 10 s").
_FINE_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 10.0, 30.0, 120.0,
)
DISPATCH_HOST = Histogram(
    "dispatch_host_seconds",
    "Host time one guarded device dispatch spent from submit to "
    "return, by dispatch site (prefill | prefill_chunk | chunk | "
    "fetch | batch | handoff | swap | prep) — the host-side half of "
    "the host-vs-device attribution split (TRACE=1 spans carry the "
    "device half); prep is the double-buffered host prep staged while "
    "the previous chunk is in flight",
    ["model", "site"], buckets=_FINE_BUCKETS,
)
JOURNAL_FSYNC = Histogram(
    "journal_fsync_seconds",
    "Wall time per journal fsync (JOURNAL_FSYNC=always pays one per "
    "record on the delivery path; interval amortizes; off never "
    "observes here)",
    ["model"], buckets=_FINE_BUCKETS,
)
WARM_SECONDS = Histogram(
    "engine_warm_seconds",
    "Wall seconds one warm phase took (engine = engine.warmup bucket "
    "grid, autotune = the kernel-variant resolution, loop = "
    "ContinuousDecodeLoop.warm's grid, spawn_build / spawn_warm "
    "/ spawn_probe = the fleet scale-up breakdown) — with the "
    "fleet-shared executable cache a second replica's loop/spawn "
    "phases collapse to dispatch time, zero XLA compiles",
    ["model", "phase"],
    buckets=(0.1, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0),
)
BOOT_PHASE_SECONDS = Gauge(
    "boot_phase_seconds",
    "Wall seconds of the last boot by top-level phase (the segment "
    "after boot/ of utils/tracing.boot_phase: imports, config, device, "
    "tokenizer, weights, engine_build, warm), plus unnamed (inside "
    "total, under no phase), total (entry of serve.build_service to "
    "readiness) and pre_build (process start to that entry) — set once, "
    "at readiness; /status.compile.boot has the rows",
    ["model", "phase"],
)
XLA_EXECUTABLES = Counter(
    "xla_executables_total",
    "Executables the XLA backend handed back, by outcome (compiled, or "
    "loaded from the persistent cache) and by when (boot = before "
    "readiness, serving = after: a recompile) — "
    "runtime/compile_cache.py, one record each in /status.compile",
    ["outcome", "when"],
)
XLA_EXECUTABLE_SECONDS = Counter(
    "xla_executable_seconds_total",
    "Seconds those executables took by stage (trace = jaxpr tracing, "
    "lower = jaxpr to MLIR, backend = the XLA compile or the cache "
    "load), summed over threads: work, not wall",
    ["outcome", "stage", "when"],
)
EXEC_CACHE_EVENTS = Counter(
    "executable_cache_events_total",
    "Process-level ExecutableCache lookups by event (hit = an existing "
    "jitted wrapper was shared — the zero-compile spawn/restart path; "
    "miss = no wrapper under the key; insert = a freshly built wrapper "
    "was cached) — runtime/compile_cache.py, docs/compilation.md",
    ["event"],
)
PALLAS_AUTOTUNE_EVENTS = Counter(
    "pallas_autotune_events_total",
    "Decode-kernel autotuner decisions by event (sweep = a measured "
    "variant search ran; hit = the tuning table answered without one; "
    "pin = PALLAS_VARIANT honored; install = a winner entered the "
    "ExecutableCache; reject_vmem/reject_verify/reject_error = "
    "candidates dropped by the cost model / reference check / build "
    "failure) — ops/autotune.py, docs/kernel_tuning.md",
    ["event"],
)
TBT = Histogram(
    "stream_tbt_seconds",
    "Streaming inter-chunk delivery gap (time between consecutive "
    "token-chunk deliveries to one stream after its first chunk) — "
    "the decode-cadence series the chunked-prefill A/B judges",
    ["model"], buckets=_FINE_BUCKETS,
)
# -- SLO burn rates (scheduler/policy.SLOTracker, docs/observability.md) --
SLO_TTFT_BURN = Gauge(
    "slo_ttft_burn_rate",
    "Per-priority-class TTFT SLO burn rate by window (fast/slow): "
    "fraction of the error budget (1 - SLO_TARGET) being consumed; "
    "1.0 = burning exactly at budget, >1 = violating "
    "(scheduler/policy.SLOTracker; SLO_TTFT_MS knobs)",
    ["model", "klass", "window"],
)
# -- multi-tenancy (tenancy/; docs/multi-tenancy.md).  The tenant
# label is BOUNDED: the first TENANT_METRICS_TOPK configured tenants
# export by name, everything else folds into "other" and anonymous
# traffic into "anon" (TenantRegistry.label) — cardinality is
# topk+2 regardless of how many API keys exist.
TENANT_SHED = Counter(
    "tenant_requests_shed_total",
    "Per-tenant load sheds by reason (quota = the tenant exhausted its "
    "own concurrency/token-window/KV envelope → HTTP 429; other "
    "reasons mirror requests_shed_total, attributed to the caller)",
    ["model", "tenant", "reason"],
)
TENANT_KV = Gauge(
    "tenant_kv_committed_bytes",
    "KV-cache bytes currently leased against each tenant's quota "
    "(tenancy/accounts.py occupancy ledger; drains to zero at idle)",
    ["model", "tenant"],
)
TENANT_TOKENS = Counter(
    "tenant_tokens_total",
    "Offered tokens charged to each tenant's sliding window (prompt "
    "length + clamped decode budget, charged at admission — metered "
    "work, not realized luck)",
    ["model", "tenant"],
)
TENANT_SLO_BURN = Gauge(
    "tenant_slo_ttft_burn_rate",
    "Per-tenant TTFT SLO burn rate by window (fast/slow), same budget "
    "arithmetic as slo_ttft_burn_rate — the noisy-neighbor blast-"
    "radius gauge fair share is supposed to keep flat",
    ["model", "tenant", "window"],
)
ADAPTER_SLOTS = Gauge(
    "adapter_pool_slots",
    "LoRA adapter device-slot pool by state (resident = installed "
    "adapters, active = slots refcounted by live streams, free = "
    "installable without eviction, host = adapters loaded host-side) "
    "— tenancy/adapters.py",
    ["model", "state"],
)
SLO_TBT_BURN = Gauge(
    "slo_tbt_burn_rate",
    "Per-priority-class TBT (inter-chunk cadence) SLO burn rate by "
    "window (fast/slow), same budget arithmetic as slo_ttft_burn_rate "
    "(SLO_TBT_MS knobs)",
    ["model", "klass", "window"],
)
TP_COLLECTIVE_SECONDS = Gauge(
    "tp_collective_seconds",
    "Measured wall time of one d_model-sized collective over the "
    "('replica','tp') serving mesh, by op (all_reduce = the row-"
    "parallel psum every decode layer pays, all_gather = the logits "
    "gather) — probed once at engine warm (parallel/tpserve.py); a "
    "step change flags ICI vs host-hop placement drift",
    ["model", "op"],
)
KV_POOL_SHARD_BLOCKS = Gauge(
    "kv_pool_shard_blocks",
    "Paged-KV blocks resident per TP shard (TP>1: every block splits "
    "its heads axis across shards, so the shards MUST stay equal — "
    "one logical pool, device-agnostic block ids; divergence means a "
    "sharding bug).  TP=1 emits shard 0 only",
    ["model", "shard"],
)


# -- where a decode loop's wall time went, and the process's own pauses
# (utils/tracing.LoopTable, utils/pauses.py; docs/observability.md).
# The four counters are fed at render time (``register_exporter``): a
# phase exit or a collection touches no Prometheus child.
LOOP_PHASE_SECONDS = Counter(
    "loop_phase_seconds_total",
    "Wall seconds of the decode loop's thread by top-level phase "
    "(utils/tracing.phase names: loop/idle = no request anywhere in the "
    "server, loop/await_api = the API holds a request that has not "
    "reached the queue, loop/await_burst = an idle wave's quiet gap, "
    "loop/queue_pop, loop/wave_dispatch, loop/wave_fetch, loop/insert, "
    "loop/chunk_prep, loop/chunk_dispatch, loop/stage_prep, loop/deliver, "
    "loop/housekeeping, ...); with loop_unnamed_seconds_total they sum "
    "to the loop's wall time; /status.decode.loop_time has counts, the "
    "longest instance and the slowest iterations",
    ["model", "phase"],
)
LOOP_UNNAMED_SECONDS = Counter(
    "loop_unnamed_seconds_total",
    "Wall seconds of the decode loop's thread under no phase at all",
    ["model"],
)
LOOP_THREAD_RUN_DELAY = Counter(
    "loop_thread_run_delay_seconds_total",
    "Seconds the decode loop's thread was runnable and on no CPU "
    "(/proc/self/task/<tid>/schedstat, Linux only): the host's cores "
    "were someone else's",
    ["model"],
)
GC_PAUSE_SECONDS = Counter(
    "gc_pause_seconds_total",
    "Seconds Python's cyclic collector held the interpreter, by "
    "generation (gc.callbacks; /status.process.gc has the counts)",
    ["generation"],
)
EVENT_LOOP_LAG = Histogram(
    "event_loop_lag_seconds",
    "How late the server's event loop ran a 20 Hz timer tick: the loop "
    "that reads every request and writes every token event was held "
    "that long by something else",
    ["model"],
    buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
             0.5, 1.0, 2.5),
)

# Objects whose ``export_metrics()`` runs before every render: what
# keeps its own sums off the hot path hands them on here.  Held weakly:
# a decode loop that is gone exports nothing.
_EXPORTERS: "weakref.WeakSet" = weakref.WeakSet()


def register_exporter(obj) -> None:
    _EXPORTERS.add(obj)


def raise_to(child, seen: dict, key, total: float) -> None:
    """Raise the counter ``child`` by what a running total kept
    elsewhere grew since the last call (``seen[key]`` remembers it)."""
    delta = total - seen.get(key, 0.0)
    if delta > 0.0:
        seen[key] = total
        child.inc(delta)


def render() -> tuple[bytes, str]:
    for obj in list(_EXPORTERS):
        obj.export_metrics()
    return generate_latest(), CONTENT_TYPE_LATEST
