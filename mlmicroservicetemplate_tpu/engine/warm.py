"""Warm-up of a decode loop: its executables compiled off the request path.

Functions over a loop's ``programs`` (engine/programs.py), its static
shapes and its state; nothing here imports ``streams.py``.  Of the loop
they read the shapes, the argument builders a dispatch shares with serving
(``_mp``, ``_ssm_window_args``, ``_ssm_row_arg``, ``_hist_row``,
``_host_tier``, ``_host_leaf_specs``) and ``_build_empty_state``; they
replace ``_state`` as every state-to-state dispatch does and leave it
all-dead; they set ``chain_depth``, ``_wave_seconds`` and
``programs.kernel_variant``.  Both KV layouts share one grid walk
(``_warm_grid``) and one chain-depth tuner (``tune_chain_depth``): a layout
brings its cells, what lands a wave's state, and its chunk call.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import metrics, tracing

log = logging.getLogger(__name__)


def warm(loop) -> None:
    """Compile the loop's executables off the request path: the
    empty-state template, the insert scatter per seq bucket, and
    the batched chunk in both greedy and sampled variants.  With
    the fleet-shared ExecutableCache every wrapper may already
    exist (a sibling replica built it), in which case this whole
    pass is dispatches only — zero XLA compiles, the property the
    spawn fast-path banks on (docs/compilation.md)."""
    from ..runtime.compile_cache import note_warm_phase

    model = loop.engine.bundle.name
    if loop._state is None:
        with tracing.boot_phase("boot/engine_build", what="empty_state"):
            loop._build_empty_state()
    # Before the paged executables trace: the winner lands in the
    # tuning table their kernel call sites resolve at trace time.
    with tracing.boot_phase("boot/warm/autotune") as ph:
        autotune_kernel(loop)
    note_warm_phase(model, "autotune", ph.seconds)
    with tracing.boot_phase("boot/warm/loop") as ph:
        _warm_loop(loop)
    note_warm_phase(model, "loop", ph.seconds)


def warm_spawn(loop, donor=None) -> None:
    """λScale spawn warm (docs/compilation.md): with a donor loop
    alive, every executable this loop will ever dispatch already
    sits in the process-level ExecutableCache — so skip the
    warm-dispatch grid entirely.  Build the device state (the one
    real dispatch), adopt the donor's measured chain depth and
    wave times instead of re-running the RTT calibration, and let
    the fleet's probe dispatch be the gate before routing.  Variants
    the donor never compiled (e.g. sampled executables under
    WARMUP_SAMPLING=0) defer to first use — exactly the donor's
    own behavior.  No donor → the full warm."""
    if donor is None:
        warm(loop)
        return
    from ..runtime.compile_cache import note_warm_phase

    with tracing.boot_phase("boot/warm/loop", spawn=True) as ph:
        if loop._state is None:
            loop._build_empty_state()
        loop.chain_depth = max(1, int(donor.chain_depth))
        loop._wave_seconds = dict(donor._wave_seconds)
        metrics.CHAIN_DEPTH.labels(loop.engine.bundle.name).set(
            loop.chain_depth
        )
    note_warm_phase(loop.engine.bundle.name, "loop", ph.seconds)


def _warm_loop(loop) -> None:
    off = ("0", "false", "no")
    warm_sampled = os.environ.get("WARMUP_SAMPLING", "1").lower() not in off
    chunk = _warm_chunk_call(loop)
    if loop.paged:
        _warm_paged(loop, warm_sampled, chunk)
        with tracing.boot_phase("boot/warm/loop/swap"):
            warm_swap(loop)
    else:
        _warm_slab(loop, warm_sampled, chunk)
    if loop.prefill_chunk:
        with tracing.boot_phase("boot/warm/loop/prefill_window"):
            warm_prefill(loop)
    if loop._auto_depth:
        with tracing.boot_phase("boot/warm/loop/chain_depth"):
            tune_chain_depth(loop, chunk)
    # Reset to all-dead so warm inserts never leak into serving.
    with tracing.boot_phase("boot/warm/loop/empty_state"):
        loop._build_empty_state()


def _warm_grid(loop, cells, land, threads: int = 1) -> None:
    """The batched start of every (bucket, rung, sampled) of ``cells``,
    each wave's state handed to ``land(state1, ids, mask, s, n_batch)``
    under the engine's lock.  One ``land`` at a time: an insert consumes
    the state (donated) and the next takes its successor.  With
    ``threads`` > 1 the cells run on that many ``warm-rung`` threads."""
    eng = loop.engine
    one_insert = threading.Lock()
    parent = tracing.boot_current()

    def warm_one(cell: tuple[int, int, bool]) -> None:
        s, n_batch, sampled = cell
        with tracing.boot_phase("boot/warm/loop/grid", parent,
                                bucket=s, rung=n_batch), eng._lock:
            state1, ids, mask = loop.programs.warm_wave(s, n_batch, sampled)
            with one_insert:
                land(state1, ids, mask, s, n_batch)

    if threads == 1:
        for cell in cells:
            warm_one(cell)
        return
    with ThreadPoolExecutor(threads, "warm-rung") as pool:
        list(pool.map(warm_one, cells))  # list(): raise what failed


def _warm_chunk_call(loop):
    """The layout's chunk as ``(state, sampled) -> (state, tokens, ...)``."""
    eng = loop.engine
    if loop.spec:
        return lambda state, sampled: eng._spec_chunk(
            eng.params, state, eng.chunk_tokens, eng.spec_k, sampled)
    wp = loop._mp(n=loop.n_slots)
    if not loop.paged:
        return lambda state, sampled: eng._gen_chunk(
            wp, state, eng.chunk_tokens, sampled)
    table = jnp.asarray(loop._table)
    return lambda state, sampled: loop.programs.paged_chunk_fn()(
        wp, state, table, eng.chunk_tokens, sampled)


def _warm_chunk(loop, chunk, sampled: bool) -> None:
    """The batched chunk in its greedy and, where asked, sampled variant."""
    for flag in (False, True) if sampled else (False,):
        with tracing.boot_phase("boot/warm/loop/chunk", sampled=flag), \
                loop.engine._lock:
            loop._state, toks = chunk(loop._state, flag)[:2]
            jax.device_get(toks)


def _warm_slab(loop, warm_sampled: bool, chunk) -> None:
    eng = loop.engine
    rungs = loop._wave_rungs

    def do_insert(state1, ids, mask, s: int, n_batch: int = 0):
        if loop.spec:
            feats0 = {"input_ids": np.ones(s, np.int32), "length": np.int32(s)}
            hist_row = loop._hist_row(
                feats0, np.zeros(eng.chunk_tokens, np.int32))
            loop._state = loop.programs.insert_fn()(
                loop._state, state1, ids, mask, hist_row,
                np.int32(0), np.int32(0),
            )
        else:
            loop._state = loop.programs.insert_fn()(
                loop._state, state1, np.int32(0), np.int32(0)
            )

    # Wave sizes to warm: every rung a wave can run at (the lowest
    # is the solo shape).  Under the prefix cache these still serve
    # grouped MISSES (hits go through the grouped prefixed waves
    # warmed below).
    _warm_grid(loop, [
        (s, n_batch, flag)
        for s in eng.seq_buckets for n_batch in rungs
        for flag in ((False, True) if warm_sampled and n_batch > 1
                     else (False,))
    ], do_insert)
    _warm_chunk(loop, chunk, warm_sampled or not loop.spec)

    def reinsert(state1, ids, mask, s: int, n_batch: int):
        do_insert(state1, ids, mask, s)
        # Miss-wave donation slicers specialize on the
        # batched state shape — warm them here so the first
        # grouped miss wave never compiles a capture on the
        # request path.
        if eng.prefix_cache is not None and n_batch > 1:
            for p_ins in eng.seq_buckets:
                if p_ins <= s:
                    eng._capture_prefix(state1, p_ins, 0)
        jax.block_until_ready(jax.tree.leaves(loop._state)[0])

    # Re-warm the inserts in SERVING order — against a chunk-OUTPUT
    # batched state.  The first such call in a process pays a
    # one-time cost of seconds (pre-round record; absent when
    # the batched-state operand comes from the warm-up's device_put
    # path), which would otherwise land on the first admission
    # after serving starts.
    _warm_grid(loop, [
        (s, n_batch, False) for s in eng.seq_buckets for n_batch in rungs
    ], reinsert)
    # Prefix-cache grid: a cache hit's state has width
    # p_len+s_suf+max_decode — a shape none of the inserts above
    # ever saw, so the FIRST hit admission would otherwise compile
    # the insert on the request path (seconds, in a pre-round record).
    # Warm the insert against B=1 hit states AND the grouped
    # (_start_prefixed_wave) states per reachable (prefix, suffix)
    # pair, plus the wave executables themselves and their hit-path
    # donation slicers.  (The B=1 starts run sample=False only:
    # engine.warmup already compiled both sample variants of
    # _start_prefixed, and the INSERT executable this block exists
    # for is sample-agnostic — state shapes don't depend on it.)
    if eng.prefix_cache is not None:
        s_max = max(eng.seq_buckets)
        with eng._lock:
            template, _, _ = loop.programs.warm_wave(s_max, 1)
        for p_len in eng.seq_buckets:
            if p_len > s_max - 1:
                continue
            with eng._lock:
                pkv = eng._capture_prefix(template, p_len)
            for s_suf in eng.seq_buckets:
                if p_len + s_suf > s_max:
                    continue
                sfeats = {
                    "input_ids": np.ones(s_suf, np.int32),
                    "length": np.int32(s_suf),
                }
                with eng._lock:
                    sids, smask, ssp = loop.programs.placed_batch([sfeats])
                    st1, _ = eng._start_prefixed(
                        loop._mp(n=1), pkv, sids, smask, ssp,
                        eng.max_decode_len, eng.chunk_tokens, False,
                    )
                    # Spec mode warms the init_spec_fn-recasting
                    # insert against the hit-state shape (full
                    # prompt = prefix + suffix for the hist row).
                    do_insert(st1, sids, smask, p_len + s_suf)
                for n_batch in rungs:
                    if n_batch < 2:
                        continue  # solo hits: the B=1 start above
                    with eng._lock:
                        wids, wmask, wsp = loop.programs.placed_batch(
                            [sfeats] * n_batch)
                        pkvs = (pkv,) * wids.shape[0]
                        for flag in (
                            (False, True) if warm_sampled else (False,)
                        ):
                            stw, tw = eng._start_prefixed_wave(
                                loop._mp(n=int(wids.shape[0])),
                                pkvs, wids, wmask, wsp,
                                eng.max_decode_len, eng.chunk_tokens,
                                flag,
                            )
                            jax.device_get(tw)
                        do_insert(stw, wids, wmask, p_len + s_suf)
                        # Wave-state donation slicers (growing
                        # conversations donate per row from the
                        # grouped hit state).
                        for p_ins in eng.seq_buckets:
                            if p_len < p_ins <= p_len + s_suf - 1:
                                eng._capture_prefix(stw, p_ins, 0)
                jax.block_until_ready(
                    jax.tree.leaves(loop._state)[0]
                )


def autotune_kernel(loop) -> None:
    """Warm-time Pallas kernel-variant resolution (ops/autotune.py,
    docs/kernel_tuning.md).  Runs BEFORE the paged executables
    below trace: a PALLAS_VARIANT pin is validated and installed,
    else PALLAS_AUTOTUNE runs the measured sweep (verify-then-time
    every feasible variant at this loop's exact decode shapes) —
    either way the winner lands in the process tuning table, where
    the model's kernel call sites resolve it at trace time, and in
    the fleet-shared ExecutableCache + persisted table, so replica
    spawns/rebuilds/replays inherit it with zero extra compiles.
    No knob set, or the bundle not on the kernel path: no-op,
    ``programs.kernel_variant`` stays "" (the default kernel)."""
    eng = loop.engine
    bcfg = getattr(eng.bundle, "cfg", None)
    scfg = getattr(eng, "cfg", None)
    if not (loop.paged and getattr(bcfg, "pallas_decode", False)):
        return
    pin = (getattr(scfg, "pallas_variant", None)
           or getattr(bcfg, "pallas_variant", "") or None)
    if not (pin or getattr(scfg, "pallas_autotune", False)):
        return
    from ..ops import autotune

    path = autotune.default_table_path(
        getattr(scfg, "device", None),
        getattr(scfg, "compile_cache_dir", None),
    )
    # KV heads and their width AS THE KERNEL SEES THEM (a differential
    # pair is one head two heads wide: LlamaConfig.kv_groups / kv_tail).
    kvh = int(getattr(bcfg, "kv_groups", 0)
              or getattr(bcfg, "num_kv_heads", bcfg.num_heads))
    kind, d = "paged_decode", int(getattr(bcfg, "kv_tail", (0, bcfg.head_dim))[1])
    if getattr(bcfg, "latent_lanes", 0):
        # One KV "head" every query head shares, as wide as the pool.
        kind, kvh, d = "latent_decode", 1, int(bcfg.latent_lanes)

    def tuned(t: int) -> str:
        return autotune.ensure_tuned(
            kind, eng.bundle, eng.replicas,
            b=loop.n_slots, kvh=kvh,
            n_rep=int(bcfg.num_heads) // kvh, d=d,
            block_size=loop.block_size, t=t,
            dtype=str(np.dtype(eng.bundle.policy.compute_jnp)),
            quant=bool(getattr(bcfg, "kv_quant", False)),
            interpret=bool(getattr(bcfg, "pallas_interpret", False)),
            pin=pin, table_path=path,
        )

    loop.programs.kernel_variant = tuned(loop.nb_max)
    if getattr(bcfg, "window", 0):
        # A window layer's kernel runs at the width of its table
        # view (models/llama.window_view): a tuning problem of its own.
        from ..models.llama import window_view_blocks

        tw = window_view_blocks(bcfg.window, loop.block_size, loop.nb_max)
        if tw != loop.nb_max:
            tuned(tw)


def _warm_paged(loop, warm_sampled: bool, chunk) -> None:
    """Paged-mode warmup: the start and the paged insert per (wave
    rung × seq bucket) and the paged chunk in both sample variants,
    against temporarily-allocated blocks that are returned (and the
    state reset) before serving.  The prefixed-hit insert variants
    ((s_lo, s_cut) pairs) compile on first hit — paged deployments
    restrict SEQ_BUCKETS anyway (the PREFIX_CACHE guidance), and a
    one-off compile beats warming a grid most cells of which are
    never served."""
    from .kv_blocks import OutOfBlocks, StreamBlocks, blocks_for

    eng = loop.engine

    # One scratch block list serves the whole grid (every insert
    # writes slot 0; warm-up resets the state below); a bucket the
    # pool cannot hold is unservable and stays cold.
    sb = StreamBlocks(loop.pool, loop.block_size)
    grid = []
    for s in sorted(eng.seq_buckets):
        try:
            sb.ensure(s + eng.chunk_tokens)
        except OutOfBlocks:
            break
        grid += [(s, n_batch, False) for n_batch in loop._wave_rungs]

    insert = loop.programs.paged_insert_fn()

    def land(state1, ids, mask, s: int, n_batch: int) -> None:
        n_blocks = blocks_for(s + eng.chunk_tokens, loop.block_size)
        loop._state = insert(*loop.programs.warm_insert_args(
            loop._state, state1, s, sb.ids[:n_blocks]
        ))
        jax.block_until_ready(loop._state.done)

    # A warm start is tracing plus the runtime loading a cached
    # executable of tens of MB, ~2.6 s a (rung, bucket) pair, and the
    # ladder's extra pairs cost a boot more than ``setup_s`` may move
    # when they load one after another.  On three threads the loads
    # overlap (the tracing does not): the grid's extra cost falls to
    # less than half (PERF.md section 6, PR 26).  Largest first, so
    # no thread starts the longest load last; up to three wave
    # states are alive at once instead of one.
    grid.sort(key=lambda cell: -cell[0] * cell[1])
    try:
        _warm_grid(loop, grid, land, threads=3)
    finally:
        sb.release()
    _warm_chunk(loop, chunk, warm_sampled)


def warm_swap(loop) -> None:
    """Compile the host-tier swap executables off the request path
    (the round-14 honest negative: the FIRST host-tier resume paid
    a one-off scatter + handoff compile on the request path).
    Warms the fixed-width host→device scatter, the device→host
    gather at every power-of-two width the swap-out padder can
    emit (log2(nb_max) executables, bounded), and — when chunked
    prefill won't warm it — the paged row handoff the swap resume
    flips live through."""
    tier = loop._host_tier()
    if tier is None or not loop.paged:
        return
    eng = loop.engine
    if not tier.ensure_pool(loop._host_leaf_specs()):
        return
    specs = loop._host_leaf_specs()
    K = loop.swap_chunk_blocks
    ids = np.zeros(K, np.int32)
    vals = [
        np.zeros((K,) + tuple(shape), dtype) for shape, dtype in specs
    ]
    with eng._lock:
        # Scatter writes zeros into block 0 of the warm state —
        # harmless: _build_empty_state resets everything after
        # warmup, before serving.
        loop._state = loop.programs.swap_scatter_fn()(loop._state, ids, vals)
        w = 1
        cap = 1 << max(0, loop.nb_max - 1).bit_length()
        while w <= cap:
            loop.programs.swap_gather_fn()(loop._state, np.zeros(w, np.int32))
            w *= 2
        if not loop.prefill_chunk:
            # Swap-resume handoff (chunked deployments warm it in
            # warm_prefill; without PREFILL_CHUNK it would compile
            # on the first resume).
            _warm_handoff(loop, np.ones(1, np.int32))
        jax.block_until_ready(jax.tree.leaves(loop._state)[0])


def _warm_handoff(loop, ids) -> None:
    """The paged row handoff of a prompt ``ids`` into slot 0 (caller
    holds ``eng._lock``)."""
    eng = loop.engine
    sp, _ = eng._collate_sample(
        [{"input_ids": ids, "length": np.int32(len(ids))}], 1
    )
    loop._state = loop.programs.paged_handoff_fn()(
        loop._state,
        np.zeros((1, loop.nb_max * loop.block_size), np.int32),
        np.zeros(1, np.int32), np.zeros(1, np.int32),
        np.zeros(1, np.int32), np.ones(1, bool),
        np.zeros((1, eng.max_decode_len), np.int32),
        sp, np.int32(0), *loop._ssm_row_arg(),
    )


def warm_prefill(loop) -> None:
    """Compile the chunked-prefill executables off the request
    path: the empty-state builder + window forward per bucket
    width (contiguous) or the pool-writing window at both batch
    widths a dispatch can have + row handoff (paged).  Long prompts
    past the bucket list still compile their width on first
    admission (contiguous) — the documented cost of lifting the
    prompt ceiling."""
    eng = loop.engine
    c = loop.prefill_chunk
    ids_w = np.ones((1, c), np.int32)
    mask_w = np.ones((1, c), np.int32)
    if loop.paged:
        from .kv_blocks import OutOfBlocks, StreamBlocks

        sb = StreamBlocks(loop.pool, loop.block_size)
        try:
            sb.ensure(c)
        except OutOfBlocks:
            return
        table_row = np.full(loop.nb_max, loop.pool.num_blocks, np.int32)
        table_row[: len(sb.ids)] = sb.ids
        try:
            with eng._lock:
                # The two widths a dispatch has (a window alone, and
                # what a boundary's budget admits), so no window
                # compiles while serving; the rows write the same
                # warm blocks, which is harmless here.
                for b in sorted({1, loop._prefill_width}):
                    out = loop.programs.paged_prefill_fn()(
                        loop._mp(n=b), loop._state,
                        jnp.asarray(np.tile(table_row, (b, 1))),
                        np.tile(ids_w, (b, 1)), np.tile(mask_w, (b, 1)),
                        np.zeros(b, np.int32), *loop._ssm_window_args(b),
                    )
                    # (state, counts) from a chip's share of the experts.
                    loop._state = out[0] if type(out) is tuple else out
                _warm_handoff(loop, ids_w[0])
        finally:
            sb.release()
        return
    for s in eng.seq_buckets:
        if not eng.chunked_prefill_applies(s):
            continue
        with eng._lock:
            st1 = loop.programs.empty_prefill_fn()(
                loop._mp(n=1), 1, s, eng.max_decode_len
            )
            loop.programs.prefill_fn()(
                loop._mp(n=1), st1, ids_w, mask_w, np.int32(0)
            )


def depth_from(rtt_s: float, compute_s: float) -> int:
    """Chain depth from measured numbers: cadence ≈ max(RTT/D,
    chunk compute), so D ≈ RTT/compute closes the gap to the wire;
    clamped to [1, 8] (deeper chains only add fetch latency)."""
    return max(1, min(8, round(rtt_s / max(compute_s, 1e-4))))


def apply_tuned_depth(loop, rtt: float, compute: float) -> None:
    loop.chain_depth = depth_from(rtt, compute)
    metrics.CHAIN_DEPTH.labels(loop.engine.bundle.name).set(
        loop.chain_depth
    )
    # No wave costs less than a chunk's round trip: the idle
    # admission's cap until the loop has timed a wave of its own.
    loop._wave_seconds.setdefault(loop._wave_rungs[0], rtt + compute)
    log.info(
        "continuous loop: chunk compute %.1f ms, dispatch RTT %.1f ms "
        "-> chain depth %d",
        compute * 1e3, rtt * 1e3, loop.chain_depth,
    )


def tune_chain_depth(loop, chunk) -> None:
    """Pick the chunk-chain pipelining depth from measured numbers:
    cadence ≈ max(RTT/D, chunk compute), so D ≈ RTT/compute closes
    the gap to the wire.  Chained dispatches against the SAME warm
    executable separate the two: wall(k chained chunks + fetch) =
    RTT + k·compute, so compute = (wall_5 − wall_1)/4 and RTT
    falls out — no extra compiles, ~6 dispatches total.
    ``chunk(state, sampled)`` is the layout's chunk call: its first two
    results are the next state and the tokens."""
    eng = loop.engine

    def wall(k: int) -> float:
        t0 = time.perf_counter()
        with eng._lock:
            for _ in range(k):
                loop._state, toks = chunk(loop._state, False)[:2]
            # graftlint: unguarded(warm-time RTT calibration probe — the raw wire is the measurement; a guard's bookkeeping is the thing being measured)
            jax.device_get(toks)
        return time.perf_counter() - t0

    wall(1)  # prime any lazy transfer
    w1 = wall(1)
    w5 = wall(5)
    compute = max((w5 - w1) / 4.0, 1e-4)
    rtt = max(w1 - compute, 0.0)
    apply_tuned_depth(loop, rtt, compute)
