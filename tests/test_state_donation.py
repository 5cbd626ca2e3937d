"""The decode state is donated: a state that is replaced is consumed.

Every executable that takes the batched decode state and returns its
successor donates it (engine/streams.py's module docstring has the
rule), so a KV pool is written in place instead of copied in and out at
every chunk and every slot insert.  The CPU backend honours donation on
this JAX, so tier-1 sees every use-after-donate the loop could make:

(a) a pipelined paged loop (chain depth 3) serves chunks and several
    admissions with chunks in flight; every fetched ``done``/``toks`` is
    readable and the tokens are the ones the tree before this rule
    produced (``GOLDEN``: greedy, seeded prompts and weights);
(b) the donation is real — after each state -> state executable the
    previous state's leaves are deleted, and after each reader they are
    not;
(c) the warm grid, whose three threads now hand one state along, ends
    with a live state and serving compiles nothing;
(d) an injected fault fires before the dispatch and its retry is
    token-identical; a dispatch that raises AFTER consuming its state is
    not retried, ends in the rebuild path and holds the host-tier swap.
"""

from __future__ import annotations

import asyncio
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import tiny_gpt_bundle, tiny_llama_bundle, tiny_t5_bundle
from mlmicroservicetemplate_tpu.engine import InferenceEngine
from mlmicroservicetemplate_tpu.engine.faults import (
    StateConsumedError,
    Watchdog,
    is_consumed,
)
from mlmicroservicetemplate_tpu.engine.kv_blocks import blocks_for
from mlmicroservicetemplate_tpu.engine.programs import LoopPrograms
from mlmicroservicetemplate_tpu.engine.streams import ContinuousDecodeLoop
from mlmicroservicetemplate_tpu.engine.supervisor import Supervisor
from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
from mlmicroservicetemplate_tpu.runtime.compile_cache import CompileWindow
from mlmicroservicetemplate_tpu.utils import metrics
from mlmicroservicetemplate_tpu.utils.config import ServiceConfig


def _cfg(**kw) -> ServiceConfig:
    kw.setdefault("device", "cpu")
    kw.setdefault("warmup", False)
    kw.setdefault("batch_buckets", (1, 2, 4))
    kw.setdefault("seq_buckets", (16, 32))
    kw.setdefault("max_decode_len", 24)
    kw.setdefault("stream_chunk_tokens", 4)
    kw.setdefault("max_streams", 4)
    kw.setdefault("paged_kv", True)
    kw.setdefault("kv_block_size", 8)
    return ServiceConfig(**kw)


def _engine(bundle, cfg):
    return InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))


def _prompts(n: int, seed: int = 11):
    rng = np.random.default_rng(seed)
    return [
        {"input_ids": p, "length": np.int32(len(p))}
        for p in (
            rng.integers(5, 250, int(k)).astype(np.int32)
            for k in rng.integers(5, 15, n)
        )
    ]


async def _consume(gen):
    out = []
    async for c in gen:
        out.extend(np.asarray(c).tolist())
    return out


def _solo(engine, feats):
    return np.concatenate(list(engine.generate_stream(dict(feats)))).tolist()


def _leaves(tree):
    return [x for x in jax.tree.leaves(tree) if hasattr(x, "is_deleted")]


# ---------------------------------------------------------------------------
# (a) pipelined loop: admissions with chunks in flight

#: What the tree before the rule (3f2af91: nothing donated) streamed for
#: ``_prompts(6)`` through this loop, per family: sum and last 4 tokens
#: of each stream — the whole sequences are held to the solo path below.
GOLDEN = {
    "llama": [(3815, [239, 269, 248, 228]), (4525, [38, 12, 61, 85]),
              (3409, [23, 38, 295, 124]), (3679, [249, 297, 276, 295]),
              (3887, [299, 228, 105, 248]), (3804, [202, 158, 101, 282])],
    "gpt": [(5421, [232, 183, 183, 183]), (5688, [237, 237, 237, 237]),
            (912, [38, 38, 38, 38]), (5040, [210, 210, 210, 210]),
            (5904, [246, 246, 246, 246]), (864, [36, 36, 36, 36])],
}


def _staggered(cdl, feats):
    """Two streams, two more once chunks are in flight, the last two
    into a full loop (they admit as slots free, chunks still flying).
    The opportunistic ``_deliver_ready`` is off: every entry is fetched
    as late as the loop's order allows, whatever the CPU's pace — the
    hard case for a ``(toks, done)`` read after its state was consumed.
    Returns the outputs and, a wave, (chunks in flight ahead of its
    start, chunks in flight at its insert)."""
    depth_at_start, depth_at_insert = [], []
    cdl._deliver_ready = lambda: None
    orig, orig_ahead = cdl._emit_and_insert, cdl._deliver_ahead_of_wave

    def spy(started, fetched):
        depth_at_insert.append(len(cdl._inflight_chunks))
        return orig(started, fetched)

    def spy_ahead(n_ahead):
        depth_at_start.append(n_ahead)
        return orig_ahead(n_ahead)

    cdl._emit_and_insert, cdl._deliver_ahead_of_wave = spy, spy_ahead

    async def until(cond):
        for _ in range(400):
            if cond():
                return
            await asyncio.sleep(0.01)

    async def body():
        tasks = [
            asyncio.ensure_future(_consume(cdl.submit_stream(dict(f))))
            for f in feats[:2]
        ]
        await until(lambda: cdl.chunk_dispatches >= 1)
        tasks += [
            asyncio.ensure_future(_consume(cdl.submit_stream(dict(f))))
            for f in feats[2:4]
        ]
        await until(lambda: cdl.chunk_dispatches >= 3)
        tasks += [
            asyncio.ensure_future(_consume(cdl.submit_stream(dict(f))))
            for f in feats[4:]
        ]
        return await asyncio.gather(*tasks)

    return asyncio.run(body()), (depth_at_start, depth_at_insert)


@pytest.mark.parametrize("family", ["llama", "gpt"])
def test_pipelined_paged_loop_is_token_identical(family):
    bundle = tiny_llama_bundle() if family == "llama" else tiny_gpt_bundle()
    cfg = _cfg(stream_pipeline=3, pipeline_depth=4, max_stream_queue=4)
    eng = _engine(bundle, cfg)
    feats = _prompts(6)
    solos = [_solo(_engine(bundle, _cfg(paged_kv=False)), f) for f in feats]
    cdl = ContinuousDecodeLoop(eng, cfg)
    assert cdl.chain_depth == 3
    try:
        outs, (depth_at_start, depth_at_insert) = _staggered(cdl, feats)
    finally:
        cdl.stop()
    assert outs == solos
    assert [(sum(o), o[-4:]) for o in outs] == GOLDEN[family]
    # The case the old "NOT donated" comment feared: a wave's start went
    # out while earlier chunks' (toks, done) were still to be fetched,
    # after later dispatches had consumed the states they came from —
    # and the insert that donates the state meets the chunk dispatched
    # BEHIND the start still in flight: its (toks, done) are fetched
    # after the insert consumed the state they came out with.
    assert len(depth_at_insert) >= 3 and max(depth_at_start) >= 1
    assert max(depth_at_insert) >= 1
    assert cdl.chunk_dispatches >= 6
    assert not is_consumed(cdl._state)


@pytest.mark.parametrize("family", ["gpt", "t5"])
def test_pipelined_contiguous_loop_is_token_identical(family):
    """The contiguous loop follows the same rule (its ``done`` is an
    output of the chunk too); T5's encoder leaves alias through."""
    bundle = tiny_gpt_bundle() if family == "gpt" else tiny_t5_bundle()
    cfg = _cfg(paged_kv=False, stream_pipeline=3, max_decode_len=16)
    eng = _engine(bundle, cfg)
    feats = _prompts(4, seed=5)
    solos = [_solo(eng, f) for f in feats]
    cdl = ContinuousDecodeLoop(eng, cfg)
    try:
        outs, _ = _staggered(cdl, feats)
    finally:
        cdl.stop()
    for got, want in zip(outs, solos):
        n = min(len(got), len(want))
        assert got[:n] == want[:n] and n >= 4


# ---------------------------------------------------------------------------
# (b) the donation is real


def _paged_loop(**kw):
    bundle = tiny_llama_bundle()
    cfg = _cfg(prefill_chunk=16, kv_host_budget_mb=1.0, **kw)
    eng = _engine(bundle, cfg)
    cdl = ContinuousDecodeLoop(eng, cfg)
    cdl._build_empty_state()
    return eng, cdl


def _table_row(cdl, n_blocks: int):
    row = np.full(cdl.nb_max, cdl.pool.num_blocks, np.int32)
    row[:n_blocks] = np.arange(n_blocks)
    return jnp.asarray(row)


def _handoff_args(eng, cdl):
    sp, _ = eng._collate_sample(
        [{"input_ids": np.ones(1, np.int32), "length": np.int32(1)}], 1
    )
    return (
        np.zeros((1, cdl.nb_max * cdl.block_size), np.int32),
        np.zeros(1, np.int32), np.zeros(1, np.int32), np.zeros(1, np.int32),
        np.ones(1, bool), np.zeros((1, eng.max_decode_len), np.int32),
        sp, np.int32(0),
    )


def _step_chunk(eng, cdl):
    return cdl.programs.paged_chunk_fn()(
        cdl._mp(n=cdl.n_slots), cdl._state, jnp.asarray(cdl._table),
        eng.chunk_tokens, False,
    )


def _step_insert(eng, cdl):
    state1 = cdl.programs.warm_wave(16, 1)[0]
    new = cdl.programs.paged_insert_fn()(
        cdl._state, state1, np.asarray(_table_row(cdl, 3))[None],
        np.zeros(1, np.int32), 0, 16 + eng.chunk_tokens,
    )
    # The wave's prefill state is read after its insert: not donated.
    assert not is_consumed(state1)
    return (new,)


def _step_handoff(eng, cdl):
    return (cdl.programs.paged_handoff_fn()(cdl._state, *_handoff_args(eng, cdl)),)


def _step_prefill_window(eng, cdl):
    c = cdl.prefill_chunk
    return (cdl.programs.paged_prefill_fn()(
        cdl._mp(n=1), cdl._state, _table_row(cdl, 2)[None],
        np.ones((1, c), np.int32), np.ones((1, c), np.int32),
        np.zeros(1, np.int32),
    ),)


def _step_swap_scatter(eng, cdl):
    k = cdl.swap_chunk_blocks
    vals = [
        np.zeros((k,) + tuple(shape), dtype)
        for shape, dtype in cdl._host_leaf_specs()
    ]
    return (cdl.programs.swap_scatter_fn()(cdl._state, np.zeros(k, np.int32), vals),)


STEPS = {
    "chunk": _step_chunk, "insert": _step_insert, "handoff": _step_handoff,
    "prefill_window": _step_prefill_window, "swap_scatter": _step_swap_scatter,
}


@pytest.mark.parametrize("kind", list(STEPS))
def test_state_to_state_executables_consume_their_state(kind):
    eng, cdl = _paged_loop()
    try:
        prev = cdl._state
        pools = _leaves((prev.cache_k, prev.cache_v))
        assert len(pools) == 2 * len(prev.cache_k) and not is_consumed(prev)
        new, *outs = STEPS[kind](eng, cdl)
        jax.block_until_ready(new)
        assert all(x.is_deleted() for x in pools), "a pool was copied"
        assert all(x.is_deleted() for x in _leaves(prev))
        assert not is_consumed(new)
        # What a caller fetches later is readable once ``new`` is
        # consumed in turn: outputs of their own, not leaves of it.
        cdl._state = new
        newer, *_ = STEPS[kind](eng, cdl)
        assert is_consumed(new)
        fetched = jax.device_get(outs)
        if kind == "chunk":
            toks, done = fetched
            assert done.shape == (cdl.n_slots,) and done.all()
            assert toks.shape == (cdl.n_slots, eng.chunk_tokens)
        jax.block_until_ready(newer)
    finally:
        cdl.stop()


@pytest.mark.parametrize("what", ["paged_chunk", "paged_insert"])
def test_programs_lower_from_an_engine_and_shapes_alone(what):
    """``LoopPrograms`` needs an engine and static shapes, no loop object:
    it lowers the paged chunk and the paged insert over a state built by
    hand (what ``_build_empty_paged`` builds), and builds nothing else."""
    from mlmicroservicetemplate_tpu.models.gpt import PagedState

    eng = _engine(tiny_llama_bundle(), _cfg())
    bs, nbp, n = eng.kv_block_size, eng.kv_pool.num_blocks, 4
    nb_max = blocks_for(32 + eng.max_decode_len, bs)
    progs = LoopPrograms(eng, n_slots=n, block_size=bs, nb_max=nb_max)
    with eng._lock:
        template = progs.warm_wave(16, 1)[0]

    def pool(x):
        return jnp.zeros((nbp, bs, int(np.prod(x.shape[2:]))), x.dtype)

    def rows(x):
        return jnp.zeros((n,) + tuple(x.shape[1:]), x.dtype)

    state = PagedState(
        cache_k=[pool(c) for c in template.cache_k],
        cache_v=[pool(c) for c in template.cache_v],
        key_valid=jnp.zeros((n, nb_max * bs), jnp.int32),
        write_idx=jnp.zeros(n, jnp.int32), pos=jnp.zeros(n, jnp.int32),
        last_token=jnp.zeros(n, jnp.int32), done=jnp.ones(n, bool),
        tokens=rows(template.tokens),
        sample=jax.tree.map(rows, template.sample),
    )
    if what == "paged_chunk":
        text = progs.paged_chunk_hlo(
            state, np.full((n, nb_max), nbp, np.int32), debug_info=True)
        assert "decode_chunk" in text
    else:
        text = progs.paged_insert_hlo(state, 16)
        assert "HloModule" in text and "scatter" in text
    assert set(progs.built) == {what} and not is_consumed(state)


def _read_swap_gather(eng, cdl):
    return cdl.programs.swap_gather_fn()(cdl._state, np.zeros(2, np.int32))


def _read_gather_prefix(eng, cdl):
    return cdl._gather_prefix(8, [0])


def _read_hlo(eng, cdl):
    return cdl.programs.paged_chunk_hlo(cdl._state, cdl._table)


@pytest.mark.parametrize(
    "read", [_read_swap_gather, _read_gather_prefix, _read_hlo],
    ids=["swap_gather", "gather_prefix", "chunk_hlo"],
)
def test_readers_leave_the_state_live(read):
    eng, cdl = _paged_loop()
    try:
        out = read(eng, cdl)
        assert not is_consumed(cdl._state)
        # ... and what they return outlives the state they read.
        cdl._state, *_ = _step_chunk(eng, cdl)
        assert not is_consumed(out)
        if not isinstance(out, str):
            jax.device_get(out)
    finally:
        cdl.stop()


@pytest.mark.parametrize("family", ["gpt", "t5", "t5-spec"])
def test_contiguous_chunk_and_insert_consume_their_state(family):
    bundle = tiny_gpt_bundle() if family == "gpt" else tiny_t5_bundle()
    spec = family == "t5-spec"
    cfg = _cfg(
        paged_kv=False, max_decode_len=16,
        spec_decode="ngram" if spec else None,
        spec_continuous=spec,
    )
    eng = _engine(bundle, cfg)
    cdl = ContinuousDecodeLoop(eng, cfg)
    try:
        assert cdl.spec == spec
        cdl._build_empty_state()
        prev = cdl._state
        state1, ids, mask = cdl.programs.warm_wave(16, 1)
        if spec:
            feats0 = {"input_ids": np.ones(16, np.int32),
                      "length": np.int32(16)}
            hist = cdl._hist_row(feats0, np.zeros(eng.chunk_tokens, np.int32))
            cdl._state = cdl.programs.insert_fn()(
                prev, state1, ids, mask, hist, np.int32(0), np.int32(0)
            )
        else:
            cdl._state = cdl.programs.insert_fn()(
                prev, state1, np.int32(0), np.int32(0)
            )
        assert all(x.is_deleted() for x in _leaves(prev))
        assert not is_consumed(state1)
        prev = cdl._state
        if spec:
            cdl._state, out, ns, done = eng._spec_chunk(
                eng.params, prev, eng.chunk_tokens, eng.spec_k, False
            )
        else:
            cdl._state, out, done = eng._gen_chunk(
                cdl._mp(n=cdl.n_slots), prev, eng.chunk_tokens, False
            )
        assert all(x.is_deleted() for x in _leaves(prev))
        assert jax.device_get(done).shape == (cdl.n_slots,)
        assert not is_consumed(cdl._state)
    finally:
        cdl.stop()


# ---------------------------------------------------------------------------
# (c) the warm grid hands one state along


@pytest.mark.parametrize("family", ["llama", "gpt"])
def test_warm_grid_ends_with_a_live_state_and_serves_without_compiling(family):
    bundle = tiny_llama_bundle() if family == "llama" else tiny_gpt_bundle()
    cfg = _cfg(stream_pipeline=2, max_decode_len=16, seq_buckets=(8, 16))
    eng = _engine(bundle, cfg)
    cdl = ContinuousDecodeLoop(eng, cfg)
    try:
        cdl.warm()
        assert cdl._state is not None and not is_consumed(cdl._state)
        assert bool(jax.device_get(cdl._state.done).all())  # reset: all dead
        assert eng.kv_pool.used_blocks == 0
        feats = _prompts(4, seed=2)

        async def drive():
            return await asyncio.gather(
                *[_consume(cdl.submit_stream(dict(f, max_tokens=16)))
                  for f in feats]
            )

        with CompileWindow() as window:
            outs = asyncio.run(drive())
        assert all(len(o) == 16 for o in outs)
        assert window.compiles == 0
    finally:
        cdl.stop()


# ---------------------------------------------------------------------------
# (d) failure semantics


def _retries() -> float:
    fam = metrics.DISPATCH_RETRIES
    return sum(
        s.value for m in fam.collect() for s in m.samples
        if s.name.endswith("_total")
    )


def test_injected_transient_chunk_fault_retries_token_identically():
    """An injected fault fires BEFORE the dispatch (Watchdog._attempt):
    the state it would have consumed is still live and the retry is
    exact — donation changes nothing here."""
    bundle = tiny_llama_bundle()
    cfg = _cfg(fault_spec="chunk:transient@2+2", dispatch_retries=3,
               dispatch_backoff_s=0.001, stream_pipeline=2)
    eng = _engine(bundle, cfg)
    feats = _prompts(3)
    solos = [_solo(_engine(bundle, _cfg(paged_kv=False)), f) for f in feats]
    cdl = ContinuousDecodeLoop(eng, cfg)  # no supervisor: the retry absorbs
    try:
        async def drive():
            return await asyncio.gather(
                *[_consume(cdl.submit_stream(dict(f))) for f in feats]
            )

        assert asyncio.run(drive()) == solos
        assert eng.faults.rules[0].fired == 2
    finally:
        cdl.stop()


def test_watchdog_does_not_retry_once_the_state_is_consumed():
    """``dispatch_guard(donates=...)``: a transient-looking error raised
    after the donated state was taken is fatal — one attempt."""
    eng = _engine(tiny_gpt_bundle(), _cfg(dispatch_retries=3,
                                          dispatch_backoff_s=0.001))
    assert isinstance(eng.watchdog, Watchdog) and eng.watchdog.retries == 3
    step = jax.jit(lambda s: s + 1, donate_argnums=(0,))
    calls = []

    def flaky(state, consume: bool):
        def fn():
            calls.append(state.is_deleted())
            if consume:
                step(state)
            if len(calls) < 3:
                raise ConnectionError("link flapped")
            return "ok"

        return fn

    live = jnp.zeros((4,))
    assert eng.dispatch_guard("chunk", flaky(live, False), donates=live) == "ok"
    assert calls == [False, False, False]  # retried: the state was live
    calls.clear()
    with pytest.raises(StateConsumedError) as err:
        eng.dispatch_guard("chunk", flaky(live, True), donates=live)
    assert calls == [False]  # never run again on deleted arrays
    assert isinstance(err.value.__cause__, ConnectionError)


def _fail_after_consuming(cdl, attr: str, nth: int):
    """Make the ``nth`` call of the loop's executable ``attr`` run for
    real — consuming the state it donates — and then fail like a flaky
    link.  Returns the list of ``is_consumed(state)`` seen at each call."""
    getattr(cdl.programs, f"{attr}_fn")()  # build the wrapper
    real = cdl.programs.built[attr]
    seen = []
    state_arg = 1 if attr == "paged_chunk" else 0

    def flaky(*args):
        seen.append(is_consumed(args[state_arg]))
        out = real(*args)
        if len(seen) == nth:
            raise ConnectionError("link flapped after the dispatch")
        return out

    cdl.programs.built[attr] = flaky
    return seen


@pytest.mark.parametrize("attr,nth", [("paged_chunk", 2), ("paged_insert", 2)])
def test_failure_after_consumption_rebuilds_and_holds_the_swap(attr, nth):
    bundle = tiny_llama_bundle()
    cfg = _cfg(dispatch_retries=2, dispatch_backoff_s=0.001,
               engine_restarts_max=2, kv_host_budget_mb=1.0)
    eng = _engine(bundle, cfg)
    assert eng.kv_host is not None
    feats = _prompts(3)
    solos = [_solo(_engine(bundle, _cfg(paged_kv=False)), f) for f in feats]
    cdl = ContinuousDecodeLoop(eng, cfg)
    cdl.supervisor = Supervisor(cfg)
    seen = _fail_after_consuming(cdl, attr, nth)
    retries0 = _retries()
    try:
        async def drive():
            tasks = [
                asyncio.ensure_future(_consume(cdl.submit_stream(dict(f))))
                for f in feats[:2]
            ]
            if attr == "paged_insert":
                # A wave is ONE insert: the third stream comes as a wave
                # of its own, and its insert (the nth) consumes the
                # state the first wave's streams live in.
                for _ in range(400):
                    if cdl.chunk_dispatches >= 1:
                        break
                    await asyncio.sleep(0.01)
            tasks += [
                asyncio.ensure_future(_consume(cdl.submit_stream(dict(f))))
                for f in feats[2:]
            ]
            return await asyncio.gather(*tasks)

        assert asyncio.run(drive()) == solos  # resumed by recompute
        assert len(seen) > nth and not any(seen), (
            "an executable was called on a consumed state"
        )
        assert _retries() == retries0, "retried as if transient"
        assert cdl.supervisor.restarts == 1 and not cdl.supervisor.failed
        assert cdl.swap_outs == 0, "swapped out of pools that were gone"
        assert not is_consumed(cdl._state)
        for _ in range(100):
            if eng.kv_pool.used_blocks == 0:
                break
            time.sleep(0.05)
        assert eng.kv_pool.used_blocks == 0
    finally:
        cdl.stop()


@pytest.mark.parametrize("half", ["host", "device"])
@pytest.mark.parametrize("supervised", [True, False])
def test_chunk_fault_in_a_wave_iteration_loses_no_stream(supervised, half):
    """An iteration that holds a wave beside live work runs the chunk's
    host half (the growth pass and the table), the wave's start, then
    the chunk's device dispatch.  A fault in the host half finds the
    wave's streams popped and reserved in ``_pending_wave``; one raised
    by the device dispatch finds them in ``_pending_admissions``, started
    and not yet fetched.  Supervised, ``_recover`` requeues them beside
    the live stream and every stream reads what it reads alone, once;
    unsupervised, the loop's handler ends each with the error — none
    hangs, none is answered twice — and the next request is served from
    a rebuilt state.  Either way the pool drains."""
    bundle = tiny_llama_bundle()
    cfg = _cfg(engine_restarts_max=2)
    eng = _engine(bundle, cfg)
    fa, fb = _prompts(2)
    solos = [_solo(_engine(bundle, _cfg(paged_kv=False)), f) for f in (fa, fb)]
    cdl = ContinuousDecodeLoop(eng, cfg)
    if supervised:
        cdl.supervisor = Supervisor(cfg)
    from test_decode_dispatch import _b_meets_a_live

    # B is popped by an iteration that finds A live, whatever the pace:
    # the first chunk whose half runs after the gate opened is that
    # iteration's, whether or not the loop has the wave on its books.
    met = _b_meets_a_live(cdl)
    attr = "_chunk_table" if half == "host" else "_dispatch_chunk_inner"
    real_half = getattr(cdl, attr)
    pending_at_fault = []

    def faulty(*args):
        if met and not pending_at_fault:
            pending_at_fault.append((
                [st.rid for st in cdl._pending_wave],
                [st.rid for st, *_ in cdl._pending_admissions]))
            raise RuntimeError("device fault at the chunk beside a wave")
        return real_half(*args)

    setattr(cdl, attr, faulty)
    try:
        async def drive():
            gen_a = cdl.submit_stream(dict(fa))
            got_a = np.asarray(await gen_a.__anext__()).tolist()
            b = asyncio.ensure_future(_consume(cdl.submit_stream(dict(fb))))

            async def rest_of_a():
                return got_a + await _consume(gen_a)

            return await asyncio.wait_for(asyncio.gather(
                rest_of_a(), b, return_exceptions=True), 120)

        got = asyncio.run(drive())
        assert met == [True]
        (in_wave, started), = pending_at_fault
        assert (len(in_wave), len(started)) == (
            (1, 0) if half == "host" else (0, 1))
        assert not cdl._pending_admissions and not cdl._pending_wave
        if supervised:
            assert got == solos
            assert cdl.supervisor.restarts == 1
        else:
            assert all(isinstance(g, RuntimeError) for g in got), got

            async def after():
                return await _consume(cdl.submit_stream(dict(fb)))

            assert asyncio.run(after()) == solos[1]
        assert not is_consumed(cdl._state)
        for _ in range(100):
            if eng.kv_pool.used_blocks == 0:
                break
            time.sleep(0.05)
        assert eng.kv_pool.used_blocks == 0
    finally:
        cdl.stop()


def test_injected_fatal_still_swaps_out_of_the_live_pools():
    """The twin of the case above: an INJECTED fault fires before the
    dispatch, the pre-fault pools are intact, and the checkpoint's swap
    runs as it did before the rule."""
    bundle = tiny_llama_bundle()
    cfg = _cfg(fault_spec="chunk:fatal@3", engine_restarts_max=2,
               kv_host_budget_mb=1.0)
    eng = _engine(bundle, cfg)
    feats = _prompts(3)
    solos = [_solo(_engine(bundle, _cfg(paged_kv=False)), f) for f in feats]
    cdl = ContinuousDecodeLoop(eng, cfg)
    cdl.supervisor = Supervisor(cfg)
    try:
        async def drive():
            return await asyncio.gather(
                *[_consume(cdl.submit_stream(dict(f))) for f in feats]
            )

        assert asyncio.run(drive()) == solos
        assert cdl.supervisor.restarts == 1
        assert cdl.swap_outs >= 1
    finally:
        cdl.stop()


def test_unsupervised_failure_after_consumption_rebuilds_lazily():
    """No supervisor: the streams that lived in the consumed state end
    with the error, the state is dropped, and the next request is served
    from a rebuilt one."""
    bundle = tiny_llama_bundle()
    cfg = _cfg(dispatch_retries=2, dispatch_backoff_s=0.001)
    eng = _engine(bundle, cfg)
    feats = _prompts(2)
    cdl = ContinuousDecodeLoop(eng, cfg)
    seen = _fail_after_consuming(cdl, "paged_chunk", 2)
    try:
        async def doomed():
            return await asyncio.gather(
                *[_consume(cdl.submit_stream(dict(f))) for f in feats],
                return_exceptions=True,
            )

        got = asyncio.run(doomed())
        assert all(isinstance(g, StateConsumedError) for g in got), got
        assert seen == [False, False]

        async def after():
            return await _consume(cdl.submit_stream(dict(feats[0])))

        want = _solo(_engine(bundle, _cfg(paged_kv=False)), feats[0])
        assert asyncio.run(after()) == want
        assert not is_consumed(cdl._state)
    finally:
        cdl.stop()
