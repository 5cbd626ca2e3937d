"""One dispatch for a boundary's prompt windows (PR 36): the windows of
DIFFERENT prompts that ``PREFILL_BUDGET`` admits between two decode
chunks run as one batched ``paged_prefill_chunk`` — ``[B, C]`` tokens,
``B`` tables, ``B`` starts.

The judged contracts:
(a) a ``B = 3`` call with three different tables, starts (0, mid-prompt,
    a last short window with pad rows) and lengths leaves the pool equal
    to three ``B = 1`` calls — dense, int8 pool pairs, expert (a shared
    expert and window layers, through the prompt-window kernel), latent
    (through the kernel) and ``gpt.py``;
(b) the loop with ``PREFILL_BUDGET = 3 x PREFILL_CHUNK`` serves greedy
    streams token-identical to ``PREFILL_BUDGET = PREFILL_CHUNK`` and
    issues ONE ``prefill_chunk`` dispatch a boundary;
(c) a job of a batch that finds the pool dry at growth is checkpointed
    while the others dispatch; a fault at the batched ``prefill_chunk``
    site checkpoints (supervised, fatal) or fails (unsupervised) every job
    of the batch — none lost, none served twice;
(d) adapter slots follow their rows;
(e) every batch width that can occur is warmed: no compile after
    ``warm()``.
"""

import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlmicroservicetemplate_tpu.engine import InferenceEngine
from mlmicroservicetemplate_tpu.engine.streams import ContinuousDecodeLoop
from mlmicroservicetemplate_tpu.engine.supervisor import Supervisor
from mlmicroservicetemplate_tpu.models import gpt as gpt_mod
from mlmicroservicetemplate_tpu.models import llama as llama_mod
from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
from mlmicroservicetemplate_tpu.utils import metrics

from helpers import TINY_GPT, TINY_LLAMA, tiny_gpt_bundle, tiny_llama_bundle
from test_prefill_chunked import (
    _cfg, _consume, _prompt, _run, _solo_tokens, _wait_pool_drained,
)

# ---------------------------------------------------------------------------
# (a) the model function: one [3, C] call against three [1, C] calls

C, BS, T_W = 8, 4, 8  # window, block size, table width (32 positions a row)
#: (prompt length, start of the compared window): a first window, a
#: mid-prompt one, and a last short window (5 real tokens, 3 pad rows).
ROWS = ((12, 0), (28, 16), (21, 16))


def _toy(variant: str):
    """(module, cfg, params, pool leaf shape) of one model variant."""
    if variant == "gpt":
        cfg = gpt_mod.GPTConfig(**{**TINY_GPT, "eos_id": 1, "pad_id": 0})
        return gpt_mod, cfg, gpt_mod.init_params(jax.random.PRNGKey(0), cfg), (
            cfg.num_heads * cfg.head_dim,)
    if variant in ("dense", "dense-int8"):
        cfg = llama_mod.LlamaConfig(
            **{**TINY_LLAMA, "eos_id": 1, "pad_id": 0},
            kv_quant=variant == "dense-int8")
    else:
        from cellbench import spec as bench_spec

        import test_deepseek_block as dsv2
        import test_trinity_block as trinity

        name, toy = {
            "expert": ("trinity-mini-d5", trinity.TOY),
            "latent": ("deepseek-v2-ep4-d5", dsv2.TOY),
        }[variant]
        config = {**bench_spec.load_json(
            f"{bench_spec.HERE}/configs/{name}.json"), **toy}
        over = json.loads(bench_spec.service_env(config)["LLAMA_CONFIG"])
        # the served path: the prompt-window kernel (interpret mode here)
        cfg = llama_mod.LlamaConfig(
            **over, eos_id=1, pad_id=0, pallas_interpret=True, pallas_decode=True)
    params = llama_mod.init_params(jax.random.PRNGKey(0), cfg)
    width = cfg.latent_lanes if cfg.mla else cfg.num_kv_heads * cfg.head_dim
    return llama_mod, cfg, params, (width,)


def _empty_pools(cfg, nb: int, width: tuple):
    from mlmicroservicetemplate_tpu.models.gpt import PagedState
    from mlmicroservicetemplate_tpu.models.sampling import greedy_params

    def pool():
        if getattr(cfg, "kv_quant", False):
            return (jnp.zeros((nb, BS) + width, jnp.int8),
                    jnp.ones((nb, BS, cfg.num_kv_heads), jnp.float32))
        return jnp.zeros((nb, BS) + width)

    n, rows = cfg.num_layers, len(ROWS)
    return PagedState(
        cache_k=[pool() for _ in range(n)],
        cache_v=[] if getattr(cfg, "mla", False) else [pool() for _ in range(n)],
        key_valid=jnp.zeros((rows, T_W * BS), jnp.int32),
        write_idx=jnp.zeros((rows,), jnp.int32), pos=jnp.zeros((rows,), jnp.int32),
        last_token=jnp.zeros((rows,), jnp.int32), done=jnp.ones((rows,), bool),
        tokens=jnp.zeros((rows, 4), jnp.int32), sample=greedy_params(rows),
    )


def _window(ids, start):
    """(ids [1, C], mask [1, C]) of the window of ``ids`` at ``start``."""
    w, m = np.zeros((1, C), np.int32), np.zeros((1, C), np.int32)
    n = max(0, min(C, len(ids) - start))
    w[0, :n], m[0, :n] = ids[start:start + n], 1
    return w, m


@pytest.mark.parametrize(
    "variant", ["dense", "dense-int8", "expert", "latent", "gpt"])
def test_a_batched_dispatch_writes_what_single_dispatches_write(variant):
    """Three prompts' windows — at start 0, mid-prompt, and a last short
    window with pad rows; three tables scattered over one pool — in ONE
    ``[3, C]`` call leave every pool leaf as three ``[1, C]`` calls do
    (each row's earlier windows ran alone on both sides), write nothing
    outside the three rows' own blocks, and neither call order matters;
    two windows and a masked row leave it as the two calls do."""
    mod, cfg, params, width = _toy(variant)
    rng = np.random.default_rng(3)
    nb = len(ROWS) * T_W + 2
    tables = jnp.asarray(rng.permutation(nb - 2)[: len(ROWS) * T_W].reshape(
        len(ROWS), T_W).astype(np.int32))
    prompts = [rng.integers(5, 100, n).astype(np.int32) for n, _ in ROWS]
    step = jax.jit(lambda st, tb, i, m, s: mod.paged_prefill_chunk(
        params, cfg, st, tb, jnp.asarray(i), jnp.asarray(m), s))

    state = _empty_pools(cfg, nb, width)
    for r, (ids, (_, upto)) in enumerate(zip(prompts, ROWS)):
        for start in range(0, upto, C):  # the earlier windows, alone
            state = step(state, tables[r:r + 1], *_window(ids, start),
                         jnp.asarray([start], jnp.int32))
    before = jax.tree.leaves((state.cache_k, state.cache_v))

    alone = state
    for r in (2, 0, 1):  # any order: the rows' blocks are disjoint
        alone = step(alone, tables[r:r + 1], *_window(prompts[r], ROWS[r][1]),
                     jnp.asarray([ROWS[r][1]], jnp.int32))
    wins = [_window(ids, start) for ids, (_, start) in zip(prompts, ROWS)]
    together = step(
        state, tables, np.concatenate([w for w, _ in wins]),
        np.concatenate([m for _, m in wins]),
        jnp.asarray([start for _, start in ROWS], jnp.int32))

    got = jax.tree.leaves((together.cache_k, together.cache_v))
    want = jax.tree.leaves((alone.cache_k, alone.cache_v))
    assert len(got) == len(want) == len(before) > 0
    wrote = False
    for g, w, b in zip(got, want, before):
        assert g.shape == w.shape and g.dtype == w.dtype
        if g.dtype == jnp.int8:  # a rounding boundary may move one step
            assert int(jnp.max(jnp.abs(g.astype(jnp.int32) - w.astype(jnp.int32)))) <= 1
        else:
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5, atol=2e-6)
        # the same positions written, and only inside the rows' windows
        np.testing.assert_array_equal(np.asarray(g != b), np.asarray(w != b))
        wrote |= bool((g != b).any())
        np.testing.assert_array_equal(np.asarray(g[nb - 2:]), np.asarray(b[nb - 2:]))
    assert wrote
    # A batch short of the full width rides it with a MASKED row (no
    # token, every table entry the sentinel): rows 0 and 1 as alone, row
    # 2's blocks and everything else untouched, nothing but finite values.
    two = state
    for r in (0, 1):
        two = step(two, tables[r:r + 1], *wins[r], jnp.asarray([ROWS[r][1]], jnp.int32))
    pad_tables = tables.at[2].set(nb)
    padded = step(
        state, pad_tables, np.concatenate([wins[0][0], wins[1][0], 0 * wins[2][0]]),
        np.concatenate([wins[0][1], wins[1][1], 0 * wins[2][1]]),
        jnp.asarray([ROWS[0][1], ROWS[1][1], 0], jnp.int32))
    for g, w in zip(jax.tree.leaves((padded.cache_k, padded.cache_v)),
                    jax.tree.leaves((two.cache_k, two.cache_v))):
        assert bool(jnp.isfinite(g.astype(jnp.float32)).all())
        if g.dtype == jnp.int8:
            assert int(jnp.max(jnp.abs(g.astype(jnp.int32) - w.astype(jnp.int32)))) <= 1
        else:
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5, atol=2e-6)
    # every field that belongs to a LIVE row is untouched
    for name in ("key_valid", "write_idx", "pos", "last_token", "done", "tokens"):
        np.testing.assert_array_equal(
            np.asarray(getattr(together, name)), np.asarray(getattr(state, name)))


# ---------------------------------------------------------------------------
# (b)-(e) the loop


def _paged_loop(bundle, **kw):
    kw = {"prefill_chunk": 8, "prefill_budget": 24, "prefill_max_prompt": 48,
          "paged_kv": True, "kv_block_size": 8, "max_stream_queue": 4, **kw}
    cfg = _cfg(**kw)
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    return cfg, eng, ContinuousDecodeLoop(eng, cfg)


def _feats(seed: int, lengths):
    rng = np.random.default_rng(seed)
    return [{"input_ids": p, "length": np.int32(len(p))}
            for p in (_prompt(rng, n) for n in lengths)]


def _spy_dispatches(cdl) -> list:
    """Every ``_advance_prefill`` call that dispatched: (streams live at
    its entry, ``prefill_chunk`` dispatches it made, windows they held)."""
    seen, inner, stats = [], cdl._advance_prefill, cdl.engine.dispatch_stats

    def spy():
        live = bool(cdl.active)
        d0 = stats.get("prefill_chunk", [0])[0]
        w0 = cdl.prefill_chunk_dispatches
        out = inner()
        d1 = stats.get("prefill_chunk", [0])[0]
        if d1 > d0:
            seen.append((live, d1 - d0, cdl.prefill_chunk_dispatches - w0))
        return out

    cdl._advance_prefill = spy
    return seen


def _hold_prefill_until(cdl, n_jobs: int) -> None:
    """No window is dispatched before ``n_jobs`` prompts are pending: the
    first dispatch then holds them all (admission pops streams one loop
    iteration at a time, so an ungated first batch may hold one)."""
    inner, opened = cdl._advance_prefill, []

    def gated():
        if not opened and len(cdl._prefilling) < n_jobs:
            return False
        opened.append(True)
        return inner()

    cdl._advance_prefill = gated


def _counter(fam, name: str) -> float:
    return sum(s.value for m in fam.collect() for s in m.samples
               if s.name.endswith("_total") and s.labels.get("model") == name)


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_a_boundarys_windows_ride_one_dispatch_token_identically(family):
    """``PREFILL_BUDGET = 3 x PREFILL_CHUNK`` against ``= PREFILL_CHUNK``:
    the same greedy tokens (and the monolithic engine's), the pool
    drained — and a chunk boundary with streams live issues ONE
    ``prefill_chunk`` dispatch of up to three windows, where the narrow
    budget issues one of one."""
    bundle = tiny_gpt_bundle() if family == "gpt" else tiny_llama_bundle()
    feats = _feats(0, (45, 19, 30, 41, 9, 27))
    eng0 = InferenceEngine(bundle, _cfg(), ReplicaSet(make_mesh(1)))
    solos = [_solo_tokens(eng0, f) for f in feats]
    name = bundle.name
    outs, widths = {}, {}
    for budget in (8, 24):
        b0 = _counter(metrics.PREFILL_WINDOWS_BATCHED, name)
        a0 = _counter(metrics.PREFILL_WINDOWS_ALONE, name)
        _, eng, cdl = _paged_loop(bundle, prefill_budget=budget)
        seen = _spy_dispatches(cdl)
        try:
            outs[budget] = _run(cdl, feats)
            assert _wait_pool_drained(eng.kv_pool) == 0
        finally:
            cdl.stop()
        assert cdl._prefill_width == budget // 8
        live = [(d, w) for was_live, d, w in seen if was_live]
        assert live and all(d == 1 for d, _ in live), seen
        widths[budget] = sorted({w for _, w in live})
        # idle compute backfills past the budget, the width bounds a dispatch
        assert all(w <= d * cdl._prefill_width for _, d, w in seen), seen
        batched = _counter(metrics.PREFILL_WINDOWS_BATCHED, name) - b0
        alone = _counter(metrics.PREFILL_WINDOWS_ALONE, name) - a0
        assert batched + alone == cdl.prefill_chunk_dispatches  # windows
        assert (batched > 0) == (budget > 8)
        n_dispatches = eng.dispatch_stats["prefill_chunk"][0]
        assert n_dispatches == sum(d for _, d, _ in seen)
        assert (n_dispatches < cdl.prefill_chunk_dispatches) == (budget > 8)
    assert outs[8] == outs[24] == solos
    assert widths[8] == [1] and max(widths[24]) == 3, widths


def test_a_job_the_pool_cannot_grow_leaves_the_batch():
    """``grow:oob`` on the second job of a three-job batch: that job is
    checkpointed (its blocks released, re-queued through admission), the
    other two windows go out in the same dispatch, and all three streams
    complete token-identically."""
    bundle = tiny_gpt_bundle()
    feats = _feats(4, (30, 26, 22))
    eng0 = InferenceEngine(bundle, _cfg(), ReplicaSet(make_mesh(1)))
    solos = [_solo_tokens(eng0, f) for f in feats]
    _, eng, cdl = _paged_loop(
        bundle, fault_spec="grow:oob@2")
    held, inner = [], cdl._dispatch_prefill_window

    def spy(jobs):
        out = inner(jobs)
        held.append((len(jobs), len(out)))
        return out

    cdl._dispatch_prefill_window = spy
    _hold_prefill_until(cdl, 3)
    stalls0 = _counter(metrics.KV_GROWTH_STALLS, bundle.name)
    try:
        assert _run(cdl, feats) == solos
        assert _wait_pool_drained(eng.kv_pool) == 0
    finally:
        cdl.stop()
    assert _counter(metrics.KV_GROWTH_STALLS, bundle.name) - stalls0 == 1
    assert held[0] == (3, 2), held  # three chosen, two dispatched together
    assert all(got == asked for asked, got in held[1:]), held


@pytest.mark.parametrize("supervised", [True, False])
def test_a_fault_at_the_batched_site_is_every_jobs_of_the_batch(supervised):
    """A fault injected at the ``prefill_chunk`` site of a three-window
    dispatch.  Supervised and fatal: every job of the batch is
    checkpointed and requeued, the engine rebuilt once, and each stream
    is served exactly once, token-identically.  Unsupervised: every
    consumer of the batch gets the error, nothing else does, and the
    pool drains."""
    bundle = tiny_gpt_bundle()
    feats = _feats(5, (30, 26, 22))
    eng0 = InferenceEngine(bundle, _cfg(), ReplicaSet(make_mesh(1)))
    solos = [_solo_tokens(eng0, f) for f in feats]
    _, eng, cdl = _paged_loop(
        bundle, dispatch_retries=0,
        fault_spec=f"prefill_chunk:{'fatal' if supervised else 'transient'}@2")
    if supervised:
        cdl.supervisor = Supervisor(_cfg())
    held, inner = [], cdl._dispatch_prefill_window

    def spy(jobs):
        held.append(len(jobs))
        return inner(jobs)

    cdl._dispatch_prefill_window = spy
    _hold_prefill_until(cdl, 3)

    async def body():
        return await asyncio.gather(
            *[_consume(cdl.submit_stream(dict(f))) for f in feats],
            return_exceptions=True)

    try:
        outs = asyncio.run(body())
        assert held[:2] == [3, 3], held  # the faulted dispatch held three
        if supervised:
            assert outs == solos
            assert cdl.supervisor.restarts == 1
        else:
            assert all(isinstance(o, Exception) for o in outs), outs
            # the loop serves on: a fresh stream is token-identical
            assert _run(cdl, feats[:1]) == solos[:1]
        assert _wait_pool_drained(eng.kv_pool) == 0
    finally:
        cdl.stop()


def test_adapter_slots_follow_their_rows(tmp_path):
    """Three prompts under three adapters (alpha, beta, none) whose
    windows share their dispatches: each row's LoRA delta is its own —
    tokens equal the same request served alone."""
    from mlmicroservicetemplate_tpu.scheduler.batcher import Batcher
    from test_tenancy import _write_adapters

    adir = _write_adapters(tmp_path)
    bundle = tiny_gpt_bundle()
    cfg = _cfg(adapter_dir=adir, adapter_slots=2, paged_kv=True, kv_block_size=8,
               prefill_chunk=8, prefill_budget=24, prefill_max_prompt=48,
               max_decode_len=8, batch_timeout_ms=1.0)
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    batcher = Batcher(eng, cfg)
    base = _feats(6, (29,))[0]
    reqs = [dict(base, adapter_id="alpha"), dict(base, adapter_id="beta"), base]
    name = bundle.name

    async def body():
        solo = [await _consume(batcher.submit_stream(dict(f))) for f in reqs]
        b0 = _counter(metrics.PREFILL_WINDOWS_BATCHED, name)
        mixed = await asyncio.gather(
            *[_consume(batcher.submit_stream(dict(f))) for f in reqs])
        return solo, mixed, _counter(metrics.PREFILL_WINDOWS_BATCHED, name) - b0

    try:
        solo, mixed, batched = asyncio.run(body())
    finally:
        asyncio.run(batcher.stop())
    assert mixed == solo
    assert batched > 0  # the three prompts' windows did share dispatches
    assert solo[0] != solo[2] and solo[1] != solo[2] and solo[0] != solo[1]


@pytest.mark.parametrize("budget", [8, 24])
def test_every_batch_width_is_warmed(budget, monkeypatch):
    """After ``warm()`` no prefill dispatch compiles, however many
    windows it holds: 1 .. ``PREFILL_BUDGET / PREFILL_CHUNK`` — a window
    alone and the full width have their executables, a batch in between
    rides the full width with masked rows (the benchmark refuses a run
    that compiles)."""
    from mlmicroservicetemplate_tpu.runtime.compile_cache import CompileWindow

    monkeypatch.setenv("WARMUP_SAMPLING", "0")
    bundle = tiny_gpt_bundle()
    _, eng, cdl = _paged_loop(bundle, prefill_budget=budget)
    seen = _spy_dispatches(cdl)
    shapes, fn = set(), cdl.programs.paged_prefill_fn

    def spy_fn():
        inner = fn()

        def call(params, state, tables, ids, mask, starts):
            shapes.add((tables.shape[0], ids.shape[0], mask.shape[0], starts.shape[0]))
            return inner(params, state, tables, ids, mask, starts)

        return call

    cdl.programs.paged_prefill_fn = spy_fn
    try:
        cdl.warm()
        assert eng.kv_pool.used_blocks == 0
        with CompileWindow() as w:
            for lengths in ((45,), (30, 19), (45, 19, 30, 41)):
                outs = _run(cdl, _feats(7, lengths))
                assert all(len(o) > 0 for o in outs)
    finally:
        cdl.stop()
    assert w.compiles == 0, f"{w.compiles} compiles: {w.names}"
    widths = {-(-n // d) for _, d, n in seen}
    assert widths >= set(range(1, budget // 8 + 1)), seen
    # two executables whatever the budget: a window alone, the full width
    assert shapes == {(b,) * 4 for b in {1, budget // 8}}, shapes
