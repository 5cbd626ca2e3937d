"""Continuous-batching decode loop tests (engine/streams.py).

The contract under test:
1. N concurrent streams produce tokens IDENTICAL to solo runs — rows
   decode independently at their own positions.
2. Total chunk dispatches scale with the LONGEST stream, not the
   stream count (the whole point of sharing one batched dispatch).
3. Cancelled streams free their slot at the next chunk boundary.
4. Sampling: seeded streams are deterministic and batch-composition
   independent; greedy streams stay exact.
"""

import asyncio
import time
from typing import NamedTuple

import numpy as np
import pytest

from mlmicroservicetemplate_tpu.engine import InferenceEngine
from mlmicroservicetemplate_tpu.engine.streams import ContinuousDecodeLoop
from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
from mlmicroservicetemplate_tpu.utils.config import ServiceConfig

from helpers import one_wave, text_feats, tiny_t5_bundle


def _cfg(**kw) -> ServiceConfig:
    kw.setdefault("device", "cpu")
    kw.setdefault("warmup", False)
    kw.setdefault("batch_buckets", (1, 2, 4, 8))
    kw.setdefault("seq_buckets", (16, 32))
    kw.setdefault("max_decode_len", 12)
    kw.setdefault("stream_chunk_tokens", 4)
    kw.setdefault("max_streams", 4)
    return ServiceConfig(**kw)


def _echo_bundle():
    """Per-row echo model: row i re-emits its own prompt ids, then the
    eos that the T5-style byte tokenizer appended — so every stream's
    token sequence is a pure function of its prompt, which makes
    cross-stream routing errors and position drift visible."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mlmicroservicetemplate_tpu.models.registry import KIND_SEQ2SEQ, ModelBundle
    from mlmicroservicetemplate_tpu.models.sampling import greedy_params
    from mlmicroservicetemplate_tpu.models.tokenizer import ByteTokenizer
    from mlmicroservicetemplate_tpu.runtime.device import default_policy

    class S(NamedTuple):
        src: jnp.ndarray  # [B, S]
        pos: jnp.ndarray  # [B]
        done: jnp.ndarray  # [B]
        tokens: jnp.ndarray  # [B, Tmax]
        sample: object

    def encode_fn(p, ids, mask):
        return ids

    def init_state_fn(p, src, mask, max_len: int, sample=None):
        b, s = src.shape
        return S(
            src,
            jnp.zeros((b,), jnp.int32),
            (mask.sum(axis=-1) == 0),
            jnp.zeros((b, max_len), jnp.int32),
            sample if sample is not None else greedy_params(b),
        )

    def generate_chunk_fn(p, s, n_steps: int, sample: bool = False):
        def step(st, _):
            b = st.pos.shape[0]
            rows = jnp.arange(b)
            tok = st.src[rows, jnp.minimum(st.pos, st.src.shape[1] - 1)]
            tok = jnp.where(st.done, jnp.int32(0), tok.astype(jnp.int32))
            done = st.done | (tok == 1)  # ByteTokenizer eos_id == 1
            tokens = st.tokens.at[rows, st.pos].set(tok, mode="drop")
            return S(st.src, st.pos + 1, done, tokens, st.sample), tok

        s, toks = lax.scan(step, s, None, length=n_steps)
        return s, jnp.transpose(toks)

    return ModelBundle(
        name="echo", kind=KIND_SEQ2SEQ, cfg=None, params={},
        policy=default_policy("cpu"),
        tokenizer=ByteTokenizer(add_eos=True), labels=None, forward=None,
        encode_fn=encode_fn, init_state_fn=init_state_fn,
        generate_chunk_fn=generate_chunk_fn,
    )


async def _consume(loop_obj, feats):
    out = []
    async for chunk in loop_obj.submit_stream(feats):
        out.append(np.asarray(chunk))
    return np.concatenate(out) if out else np.zeros(0, np.int32)


async def _collect(gen):
    out = []
    async for chunk in gen:
        out.append(np.asarray(chunk))
    return np.concatenate(out) if out else np.zeros(0, np.int32)


def _run_concurrent(loop_obj, feats_list):
    async def body():
        # Submit every stream before consuming any: all of them sit in
        # pending before the loop thread reaches its first admission
        # boundary, so one shared batch serves the whole wave.
        with one_wave(loop_obj):
            gens = [loop_obj.submit_stream(dict(f)) for f in feats_list]
        return await asyncio.gather(*[_collect(g) for g in gens])

    return asyncio.run(body())


def _solo_tokens(engine, feats):
    return np.concatenate(list(engine.generate_stream(dict(feats))))


def test_concurrent_streams_match_solo_and_share_dispatches():
    """4 concurrent echo streams: token identity with solo runs AND
    ~1/N the chunk dispatches of the per-stream design."""
    bundle = _echo_bundle()
    cfg = _cfg()
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    texts = ["abc", "hello world stream", "xy", "some mid-size text"]
    feats = [text_feats(bundle.tokenizer, t) for t in texts]
    solos = [_solo_tokens(eng, f) for f in feats]

    cdl = ContinuousDecodeLoop(eng, cfg)
    try:
        outs = _run_concurrent(cdl, feats)
        for got, want in zip(outs, solos):
            n = min(len(got), len(want))
            np.testing.assert_array_equal(got[:n], want[:n])
            # Streams may differ only in trailing pad-chunk granularity.
            assert not np.any(want[n:] != 0) and not np.any(got[n:] != 0)
        # Dispatch economics: 4 streams, budget 12, chunk 4 → solo would
        # cost 4 streams × 2 follow-up chunks = 8 chunk dispatches; the
        # shared loop pays at most the longest stream's chunks plus one
        # admission-staggering chunk per wave.
        # Wave batching: a multi-stream wave prefills as ONE batched
        # dispatch (racy wave formation may split it, never exceed N).
        assert 1 <= cdl.prefill_dispatches <= 4
        assert cdl.chunk_dispatches <= 4, cdl.chunk_dispatches
    finally:
        cdl.stop()


def test_late_admission_identity():
    """A stream admitted mid-flight (while another is decoding) still
    produces its solo tokens — insert into a live batch is exact."""
    bundle = _echo_bundle()
    cfg = _cfg(max_decode_len=16)
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    f_long = text_feats(bundle.tokenizer, "a fairly long prompt text!")
    f_late = text_feats(bundle.tokenizer, "late")
    solo_long = _solo_tokens(eng, f_long)
    solo_late = _solo_tokens(eng, f_late)

    cdl = ContinuousDecodeLoop(eng, cfg)

    async def body():
        t1 = asyncio.ensure_future(_consume(cdl, dict(f_long)))
        await asyncio.sleep(0.3)  # let the first stream get admitted
        t2 = asyncio.ensure_future(_consume(cdl, dict(f_late)))
        return await asyncio.gather(t1, t2)

    try:
        got_long, got_late = asyncio.run(body())
        n = min(len(got_long), len(solo_long))
        np.testing.assert_array_equal(got_long[:n], solo_long[:n])
        n = min(len(got_late), len(solo_late))
        np.testing.assert_array_equal(got_late[:n], solo_late[:n])
    finally:
        cdl.stop()


def test_cancel_frees_slot():
    """Breaking out of a stream releases its admission slot so new
    streams are accepted."""
    bundle = _echo_bundle()
    cfg = _cfg(max_streams=1, max_decode_len=32)
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    cdl = ContinuousDecodeLoop(eng, cfg)
    feats = text_feats(bundle.tokenizer, "spans several chunks")

    async def body():
        gen = cdl.submit_stream(dict(feats))
        async for _ in gen:
            break  # client disconnects after the first chunk
        await gen.aclose()
        # The slot must come back (released at a chunk boundary).
        for _ in range(100):
            if cdl._admitted == 0:
                break
            await asyncio.sleep(0.05)
        assert cdl._admitted == 0
        out = await _consume(cdl, dict(feats))
        assert len(out) > 0

    try:
        asyncio.run(body())
    finally:
        cdl.stop()


def test_admission_cap_503():
    from mlmicroservicetemplate_tpu.scheduler.batcher import QueueFullError

    bundle = _echo_bundle()
    cfg = _cfg(max_streams=2)
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    cdl = ContinuousDecodeLoop(eng, cfg)
    feats = text_feats(bundle.tokenizer, "abc")

    async def body():
        g1 = cdl.submit_stream(dict(feats))
        g2 = cdl.submit_stream(dict(feats))
        with pytest.raises(QueueFullError):
            cdl.submit_stream(dict(feats))
        # Drain both so stop() is clean.
        async for _ in g1:
            pass
        async for _ in g2:
            pass

    try:
        asyncio.run(body())
    finally:
        cdl.stop()


# ---------------------------------------------------------------------------
# real model: t5


def test_t5_concurrent_streams_match_solo():
    """Three concurrent t5 streams (different prompts/buckets) produce
    exactly their solo token sequences through the shared batch."""
    bundle = tiny_t5_bundle()
    cfg = _cfg(max_decode_len=8, seq_buckets=(16, 32))
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    texts = [
        "summarize: the quick fox",
        "translate: hello",
        "a different prompt here",
    ]
    feats = [text_feats(bundle.tokenizer, t) for t in texts]
    solos = [_solo_tokens(eng, f) for f in feats]
    cdl = ContinuousDecodeLoop(eng, cfg)
    try:
        outs = _run_concurrent(cdl, feats)
        for got, want in zip(outs, solos):
            n = min(len(got), len(want))
            np.testing.assert_array_equal(got[:n], want[:n])
    finally:
        cdl.stop()


def test_t5_sampled_stream_deterministic_under_seed():
    """temperature>0 + seed: the same request yields the same tokens
    solo and inside a batch with other (greedy) streams."""
    bundle = tiny_t5_bundle()
    cfg = _cfg(max_decode_len=8, seq_buckets=(16, 32))
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    f_sampled = text_feats(bundle.tokenizer, "summarize: the quick brown fox")
    f_sampled.update(temperature=0.8, top_k=0, top_p=1.0, seed=1234)
    f_greedy = text_feats(bundle.tokenizer, "another prompt")

    solo1 = _solo_tokens(eng, f_sampled)
    solo2 = _solo_tokens(eng, f_sampled)
    np.testing.assert_array_equal(solo1, solo2)

    cdl = ContinuousDecodeLoop(eng, cfg)
    try:
        outs = _run_concurrent(cdl, [f_sampled, f_greedy])
        n = min(len(outs[0]), len(solo1))
        np.testing.assert_array_equal(outs[0][:n], solo1[:n])
    finally:
        cdl.stop()


def test_t5_sampling_seeds_differ_and_topk1_is_greedy():
    bundle = tiny_t5_bundle()
    cfg = _cfg(max_decode_len=8, seq_buckets=(16, 32))
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    base = text_feats(bundle.tokenizer, "summarize: the quick brown fox")
    greedy = _solo_tokens(eng, dict(base))

    diffs = 0
    for seed in (7, 8, 9):
        f = dict(base)
        f.update(temperature=5.0, top_k=0, top_p=1.0, seed=seed)
        toks = _solo_tokens(eng, f)
        n = min(len(toks), len(greedy))
        if not np.array_equal(toks[:n], greedy[:n]):
            diffs += 1
    assert diffs >= 2, "high-temperature sampling should usually diverge"

    f = dict(base)
    f.update(temperature=1.0, top_k=1, top_p=1.0, seed=42)
    toks = _solo_tokens(eng, f)
    n = min(len(toks), len(greedy))
    np.testing.assert_array_equal(toks[:n], greedy[:n])


def test_padded_prefill_does_not_clobber_neighbor_slot():
    """When the prefill batch is padded past 1 row (batch bucket floor /
    replica pad multiple), insert must write ONLY row 0 — a full-width
    write would overwrite the adjacent live stream's state."""
    bundle = _echo_bundle()
    # batch bucket floor of 2: every batch=1 prefill is padded to 2 rows.
    cfg = _cfg(batch_buckets=(2, 4), max_decode_len=16, max_streams=4)
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    f_a = text_feats(bundle.tokenizer, "first stream text!")
    f_b = text_feats(bundle.tokenizer, "second one, later")
    solo_a = _solo_tokens(eng, f_a)
    solo_b = _solo_tokens(eng, f_b)

    cdl = ContinuousDecodeLoop(eng, cfg)

    async def body():
        # Stagger so B occupies the slot right after A while A is live.
        t1 = asyncio.ensure_future(_consume(cdl, dict(f_a)))
        await asyncio.sleep(0.3)
        t2 = asyncio.ensure_future(_consume(cdl, dict(f_b)))
        return await asyncio.gather(t1, t2)

    try:
        got_a, got_b = asyncio.run(body())
        n = min(len(got_a), len(solo_a))
        np.testing.assert_array_equal(got_a[:n], solo_a[:n])
        n = min(len(got_b), len(solo_b))
        np.testing.assert_array_equal(got_b[:n], solo_b[:n])
    finally:
        cdl.stop()


def test_dispatch_failure_errors_streams_and_recovers():
    """A device-dispatch failure mid-decode must surface to the live
    consumers as an error AND leave the loop serviceable: the shared
    state rebuilds and a fresh stream completes."""
    bundle = _echo_bundle()
    cfg = _cfg(max_decode_len=16)
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    cdl = ContinuousDecodeLoop(eng, cfg)

    real_chunk = eng._gen_chunk
    boom = {"armed": False, "fired": False}

    def flaky(p, state, n, sample):
        if boom["armed"] and not boom["fired"]:
            boom["fired"] = True
            raise RuntimeError("injected link failure")
        return real_chunk(p, state, n, sample)

    eng._gen_chunk = flaky
    feats = text_feats(bundle.tokenizer, "long enough to need chunks!!")

    async def body():
        boom["armed"] = True
        with pytest.raises(RuntimeError, match="injected link failure"):
            await _consume(cdl, dict(feats))
        assert boom["fired"]
        boom["armed"] = False
        # Loop must have reset (state rebuilt lazily) and still serve.
        for _ in range(100):
            if cdl._admitted == 0:
                break
            await asyncio.sleep(0.05)
        assert cdl._admitted == 0, "failure path leaked an admission slot"
        out = await _consume(cdl, dict(feats))
        assert len(out) > 0

    try:
        asyncio.run(body())
    finally:
        cdl.stop()


def test_continuous_batching_on_replica_mesh(cpu_devices):
    """The shared decode loop composes with replica-DP serving: slot
    count pads to the mesh width and tokens stay solo-identical."""
    bundle = tiny_t5_bundle()
    cfg = _cfg(max_decode_len=8, seq_buckets=(16, 32), max_streams=3)
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(2)))
    feats = [text_feats(bundle.tokenizer, t)
             for t in ("summarize: alpha", "translate: beta")]
    solos = [_solo_tokens(eng, f) for f in feats]
    cdl = ContinuousDecodeLoop(eng, cfg)
    assert cdl.n_slots % 2 == 0  # padded to the replica multiple
    try:
        outs = _run_concurrent(cdl, feats)
        for got, want in zip(outs, solos):
            n = min(len(got), len(want))
            np.testing.assert_array_equal(got[:n], want[:n])
    finally:
        cdl.stop()


def test_deep_chain_pipelining_token_identity():
    """chain_depth > 1 (STREAM_PIPELINE): up to D chunk dispatches ride
    in flight before the oldest is delivered — tokens must remain
    identical to solo runs, late admission included."""
    bundle = _echo_bundle()
    cfg = _cfg(stream_pipeline=3, max_decode_len=16)
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    cdl = ContinuousDecodeLoop(eng, cfg)
    assert cdl.chain_depth == 3 and not cdl._auto_depth
    texts = ["alpha one", "bb", "stream three!", "dddddd"]
    feats = [text_feats(bundle.tokenizer, t) for t in texts]

    async def body():
        gens = [cdl.submit_stream(dict(feats[i])) for i in range(2)]
        tasks = [asyncio.ensure_future(_collect(g)) for g in gens]
        await asyncio.sleep(0.3)  # loop runs; chunks in flight
        gens2 = [cdl.submit_stream(dict(feats[i])) for i in (2, 3)]
        tasks += [asyncio.ensure_future(_collect(g)) for g in gens2]
        return await asyncio.gather(*tasks)

    outs = asyncio.run(body())
    cdl.stop()
    for f, got in zip(feats, outs):
        np.testing.assert_array_equal(
            got, _solo_tokens(eng, f), err_msg=str(f)
        )


def test_auto_depth_tunes_at_warm():
    """STREAM_PIPELINE=0 (auto): warm() measures RTT vs chunk compute
    and picks a depth >= 1 (on CPU the ratio is ~0 -> depth stays
    small); a fixed setting disables tuning."""
    bundle = tiny_t5_bundle()
    cfg = _cfg(stream_pipeline=0)
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    cdl = ContinuousDecodeLoop(eng, cfg)
    assert cdl._auto_depth and cdl.chain_depth == 1
    cdl.warm()
    assert 1 <= cdl.chain_depth <= 8
    cdl.stop()


def test_chunk_dispatch_failure_does_not_orphan_wave():
    """A chunk-dispatch exception raised in the SAME iteration that
    popped a wave off the pending queue must terminate the wave's
    consumers with the error (not leave them blocked forever) and
    return their admission slots."""
    bundle = _echo_bundle()
    cfg = _cfg(max_streams=4, max_decode_len=96, stream_chunk_tokens=2)
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    cdl = ContinuousDecodeLoop(eng, cfg)
    f_long = text_feats(bundle.tokenizer, "a prompt spanning many chunks")
    f_b = text_feats(bundle.tokenizer, "bb")

    # Raise ONLY when this iteration popped a wave: the exact
    # interleaving the orphan bug needed.  (The wave's start goes out
    # ahead of the chunk, so by the chunk's dispatch its streams are the
    # iteration's pending admissions.)
    orig_dc = ContinuousDecodeLoop._dispatch_chunk

    def dc(self, *args):
        if self._pending_wave or self._pending_admissions:
            raise RuntimeError("injected dispatch failure")
        return orig_dc(self, *args)

    cdl._dispatch_chunk = dc.__get__(cdl)

    async def body():
        gen_a = cdl.submit_stream(dict(f_long))
        # First chunk delivered == A is admitted and definitely
        # mid-flight (budget 96 >> chunk 2) — no sleeps, no races.
        await asyncio.wait_for(gen_a.__anext__(), timeout=60)
        gen_b = cdl.submit_stream(dict(f_b))
        with pytest.raises(RuntimeError, match="injected"):
            await asyncio.wait_for(_collect(gen_b), timeout=30)
        # A also saw the failure (it was active when the chunk raised).
        with pytest.raises(Exception):
            await asyncio.wait_for(_collect(gen_a), timeout=30)
        # Slots returned; the loop recovers for fresh streams.
        for _ in range(200):
            if cdl._admitted == 0:
                break
            await asyncio.sleep(0.05)
        assert cdl._admitted == 0
        out = await _collect(cdl.submit_stream(dict(f_b)))
        assert out.size > 0

    asyncio.run(body())
    cdl.stop()


# ---------------------------------------------------------------------------
# SPEC_CONTINUOUS: draft→verify rounds inside the shared slot batch


def _spec_cfg(**kw) -> ServiceConfig:
    kw.setdefault("spec_decode", "ngram")
    kw.setdefault("spec_continuous", True)
    kw.setdefault("spec_k", 4)
    return _cfg(**kw)


def test_spec_continuous_token_identity_gpt():
    """2-8 concurrent greedy streams through the speculative continuous
    loop emit exactly the non-speculative engine's tokens — and the
    loop reports speculative emission (>= 1 token per verify round)."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).parent))
    from test_spec import _tiny_gpt_bundle

    bundle = _tiny_gpt_bundle()
    common = dict(seq_buckets=(32,), max_decode_len=16, max_streams=8,
                  batch_buckets=(1, 2, 4, 8))
    cfg = _spec_cfg(**common)
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    eng_off = InferenceEngine(
        bundle, _cfg(**common), ReplicaSet(make_mesh(1))
    )
    texts = [
        "abcababababab", "the quick brown fox", "xyxyxyxyxyxy",
        "hello", "aaaaabbbbb", "cdcdcdcdcd", "one two three", "zz",
    ]
    for n in (2, 8):
        cdl = ContinuousDecodeLoop(eng, cfg)
        assert cdl.spec
        try:
            feats = [
                text_feats(bundle.tokenizer, t, 32) for t in texts[:n]
            ]
            outs = _run_concurrent(cdl, feats)
            for f, got, t in zip(feats, outs, texts):
                ref = _solo_tokens(eng_off, f)
                m = min(len(got), len(ref))
                np.testing.assert_array_equal(got[:m], ref[:m], err_msg=t)
                # A shorter stream must have stopped for a reason: EOS
                # or the server budget.
                if len(got) < len(ref):
                    assert got[-1] == bundle.cfg.eos_id or len(got) >= 16
        finally:
            cdl.stop()


def test_spec_continuous_token_identity_t5():
    """Same contract for the encoder-decoder family: slot histories
    carry [encoder ids | decoder tokens] at the slot layout."""
    bundle = tiny_t5_bundle()
    common = dict(seq_buckets=(16, 32), max_decode_len=12, max_streams=4)
    cfg = _spec_cfg(**common)
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    eng_off = InferenceEngine(bundle, _cfg(**common), ReplicaSet(make_mesh(1)))
    texts = ["the cat sat on the mat the cat", "ab", "hello world hello"]
    cdl = ContinuousDecodeLoop(eng, cfg)
    assert cdl.spec
    try:
        feats = [text_feats(bundle.tokenizer, t, 32) for t in texts]
        outs = _run_concurrent(cdl, feats)
        for f, got, t in zip(feats, outs, texts):
            ref = _solo_tokens(eng_off, f)
            m = min(len(got), len(ref))
            np.testing.assert_array_equal(got[:m], ref[:m], err_msg=t)
            if len(got) < len(ref):
                assert got[-1] == bundle.cfg.eos_id or len(got) >= 12
    finally:
        cdl.stop()


def test_spec_continuous_late_admission_and_budget():
    """A stream admitted mid-flight into the speculative loop gets its
    solo tokens; max_tokens trims mid-verify-round overshoot."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).parent))
    from test_spec import _tiny_gpt_bundle

    bundle = _tiny_gpt_bundle()
    common = dict(seq_buckets=(32,), max_decode_len=24, max_streams=4,
                  batch_buckets=(1, 2, 4))
    cfg = _spec_cfg(**common)
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    eng_off = InferenceEngine(bundle, _cfg(**common), ReplicaSet(make_mesh(1)))
    f_long = text_feats(bundle.tokenizer, "abcabcabcabcabc", 32)
    f_late = text_feats(bundle.tokenizer, "late stream", 32)
    f_cap = dict(text_feats(bundle.tokenizer, "xyxyxyxy", 32), max_tokens=5)
    cdl = ContinuousDecodeLoop(eng, cfg)

    async def body():
        t1 = asyncio.ensure_future(_consume(cdl, dict(f_long)))
        await asyncio.sleep(0.5)
        t2 = asyncio.ensure_future(_consume(cdl, dict(f_late)))
        t3 = asyncio.ensure_future(_consume(cdl, dict(f_cap)))
        return await asyncio.gather(t1, t2, t3)

    try:
        got_long, got_late, got_cap = asyncio.run(body())
        for got, f in ((got_long, f_long), (got_late, f_late)):
            ref = _solo_tokens(eng_off, f)
            m = min(len(got), len(ref))
            np.testing.assert_array_equal(got[:m], ref[:m])
        assert len(got_cap) <= 5 + eng.chunk_tokens  # first chunk + trim
    finally:
        cdl.stop()


def test_spec_continuous_sampled_deterministic():
    """A seeded sampled stream through the speculative loop reproduces
    its tokens regardless of batch composition (solo vs concurrent)."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).parent))
    from test_spec import _tiny_gpt_bundle

    bundle = _tiny_gpt_bundle()
    common = dict(seq_buckets=(32,), max_decode_len=16, max_streams=4,
                  batch_buckets=(1, 2, 4))
    cfg = _spec_cfg(**common)
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    f_s = dict(
        text_feats(bundle.tokenizer, "abcababab", 32),
        temperature=1.0, seed=13,
    )
    f_g = text_feats(bundle.tokenizer, "greedy neighbor", 32)

    cdl = ContinuousDecodeLoop(eng, cfg)
    try:
        solo = _run_concurrent(cdl, [f_s])[0]
    finally:
        cdl.stop()
    cdl = ContinuousDecodeLoop(eng, cfg)
    try:
        outs = _run_concurrent(cdl, [f_s, f_g])
    finally:
        cdl.stop()
    np.testing.assert_array_equal(solo, outs[0])


def test_spec_continuous_sampled_opt_out_routes_around_loop():
    """SPEC_CONTINUOUS + SPEC_SAMPLED=0: sampled streams bypass the
    speculative loop (strict seed contract) and match the plain
    engine's seeded output exactly; greedy streams still use it."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).parent))
    from test_spec import _tiny_gpt_bundle

    from mlmicroservicetemplate_tpu.scheduler import Batcher

    bundle = _tiny_gpt_bundle()
    common = dict(seq_buckets=(32,), max_decode_len=12, max_streams=4,
                  batch_buckets=(1, 2, 4), batch_timeout_ms=1.0)
    cfg = _spec_cfg(spec_sampled=False, spec_max_streams=0, **common)
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    eng_off = InferenceEngine(bundle, _cfg(**common), ReplicaSet(make_mesh(1)))
    batcher = Batcher(eng, cfg)
    f_s = dict(
        text_feats(bundle.tokenizer, "ababab", 32), temperature=1.0, seed=9
    )
    ref = _solo_tokens(eng_off, f_s)

    async def body():
        got = await _collect(batcher.submit_stream(dict(f_s)))
        # Bypassed the loop entirely (no loop prefill)...
        assert batcher._cdl.prefill_dispatches == 0
        # ...and the greedy stream DOES use the speculative loop.
        await _collect(batcher.submit_stream(
            text_feats(bundle.tokenizer, "greedy", 32)
        ))
        assert batcher._cdl.prefill_dispatches == 1
        await batcher.stop()
        return got

    got = asyncio.run(body())
    np.testing.assert_array_equal(got, ref)


def test_spec_continuous_hist_row_full_budget_chunk():
    """chunk_tokens == max_decode_len: the first chunk fills the whole
    decoder history region; _hist_row must clamp, not crash (T5's
    decoder region is exactly max_decode_len wide)."""
    bundle = tiny_t5_bundle()
    cfg = _spec_cfg(
        seq_buckets=(16,), max_decode_len=8, stream_chunk_tokens=8,
        max_streams=2,
    )
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    cdl = ContinuousDecodeLoop(eng, cfg)
    try:
        cdl._build_empty_state()
        feats = text_feats(bundle.tokenizer, "abc", 32)
        row = cdl._hist_row(feats, np.arange(1, 9, dtype=np.int32))
        hoff = cdl._hist_w - cdl._kv_w
        # decoder region: start id + the first 7 chunk tokens (the 8th
        # would land past the region; the stream is finished anyway).
        assert row[0, hoff] == bundle.cfg.decoder_start_id
        np.testing.assert_array_equal(
            row[0, hoff + 1 :], np.arange(1, 8, dtype=np.int32)
        )
    finally:
        cdl.stop()


# ---------------------------------------------------------------------------
# The row ladder: a prefill wave runs the smallest rung that holds it.


@pytest.mark.parametrize("multiple", [1, 2])
@pytest.mark.parametrize("max_streams", [1, 2, 8, 12, 64])
def test_wave_rows_ladder(max_streams, multiple):
    """Every wave size 1..n_slots maps to a rung >= it, <= n_slots, a
    multiple of the placement's pad multiple, monotone in the size, and
    n_slots itself is reachable; a small wave runs the small rung, not
    the slot count."""
    from types import SimpleNamespace

    from mlmicroservicetemplate_tpu.engine.streams import (
        _SMALL_WAVE_ROWS,
        wave_rungs,
    )

    n_slots = -(-max_streams // multiple) * multiple  # as the loop rounds it
    rungs = wave_rungs(n_slots, multiple)
    assert rungs == tuple(sorted(set(rungs))) and rungs[-1] == n_slots
    loop_like = SimpleNamespace(_wave_rungs=rungs, n_slots=n_slots)
    rows = [ContinuousDecodeLoop._wave_rows(loop_like, k)
            for k in range(1, n_slots + 1)]
    small = -(-_SMALL_WAVE_ROWS // multiple) * multiple
    for k, r in zip(range(1, n_slots + 1), rows):
        assert k <= r <= n_slots and r % multiple == 0 and r in rungs
        if k <= small:
            assert r <= small
    assert rows == sorted(rows) and rows[-1] == n_slots
    assert set(rows) == set(rungs)  # no rung is warmed for nothing


def _llama_loop(paged: bool, **kw):
    from helpers import tiny_llama_bundle

    if paged:
        kw.update(paged_kv=True, kv_block_size=4)
    # (a deep queue: ``asyncio.run`` closes each wave's event loop, which
    # can drop a finished stream's admission-count callback)
    cfg = _cfg(batch_buckets=(1,), max_streams=8, max_stream_queue=256, **kw)
    bundle = tiny_llama_bundle()
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    return bundle, eng, ContinuousDecodeLoop(eng, cfg)


def _spy_waves(cdl) -> list[tuple[int, int]]:
    """Record (rows, width) of every prefill executable run."""
    ran: list[tuple[int, int]] = []
    note = cdl._note_wave_fill

    def spy(real_tokens, rows, width):
        ran.append((rows, width))
        note(real_tokens, rows, width)

    cdl._note_wave_fill = spy
    return ran


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "contiguous"])
def test_wave_of_three_at_eight_slots_matches_solo(paged):
    """Three streams admitted as ONE wave at 8 slots run their rung
    (4 rows, not 8) and emit the tokens each emits served alone."""
    bundle, eng, cdl = _llama_loop(paged)
    ran = _spy_waves(cdl)
    feats = [text_feats(bundle.tokenizer, t) for t in
             ("the quick brown fox", "hi", "jumps over the lazy dog again")]
    try:
        outs = _run_concurrent(cdl, feats)
    finally:
        cdl.stop()
    assert ran == [(cdl._wave_rows(3), 32)] and ran[0][0] < cdl.n_slots
    for f, got in zip(feats, outs):
        np.testing.assert_array_equal(got, _solo_tokens(eng, f))


@pytest.mark.parametrize("mode", ["paged", "contiguous-prefix"])
def test_warm_covers_every_wave_size(mode, monkeypatch):
    """After ``warm()``, waves of every size 1..n_slots at every seq
    bucket (under PREFIX_CACHE: as misses, then again as one hit group)
    compile nothing: each meets a start and an insert the grid warmed."""
    from mlmicroservicetemplate_tpu.runtime.compile_cache import CompileWindow

    monkeypatch.setenv("WARMUP_SAMPLING", "0")
    prefix = mode == "contiguous-prefix"
    bundle, eng, cdl = _llama_loop(
        not prefix, max_decode_len=8, prefix_cache=prefix
    )
    tok = bundle.tokenizer
    want, ran = [], None
    try:
        cdl.warm()
        ran = _spy_waves(cdl)
        with CompileWindow() as w:
            for s, body in ((16, "x" * 6), (32, "x" * 20)):
                passes = 2 if (prefix and s == 32) else 1
                for k in range(1, cdl.n_slots + 1):
                    # Distinct leading bytes: distinct prefixes, so the
                    # first pass misses and the second hits per row.
                    feats = [text_feats(tok, f"{k}{i}-{body}")
                             for i in range(k)]
                    for hit in range(passes):
                        # An IDLE loop holds an announced burst for its
                        # stragglers (``_collect_burst``, ``one_wave``'s
                        # ``Arrival``); one with a chunk still in flight
                        # admits what is queued at its boundary.
                        t_end = time.monotonic() + 5.0
                        while not cdl.idle():  # the last wave, all of it
                            assert time.monotonic() < t_end
                            time.sleep(0.002)
                        outs = _run_concurrent(cdl, feats)
                        assert all(len(o) > 0 for o in outs)
                        # (a lone admission notes its own bucket, hit
                        # or miss; a hit group its suffix bucket)
                        want.append(
                            (1, s) if k == 1
                            else (cdl._wave_rows(k), 16 if hit else s)
                        )
    finally:
        cdl.stop()
    assert ran == want
    assert w.compiles == 0, f"{w.compiles} compiles on the admission path"


def test_prefill_wave_rows_observes_the_rung():
    """``prefill_wave_rows`` counts the rows the executable ran: a wave
    of three at 8 slots observes its rung (4), a lone admission 1, a
    wave of five the slot count."""
    from prometheus_client import REGISTRY

    bundle = _echo_bundle()
    cfg = _cfg(max_streams=8)
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    cdl = ContinuousDecodeLoop(eng, cfg)

    def read():
        return tuple(
            REGISTRY.get_sample_value(
                f"prefill_wave_rows_{k}", {"model": bundle.name}
            ) or 0.0
            for k in ("sum", "count")
        )

    feats = [text_feats(bundle.tokenizer, t)
             for t in ("abc", "de", "fgh", "ij", "klm")]
    try:
        s0, c0 = read()
        _run_concurrent(cdl, feats[:3])
        s1, c1 = read()
        _run_concurrent(cdl, feats[:1])
        s2, c2 = read()
        rung = cdl._wave_rows(3)
        _run_concurrent(cdl, feats)
        s3, c3 = read()
    finally:
        cdl.stop()
    assert (s1 - s0, c1 - c0) == (float(rung), 1.0) and rung < cdl.n_slots
    assert (s2 - s1, c2 - c1) == (1.0, 1.0)
    assert (s3 - s2, c3 - c2) == (float(cdl.n_slots), 1.0)


# ---------------------------------------------------------------------------
# Idle admission: the loop holds a wave for the requests the server is still
# reading (``_collect_burst``), and for nothing else.


def _idle_admit_samples(name: str) -> dict:
    from prometheus_client import REGISTRY

    return {
        k: REGISTRY.get_sample_value(k, {"model": name}) or 0.0
        for k in ("idle_admit_rows_total", "idle_admit_capped_total",
                  "stream_insert_rows_count", "stream_insert_rows_sum")
    }


def _idle_waits(cdl) -> tuple:
    """(waits, seconds) of the loop's idle admissions: the loop table's
    ``idle_admit`` row, what ``/status.decode.idle_admit`` shows."""
    row = cdl.loop_time.snapshot()["inside"].get("idle_admit")
    return (row["n"], row["s"]) if row else (0, 0.0)


def _waits(cdl) -> dict:
    """Seconds of the loop's blocking waits so far, by cause."""
    phases = cdl.loop_time.snapshot()["phases"]
    return {k: phases.get(f"loop/{k}", {"s": 0.0})["s"]
            for k in ("idle", "await_api", "await_burst")}


async def _until(cond, what: str, timeout: float = 10.0) -> None:
    t_end = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < t_end, what
        await asyncio.sleep(0.002)


@pytest.mark.parametrize("k", [5, 8], ids=["five", "n_slots"])
def test_announced_burst_lands_as_one_wave(k):
    """A burst whose requests are announced (the API's counted entry)
    reaches an idle loop one row first, the rest 30 ms behind: the loop
    holds the first row for them, so ONE prefill run and ONE insert
    dispatch serve all ``k`` — and the wait's counters say so."""
    from mlmicroservicetemplate_tpu.scheduler.policy import Arrival

    bundle, eng, cdl = _llama_loop(True)
    assert cdl.n_slots == 8
    ran = _spy_waves(cdl)
    feats = [text_feats(bundle.tokenizer, f"{i} burst row") for i in range(k)]
    before = _idle_admit_samples(bundle.name)

    async def body():
        with one_wave(cdl):
            with Arrival([cdl.queue]):
                first = cdl.submit_stream(dict(feats[0]))
            # The first row's own announcement is settled, the burst's is
            # not: the loop pops the row and waits.
            await _until(lambda: cdl.queue.qsize() == 0, "first row not popped")
            await asyncio.sleep(0.03)
            assert not ran and not cdl.active  # held, not started alone
            rest = [cdl.submit_stream(dict(f)) for f in feats[1:]]
        return await asyncio.gather(*[_collect(g) for g in [first] + rest])

    try:
        outs = asyncio.run(body())
    finally:
        cdl.stop()
    assert [r for r, _ in ran] == [cdl.n_slots] == [cdl._wave_rows(k)]
    for f, got in zip(feats, outs):
        np.testing.assert_array_equal(got, _solo_tokens(eng, f))
    d = {n: v - before[n] for n, v in _idle_admit_samples(bundle.name).items()}
    assert (d["stream_insert_rows_count"], d["stream_insert_rows_sum"]) == (1, k)
    waits, wait_s = _idle_waits(cdl)
    assert (waits, cdl.idle_wait_rows, cdl.idle_waits_capped) == (1, k - 1, 0)
    assert d["idle_admit_rows_total"] == k - 1 and d["idle_admit_capped_total"] == 0
    # The wait was for the API (the burst's announcement stayed open), and
    # the table's slices of it lie inside the admission's whole wait.
    by_cause = _waits(cdl)
    assert 0.03 <= by_cause["await_api"] <= wait_s + 1e-3
    # A wave with room left then stays for the clients' quiet gap (twice
    # the 30 ms the burst paused for); a full one goes at once.
    if k == cdl.n_slots:
        assert by_cause["await_burst"] == 0.0
    else:
        assert 0.03 <= by_cause["await_burst"] <= wait_s + 1e-3


@pytest.mark.parametrize("grace_env", [None, "500"], ids=["plain", "ADMIT_GRACE_MS"])
def test_lone_submit_is_dispatched_without_waiting(grace_env, monkeypatch):
    """A lone request with nothing announced finds nothing to wait for:
    the loop's wait counter does not move (whatever wave times it has
    seen).  ``ADMIT_GRACE_MS`` is gone: set, it changes nothing."""
    if grace_env is not None:
        monkeypatch.setenv("ADMIT_GRACE_MS", grace_env)
    bundle, eng, cdl = _llama_loop(True)
    cdl._wave_seconds = dict.fromkeys(cdl._wave_rungs, 5.0)
    before = _idle_admit_samples(bundle.name)
    f = text_feats(bundle.tokenizer, "a lone request")
    try:
        got = asyncio.run(_consume(cdl, dict(f)))
    finally:
        cdl.stop()
    np.testing.assert_array_equal(got, _solo_tokens(eng, f))
    assert (*_idle_waits(cdl), cdl.idle_wait_rows) == (0, 0.0, 0)
    by_cause = _waits(cdl)  # it waited for clients only, with an empty server
    assert by_cause["await_api"] == by_cause["await_burst"] == 0.0
    after = _idle_admit_samples(bundle.name)
    for fam in ("idle_admit_rows_total", "idle_admit_capped_total"):
        assert after[fam] == before[fam]
    assert not hasattr(cdl, "_admit_grace_s")


@pytest.mark.parametrize("how", ["settled", "raised"])
def test_failed_arrival_releases_a_waiting_loop(how):
    """A counted request that fails before it queues (a 400 from
    preprocess, a shed) lowers the count on its way out, and the loop
    that waited for it goes at once with the rows it holds."""
    from mlmicroservicetemplate_tpu.scheduler.policy import Arrival

    bundle, eng, cdl = _llama_loop(True)
    ran = _spy_waves(cdl)
    f = text_feats(bundle.tokenizer, "the one that made it")

    async def body():
        cdl._wave_seconds = dict.fromkeys(cdl._wave_rungs, 30.0)
        failing = Arrival([cdl.queue])
        gen = cdl.submit_stream(dict(f))
        await _until(lambda: cdl.queue.qsize() == 0, "row not popped")
        await asyncio.sleep(0.03)
        assert not ran and cdl.queue.expected() == 1  # the loop waits
        if how == "settled":
            failing.settle()
            failing.settle()  # every exit may settle: once counts
        else:
            with pytest.raises(ValueError), failing:
                raise ValueError("undecodable payload")
        assert cdl.queue.expected() == 0
        return await _collect(gen)

    try:
        got = asyncio.run(body())
    finally:
        cdl.stop()
    np.testing.assert_array_equal(got, _solo_tokens(eng, f))
    assert [r for r, _ in ran] == [1]
    waits, wait_s = _idle_waits(cdl)
    assert (waits, cdl.idle_wait_rows, cdl.idle_waits_capped) == (1, 0, 0)
    assert 0.03 <= wait_s < 30.0


@pytest.mark.parametrize("k", [1, 3], ids=["lone_rung", "small_rung"])
def test_count_that_never_falls_ends_on_the_cap(k):
    """An announcement nobody settles holds the loop no longer than the
    wave it would run now last took (by rung), and the wait is counted
    as capped."""
    from mlmicroservicetemplate_tpu.scheduler.policy import Arrival

    bundle, eng, cdl = _llama_loop(True)
    ran = _spy_waves(cdl)
    feats = [text_feats(bundle.tokenizer, f"{i} held row") for i in range(k)]
    rung = cdl._wave_rows(k)
    caps = {1: 0.3, cdl._wave_rows(3): 0.6, cdl.n_slots: 30.0}
    assert len(caps) == 3 and cdl._burst_cap_s(k) == 0.0  # nothing timed yet
    before = _idle_admit_samples(bundle.name)

    async def body():
        cdl._wave_seconds = dict(caps)
        stuck = Arrival([cdl.queue])
        try:
            gens = [cdl.submit_stream(dict(f)) for f in feats]
            return await asyncio.gather(*[_collect(g) for g in gens])
        finally:
            stuck.settle()

    try:
        outs = asyncio.run(body())
    finally:
        cdl.stop()
    for f, got in zip(feats, outs):
        np.testing.assert_array_equal(got, _solo_tokens(eng, f))
    assert [r for r, _ in ran] == [rung]
    waits, wait_s = _idle_waits(cdl)
    assert (waits, cdl.idle_waits_capped) == (1, 1)
    assert caps[rung] <= wait_s < caps[rung] + 5.0
    after = _idle_admit_samples(bundle.name)
    assert after["idle_admit_capped_total"] - before["idle_admit_capped_total"] == 1
    # ... and the wave just timed is the next cap of its rung.
    assert 0.0 < cdl._wave_seconds[rung] != caps[rung]


def test_first_row_borrows_the_last_idle_waves_gap():
    """A lone row has no gaps of its own: after a burst it waits twice
    that burst's widest gap for a companion (unannounced: the server
    read faster than its client wrote), after a lone request it waits
    for nothing."""
    bundle, eng, cdl = _llama_loop(True)
    ran = _spy_waves(cdl)
    f = [text_feats(bundle.tokenizer, f"{i} row") for i in range(4)]

    async def idle():
        await _until(lambda: not cdl.active and not cdl._inflight_chunks,
                     "loop not idle")

    async def body():
        cdl._wave_seconds = dict.fromkeys(cdl._wave_rungs, 5.0)
        cdl._idle_gap_s = 0.5  # as a burst with a 500 ms pause left it
        a = cdl.submit_stream(dict(f[0]))
        await asyncio.sleep(0.03)
        assert not ran  # held: up to a second past its arrival
        b = cdl.submit_stream(dict(f[1]))
        outs = list(await asyncio.gather(_collect(a), _collect(b)))
        await idle()
        assert (_idle_waits(cdl)[0], cdl.idle_wait_rows) == (1, 1)
        assert 0.03 <= cdl._idle_gap_s < 0.5  # the pair's own gap now
        cdl._wave_seconds = dict.fromkeys(cdl._wave_rungs, 5.0)
        outs.append(await _collect(cdl.submit_stream(dict(f[2]))))
        await idle()  # waited (twice the pair's gap) and found nobody
        assert (_idle_waits(cdl)[0], cdl.idle_wait_rows) == (2, 1)
        assert cdl._idle_gap_s == 0.0
        outs.append(await _collect(cdl.submit_stream(dict(f[3]))))
        return outs

    try:
        outs = asyncio.run(body())
    finally:
        cdl.stop()
    for feats, got in zip(f, outs):
        np.testing.assert_array_equal(got, _solo_tokens(eng, feats))
    assert [r for r, _ in ran] == [cdl._wave_rows(2), 1, 1]
    assert (_idle_waits(cdl)[0], cdl.idle_wait_rows, cdl.idle_waits_capped) == (2, 1, 0)
    # Both waits were quiet gaps: nothing was announced, so none was the API's.
    by_cause = _waits(cdl)
    assert by_cause["await_burst"] >= 0.03 and by_cause["await_api"] == 0.0


@pytest.mark.parametrize("announced", [False, True], ids=["settled", "announced"])
def test_rows_already_queued_join_the_wave(announced):
    """Rows that were put while the loop thread was slow to wake are
    part of the wave whether or not their announcement is still open:
    the loop drains the queue before it decides there is nothing to
    wait for (and having found them all, it does not wait)."""
    from mlmicroservicetemplate_tpu.scheduler.policy import Arrival

    bundle, eng, cdl = _llama_loop(True)
    cdl._ensure_thread = lambda: None  # the test is the loop thread
    cdl._wave_seconds = dict.fromkeys(cdl._wave_rungs, 0.2)

    async def body():
        for i in range(3):
            cdl.submit_stream(text_feats(bundle.tokenizer, f"{i} queued row"))
        arrival = Arrival([cdl.queue] if announced else [])
        await asyncio.sleep(0.05)  # the loop wakes late: past any quiet gap
        wave = [cdl.queue.pop_nowait()]
        t = time.monotonic()
        cdl._collect_burst(wave)
        arrival.settle()
        return wave, time.monotonic() - t

    wave, dt = asyncio.run(body())
    assert len(wave) == 3 and cdl.queue.qsize() == 0
    if announced:  # ... then held for the one still announced, to the cap
        assert (_idle_waits(cdl)[0], cdl.idle_wait_rows, cdl.idle_waits_capped) == (1, 2, 1)
        assert dt >= 0.2
    else:
        assert _idle_waits(cdl) == (0, 0.0)


# ---------------------------------------------------------------------------
# The loop table (utils/tracing.LoopTable): the loop's blocking waits carry
# their cause, and an iteration's wall time adds up.


@pytest.mark.parametrize("cause", ["idle", "await_api", "await_burst"])
def test_blocking_waits_are_named_by_cause(cause):
    """An empty server's wait is ``loop/idle`` (the clients'); with a
    request the API has read and not queued (an ``Arrival`` held open) it
    is ``loop/await_api`` and not ``loop/idle``; an idle wave's quiet gap
    with nothing announced is ``loop/await_burst``."""
    from mlmicroservicetemplate_tpu.scheduler.policy import Arrival

    bundle, eng, cdl = _llama_loop(True)
    f = text_feats(bundle.tokenizer, "a row")

    async def body():
        if cause == "await_burst":
            # The last idle wave's rows came 50 ms apart: a lone row
            # stays for twice that before it goes alone.
            cdl._wave_seconds = dict.fromkeys(cdl._wave_rungs, 5.0)
            cdl._idle_gap_s = 0.05
            return await _collect(cdl.submit_stream(dict(f)))
        arrival = Arrival([cdl.queue] if cause == "await_api" else [])
        cdl._ensure_thread()  # the loop's first wait starts announced
        await asyncio.sleep(0.15)
        got = _waits(cdl)
        arrival.settle()
        return got

    try:
        got = asyncio.run(body())
        by_cause = got if isinstance(got, dict) else _waits(cdl)
    finally:
        cdl.stop()
    others = {k: v for k, v in by_cause.items() if k != cause}
    assert by_cause[cause] >= 0.05
    if cause == "await_burst":
        np.testing.assert_array_equal(got, _solo_tokens(eng, f))
        assert others["await_api"] == 0.0  # (idle: before the row came)
        assert _idle_waits(cdl)[0] == 1
    else:
        assert others == dict.fromkeys(others, 0.0)
        assert _idle_waits(cdl) == (0, 0.0)  # no wave was held


def test_loop_time_adds_up_and_names_a_slowed_iteration():
    """After real waves and chunks the table closes — ``wall_s`` = the
    top-level phases + ``unnamed_s``, every ``dispatch:<site>`` counted
    inside its phase and not beside it — and an iteration slowed on
    purpose (a ``_stage_host_prep`` that sleeps once) is the slowest
    since the compiles, with its phase named."""
    bundle, eng, cdl = _llama_loop(True)
    feats = [text_feats(bundle.tokenizer, f"{i} row of the wave") for i in range(5)]
    prep = cdl._stage_host_prep

    def slow_prep():
        cdl._stage_host_prep = prep  # once
        time.sleep(0.25)
        prep()

    try:
        outs = _run_concurrent(cdl, feats)  # (compiles: the slowest of all)
        _run_concurrent(cdl, feats[:2])
        t_warm = time.monotonic()
        cdl._stage_host_prep = slow_prep
        _run_concurrent(cdl, feats)
    finally:
        cdl.stop()
    for f, got in zip(feats, outs):
        np.testing.assert_array_equal(got, _solo_tokens(eng, f))
    snap = cdl.loop_time.snapshot()
    phases, inside = snap["phases"], snap["inside"]
    named = sum(row["s"] for row in phases.values())
    assert snap["wall_s"] == pytest.approx(
        named + snap["unnamed_s"], abs=1e-6 * (len(phases) + 2))
    assert snap["unnamed_s"] < 0.25 * snap["wall_s"]
    assert all(k.startswith("loop/") for k in phases), sorted(phases)
    assert {"loop/wave_dispatch", "loop/wave_fetch", "loop/insert",
            "loop/chunk_prep", "loop/chunk_dispatch", "loop/stage_prep",
            "loop/deliver", "loop/housekeeping", "loop/queue_pop",
            "loop/idle"} <= set(phases)
    sites = {k for k in inside if k.startswith("dispatch:")}
    assert {"dispatch:prefill", "dispatch:chunk", "dispatch:fetch"} <= sites
    assert inside["dispatch:chunk"]["s"] <= phases["loop/chunk_dispatch"]["s"]
    assert inside["dispatch:chunk"]["n"] == phases["loop/chunk_dispatch"]["n"]
    # A paged chunk's host half, once a chunk (no pool ran dry here).
    assert phases["loop/chunk_prep"]["n"] == phases["loop/chunk_dispatch"]["n"]
    top = [r for r in snap["slowest"] if r["t"] >= t_warm][0]
    assert top["phase"] == "loop/stage_prep" and top["phase_s"] >= 0.25
    assert top["wall_s"] >= top["phase_s"] and top["live"] >= 1
    assert len(snap["slowest"]) <= cdl.loop_time.SLOWEST
