"""A Mamba-2 mixer THEN an expert block, served beside a GQA pool: state rows,
keys and a chip's share of the experts together through the model's step
kinds and the continuous loop (``engine/streams.py``) — the toy of
``tests/test_granite_block.py`` at three layers (Mamba-2, attention at 4 heads
on 2 KV heads with the softmax scale 1/128, Mamba-2; an expert block of 8
experts top-3, 4 held, plus a shared expert behind EVERY mixer; the four
multipliers; tied head) on the CPU.  The contracts are
``tests/test_nemotron_serving.py``'s, for the same mixer in its new place:

(a) a prompt prefilled in windows beside a mate at OTHER starts and a
    filled-up row leaves its state row as one pass over the prompt does,
    with the kernels off and on (the one-group scan kernel, the prompt-window
    and paged decode kernels handed ``attention_multiplier`` as their scale);
    the decode step moves live rows only;
(b) the loop serves waves and windows (several prompts a dispatch)
    token-identical to the plain reference's greedy continuation — prefill
    windows then decode through pool and state rows against the full forward
    pass, logits —, gives every state row and block back and feeds the shared
    ``ssm_*`` and ``moe_*`` counters;
(c) a stream dropped and resumed rebuilds its state by recompute.
(Boot refusals: ``tests/test_granite_block.py``.)
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlmicroservicetemplate_tpu.engine import InferenceEngine
from mlmicroservicetemplate_tpu.engine.streams import ContinuousDecodeLoop
from mlmicroservicetemplate_tpu.engine.supervisor import Supervisor
from mlmicroservicetemplate_tpu.models import llama as llama_mod
from mlmicroservicetemplate_tpu.models.gpt import PagedState
from mlmicroservicetemplate_tpu.models.sampling import greedy_params
from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
from mlmicroservicetemplate_tpu.utils import metrics

from test_granite_block import SMALL_TYPES, _close, _ids, _svc  # noqa: F401
from test_granite_block import config, kw, ref  # noqa: F401
from test_nemotron_serving import (  # the same toy geometry: BS, NB, T_W, C, R
    _feats, _go_live, _greedy, _loop_cfg, _table, _windows)
from test_prefill_chunked import _run, _wait_pool_drained

BS, NB, T_W, C, R = 4, 40, 12, 8, 5  # block, pool, table width, window, state rows
TYPES = ["mamba2", "attention", "mamba2"]


@pytest.fixture(scope="module")
def cfg(kw):  # noqa: F811
    return llama_mod.LlamaConfig(**{**kw, "layer_types": TYPES, "num_layers": 3})


@pytest.fixture(scope="module")
def params(cfg):
    return llama_mod.init_params(jax.random.PRNGKey(0), cfg)


def _paged(cfg, slots=3):
    z = llama_mod.zero_ssm(cfg, R, jnp.float32)
    # every row POISONED: a first window must start from zeros all the same
    z = z._replace(conv=[c + 7.0 for c in z.conv], state=[s + 3.0 for s in z.state],
                   row=jnp.full((slots,), R, jnp.int32))
    width = cfg.num_kv_heads * cfg.head_dim
    return PagedState(
        cache_k=[jnp.zeros((NB, BS, width))], cache_v=[jnp.zeros((NB, BS, width))],
        key_valid=jnp.zeros((slots, T_W * BS), jnp.int32),
        write_idx=jnp.zeros((slots,), jnp.int32), pos=jnp.zeros((slots,), jnp.int32),
        last_token=jnp.zeros((slots,), jnp.int32), done=jnp.ones((slots,), bool),
        tokens=jnp.zeros((slots, 8), jnp.int32), sample=greedy_params(slots), ssm=z)


@pytest.mark.parametrize("n,kernels", [(21, False), (9, False), (24, True)],
                         ids=["short-last-window", "one-window", "kernels-aligned"])
def test_windows_beside_unequal_mates_leave_the_one_shot_state(cfg, params, n, kernels):
    """A prompt of ``n`` tokens into a POISONED state row, beside a mate
    whose windows run at other starts and a filled-up row: the row holds
    what one pass over the prompt leaves (all but the last token), no other
    row moved, and the paged decode from it — the one-token update, the pool
    read under the scale 1/128 (``kernels``: through the paged decode kernel,
    the prompt-window kernel and the one-group scan kernel, interpret mode)
    — emits the contiguous path's tokens."""
    run = dataclasses.replace(cfg, pallas_decode=kernels)
    ids, mate = _ids(n, 11), _ids(40, 12)
    state = _windows(params, run, _paged(cfg), ids, 2, _table(3), mate, 4, _table(20))
    out: list = []
    llama_mod.forward_hidden(params, cfg, ids[None], np.ones((1, n), np.int32),
                             ssm_out=out)
    for got, want in zip(state.ssm.state, out[0].state):
        assert got.dtype == jnp.float32 and _close(got[2], want[0]) < 1e-5
        assert _close(got[0], 3.0) == 0.0 and _close(got[3], 3.0) == 0.0
    for got, want in zip(state.ssm.conv, out[0].conv):
        assert _close(got[2], want[0]) < 1e-6
    want_toks = llama_mod.greedy_generate(
        params, cfg, ids[None], np.ones((1, n), np.int32), 8)
    state, table = _go_live(state, 1, 2, ids, _table(3))
    before = [np.asarray(s) for s in state.ssm.state]
    state, (toks, counts) = llama_mod.generate_chunk_paged(params, run, state, table, 8)
    np.testing.assert_array_equal(np.asarray(toks[1]), np.asarray(want_toks[0]))
    # every layer has an expert block: a row of the tally each, over the
    # PUBLISHED 8 experts, 3 a token a step of the one live row
    assert counts.shape == (3, 8) and counts.sum(axis=1).tolist() == [24, 24, 24]
    for b, a in zip(before, state.ssm.state):
        moved = np.abs(b - np.asarray(a)).reshape(R, -1).max(axis=1) > 0
        assert moved.tolist() == [False, False, True, False, False]


def test_a_done_or_freed_slot_moves_no_state(cfg, params):
    ids = _ids(13, 21)
    state = _windows(params, cfg, _paged(cfg), ids, 1, _table(3), _ids(16, 22), 4, _table(20))
    state, table = _go_live(state, 0, 1, ids, _table(3))
    before = [np.asarray(s) for s in state.ssm.state]
    done = state._replace(done=state.done.at[0].set(True))
    after, _ = llama_mod.generate_chunk_paged(params, cfg, done, table, 4)
    cleared, _ = llama_mod.generate_chunk_paged(
        params, cfg, state, jnp.full_like(table, NB), 4)
    for b, a, c in zip(before, after.ssm.state, cleared.ssm.state):
        assert _close(b, a) == 0.0 and _close(b, c) == 0.0


# ---------------------------------------------------------------------------
# the loop


def _bundle(monkeypatch, kw):  # noqa: F811
    from mlmicroservicetemplate_tpu.models.registry import build_model

    bundle = build_model(_svc(monkeypatch, kw))
    assert bundle.cfg.layer_types == ("mamba2", "full", "mamba2")
    return bundle


def _moe_rows():
    return sum(s.value for m in metrics.MOE_ROWS.collect() for s in m.samples
               if s.name.endswith("_total"))


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernel"])
def test_the_loop_serves_waves_and_windows_as_the_reference(
        monkeypatch, kw, ref, config, kernels):  # noqa: F811
    """Short prompts (the wave path: the state inserted into a row, the keys
    into blocks) and long ones (windows, three different prompts a dispatch)
    together: every stream's tokens are the plain reference's greedy
    continuation, teacher-forced on its full forward pass; the rows, the
    blocks and the shared counters add up afterwards.  With the kernels on
    (``USE_PALLAS_DECODE``, interpret mode) the scans take the fused kernel
    and ``ssm_scan_fused_tokens_total`` counts every scanned position; with
    them off it counts none."""
    if kernels:
        monkeypatch.setenv("USE_PALLAS_DECODE", "1")
    bundle = _bundle(monkeypatch, kw)
    assert bundle.cfg.pallas_decode == bundle.cfg.scan_fused == kernels
    fused0 = metrics.SSM_SCAN_FUSED.labels("llama")._value.get()
    cfgc = _loop_cfg()
    eng = InferenceEngine(bundle, cfgc, ReplicaSet(make_mesh(1)))
    feats = _feats((7, 30, 45, 30, 12))
    scanned0 = metrics.SSM_SCAN_TOKENS.labels("llama")._value.get()
    masked0 = metrics.SSM_SCAN_MASKED.labels("llama")._value.get()
    batched0 = metrics.PREFILL_WINDOWS_BATCHED.labels("llama")._value.get()
    absent0 = metrics.MOE_ASSIGNMENTS_ABSENT.labels("llama")._value.get()
    held0 = metrics.MOE_ASSIGNMENTS_HELD.labels("llama")._value.get()
    rows0 = _moe_rows()
    cdl = ContinuousDecodeLoop(eng, cfgc)
    try:
        assert len(cdl._ssm_free) == cdl.n_slots == 4  # a row a slot, none spare
        outs = _run(cdl, feats)
        assert cdl.prefill_chunk_dispatches > 0
        # several prompts' windows in ONE dispatch
        assert metrics.PREFILL_WINDOWS_BATCHED.labels("llama")._value.get() > batched0
        assert _wait_pool_drained(eng.kv_pool) == 0
        assert sorted(cdl._ssm_free) == list(range(4))
        assert metrics.SSM_STATE_BYTES.labels("llama")._value.get() == 0
    finally:
        cdl.stop()
    hp = ref.hyper({**config, "vocab_size": 300, "num_hidden_layers": 3,
                    "layer_types": SMALL_TYPES})
    for f, toks in zip(feats, outs):
        assert len(toks) == 12
        seq = np.concatenate([f["input_ids"], toks]).astype(np.int32)[None]
        logits = np.asarray(ref.logits(bundle.params, hp, seq))[0]
        n = int(f["length"])
        rows = logits[n - 1: n - 1 + len(toks)]
        assert float((rows.max(axis=-1) - rows[np.arange(len(toks)), toks]).max()) < 1e-6
    scanned = metrics.SSM_SCAN_TOKENS.labels("llama")._value.get() - scanned0
    masked = metrics.SSM_SCAN_MASKED.labels("llama")._value.get() - masked0
    assert scanned - masked == sum(int(f["length"]) for f in feats) and masked > 0
    fused = metrics.SSM_SCAN_FUSED.labels("llama")._value.get() - fused0
    assert fused == (scanned if kernels else 0)
    # a share of the experts: some assignments land here, some on the absent
    # chip (counted, computed nowhere), and the expert block's rows are counted
    absent = metrics.MOE_ASSIGNMENTS_ABSENT.labels("llama")._value.get() - absent0
    held = metrics.MOE_ASSIGNMENTS_HELD.labels("llama")._value.get() - held0
    assert held > 0 and absent > 0 and 0.2 < held / (held + absent) < 0.8
    assert _moe_rows() > rows0
    # two Mamba-2 layers' [8, 8, 16] float32 state and 3 taps of 96 a stream;
    # ONE attention layer's K and V of 2 heads of 16 a token
    assert eng.stream_fixed_bytes() == bundle.cfg.ssm_row_bytes == 2 * (4096 + 576)
    assert eng.kv_token_bytes() == 2 * 2 * 16 * 4


@pytest.mark.parametrize("site", ["prefill_chunk:fatal@2", "chunk:fatal@2"])
def test_a_checkpointed_stream_resumes_to_the_same_tokens(monkeypatch, kw, site):  # noqa: F811
    """A fatal fault at a prompt's second window, or at the second decode
    chunk: the stream's state row and blocks go back, and the resume — the
    prompt and what was delivered, prefilled again — continues
    token-identically: the state was rebuilt by recompute, and counted."""
    bundle = _bundle(monkeypatch, kw)
    cfgc = _loop_cfg(fault_spec=site)
    eng = InferenceEngine(bundle, cfgc, ReplicaSet(make_mesh(1)))
    (f,) = _feats((26,), seed=3)
    before = metrics.SSM_STATE_RECOMPUTES.labels("llama")._value.get()
    cdl = ContinuousDecodeLoop(eng, cfgc)
    cdl.supervisor = Supervisor(cfgc)
    try:
        assert _run(cdl, [f])[0] == _greedy(bundle, f)
        assert cdl.supervisor.restarts == 1
        assert _wait_pool_drained(eng.kv_pool) == 0
        assert sorted(cdl._ssm_free) == list(range(cdl.n_slots))
    finally:
        cdl.stop()
    assert metrics.SSM_STATE_RECOMPUTES.labels("llama")._value.get() == before + 1
