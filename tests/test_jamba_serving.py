"""Mamba-1 layers served beside a multi-query pool: state rows and ONE KV
head's keys together through the model's step kinds and the continuous loop
(``engine/streams.py``) — the toy of ``tests/test_jamba_block.py`` at three
layers (Mamba, attention at 5 heads on 1 KV head, Mamba; tied head) on the
CPU.  The contracts are ``tests/test_nemotron_serving.py``'s, for the third
recurrence in the same rows:

(a) a prompt prefilled in windows beside a mate at OTHER starts and a
    filled-up row leaves its state row as one pass over the prompt does,
    with the kernels off and on (the prompt-window and paged decode kernels
    at ``n_rep`` 5); the decode step moves live rows only;
(b) the loop serves waves and windows (several prompts a dispatch)
    token-identical to the plain reference's greedy continuation, gives
    every state row and block back and feeds the shared ``ssm_*`` counters;
(c) a stream dropped and resumed rebuilds its state by recompute.
(Boot refusals: ``tests/test_jamba_block.py``.)
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

from test_jamba_block import _close, _ids  # noqa: F401
from test_jamba_block import config, kw, ref  # noqa: F401
from test_nemotron_serving import (  # the same toy geometry: BS, NB, T_W, C, R
    _feats, _go_live, _greedy, _loop_cfg, _table, _windows)
from test_prefill_chunked import _run, _wait_pool_drained

BS, NB, T_W, C, R = 4, 40, 12, 8, 5  # block, pool, table width, window, state rows
TYPES = ["mamba", "attention", "mamba"]


def _kw3(kw):  # noqa: F811
    return {**kw, "layer_types": TYPES, "num_layers": len(TYPES)}


@pytest.fixture(scope="module")
def cfg(kw):  # noqa: F811
    return llama_mod.LlamaConfig(**_kw3(kw))


@pytest.fixture(scope="module")
def params(cfg):
    return llama_mod.init_params(jax.random.PRNGKey(0), cfg)


def _paged(cfg, slots=3):
    z = llama_mod.zero_ssm(cfg, R, jnp.float32)
    # every row POISONED: a first window must start from zeros all the same
    z = z._replace(conv=[c + 7.0 for c in z.conv], state=[s + 3.0 for s in z.state],
                   row=jnp.full((slots,), R, jnp.int32))
    width = cfg.num_kv_heads * cfg.head_dim  # ONE KV head: 16 lanes a token
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
    read at 5 heads on one KV head (``kernels``: through the paged decode
    kernel and the prompt-window kernel, interpret mode) — emits the
    contiguous path's tokens."""
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
    state, toks = llama_mod.generate_chunk_paged(params, run, state, table, 8)
    np.testing.assert_array_equal(np.asarray(toks[1]), np.asarray(want_toks[0]))
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


def test_a_windows_tiles_count_the_attention_layer_alone(cfg):
    """``prefill_tile_counts`` (the ``prefill_key_tiles_*`` counters): a
    Mamba layer has no keys and adds no tile — one attention layer's pairs,
    at the q tile ``tile_sizes`` gives 5 heads a KV head."""
    from mlmicroservicetemplate_tpu.ops.prefill_attention import (
        count_live_tiles, tile_sizes)

    run = dataclasses.replace(cfg, pallas_decode=True)
    tq, tk = tile_sizes(C, 5, T_W * BS)
    assert C % tq == 0
    assert llama_mod.prefill_tile_counts(run, C, T_W, BS, 8, C) == count_live_tiles(
        8, C, C, 0, T_W * BS, 0, tq, tk)
    assert llama_mod.prefill_tile_counts(cfg, C, T_W, BS, 8, C) == (0, 0)


# ---------------------------------------------------------------------------
# the loop


def _bundle(monkeypatch, kw):  # noqa: F811
    from mlmicroservicetemplate_tpu.models.registry import build_model
    from test_jamba_block import _svc

    bundle = build_model(_svc(monkeypatch, kw))
    assert bundle.cfg.layer_types == ("mamba", "full", "mamba", "mamba")
    return bundle


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernel"])
def test_the_loop_serves_waves_and_windows_as_the_reference(
        monkeypatch, kw, ref, config, kernels):  # noqa: F811
    """Short prompts (the wave path: the state inserted into a row, the keys
    into blocks) and long ones (windows, three different prompts a dispatch)
    together: every stream's tokens are the plain reference's greedy
    continuation, teacher-forced; the rows, the blocks and the shared
    ``ssm_*`` counters add up afterwards.  With the kernels on
    (``USE_PALLAS_DECODE``, interpret mode) the scans take the fused kernel
    and ``ssm_scan_fused_tokens_total`` counts every scanned position; with
    them off it counts none."""
    from test_jamba_block import SMALL

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
    hp = ref.hyper({**config, **SMALL, "vocab_size": 300})
    for f, toks in zip(feats, outs):
        assert len(toks) == 12
        seq = np.concatenate([f["input_ids"], toks]).astype(np.int32)[None]
        logits = np.asarray(ref.logits(bundle.params, hp, seq))[0]
        n = int(f["length"])
        rows = logits[n - 1: n - 1 + len(toks)]
        assert float((rows.max(axis=-1) - rows[np.arange(len(toks)), toks]).max()) < 1e-5
    scanned = metrics.SSM_SCAN_TOKENS.labels("llama")._value.get() - scanned0
    masked = metrics.SSM_SCAN_MASKED.labels("llama")._value.get() - masked0
    assert scanned - masked == sum(int(f["length"]) for f in feats) and masked > 0
    fused = metrics.SSM_SCAN_FUSED.labels("llama")._value.get() - fused0
    assert fused == (scanned if kernels else 0)
    # three Mamba layers' [4, 160] float32 state and taps a stream; ONE
    # attention layer's K and V of ONE 16-wide head a token
    assert eng.stream_fixed_bytes() == bundle.cfg.ssm_row_bytes == 3 * (2560 + 960)
    assert eng.kv_token_bytes() == 2 * 1 * 16 * 4


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
