"""Mamba layers served: the recurrent state beside the paged KV, through
the model's step kinds and the continuous loop (``engine/streams.py``'s
state rows) — the toy of ``tests/test_nemotron_block.py`` on the CPU.

The judged contracts:
(a) a prompt prefilled in windows beside batch mates at OTHER starts and a
    filled-up row leaves its state row as one pass over the prompt does; a
    row that held another stream's state carries nothing over; the decode
    step moves live rows' states only;
(b) the loop serves waves and windows token-identical to the plain
    reference's greedy continuation, gives every state row and block back,
    and counts what it scanned;
(c) a model without recurrent layers has no row, argument or counter.
(A checkpointed stream's resume: ``tests/test_nemotron_resume.py``.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlmicroservicetemplate_tpu.engine import InferenceEngine
from mlmicroservicetemplate_tpu.engine.streams import ContinuousDecodeLoop
from mlmicroservicetemplate_tpu.models import llama as llama_mod
from mlmicroservicetemplate_tpu.models.gpt import PagedState
from mlmicroservicetemplate_tpu.models.sampling import greedy_params
from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
from mlmicroservicetemplate_tpu.utils import metrics

from helpers import tiny_llama_bundle
from test_nemotron_block import TOY, _close, _ids  # noqa: F401
from test_nemotron_block import config, kw, ref  # noqa: F401
from test_prefill_chunked import _cfg, _run, _wait_pool_drained

BS, NB, T_W, C, R = 4, 40, 12, 8, 5  # block, pool, table width, window, state rows
#: This file's toy: one layer of each kind and a second Mamba layer after
#: the attention (4 layers compile in a fraction of the 11's time here).
PATTERN = "ME*M"


@pytest.fixture(scope="module")
def cfg(kw):  # noqa: F811
    return llama_mod.LlamaConfig(
        **{**kw, "layer_pattern": PATTERN, "num_layers": len(PATTERN)})


@pytest.fixture(scope="module")
def params(cfg):  # noqa: F811
    return llama_mod.init_params(jax.random.PRNGKey(0), cfg)


def _paged(cfg, slots=3):  # noqa: F811
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


def _windows(params, cfg, state, ids, row, table_row, mate_ids, mate_row, mate_table):  # noqa: F811
    """``ids`` in windows of C into state row ``row`` as row 1 of a
    three-row dispatch: row 0 a mate at another start, row 2 filled up."""
    n, pos, mate_pos = len(ids), 0, 0
    while pos < n:
        end = min(pos + C, n)
        iw, mw = np.zeros((3, C), np.int32), np.zeros((3, C), np.int32)
        tabs, starts = np.full((3, T_W), NB, np.int32), np.zeros(3, np.int32)
        iw[1, :end - pos], mw[1, :end - pos] = ids[pos:end], 1
        starts[1], tabs[1] = pos, table_row
        iw[0], mw[0], starts[0], tabs[0] = (
            mate_ids[mate_pos:mate_pos + C], 1, mate_pos, mate_table)
        rows = np.array([[mate_row, C], [row, end - pos - (end == n)], [R, 0]], np.int32)
        state = llama_mod.paged_prefill_chunk(
            params, cfg, state, jnp.asarray(tabs), iw, mw, starts,
            ssm_rows=jnp.asarray(rows))
        pos, mate_pos = end, mate_pos + C
    return state


def _table(first, n=8):
    row = np.full((T_W,), NB, np.int32)
    row[:n] = np.arange(first, first + n)
    return row


def _go_live(state, slot, row, ids, table_row):
    n = len(ids)
    kv = np.zeros((T_W * BS,), np.int32)
    kv[:n] = 1
    state = state._replace(
        key_valid=state.key_valid.at[slot].set(kv),
        write_idx=state.write_idx.at[slot].set(n - 1),
        last_token=state.last_token.at[slot].set(int(ids[-1])),
        done=state.done.at[slot].set(False),
        ssm=state.ssm._replace(row=state.ssm.row.at[slot].set(row)))
    table = np.full((state.done.shape[0], T_W), NB, np.int32)
    table[slot] = table_row
    return state, jnp.asarray(table)


@pytest.mark.parametrize("n", [21, 24, 9])
def test_windows_beside_unequal_mates_leave_the_one_shot_state(cfg, params, n):  # noqa: F811
    """A prompt of ``n`` tokens (a last short window, a window-aligned
    end, a single window) into a POISONED state row, beside a mate whose
    windows run at other starts and a filled-up row: the row holds what
    one pass over the prompt leaves (``forward_hidden``'s state: all but
    the last token), the mate's row its own, no other row moved, and the
    paged decode from it emits the contiguous path's tokens."""
    ids, mate = _ids(n, 11), _ids(40, 12)
    state = _windows(params, cfg, _paged(cfg), ids, 2, _table(3), mate, 4, _table(20))
    out: list = []
    llama_mod.forward_hidden(params, cfg, ids[None], np.ones((1, n), np.int32),
                             ssm_out=out)
    for got, want in zip(state.ssm.state, out[0].state):
        assert _close(got[2], want[0]) < 1e-5
        assert _close(got[0], 3.0) == 0.0 and _close(got[3], 3.0) == 0.0
    for got, want in zip(state.ssm.conv, out[0].conv):
        assert _close(got[2], want[0]) < 1e-6
    want_toks = llama_mod.greedy_generate(
        params, cfg, ids[None], np.ones((1, n), np.int32), 8)
    state, table = _go_live(state, 1, 2, ids, _table(3))
    before = [np.asarray(s) for s in state.ssm.state]
    state, (toks, counts) = llama_mod.generate_chunk_paged(params, cfg, state, table, 8)
    np.testing.assert_array_equal(np.asarray(toks[1]), np.asarray(want_toks[0]))
    assert counts.shape == (1, 16)  # a row an EXPERT layer, the published experts
    # only the live slot's row moved: the mate's (no slot), the dead slots', the free ones' did not
    for b, a in zip(before, state.ssm.state):
        moved = np.abs(b - np.asarray(a)).reshape(R, -1).max(axis=1) > 0
        assert moved.tolist() == [False, False, True, False, False]


@pytest.mark.parametrize("n", [21, 24])
def test_windows_through_the_kernels_leave_the_one_shot_state(cfg, params, n):  # noqa: F811
    """The same dispatches with the kernels on (``pallas_decode``, interpret
    mode: the fused scan of ``ops/ssm.py`` and the prompt-window attention):
    the prompt's state row and taps are the one pass's (the ``jax.numpy``
    scan's), a filled-up row's and every other row's bit for bit as they
    were, float32."""
    import dataclasses

    kernels = dataclasses.replace(cfg, pallas_decode=True)
    ids, mate = _ids(n, 11), _ids(40, 12)
    state = _windows(params, kernels, _paged(cfg), ids, 2, _table(3), mate, 4, _table(20))
    out: list = []
    llama_mod.forward_hidden(params, cfg, ids[None], np.ones((1, n), np.int32),
                             ssm_out=out)
    for got, want in zip(state.ssm.state, out[0].state):
        assert got.dtype == jnp.float32 and _close(got[2], want[0]) < 1e-5
        assert _close(got[0], 3.0) == 0.0 and _close(got[3], 3.0) == 0.0
    for got, want in zip(state.ssm.conv, out[0].conv):
        assert _close(got[2], want[0]) < 1e-6


def test_a_done_or_freed_slot_moves_no_state(cfg, params):  # noqa: F811
    """A slot that is done, and a slot whose table row the host has
    cleared (a freed slot with a STALE row index naming a row since given
    to another prompt), leave every state row as it was."""
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
    assert not np.isnan(np.asarray(jax.tree.leaves(after.ssm)[0])).any()


# ---------------------------------------------------------------------------
# the loop


def _bundle(monkeypatch, kw):  # noqa: F811
    from mlmicroservicetemplate_tpu.models.registry import build_model
    from test_nemotron_block import _svc

    return build_model(_svc(monkeypatch, {
        **kw, "layer_pattern": PATTERN, "num_layers": len(PATTERN)}))


def _loop_cfg(**over):
    return _cfg(**{**dict(
        paged_kv=True, kv_block_size=4, prefill_chunk=8, prefill_budget=24,
        prefill_max_prompt=48, seq_buckets=(16,), max_decode_len=12,
        pallas_interpret=True, max_stream_queue=8,
        # 81 blocks of 4 tokens (1536 B each): every stream's prompt and
        # answer fit at once — a dry pool is another test's subject
        kv_budget_mb=0.12), **over})


def _feats(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [{"input_ids": rng.integers(5, 290, n).astype(np.int32),
             "length": np.int32(n)} for n in lengths]


def _greedy(bundle, feats, n=12):
    ids = np.asarray(feats["input_ids"])[None]
    return np.asarray(llama_mod.greedy_generate(
        bundle.params, bundle.cfg, ids, np.ones_like(ids), n))[0].tolist()


def _gauge(state):
    return metrics.SSM_STATE_ROWS.labels("llama", state)._value.get()


def test_the_loop_serves_waves_and_windows_as_the_reference(monkeypatch, kw, ref, config):  # noqa: F811
    """Short prompts (the wave path: prefill + first chunk, the state
    inserted into a row) and long ones (windows, three different prompts
    a dispatch) together: every stream's tokens are the plain reference's
    greedy continuation, teacher-forced; the rows, the blocks and the
    counters add up afterwards."""
    bundle = _bundle(monkeypatch, kw)
    cfgc = _loop_cfg()
    eng = InferenceEngine(bundle, cfgc, ReplicaSet(make_mesh(1)))
    feats = _feats((7, 30, 45, 30, 12))
    scanned0 = metrics.SSM_SCAN_TOKENS.labels("llama")._value.get()
    masked0 = metrics.SSM_SCAN_MASKED.labels("llama")._value.get()
    cdl = ContinuousDecodeLoop(eng, cfgc)
    try:
        assert len(cdl._ssm_free) == cdl.n_slots == 4  # a row a slot, none spare
        outs = _run(cdl, feats)
        assert cdl.prefill_chunk_dispatches > 0
        assert _wait_pool_drained(eng.kv_pool) == 0
        assert sorted(cdl._ssm_free) == list(range(4))
        assert (_gauge("live"), _gauge("prefill"), _gauge("free")) == (0, 0, 4)
        assert metrics.SSM_STATE_BYTES.labels("llama")._value.get() == 0
    finally:
        cdl.stop()
    hp = ref.hyper({**config, "vocab_size": 300, "num_hidden_layers": 4,
                    "hybrid_override_pattern": PATTERN})
    for f, toks in zip(feats, outs):
        assert len(toks) == 12
        seq = np.concatenate([f["input_ids"], toks]).astype(np.int32)[None]
        logits = np.asarray(ref.logits(bundle.params, hp, seq))[0]
        n = int(f["length"])
        rows = logits[n - 1: n - 1 + len(toks)]
        assert float((rows.max(axis=-1) - rows[np.arange(len(toks)), toks]).max()) < 1e-5
    scanned = metrics.SSM_SCAN_TOKENS.labels("llama")._value.get() - scanned0
    masked = metrics.SSM_SCAN_MASKED.labels("llama")._value.get() - masked0
    real = sum(int(f["length"]) for f in feats)
    assert scanned - masked == real and masked > 0
    assert eng.stream_fixed_bytes() == bundle.cfg.ssm_row_bytes == 2 * 4864
    # ONE attention layer's keys and values a token, not eleven layers'
    assert eng.kv_token_bytes() == 2 * 1 * 2 * 24 * 4
    assert eng.kv_bytes_estimate(feats[0]) == (16 + 12) * 384 + 2 * 4864


def test_the_loop_counts_the_expert_rows_its_windows_ran_and_skipped(monkeypatch, kw):  # noqa: F811
    """Two long prompts through windows (8 tokens x 5 experts a token, 4 of
    16 experts held; a rung is 8 rows and leaves out 16 here): the prompt dispatches' counts
    ride the next chunk's fetch, ``moe_rows_total`` and ``/status``'s
    ``expert_rows`` count what each dispatch's expert layers ran and left
    out from them — some rows skipped in the windows, none in the decode
    steps, whose ladder has one rung."""
    from mlmicroservicetemplate_tpu.ops import moe

    monkeypatch.setattr(moe, "ROW_TILE", 8)
    monkeypatch.setattr(moe, "LADDER_MIN_SKIP", 16)  # a step's 20 rows: one rung
    bundle = _bundle(monkeypatch, kw)
    cfgc = _loop_cfg()
    eng = InferenceEngine(bundle, cfgc, ReplicaSet(make_mesh(1)))

    def seen():
        return {(kind, state): metrics.MOE_ROWS.labels("llama", kind, state)._value.get()
                for kind in ("decode", "prefill") for state in ("ran", "skipped")}

    before = seen()
    cdl = ContinuousDecodeLoop(eng, cfgc)
    try:
        outs = _run(cdl, _feats((30, 45), seed=3))
        rows = {k: list(v) for k, v in cdl.moe_rows.items()}
        windows, chunks = cdl.prefill_chunk_dispatches, cdl.chunk_dispatches
    finally:
        cdl.stop()
    assert all(len(t) == 12 for t in outs) and not cdl._moe_windows
    grown = {key: v - before[key] for key, v in seen().items()}
    assert grown == {(kind, state): rows[kind][i] for kind in rows
                     for i, state in enumerate(("ran", "skipped"))}
    layers, k = len(bundle.cfg.expert_layers), bundle.cfg.experts_per_token
    # a window alone is 8 tokens, a batch the full width of 3 x 8
    assert sum(rows["prefill"]) % (layers * 8 * k) == 0
    assert layers * 8 * k * windows <= sum(rows["prefill"]) <= layers * 24 * k * windows
    assert rows["prefill"][1] > 0
    assert rows["decode"] == [chunks * 4 * layers * cdl.n_slots * k, 0]


def test_the_loop_counts_the_held_rows_of_the_dispatches_that_took_the_kernels(monkeypatch, kw):  # noqa: F811
    """The same two prompts with the latent 128 lanes wide and the shape
    rule (``ops/moe.row_kernels_fit``) lowered to a window's 40 rows of
    512 B: the windows' expert blocks run the two DMA kernels (interpret
    mode), ``moe_rows_fused_total{kind="prefill"}`` and ``/status``'s
    ``fused`` grow by exactly the HELD rows of those dispatches' counts,
    a decode chunk's 20-row steps keep XLA's form and add nothing, and
    ``moe_rows_total`` still counts every row of every call."""
    from helpers import expert_row_kernels_at_toy_size
    from mlmicroservicetemplate_tpu.ops import moe

    monkeypatch.setattr(moe, "ROW_TILE", 8)
    monkeypatch.setattr(moe, "LADDER_MIN_SKIP", 16)
    expert_row_kernels_at_toy_size(monkeypatch, rows=40, row_bytes=512)
    bundle = _bundle(monkeypatch, {**kw, "moe_latent": 128})
    k, first, held = (bundle.cfg.experts_per_token, bundle.cfg.expert_first,
                      bundle.cfg.held)
    assert moe.row_kernels_fit(8 * k, 128, jnp.float32) and moe.row_kernels_fit(24 * k, 128, jnp.float32)
    assert not moe.row_kernels_fit(4 * k, 128, jnp.float32)  # a step of the 4 slots
    cfgc = _loop_cfg()
    eng = InferenceEngine(bundle, cfgc, ReplicaSet(make_mesh(1)))

    def fused():
        return {kind: metrics.MOE_ROWS_FUSED.labels("llama", kind)._value.get()
                for kind in ("decode", "prefill")}

    before = fused()
    cdl = ContinuousDecodeLoop(eng, cfgc)
    arrived, note = {"decode": 0, "prefill": 0}, cdl._note_moe_rows

    def keep(kind, counts, tokens, steps=1):
        arrived[kind] += int(np.asarray(counts)[:, first:first + held].sum())
        return note(kind, counts, tokens, steps)

    cdl._note_moe_rows = keep
    try:
        outs = _run(cdl, _feats((30, 45), seed=3))
        rows, seen = dict(cdl.moe_rows), dict(cdl.moe_rows_fused)
    finally:
        cdl.stop()
    assert all(len(t) == 12 for t in outs)
    assert arrived["prefill"] > 0 and arrived["decode"] > 0
    assert seen == {"prefill": arrived["prefill"], "decode": 0}
    assert {kind: v - before[kind] for kind, v in fused().items()} == seen
    assert 0 < seen["prefill"] <= rows["prefill"][0] and rows["decode"][1] == 0


def test_a_model_without_recurrent_layers_has_none_of_it():
    bundle = tiny_llama_bundle()
    cfgc = _cfg(paged_kv=True, kv_block_size=8, prefill_chunk=8, prefill_max_prompt=48)
    eng = InferenceEngine(bundle, cfgc, ReplicaSet(make_mesh(1)))

    def families():  # every ssm_* sample of this model's label, as /metrics has it
        return [ln for ln in metrics.render()[0].decode().splitlines()
                if ln.startswith("ssm_") and f'model="{bundle.name}"' in ln]

    before = families()
    cdl = ContinuousDecodeLoop(eng, cfgc)
    try:
        assert cdl._ssm_free is None and eng.stream_fixed_bytes() == 0
        assert cdl._ssm_window_args(3) == () and cdl._ssm_row_arg() == ()
        _run(cdl, _feats((19,), seed=1)[:1])
        assert cdl._state.ssm == ()
    finally:
        cdl.stop()
    assert families() == before  # no child made, none moved
