"""A stream with a recurrent state that is dropped and brought back: no
tier carries a state row, so the state is rebuilt by recomputing the
prompt and what the stream had emitted (``tests/test_nemotron_serving.py``
has the loop's other contracts and this file's toy)."""

import pytest

from mlmicroservicetemplate_tpu.engine import InferenceEngine
from mlmicroservicetemplate_tpu.engine.streams import ContinuousDecodeLoop
from mlmicroservicetemplate_tpu.engine.supervisor import Supervisor
from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
from mlmicroservicetemplate_tpu.utils import metrics

from test_nemotron_block import config, kw  # noqa: F401
from test_nemotron_serving import _bundle, _feats, _greedy, _loop_cfg
from test_prefill_chunked import _run, _wait_pool_drained


@pytest.mark.parametrize("site", ["prefill_chunk:fatal@2", "chunk:fatal@2"])
def test_a_checkpointed_stream_resumes_to_the_same_tokens(monkeypatch, kw, site):  # noqa: F811
    """A fatal fault at a prompt's second window, or at the second decode
    chunk: the supervised loop checkpoints the stream (its state row and
    blocks go back), rebuilds the state, and the resume — the prompt and
    what was delivered, prefilled again — continues token-identically:
    the recurrent state was rebuilt by recompute, and counted."""
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
