"""Both plain references against the package's model functions, at a
tiny size on the CPU (float32 on both sides, so they agree closely)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import spec

REFS = os.path.join(spec.HERE, "references")


def test_mistral_reference_matches_models_llama():
    from mlmicroservicetemplate_tpu.models import llama

    ref = spec.load_module(os.path.join(REFS, "mistral.py"), "ref_mistral")
    config = {"hidden_size": 64, "num_attention_heads": 4,
              "num_key_value_heads": 2, "rope_theta": 10000.0,
              "rms_norm_eps": 1e-5}
    cfg = llama.LlamaConfig(vocab_size=97, d_model=64, num_heads=4,
                            num_kv_heads=2, num_layers=3, d_ff=160,
                            max_position=64)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    ids = np.random.default_rng(0).integers(3, 97, (2, 21)).astype(np.int32)
    want = llama.lm_logits(params, cfg, jnp.asarray(ids), jnp.ones_like(ids),
                           dtype=jnp.float32)
    got = ref.logits(params, ref.hyper(config), ids)
    assert got.shape == (2, 21, 97)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)
    # the check's comparison: the argmax passes, a wrong token does not
    top = np.asarray(want).argmax(-1)
    served = [[int(top[b, 9 + j]) for j in range(4)] for b in range(2)]
    ok = ref.compare(want, [10, 10], served)
    assert ok["correct"] and ok["worst_margin"] == 0.0 and ok["top1_share"] == 1.0
    worst = np.asarray(want).argmin(-1)
    bad = ref.compare(want, [10, 10],
                      [[int(worst[b, 9 + j]) for j in range(4)] for b in range(2)])
    assert not bad["correct"]
    assert not ref.compare(want, [10, 10], [[], []])["correct"]


def test_bert_reference_matches_models_bert():
    from mlmicroservicetemplate_tpu.models import bert

    ref = spec.load_module(os.path.join(REFS, "bert.py"), "ref_bert")
    cfg = bert.BertConfig(vocab_size=101, hidden_size=48, num_layers=2,
                          num_heads=4, intermediate_size=96, max_position=40)
    params = bert.init_params(jax.random.PRNGKey(1), cfg)
    ids = np.random.default_rng(1).integers(5, 101, (17,)).astype(np.int32)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :17] = ids
    mask = (np.arange(32) < 17).astype(np.int32)[None]
    want = jax.nn.softmax(bert.classify(
        params, cfg, jnp.asarray(padded), jnp.asarray(mask), dtype=jnp.float32))
    got = ref.probs(params, {"num_attention_heads": 4, "layer_norm_eps": 1e-12},
                    ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want)[0], atol=1e-5)
    assert float(np.sum(got)) == pytest.approx(1.0, abs=1e-5)
