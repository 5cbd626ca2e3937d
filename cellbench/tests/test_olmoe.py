"""The OLMoE configuration: its reference against the package's model
functions (tiny, CPU, float32), its cost arithmetic against numbers
worked out by hand, its entries in BENCHMARK.json by name, its readers
on a small hand-made trace, and the cell end to end as a rehearsal."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import entry_reading

from cellbench import costs, costs_moe, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "olmoe-1b-7b-d8.decode-closed"
#: What PR 27 made the cell read, as (reader, arguments): seven of its twelve
#: entries were twins of Mistral's and live under those names since PR 55.
NEW_READINGS = [
    ("trace_module_ms", {"module": "jit_paged_chunk_fn", "per": "step"}),
    ("moe_roofline", {"what": "step"}),
    ("trace_scope_ms", {"scopes": ["mlp"]}),
    ("trace_subscope_ms", {"scopes": ["moe_route", "moe_combine"]}),
    ("moe_roofline", {"what": "experts"}),
    ("trace_scope_ms", {"scopes": ["kv_write", "attn"]}),
    ("decode_roofline", {"what": "attention"}),
    ("prom_hist", {"family": "moe_load_imbalance"}),
    ("trace_idle_pct", {}),
    ("prom_hist", {"family": "stream_batch_size"}),
    ("prom_hist", {"family": "stream_queue_wait_seconds"}),
    ("prom_hist", {"family": "stream_admit_seconds"}),
]


@pytest.fixture(scope="module")
def config():
    return spec.load_json(os.path.join(spec.HERE, "configs", "olmoe-1b-7b-d8.json"))


def test_reference_matches_models_llama():
    from mlmicroservicetemplate_tpu.models import llama

    ref = spec.load_module(os.path.join(spec.HERE, "references", "olmoe.py"),
                           "ref_olmoe")
    config = {"hidden_size": 64, "num_attention_heads": 4,
              "num_key_value_heads": 4, "rope_theta": 10000, "rms_norm_eps": 1e-5,
              "num_experts_per_tok": 2, "norm_topk_prob": False}
    cfg = llama.LlamaConfig(
        vocab_size=97, d_model=64, num_heads=4, num_kv_heads=4, num_layers=3,
        d_ff=32, max_position=64, num_experts=8, experts_per_token=2,
        qk_norm=True, pallas_interpret=True)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    ids = np.random.default_rng(0).integers(3, 97, (2, 21)).astype(np.int32)
    want = llama.lm_logits(params, cfg, jnp.asarray(ids), jnp.ones_like(ids),
                           dtype=jnp.float32)
    picks = []
    got = ref.logits(params, ref.hyper(config), ids, chosen=picks)
    assert got.shape == (2, 21, 97)
    assert len(picks) == 3 and all(p.shape == (2, 21, 2) for p in picks)
    # one step over these two rows: their last REAL positions, never padding
    r = ref.routing(picks, [21, 9], 8)
    assert r["rows"] == 2 and 2 <= r["experts_hit_least"] <= r["experts_hit"] <= 4
    assert r["busiest_expert_share"] in (0.5, 1.0)
    by_hand = len({int(e) for e in picks[0][0, 20]} | {int(e) for e in picks[0][1, 8]})
    assert ref.routing(picks[:1], [21, 9], 8)["experts_hit"] == by_hand
    assert ref.logits(params, ref.hyper(config), ids, head=False).shape == (2, 21, 64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)
    assert ref.logit_rms_error(got, want, [21, 21]) < 1e-5
    # a changed routing rule is another model, outside that tolerance
    for wrong in ({"num_experts_per_tok": 1}, {"norm_topk_prob": True}):
        other = ref.logits(params, ref.hyper({**config, **wrong}), ids)
        assert float(np.abs(np.asarray(other) - np.asarray(want)).max()) > 2e-3
        assert ref.logit_rms_error(other, want, [21, 21]) > 5e-4
    # rms over the REAL positions only: 0.5 off there, anything on the padding
    off = np.asarray(want) + 0.5
    off[1, 9:] = 1e6
    assert ref.logit_rms_error(want, off, [21, 9]) == pytest.approx(0.5, rel=1e-5)
    top = np.asarray(want).argmax(-1)
    served = [[int(top[b, 9 + j]) for j in range(4)] for b in range(2)]
    ok = ref.compare(want, [10, 10], served)
    assert ok["correct"] and ok["worst_margin"] == 0.0
    assert not ref.compare(want, [10, 10], [[], []])["correct"]


def test_costs_against_hand_computed_numbers(config):
    lp = costs_moe.expert_layer_params(config)
    # q, k, v, o: 4 x 2048^2; 64 experts x 3 x 2048 x 1024; router 2048 x 64;
    # norms 2 x 2048 + q/k-norm 2 x 2048
    assert lp["attention"] == 16_777_216
    assert lp["experts"] == 402_653_184
    assert lp["router"] == 131_072 and lp["norms"] == 8_192
    assert lp["total"] == 419_569_664  # 419.6 M a layer
    p = costs_moe.decoder_params(config)
    assert p["embedding"] == p["head"] == 50304 * 2048
    assert p["total"] == 8 * 419_569_664 + 2 * 103_022_592 + 2048  # 3.56 B
    assert round(p["total"] / 1e9, 2) == 3.56
    assert costs.kv_bytes_per_token(config) == 65_536
    # 64 tokens x 8 of 64 experts: all but 0.01 of an expert are hit
    assert costs_moe.experts_hit(config, 64) == pytest.approx(63.99, abs=0.01)
    assert costs_moe.experts_hit(config, 1) == pytest.approx(8.0)
    # nothing the program or a run reports moves it: uniform, as stated
    assert costs_moe.experts_hit({**config, "measured_pick_share": [[1.0] * 64]},
                                 8) == costs_moe.experts_hit(config, 8)
    step = costs_moe.decode_step(config, 64, 64 * 136)
    assert step["expert_bytes"] == pytest.approx(8 * 402_653_184 * 2 * 63.99 / 64,
                                                 rel=1e-4)  # 6.44 GB
    assert step["kv_bytes"] == 65_536 * (64 * 136 + 64)
    assert 6.8e9 < step["weight_bytes"] < 7.0e9
    assert step["expert_flops"] == 2.0 * 8 * 8 * 3 * 2048 * 1024 * 64
    least, bound = costs.roofline_seconds(step, {"hbm_bytes_per_s": 819e9,
                                                 "bf16_flops_per_s": 197e12})
    assert bound == "hbm" and least * 1e3 == pytest.approx(9.1, abs=0.2)
    mm = costs_moe.expert_matmuls(config, 64)
    assert mm["flops"] == step["expert_flops"]
    assert mm["bytes"] == pytest.approx(
        step["expert_bytes"] + 8 * 512 * (2 * 2048 + 3 * 1024) * 2)


def test_entries_resolve_by_name(config):
    """One configuration, one cell, the three end-to-end lists and the
    twelve readings of PR 27, each found by NAME or by what it reads:
    later PRs append cells and fold twin entries (test_entries.py holds
    every pair)."""
    bench = spec.load_benchmark()
    assert "olmoe-1b-7b-d8" in [c["name"] for c in bench["configs"]]
    assert CELL in [w["name"] for w in bench["workloads"]]
    cell = spec.resolve(CELL)
    for reader, args in NEW_READINGS:
        assert callable(entry_reading(CELL, reader, **args).read)
    assert [m.name for m in cell.end_to_end] == [
        "ttft_p95_ms", "tbt_p95_ms", "tokens_per_s", "setup_s"]
    env = spec.service_env(cell.config)
    llama_cfg = json.loads(env["LLAMA_CONFIG"])
    assert llama_cfg["num_experts"] == 64 and llama_cfg["experts_per_token"] == 8
    assert llama_cfg["qk_norm"] is True and llama_cfg["norm_topk_prob"] is False
    assert llama_cfg["d_ff"] == 1024 and llama_cfg["num_layers"] == 8
    # OLMoE's tokenizer has no BOS, so a prompt is its words and no more
    assert llama_cfg["add_bos"] is False and cell.config["prompt"]["specials"] == 0
    # the serving environment is Mistral's but for the buckets and the pool
    mistral = spec.resolve("mistral-7b-d8.decode-closed").config["env"]
    assert {k for k in set(mistral) | set(cell.config["env"])
            if mistral.get(k) != cell.config["env"].get(k)} == {
        "SEQ_BUCKETS", "KV_BUDGET_MB"}
    # the cell that was there reads nothing of the expert block
    old = spec.resolve("mistral-7b-d8.decode-closed")
    assert not {m.reader for m in old.per_layer} & {"moe_roofline", "trace_subscope_ms"}


def test_catalog_keys_are_the_sources(config):
    """Every number of the catalog entry under its key, depth aside."""
    source = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
              "hidden_size": 2048, "intermediate_size": 1024,
              "max_position_embeddings": 4096, "model_type": "olmoe",
              "norm_topk_prob": False, "num_attention_heads": 16,
              "num_experts": 64, "num_experts_per_tok": 8,
              "num_hidden_layers": 16, "num_key_value_heads": 16,
              "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
              "tie_word_embeddings": False, "vocab_size": 50304}
    differs = {k for k, v in source.items() if config.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"} == set(config["reduced"])


def _trace_dir(tmp_path, paths):
    """A hand-made .xplane.pb: one device plane, one executable run of
    1000 ns, one operation of 100 ns per scope path given."""
    def varint(n):
        out = b""
        while True:
            b, n = n & 0x7F, n >> 7
            out += bytes([b | (0x80 if n else 0)])
            if not n:
                return out

    def field(num, wire, payload):
        return varint(num << 3 | wire) + (
            varint(len(payload)) + payload if wire == 2 else varint(payload))

    def entry(key, msg):
        return field(1, 0, key) + field(2, 2, msg)

    stat_md = field(5, 2, entry(1, field(1, 0, 1) + field(2, 2, b"tf_op")))
    ev_md = field(4, 2, entry(1, field(1, 0, 1) + field(2, 2, b"jit_paged_chunk_fn(1)")))
    ops = b""
    for i, path in enumerate(paths):
        mid = 2 + i
        stat = field(1, 0, 1) + field(5, 2, path.encode())
        ev_md += field(4, 2, entry(mid, field(1, 0, mid) + field(
            2, 2, f"%fusion.{i}".encode()) + field(5, 2, stat)))
        ops += field(4, 2, field(1, 0, mid) + field(2, 0, (10 + 110 * i) * 1000)
                     + field(3, 0, 100 * 1000))
    modules = field(2, 2, b"XLA Modules") + field(3, 0, 0) + field(
        4, 2, field(1, 0, 1) + field(2, 0, 0) + field(3, 0, 1000 * 1000))
    plane = (field(2, 2, b"/device:TPU:0") + field(3, 2, modules)
             + field(3, 2, field(2, 2, b"XLA Ops") + field(3, 0, 0) + ops)
             + ev_md + stat_md)
    d = tmp_path / ".cellbench_work" / "trace_x" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "h.xplane.pb").write_bytes(field(1, 2, plane))
    return str(tmp_path)


def test_subscope_reader_takes_the_innermost_of_its_own_names(tmp_path, monkeypatch):
    from cellbench import scopes

    root = _trace_dir(tmp_path, [
        "jit(f)/decode_chunk/while/body/mlp/moe_route/sort",
        "jit(f)/decode_chunk/while/body/mlp/moe_experts/gmm",
        "jit(f)/decode_chunk/while/body/mlp/moe_combine/gather",
        "jit(f)/decode_chunk/while/body/mlp/rmsnorm",
        "jit(f)/decode_chunk/while/body/attn/dot",
    ])
    monkeypatch.setattr(scopes.spec, "REPO", root)
    monkeypatch.setattr(scopes.newest_xplane, "__defaults__", (root,))
    reader = spec.load_module(
        os.path.join(spec.HERE, "readers", "trace_subscope_ms.py"), "r_sub")
    ctx = type("C", (), {"trace": object(), "engine": {"chunk_tokens": 4},
                         "notes": {}})()
    got = reader.read(ctx, "jit_paged_chunk_fn", ["moe_route", "moe_combine"])
    assert got == pytest.approx(2 * 100e-9 / 4 * 1e3)  # two ops, 4 steps, in ms
    assert reader.read(ctx, "jit_paged_chunk_fn", ["moe_experts"]) == pytest.approx(
        100e-9 / 4 * 1e3)
    # the accepted table folds all three into ``mlp``
    assert scopes.scope_table("jit_paged_chunk_fn", root)["seconds"]["mlp"] == (
        pytest.approx(400e-9))
    # a program from before the scopes: nothing to read, no value
    assert reader.read(ctx, "jit_paged_chunk_fn", ["no_such_scope"]) is None
    assert reader.read(ctx, "jit_other", ["moe_route"]) is None


def test_rehearsal_end_to_end():
    """The new cell's whole command on the CPU at a tiny size; the traced
    run reports the counters' metrics and nothing under a device name."""
    from test_rehearsal import run

    r = run("--workload", CELL, "--seed", str(2**31 + 7), "--seconds", "2",
            "--trace", "1", "--rehearse", os.path.join(HERE, "rehearse_olmoe.json"))
    assert r.returncode == 0, r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["metrics"] == {}
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert {entry_reading(CELL, "prom_hist", family=f).name
            for f in ("moe_load_imbalance", "stream_batch_size")} <= set(
        last["rehearsal_values"])
