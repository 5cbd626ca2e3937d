"""The Trinity configuration: its file against the catalog's keys, its
cost arithmetic against ISSUE 31's numbers worked out by hand, its
entries in BENCHMARK.json by name, its readers on hand-made inputs, and
the cell end to end as a rehearsal.  (The reference against the
package's model functions, at a toy size: ``tests/test_trinity_block.py``.)"""

import json
import os

import pytest

from conftest import entry_reading

from cellbench import costs, costs_afmoe, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "trinity-mini-d5.longdoc-closed"
#: PR 31's entries that are still the cell's own by name; `streams_per_chunk`,
#: `device_idle_pct` and `prefill_stall_ms` were twins of chat-open's and
#: live under those names since PR 55 (test_entries.py holds every pair).
NEW_PER_LAYER = [
    "decode_step_ms.trinity", "decode_step_roofline.trinity",
    "decode_moe_ms.trinity", "moe_experts_roofline.trinity",
    "moe_overhead_ms.trinity", "moe_shared_ms.trinity",
    "decode_attn_window_ms.trinity", "decode_attn_full_ms.trinity",
    "paged_decode_attention_roofline.trinity", "window_keys_behind_pct.trinity",
    "moe_imbalance.trinity",
]
NEW_SHARED = [("prom_hist", {"family": "stream_batch_size"}), ("trace_idle_pct", {}),
              ("prom_counter_rate", {"family": "prefill_stall_seconds"})]


@pytest.fixture(scope="module")
def config():
    return spec.load_json(os.path.join(spec.HERE, "configs", "trinity-mini-d5.json"))


def test_catalog_keys_are_the_sources(config):
    """Every key of the catalog entry under its name; the two cuts in
    depth aside, each value the source's — layer_types whole."""
    source = {
        "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
        "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
        "model_type": "afmoe", "moe_intermediate_size": 1024, "mup_enabled": True,
        "n_group": 1, "num_attention_heads": 32, "num_dense_layers": 2,
        "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 32, "num_key_value_heads": 4,
        "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
        "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048,
        "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
        "vocab_size": 200192,
    }
    source["layer_types"] = source["layer_types"] * 8
    differs = {k for k, v in source.items() if config.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "num_dense_layers"} == set(config["reduced"])
    assert config["num_hidden_layers"] == 5 and config["num_dense_layers"] == 1
    for letter in ("router_bias", "sandwich_norm", "attn_gate", "qk_norm",
                   "nope_on_full", "mup_embed"):  # (a)-(f)
        assert letter in config["assumed"]
    bench = spec.load_benchmark()
    entry = [c for c in bench["configs"] if c["name"] == "trinity-mini-d5"][0]
    assert entry["reduced"] == ["num_hidden_layers", "num_dense_layers"]


def test_the_file_builds_the_pattern(config):
    from mlmicroservicetemplate_tpu.models.llama import LlamaConfig

    env = spec.service_env(config)
    cfg = LlamaConfig(**json.loads(env["LLAMA_CONFIG"]))
    assert cfg.layer_types == ("window", "window", "window", "full", "window")
    assert [cfg.layer_kind(li).experts for li in range(5)] == [False] + [True] * 4
    assert cfg.layer_kind(0).d_ff == 6144 and cfg.layer_kind(1).d_ff == 1024
    assert cfg.head_dim == 128 and cfg.q_dim == 4096 and cfg.window == 2048
    assert cfg.router_score == "sigmoid" and cfg.route_scale == 2.826
    assert cfg.norm_topk_prob and cfg.router_bias and cfg.num_shared_experts == 1
    assert cfg.qk_norm == "head" and not cfg.add_bos and cfg.mup_embed
    for field, want in config["expect_cfg"].items():
        if field != "pallas_decode":
            assert getattr(cfg, field) == spec.subst(want, config), field
    # 32 streams x (6016 + 256) tokens fit the pool, and little more
    block = 16 * costs_afmoe.kv_bytes_per_token_layer(config) * 5
    blocks = int(env["KV_BUDGET_MB"]) * 1_000_000 // block
    assert 32 * 392 <= blocks < 32 * 392 + 64
    assert int(env["PREFILL_MAX_PROMPT"]) + int(env["MAX_DECODE_LEN"]) == 392 * 16
    lo, hi = config["check_prompt_tokens"]
    assert lo > cfg.window and hi - 1 + 16 <= int(env["PREFILL_MAX_PROMPT"])
    assert config["logit_check_tokens"] > cfg.window


def test_costs_against_the_issues_arithmetic(config):
    lp = costs_afmoe.layer_params(config)
    # q, gate, o: 3 x 2048 x 4096; k, v: 2 x 2048 x 512
    assert lp["attention"] == 3 * 2048 * 4096 + 2 * 2048 * 512 == 27_262_976
    assert lp["one_expert"] == 3 * 2048 * 1024 == 6_291_456
    assert lp["shared"] == 6_291_456 and lp["router"] == 2048 * 128 + 128
    assert lp["experts"] == 128 * 6_291_456
    assert round(lp["expert_layer"] / 1e6, 1) == 839.1
    assert round(lp["dense_layer"] / 1e6, 1) == 65.0
    p = costs_afmoe.decoder_params(config)
    assert p["embedding"] == p["head"] == 200192 * 2048
    assert round((p["embedding"] + p["head"]) / 1e6, 1) == 820.0
    assert int(p["total"] / 1e6) == 4241 and round(p["total"] * 2 / 1e9, 2) == 8.48
    assert costs_afmoe.kv_bytes_per_token_layer(config) == 2048
    assert 32 * 6272 * 5 * 2048 == pytest.approx(2.06e9, rel=3e-3)
    assert costs_afmoe.experts_hit(config, 32) == pytest.approx(111.8, abs=0.05)
    assert costs_afmoe.experts_hit(config, 1) == pytest.approx(8.0)
    # the bytes count the experts the SEEDED router streams: 98.5 at 32 rows,
    # the reference's own reading, by 107.5 equally likely experts
    assert costs_afmoe.experts_streamed(config, 32) == pytest.approx(98.5, abs=0.3)
    assert costs_afmoe.experts_streamed(
        {**config, "routing_effective_experts": None}, 32) == pytest.approx(111.8, abs=0.05)
    # window-aware: 32 streams of 4300 tokens; a sliding layer reads 2048 each
    live, inside = 32 * 4300, 32 * 2048
    assert costs_afmoe.kv_read_bytes(config, live, inside) == 2048 * (
        1 * live + 4 * inside)
    # a short context is inside the window whole: no saving to claim
    assert costs_afmoe.kv_read_bytes(config, 32 * 700, 32 * 700) == 2048 * 5 * 32 * 700
    step = costs_afmoe.decode_step(config, 32, live, inside)
    assert step["expert_bytes"] == pytest.approx(
        4 * costs_afmoe.experts_streamed(config, 32) * 6_291_456 * 2)  # 4.96 GB
    uniform = costs_afmoe.decode_step(
        {**config, "routing_effective_experts": None}, 32, live, inside)
    assert uniform["expert_bytes"] == pytest.approx(
        4 * 111.8 * 6_291_456 * 2, rel=1e-3)  # ISSUE 31's 5.63 GB
    assert step["kv_bytes"] == 2048 * (live + 4 * inside) + 2048 * 5 * 32
    assert 6.8e9 < uniform["weight_bytes"] < 6.9e9  # + 0.40 GB dense + 0.82 GB head
    least, bound = costs.roofline_seconds(step, {"hbm_bytes_per_s": 819e9,
                                                 "bf16_flops_per_s": 197e12})
    assert bound == "hbm" and least * 1e3 == pytest.approx(8.6, abs=0.2)
    mm = costs_afmoe.expert_matmuls(config, 32)
    assert mm["flops"] == step["expert_flops"] == 2.0 * 4 * 8 * 6_291_456 * 32
    assert mm["bytes"] == pytest.approx(
        step["expert_bytes"] + 4 * 256 * (2 * 2048 + 3 * 1024) * 2)


def test_entries_resolve_by_name(config):
    """One configuration, one cell, the fourteen per-layer entries
    appended, each resolving to its files; of the three end-to-end lists
    ISSUE 31 names the cell is on ``tbt_p99_ms``'s alone (the other two
    spread by more than half their bounds: PERF.md section 6), so every
    per-layer entry moves that one."""
    bench = spec.load_benchmark()  # by NAME: later PRs append after these
    assert "trinity-mini-d5" in [c["name"] for c in bench["configs"]]
    entry = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert entry["chips"] == 1 and entry["traffic"] == "longdoc-closed"
    mine = [m for m in bench["per_layer"] if m["name"] in NEW_PER_LAYER]
    assert [m["name"] for m in mine] == NEW_PER_LAYER
    for m in mine:  # later cells append themselves to an entry's list
        assert CELL in m["workloads"] and m["moves"] == "tbt_p99_ms"
    cell = spec.resolve(CELL)
    assert set(NEW_PER_LAYER) <= {m.name for m in cell.per_layer}
    for reader, args in NEW_SHARED:
        assert callable(entry_reading(CELL, reader, **args).read)
    assert [m.name for m in cell.end_to_end] == ["tbt_p99_ms", "setup_s"]
    mix = cell.traffic
    assert mix["loop"] == "closed" and mix["clients"] == 32 and not mix["barrier"]
    assert mix["prompt_tokens"] == {"dist": "uniform", "lo": 2304, "hi": 6016}
    assert mix["output_tokens"]["median"] == 192 and mix["pool"] == 256
    assert mix["clients"] == int(cell.config["env"]["MAX_STREAMS"])
    # the cells that were there keep their metrics
    for old in ("mistral-7b-d8.decode-closed", "mistral-7b-d8.chat-open",
                "olmoe-1b-7b-d8.decode-closed"):
        assert not {m.name for m in spec.resolve(old).per_layer} & set(NEW_PER_LAYER)


class _Ctx:
    def __init__(self, **kw):
        self.notes = {}
        self.__dict__.update(kw)

    def prom_delta(self, family):
        return self.deltas.get(family)


def test_counter_ratio_reader():
    reader = spec.load_module(
        os.path.join(spec.HERE, "readers", "prom_counter_ratio.py"), "r_ratio")
    ctx = _Ctx(deltas={"kv_window_keys_behind": {"value": 300.0},
                       "kv_window_keys_read": {"value": 100.0}})
    assert reader.read(ctx, "kv_window_keys_behind", ["kv_window_keys_read"]) == 75.0
    # a program from before the counters, or a window in which nothing moved
    assert reader.read(_Ctx(deltas={}), "kv_window_keys_behind",
                       ["kv_window_keys_read"]) is None
    still = _Ctx(deltas={"kv_window_keys_behind": {"value": 0.0},
                         "kv_window_keys_read": {"value": 0.0}})
    assert reader.read(still, "kv_window_keys_behind", ["kv_window_keys_read"]) is None


def test_roofline_reader_counts_window_aware_bytes(config):
    reader = spec.load_module(
        os.path.join(spec.HERE, "readers", "afmoe_roofline.py"), "r_afmoe")

    class Trace:
        ops = {"paged_decode_attention": 0.004}

        def module_time(self, module):
            return (0.064, 1) if module == "jit_paged_chunk_fn" else (0.0, 0)

    recs = [{"first": 0.0, "done": 9.0, "prompt_tokens": n, "events": []}
            for n in (1000, 3000, 5000)]
    ctx = _Ctx(trace=Trace(), trace_span=[1.0, 4.0], all_records=recs, config=config,
               peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
               engine={"chunk_tokens": 4})
    assert reader.live_contexts(ctx) == (3.0, 9000.0, 1000.0 + 2048 + 2048)
    got = reader.read(ctx, "attention", "jit_paged_chunk_fn")
    kv = 2048 * (9000 + 4 * 5096)  # one full layer, four sliding
    assert got == pytest.approx(kv / 819e9 / (0.004 / 4) * 100.0)
    assert ctx.notes["afmoe_roofline:attention"]["live_tokens_in_window"] == 5096
    step = reader.read(ctx, "step", "jit_paged_chunk_fn")
    cost = costs_afmoe.decode_step(config, 3.0, 9000.0, 5096.0)
    assert step == pytest.approx(cost["bytes"] / 819e9 / 0.016 * 100.0)
    # a program without the kernel, or a run that was not traced: no value
    Trace.ops = {}
    assert reader.read(ctx, "attention", "jit_paged_chunk_fn") is None
    assert reader.read(ctx, "step", "jit_other") is None
    ctx.trace = None
    assert reader.read(ctx, "step", "jit_paged_chunk_fn") is None


def test_rehearsal_end_to_end():
    """The new cell's whole command on the CPU at a tiny size (chunked
    paged prefill across window boundaries, the view, the check); the
    traced run reports the counters' metrics and nothing under a device
    name."""
    from test_rehearsal import run

    r = run("--workload", CELL, "--seed", str(2**31 + 7), "--seconds", "2",
            "--trace", "1", "--rehearse", os.path.join(HERE, "rehearse_trinity.json"))
    assert r.returncode == 0, r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["metrics"] == {}
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert {"window_keys_behind_pct.trinity", "moe_imbalance.trinity",
            entry_reading(CELL, "prom_hist", family="stream_batch_size").name,
            entry_reading(CELL, "prom_counter_rate", family="prefill_stall_seconds").name,
            } <= set(last["rehearsal_values"])
    assert last["rehearsal_values"]["window_keys_behind_pct.trinity"]["value"] > 0
