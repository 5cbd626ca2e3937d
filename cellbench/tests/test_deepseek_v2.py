"""The DeepSeek-V2 configuration: its file against the catalog's keys,
its cost arithmetic against ISSUE 33's numbers worked out by hand, its
entries in BENCHMARK.json by name, its reader on hand-made inputs, and
the cell end to end as a rehearsal.  (The reference against the
package's model functions, at a toy size: ``tests/test_deepseek_block.py``.)"""

import json
import os

import pytest

from cellbench import costs, costs_mla, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "deepseek-v2-ep4-d5.longdoc-closed"
NEW_PER_LAYER = [
    "decode_step_ms.dsv2", "decode_step_roofline.dsv2",
    "decode_attn_latent_ms.dsv2", "mla_absorb_ms.dsv2", "mla_proj_ms.dsv2",
    "latent_decode_attention_roofline.dsv2", "decode_moe_ms.dsv2",
    "moe_experts_roofline.dsv2", "moe_overhead_ms.dsv2", "moe_shared_ms.dsv2",
    "moe_held_share_pct.dsv2", "moe_imbalance.dsv2",
    "table_blocks_dead_pct.dsv2", "streams_per_chunk.dsv2",
    "prefill_stall_ms.dsv2", "device_idle_pct.dsv2",
]
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture(scope="module")
def config():
    return spec.load_json(
        os.path.join(spec.HERE, "configs", "deepseek-v2-ep4-d5.json"))


def test_catalog_keys_are_the_sources(config):
    """Every key of the catalog entry under its name; the three cuts
    aside, each value the source's — rope_scaling whole."""
    source = {
        "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
        "hidden_size": 5120, "intermediate_size": 12288, "kv_lora_rank": 512,
        "max_position_embeddings": 163840, "model_type": "deepseek_v2",
        "moe_intermediate_size": 1536, "moe_layer_freq": 1, "n_group": 8,
        "n_routed_experts": 160, "n_shared_experts": 2, "norm_topk_prob": False,
        "num_attention_heads": 128, "num_experts_per_tok": 6,
        "num_hidden_layers": 60, "num_key_value_heads": 128, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                         "mscale": 0.707, "mscale_all_dim": 0.707,
                         "original_max_position_embeddings": 4096, "type": "yarn"},
        "rope_theta": 10000, "routed_scaling_factor": 16,
        "scoring_func": "softmax", "seq_aux": True, "tie_word_embeddings": False,
        "topk_group": 3, "topk_method": "group_limited_greedy", "v_head_dim": 128,
        "vocab_size": 102400,
    }
    differs = {k for k, v in source.items() if config.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert differs == set(config["reduced"])
    for key, cut in config["reduced"].items():
        assert cut["source"] == source[key] and cut["here"] == config[key], key
    assert config["router_experts"] == 160  # the router's width: published
    for note in ("deployment", "inner_norms", "rotary_pairing", "yarn",
                 "latent_lanes", "tokenizer", "dtype", "weights"):
        assert note in config["assumed"]
    bench = spec.load_benchmark()
    entry = [c for c in bench["configs"] if c["name"] == "deepseek-v2-ep4-d5"][0]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"].endswith("deepseek-ai/DeepSeek-V2/blob/main/config.json")


def test_the_file_builds_the_latent_model(config):
    from mlmicroservicetemplate_tpu.models.llama import LlamaConfig

    env = spec.service_env(config)
    cfg = LlamaConfig(**json.loads(env["LLAMA_CONFIG"]))
    assert cfg.mla and [cfg.layer_kind(li).experts for li in range(5)] == [False] + [True] * 4
    assert cfg.layer_kind(0).d_ff == 12288 and cfg.layer_kind(1).d_ff == 1536
    assert (cfg.head_dim, cfg.q_dim, cfg.o_dim) == (192, 128 * 192, 128 * 128)
    assert (cfg.latent_dim, cfg.latent_lanes, cfg.rope_dim) == (576, 640, 64)
    assert (cfg.num_experts, cfg.held, cfg.expert_first) == (160, 40, 0)
    assert (cfg.n_group, cfg.topk_group, cfg.experts_per_token) == (8, 3, 6)
    assert cfg.route_scale == 16 and not cfg.norm_topk_prob and not cfg.add_bos
    assert cfg.num_shared_experts == 2 and cfg.router_score == "softmax"
    assert cfg.attn_scale == pytest.approx(192 ** -0.5 * 1.2608 ** 2, rel=1e-4)
    for field, want in config["expect_cfg"].items():
        if field != "pallas_decode":
            assert getattr(cfg, field) == spec.subst(want, config), field
    # 32 streams x (6016 + 256) tokens of 640 lanes fit the pool, and little more
    block = 16 * 640 * 2 * 5
    blocks = int(env["KV_BUDGET_MB"]) * 1_000_000 // block
    assert 32 * 392 <= blocks < 32 * 392 + 64
    assert int(env["PREFILL_MAX_PROMPT"]) + int(env["MAX_DECODE_LEN"]) == 392 * 16
    lo, hi = config["check_prompt_tokens"]
    assert lo > 2048 and hi - 1 + 16 <= int(env["PREFILL_MAX_PROMPT"])


def test_costs_against_the_issues_arithmetic(config):
    a = costs_mla.attention_params(config)
    # 7.86 + 37.75 + 2.95 + 16.78 + 83.89 M
    assert a["projections"] == (5120 * 1536 + 1536 * 128 * 192 + 5120 * 576
                                + 512 * 128 * 256 + 128 * 128 * 5120) == 149_225_472
    lp = costs_mla.layer_params(config)
    assert lp["one_expert"] == 3 * 5120 * 1536 == 23_592_960
    assert lp["shared"] == 2 * 23_592_960 and lp["router"] == 5120 * 160
    assert lp["experts"] == 40 * 23_592_960
    assert round(lp["expert_layer"] / 1e6, 1) == 1141.0
    assert round(lp["dense_layer"] / 1e6, 1) == 338.0
    p = costs_mla.decoder_params(config)
    assert p["embedding"] == p["head"] == 25600 * 5120
    assert int(p["total"] / 1e6) == 5163 and round(p["total"] * 2 / 1e9, 2) == 10.33
    # the cache: 576 values a token a layer, read once a key
    assert costs_mla.latent_bytes_per_token_layer(config) == 1152
    assert costs_mla.latent_flops_per_key_layer(config) == 128 * (576 + 512) * 2 == 278_528
    assert 278_528 / 1152 == pytest.approx(241.8, abs=0.1)  # against the ridge's 240
    # experts: 25 % of a token's assignments land here if even; uniform
    # routing streams 28.2 of the 40 at 32 rows, the file's reading stands in
    assert costs_mla.held_share(config) == 0.25
    assert costs_mla.experts_streamed(
        {**config, "routing_held_experts_hit": None}, 32) == pytest.approx(28.2, abs=0.05)
    hit = config["routing_held_experts_hit"]
    assert costs_mla.experts_streamed(config, 32) == pytest.approx(hit, abs=0.01)
    assert costs_mla.experts_streamed(config, 24) < hit
    live = 24 * 4300
    k = costs_mla.latent_kernel(config, 24, live)
    assert k["flops"] == 5 * 278_528 * live
    assert k["bytes"] == 5 * (1152 * live + 24 * 128 * (576 + 512) * 2)
    least, bound = costs.roofline_seconds(k, PEAKS)
    assert bound == "hbm" and k["flops"] / 197e12 == pytest.approx(least, rel=0.06)
    step = costs_mla.decode_step(config, 24, live)
    assert step["expert_bytes"] == pytest.approx(
        4 * costs_mla.experts_streamed(config, 24) * 23_592_960 * 2)
    assert step["kv_bytes"] == k["bytes"] + 1152 * 5 * 24
    # ISSUE 33's ~7.0 GB of weights a step at 24 streams (its ~24 experts hit;
    # ~21.5 by the reference's reading of the seeded router: 6.5 GB)
    assert 6.3e9 < step["weight_bytes"] < 7.4e9
    least, bound = costs.roofline_seconds(step, PEAKS)
    assert bound == "hbm" and 8.5 < least * 1e3 < 10.0
    mm = costs_mla.expert_matmuls(config, 24)
    assert mm["flops"] == step["expert_flops"] == 2.0 * 4 * 6 * 0.25 * 23_592_960 * 24
    assert mm["bytes"] == pytest.approx(
        step["expert_bytes"] + 4 * 24 * 6 * (2 * 5120 + 3 * 1536) * 2)


def test_entries_resolve_by_name(config):
    """One configuration, one cell, sixteen per-layer entries, each
    resolving to its files; the cell is on ``tbt_p99_ms``'s list alone
    beside ``setup_s``, so every per-layer entry moves that one."""
    bench = spec.load_benchmark()  # by NAME: later PRs append after these
    assert "deepseek-v2-ep4-d5" in [c["name"] for c in bench["configs"]]
    entry = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert entry["chips"] == 1 and entry["traffic"] == "longdoc-closed"
    assert len(entry["why"]) <= 200
    mine = [m for m in bench["per_layer"] if m["name"] in NEW_PER_LAYER]
    assert [m["name"] for m in mine] == NEW_PER_LAYER  # PR 33's sixteen, by NAME
    for m in mine:  # later cells append themselves to an entry's list
        assert CELL in m["workloads"] and m["moves"] == "tbt_p99_ms"
    cell = spec.resolve(CELL)
    assert set(NEW_PER_LAYER) <= {m.name for m in cell.per_layer}
    assert [m.name for m in cell.end_to_end] == ["tbt_p99_ms", "setup_s"]
    mix = cell.traffic
    assert mix["loop"] == "closed" and mix["clients"] == 32 and not mix["barrier"]
    assert mix["clients"] == int(cell.config["env"]["MAX_STREAMS"])
    # the cells that were there keep their metrics
    for old in ("mistral-7b-d8.decode-closed", "mistral-7b-d8.chat-open",
                "olmoe-1b-7b-d8.decode-closed", "trinity-mini-d5.longdoc-closed"):
        assert not {m.name for m in spec.resolve(old).per_layer} & set(NEW_PER_LAYER)


class _Ctx:
    def __init__(self, **kw):
        self.notes = {}
        self.__dict__.update(kw)


def test_roofline_reader_counts_each_latent_row_once(config):
    reader = spec.load_module(
        os.path.join(spec.HERE, "readers", "mla_roofline.py"), "r_mla")

    class Trace:
        ops = {"latent_decode_attention": 0.008}

        def module_time(self, module):
            return (0.064, 1) if module == "jit_paged_chunk_fn" else (0.0, 0)

    recs = [{"first": 0.0, "done": 9.0, "prompt_tokens": n, "events": []}
            for n in (1000, 3000, 5000)]
    ctx = _Ctx(trace=Trace(), trace_span=[1.0, 4.0], all_records=recs,
               config=config, peaks=PEAKS, engine={"chunk_tokens": 4})
    assert reader.live_contexts(ctx) == (3.0, 9000.0)
    got = reader.read(ctx, "attention", "jit_paged_chunk_fn")
    cost = costs_mla.latent_kernel(config, 3.0, 9000.0)
    least = max(cost["bytes"] / 819e9, cost["flops"] / 197e12)
    assert got == pytest.approx(least / (0.008 / 4) * 100.0)
    assert ctx.notes["mla_roofline:attention"]["bound"] in ("hbm", "flops")
    step = reader.read(ctx, "step", "jit_paged_chunk_fn")
    assert step == pytest.approx(
        costs_mla.decode_step(config, 3.0, 9000.0)["bytes"] / 819e9 / 0.016 * 100.0)
    # a program without the kernel, or a run that was not traced: no value
    Trace.ops = {}
    assert reader.read(ctx, "attention", "jit_paged_chunk_fn") is None
    assert reader.read(ctx, "step", "jit_other") is None
    ctx.trace = None
    assert reader.read(ctx, "step", "jit_paged_chunk_fn") is None


def test_rehearsal_end_to_end():
    """The new cell's whole command on the CPU at a tiny size (chunked
    paged prefill over the latent pool, the absorbed step through the
    latent kernel in interpret mode, the check); the traced run reports
    the counters' metrics and nothing under a device name."""
    from test_rehearsal import run

    r = run("--workload", CELL, "--seed", str(2**31 + 7), "--seconds", "2",
            "--trace", "1", "--rehearse",
            os.path.join(HERE, "rehearse_deepseek_v2.json"))
    assert r.returncode == 0, r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["metrics"] == {}
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert {"moe_held_share_pct.dsv2", "moe_imbalance.dsv2",
            "table_blocks_dead_pct.dsv2", "streams_per_chunk.dsv2",
            "prefill_stall_ms.dsv2"} <= set(last["rehearsal_values"])
    assert 0 < last["rehearsal_values"]["moe_held_share_pct.dsv2"]["value"] < 100
