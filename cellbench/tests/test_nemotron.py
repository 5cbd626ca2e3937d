"""``cellbench/costs_nemotron.py`` against a hand count at the published
sizes of nemotron3-super-ep4-d11, and the configuration file's promises."""

import pytest

from cellbench import costs_nemotron as cn
from cellbench import spec


@pytest.fixture(scope="module")
def c():
    return spec.load_json(spec.HERE + "/configs/nemotron3-super-ep4-d11.json")


def test_parameters_by_layer_kind(c):
    lp = cn.layer_params(c)
    # in 4096 x (8192 + 10240 + 128), conv 4 x 10240 + 10240, 3 x 128, norm 8192,
    # out 8192 x 4096, pre-norm 4096
    assert lp["mamba_layer"] == 4096 * 18560 + 51200 + 384 + 8192 + 8192 * 4096 + 4096
    assert lp["mamba_layer"] == 109_640_064
    assert lp["attention_layer"] == 2 * 4096 * 4096 + 2 * 4096 * 256 + 4096 == 35_655_680
    assert lp["one_expert"] == 2 * 1024 * 2688 == 5_505_024
    assert lp["expert_dense"] == (4096 * 512 + 512 + 2 * 4096 * 1024
                                  + 2 * 4096 * 5376 + 4096) == 54_530_560
    p = cn.decoder_params(c)
    assert p["total"] == (5 * 109_640_064 + 35_655_680
                          + 5 * (54_530_560 + 128 * 5_505_024)
                          + 2 * 32768 * 4096 + 4096)
    assert round(p["total"] * 2 / 1e9, 2) == 9.30  # GB in bf16


def test_a_streams_state(c):
    assert cn.kv_bytes_per_token(c) == 1024  # ONE attention layer, 2 KV heads of 128
    assert cn.state_bytes_per_stream(c) == 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    assert cn.state_bytes_per_stream(c) == 21_278_720
    assert cn.held_share(c) == 0.25


def test_experts_streamed_follows_the_files_reading(c):
    hit = float(c["routing_held_experts_hit"])
    assert cn.experts_streamed(c, 32.0) == pytest.approx(hit)
    uniform = cn.experts_streamed({**c, "routing_held_experts_hit": None}, 32.0)
    assert uniform == pytest.approx(128 * (1 - (1 - 22 / 512) ** 32))
    assert uniform == pytest.approx(96.6, abs=0.05)
    assert cn.experts_streamed(c, 1.0) < cn.experts_streamed(c, 32.0) < 128


def test_a_decode_steps_bytes(c):
    step = cn.decode_step(c, 24.0, 24 * 4000.0)
    # each live stream's state once in, once out
    assert step["state_bytes"] == 2 * 24 * 21_278_720
    assert step["kv_bytes"] == 1024 * 24 * 4000 + 2 * 24 * 32 * 128 * 2 + 1024 * 24
    dense = 5 * 109_640_064 + 35_655_680 + 5 * 54_530_560 + 32768 * 4096 + 4096
    hit = 5 * cn.experts_streamed(c, 24.0) * 5_505_024
    assert step["weight_bytes"] == pytest.approx((dense + hit) * 2 + 24 * 4096 * 2)
    # 5.9 GB at 24 rows: 2.0 GB dense weights + 2.8 GB of hit experts (51 a
    # layer by the file's reading of 63.2 at 32 rows: the seeded router is
    # skewed) + 1.0 GB of state; a uniform router reads 7.7 GB (ISSUE 40
    # estimated ~8-9 GB)
    assert 5.8e9 < step["bytes"] < 6.2e9
    uniform = cn.decode_step({**c, "routing_held_experts_hit": None}, 24.0, 24 * 4000.0)
    assert 7.5e9 < uniform["bytes"] < 7.9e9
    assert cn.ssm_step(c, 24.0)["bytes"] == 2 * 5 * 24 * 128 * 64 * 128 * 4


def test_a_window_dispatchs_scan(c):
    scan = cn.ssm_scan(c, 3.0, 3072.0)
    per_chunk = 2 * 128 * 128 * (128 * 8 + 64 * 128) + 4 * 128 * 64 * 128 * 128
    assert scan["flops"] == 5 * 24 * per_chunk  # 24 chunks of 128 a layer
    assert scan["bytes"] == 5 * (3072 * (10240 * 2 + 128 * 4 + 8192 * 4)
                                 + 3 * 2 * 128 * 64 * 128 * 4)


def test_the_file_states_its_cut_and_its_assumptions(c):
    assert set(c["reduced"]) == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    for key, (source, here) in {"num_hidden_layers": (88, 11),
                                "n_routed_experts": (512, 128),
                                "vocab_size": (131072, 32768)}.items():
        assert (c["reduced"][key]["source"], c["reduced"][key]["here"]) == (source, here)
        assert c[key] == here
    for key in ("deployment", "mamba_time_step", "mamba_gate_norm", "mamba_A",
                "mamba_state_dtype", "attention_rope", "router", "experts", "mtp",
                "tokenizer", "weights"):
        assert key in c["assumed"], key
    # no toy width
    assert (c["hidden_size"], c["mamba_num_heads"], c["mamba_head_dim"],
            c["ssm_state_size"], c["moe_latent_size"], c["moe_intermediate_size"],
            c["moe_shared_expert_intermediate_size"], c["num_experts_per_tok"]) == (
        4096, 128, 64, 128, 1024, 2688, 5376, 22)
