"""``cellbench/costs_gigachat.py`` against a hand count at the published
sizes of gigachat35-ep16-d5, the configuration file's promises, and the
cell's entries."""

import json

import pytest

from cellbench import costs_gigachat as cg
from cellbench import spec

CELL = "gigachat35-ep16-d5.longdoc-closed"


@pytest.fixture(scope="module")
def c():
    return spec.load_json(spec.HERE + "/configs/gigachat35-ep16-d5.json")


def test_parameters_by_part(c):
    lp = cg.layer_params(c)
    # W_qkvz 7168 x (16384 + 8192), W_ba 7168 x 128, taps 4 x 16384, A_log +
    # dt_bias 2 x 64, the output norm 128, W_out 8192 x 7168
    assert lp["gdn"] == 7168 * 24576 + 7168 * 128 + 65536 + 128 + 128 + 8192 * 7168
    assert lp["gdn"] == 235_864_320
    # q 7168 x 1536 + 1536 x 64 x 192, kv 7168 x 576 + 512 x 64 x 256,
    # o 8192 x 7168, the gate 7168 x 8192, the inner norms 1536 + 512
    assert lp["latent"] == (7168 * 1536 + 1536 * 12288 + 7168 * 576 + 512 * 16384
                            + 2 * 8192 * 7168 + 2048) == 159_844_352
    assert lp["one_expert"] == 3 * 7168 * 2048 == 44_040_192
    assert lp["dense_ffn"] == 3 * 7168 * 18432 == 396_361_728
    p = cg.decoder_params(c)
    assert p["total"] == (4 * 235_864_320 + 159_844_352 + 5 * 4 * 7168 + 396_361_728
                          + 4 * (7168 * 256 + 256 + 44_040_192 + 16 * 44_040_192)
                          + 2 * 16032 * 7168 + 7168)
    assert round(p["total"] * 2 / 1e9, 2) == 9.46  # GB in bf16


def test_a_streams_state(c):
    assert cg.latent_bytes_per_token(c) == 1152  # ONE latent layer: 576 values
    assert cg.state_bytes_per_stream(c) == 4 * (64 * 128 * 128 * 4 + 3 * 16384 * 2)
    assert cg.state_bytes_per_stream(c) == 17_170_432 == c["expect_cfg"]["ssm_row_bytes"]


def test_experts_streamed_follows_the_files_reading(c):
    hit = float(c["routing_held_experts_hit"])
    assert cg.experts_streamed(c, 32.0) == pytest.approx(hit)
    uniform = cg.experts_streamed({**c, "routing_held_experts_hit": None}, 32.0)
    assert uniform == pytest.approx(16 * (1 - (1 - 8 / 256) ** 32))
    assert uniform == pytest.approx(10.2, abs=0.05)


def test_a_decode_steps_bytes(c):
    step = cg.decode_step(c, 24.0, 24 * 4000.0)
    # every state ROW once in, once out (all 32 under the mask), the live rows' taps
    assert step["state_bytes"] == (2 * 32 * 4 * 64 * 128 * 128 * 4
                                   + 2 * 24 * 4 * 3 * 16384 * 2)
    assert step["kv_bytes"] == 1152 * 24 * 4000 + 24 * 64 * (576 + 512) * 2 + 1152 * 24
    dense = cg.decoder_params(c)["dense"] + 16032 * 7168 + 7168
    hit = 4 * cg.experts_streamed(c, 24.0) * 44_040_192
    assert step["weight_bytes"] == pytest.approx((dense + hit) * 2 + 24 * 7168 * 2)
    assert cg.gdn_step(c, 24.0)["bytes"] == 2 * 4 * 32 * 64 * 128 * 128 * 4
    assert cg.gdn_step(c, 24.0)["live_bytes"] == 2 * 4 * 24 * 64 * 128 * 128 * 4


def test_a_window_dispatchs_scan(c):
    """What a fused kernel must move and do, by hand: [q | k | v] a KEY
    head in bf16, two float32 gates a value head, o in bf16; the inverse
    by substitution (16-row blocks) and two merge levels, not a Neumann
    product; the chunk and block sizes are the program's."""
    from mlmicroservicetemplate_tpu.ops import ssm

    assert (cg.SCAN_CHUNK, cg.INVERSE_BLOCK) == (ssm.GDN_CHUNK, ssm.INVERSE_BLOCK)
    scan = cg.gdn_scan(c, 3.0, 3072.0)
    inverse = 2 * 16 * 15 * 64 + 2 * 64 * 16 * 16 + 2 * 64 * 32 * 32
    per_chunk = 64 * (4 * 64 * 64 * 128 + inverse + 2 * 64 * 64 * 256
                      + 6 * 64 * 128 * 128 + 2 * 64 * 64 * 128)
    assert scan["flops"] == 4 * 48 * per_chunk  # 48 chunks of 64 a layer
    assert scan["bytes"] == 4 * (3072 * (16384 * 2 + 2 * 64 * 4 + 8192 * 2)
                                 + 3 * 2 * 64 * 128 * 128 * 4)
    # HBM binds: 0.87 ms of bytes against 0.73 ms of operations a dispatch
    assert scan["bytes"] / 819e9 > scan["flops"] / 197e12


def test_the_projections(c):
    one = 7168 * (24576 + 128) + 8192 * 7168  # W_qkvz, W_ba, W_out
    step = cg.gdn_projections(c, 24.0)
    assert step["flops"] == 2 * 4 * one * 24
    assert step["bytes"] == 4 * (one + 24 * (2 * 7168 + 16384 + 2 * 8192 + 128)) * 2
    # a three-window dispatch: 4 x 1.45 TFLOP (ISSUE 47's ~30 ms at the MXU peak)
    assert cg.gdn_projections(c, 3072.0)["flops"] == pytest.approx(4 * 1.449e12, rel=1e-3)


def test_the_file_states_its_cut_and_its_assumptions(c):
    assert set(c["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                 "first_k_dense_replace", "vocab_size"}
    for key, (source, here) in {"num_hidden_layers": (40, 5),
                                "n_routed_experts": (256, 16),
                                "first_k_dense_replace": (3, 1),
                                "vocab_size": (128256, 16032)}.items():
        assert (c["reduced"][key]["source"], c["reduced"][key]["here"]) == (source, here)
        assert c[key] == here
    for key in ("deployment", "norm", "deltanet", "rope_interleave",
                "gated_attention", "router", "swiglu_limit", "mtp", "tokenizer"):
        assert key in c["assumed"], key
    # every published number of the catalog's entry, under its own key
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        cat = next(d for d in map(json.loads, f)
                   if d["name"] == "GigaChat3.5-432B-A28B")["config"]
    for key, want in cat.items():
        assert c[key] == (c["reduced"][key]["here"] if key in c["reduced"] else want), key


def test_the_cell_resolves_with_its_entries():
    cell = spec.resolve(CELL)
    assert cell.chips == 1 and cell.traffic["endpoint"] == "stream"
    assert [m.name for m in cell.end_to_end] == ["tbt_p99_ms", "setup_s"]
    mine = [m for m in cell.per_layer if m.name.endswith(".gigachat")]
    assert len(mine) == 29
    assert {m.reader for m in mine} >= {"gigachat_roofline", "trace_subscope_ms"}
    bench = spec.load_benchmark()
    assert CELL in [w["name"] for w in bench["workloads"]]  # by NAME: later PRs append
    (entry,) = [c for c in bench["configs"] if c["name"] == "gigachat35-ep16-d5"]
    assert entry["reduced"] == list(
        spec.load_json(spec.HERE + "/configs/gigachat35-ep16-d5.json")["reduced"])


def test_readers_return_nothing_where_there_is_nothing_to_read():
    """On a program without the scopes or the families (the parent), and
    untraced, every new entry's reader returns None and raises nothing."""
    import types

    cell = spec.resolve(CELL)
    ctx = types.SimpleNamespace(
        trace=None, peaks=None, prom_after={}, prom_before={}, notes={},
        config=cell.config, engine={"chunk_tokens": 4},
        prom_delta=lambda family: None)
    for m in cell.per_layer:
        if m.name.endswith(".gigachat") and m.reader in (
                "gigachat_roofline", "trace_subscope_ms", "trace_scope_ms",
                "trace_module_ms", "trace_idle_pct"):
            assert m.read(ctx, **m.args) is None, m.name


def test_rehearsal_end_to_end(tmp_path):
    """The whole command on the CPU at a toy size, traced: boot, the check
    against the reference (tokens, logits, the loop's state rows), load,
    the new readers over a CPU trace.  A rehearsal proves the path and
    never a number."""
    import os
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    r = subprocess.run(
        [sys.executable, "-m", "cellbench.run", "--workload", CELL, "--seed",
         str(2**31 + 5), "--seconds", "2", "--trace", "1", "--rehearse",
         os.path.join(here, "rehearse_gigachat.json")],
        cwd=spec.REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["device"]["platform"] == "cpu" and last["rehearsal"] is True
    assert last["metrics"] == {} and last["correct"] is True and last["failed"] == 0
    check = next(json.loads(ln.split(" ", 2)[2]) for ln in r.stdout.splitlines()
                 if ln.startswith("cellbench correct"))
    assert len(check["state_slow_rel_err"]) == 4 and len(set(check["state_row"])) == 1
    got = set(last["rehearsal_values"])
    # (the state's share needs the admission ledger: KV_BUDGET_MB is 0 here)
    assert {"gdn_scan_masked_pct.gigachat", "moe_held_share_pct.gigachat",
            "prefill_windows_batched_pct.gigachat"} <= got
