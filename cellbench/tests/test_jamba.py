"""``cellbench/costs_jamba.py`` against a hand count at the published sizes
of jamba2-3b-d28, the configuration file's promises, and the cell."""

import json

import pytest

from cellbench import costs_jamba as cj
from cellbench import spec

CELL = "jamba2-3b-d28.longdoc-closed"


@pytest.fixture(scope="module")
def c():
    return spec.load_json(spec.HERE + "/configs/jamba2-3b-d28.json")


def test_parameters_by_layer_kind(c):
    lp = cj.layer_params(c)
    # in 2560 x 10240, x 5120 x 192, dt 160 x 5120, out 5120 x 2560
    assert lp["mamba_proj"] == 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    # + taps 4 x 5120 and bias, dt bias, A_log 16 x 5120, D, inner norms 192, pre-norm
    assert lp["mamba_mixer"] == lp["mamba_proj"] + 5 * 5120 + 5120 + 17 * 5120 + 192 + 2560
    assert lp["mamba_mixer"] == 41_244_352
    assert lp["attention_mixer"] == 2 * 2560 * 2560 + 2 * 2560 * 128 + 2560 == 13_765_120
    assert lp["mlp"] == 3 * 2560 * 8192 + 2560 == 62_917_120
    p = cj.decoder_params(c)
    assert p["head"] == 0  # tied: ONE 65536 x 2560 table
    assert p["total"] == 26 * 104_161_472 + 2 * 76_682_240 + 65536 * 2560 + 2560
    assert p["total"] == 3_029_337_472 and round(p["total"] * 2 / 1e9, 2) == 6.06


def test_a_streams_state(c):
    assert cj.kv_bytes_per_token(c) == 1024  # 2 layers x (K + V) x ONE head of 128 x 2 B
    assert cj.state_bytes_per_stream(c) == 26 * (16 * 5120 * 4 + 3 * 5120 * 2)
    assert cj.state_bytes_per_stream(c) == 26 * 358_400 == 9_318_400
    assert 32 * 6272 * 1024 <= int(c["env"]["KV_BUDGET_MB"]) * 2**20


def test_a_decode_steps_bytes(c):
    step = cj.decode_step(c, 24.0, 24 * 4000.0)
    assert step["state_bytes"] == 2 * 24 * 9_318_400  # the live rows', in and out
    assert step["kv_bytes"] == 1024 * 24 * 4000 + 2 * 2 * 24 * 20 * 128 * 2 + 1024 * 24
    # every layer once, the final norm, and the table ONCE MORE as the head
    layers = 26 * 104_161_472 + 2 * 76_682_240
    assert step["weight_bytes"] == (layers + 65536 * 2560 + 2560) * 2 + 24 * 2560 * 2
    assert 6.5e9 < step["bytes"] < 6.7e9  # 8.1 ms at 819 GB/s
    assert step["flops"] == 2 * (layers + 65536 * 2560) * 24 + 4 * 2 * 20 * 128 * 24 * 4000
    # ALL 32 rows move under the mask; the live rows alone are the ceiling
    moved = cj.ssm_step(c, 24.0)
    assert moved["bytes"] == 2 * 32 * 26 * 16 * 5120 * 4
    assert moved["live_bytes"] == 2 * 24 * 26 * 16 * 5120 * 4
    assert moved["flops"] == 0.0 and moved["exponentials"] == 24 * 26 * 81_920
    assert moved["vector_ops"] == 7 * moved["exponentials"]


def test_a_window_dispatchs_scan_and_matmuls(c):
    scan = cj.ssm_scan(c, 3.0, 3072.0)
    # x bf16, Delta f32, B and C f32 in, y f32 out a position; a row's state in and out
    assert scan["bytes"] == 26 * (3072 * (5120 * 2 + 5120 * 4 + 2 * 16 * 4 + 5120 * 4)
                                  + 3 * 2 * 16 * 5120 * 4)
    assert scan["exponentials"] == 26 * 3072 * 81_920 == 6_543_114_240
    assert scan["vector_ops"] == 7 * scan["exponentials"] and scan["flops"] == 0.0
    # 0.39 TFLOP an MLP layer, 0.25 a Mamba layer's projections (ISSUE 51)
    assert cj.mlp(c, 3072.0)["flops"] / 28 == pytest.approx(0.3865e12, rel=1e-3)
    assert cj.mamba_projections(c, 3072.0)["flops"] / 26 == pytest.approx(0.2527e12, rel=1e-3)
    assert cj.attention_kernel(c, 24.0, 96000.0)["bytes"] == (
        1024 * 96000 + 2 * 2 * 24 * 20 * 128 * 2)


def test_the_file_states_no_cut_and_its_assumptions(c):
    assert c["reduced"] == {}
    for key in ("deployment", "block", "layer_order", "mamba_inner_norms",
                "mamba_gate", "mamba_init", "mamba_state_dtype", "attention_rope",
                "unused_keys", "tokenizer", "weights"):
        assert key in c["assumed"], key
    # every published number of the catalog's entry, under its own key
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        cat = next(d for d in map(json.loads, f) if d["name"] == "AI21-Jamba2-3B")
    for key, want in cat["config"].items():
        assert c[key] == want, key
    assert c["layers_block_type"] == [
        "attention" if li % 14 == 7 else "mamba" for li in range(28)]
    assert c["mamba_d_inner"] == c["mamba_expand"] * c["hidden_size"] == 5120


def test_the_cell_resolves_with_its_entries():
    cell = spec.resolve(CELL)
    assert cell.chips == 1 and cell.traffic["endpoint"] == "stream"
    assert [m.name for m in cell.end_to_end] == ["tbt_p99_ms", "setup_s"]
    names = [m.name for m in cell.per_layer]
    # PR 51's 21 and PR 55's three: the step's and the attention kernel's shares
    # the reader already computed, and the loop's unnamed share
    assert {"ssm_scan_roofline.jamba2", "decode_step_roofline.jamba2",
            "paged_decode_attention_roofline.jamba2"} <= set(names) and len(names) >= 24
    assert sum(n.startswith("boot_") for n in names) == 7
    # the sibling entries whose readers read this cell's scopes and counters
    assert {"decode_ssm_ms.nemotron", "prefill_ssm_scan_ms.nemotron",
            "ssm_scan_masked_pct.nemotron", "prefill_mlp_ms.gigachat"} <= set(names)
    # a traced run of this cell stops its profiler long after the window's
    # end (28 layers of scan trips), when no stream holds a row: the gauge's
    # share has nothing to read here and the cell is not listed for it
    assert "ssm_state_share_pct.nemotron" not in names
    bench = spec.load_benchmark()
    assert CELL in [w["name"] for w in bench["workloads"]]  # by NAME: later PRs append
    (entry,) = [c for c in bench["configs"] if c["name"] == "jamba2-3b-d28"]
    assert entry["reduced"] == [] and entry["source"] == (
        "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json")
    assert len(bench["per_layer"]) <= 128


def test_readers_return_nothing_where_there_is_nothing_to_read():
    """On a program without the scopes or the families (the parent), and
    untraced, the new entry's reader returns None and raises nothing."""
    import types

    cell = spec.resolve(CELL)
    ctx = types.SimpleNamespace(
        trace=None, peaks=None, prom_after={}, prom_before={}, notes={},
        config=cell.config, engine={"chunk_tokens": 4},
        prom_delta=lambda family: None)
    own = [m for m in cell.per_layer if m.name.endswith(".jamba2")]
    assert {m.args["what"] for m in own} == {"ssm_scan", "step", "attention"}
    for mine in own:
        assert mine.reader == "jamba_roofline" and mine.read(ctx, **mine.args) is None
    for what in ("step", "ssm_step", "attention", "proj_ms", "window_proj_ms"):
        assert own[0].read(ctx, what=what) is None


def test_rehearsal_end_to_end():
    """The whole command on the CPU at a toy size, traced: boot, the check
    against the reference (tokens, logits, the loop's state rows), load, the
    readers over a CPU trace.  A rehearsal proves the path and never a
    number."""
    import os
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    r = subprocess.run(
        [sys.executable, "-m", "cellbench.run", "--workload", CELL, "--seed",
         str(2**31 + 5), "--seconds", "2", "--trace", "1", "--rehearse",
         os.path.join(here, "rehearse_jamba.json")],
        cwd=spec.REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["device"]["platform"] == "cpu" and last["rehearsal"] is True
    assert last["metrics"] == {} and last["correct"] is True and last["failed"] == 0
    check = next(json.loads(ln.split(" ", 2)[2]) for ln in r.stdout.splitlines()
                 if ln.startswith("cellbench correct"))
    assert len(check["state_slow_rel_err"]) == 13 and len(set(check["state_row"])) == 1
    got = set(last["rehearsal_values"])
    # (the state's share needs the admission ledger: KV_BUDGET_MB is 0 here)
    assert {"ssm_scan_masked_pct.nemotron",
            "prefill_windows_batched_pct.nemotron"} <= got
