"""``cellbench/costs_phi4flash.py`` against a hand count at the published
sizes of phi4-mini-flash-d32, the configuration file's promises, the reader
that waits for its entries, and the cell."""

import json
import types

import pytest

from cellbench import costs_phi4flash as cp
from cellbench import spec

CELL = "phi4-mini-flash-d32.longdoc-closed"
WHATS = ("step", "attention_full", "attention_window", "ssm_scan", "gmu",
         "prefill_self", "prefill_cross")


@pytest.fixture(scope="module")
def c():
    return spec.load_json(spec.HERE + "/configs/phi4-mini-flash-d32.json")


def test_parameters_by_layer_kind(c):
    z, lp = cp.sizes(c), cp.layer_params(c)
    assert (z["mamba"], z["window"], z["full"], z["gmu"], z["cross"]) == (9, 8, 1, 7, 7)
    assert z["self_layers"] == 18 and z["hd"] == 64
    # in 2560 x 10240, x 5120 x 192, dt 160 x 5120, out 5120 x 2560
    assert lp["mamba_proj"] == 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    # + taps 4 x 5120, conv bias, dt bias, A_log 16 x 5120, D, LayerNorm 2 x 2560: NO inner norm
    assert lp["mamba_mixer"] == lp["mamba_proj"] + 4 * 5120 + 2 * 5120 + 17 * 5120 + 2 * 2560
    # q 2560^2 + b, k and v 2560 x 1280 + b each, o 2560^2 + b, lambda 4 x 64, sub-norm 128, LN
    assert lp["attention_mixer"] == (2 * (2560 * 2560 + 2560) + 2 * (2560 * 1280 + 1280)
                                     + 256 + 128 + 5120) == 19_673_984
    assert lp["cross_mixer"] == 2 * (2560 * 2560 + 2560) + 256 + 128 + 5120  # q and o alone
    assert lp["gmu_mixer"] == 2 * 2560 * 5120 + 5120 and lp["mlp"] == 3 * 2560 * 10240 + 5120
    p = cp.decoder_params(c)
    assert p["head"] == 0  # tied: ONE 200064 x 2560 table
    # 9 x 119.9 M, 9 x 98.3 M, 7 x 104.9 M, 7 x 91.8 M, 512.2 M (ISSUE 63)
    per = [lp[k] + lp["mlp"] for k in ("mamba_mixer", "attention_mixer", "gmu_mixer", "cross_mixer")]
    assert [round(x / 1e6, 1) for x in per] == [119.9, 98.3, 104.9, 91.8]
    assert p["total"] == 9 * per[0] + 9 * per[1] + 7 * per[2] + 7 * per[3] + 200064 * 2560 + 5120
    assert round(p["total"] / 1e6, 1) == 3852.6 and round(p["total"] * 2 / 1e9, 2) == 7.71


def test_the_three_stores(c):
    assert cp.full_bytes_per_token(c) == 5120  # ONE layer x (K + V) x 20 x 64 x 2 B
    assert cp.window_bytes_per_token(c) == 8 * 5120
    assert cp.state_bytes_per_stream(c) == 9 * (16 * 5120 * 4 + 3 * 5120 * 2) == 3_225_600
    s = cp.stores(c, 6272)
    assert s == {"pool": 32 * 6272 * 5120, "window_store": 32 * 1552 * 8 * 5120,
                 "state": 32 * 3_225_600}
    assert [round(v / 1e9, 2) for v in s.values()] == [1.03, 2.03, 0.10]
    assert round((sum(s.values()) + 2 * cp.decoder_params(c)["total"]) / 1e9, 2) == 10.87
    assert s["pool"] <= int(c["env"]["KV_BUDGET_MB"]) * 10**6
    # a table that kept every block of every window layer: what the ring is not
    assert round(9 * s["pool"] / 1e9, 2) == 9.25


def test_a_decode_steps_bytes(c):
    step = cp.decode_step(c, 32.0, 32 * 4250.0)
    # EIGHT layers read the one pool's live keys: each key and value once a layer
    assert step["full_pool_read_bytes"] == 8 * (5120 * 32 * 4250 + 32 * 40 * 192 * 2)
    assert round(step["full_pool_read_bytes"] / 1e9, 2) == 5.57
    # a window layer reads at most 512 keys a stream
    assert step["window_read_bytes"] == 8 * (5120 * 32 * 512 + 32 * 40 * 192 * 2)
    assert step["state_bytes"] == 2 * 32 * 3_225_600
    layers = cp.decoder_params(c)["layers"]
    assert step["weight_bytes"] == (layers + 200064 * 2560 + 5120) * 2 + 32 * 2560 * 2
    assert round(step["bytes"] / 819e9 * 1e3, 1) == 17.3  # ms at the HBM's peak (ISSUE 63)
    # a head scores 64 dims a key and weighs 128 a value
    assert cp.attention_full(c, 32.0, 1000.0)["flops"] == 8 * 2 * 40 * 192 * 1000
    short = cp.attention_window(c, 4.0, 4 * 100.0)  # under a window: every key
    assert short["bytes"] == 8 * (5120 * 400 + 4 * 40 * 192 * 2)
    moved = cp.ssm_step(c, 24.0)
    assert moved["bytes"] == 2 * 32 * 9 * 16 * 5120 * 4 and moved["flops"] == 0.0
    assert moved["live_bytes"] == 2 * 24 * 9 * 16 * 5120 * 4
    unit = cp.gmu(c, 32.0)
    assert unit["flops"] == 2 * 7 * 2 * 2560 * 5120 * 32


def test_a_prompt_dispatch_split_self_and_cross(c):
    d = cp.prefill_dispatch(c, 3.0, 3072.0)
    # 20.5 TFLOP through all 32 layers, 12.1 through layers 0 - 17 (ISSUE 63)
    assert round(d["self"]["flops"] / 1e12, 1) == 12.1
    assert round((d["self"]["flops"] + d["cross_all"]["flops"]) / 1e12, 1) == 20.5
    assert d["cross"] == {"bytes": 0.0, "flops": 0.0}  # a window reads no logit
    one = cp.prefill_dispatch(c, 3.0, 3072.0, cross_tokens=1.0)["cross"]
    assert one["flops"] == 2 * cp.decoder_params(c)["cross_layers"]
    scan = cp.ssm_scan(c, 3.0, 3072.0)
    assert scan["exponentials"] == 9 * 3072 * 81_920 and scan["flops"] == 0.0
    assert scan["bytes"] == 9 * (3072 * (5120 * 2 + 5120 * 4 + 2 * 16 * 4 + 5120 * 4)
                                 + 3 * 2 * 16 * 5120 * 4)


def test_the_file_states_no_cut_and_its_assumptions(c):
    assert c["reduced"] == {}
    for key in ("deployment", "block", "layer_order", "mamba_sizes", "mamba_memory",
                "differential_pairing", "lambda_init", "window_edge", "head_dim",
                "attention_rope", "attention_bias", "window_store", "shared_pool",
                "prompt_path", "unused_keys", "tokenizer", "dtype", "weights"):
        assert key in c["assumed"], key
    # every published number of the catalog's entry, under its own key
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        cat = next(d for d in map(json.loads, f)
                   if d["name"] == "Phi-4-mini-flash-reasoning")
    for key, want in cat["config"].items():
        assert c[key] == want, key
    kinds = c["layers_block_type"]
    assert kinds == [("mamba" if li <= 16 else "gmu") if li % 2 == 0 else
                     "window" if li <= 15 else "full" if li == 17 else "cross"
                     for li in range(32)]
    ref = spec.load_module(spec.HERE + "/references/phi4flash.py", "ref_phi4flash")
    assert ref.layer_kinds(c) == kinds  # the family's rule, from the published keys
    assert c["mamba_d_inner"] == c["mamba_expand"] * c["hidden_size"] == 5120
    assert c["mamba_dt_rank"] == -(-c["hidden_size"] // 16) == 160
    assert c["window_ring"] == 512 - 1 + 1024 + 16 + 1 == 1552 and 1552 % 16 == 0


def test_the_reference_imports_nothing_of_the_package():
    src = open(spec.HERE + "/references/phi4flash.py", encoding="utf-8").read()
    assert "mlmicroservicetemplate_tpu" not in src.split('"""', 2)[2]
    assert "from cellbench" not in src and "import cellbench" not in src


def test_the_cell_resolves_with_its_entries():
    cell = spec.resolve(CELL)
    assert cell.chips == 1 and cell.traffic["endpoint"] == "stream"
    assert [m.name for m in cell.end_to_end] == ["tbt_p99_ms", "setup_s"]
    names = {m.name for m in cell.per_layer}
    assert sum(n.startswith("boot_") for n in names) == 7 and len(names) == 24
    # the sibling entries whose readers read this cell's scopes and counters
    assert {"decode_ssm_ms.nemotron", "prefill_ssm_scan_ms.nemotron",
            "ssm_scan_masked_pct.nemotron", "prefill_mlp_ms.gigachat",
            "decode_attn_window_ms.trinity", "decode_attn_full_ms.trinity",
            "loop_unnamed_pct.serve", "event_loop_lag_p99_ms"} <= names
    # not Jamba's shares (its costs count Mamba layers by a period and an
    # offset), nor the share of keys a TABLE keeps behind a window: a ring keeps none
    assert not {n for n in names if n.endswith(".jamba2")}
    assert "window_keys_behind_pct.trinity" not in names
    bench = spec.load_benchmark()
    assert CELL in [w["name"] for w in bench["workloads"]]
    (entry,) = [e for e in bench["configs"] if e["name"] == "phi4-mini-flash-d32"]
    assert entry["reduced"] == [] and entry["source"] == (
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json")
    assert len(bench["per_layer"]) == 128  # full: this cell's own entries are held


def _ctx(cell, **over):
    base = dict(trace=None, peaks=None, prom_after={}, prom_before={}, notes={},
                config=cell.config, engine={"chunk_tokens": 8},
                prom_delta=lambda family: None)
    return types.SimpleNamespace(**{**base, **over})


@pytest.mark.parametrize("what", WHATS)
def test_the_held_reader_returns_nothing_where_there_is_nothing_to_read(what):
    """Untraced, or on a program without the scopes and families (the
    parent), the reader returns None and raises nothing."""
    reader = spec.load_module(spec.HERE + "/readers/phi4flash_roofline.py", "r_phi")
    cell = spec.resolve(CELL)
    assert reader.read(_ctx(cell), what=what) is None
    empty = types.SimpleNamespace(module_time=lambda m: (0.0, 0), ops={})
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    ctx = _ctx(cell, trace=empty, peaks=peaks, trace_span=(1.0, 2.0), all_records=[])
    assert reader.read(ctx, what=what) is None


def test_the_held_reader_reads_a_step_share(monkeypatch):
    """A step of 20 ms at 32 streams of 4250 tokens reads 17.29 / 20 = 86 %;
    the eight reads of the pool against 8 ms under their scopes 85 %."""
    reader = spec.load_module(spec.HERE + "/readers/phi4flash_roofline.py", "r_phi2")
    cell = spec.resolve(CELL)
    trace = types.SimpleNamespace(module_time=lambda m: (0.020 * 8 * 5, 5), ops={})
    monkeypatch.setattr(reader, "live_contexts", lambda ctx: (32.0, 32 * 4250.0))
    monkeypatch.setattr(reader.trace_subscope_ms, "table", lambda module, scopes: {
        "runs": 5, "seconds": {"attn_full": 0.001 * 40, "attn_cross": 0.007 * 40}})
    ctx = _ctx(cell, trace=trace, peaks={"hbm_bytes_per_s": 819e9,
                                         "bf16_flops_per_s": 197e12})
    assert reader.read(ctx, what="step") == pytest.approx(86.46, abs=0.01)
    assert reader.read(ctx, what="attention_full") == pytest.approx(85.08, abs=0.01)
    note = ctx.notes["phi4flash_roofline:attention_full"]
    assert note["bound"] == "hbm" and note["live_streams"] == 32.0
    with pytest.raises(ValueError, match="unknown what"):
        reader.read(ctx, what="nope")


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
         os.path.join(here, "rehearse_phi4flash.json")],
        cwd=spec.REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["device"]["platform"] == "cpu" and last["rehearsal"] is True
    assert last["metrics"] == {} and last["correct"] is True and last["failed"] == 0
    check = next(json.loads(ln.split(" ", 2)[2]) for ln in r.stdout.splitlines()
                 if ln.startswith("cellbench correct"))
    assert len(check["state_slow_rel_err"]) == 3 and len(set(check["state_row"])) == 1
    window = next(json.loads(ln.split(" ", 2)[2]) for ln in r.stdout.splitlines()
                  if ln.startswith("cellbench window"))
    stores = window["decode"]["stores"]
    assert (stores["pool_layers"], stores["window_store_layers"],
            stores["shared_pool_readers"]) == (1, 2, 2)
    got = set(last["rehearsal_values"])
    assert {"ssm_scan_masked_pct.nemotron",
            "prefill_windows_batched_pct.nemotron"} <= got
