"""``cellbench/costs_granite.py`` against a hand count at the published sizes
of granite-4.0-h-small-ep2-d10, the configuration file's promises, the scopes
the new composition runs under, and the cell."""

import json

import pytest

from cellbench import costs_granite as cg
from cellbench import spec

CELL = "granite-4.0-h-small-ep2-d10.longdoc-closed"
OWN = {"decode_step_roofline.granite": "step", "moe_experts_roofline.granite": "experts",
       "ssm_scan_roofline.granite": "ssm_scan", "ssm_step_roofline.granite": "ssm_step",
       "paged_decode_attention_roofline.granite": "attention"}


@pytest.fixture(scope="module")
def c():
    return spec.load_json(spec.HERE + "/configs/granite-4.0-h-small-ep2-d10.json")


def test_parameters_by_layer_kind(c):
    lp = cg.layer_params(c)
    # in 4096 x (8192 + 8448 + 128), conv 4 x 8448 + 8448, 3 x 128, norm 8192, out
    assert lp["mamba_mixer"] == 4096 * 16768 + 42240 + 384 + 8192 + 8192 * 4096
    assert lp["attention_mixer"] == 2 * 4096 * 4096 + 2 * 4096 * 1024 == 41_943_040
    # router 4096 x 72, the shared expert 3 x 4096 x 1536, two pre-norms
    assert lp["ffn_dense"] == 294_912 + 18_874_368 + 8192
    assert lp["mamba_dense"] == 121_464_448 and lp["attention_dense"] == 61_120_512
    assert lp["one_expert"] == 3 * 4096 * 768 == 9_437_184
    assert lp["mamba_layer"] == 461_203_072 and lp["attention_layer"] == 400_859_136
    p = cg.decoder_params(c)
    assert p["head"] == 0  # tied: ONE 50176 x 4096 table
    assert p["total"] == 9 * 461_203_072 + 400_859_136 + 50176 * 4096 + 4096
    assert p["total"] == 4_757_211_776 and round(p["total"] * 2 / 1e9, 2) == 9.51


def test_a_streams_state(c):
    assert cg.kv_bytes_per_token(c) == 4096  # ONE attention layer, 8 KV heads of 128
    assert cg.state_bytes_per_stream(c) == 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
    assert cg.state_bytes_per_stream(c) == 38_204_928 == c["expect_cfg"]["ssm_row_bytes"]
    assert cg.held_share(c) == 0.5
    assert 32 * 6272 * 4096 <= int(c["env"]["KV_BUDGET_MB"]) * 10**6 < 33 * 6272 * 4096


def test_experts_streamed_follows_the_files_reading(c):
    hit = float(c["routing_held_experts_hit"])
    assert cg.experts_streamed(c, 32.0) == pytest.approx(hit)
    uniform = cg.experts_streamed({**c, "routing_held_experts_hit": None}, 32.0)
    assert uniform == pytest.approx(36 * (1 - (1 - 10 / 72) ** 32))
    assert uniform == pytest.approx(35.7, abs=0.05)  # the densest routing in the benchmark
    assert cg.experts_streamed(c, 1.0) < cg.experts_streamed(c, 32.0) <= 36


def test_a_decode_steps_bytes(c):
    step = cg.decode_step(c, 24.0, 24 * 4000.0)
    # each live stream's state once in, once out
    assert step["state_bytes"] == 2 * 24 * 38_204_928
    assert step["kv_bytes"] == 4096 * 24 * 4000 + 2 * 24 * 32 * 128 * 2 + 4096 * 24
    dense = 9 * 121_464_448 + 61_120_512 + 50176 * 4096 + 4096
    hit = 10 * cg.experts_streamed(c, 24.0) * 9_437_184
    assert step["weight_bytes"] == pytest.approx((dense + hit) * 2 + 24 * 4096 * 2)
    # ~11 GB at 24 rows: 2.7 GB of dense weights and the table, ~6 GB of hit
    # experts (nearly all 36 a layer), 1.8 GB of state in and out, 0.4 GB of KV
    assert 10.0e9 < step["bytes"] < 12.0e9
    assert cg.ssm_step(c, 24.0)["bytes"] == 2 * 9 * 24 * 128 * 64 * 128 * 4
    em = cg.expert_matmuls(c, 24.0)
    assert em["bytes"] == pytest.approx(hit * 2 + 10 * 24 * 10 * (2 * 4096 + 3 * 768) * 2)
    assert em["flops"] == 2 * 10 * 10 * 0.5 * 9_437_184 * 24


def test_a_window_dispatchs_scan(c):
    scan = cg.ssm_scan(c, 3.0, 3072.0)
    # C B^T once a GROUP (one), the decay matrices and the state a head
    per_chunk = 2 * 128 * 128 * (128 * 1 + 64 * 128) + 4 * 128 * 64 * 128 * 128
    assert scan["flops"] == 9 * 24 * per_chunk  # 24 chunks of 128 a layer
    assert scan["bytes"] == 9 * (3072 * (8448 * 2 + 128 * 4 + 8192 * 4)
                                 + 3 * 2 * 128 * 64 * 128 * 4)


def test_the_file_states_its_cut_and_its_assumptions(c):
    assert set(c["reduced"]) == {"num_hidden_layers", "num_local_experts", "vocab_size"}
    for key, (source, here) in {"num_hidden_layers": (40, 10),
                                "num_local_experts": (72, 36),
                                "vocab_size": (100352, 50176)}.items():
        assert (c["reduced"][key]["source"], c["reduced"][key]["here"]) == (source, here)
        assert c[key] == here and c["reduced"][key]["why"]
    for key in ("deployment", "block", "llama_layer_types", "router", "experts",
                "mamba_chunk", "mamba_time_step", "mamba_gate_norm", "mamba_init",
                "mamba_state_dtype", "attention", "tokenizer", "weights"):
        assert key in c["assumed"], key
    # every published number of the catalog's entry under its own key, the
    # three of the cut aside
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        cat = next(d for d in map(json.loads, f) if d["name"] == "granite-4.0-h-small")
    for key, want in cat["config"].items():
        if key not in c["reduced"]:
            assert c[key] == want, key
    assert c["router_experts"] == cat["config"]["num_local_experts"] == 72
    # no toy width
    assert (c["hidden_size"], c["mamba_n_heads"], c["mamba_d_head"], c["mamba_n_groups"],
            c["mamba_d_state"], c["intermediate_size"], c["shared_intermediate_size"],
            c["num_experts_per_tok"]) == (4096, 128, 64, 1, 128, 768, 1536, 10)
    # the published 'mamba' is this repo's 'mamba2' (its 'mamba' is Jamba's Mamba-1)
    assert c["llama_layer_types"] == [
        {"mamba": "mamba2"}.get(t, t) for t in c["layer_types"]]
    assert c["layer_types"][:10].count("mamba") == 9 and c["layer_types"][5] == "attention"
    kw = json.loads(spec.service_env(c)["LLAMA_CONFIG"])
    assert (kw["embedding_multiplier"], kw["attention_multiplier"],
            kw["residual_multiplier"], kw["logits_scaling"]) == (12, 0.0078125, 0.22, 16)
    assert kw["experts_held"] == 36 and kw["num_experts"] == 72 and kw["ssm_groups"] == 1


def test_the_new_composition_runs_under_the_scopes_the_readers_know():
    """The toy's paged decode step and prompt window, lowered: the mixer in
    its new place under ``ssm`` and its parts, the expert block behind it
    under ``mlp`` and its parts, the attention, the two ends — every name a
    standing reader's ``scopes`` asks for, each INSIDE the part
    ``cellbench/scopes.py`` folds it into."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cellbench import scopes
    from mlmicroservicetemplate_tpu.models import llama
    from mlmicroservicetemplate_tpu.models.gpt import PagedState
    from mlmicroservicetemplate_tpu.models.sampling import greedy_params

    c = spec.load_json(spec.HERE + "/configs/granite-4.0-h-small-ep2-d10.json")
    toy = spec.load_json(spec.HERE + "/tests/rehearse_granite.json")["config"]
    kw = json.loads(spec.service_env({**c, **toy, "vocab_size": 128})["LLAMA_CONFIG"])
    cfg = llama.LlamaConfig(**{**kw, "num_layers": 3, "eos_id": 1, "pad_id": 0,
                               "layer_types": ["mamba2", "attention", "mamba2"],
                               "pallas_interpret": True})
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    nb, bs, tw, slots = 12, 4, 6, 2
    width = cfg.num_kv_heads * cfg.head_dim
    state = PagedState(
        cache_k=[jnp.zeros((nb, bs, width))], cache_v=[jnp.zeros((nb, bs, width))],
        key_valid=jnp.zeros((slots, tw * bs), jnp.int32),
        write_idx=jnp.zeros((slots,), jnp.int32), pos=jnp.zeros((slots,), jnp.int32),
        last_token=jnp.zeros((slots,), jnp.int32), done=jnp.zeros((slots,), bool),
        tokens=jnp.zeros((slots, 4), jnp.int32), sample=greedy_params(slots),
        ssm=llama.zero_ssm(cfg, slots, jnp.float32))
    table = jnp.asarray(np.arange(slots * tw, dtype=np.int32).reshape(slots, tw))
    # the compiled program's ``op_name`` metadata: the paths a device trace's
    # ``tf_op`` statistic carries
    step = jax.jit(lambda p, s: llama.generate_chunk_paged(p, cfg, s, table, 2)).lower(
        params, state).compile().as_text()
    ids = jnp.ones((slots, 8), jnp.int32)
    window = jax.jit(lambda p, s: llama.paged_prefill_chunk(
        p, cfg, s, table, ids, ids, jnp.zeros((slots,), jnp.int32),
        ssm_rows=jnp.asarray([[0, 8], [1, 7]], jnp.int32), tally=[])).lower(
            params, state).compile().as_text()
    mixer = ["ssm/ssm_in_proj", "ssm/ssm_conv", "ssm/ssm_gate_norm", "ssm/ssm_out_proj"]
    block = ["mlp/moe_route", "mlp/moe_experts", "mlp/moe_shared", "mlp/moe_combine"]
    for text, own in ((step, ["ssm/ssm_step", "embed", "lm_head", "attn/attn_full",
                              "kv_write"]),
                      (window, ["ssm/ssm_scan", "embed", "attn/attn_full", "kv_write"])):
        for path in mixer + block + own:
            assert f"/{path}/" in text or f"/{path}\"" in text, path
    # the parts' table folds the block into ``mlp``; the mixer's names are the
    # sub-scope readers' own (``decode_ssm_ms`` asks for "ssm")
    assert scopes.scope_of("jit(f)/decode_chunk/while/body/mlp/moe_experts/x") == "mlp"
    assert scopes.scope_of("jit(f)/decode_chunk/while/body/ssm/ssm_step/x") == "decode_chunk"


def test_the_cell_resolves_with_its_entries():
    cell = spec.resolve(CELL)
    assert cell.chips == 1 and cell.traffic["endpoint"] == "stream"
    assert [m.name for m in cell.end_to_end] == ["tbt_p99_ms", "setup_s"]
    names = [m.name for m in cell.per_layer]
    assert set(OWN) <= set(names) and len(names) == 35
    assert sum(n.startswith("boot_") for n in names) == 7
    # the standing entries whose definitions read this cell's scopes and
    # counters: the cell is APPENDED to them (one entry a definition, PR 55)
    assert {"decode_ssm_ms.nemotron", "ssm_proj_ms.nemotron", "decode_attn_ms.nemotron",
            "prefill_ssm_scan_ms.nemotron", "ssm_scan_masked_pct.nemotron",
            "decode_moe_ms.nemotron", "moe_overhead_ms.nemotron", "moe_shared_ms.nemotron",
            "moe_imbalance.nemotron", "moe_held_share_pct.nemotron",
            "moe_rows_skipped_pct.dsv2", "decode_step_ms.nemotron",
            "prefill_window_ms.nemotron", "loop_unnamed_pct.serve",
            "event_loop_lag_p99_ms", "prefill_moe_experts_ms.gigachat"} <= set(names)
    bench = spec.load_benchmark()
    assert CELL in [w["name"] for w in bench["workloads"]]  # by NAME: later PRs append
    (entry,) = [e for e in bench["configs"] if e["name"] == "granite-4.0-h-small-ep2-d10"]
    assert entry["reduced"] == ["num_hidden_layers", "num_local_experts", "vocab_size"]
    assert entry["source"] == (
        "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json")
    assert len(bench["per_layer"]) <= 128
    for m in bench["per_layer"]:
        if m["name"] in OWN:  # a new definition each, this cell's alone
            assert m["workloads"] == [CELL] and m["moves"] == "tbt_p99_ms"
            assert m["unit"] == "%" and m["source"] == "device_trace"


def test_readers_return_nothing_where_there_is_nothing_to_read():
    """On a program without the scopes or the families (the parent), and
    untraced, the new entries' reader returns None and raises nothing."""
    import types

    cell = spec.resolve(CELL)
    own = [m for m in cell.per_layer if m.name in OWN]
    assert {m.name: m.args["what"] for m in own} == OWN
    for trace in (None, types.SimpleNamespace(module_time=lambda m: (0.0, 0), ops={})):
        ctx = types.SimpleNamespace(
            trace=trace, peaks=None if trace is None else {
                "hbm_bytes_per_s": 8.19e11, "bf16_flops_per_s": 1.97e14},
            prom_after={}, prom_before={}, notes={}, config=cell.config,
            engine={"chunk_tokens": 4}, prom_delta=lambda family: None)
        for mine in own:
            assert mine.reader == "granite_roofline"
            assert mine.read(ctx, **mine.args) is None


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
         os.path.join(here, "rehearse_granite.json")],
        cwd=spec.REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=1500)
    assert r.returncode == 0, r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["device"]["platform"] == "cpu" and last["rehearsal"] is True
    assert last["metrics"] == {} and last["correct"] is True and last["failed"] == 0
    check = next(json.loads(ln.split(" ", 2)[2]) for ln in r.stdout.splitlines()
                 if ln.startswith("cellbench correct"))
    assert len(check["state_slow_rel_err"]) == 9 and len(set(check["state_row"])) == 1
    assert 0.0 < check["logit_std"] < 0.05 and check["routing"]["held_share"] > 0.2
    # the pool's keys: a prompt block and a decode block, found and near
    assert len(check["kv_rel_err"]) == 2 and max(check["kv_rel_err"]) < 1e-4
    got = set(last["rehearsal_values"])
    # (the state's share needs the admission ledger: KV_BUDGET_MB is 0 here)
    assert {"ssm_scan_masked_pct.nemotron", "prefill_windows_batched_pct.nemotron",
            "moe_held_share_pct.nemotron"} <= got
