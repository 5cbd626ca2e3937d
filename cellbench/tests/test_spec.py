"""Every cell of BENCHMARK.json resolves; a new cell is new files and
one new entry."""

import json
import os
import shutil

import pytest

from cellbench import spec


def test_every_cell_resolves_its_files():
    bench = spec.load_benchmark()
    assert bench["paths"] == ["cellbench"]
    for w in bench["workloads"]:
        cell = spec.resolve(w["name"])
        assert cell.chips == w["chips"]
        names = {m.name for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, "every cell reports a per-layer metric"
        assert all(callable(m.read) for m in cell.end_to_end + cell.per_layer)
        e2e = {m["name"] for m in bench["end_to_end"]}
        for m in bench["per_layer"]:
            if m["name"] in {x.name for x in cell.per_layer}:
                assert m["moves"] in names and m["moves"] in e2e
        env = spec.service_env(cell.config)
        assert env["MODEL_NAME"]
        ref = os.path.join(cell.bench_dir, "references",
                           cell.config["reference"] + ".py")
        assert os.path.exists(ref)
        for key in cell.config.get("reduced", {}):
            assert key in cell.config


def test_config_files_list_what_benchmark_json_lists():
    bench = spec.load_benchmark()
    for c in bench["configs"]:
        cfg = spec.load_json(os.path.join(spec.REPO, c["file"]))
        assert sorted(cfg.get("reduced", {})) == sorted(c["reduced"])


def test_llama_config_is_derived_from_the_published_keys():
    cell = spec.resolve("mistral-7b-d8.decode-closed")
    lc = json.loads(spec.service_env(cell.config)["LLAMA_CONFIG"])
    assert lc == {"vocab_size": 32000, "d_model": 4096, "num_heads": 32,
                  "num_kv_heads": 8, "num_layers": 8, "d_ff": 14336,
                  "max_position": 32768, "rope_theta": 10000.0, "rms_eps": 1e-05}


def test_unknown_names_are_refused():
    with pytest.raises(spec.SpecError):
        spec.resolve("no-such.cell")


def test_a_new_cell_is_new_files_and_one_entry(tmp_path):
    """A throwaway benchmark root: the installed files untouched, one
    new traffic mix, one new per-layer metric with a reader of its
    own, and one entry each in BENCHMARK.json."""
    root = tmp_path
    shutil.copytree(spec.HERE, root / "cellbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = spec.load_benchmark()
    (root / "cellbench" / "traffic" / "tiny-burst.json").write_text(json.dumps({
        "loop": "open", "rate_per_s": 2, "arrivals": {"dist": "gamma", "cv": 3},
        "ramp_s": 0, "endpoint": "stream",
        "prompt_tokens": {"dist": "fixed", "value": 32},
        "output_tokens": {"dist": "fixed", "value": 8}}))
    (root / "cellbench" / "readers" / "count_requests.py").write_text(
        "def read(ctx):\n    return float(len(ctx.records)) or None\n")
    (root / "cellbench" / "layer_metrics" / "requests.burst.json").write_text(
        json.dumps({"reader": "count_requests"}))
    new = "mistral-7b-d8.tiny-burst"
    bench["workloads"].append({"name": new, "config": "mistral-7b-d8",
                               "traffic": "tiny-burst", "chips": 4, "why": "x"})
    bench["per_layer"].append({
        "name": "requests.burst", "unit": "req", "better": "higher",
        "source": "host_clock", "layer": "load generator",
        "moves": "ttft_p95_ms", "workloads": [new]})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p95_ms", "tbt_p99_ms"):
            m["workloads"].append(new)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.resolve(new, str(root))
    assert cell.chips == 4 and cell.traffic["arrivals"]["cv"] == 3
    assert [m.name for m in cell.per_layer] == ["requests.burst"]
    assert {m.name for m in cell.end_to_end} == {
        "ttft_p95_ms", "tbt_p99_ms", "setup_s"}

    class Ctx:
        records = [{}, {}, {}]

    assert cell.per_layer[0].read(Ctx()) == 3.0
    # the cells that were there resolve as before
    assert spec.resolve("mistral-7b-d8.chat-open", str(root)).chips == 1


def test_the_bert_cell_is_entries_only(tmp_path):
    """`bert-base.predict-closed` is left out of BENCHMARK.json (its
    peak memory is under the floor, PERF.md); every file it needs is
    here, so adding it is the entries of bert_cell_entries.json."""
    from conftest import root_with_bert

    root = root_with_bert(tmp_path)
    cell = spec.resolve("bert-base.predict-closed", root)
    assert cell.config["hidden_size"] == 768 and cell.config["reduced"] == {}
    assert {m.name for m in cell.end_to_end} == {"predict_p95_ms", "setup_s"}
    assert {m.name for m in cell.per_layer} == {
        "predict_req_per_s", "queue_wait_p95_ms.predict", "batch_mean.predict",
        "predict_step_ms.predict", "device_idle_pct.predict"}
