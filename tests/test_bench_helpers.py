"""The benchmark's ``/metrics`` readers (``cellbench/reduce.py``): every
``prom_hist`` per-layer metric is a scrape before and after the window,
``hist_delta`` between them and ``hist_pctile`` over the difference, so
the text-format parsing and the bucket-percentile arithmetic are pinned
here (pure logic, no service)."""

import functools
import hashlib
import json
import math
import os

import pytest

from cellbench import spec
from cellbench.reduce import hist_delta, hist_pctile, parse_prom

SCRAPE = """\
# HELP stream_tbt_seconds Streaming inter-chunk delivery gap
# TYPE stream_tbt_seconds histogram
stream_tbt_seconds_bucket{le="0.001",model="gpt2"} 2.0
stream_tbt_seconds_bucket{le="0.01",model="gpt2"} 6.0
stream_tbt_seconds_bucket{le="1.0",model="gpt2"} 9.0
stream_tbt_seconds_bucket{le="+Inf",model="gpt2"} 10.0
stream_tbt_seconds_count{model="gpt2"} 10.0
stream_tbt_seconds_sum{model="gpt2"} 3.5
stream_tbt_seconds_created{model="gpt2"} 1.7e+09
other_series_total{model="gpt2"} 5.0
"""


def _scrape(text):
    return parse_prom(text)["stream_tbt_seconds"]


def test_scrape_histogram_parses_family():
    h = _scrape(SCRAPE)
    assert h["count"] == 10.0
    assert h["sum"] == 3.5
    assert h["buckets"] == {0.001: 2.0, 0.01: 6.0, 1.0: 9.0, math.inf: 10.0}


def test_scrape_histogram_sums_label_children():
    two_models = SCRAPE + (
        'stream_tbt_seconds_bucket{le="0.001",model="llama"} 1.0\n'
        'stream_tbt_seconds_bucket{le="+Inf",model="llama"} 1.0\n'
        'stream_tbt_seconds_count{model="llama"} 1.0\n'
        'stream_tbt_seconds_sum{model="llama"} 0.0005\n'
    )
    h = _scrape(two_models)
    assert h["count"] == 11.0
    assert h["buckets"][0.001] == 3.0


def test_hist_delta_isolates_section():
    before = _scrape(SCRAPE)
    after = {
        "count": 14.0,
        "sum": 5.0,
        "value": 0.0,
        "buckets": {0.001: 2.0, 0.01: 8.0, 1.0: 13.0, math.inf: 14.0},
    }
    d = hist_delta(after, before)
    assert d["count"] == 4.0 and d["sum"] == 1.5
    assert d["buckets"] == {0.001: 0.0, 0.01: 2.0, 1.0: 4.0, math.inf: 4.0}


def test_hist_pctile_interpolates():
    h = {"count": 10.0, "sum": 3.5,
         "buckets": {0.001: 2.0, 0.01: 6.0, 1.0: 9.0, math.inf: 10.0}}
    # p50 target = 5th observation: bucket (0.001, 0.01], 3rd of 4 in
    # the bucket → 0.001 + (0.01-0.001) * (5-2)/4.
    assert hist_pctile(h, 0.5) == pytest.approx(0.001 + 0.009 * 0.75)
    # A percentile landing in +Inf reports the largest finite edge.
    assert hist_pctile(h, 0.99) == 1.0
    # Empty histogram → None.
    assert hist_pctile({"count": 0.0, "sum": 0.0, "buckets": {}}, 0.5) is None


def test_hist_pctile_median_agrees_with_mean_regime():
    # Sanity tie to the A/B's use: all mass in one bucket → percentile
    # lands inside it, bounded by its edges.
    h = {"count": 8.0, "sum": 4.0, "buckets": {0.5: 0.0, 1.0: 8.0, math.inf: 8.0}}
    p = hist_pctile(h, 0.99)
    assert 0.5 < p <= 1.0


def test_hist_pctile_resolves_past_ten_seconds_with_r20_buckets():
    """The r11 honest negative, closed (r20): with the old 10 s top
    bucket a CPU-box p99 could only report "≥ 10 s"; the extended
    default buckets now interpolate a real value inside (10, 30]."""
    from mlmicroservicetemplate_tpu.utils import metrics as m

    assert max(m._DEFAULT_LATENCY_BUCKETS) > 10.0
    assert max(m._FINE_BUCKETS) > 10.0
    # 9 fast observations + 1 at ~20 s: p99 used to land in +Inf and
    # report the 10.0 edge; with the extended set it interpolates.
    buckets = {le: 9.0 for le in m._FINE_BUCKETS if le <= 10.0}
    buckets[30.0] = 10.0
    buckets[120.0] = 10.0
    buckets[math.inf] = 10.0
    h = {"count": 10.0, "sum": 29.0, "buckets": buckets}
    p = hist_pctile(h, 0.99)
    assert 10.0 < p <= 30.0


def test_latency_buckets_env_overrides_defaults():
    from mlmicroservicetemplate_tpu.utils import metrics as m

    assert m.parse_buckets("0.5,1,2,4") == (0.5, 1.0, 2.0, 4.0)
    # Lenient at import time: garbage falls back to None (defaults) —
    # ServiceConfig's validator is the strict boot-time gate.
    assert m.parse_buckets("garbage") is None
    assert m.parse_buckets("2,1") is None



# ---------------------------------------------------------------------------
# BENCHMARK.json, held by what a cell READS and by (cell, entry) PAIR — never
# by what an entry is called, where it stands or what stands beside it (PR
# 58).  A ``benchmark`` PR may rename, fold and append entries and may not
# edit ``tests/``: no case below names an entry, and how many cases there are
# is a property of this directory (``bench_cell_readings.json``), not of the
# benchmark.

TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "bench_cell_readings.json")
COLUMNS = ["reader", "args", "unit", "better", "source", "layer", "moves"]
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
PROM_READERS = ("prom_hist", "prom_counter_ratio", "prom_counter_rate", "prom_labelled")
#: Entries the parent's files fail ``test_every_pair_of_the_benchmark_resolves``
#: on, by fault as the test words it, with the reason (PERF.md section 7 lists
#: them for the ``benchmark`` PR that repairs them).  None on PR 58's tree.
KNOWN: dict[str, str] = {}


_bench = functools.lru_cache(maxsize=None)(spec.load_benchmark)


def _cells() -> list[str]:
    return [w["name"] for w in _bench()["workloads"]]


@functools.lru_cache(maxsize=None)
def _resolved(cell: str) -> spec.Cell:
    return spec.resolve(cell)


def _key(row: list) -> tuple:
    """A row of the table as it is compared: the arguments in one spelling."""
    reader, args, *rest = row
    return (reader, json.dumps(args, sort_keys=True), *rest)


def _reading(entry: dict, reader: str, args: dict) -> tuple:
    """What an entry reads, ``COLUMNS`` of it and its data file: no name."""
    return _key([reader, args, *(entry[k] for k in COLUMNS[2:])])


@functools.lru_cache(maxsize=None)
def _read_in(cell: str) -> dict:
    """``{reading: [metric, ...]}`` of what ``cell`` resolves on these files."""
    entry = {m["name"]: m for m in _bench()["per_layer"]}
    out: dict = {}
    for m in _resolved(cell).per_layer:
        out.setdefault(_reading(entry[m.name], m.reader, m.args), []).append(m)
    return out


def _reads(cell: str, reader: str, **args) -> spec.Metric:
    """The ONE per-layer metric of ``cell`` that ``reader`` reads with ``args``
    among its arguments: a reader's test finds its entry by definition."""
    hit = [m for m in _resolved(cell).per_layer if m.reader == reader
           and all(m.args.get(k) == v for k, v in args.items())]
    assert len(hit) == 1, (cell, reader, args, len(hit))
    return hit[0]


def _table(path: str = TABLE) -> dict:
    return spec.load_json(path) if os.path.exists(path) else {
        "columns": COLUMNS, "cells": {}}


def freeze_missing_cells(path: str = TABLE) -> list[str]:
    """ADD to the table the cells ``BENCHMARK.json`` has and the table lacks
    and return their names.  A cell the table has is never rewritten:
    re-freezing after a loss would hide the loss.  The PR that adds a cell
    (and may edit ``tests/``) runs, from the root of the repo, ``python -c
    "import sys; sys.path.insert(0, 'tests'); import test_bench_helpers as t;
    print(t.freeze_missing_cells())"``."""
    table = _table(path)
    added = [cell for cell in _cells() if cell not in table["cells"]]
    for cell in added:
        twice = {k: [m.name for m in ms] for k, ms in _read_in(cell).items() if len(ms) > 1}
        if twice:  # one row a pair: two entries of a cell with one reading are twins
            raise ValueError(f"{cell}: entries that read the same {twice}")
        table["cells"][cell] = [[k[0], json.loads(k[1]), *k[2:]] for k in sorted(_read_in(cell))]
    if added:
        cells = ",\n".join(
            f"  {json.dumps(cell)}: [\n"
            + ",\n".join("   " + json.dumps(row, sort_keys=True) for row in rows) + "\n  ]"
            for cell, rows in table["cells"].items())
        with open(path, "w", encoding="utf-8") as f:
            f.write('{\n "columns": %s,\n "cells": {\n%s\n }\n}\n'
                    % (json.dumps(table["columns"]), cells))
    return added


ROWS = [(cell, row) for cell, rows in _table()["cells"].items() for row in rows]


def _row_id(param: tuple) -> str:
    cell, row = param
    return f"{cell}-{row[0]}-{hashlib.sha1(_key(row)[1].encode()).hexdigest()[:6]}"


@pytest.mark.parametrize("cell,row", ROWS, ids=[_row_id(p) for p in ROWS])
def test_reading_still_read(cell, row):
    """The cell still resolves a metric with exactly this reading, under
    whatever name, through a callable reader.  A cell the benchmark no longer
    has holds nothing: ``test_configuration_and_cell`` reports it, once."""
    if cell not in _cells():
        return
    held = _read_in(cell).get(_key(row), [])
    assert held and all(callable(m.read) for m in held), (
        f"{cell} no longer reads {dict(zip(COLUMNS, row))}")


def test_freezing_adds_the_cells_a_table_lacks_and_rewrites_none(tmp_path):
    path = str(tmp_path / "table.json")
    first, *others = _cells()
    assert freeze_missing_cells(path) == [first, *others]
    whole = _table(path)["cells"]
    assert all(len({_key(r) for r in whole[c]}) == len(_resolved(c).per_layer) for c in whole)
    with open(path, "w", encoding="utf-8") as f:  # a cell went, another lost readings
        json.dump({"columns": COLUMNS, "cells": {first: whole[first][:1]}}, f)
    assert freeze_missing_cells(path) == others and freeze_missing_cells(path) == []
    assert _table(path)["cells"] == {**whole, first: whole[first][:1]}


@functools.lru_cache(maxsize=None)
def _declared() -> dict:
    """``{family: metric object}`` of what ``utils/metrics.py`` declares."""
    from mlmicroservicetemplate_tpu.utils import metrics

    return {getattr(obj, "_name", None): obj for obj in vars(metrics).values()}


def _prom_faults(reader: str, args: dict) -> list[str]:
    """What a ``prom_*`` data file asks of ``/metrics`` that
    ``utils/metrics.py`` does not declare: a family, or a label name."""
    declared, out = _declared(), []
    for family in [args[k] for k in ("family", "part") if k in args] + args.get("rest", []):
        base = family[:-len("_total")] if family.endswith("_total") else family
        if base not in declared:
            out.append(f"{reader} reads {family}, which the program does not declare")
            continue
        for pick in ("labels", "over"):
            extra = set(args.get(pick) or {}) - set(declared[base]._labelnames)
            if extra:
                out.append(f"{reader} picks {family} by {sorted(extra)}, no label of it")
    return out


def test_every_pair_of_the_benchmark_resolves():
    """Every (cell, entry) pair ``BENCHMARK.json`` has (247 on PR 58's files),
    in ONE case that lists every offender: the entry resolves in the cell to
    one metric with a callable reader and the entry's unit; it moves an
    end-to-end metric that cell reports; no cell outside its list resolves
    it; the readings of the ``compile`` layer (a boot's) are the only ones
    that move ``setup_s`` and every cell reads them; a ``prom_*`` data file
    names families and labels the program declares."""
    bench, cells = _bench(), _cells()
    names = [m["name"] for m in bench["per_layer"]]
    wrong = [f"{n}: {names.count(n)} entries of one name" for n in set(names)
             if names.count(n) > 1]
    for entry in bench["per_layer"]:
        name, listed = entry["name"], entry.get("workloads", cells)
        faults = [f"{field} {entry[field]!r}" for field, known in
                  (("source", SOURCES), ("better", ("lower", "higher")))
                  if entry[field] not in known]
        if len(set(listed)) != len(listed) or not set(listed) <= set(cells):
            faults.append(f"lists {listed}")
        if (entry["moves"] == "setup_s") != (entry["layer"] == "compile"):
            faults.append(f"layer {entry['layer']!r} moves {entry['moves']}")
        if entry["moves"] == "setup_s" and set(listed) != set(cells):
            faults.append("a boot reading that some cell does not read")
        for cell in cells:
            hits = [m for m in _resolved(cell).per_layer if m.name == name]
            if cell not in listed:
                faults += [f"read in {cell}, which it does not list"] * bool(hits)
            elif len(hits) != 1 or not callable(hits[0].read) or hits[0].unit != entry["unit"]:
                faults.append(f"resolves in {cell} to {hits}")
            elif entry["moves"] not in [m.name for m in _resolved(cell).end_to_end]:
                faults.append(f"moves {entry['moves']}, which {cell} does not report")
        data = spec.load_json(os.path.join(spec.HERE, "layer_metrics", name + ".json"))
        if data["reader"] in PROM_READERS:
            faults += _prom_faults(data["reader"], data.get("args", {}))
        wrong += [f"{name}: {f}" for f in faults if f"{name}: {f}" not in KNOWN]
    assert not wrong, "\n".join(wrong)


def test_one_entry_a_definition():
    """No two entries agree in every field but ``name`` and ``workloads`` and
    in their data file's reader and arguments — but for the twins the
    benchmark's own ``twins_waiting.json`` lists, a list that may only shrink
    (39 wait on PR 58's files; a fold empties it and changes no case here)."""
    bench = _bench()
    waiting = spec.load_json(
        os.path.join(spec.HERE, "tests", "twins_waiting.json"))["groups"]
    groups: dict = {}
    for m in bench["per_layer"]:
        d = spec.load_json(os.path.join(spec.HERE, "layer_metrics", m["name"] + ".json"))
        groups.setdefault(_reading(m, d["reader"], d.get("args", {})), []).append(m["name"])
    twins = [names for names in groups.values() if len(names) > 1]
    for names in twins:
        assert any(set(names) <= set(g) for g in waiting), names
    assert len(bench["per_layer"]) <= 128  # the file's limit
    assert sum(len(names) - 1 for names in twins) <= 39


#: The configurations the benchmark holds and their cells:
#: ``(name, source, reduced, holds, {cell: (traffic, chips, end-to-end)})``.
#: ``holds`` is what the configuration's file says at its cut keys and of its
#: layer pattern (a string or a list is held by its head).
_HF = "https://huggingface.co/"
_CLOSED = ["ttft_p95_ms", "tbt_p95_ms", "tokens_per_s", "setup_s"]
_LONGDOC = ("longdoc-closed", 1, ["tbt_p99_ms", "setup_s"])
CONFIGS = [
    ("mistral-7b-d8", _HF + "mistralai/Mistral-7B-v0.1/blob/main/config.json",
     ["num_hidden_layers"], {"num_hidden_layers": 8},
     {"mistral-7b-d8.decode-closed": ("decode-closed", 1, _CLOSED),
      "mistral-7b-d8.chat-open": ("chat-open", 1, ["ttft_p95_ms", "tbt_p99_ms", "setup_s"])}),
    ("olmoe-1b-7b-d8", _HF + "allenai/OLMoE-1B-7B-0125-Instruct/blob/main/config.json",
     ["num_hidden_layers"], {"num_hidden_layers": 8, "num_experts": 64},
     {"olmoe-1b-7b-d8.decode-closed": ("decode-closed", 1, _CLOSED)}),
    ("trinity-mini-d5", _HF + "arcee-ai/Trinity-Mini/blob/main/config.json",
     ["num_hidden_layers", "num_dense_layers"],
     {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 128,
      "layer_types": ["sliding_attention"] * 3 + ["full_attention", "sliding_attention"]},
     {"trinity-mini-d5.longdoc-closed": _LONGDOC}),
    ("deepseek-v2-ep4-d5", _HF + "deepseek-ai/DeepSeek-V2/blob/main/config.json",
     ["num_hidden_layers", "n_routed_experts", "vocab_size"],
     {"num_hidden_layers": 5, "n_routed_experts": 40, "vocab_size": 25600,
      "router_experts": 160},
     {"deepseek-v2-ep4-d5.longdoc-closed": _LONGDOC}),
    ("nemotron3-super-ep4-d11",
     _HF + "nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json",
     ["num_hidden_layers", "n_routed_experts", "vocab_size"],
     {"num_hidden_layers": 11, "n_routed_experts": 128, "vocab_size": 32768,
      "hybrid_override_pattern": "MEMEMEM*EME"},
     {"nemotron3-super-ep4-d11.longdoc-closed": _LONGDOC}),
    ("gigachat35-ep16-d5", _HF + "ai-sage/GigaChat3.5-432B-A28B/blob/main/config.json",
     ["num_hidden_layers", "n_routed_experts", "first_k_dense_replace", "vocab_size"],
     {"num_hidden_layers": 5, "n_routed_experts": 16, "first_k_dense_replace": 1,
      "vocab_size": 16032, "layer_types": ["linear"] * 4 + ["full"]},
     {"gigachat35-ep16-d5.longdoc-closed": _LONGDOC}),
    ("jamba2-3b-d28", _HF + "ai21labs/AI21-Jamba2-3B/blob/main/config.json",
     [], {"num_hidden_layers": 28, "tie_word_embeddings": True, "num_key_value_heads": 1},
     {"jamba2-3b-d28.longdoc-closed": _LONGDOC}),
    ("granite-4.0-h-small-ep2-d10",
     _HF + "ibm-granite/granite-4.0-h-small/blob/main/config.json",
     ["num_hidden_layers", "num_local_experts", "vocab_size"],
     {"num_hidden_layers": 10, "num_local_experts": 36, "vocab_size": 50176,
      "router_experts": 72,
      "layer_types": ["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
      "llama_layer_types": ["mamba2"] * 5 + ["attention"] + ["mamba2"] * 4},
     {"granite-4.0-h-small-ep2-d10.longdoc-closed": _LONGDOC}),
    ("phi4-mini-flash-d32",
     _HF + "microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json",
     [], {"num_hidden_layers": 32, "tie_word_embeddings": True, "sliding_window": 512,
          "num_key_value_heads": 20, "vocab_size": 200064, "window_ring": 1552,
          "layers_block_type": ["mamba", "window"] * 8 + ["mamba", "full", "gmu", "cross"]},
     {"phi4-mini-flash-d32.longdoc-closed": _LONGDOC}),
]


@pytest.mark.parametrize("name,source,reduced,holds,cells", CONFIGS,
                         ids=[c[0] for c in CONFIGS])
def test_configuration_and_cell(name, source, reduced, holds, cells):
    """The configuration is in the benchmark with its published source, its
    file and the keys it was cut at; the file says what each was cut to; each
    of its cells is there with its traffic, its chips and the end-to-end
    metrics it reports.  A cell that went fails here and nowhere else."""
    bench = _bench()
    (cfg,) = [c for c in bench["configs"] if c["name"] == name]
    assert (cfg["file"], cfg["source"], cfg["reduced"]) == (
        f"cellbench/configs/{name}.json", source, reduced)
    assert len(cfg["why"]) <= 200
    file = spec.load_json(os.path.join(spec.REPO, cfg["file"]))
    assert list(file["reduced"]) == reduced and "deployment" in file["assumed"]
    for key, cut in file["reduced"].items():
        assert cut["here"] == file[key] == holds[key] != cut["source"]
    for key, want in holds.items():
        have = file[key]
        assert (have[:len(want)] if isinstance(want, (str, list)) else have) == want, key
    have = {w["name"]: w for w in bench["workloads"]}
    for cell, (traffic, chips, end_to_end) in cells.items():
        assert cell in have, f"the benchmark no longer has the cell {cell}"
        w = have[cell]
        assert (w["config"], w["traffic"], w["chips"]) == (name, traffic, chips)
        assert len(w["why"]) <= 200
        assert [m.name for m in _resolved(cell).end_to_end] == end_to_end
        assert _resolved(cell).config == file


# ---------------------------------------------------------------------------
# The readers, on the program's own families and scopes: each finds its entry
# by what it reads (reader and arguments), and reads no value — never 0 —
# from a program without the family or the scope (the parent)

DSV2_CELL = "deepseek-v2-ep4-d5.longdoc-closed"
NEMO_CELL = "nemotron3-super-ep4-d11.longdoc-closed"
GRANITE_CELL = "granite-4.0-h-small-ep2-d10.longdoc-closed"


def test_table_blocks_dead_pct_reads_the_programs_counters():
    """The families as ``/metrics`` exports them, through the reader the
    entries name; a program without them (the parent) reads no value."""
    import types

    from cellbench.readers import prom_counter_ratio
    from mlmicroservicetemplate_tpu.utils import metrics  # registers the families
    from prometheus_client import generate_latest

    metrics.KV_TABLE_BLOCKS_LIVE.labels("reader-unit").inc(25)
    metrics.KV_TABLE_BLOCKS_DEAD.labels("reader-unit").inc(75)
    after = parse_prom(generate_latest().decode())
    base = {f: dict(after[f], value=after[f]["value"] - v) for f, v in
            (("kv_table_blocks_live", 25.0), ("kv_table_blocks_dead", 75.0))}

    def ctx(after, before):
        return types.SimpleNamespace(
            notes={}, prom_delta=lambda fam: (
                None if fam not in after
                else hist_delta(after[fam], before.get(fam))))

    args = ("kv_table_blocks_dead", ["kv_table_blocks_live"])
    assert prom_counter_ratio.read(ctx(after, base), *args) == 75.0
    assert prom_counter_ratio.read(ctx({}, {}), *args) is None


def test_dsv2_held_share_reads_the_programs_counters():
    """The held share of a cell's expert assignments through the reader its
    entry names, off the families as ``/metrics`` exports them; a program
    without them (the parent) reads no value."""
    import types

    from cellbench.readers import prom_counter_ratio
    from mlmicroservicetemplate_tpu.utils import metrics
    from prometheus_client import generate_latest

    metrics.MOE_ASSIGNMENTS_HELD.labels("reader-unit").inc(30)
    metrics.MOE_ASSIGNMENTS_ABSENT.labels("reader-unit").inc(90)
    metrics.KV_LATENT_KEYS_READ.labels("reader-unit").inc(7)
    after = parse_prom(generate_latest().decode())
    assert after["kv_latent_keys_read"]["value"] >= 7.0
    base = {f: dict(after[f], value=after[f]["value"] - v) for f, v in
            (("moe_assignments_held", 30.0), ("moe_assignments_absent", 90.0))}

    def ctx(after, before):
        return types.SimpleNamespace(
            notes={}, prom_delta=lambda fam: (
                None if fam not in after
                else hist_delta(after[fam], before.get(fam))))

    m = _reads(DSV2_CELL, "prom_counter_ratio", part="moe_assignments_held")
    assert m.args == {"part": "moe_assignments_held",
                      "rest": ["moe_assignments_absent"]}
    assert prom_counter_ratio.read(ctx(after, base), **m.args) == 25.0
    assert prom_counter_ratio.read(ctx({}, {}), **m.args) is None


def test_prefill_window_ms_is_a_window_executables_mean_time():
    """Through the reader: the device seconds of the runs of the module
    the loop jits ``bundle.paged_prefill_chunk_fn`` as, a run at a time;
    a trace without such a module (no chunked prefill) reads no value."""
    import types

    from cellbench.readers import trace_module_ms
    from mlmicroservicetemplate_tpu.models import registry

    src = open(registry.__file__).read()
    assert "def paged_prefill_chunk_fn(" in src  # the name the module carries

    def ctx(table):
        return types.SimpleNamespace(
            notes={}, engine={"chunk_tokens": 4},
            trace=types.SimpleNamespace(
                module_time=lambda pat: table.get(pat, (0.0, 0))))

    args = {"module": "jit_paged_prefill_chunk_fn", "per": "run"}
    assert trace_module_ms.read(
        ctx({"jit_paged_prefill_chunk_fn": (0.30, 20)}), **args) == pytest.approx(15.0)
    assert trace_module_ms.read(ctx({}), **args) is None


def test_prom_labelled_keeps_children_apart_and_reads_nothing_from_a_parent():
    """Over a scrape text: one child by its labels, children that match
    summed, a share through ``over``; an absent family (the parent
    commit) or an absent child reads no value, never 0."""
    from cellbench.readers import prom_labelled

    text = """\
# HELP boot_phase_seconds x
boot_phase_seconds{model="llama",phase="imports"} 9.5
boot_phase_seconds{model="llama",phase="unnamed"} 0.5
boot_phase_seconds{model="llama",phase="total"} 50.0
xla_executables_total{outcome="compiled",when="boot"} 0.0
xla_executables_total{outcome="loaded",when="boot"} 41.0
xla_executables_total{outcome="loaded",when="serving"} 9.0
xla_executables_created{outcome="loaded",when="boot"} 1.7e+09
"""
    kids = prom_labelled.children(text, "xla_executables_total")
    assert len(kids) == 3
    assert prom_labelled.pick(kids, {"outcome": "compiled", "when": "boot"}) == 0.0
    assert prom_labelled.pick(kids, {"outcome": "loaded", "when": "boot"}) == 41.0
    assert prom_labelled.pick(kids, {"outcome": "loaded"}) == 50.0
    assert prom_labelled.pick(kids, {"outcome": "compiled", "when": "serving"}) is None
    assert prom_labelled.children(text, "xla_executable_seconds_total") == []
    phases = prom_labelled.children(text, "boot_phase_seconds")
    assert prom_labelled.pick(phases, {"phase": "imports"}) == 9.5
    assert parse_prom(text)["boot_phase_seconds"]["value"] == 60.0  # summed there


def test_prefill_windows_batched_pct_reads_the_programs_counters():
    """The two families as ``/metrics`` exports them, through the reader:
    the batched share of the windows dispatched in the window; a cell whose
    every dispatch holds one window reads 0 (both children exist from the
    first dispatch on), a program without the families (the parent) no
    value."""
    import types

    from cellbench.readers import prom_counter_ratio
    from mlmicroservicetemplate_tpu.utils import metrics  # registers the families
    from prometheus_client import generate_latest

    def ctx(after, before):
        return types.SimpleNamespace(
            notes={}, prom_delta=lambda fam: (
                None if fam not in after
                else hist_delta(after[fam], before.get(fam))))

    before = parse_prom(generate_latest().decode())
    metrics.PREFILL_WINDOWS_BATCHED.labels("reader-unit-36").inc(9)
    metrics.PREFILL_WINDOWS_ALONE.labels("reader-unit-36").inc(3)
    after = parse_prom(generate_latest().decode())
    args = ("prefill_windows_batched", ["prefill_windows_alone"])
    assert prom_counter_ratio.read(ctx(after, before), *args) == 75.0
    metrics.PREFILL_WINDOWS_BATCHED.labels("reader-unit-36").inc(0)
    metrics.PREFILL_WINDOWS_ALONE.labels("reader-unit-36").inc(5)
    later = parse_prom(generate_latest().decode())
    assert prom_counter_ratio.read(ctx(later, after), *args) == 0.0
    assert prom_counter_ratio.read(ctx({}, {}), *args) is None


def test_nemotron_counter_readings_read_the_programs_families():
    """``ssm_scan_masked_pct`` and ``ssm_state_share_pct`` through the
    reader their entries name, off the families as ``/metrics`` exports
    them; a program without them (the parent) reads no value and does not
    raise."""
    import types

    from mlmicroservicetemplate_tpu.utils import metrics
    from prometheus_client import generate_latest

    metrics.SSM_SCAN_TOKENS.labels("reader-unit").inc(3072)
    metrics.SSM_SCAN_MASKED.labels("reader-unit").inc(768)
    after = parse_prom(generate_latest().decode())
    base = {f: dict(after[f], value=after[f]["value"] - v) for f, v in
            (("ssm_scan_tokens", 3072.0), ("ssm_scan_masked_tokens", 768.0))}

    def ctx(after, before):
        return types.SimpleNamespace(
            notes={}, prom_after=after, trace=None, peaks=None, prom_delta=lambda fam: (
                None if fam not in after
                else hist_delta(after[fam], before.get(fam))))

    masked = _reads(NEMO_CELL, "nemotron_roofline", what="masked_pct")
    assert masked.read(ctx(after, base), **masked.args) == 25.0
    share = _reads(NEMO_CELL, "nemotron_roofline", what="state_share")
    gauges = {"ssm_state_bytes": {"value": 3.0e8}, "kv_committed_bytes": {"value": 1.0e8}}
    assert share.read(ctx(gauges, {}), **share.args) == 75.0
    for m in _resolved(NEMO_CELL).per_layer:
        if m.reader == "nemotron_roofline":
            assert m.read(ctx({}, {}), **m.args) is None


def test_insert_rows_per_dispatch_reads_the_programs_histogram():
    """The family as ``/metrics`` exports it, through the reader: the mean
    rows a dispatch over the window (a lone start, a wave of 3 and one of
    60: 64 rows over 3 dispatches); a window with no insert, and a program
    without the family (the parent), no value."""
    import types

    from cellbench.readers import prom_hist
    from mlmicroservicetemplate_tpu.utils import metrics  # registers the family
    from prometheus_client import generate_latest

    def ctx(after, before):
        return types.SimpleNamespace(
            notes={}, prom_delta=lambda fam: (
                None if fam not in after
                else hist_delta(after[fam], before.get(fam))))

    metrics.STREAM_INSERT_ROWS.labels("reader-unit-41").observe(7)
    before = parse_prom(generate_latest().decode())
    for rows in (1, 3, 60):
        metrics.STREAM_INSERT_ROWS.labels("reader-unit-41").observe(rows)
    after = parse_prom(generate_latest().decode())
    args = ("stream_insert_rows", "mean")
    assert prom_hist.read(ctx(after, before), *args) == pytest.approx(64 / 3)
    assert prom_hist.read(ctx(after, after), *args) is None
    assert prom_hist.read(ctx({}, {}), *args) is None


def test_granite_rooflines_read_nothing_from_a_program_without_the_scopes():
    """The five shares through the reader their entries name: untraced, or on
    a trace without the executable (the parent), no value and no raise."""
    import types

    cell = _resolved(GRANITE_CELL)
    own = [m for m in cell.per_layer if m.reader == "granite_roofline"]
    assert len(own) == 5
    empty = types.SimpleNamespace(module_time=lambda m: (0.0, 0), ops={})
    for trace in (None, empty):
        ctx = types.SimpleNamespace(
            trace=trace, peaks={"hbm_bytes_per_s": 8.19e11, "bf16_flops_per_s": 1.97e14},
            prom_after={}, prom_before={}, notes={}, config=cell.config,
            engine={"chunk_tokens": 4}, prom_delta=lambda family: None)
        assert [m.read(ctx, **m.args) for m in own] == [None] * 5
