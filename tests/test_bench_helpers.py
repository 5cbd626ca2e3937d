"""The benchmark's ``/metrics`` readers (``cellbench/reduce.py``): every
``prom_hist`` per-layer metric is a scrape before and after the window,
``hist_delta`` between them and ``hist_pctile`` over the difference, so
the text-format parsing and the bucket-percentile arithmetic are pinned
here (pure logic, no service)."""

import math

import pytest

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
# table_blocks_dead_pct.* (PR 32): the entries resolve in their cells, and
# the reader finds the program's counters under the names it exports


@pytest.mark.parametrize("metric,moves,cells", [
    ("table_blocks_dead_pct.decode", "tbt_p95_ms",
     ["mistral-7b-d8.decode-closed", "olmoe-1b-7b-d8.decode-closed"]),
    ("table_blocks_dead_pct.chat", "tbt_p99_ms",
     ["mistral-7b-d8.chat-open", "trinity-mini-d5.longdoc-closed"]),
])
def test_table_blocks_dead_pct_resolves_in_its_cells(metric, moves, cells):
    from cellbench import spec

    (entry,) = [m for m in spec.load_benchmark()["per_layer"] if m["name"] == metric]
    assert entry["workloads"] == cells and entry["moves"] == moves
    assert entry["layer"] == "kernels" and entry["source"] == "program_counter"
    for cell in cells:
        resolved = spec.resolve(cell)
        assert metric in [m.name for m in resolved.per_layer]
        assert moves in [m.name for m in resolved.end_to_end]


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


# ---------------------------------------------------------------------------
# deepseek-v2-ep4-d5 (PR 33): the configuration, the cell and its sixteen
# per-layer entries, held by NAME (cellbench/tests/*::test_entries_resolve_by_name
# want older lists to be the tail of theirs)

def _named(entries: list, name: str) -> dict:
    """The ONE entry of a ``BENCHMARK.json`` list called ``name``: a
    configuration, a cell or a metric is held by its NAME, never by its place
    in the list — every later PR appends (PR 56)."""
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


def _lists(entry: dict, standing: list) -> bool:
    """Whether ``entry``'s ``workloads`` begin with its ``standing`` members
    in their order: a later PR appends its cell behind them and changes
    nothing else (one entry a definition, PR 55)."""
    return entry["workloads"][:len(standing)] == standing


def _without_workloads(entry: dict) -> dict:
    return {k: v for k, v in entry.items() if k != "workloads"}


DSV2_CELL = "deepseek-v2-ep4-d5.longdoc-closed"
DSV2_ENTRIES = [
    ("decode_step_ms.dsv2", "ms", "device_trace", "model step", "trace_module_ms"),
    ("decode_step_roofline.dsv2", "%", "device_trace", "model step", "mla_roofline"),
    ("decode_attn_latent_ms.dsv2", "ms", "device_trace", "model step", "trace_scope_ms"),
    ("mla_absorb_ms.dsv2", "ms", "device_trace", "model step", "trace_subscope_ms"),
    ("mla_proj_ms.dsv2", "ms", "device_trace", "model step", "trace_scope_ms"),
    ("latent_decode_attention_roofline.dsv2", "%", "device_trace", "kernels", "mla_roofline"),
    ("decode_moe_ms.dsv2", "ms", "device_trace", "model step", "trace_scope_ms"),
    ("moe_experts_roofline.dsv2", "%", "device_trace", "kernels", "mla_roofline"),
    ("moe_overhead_ms.dsv2", "ms", "device_trace", "model step", "trace_subscope_ms"),
    ("moe_shared_ms.dsv2", "ms", "device_trace", "model step", "trace_subscope_ms"),
    ("moe_held_share_pct.dsv2", "%", "program_counter", "model step", "prom_counter_ratio"),
    ("moe_imbalance.dsv2", "ratio", "program_counter", "engine", "prom_hist"),
    ("table_blocks_dead_pct.dsv2", "%", "program_counter", "kernels", "prom_counter_ratio"),
    ("streams_per_chunk.dsv2", "streams", "program_counter", "engine", "prom_hist"),
    ("prefill_stall_ms.dsv2", "ms/s", "program_counter", "engine", "prom_counter_rate"),
    ("device_idle_pct.dsv2", "%", "device_trace", "device", "trace_idle_pct"),
]


def test_dsv2_configuration_and_cell_are_in_the_benchmark():
    from cellbench import spec

    bench = spec.load_benchmark()
    (cfg,) = [c for c in bench["configs"] if c["name"] == "deepseek-v2-ep4-d5"]
    assert cfg["file"] == "cellbench/configs/deepseek-v2-ep4-d5.json"
    assert cfg["source"] == (
        "https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json")
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == DSV2_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "deepseek-v2-ep4-d5", "longdoc-closed", 1)
    assert len(cell["why"]) <= 200 and len(cfg["why"]) <= 200
    on = [m["name"] for m in bench["end_to_end"]
          if "workloads" not in m or DSV2_CELL in m["workloads"]]
    assert on == ["tbt_p99_ms", "setup_s"]
    # PR 32's lists keep the cells they had (this cell has an entry of its own)
    for m in bench["per_layer"]:
        if m["name"].startswith("table_blocks_dead_pct.") and not m["name"].endswith("dsv2"):
            assert DSV2_CELL not in m["workloads"]


@pytest.mark.parametrize("name,unit,source,layer,reader", DSV2_ENTRIES)
def test_dsv2_per_layer_entry_resolves(name, unit, source, layer, reader):
    from cellbench import spec

    entry = _named(spec.load_benchmark()["per_layer"], name)
    assert _without_workloads(entry) == {
        "name": name, "unit": unit,
        "better": entry["better"], "source": source, "layer": layer,
        "moves": "tbt_p99_ms"}
    assert _lists(entry, [DSV2_CELL])
    assert entry["better"] in ("lower", "higher")
    (resolved,) = [m for m in spec.resolve(DSV2_CELL).per_layer if m.name == name]
    assert resolved.reader == reader and callable(resolved.read)


def test_dsv2_held_share_reads_the_programs_counters():
    """``moe_held_share_pct.dsv2`` through the reader its entry names, off
    the families as ``/metrics`` exports them; a program without them (the
    parent) reads no value."""
    import types

    from cellbench import spec
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

    (m,) = [m for m in spec.resolve(DSV2_CELL).per_layer
            if m.name == "moe_held_share_pct.dsv2"]
    assert m.args == {"part": "moe_assignments_held",
                      "rest": ["moe_assignments_absent"]}
    assert prom_counter_ratio.read(ctx(after, base), **m.args) == 25.0
    assert prom_counter_ratio.read(ctx({}, {}), **m.args) is None


# ---------------------------------------------------------------------------
# prefill_window_ms.* (PR 34): two entries, data files only, read by the
# reader the benchmark has, off the prompt-window executable's own name


@pytest.mark.parametrize("name,cell", [
    ("prefill_window_ms.trinity", "trinity-mini-d5.longdoc-closed"),
    ("prefill_window_ms.dsv2", DSV2_CELL),
])
def test_prefill_window_ms_resolves_in_its_cell(name, cell):
    from cellbench import spec

    (entry,) = [m for m in spec.load_benchmark()["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": "ms", "better": "lower", "source": "device_trace",
        "layer": "model step", "moves": "tbt_p99_ms", "workloads": [cell]}
    (resolved,) = [m for m in spec.resolve(cell).per_layer if m.name == name]
    assert resolved.reader == "trace_module_ms" and callable(resolved.read)
    assert resolved.args == {"module": "jit_paged_prefill_chunk_fn", "per": "run"}


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


# ---------------------------------------------------------------------------
# boot_* (PR 35): the first per-layer entries that move setup_s — seven
# entries, seven data files, one reader (prom_labelled), nothing edited


BOOT_CELLS = [
    "mistral-7b-d8.decode-closed", "mistral-7b-d8.chat-open",
    "olmoe-1b-7b-d8.decode-closed", "trinity-mini-d5.longdoc-closed", DSV2_CELL,
    "nemotron3-super-ep4-d11.longdoc-closed",  # PR 40 appended its cell
    "gigachat35-ep16-d5.longdoc-closed",  # PR 47 its
    "jamba2-3b-d28.longdoc-closed"]  # and PR 51 its
BOOT_ENTRIES = [
    ("boot_imports_s", "s", "boot_phase_seconds", {"phase": "imports"}),
    ("boot_weights_s", "s", "boot_phase_seconds", {"phase": "weights"}),
    ("boot_warm_s", "s", "boot_phase_seconds", {"phase": "warm"}),
    ("boot_xla_compiled", "executables", "xla_executables_total",
     {"outcome": "compiled", "when": "boot"}),
    ("boot_xla_compile_s", "s", "xla_executable_seconds_total",
     {"outcome": "compiled", "stage": "backend", "when": "boot"}),
    ("boot_xla_load_s", "s", "xla_executable_seconds_total",
     {"outcome": "loaded", "stage": "backend", "when": "boot"}),
    ("boot_unnamed_pct", "%", "boot_phase_seconds", {"phase": "unnamed"}),
]


@pytest.mark.parametrize("name,unit,family,labels", BOOT_ENTRIES)
def test_boot_entry_resolves_in_every_cell(name, unit, family, labels):
    from cellbench import spec
    from mlmicroservicetemplate_tpu.utils import metrics

    bench = spec.load_benchmark()
    entry = _named(bench["per_layer"], name)
    assert _without_workloads(entry) == {
        "name": name, "unit": unit, "better": "lower", "source": "program_counter",
        "layer": "compile", "moves": "setup_s"}
    # every cell reports setup_s, so every cell lists the boot entries: the
    # standing eight at the head, each later PR's cell behind them
    assert _lists(entry, BOOT_CELLS)
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    for cell in entry["workloads"]:
        (resolved,) = [m for m in spec.resolve(cell).per_layer if m.name == name]
        assert resolved.reader == "prom_labelled" and callable(resolved.read)
        assert resolved.args["family"] == family
        assert resolved.args["labels"] == labels
    # the family is one the program declares, with the labels the file picks by
    declared = {getattr(getattr(metrics, a), "_name", None): getattr(metrics, a)
                for a in dir(metrics)}
    fam = declared[family[:-len("_total")] if family.endswith("_total") else family]
    assert set(labels) <= set(fam._labelnames)


def test_boot_entries_stand_together_and_are_the_only_ones_that_move_setup_s():
    from cellbench import spec

    per_layer = spec.load_benchmark()["per_layer"]
    names = [m["name"] for m in per_layer]
    first = names.index(BOOT_ENTRIES[0][0])  # by name: later PRs append after them
    assert names[first:first + 7] == [e[0] for e in BOOT_ENTRIES]
    assert [m["name"] for m in per_layer if m["moves"] == "setup_s"] == [
        e[0] for e in BOOT_ENTRIES]
    assert [m["name"] for m in per_layer if m["layer"] == "compile"] == [
        e[0] for e in BOOT_ENTRIES]


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


# ---------------------------------------------------------------------------
# prefill_windows_batched_pct.* (PR 36): how often a boundary's prompt
# windows share their dispatch — two entries, two data files, the reader
# the benchmark has (prom_counter_ratio), nothing edited


@pytest.mark.parametrize("name,cell", [
    ("prefill_windows_batched_pct.trinity", "trinity-mini-d5.longdoc-closed"),
    ("prefill_windows_batched_pct.dsv2", DSV2_CELL),
])
def test_prefill_windows_batched_pct_resolves_in_its_cell(name, cell):
    from cellbench import spec

    per_layer = spec.load_benchmark()["per_layer"]
    (entry,) = [m for m in per_layer if m["name"] == name]
    assert entry == {
        "name": name, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "engine", "moves": "tbt_p99_ms", "workloads": [cell]}
    # appended by PR 36 behind the boot entries, the two side by side (by
    # NAME: every later PR's entries follow them)
    names = [m["name"] for m in per_layer]
    assert names.index(name) > names.index("boot_unnamed_pct")
    assert abs(names.index("prefill_windows_batched_pct.trinity")
               - names.index("prefill_windows_batched_pct.dsv2")) == 1
    resolved = spec.resolve(cell)
    (metric,) = [m for m in resolved.per_layer if m.name == name]
    assert metric.reader == "prom_counter_ratio" and callable(metric.read)
    assert metric.args == {
        "part": "prefill_windows_batched", "rest": ["prefill_windows_alone"]}
    assert "tbt_p99_ms" in [m.name for m in resolved.end_to_end]


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


# ---------------------------------------------------------------------------
# nemotron3-super-ep4-d11 (PR 40): the configuration, the cell and its
# twenty-four per-layer entries, held by NAME

NEMO_CELL = "nemotron3-super-ep4-d11.longdoc-closed"
_T, _C = "device_trace", "program_counter"
NEMO_ENTRIES = [
    ("decode_ssm_ms", "ms", _T, "model step", "trace_subscope_ms"),
    ("ssm_step_roofline", "%", _T, "kernels", "nemotron_roofline"),
    ("ssm_proj_ms", "ms", _T, "model step", "trace_subscope_ms"),
    ("prefill_ssm_scan_ms", "ms", _T, "model step", "nemotron_roofline"),
    ("ssm_scan_roofline", "%", _T, "kernels", "nemotron_roofline"),
    ("moe_latent_ms", "ms", _T, "model step", "trace_subscope_ms"),
    ("ssm_state_share_pct", "%", _C, "engine", "nemotron_roofline"),
    ("ssm_scan_masked_pct", "%", _C, "engine", "nemotron_roofline"),
    ("decode_step_ms", "ms", _T, "model step", "trace_module_ms"),
    ("decode_step_roofline", "%", _T, "model step", "nemotron_roofline"),
    ("decode_moe_ms", "ms", _T, "model step", "trace_scope_ms"),
    ("moe_experts_roofline", "%", _T, "kernels", "nemotron_roofline"),
    ("moe_overhead_ms", "ms", _T, "model step", "trace_subscope_ms"),
    ("moe_shared_ms", "ms", _T, "model step", "trace_subscope_ms"),
    ("moe_held_share_pct", "%", _C, "model step", "prom_counter_ratio"),
    ("moe_imbalance", "ratio", _C, "engine", "prom_hist"),
    ("decode_attn_ms", "ms", _T, "model step", "trace_scope_ms"),
    ("paged_decode_attention_roofline", "%", _T, "kernels", "nemotron_roofline"),
    ("table_blocks_dead_pct", "%", _C, "kernels", "prom_counter_ratio"),
    ("streams_per_chunk", "streams", _C, "engine", "prom_hist"),
    ("prefill_stall_ms", "ms/s", _C, "engine", "prom_counter_rate"),
    ("prefill_window_ms", "ms", _T, "model step", "trace_module_ms"),
    ("prefill_windows_batched_pct", "%", _C, "engine", "prom_counter_ratio"),
    ("device_idle_pct", "%", _T, "device", "trace_idle_pct"),
]


def test_nemotron_configuration_and_cell_are_in_the_benchmark():
    from cellbench import spec

    bench = spec.load_benchmark()
    cfg = _named(bench["configs"], "nemotron3-super-ep4-d11")  # by NAME
    assert cfg["file"] == "cellbench/configs/nemotron3-super-ep4-d11.json"
    assert cfg["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
        "/blob/main/config.json")
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    cell = _named(bench["workloads"], NEMO_CELL)
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        NEMO_CELL, "nemotron3-super-ep4-d11", "longdoc-closed", 1)
    assert len(cell["why"]) <= 200 and len(cfg["why"]) <= 200
    on = [m["name"] for m in bench["end_to_end"]
          if "workloads" not in m or NEMO_CELL in m["workloads"]]
    assert on == ["tbt_p99_ms", "setup_s"]
    boots = [m for m in bench["per_layer"] if m["name"].startswith("boot_")]
    assert len(boots) == 7 and all(NEMO_CELL in m["workloads"] for m in boots)
    file = spec.load_json(spec.REPO + "/" + cfg["file"])
    assert set(file["reduced"]) == set(cfg["reduced"])
    assert (file["num_hidden_layers"], file["n_routed_experts"],
            file["vocab_size"]) == (11, 128, 32768)
    assert file["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    assert "deployment" in file["assumed"] and "mtp" in file["assumed"]


@pytest.mark.parametrize("name,unit,source,layer,reader", NEMO_ENTRIES)
def test_nemotron_per_layer_entry_resolves(name, unit, source, layer, reader):
    from cellbench import spec

    name += ".nemotron"
    entry = _named(spec.load_benchmark()["per_layer"], name)
    assert _without_workloads(entry) == {
        "name": name, "unit": unit,
        "better": entry["better"], "source": source, "layer": layer,
        "moves": "tbt_p99_ms"}
    assert _lists(entry, [NEMO_CELL] + [JAMBA_CELL] * (name in JAMBA_SHARED))
    assert entry["better"] in ("lower", "higher")
    (resolved,) = [m for m in spec.resolve(NEMO_CELL).per_layer if m.name == name]
    assert resolved.reader == reader and callable(resolved.read)


def test_nemotron_counter_readings_read_the_programs_families():
    """``ssm_scan_masked_pct`` and ``ssm_state_share_pct`` through the
    reader their entries name, off the families as ``/metrics`` exports
    them; a program without them (the parent) reads no value and does not
    raise."""
    import types

    from cellbench import spec
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

    by_name = {m.name: m for m in spec.resolve(NEMO_CELL).per_layer}
    masked = by_name["ssm_scan_masked_pct.nemotron"]
    assert masked.read(ctx(after, base), **masked.args) == 25.0
    share = by_name["ssm_state_share_pct.nemotron"]
    gauges = {"ssm_state_bytes": {"value": 3.0e8}, "kv_committed_bytes": {"value": 1.0e8}}
    assert share.read(ctx(gauges, {}), **share.args) == 75.0
    for m in by_name.values():
        if m.reader == "nemotron_roofline":
            assert m.read(ctx({}, {}), **m.args) is None


# ---------------------------------------------------------------------------
# insert_rows_per_dispatch.* (PR 41): how many rows a paged insert dispatch
# lands — two entries, two data files, the reader the benchmark has
# (prom_hist), nothing edited


@pytest.mark.parametrize("name,cell", [
    ("insert_rows_per_dispatch.decode", "mistral-7b-d8.decode-closed"),
    ("insert_rows_per_dispatch.olmoe", "olmoe-1b-7b-d8.decode-closed"),
])
def test_insert_rows_per_dispatch_resolves_in_its_cell(name, cell):
    from cellbench import spec

    per_layer = spec.load_benchmark()["per_layer"]
    (entry,) = [m for m in per_layer if m["name"] == name]
    assert entry == {
        "name": name, "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "engine", "moves": "tokens_per_s",
        "workloads": [cell]}
    # appended behind PR 40's entries, the two side by side (by NAME: every
    # later PR's entries follow them)
    names = [m["name"] for m in per_layer]
    assert names.index(name) > names.index("device_idle_pct.nemotron")
    assert abs(names.index("insert_rows_per_dispatch.decode")
               - names.index("insert_rows_per_dispatch.olmoe")) == 1
    resolved = spec.resolve(cell)
    (metric,) = [m for m in resolved.per_layer if m.name == name]
    assert metric.reader == "prom_hist" and callable(metric.read)
    assert metric.args == {"family": "stream_insert_rows", "stat": "mean"}
    assert "tokens_per_s" in [m.name for m in resolved.end_to_end]
    others = [w["name"] for w in spec.load_benchmark()["workloads"] if w["name"] != cell]
    for other in others:  # read in its own cell only
        assert name not in [m.name for m in spec.resolve(other).per_layer]


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


# ---------------------------------------------------------------------------
# PR 47: GigaChat3.5 — one configuration, one cell, twenty-nine entries, a
# cost file and one reader, every accepted file as it was

GIGA_CELL = "gigachat35-ep16-d5.longdoc-closed"
GIGA_ENTRIES = [
    ("decode_step_ms", "ms", _T, "model step", "trace_module_ms"),
    ("decode_step_roofline", "%", _T, "model step", "gigachat_roofline"),
    ("decode_gdn_ms", "ms", _T, "model step", "trace_subscope_ms"),
    ("gdn_step_roofline", "%", _T, "kernels", "gigachat_roofline"),
    ("gdn_proj_ms", "ms", _T, "model step", "trace_subscope_ms"),
    ("prefill_gdn_scan_ms", "ms", _T, "model step", "gigachat_roofline"),
    ("gdn_scan_roofline", "%", _T, "kernels", "gigachat_roofline"),
    ("gdn_state_share_pct", "%", _C, "engine", "gigachat_roofline"),
    ("gdn_scan_masked_pct", "%", _C, "engine", "gigachat_roofline"),
    ("decode_attn_latent_ms", "ms", _T, "model step", "trace_scope_ms"),
    ("latent_decode_attention_roofline", "%", _T, "kernels", "gigachat_roofline"),
    ("decode_moe_ms", "ms", _T, "model step", "trace_scope_ms"),
    ("moe_experts_roofline", "%", _T, "kernels", "gigachat_roofline"),
    ("moe_held_share_pct", "%", _C, "model step", "prom_counter_ratio"),
    ("moe_imbalance", "ratio", _C, "engine", "prom_hist"),
    ("streams_per_chunk", "streams", _C, "engine", "prom_hist"),
    ("prefill_stall_ms", "ms/s", _C, "engine", "prom_counter_rate"),
    ("prefill_window_ms", "ms", _T, "model step", "trace_module_ms"),
    ("prefill_windows_batched_pct", "%", _C, "engine", "prom_counter_ratio"),
    ("device_idle_pct", "%", _T, "device", "trace_idle_pct"),
    # the accepted twins of the latent layer, the expert overhead and the table
    ("mla_absorb_ms", "ms", _T, "model step", "trace_subscope_ms"),
    ("mla_proj_ms", "ms", _T, "model step", "trace_scope_ms"),
    ("moe_overhead_ms", "ms", _T, "model step", "trace_subscope_ms"),
    ("moe_shared_ms", "ms", _T, "model step", "trace_subscope_ms"),
    ("table_blocks_dead_pct", "%", _C, "kernels", "prom_counter_ratio"),
    # a prompt-window dispatch, part by part
    ("prefill_gdn_proj_ms", "ms", _T, "model step", "gigachat_roofline"),
    ("prefill_attn_latent_ms", "ms", _T, "model step", "gigachat_roofline"),
    ("prefill_mlp_ms", "ms", _T, "model step", "gigachat_roofline"),
    ("prefill_moe_experts_ms", "ms", _T, "model step", "gigachat_roofline"),
]


def test_gigachat_configuration_and_cell_are_in_the_benchmark():
    from cellbench import spec

    bench = spec.load_benchmark()
    cfg = _named(bench["configs"], "gigachat35-ep16-d5")  # by NAME
    cell = _named(bench["workloads"], GIGA_CELL)
    assert cfg["file"] == "cellbench/configs/gigachat35-ep16-d5.json"
    assert cfg["source"] == (
        "https://huggingface.co/ai-sage/GigaChat3.5-432B-A28B/blob/main/config.json")
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "first_k_dense_replace", "vocab_size"]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        GIGA_CELL, "gigachat35-ep16-d5", "longdoc-closed", 1)
    assert len(cell["why"]) <= 200 and len(cfg["why"]) <= 200
    on = [m["name"] for m in bench["end_to_end"]
          if "workloads" not in m or GIGA_CELL in m["workloads"]]
    assert on == ["tbt_p99_ms", "setup_s"]
    boots = [m for m in bench["per_layer"] if m["name"].startswith("boot_")]
    assert len(boots) == 7 and all(GIGA_CELL in m["workloads"] for m in boots)
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(GIGA_ENTRIES[0][0] + ".gigachat")  # the 29 stand together
    assert names[first:first + len(GIGA_ENTRIES)] == [
        n + ".gigachat" for n, *_ in GIGA_ENTRIES]
    assert len(bench["per_layer"]) <= 128  # the file's limit
    file = spec.load_json(spec.REPO + "/" + cfg["file"])
    assert list(file["reduced"]) == cfg["reduced"]
    assert (file["num_hidden_layers"], file["n_routed_experts"],
            file["first_k_dense_replace"], file["vocab_size"]) == (5, 16, 1, 16032)
    assert file["layer_types"] == ["linear"] * 4 + ["full"]
    assert "deployment" in file["assumed"] and "mtp" in file["assumed"]
    # the traffic file is the three other long-document cells', unchanged
    assert spec.resolve(GIGA_CELL).traffic == spec.resolve(NEMO_CELL).traffic


@pytest.mark.parametrize("name,unit,source,layer,reader", GIGA_ENTRIES)
def test_gigachat_per_layer_entry_resolves(name, unit, source, layer, reader):
    from cellbench import spec

    name += ".gigachat"
    entry = _named(spec.load_benchmark()["per_layer"], name)
    assert _without_workloads(entry) == {
        "name": name, "unit": unit,
        "better": entry["better"], "source": source, "layer": layer,
        "moves": "tbt_p99_ms"}
    assert _lists(entry, [GIGA_CELL] + [JAMBA_CELL] * (name in JAMBA_SHARED))
    assert entry["better"] in ("lower", "higher")
    (resolved,) = [m for m in spec.resolve(GIGA_CELL).per_layer if m.name == name]
    assert resolved.reader == reader and callable(resolved.read)
    for other in spec.load_benchmark()["workloads"]:  # read in the cells it lists only
        if other["name"] not in entry["workloads"]:
            assert name not in [m.name for m in spec.resolve(other["name"]).per_layer]


# ---------------------------------------------------------------------------
# jamba2-3b-d28 (PR 51): one configuration, one cell, ONE new per-layer entry
# (the file's limit is 128 entries and 127 stood) and the cell's name appended
# to the sibling entries whose readers read the same scopes and counters


JAMBA_CELL = "jamba2-3b-d28.longdoc-closed"
#: The accepted entries the cell is appended to (their readers are generic:
#: the ``ssm`` / ``ssm_scan`` scopes, the shared ``ssm_*`` families, the
#: loop's counters, the window executable by name).
JAMBA_SHARED = [
    "decode_ssm_ms.nemotron", "ssm_proj_ms.nemotron", "prefill_ssm_scan_ms.nemotron",
    "ssm_scan_masked_pct.nemotron",
    "decode_step_ms.nemotron", "decode_attn_ms.nemotron",
    "table_blocks_dead_pct.nemotron", "streams_per_chunk.nemotron",
    "prefill_stall_ms.nemotron", "prefill_window_ms.nemotron",
    "prefill_windows_batched_pct.nemotron", "device_idle_pct.nemotron",
    "prefill_mlp_ms.gigachat"]


def test_jamba_configuration_and_cell_are_in_the_benchmark():
    from cellbench import spec

    bench = spec.load_benchmark()
    cfg = _named(bench["configs"], "jamba2-3b-d28")  # by NAME
    cell = _named(bench["workloads"], JAMBA_CELL)
    assert (cfg["name"], cfg["file"], cfg["reduced"]) == (
        "jamba2-3b-d28", "cellbench/configs/jamba2-3b-d28.json", [])
    assert cfg["source"] == (
        "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json")
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        JAMBA_CELL, "jamba2-3b-d28", "longdoc-closed", 1)
    assert len(cell["why"]) <= 200 and len(cfg["why"]) <= 200
    on = [m["name"] for m in bench["end_to_end"]
          if "workloads" not in m or JAMBA_CELL in m["workloads"]]
    assert on == ["tbt_p99_ms", "setup_s"]
    boots = [m for m in bench["per_layer"] if m["name"].startswith("boot_")]
    assert len(boots) == 7 and all(JAMBA_CELL in m["workloads"] for m in boots)
    assert len(bench["configs"]) >= 7 and len(bench["workloads"]) >= 8
    assert len(bench["per_layer"]) <= 128  # the file's limit
    file = spec.load_json(spec.REPO + "/" + cfg["file"])
    assert file["reduced"] == {} and file["num_hidden_layers"] == 28
    assert file["tie_word_embeddings"] is True and file["num_key_value_heads"] == 1
    assert spec.resolve(JAMBA_CELL).traffic == spec.resolve(NEMO_CELL).traffic


def test_jamba_per_layer_entries_resolve():
    """PR 51's one new entry, by NAME; every sibling entry the cell was
    appended to lists it, moves the end-to-end metric the cell reports and
    resolves to a reader; no other cell reads the new entry."""
    from cellbench import spec

    per_layer = spec.load_benchmark()["per_layer"]
    assert _named(per_layer, "ssm_scan_roofline.jamba2") == {
        "name": "ssm_scan_roofline.jamba2", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels", "moves": "tbt_p99_ms",
        "workloads": [JAMBA_CELL]}
    mine = {m.name: m for m in spec.resolve(JAMBA_CELL).per_layer}
    assert mine["ssm_scan_roofline.jamba2"].reader == "jamba_roofline"
    assert mine["ssm_scan_roofline.jamba2"].args == {"what": "ssm_scan"}
    listed = [m["name"] for m in per_layer if JAMBA_CELL in m.get("workloads", [])]
    # PR 51's, and the three PR 55 landed for the cell (the step's and the
    # attention kernel's shares its reader already computed, the loop's
    # unnamed share); a later PR may list the cell elsewhere too
    assert set(listed) >= {
        *JAMBA_SHARED, "ssm_scan_roofline.jamba2", "decode_step_roofline.jamba2",
        "paged_decode_attention_roofline.jamba2", "loop_unnamed_pct.serve",
        *(m["name"] for m in per_layer if m["name"].startswith("boot_"))}
    for m in per_layer:
        if m["name"] in JAMBA_SHARED:
            assert JAMBA_CELL in m["workloads"] and m["moves"] == "tbt_p99_ms"
            assert callable(mine[m["name"]].read)
    for other in spec.load_benchmark()["workloads"]:
        if other["name"] != JAMBA_CELL:
            assert "ssm_scan_roofline.jamba2" not in [
                m.name for m in spec.resolve(other["name"]).per_layer]


# ---------------------------------------------------------------------------
# granite-4.0-h-small-ep2-d10 (PR 56): one configuration, one cell, FIVE new
# per-layer entries (each a new definition: the file stands at its limit of
# 128) and the cell's name appended to the thirty standing entries whose
# definitions read its scopes and counters — all held by NAME


GRANITE_CELL = "granite-4.0-h-small-ep2-d10.longdoc-closed"
GRANITE_ENTRIES = [
    ("decode_step_roofline.granite", "model step", "step"),
    ("moe_experts_roofline.granite", "kernels", "experts"),
    ("ssm_scan_roofline.granite", "kernels", "ssm_scan"),
    ("ssm_step_roofline.granite", "kernels", "ssm_step"),
    ("paged_decode_attention_roofline.granite", "kernels", "attention"),
]
#: The standing entries the cell is appended to (one entry a definition, PR
#: 55): the loop's and the process's counters, the long-document cells'
#: spans, Nemotron's readings of the Mamba-2 scopes, the expert block's.
GRANITE_SHARED = [
    "loop_unnamed_pct.serve", "event_loop_lag_p99_ms",
    "device_idle_pct.nemotron", "streams_per_chunk.nemotron",
    "prefill_stall_ms.nemotron", "table_blocks_dead_pct.nemotron",
    "prefill_window_ms.nemotron", "prefill_windows_batched_pct.nemotron",
    "decode_step_ms.nemotron", "decode_ssm_ms.nemotron", "ssm_proj_ms.nemotron",
    "decode_attn_ms.nemotron", "decode_moe_ms.nemotron", "moe_overhead_ms.nemotron",
    "moe_shared_ms.nemotron", "moe_imbalance.nemotron", "moe_held_share_pct.nemotron",
    "moe_rows_skipped_pct.dsv2", "prefill_ssm_scan_ms.nemotron",
    "ssm_scan_masked_pct.nemotron", "ssm_state_share_pct.nemotron",
    "prefill_mlp_ms.gigachat", "prefill_moe_experts_ms.gigachat"]


def test_granite_configuration_and_cell_are_in_the_benchmark():
    from cellbench import spec

    bench = spec.load_benchmark()
    cfg = _named(bench["configs"], "granite-4.0-h-small-ep2-d10")
    cell = _named(bench["workloads"], GRANITE_CELL)
    assert cfg["file"] == "cellbench/configs/granite-4.0-h-small-ep2-d10.json"
    assert cfg["source"] == (
        "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json")
    assert cfg["reduced"] == ["num_hidden_layers", "num_local_experts", "vocab_size"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite-4.0-h-small-ep2-d10", "longdoc-closed", 1)
    assert len(cell["why"]) <= 200 and len(cfg["why"]) <= 200
    on = [m["name"] for m in bench["end_to_end"]
          if "workloads" not in m or GRANITE_CELL in m["workloads"]]
    assert on == ["tbt_p99_ms", "setup_s"]
    boots = [m for m in bench["per_layer"] if m["name"].startswith("boot_")]
    assert len(boots) == 7 and all(GRANITE_CELL in m["workloads"] for m in boots)
    assert len(bench["per_layer"]) <= 128  # the file's limit
    file = spec.load_json(spec.REPO + "/" + cfg["file"])
    assert list(file["reduced"]) == cfg["reduced"]
    assert (file["num_hidden_layers"], file["num_local_experts"], file["router_experts"],
            file["vocab_size"]) == (10, 36, 72, 50176)
    assert file["layer_types"][:10] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert file["llama_layer_types"][:10] == ["mamba2"] * 5 + ["attention"] + ["mamba2"] * 4
    assert "deployment" in file["assumed"] and "router" in file["assumed"]
    # the traffic file is the other long-document cells', unchanged
    assert spec.resolve(GRANITE_CELL).traffic == spec.resolve(NEMO_CELL).traffic


@pytest.mark.parametrize("name,layer,what", GRANITE_ENTRIES)
def test_granite_per_layer_entry_resolves(name, layer, what):
    from cellbench import spec

    bench = spec.load_benchmark()
    assert _named(bench["per_layer"], name) == {
        "name": name, "unit": "%", "better": "higher", "source": "device_trace",
        "layer": layer, "moves": "tbt_p99_ms", "workloads": [GRANITE_CELL]}
    (resolved,) = [m for m in spec.resolve(GRANITE_CELL).per_layer if m.name == name]
    assert resolved.reader == "granite_roofline" and resolved.args == {"what": what}
    for other in bench["workloads"]:  # read in its own cell only
        if other["name"] != GRANITE_CELL:
            assert name not in [m.name for m in spec.resolve(other["name"]).per_layer]


@pytest.mark.parametrize("name", GRANITE_SHARED)
def test_granite_is_appended_to_the_entry_that_has_its_definition(name):
    """The standing entry lists the cell BEHIND the cells it had, moves the
    end-to-end metric the cell reports and resolves there to the reader and
    arguments it resolves to in its first cell: one definition, read twice."""
    from cellbench import spec

    entry = _named(spec.load_benchmark()["per_layer"], name)
    assert entry["workloads"][-1] == GRANITE_CELL and len(entry["workloads"]) >= 2
    assert entry["moves"] == "tbt_p99_ms"
    (mine,) = [m for m in spec.resolve(GRANITE_CELL).per_layer if m.name == name]
    (first,) = [m for m in spec.resolve(entry["workloads"][0]).per_layer if m.name == name]
    assert (mine.reader, mine.args) == (first.reader, first.args) and callable(mine.read)


def test_granite_rooflines_read_nothing_from_a_program_without_the_scopes():
    """The five shares through the reader their entries name: untraced, or on
    a trace without the executable (the parent), no value and no raise."""
    import types

    from cellbench import spec

    cell = spec.resolve(GRANITE_CELL)
    own = [m for m in cell.per_layer if m.reader == "granite_roofline"]
    assert len(own) == 5
    empty = types.SimpleNamespace(module_time=lambda m: (0.0, 0), ops={})
    for trace in (None, empty):
        ctx = types.SimpleNamespace(
            trace=trace, peaks={"hbm_bytes_per_s": 8.19e11, "bf16_flops_per_s": 1.97e14},
            prom_after={}, prom_before={}, notes={}, config=cell.config,
            engine={"chunk_tokens": 4}, prom_delta=lambda family: None)
        assert [m.read(ctx, **m.args) for m in own] == [None] * 5
