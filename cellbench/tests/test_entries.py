"""``BENCHMARK.json``'s per-layer entries, held by (cell, entry) PAIR and
by what a cell READS — never by an entry's suffix or its place in the list
(PR 55): a new cell appends its name to the entry that has its definition,
a new entry needs a new definition."""

import functools
import json
import os

import pytest

from cellbench import spec

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
ENTRY = {m["name"]: m for m in BENCH["per_layer"]}
PAIRS = [(cell, name) for name, m in ENTRY.items()
         for cell in m.get("workloads", CELLS)]
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


@functools.lru_cache(maxsize=None)
def resolved(cell: str) -> spec.Cell:
    return spec.resolve(cell)


def data_file(name: str) -> dict:
    return spec.load_json(os.path.join(spec.HERE, "layer_metrics", name + ".json"))


def definition(m: dict) -> tuple:
    """What makes two entries ONE: every field but ``name`` and
    ``workloads``, and the data file's reader and arguments (its ``note``
    is no part of it)."""
    d = data_file(m["name"])
    return (m["unit"], m["better"], m["source"], m["layer"], m["moves"],
            d["reader"], json.dumps(d.get("args", {}), sort_keys=True))


def test_the_pairs_are_counted():
    # 192 on PR 54's files; PR 55 landed eight entries on 20 pairs
    assert len(PAIRS) == len(set(PAIRS)) >= 212


@pytest.mark.parametrize("cell,name", PAIRS)
def test_pair_resolves(cell, name):
    """The entry resolves in the cell to a callable reader, moves an
    end-to-end metric the cell reports, and is read by no cell it does not
    list."""
    entry = ENTRY[name]
    (metric,) = [m for m in resolved(cell).per_layer if m.name == name]
    assert callable(metric.read) and metric.unit == entry["unit"]
    assert entry["source"] in SOURCES and entry["better"] in ("lower", "higher")
    assert entry["moves"] in [m.name for m in resolved(cell).end_to_end]
    for other in CELLS:
        if other not in entry.get("workloads", CELLS):
            assert name not in [m.name for m in resolved(other).per_layer]


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_keeps_every_reading_it_had(cell):
    """``parent_cell_readings.json`` was written from PR 54's files: each
    cell's (reader, sorted arguments, unit, moves).  Whatever the entries
    are called now, the cell still resolves every one of them."""
    had = spec.load_json(os.path.join(HERE, "parent_cell_readings.json"))["cells"]
    now = {(m.reader, json.dumps(m.args, sort_keys=True), m.unit, ENTRY[m.name]["moves"])
           for m in resolved(cell).per_layer}
    assert not [row for row in had[cell] if tuple(row) not in now]


def test_one_entry_a_definition():
    """No two entries agree in every field but ``name`` and ``workloads``
    — but for the twins ``twins_waiting.json`` lists, which tier-1's
    ``tests/test_bench_helpers.py`` still holds by name (a ``benchmark``
    PR may not edit it, PR 55).  That list may only shrink; a new twin is
    refused here."""
    waiting = spec.load_json(os.path.join(HERE, "twins_waiting.json"))["groups"]
    allowed = {frozenset(g) for g in waiting}
    groups: dict = {}
    for name, m in ENTRY.items():
        groups.setdefault(definition(m), []).append(name)
    twins = [names for names in groups.values() if len(names) > 1]
    for names in twins:
        assert any(set(names) <= g for g in allowed), names
    assert len(BENCH["per_layer"]) <= 128  # the file's limit
    assert sum(len(n) - 1 for n in twins) <= 39  # PR 55 left 39 of 52


def test_every_data_file_has_an_entry_or_waits():
    names = set(ENTRY)
    files = {f[:-len(".json")] for f in os.listdir(os.path.join(spec.HERE, "layer_metrics"))}
    assert names <= files
    # PERF.md section 7 lists these as waiting (cellbench/sweep.py reads them)
    assert {f for f in files - names if "predict" not in f} == set()
