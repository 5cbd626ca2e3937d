"""cellbench's own tests: ``python -m pytest cellbench/tests`` (CPU).
Not part of tier-1; they describe no TPU topology."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def root_with_bert(tmp_path) -> str:
    """A benchmark root whose BENCHMARK.json also lists the BERT cell:
    the installed cellbench/ (a link) plus the entries kept in
    bert_cell_entries.json."""
    import json

    from cellbench import spec

    bench = spec.load_benchmark()
    with open(os.path.join(os.path.dirname(__file__),
                           "bert_cell_entries.json"), encoding="utf-8") as f:
        extra = json.load(f)
    for key, entries in extra.items():
        bench[key] += entries
    os.symlink(spec.HERE, tmp_path / "cellbench")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def entry_reading(cell: str, reader: str, **args):
    """The ONE per-layer metric of ``cell`` that reads ``reader`` with
    ``args`` among its arguments: an entry is found by what it reads, so
    a definition that moves under another name (one entry a definition,
    PR 55) breaks no test."""
    from cellbench import spec

    hit = [m for m in spec.resolve(cell).per_layer if m.reader == reader
           and all(m.args.get(k) == v for k, v in args.items())]
    assert len(hit) == 1, (cell, reader, args, [m.name for m in hit])
    return hit[0]
