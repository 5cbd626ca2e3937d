"""cellbench: the repository's on-chip benchmark (see BENCHMARK.json, PERF.md).

One command runs one cell once in a new process::

    python3 -m cellbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
metric is a file of its own that the harness finds by the name in
``BENCHMARK.json``: ``configs/<config>.json``, ``traffic/<mix>.json``,
``end_to_end/<metric>.json`` and ``layer_metrics/<metric>.json`` (each
naming a reader in ``readers/``), ``references/<family>.py``.  Adding a
cell, a configuration, a mix or a metric adds files and one entry in
``BENCHMARK.json``; it edits no file that is there.
"""
