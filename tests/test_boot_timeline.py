"""Set-up reports itself (ISSUE 35): the boot table of
``utils/tracing.boot_phase`` and the per-executable records of
``runtime/compile_cache`` — a load from the persistent cache told apart
from a compile, names kept, ``CompileWindow`` counting as it always
did, an after-ready compile left in the flight recorder, and the
Prometheus families the benchmark's ``prom_labelled`` reader reads."""

import json
import logging
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from mlmicroservicetemplate_tpu.runtime import compile_cache as cc
from mlmicroservicetemplate_tpu.utils import metrics, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def boot(monkeypatch):
    """A boot table of the test's own: whatever it opens, closes or
    marks ready never reaches another test of this worker."""
    table = tracing.BootTable()
    monkeypatch.setattr(tracing, "_BOOT", table)
    return table


def _fresh(tag: str):
    """A jitted function no other test has compiled (the name is the
    record's name, the constant keeps the cache key apart)."""
    salt = float(time.monotonic_ns() % 100003)

    def fn(x):
        return jnp.sin(x) * salt

    fn.__name__ = fn.__qualname__ = f"boot_timeline_{tag}"
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# the boot table


def test_boot_rows_nest_and_top_level_plus_unnamed_is_total(boot):
    boot.begin(time.monotonic())
    with tracing.boot_phase("boot/config"):
        time.sleep(0.01)
    time.sleep(0.02)  # under no phase: unnamed
    with tracing.boot_phase("boot/warm/loop") as outer:
        parent = tracing.boot_current()
        assert parent == "boot/warm/loop"

        def cell(i):
            with tracing.boot_phase("boot/warm/loop/grid", parent, rung=i):
                time.sleep(0.01)

        threads = [threading.Thread(target=cell, args=(i,)) for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10.0)
            assert not t.is_alive()
        with tracing.boot_phase("boot/warm/loop/chunk", sampled=False):
            time.sleep(0.005)
    assert outer.seconds >= 0.015
    boot.ready()
    snap = boot.snapshot()
    rows = snap["rows"]
    assert [r["name"] for r in rows if r["parent"] is None] == [
        "boot/config", "boot/warm/loop", "boot/ready"]
    kids = [r for r in rows if r["parent"] == "boot/warm/loop"]
    assert sorted(r["name"] for r in kids) == [
        "boot/warm/loop/chunk"] + ["boot/warm/loop/grid"] * 3
    assert sorted(r["args"]["rung"] for r in kids if "rung" in r["args"]) == [0, 1, 2]
    assert len({r["thread"] for r in kids}) == 4  # three workers + this thread
    (loop,) = [r for r in rows if r["name"] == "boot/warm/loop"]
    for r in kids:  # a child lies inside its parent
        assert r["start"] >= loop["start"] - 1e-3
        assert r["start"] + r["seconds"] <= loop["start"] + loop["seconds"] + 1e-3
    assert snap["ready"] and snap["unnamed_s"] >= 0.02
    assert sum(snap["phases"].values()) + snap["unnamed_s"] == pytest.approx(
        snap["total_s"], abs=2e-3)
    if sys.platform.startswith("linux"):
        assert snap["pre_build_s"] > 0.0  # from /proc/self/stat
    # closed: a later phase times itself and leaves no row
    with tracing.boot_phase("boot/late") as late:
        pass
    assert late.seconds >= 0.0
    assert "boot/late" not in [r["name"] for r in boot.snapshot()["rows"]]


def test_boot_table_is_bounded_and_overlap_is_covered_once(boot):
    t0 = time.monotonic()
    boot.begin(t0)
    boot.add("boot/a", t0, 2.0)
    boot.add("boot/b", t0 + 1.0, 2.0)  # another thread, overlapping a
    for i in range(tracing.BootTable.MAX_ROWS + 5):
        boot.add("boot/a/child", t0, 0.0, "boot/a", i=i)
    boot.t_ready = t0 + 4.0
    snap = boot.snapshot()
    assert len(snap["rows"]) == tracing.BootTable.MAX_ROWS
    assert snap["rows_dropped"] == 7
    assert snap["total_s"] == 4.0 and snap["unnamed_s"] == pytest.approx(1.0)


def test_boot_phase_with_trace_off_builds_no_span(boot, monkeypatch):
    tracing.configure(False)
    created = []
    orig = tracing.Span.__init__

    def spy(self, *a, **kw):
        created.append(self)
        orig(self, *a, **kw)

    monkeypatch.setattr(tracing.Span, "__init__", spy)
    with tracing.boot_phase("boot/weights", parameters=1) as ph:
        ph.set(bytes=4)
    assert created == []
    (row,) = boot.snapshot()["rows"]
    assert row["args"] == {"parameters": 1, "bytes": 4}


def test_boot_phase_is_a_ring_span_under_trace(boot):
    tr = tracing.configure(True, 64)
    try:
        with tracing.boot_phase("boot/device"):
            pass
        assert [sp.name for sp in tr.snapshot()] == ["boot/device"]
    finally:
        tracing.configure(False)


# ---------------------------------------------------------------------------
# one record per executable


def test_compile_window_counts_what_the_backend_event_counts(boot):
    """``compiles`` / ``seconds`` stay the count and the seconds of
    ``backend_compile_duration`` events (what cellbench exits 4 on);
    ``compiled`` + ``loaded`` split it and ``names`` names it."""
    seen = []

    def listener(name, dur, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            seen.append((kw.get("fun_name"), dur))

    jax.monitoring.register_event_duration_secs_listener(listener)
    f, g = _fresh("win_f"), _fresh("win_g")
    try:
        with cc.CompileWindow() as w:
            f(jnp.ones(7)).block_until_ready()
            g(jnp.ones(9)).block_until_ready()
            f(jnp.ones(7)).block_until_ready()  # jit's own cache: no event
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert w.compiles == len(seen) >= 2
    assert w.seconds == pytest.approx(sum(d for _, d in seen))
    assert w.compiled + w.loaded == w.compiles
    assert w.names == [n for n, _ in seen]
    assert "jit(boot_timeline_win_f)" in w.names
    assert "jit(boot_timeline_win_g)" in w.names
    rec = [r for r in cc.executable_records()
           if r["name"] == "jit(boot_timeline_win_g)"][-1]
    assert rec["outcome"] == "compiled" and not rec["after_ready"]
    assert rec["trace_s"] > 0.0 and rec["lower_s"] > 0.0 and rec["backend_s"] > 0.0
    assert rec["thread"] == threading.current_thread().name
    with cc.CompileWindow() as again:
        g(jnp.ones(9)).block_until_ready()
    assert (again.compiles, again.names) == (0, [])


_CHILD = """
import json, sys
from mlmicroservicetemplate_tpu.runtime.device import apply_device_env
apply_device_env("cpu", sys.argv[1])
import jax, jax.numpy as jnp
from mlmicroservicetemplate_tpu.runtime import compile_cache as cc
from mlmicroservicetemplate_tpu.utils import tracing

def told_apart(x):
    return jnp.tanh(x) @ x

with tracing.boot_phase("boot/warm/loop"):
    with cc.CompileWindow() as w:
        jax.jit(told_apart)(jnp.ones((16, 16))).block_until_ready()
rec = [r for r in cc.executable_records() if r["name"] == "jit(told_apart)"]
print(json.dumps({"records": rec, "compiles": w.compiles, "compiled": w.compiled,
                  "loaded": w.loaded, "names": w.names,
                  "status": cc.boot_status()}))
"""


def test_a_load_and_a_compile_are_told_apart(tmp_path):
    """Two processes over one ``COMPILE_CACHE_DIR``: the first compiles
    ``jit(told_apart)`` and writes it, the second loads it.  JAX
    announces the hit on the same thread before the enclosing
    ``backend_compile_duration`` — the order the record rests on,
    pinned here on the installed JAX."""
    cache = str(tmp_path / "xla")
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "COMPILE_CACHE_DIR")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    outs = []
    for _ in range(2):
        p = subprocess.run([sys.executable, "-c", _CHILD, cache], env=env,
                           capture_output=True, text=True, timeout=240)
        assert p.returncode == 0, p.stderr[-2000:]
        outs.append(json.loads(p.stdout.strip().splitlines()[-1]))
    first, second = outs
    (a,), (b,) = first["records"], second["records"]
    assert (a["outcome"], b["outcome"]) == ("compiled", "loaded")
    assert a["name"] == b["name"] == "jit(told_apart)"
    assert a["phase"] == b["phase"] == "boot/warm/loop"
    assert b["retrieval_s"] > 0.0 and a["retrieval_s"] == 0.0
    for run in outs:  # the window counts both kinds alike, as it always did
        assert run["compiles"] == run["compiled"] + run["loaded"] >= 1
        assert "jit(told_apart)" in run["names"]
    assert first["compiled"] >= 1 and first["loaded"] == 0
    assert second["compiled"] == 0 and second["loaded"] == second["compiles"]
    exe = second["status"]["executables"]
    assert exe["totals"]["boot"]["loaded"]["count"] == second["loaded"]
    assert exe["totals"]["boot"]["compiled"]["count"] == 0
    assert exe["compiled"] == {}  # nothing compiled by name on a warm cache
    assert "jit(told_apart)" in first["status"]["executables"]["compiled"]
    assert exe["by_phase"]["boot/warm/loop"]["loaded"] >= 1
    pc1, pc2 = (o["status"]["persistent_cache"] for o in outs)
    assert pc1["dir"] == pc2["dir"] == cache
    assert pc1["at_device"] == {"bytes": 0, "entries": 0}
    assert pc2["at_device"]["entries"] >= 1 and pc2["at_device"]["bytes"] > 0


def test_after_ready_compile_is_a_flight_event_a_line_and_a_span(boot, caplog):
    flight = tracing.FlightRecorder(16)
    cc.report_to(flight)
    before = _fresh("before_ready")
    late = _fresh("after_ready")
    tr = tracing.configure(True, 64)
    try:
        before(jnp.ones(3)).block_until_ready()
        assert flight.snapshot()["events"] == []
        cc.mark_ready("unit-after-ready")
        with caplog.at_level(logging.WARNING, logger=cc.log.name):
            with cc.CompileWindow() as w:
                late(jnp.ones(3)).block_until_ready()
        spans = [sp for sp in tr.snapshot() if sp.name.startswith("compile:")]
    finally:
        tracing.configure(False)
    assert w.compiles >= 1 and "jit(boot_timeline_after_ready)" in w.names
    events = [e for e in flight.snapshot()["events"] if e["event"] == "compile"]
    assert "jit(boot_timeline_after_ready)" in [e["name"] for e in events]
    assert all(e["outcome"] in cc.OUTCOMES and e["seconds"] >= 0.0 for e in events)
    assert "jit(boot_timeline_before_ready)" not in [e["name"] for e in events]
    assert any("jit(boot_timeline_after_ready) after readiness" in r.getMessage()
               for r in caplog.records)
    assert "compile:jit(boot_timeline_after_ready)" in [sp.name for sp in spans]
    rec = [r for r in cc.executable_records()
           if r["name"] == "jit(boot_timeline_after_ready)"][-1]
    assert rec["after_ready"]


# ---------------------------------------------------------------------------
# export: the families, and the benchmark's reader over them


def test_mark_ready_exports_the_phases_and_the_reader_reads_them(boot):
    import types

    from cellbench.readers import prom_labelled

    if not metrics.HAVE_PROM:
        pytest.skip("prometheus_client not installed")
    t0 = time.monotonic() - 10.0
    boot.begin(t0)
    boot.add("boot/imports", t0, 1.0)
    boot.add("boot/imports", t0 + 2.0, 0.5)
    boot.add("boot/weights", t0 + 3.0, 2.0, parameters=5)
    boot.add("boot/warm/engine", t0 + 5.0, 1.0)
    boot.add("boot/warm/autotune", t0 + 6.0, 0.25)
    boot.add("boot/warm/loop", t0 + 6.25, 2.0)
    boot.add("boot/warm/loop/grid", t0 + 6.5, 1.0, "boot/warm/loop", rung=4)
    snap = cc.mark_ready("unit-export")
    assert snap["phases"]["boot/imports"] == 1.5
    status = cc.boot_status()
    assert status["warm_phases_s"] == {"autotune": 0.25, "engine": 1.0, "loop": 2.0}
    assert status["boot"]["ready"] and "executables" in status

    def read(**args):
        ctx = types.SimpleNamespace(notes={})
        return prom_labelled.read(ctx, **args)

    def phase(name):
        return read(family="boot_phase_seconds",
                    labels={"model": "unit-export", "phase": name})

    assert phase("imports") == 1.5 and phase("weights") == 2.0
    assert phase("warm") == 3.25  # the wall of boot/warm/*, children not counted
    total, unnamed = phase("total"), phase("unnamed")
    assert total == pytest.approx(10.0, abs=0.2)
    assert unnamed == pytest.approx(total - 1.5 - 2.0 - 3.25, abs=1e-3)
    pct = read(family="boot_phase_seconds",
               labels={"model": "unit-export", "phase": "unnamed"},
               over={"model": "unit-export", "phase": "total"}, scale=100.0)
    assert pct == pytest.approx(unnamed / total * 100.0)
    # children stay apart; a child that never counted reads 0, not nothing;
    # a family the program lacks (the parent) reads nothing
    cc._install_monitor()
    text = metrics.render()[0].decode()
    kids = prom_labelled.children(text, "xla_executables_total")
    assert len(kids) == 4 and {k["when"] for k, _ in kids} == {"boot", "serving"}
    assert read(family="xla_executables_total",
                labels={"outcome": "loaded", "when": "serving"}) is not None
    assert read(family="no_such_family_total", labels={"outcome": "compiled"}) is None
    assert read(family="boot_phase_seconds", labels={"phase": "no-such"}) is None
    assert len(prom_labelled.children(text, "xla_executable_seconds_total")) == 12
