"""graftlint + locktrace test suite (ISSUE 13, r18).

Three layers:

1. **Rule-engine fixtures**: per rule, a positive hit, a waived hit
   (reasoned waiver) and a clean snippet, driven through
   ``tools.graftlint.lint_source`` / ``lint_paths`` on synthetic
   sources — the rules are pinned by behavior, not by the repo's
   current state.
2. **locktrace units**: lock-order inversion detection, the
   held-across-dispatch flag with its allowlist, RLock re-entry and
   Condition round-trips staying clean.
3. **Repo pins**: the full-repo graftlint run is CLEAN (zero unwaived
   findings, every waiver reasoned), ≥ 6 rules exist, and the r18
   behavior fixes hold — the write-ahead terminal ordering, the new
   ``handoff`` dispatch site, and the batcher's classified breaker.
"""

from __future__ import annotations

import asyncio
import textwrap
from pathlib import Path

import numpy as np
import pytest

from tools.graftlint import lint_paths, lint_source, rules
from tools.graftlint.core import find_repo_root

STREAMS_REL = "mlmicroservicetemplate_tpu/engine/streams.py"
POLICY_REL = "mlmicroservicetemplate_tpu/scheduler/policy.py"
ENGINE_DIR = "mlmicroservicetemplate_tpu/engine"
PROGRAMS_REL = f"{ENGINE_DIR}/programs.py"
WARM_REL = f"{ENGINE_DIR}/warm.py"

REPO_ROOT = Path(__file__).resolve().parent.parent


def unwaived(findings):
    return [f for f in findings if not f.waived]


def _src(s: str) -> str:
    return textwrap.dedent(s)


# ---------------------------------------------------------------------------
# rule: dispatch-guard


def test_dispatch_guard_positive_hit():
    fs = lint_source(_src("""
        import jax

        class Loop:
            def step(self, eng):
                state, toks = eng._gen_chunk(eng.params, 1, False)
                return jax.device_get(toks)
    """), STREAMS_REL, "dispatch-guard")
    assert len(unwaived(fs)) == 2
    assert all(f.rule == "dispatch-guard" for f in fs)


def test_dispatch_guard_guarded_and_traced_clean():
    fs = lint_source(_src("""
        import jax

        class Loop:
            def step(self, eng):
                # lambda argument of the guard
                state, toks = eng.dispatch_guard(
                    "chunk", lambda: eng._gen_chunk(eng.params, 1, False)
                )
                # named closure passed to the guard
                def go():
                    return jax.device_get(toks)
                return eng.dispatch_guard("fetch", go)

        def build(bundle):
            # trace-time composition inside a jit argument
            def start(p, ids):
                return bundle.generate_chunk_fn(p, ids, 1, False)
            return jax.jit(start)

        def _warm_probe(eng):
            # warm-up functions are pre-serving by construction
            return jax.device_get(eng.template)
    """), STREAMS_REL, "dispatch-guard")
    assert unwaived(fs) == []


def test_dispatch_guard_waiver_and_empty_reason():
    waived = lint_source(_src("""
        import jax

        def probe(eng):
            # graftlint: unguarded(calibration probe measures the raw wire)
            return jax.device_get(eng.t)
    """), STREAMS_REL, "dispatch-guard")
    assert unwaived(waived) == [] and len(waived) == 1
    assert waived[0].waived and "raw wire" in waived[0].reason

    empty = lint_source(_src("""
        import jax

        def probe(eng):
            # graftlint: unguarded()
            return jax.device_get(eng.t)
    """), STREAMS_REL, "dispatch-guard")
    # An empty waiver is itself an unwaived finding.
    assert len(unwaived(empty)) == 1
    assert "no reason" in unwaived(empty)[0].message


@pytest.mark.parametrize("rel", [STREAMS_REL, PROGRAMS_REL, WARM_REL])
def test_dispatch_guard_fires_on_the_programs_accessor_idiom(rel):
    """An unguarded ``programs.<x>_fn()(...)`` is a finding in the loop, in
    ``engine/programs.py`` itself and in ``engine/warm.py`` (outside a
    ``warm*`` function); under the guard it is clean."""
    fs = lint_source(_src("""
        def step(loop, wp, table):
            return loop.programs.paged_chunk_fn()(
                wp, loop._state, table, 4, False)

        def guarded(loop, eng, wp, table):
            return eng.dispatch_guard(
                "chunk", lambda: loop.programs.paged_chunk_fn()(
                    wp, loop._state, table, 4, False))
    """), rel, "dispatch-guard")
    assert [f.line for f in unwaived(fs)] == [3]
    assert "paged_chunk_fn()" in fs[0].message


@pytest.mark.parametrize("name", ["programs", "warm"])
def test_programs_and_warm_import_no_streams(name):
    """The arrow points one way: the loop imports its executables and its
    warm-up, never the reverse."""
    import ast

    tree = ast.parse((REPO_ROOT / ENGINE_DIR / f"{name}.py").read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module or "")
            imported |= {f"{node.module or ''}.{a.name}" for a in node.names}
    assert not [m for m in imported if m.split(".")[-1] == "streams"]
    if name == "warm":  # ... and warm-up walks programs, not the reverse
        prog = ast.parse((REPO_ROOT / ENGINE_DIR / "programs.py").read_text())
        assert not [n for n in ast.walk(prog)
                    if isinstance(n, ast.ImportFrom)
                    and "warm" in [a.name for a in n.names] + [n.module]]


def test_dispatch_guard_out_of_scope_files_ignored():
    fs = lint_source(
        "import jax\n\ndef f(x):\n    return jax.device_get(x)\n",
        "mlmicroservicetemplate_tpu/models/gpt.py", "dispatch-guard",
    )
    assert fs == []


# ---------------------------------------------------------------------------
# rule: write-ahead


def test_write_ahead_positive_waived_clean():
    hit = lint_source(_src("""
        class Loop:
            def _finish(self, st):
                st.emit("end")
    """), STREAMS_REL, "write-ahead")
    assert len(unwaived(hit)) == 1

    clean = lint_source(_src("""
        class Loop:
            def _finish(self, st):
                self._journal_done(st)
                st.emit("end")

            def _emit_tokens(self, st, j, arr):
                j.tokens(st.rid, arr)
                st.emit(arr)
    """), STREAMS_REL, "write-ahead")
    assert unwaived(clean) == []

    # Journal append AFTER the emit is still a finding — ordering is
    # the contract, not presence.
    late = lint_source(_src("""
        class Loop:
            def _finish(self, st, j):
                st.emit("end")
                j.done(st.rid)
    """), STREAMS_REL, "write-ahead")
    assert len(unwaived(late)) == 1

    waived = lint_source(_src("""
        class Loop:
            def _finish(self, st):
                # graftlint: write-ahead(error sentinel for a stream the journal never admitted)
                st.emit("end")
    """), STREAMS_REL, "write-ahead")
    assert unwaived(waived) == [] and waived[0].waived


def test_write_ahead_store_results_assignment():
    hit = lint_source(_src("""
        class Store:
            def line_done(self, job, i, row):
                job.results[i] = row
    """), "mlmicroservicetemplate_tpu/jobs/store.py", "write-ahead")
    assert len(unwaived(hit)) == 1

    clean = lint_source(_src("""
        class Store:
            def line_done(self, job, i, row, rec):
                self._append(rec)
                job.results[i] = row
    """), "mlmicroservicetemplate_tpu/jobs/store.py", "write-ahead")
    assert unwaived(clean) == []


# ---------------------------------------------------------------------------
# rule: clock-injection


def test_clock_injection_positive_default_waived():
    hit = lint_source(_src("""
        import time

        class Gov:
            def decide(self):
                return time.monotonic()
    """), POLICY_REL, "clock-injection")
    assert len(unwaived(hit)) == 1

    clean = lint_source(_src("""
        import time

        class Gov:
            def __init__(self, clock=None):
                self._clock = clock if clock is not None else time.monotonic

            def decide(self):
                return self._clock()
    """), POLICY_REL, "clock-injection")
    assert unwaived(clean) == []

    waived = lint_source(_src("""
        import time

        def helper():
            # graftlint: clock(wall time only feeds a log line, never a decision)
            return time.time()
    """), POLICY_REL, "clock-injection")
    assert unwaived(waived) == [] and waived[0].waived

    # Out of scope: other files may read the clock freely.
    free = lint_source(
        "import time\n\ndef f():\n    return time.monotonic()\n",
        STREAMS_REL, "clock-injection",
    )
    assert free == []


# ---------------------------------------------------------------------------
# rules: knob-drift + metric-drift (repo-wide, synthetic mini-repo)


def _mini_repo(tmp_path: Path, config_body: str, readme: str = "",
               metrics_body: str | None = None, grafana: str = "{}",
               surface_test: str = "") -> Path:
    (tmp_path / "pyproject.toml").write_text("[project]\nname='mini'\n")
    pkg = tmp_path / "mlmicroservicetemplate_tpu" / "utils"
    pkg.mkdir(parents=True)
    (pkg / "config.py").write_text(_src(config_body))
    if metrics_body is not None:
        (pkg / "metrics.py").write_text(_src(metrics_body))
    (tmp_path / "README.md").write_text(readme)
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "grafana-serving.json").write_text(grafana)
    (tmp_path / "tests").mkdir()
    (tmp_path / "tests" / "test_metrics_surface.py").write_text(
        surface_test
    )
    return tmp_path


def test_knob_drift_positive_and_clean(tmp_path):
    root = _mini_repo(tmp_path, """
        from pydantic import BaseModel, field_validator

        class ServiceConfig(BaseModel):
            loose_knob: int = 3
            tight_knob: int = 1
            free_path: str | None = None   # exempt: optional free-form
            flag: bool = False             # exempt: bool

            @field_validator("tight_knob")
            @classmethod
            def _check_tight(cls, v):
                return v
    """, readme="| `TIGHT_KNOB` | 1 | documented |\n"
                "| `FREE_PATH` / `FLAG` | unset / 0 | documented |\n")
    fs = lint_paths(
        [root / "mlmicroservicetemplate_tpu"], root=root, only="knob-drift"
    )
    msgs = " | ".join(f.message for f in unwaived(fs))
    assert "loose_knob" in msgs and "no validator" in msgs
    assert "`LOOSE_KNOB` has no README knob-table row" in msgs
    # tight_knob is validated + documented; bool and optional free-form
    # str fields are exempt from the VALIDATOR requirement (but still
    # need their documented rows, provided above).
    assert "tight_knob" not in msgs
    assert "`free_path` (FREE_PATH) has no validator" not in msgs
    assert "`flag` (FLAG) has no validator" not in msgs
    assert "FREE_PATH" not in msgs and "FLAG" not in msgs


def test_knob_drift_waiver(tmp_path):
    root = _mini_repo(tmp_path, """
        from pydantic import BaseModel

        class ServiceConfig(BaseModel):
            # graftlint: knob(internal tuning escape hatch, deliberately undocumented)
            secret_knob: int = 3
    """)
    fs = lint_paths(
        [root / "mlmicroservicetemplate_tpu"], root=root, only="knob-drift"
    )
    assert unwaived(fs) == [] and len(fs) == 3  # all three checks waived


_METRICS_PIN = (
    "def _declared_families():\n    pass\n"
    "# asserts 'missing from /metrics'\n"
)


def test_metric_drift_dashboard_and_labels(tmp_path):
    root = _mini_repo(tmp_path, "class ServiceConfig:\n    pass\n",
                      metrics_body="""
        from prometheus_client import Counter

        SEEN = Counter("seen_total", "on dashboard", ["model"])
        GHOST = Counter("ghost_total", "missing everywhere", ["model"])
        WIDE = Counter(
            "wide_total", "too many labels",
            ["model", "a", "b", "c"],
        )
        LEAKY = Counter("leaky_total", "request-unique", ["request_id"])
    """, grafana='{"expr": "seen_total wide_total leaky_total"}',
                      surface_test=_METRICS_PIN)
    fs = lint_paths(
        [root / "mlmicroservicetemplate_tpu"], root=root,
        only="metric-drift",
    )
    msgs = " | ".join(f.message for f in unwaived(fs))
    assert "ghost_total" in msgs and "nowhere" in msgs
    assert "wide_total" in msgs and "4 labels" in msgs
    assert "leaky_total" in msgs and "request-unique" in msgs
    assert "seen_total" not in msgs


def test_metric_drift_inline_creation_and_missing_pin(tmp_path):
    root = _mini_repo(tmp_path, "class ServiceConfig:\n    pass\n",
                      metrics_body='from prometheus_client import Counter\n'
                                   'OK = Counter("ok_total", "d", ["model"])\n',
                      grafana='"ok_total"', surface_test="")  # pin ABSENT
    rogue = root / "mlmicroservicetemplate_tpu" / "rogue.py"
    rogue.write_text("from prometheus_client import Gauge\n")
    fs = lint_paths(
        [root / "mlmicroservicetemplate_tpu"], root=root,
        only="metric-drift",
    )
    msgs = " | ".join(f.message for f in unwaived(fs))
    assert "introspection pin" in msgs
    assert "prometheus_client import outside" in msgs


# ---------------------------------------------------------------------------
# rule: exception-discipline


def test_exception_discipline_bare_and_classify():
    bare = lint_source(
        "def f():\n    try:\n        pass\n    except:\n        pass\n",
        "mlmicroservicetemplate_tpu/api/app.py", "exception-discipline",
    )
    assert len(unwaived(bare)) == 1
    assert "bare" in unwaived(bare)[0].message

    unclassified = lint_source(_src("""
        def f(eng, fn, items):
            try:
                eng.dispatch_guard("batch", fn)
            except Exception as e:
                for it in items:
                    it.fail(e)
    """), "mlmicroservicetemplate_tpu/scheduler/batcher.py",
        "exception-discipline")
    assert len(unwaived(unclassified)) == 1

    classified = lint_source(_src("""
        from ..engine import faults

        def f(eng, fn, rep, items):
            try:
                eng.dispatch_guard("batch", fn)
            except Exception as e:
                if faults.is_transient(e) or faults.is_fatal_device(e):
                    rep.breaker.record_fault()
                for it in items:
                    it.fail(e)
    """), "mlmicroservicetemplate_tpu/scheduler/batcher.py",
        "exception-discipline")
    assert unwaived(classified) == []

    narrow = lint_source(_src("""
        def f(eng, fn):
            try:
                eng.dispatch_guard("batch", fn)
            except KeyError:
                return None
    """), "mlmicroservicetemplate_tpu/scheduler/batcher.py",
        "exception-discipline")
    assert unwaived(narrow) == []  # narrow handlers are fine


# ---------------------------------------------------------------------------
# locktrace


@pytest.fixture
def traced():
    from mlmicroservicetemplate_tpu.utils import locktrace

    was_active = locktrace.is_active()
    if not was_active:
        locktrace.install()
    yield locktrace
    locktrace.reset()
    if not was_active:
        locktrace.uninstall()


def test_locktrace_lock_order_inversion(traced):
    import threading

    a, b = threading.Lock(), threading.Lock()
    with a:
        with b:
            pass

    def worker():
        with b:
            with a:
                pass

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    kinds = [v["kind"] for v in traced.violations()]
    assert "lock_order_inversion" in kinds


def test_locktrace_consistent_order_clean(traced):
    import threading

    a, b = threading.Lock(), threading.Lock()
    for _ in range(3):
        with a:
            with b:
                pass
    t = threading.Thread(target=lambda: a.acquire() and None)
    with a:
        with b:
            pass
    assert traced.violations() == []


def test_locktrace_rlock_reentry_and_condition_clean(traced):
    import threading

    r = threading.RLock()
    with r:
        with r:  # re-entry: no self-edge, no violation
            pass
    cond = threading.Condition()
    with cond:
        cond.wait(timeout=0.01)  # release/re-acquire through the tracer
    # The held-stack must be balanced: acquiring another lock now
    # creates no edge from a lock we no longer hold.
    x = threading.Lock()
    with x:
        pass
    assert traced.violations() == []


def test_locktrace_held_across_dispatch_and_allowlist(traced):
    import threading

    held = threading.Lock()
    with held:
        traced.tracer().note_dispatch("chunk")
    vs = traced.violations()
    assert len(vs) == 1 and vs[0]["kind"] == "held_across_dispatch"
    assert "chunk" in vs[0]["site"]

    allowed = threading.Lock()
    traced.allow_across_dispatch(allowed)
    with allowed:
        traced.tracer().note_dispatch("chunk")
    assert len(traced.violations()) == 1  # no new violation


def test_locktrace_engine_dispatch_hook(traced):
    """A real guarded dispatch under a traced lock is flagged; the
    engine's own dispatch path (no foreign lock held) stays clean."""
    import threading

    from mlmicroservicetemplate_tpu.engine import InferenceEngine
    from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
    from mlmicroservicetemplate_tpu.utils.config import ServiceConfig

    from helpers import tiny_gpt_bundle

    cfg = ServiceConfig(
        device="cpu", warmup=False, batch_buckets=(1, 2),
        seq_buckets=(16,), max_decode_len=8, stream_chunk_tokens=4,
    )
    eng = InferenceEngine(tiny_gpt_bundle(), cfg, ReplicaSet(make_mesh(1)))
    eng.dispatch_guard("chunk", lambda: 1)
    assert traced.violations() == []
    foreign = threading.Lock()
    with foreign:
        eng.dispatch_guard("chunk", lambda: 1)
    assert any(
        v["kind"] == "held_across_dispatch" for v in traced.violations()
    )


# ---------------------------------------------------------------------------
# repo pins


def test_at_least_six_rules():
    ids = {r.id for r in rules()}
    assert len(ids) >= 6
    assert {"dispatch-guard", "write-ahead", "clock-injection",
            "knob-drift", "metric-drift",
            "exception-discipline"} <= ids


def test_full_repo_run_is_clean():
    """THE acceptance pin: `python -m tools.graftlint
    mlmicroservicetemplate_tpu/` exits 0 — zero unwaived findings, and
    every waiver carries a written reason."""
    root = find_repo_root(REPO_ROOT / "mlmicroservicetemplate_tpu")
    fs = lint_paths([REPO_ROOT / "mlmicroservicetemplate_tpu"], root=root)
    bad = unwaived(fs)
    assert bad == [], "unwaived findings:\n" + "\n".join(
        f.render() for f in bad
    )
    for f in fs:
        assert f.reason.strip(), f"waiver without reason: {f.render()}"


def test_fault_spec_accepts_new_sites():
    from mlmicroservicetemplate_tpu.engine.faults import parse_spec

    rules_ = parse_spec("handoff:fatal@1;swap:transient@2")
    assert [r.site for r in rules_] == ["handoff", "swap"]


# ---------------------------------------------------------------------------
# r18 behavior fixes (the genuine findings graftlint surfaced, fixed
# not waived — ISSUE 13 satellite 1)


def _cfg(**kw):
    from mlmicroservicetemplate_tpu.utils.config import ServiceConfig

    kw.setdefault("device", "cpu")
    kw.setdefault("warmup", False)
    kw.setdefault("batch_buckets", (1, 2, 4))
    kw.setdefault("seq_buckets", (16, 32))
    kw.setdefault("max_decode_len", 12)
    kw.setdefault("stream_chunk_tokens", 4)
    kw.setdefault("max_streams", 4)
    return ServiceConfig(**kw)


def test_terminal_journal_record_dominates_terminal_emit(
    tmp_path, monkeypatch
):
    """streams.py write-ahead fix: at the instant the consumer can
    observe a stream's terminal event, the journal must already hold
    its ``done`` record — otherwise a kill in that gap makes restart
    replay resurrect (and headlessly re-run) a stream its client
    watched finish."""
    from helpers import tiny_gpt_bundle

    from mlmicroservicetemplate_tpu.engine import InferenceEngine
    from mlmicroservicetemplate_tpu.engine import streams as streams_mod
    from mlmicroservicetemplate_tpu.engine.streams import (
        ContinuousDecodeLoop,
    )
    from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
    from mlmicroservicetemplate_tpu.runtime.durability import StreamJournal
    from mlmicroservicetemplate_tpu.scheduler.admission import (
        AdmissionController,
    )

    cfg = _cfg()
    bundle = tiny_gpt_bundle()
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    j = StreamJournal(str(tmp_path / "j"), fsync="off", model=bundle.name)
    eng.journal = j
    cdl = ContinuousDecodeLoop(eng, cfg)
    cdl.admission = AdmissionController(cfg, eng)

    incomplete_at_end: dict = {}
    orig_emit = streams_mod._Stream.emit

    def spy_emit(self, item):
        if item is streams_mod._END:
            incomplete_at_end[self.rid] = {
                s.rid for s in j.incomplete()
            }
        orig_emit(self, item)

    monkeypatch.setattr(streams_mod._Stream, "emit", spy_emit)

    rid = "r18-write-ahead"
    feats = {
        "input_ids": np.arange(1, 9, dtype=np.int32),
        "length": np.int32(8), "request_id": rid,
    }

    async def run():
        gen = cdl.submit_stream(dict(feats))
        async for _ in gen:
            pass

    try:
        asyncio.run(run())
    finally:
        cdl.stop()
        j.close()
    assert rid in incomplete_at_end, "stream never emitted _END"
    assert rid not in incomplete_at_end[rid], (
        "terminal _END was observable before the journal's done record"
    )


def test_fleet_lost_stream_journals_done_before_error(tmp_path):
    """fleet.py write-ahead fix: a stream lost at failover (no healthy
    adopter) journals its terminal record BEFORE the consumer sees the
    error — restart replay must not resurrect it."""
    from helpers import tiny_gpt_bundle

    from mlmicroservicetemplate_tpu.engine import InferenceEngine
    from mlmicroservicetemplate_tpu.engine import streams as streams_mod
    from mlmicroservicetemplate_tpu.engine.fleet import ReplicaFleet
    from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
    from mlmicroservicetemplate_tpu.runtime.durability import StreamJournal

    cfg = _cfg(fleet_replicas=1, fleet_max_replicas=2)
    bundle = tiny_gpt_bundle()
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    j = StreamJournal(str(tmp_path / "j"), fsync="off", model=bundle.name)
    eng.journal = j
    fleet = ReplicaFleet(eng, cfg, autoscale_thread=False)
    loop = asyncio.new_event_loop()
    try:
        rid = "r18-lost-stream"
        feats = {
            "input_ids": np.arange(1, 5, dtype=np.int32),
            "length": np.int32(4), "request_id": rid,
        }
        st = streams_mod._Stream(dict(feats), loop, budget=8)
        j.admit(rid, feats, "interactive", 8)
        assert rid in {s.rid for s in j.incomplete()}
        rep = fleet.replicas[0]
        # Kill the only replica: the failover callback finds no healthy
        # adopter and must lose (error-terminate) the stream.
        fleet._failover_cb(rep)([st], RuntimeError("replica dead"),
                                "budget")
        assert st.done_journaled
        assert rid not in {s.rid for s in j.incomplete()}, (
            "lost stream stayed journal-incomplete after its consumer "
            "saw the terminal error"
        )
    finally:
        fleet.stop()
        j.close()
        loop.close()


def test_batch_poison_does_not_open_breaker_device_fault_does():
    """batcher.py exception-discipline fix: only faults.classify'd
    DEVICE errors feed the replica breaker on the unary batch path.
    Before the fix, FLEET_BREAKER_N malformed client requests evicted
    a healthy replica."""
    from helpers import tiny_gpt_bundle

    from mlmicroservicetemplate_tpu.engine import InferenceEngine
    from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
    from mlmicroservicetemplate_tpu.scheduler import Batcher

    bundle = tiny_gpt_bundle()

    # Arm 1: poison input (KeyError inside the guarded run_batch) —
    # breaker_n=1 so a single indicting fault would open it.
    cfg = _cfg(fleet_replicas=2, fleet_breaker_n=1, batch_timeout_ms=1.0)
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))

    async def poison_arm():
        batcher = Batcher(eng, cfg)
        await batcher.start()
        try:
            for _ in range(3):
                with pytest.raises(Exception):
                    await batcher.submit({"bogus": True})
            assert len(batcher.fleet.healthy_replicas()) == 2, (
                "poison input opened a replica breaker"
            )
            assert all(
                r.breaker.state == 0 for r in batcher.fleet.replicas
            )
        finally:
            await batcher.stop()

    asyncio.run(poison_arm())

    # Arm 2: an injected FATAL device fault on the same site DOES open
    # the breaker (classification still indicts real device faults).
    cfg2 = _cfg(fleet_replicas=2, fleet_breaker_n=1,
                batch_timeout_ms=1.0, fault_spec="batch:fatal@1")
    eng2 = InferenceEngine(bundle, cfg2, ReplicaSet(make_mesh(1)))

    async def device_fault_arm():
        batcher = Batcher(eng2, cfg2)
        await batcher.start()
        try:
            with pytest.raises(Exception):
                await batcher.submit({
                    "input_ids": np.arange(1, 9, dtype=np.int32),
                    "length": np.int32(8),
                })
            assert len(batcher.fleet.healthy_replicas()) == 1, (
                "a fatal device fault did not open the replica breaker"
            )
        finally:
            await batcher.stop()

    asyncio.run(device_fault_arm())


def test_handoff_dispatch_site_recorded():
    """streams.py dispatch-guard fix: the chunked-prefill handoff (row
    surgery flipping a prefilled stream live) now runs under the guard
    at its own ``handoff`` site — visible in dispatch attribution and
    targetable by FAULT_SPEC without renumbering chunk schedules."""
    from helpers import tiny_gpt_bundle

    from mlmicroservicetemplate_tpu.engine import InferenceEngine
    from mlmicroservicetemplate_tpu.engine.streams import (
        ContinuousDecodeLoop,
    )
    from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh

    cfg = _cfg(prefill_chunk=8)
    bundle = tiny_gpt_bundle()
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    cdl = ContinuousDecodeLoop(eng, cfg)
    feats = {
        "input_ids": np.arange(1, 25, dtype=np.int32),
        "length": np.int32(24),
    }

    async def run():
        gen = cdl.submit_stream(dict(feats))
        async for _ in gen:
            pass

    try:
        asyncio.run(run())
    finally:
        cdl.stop()
    assert eng.dispatch_stats.get("handoff", [0])[0] >= 1, (
        f"no handoff-site dispatch recorded: {eng.dispatch_attribution()}"
    )
