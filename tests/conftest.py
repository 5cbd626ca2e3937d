"""Test harness: run everything on a virtual 8-device CPU mesh.

This is the JAX equivalent of a fake multi-accelerator backend
(SURVEY.md §4): identical pmap/shard_map code paths, no TPU required.
Must set env before the first jax import anywhere in the test process.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
# Serving defaults that keep tests fast.
os.environ.setdefault("WARMUP", "0")

# LOCKTRACE=1 (scripts/check.sh chaos stages; docs/static-analysis.md):
# install the lock-order detector BEFORE any engine module creates a
# lock — the fixture below then asserts no inversion / held-across-
# dispatch violation per test.  Stdlib-only import, safe pre-jax.
from mlmicroservicetemplate_tpu.utils import locktrace  # noqa: E402

locktrace.auto_install()

# XLA CPU's default conv/matmul precision is reduced (bf16-ish passes);
# golden tests need real f32 math.
import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_devices():
    import jax

    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs


# ---------------------------------------------------------------------------
# Test tiers: `pytest -m quick` is the <2-minute CI loop; the slow set
# splits into a `mid` and a heavy tier so EVERY tier fits a 10-minute
# cap on the 1-vCPU reference box:
#
#   pytest -m quick              # < 2 min   (measured < 3 s each)
#   pytest -m mid                # < 10 min  (measured 3–12 s each)
#   pytest -m 'slow and not mid' # < 10 min  (measured >= 12 s each)
#
# `mid` tests carry BOTH markers (mid + slow), so the long-standing
# tier-1 invocation `-m 'not slow'` keeps selecting exactly the quick
# set — the new tier subdivides, it never reclassifies.
#
# Classification is data-driven: tests/measured_durations.json maps
# node ids to measured call seconds (regenerate with
# `pytest -q --durations=0` and the helper in its header); anything at
# or above _SLOW_THRESHOLD_S is marked `slow` (plus `mid` below
# _MID_MAX_S), everything else (including tests too new to have a
# measurement) is `quick`.

_SLOW_THRESHOLD_S = 3.0
_MID_MAX_S = 12.0


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "quick: fast tier (pytest -m quick, <2 min total)"
    )
    config.addinivalue_line(
        "markers",
        "slow: measured >= 3s on the reference box (excluded from -m quick)",
    )
    config.addinivalue_line(
        "markers",
        "mid: measured 3-12s (subset of slow; pytest -m mid, <10 min "
        "total; the heavy remainder is -m 'slow and not mid')",
    )
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection / crash-recovery suite (pytest -m "
        "chaos; also marked slow so tier-1's -m 'not slow' never runs "
        "it — scripts/check.sh has the chaos stage)",
    )
    config.addinivalue_line(
        "markers",
        "leaves_threads: this test intentionally leaves background "
        "threads running (escape hatch for the conftest thread-leak "
        "guard)",
    )


def pytest_collection_modifyitems(config, items):
    import json
    import pathlib

    path = pathlib.Path(__file__).parent / "measured_durations.json"
    try:
        durations = json.loads(path.read_text())
    except Exception:
        durations = {}
    for item in items:
        # Chaos tests live in their own tier: always slow (kept out of
        # tier-1), never quick, regardless of measured duration.
        if item.get_closest_marker("chaos") is not None:
            item.add_marker(pytest.mark.slow)
            continue
        # Node ids in the file are relative to the repo root
        # ("tests/test_x.py::test_y").
        nid = item.nodeid
        if not nid.startswith("tests/"):
            nid = f"tests/{nid}"
        measured = durations.get(nid, 0.0)
        if measured >= _SLOW_THRESHOLD_S:
            item.add_marker(pytest.mark.slow)
            if measured < _MID_MAX_S:
                item.add_marker(pytest.mark.mid)
        else:
            item.add_marker(pytest.mark.quick)


# ---------------------------------------------------------------------------
# Thread-leak guard (r18 / graftlint PR): after each test, no NEW
# non-daemon thread may survive, and the stack's own service threads —
# the continuous decode loop ("decode-loop") and the scaling governor
# ("fleet-scaler") — must have been stopped/joined by the test that
# started them.  A short grace window absorbs threads that are mid-
# teardown when the test body returns.  Watchdog "dispatch-*" threads
# are exempt: a watchdog-cut hang ABANDONS its worker thread by design
# (engine/faults.py), so those may outlive any hang-injection test.
# Mark tests that intentionally background work with
# @pytest.mark.leaves_threads.

_LEAK_GRACE_S = 5.0
_STRICT_NAMES = ("decode-loop", "fleet-scaler")


@pytest.fixture(autouse=True)
def _thread_leak_guard(request):
    import threading
    import time as _time

    if request.node.get_closest_marker("leaves_threads") is not None:
        yield
        return
    before = set(threading.enumerate())
    yield
    def _leaked():
        out = []
        for t in threading.enumerate():
            if t in before or not t.is_alive():
                continue
            if not t.daemon:
                out.append(t)
            elif any(t.name.startswith(n) for n in _STRICT_NAMES):
                out.append(t)
        return out

    deadline = _time.time() + _LEAK_GRACE_S
    leaked = _leaked()
    while leaked and _time.time() < deadline:
        _time.sleep(0.05)
        leaked = _leaked()
    assert not leaked, (
        f"test leaked thread(s): {[t.name for t in leaked]} — stop/join "
        f"every engine loop and governor the test started, or mark it "
        f"@pytest.mark.leaves_threads"
    )


@pytest.fixture(autouse=True)
def _locktrace_guard():
    """LOCKTRACE=1 only: no lock-order inversion or held-across-
    dispatch violation may be recorded during a test."""
    if not locktrace.is_active():
        yield
        return
    n0 = len(locktrace.violations())
    yield
    new = locktrace.violations()[n0:]
    assert not new, (
        "locktrace violations recorded during this test "
        f"(docs/static-analysis.md): {new}"
    )


# ---------------------------------------------------------------------------
# JAX's persistent compilation cache is process-wide: a test that turns it on
# (``runtime/device.enable_compilation_cache`` with a directory under its
# ``tmp_path``: tests/test_compile_cache.py, tests/test_config.py) left the
# directory set — or, where it put the value back itself, the cache OBJECT
# latched on it — for whatever that xdist worker ran next, which then wrote
# its executables there and read them back (a worker lost in the read, PR
# 54's and PR 57's tier-1 runs).  After such a test: the values as they
# were, and no cache object.

_CACHE_KNOBS = (
    "jax_compilation_cache_dir",
    "jax_persistent_cache_min_compile_time_secs",
    "jax_persistent_cache_min_entry_size_bytes",
    "jax_compilation_cache_include_metadata_in_key",
)


def _mappings() -> tuple[int, int]:
    """(this process's memory mappings, the kernel's limit a process);
    (0, 0) where the kernel does not say."""
    try:
        with open("/proc/self/maps", "rb") as f:
            held = sum(1 for _ in f)
        with open("/proc/sys/vm/max_map_count", "rb") as f:
            return held, int(f.read())
    except (OSError, ValueError):
        return 0, 0


@pytest.fixture(autouse=True)
def _mapping_guard():
    """XLA's CPU backend keeps ~40 memory mappings an executable for as
    long as JAX caches it, and a tier-1 worker compiles thousands: by the
    last tenth of a whole run its count reaches the kernel's
    ``vm.max_map_count`` (65530), the next compile's ``mmap`` fails inside
    the compiler and the worker dies of a segmentation fault or an abort in
    whatever test compiles next (PERF.md section 7, PR 60: a worker read
    65121 at its last sample).  After a test, a worker past seven tenths
    of the limit drops JAX's caches, which unmaps them: once a run for most
    workers (what it drops is compiled again, ~30 s of test time a drop),
    with room for one more test's executables (a whole FILE's can be
    25,000 mappings)."""
    yield
    held, limit = _mappings()
    if held > limit * 7 // 10 > 0:
        import gc

        jax.clear_caches()
        gc.collect()


@pytest.fixture(autouse=True)
def _compile_cache_guard():
    from jax._src import compilation_cache

    knobs = {k: getattr(jax.config, k) for k in _CACHE_KNOBS}
    cache = compilation_cache._cache
    yield
    if (compilation_cache._cache is not cache
            or knobs != {k: getattr(jax.config, k) for k in _CACHE_KNOBS}):
        for k, v in knobs.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
