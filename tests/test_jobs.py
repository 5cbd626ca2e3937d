"""Bulk inference lane tests (JOBS_ENABLED; jobs/ + /v1/batches).

The judged contracts (ISSUE 11):
1. The JobStore is crash-safe and exactly-once: line results append
   write-ahead (CRC-framed under JOURNAL_DIR/jobs), duplicates are
   refused, manifests/results/states survive reopen, the idempotency
   key dedups resubmission, TTL purges terminal jobs.
2. The HTTP surface: submit (JSON or JSONL), status, results, cancel —
   and every job line's result is IDENTICAL to the same prompt served
   interactively (the bulk lane is the same engine path).
3. Startup replay resumes an incomplete job from its last completed
   line: recorded lines are NOT re-run, remaining lines complete.
4. ``JOBS_ENABLED`` unset (default) builds none of it; enabled without
   its prerequisites refuses at construction.
5. The backfill governor throttles claiming under interactive
   pressure; ``backfill_ok`` defers instead of shedding.
6. Chaos: a REAL serve process SIGKILLed mid-job completes the job
   after restart with exactly-once per-line results (JOB_SMOKE stage).
"""

import asyncio
import json
import os
import time

import pytest

from mlmicroservicetemplate_tpu.engine import InferenceEngine
from mlmicroservicetemplate_tpu.jobs.store import JobStore
from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
from mlmicroservicetemplate_tpu.scheduler import Batcher
from mlmicroservicetemplate_tpu.scheduler.admission import AdmissionController
from mlmicroservicetemplate_tpu.scheduler.policy import BackfillGovernor
from mlmicroservicetemplate_tpu.utils.config import ServiceConfig

from helpers import tiny_gpt_bundle


def _cfg(**kw) -> ServiceConfig:
    kw.setdefault("device", "cpu")
    kw.setdefault("warmup", False)
    kw.setdefault("batch_buckets", (1, 2, 4))
    kw.setdefault("seq_buckets", (16, 32))
    kw.setdefault("max_decode_len", 8)
    kw.setdefault("stream_chunk_tokens", 4)
    kw.setdefault("max_streams", 4)
    kw.setdefault("batch_timeout_ms", 1.0)
    return ServiceConfig(**kw)


def _line(text: str, **kw) -> dict:
    return {
        "text": text, "temperature": 0.0, "top_k": 0, "top_p": 1.0,
        "seed": None, "max_tokens": None, "stop": [], **kw,
    }


async def _ready(client):
    for _ in range(200):
        if (await client.get("/readyz")).status == 200:
            return
        await asyncio.sleep(0.05)
    raise RuntimeError("never ready")


async def _wait_job(client, jid: str, want="completed", timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        r = await client.get(f"/v1/batches/{jid}")
        body = await r.json()
        if body["status"] == want:
            return body
        await asyncio.sleep(0.1)
    raise AssertionError(f"job never reached {want}: {body}")


def _app_client(cfg, bundle):
    from aiohttp.test_utils import TestClient, TestServer

    from mlmicroservicetemplate_tpu.api import build_app

    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    batcher = Batcher(eng, cfg)
    app = build_app(cfg, bundle, eng, batcher)
    return TestClient(TestServer(app)), batcher


# ---------------------------------------------------------------------------
# store primitives


def test_job_store_roundtrip_exactly_once_and_ttl(tmp_path):
    d = str(tmp_path / "jobs")
    store = JobStore(d, fsync="off", model="t")
    job, created = store.create(
        [_line("a"), _line("b"), _line("c")], key="k1"
    )
    assert created and job.total == 3 and job.state == "queued"
    # Idempotency: same key → same job, no new work.
    job2, created2 = store.create([_line("x")], key="k1")
    assert not created2 and job2.id == job.id
    store.set_state(job.id, "running")
    assert store.line_done(job.id, 0, "r0", 4, "stop")
    assert store.line_done(job.id, 2, "r2", 4, "length")
    # Exactly-once: the duplicate is refused, nothing overwritten.
    assert not store.line_done(job.id, 0, "DIFFERENT", 9, "stop")
    assert job.results[0]["text"] == "r0"
    assert job.remaining() == [1]
    store.close()

    # Reopen: everything replays (compaction included); terminal-state
    # guard keeps a completed job completed.
    store2 = JobStore(d, fsync="off", model="t")
    j = store2.get(job.id)
    assert j is not None and j.state == "running"
    assert j.results[0]["text"] == "r0" and j.results[2]["finish"] == "length"
    assert j.remaining() == [1] and store2.by_key["k1"] == job.id
    store2.line_done(job.id, 1, "r1", 2, "stop")
    store2.set_state(job.id, "completed")
    store2.set_state(job.id, "running")  # terminal states never regress
    assert store2.get(job.id).state == "completed"
    assert store2.get(job.id).counts() == {
        "total": 3, "completed": 3, "failed": 0,
    }
    store2.close()

    # TTL: a terminal job past its TTL purges at sweep AND at open.
    # The TTL is set after the open: on a busy machine the job is already
    # 10 ms old by then, the OPEN purges it and the sweep finds nothing.
    store3 = JobStore(d, fsync="off", model="t", ttl_s=3600)
    store3.ttl_s = 0.01
    time.sleep(0.05)
    assert store3.sweep() == 1
    assert store3.get(job.id) is None and "k1" not in store3.by_key
    store3.close()
    store4 = JobStore(d, fsync="off", model="t", ttl_s=0.01)
    assert store4.get(job.id) is None, "purge must be durable"
    store4.close()

    # Validation bounds.
    store5 = JobStore(d, fsync="off", model="t")
    with pytest.raises(ValueError, match="at least one line"):
        store5.create([])
    store5.close()


def test_backfill_governor_and_admission_gate():
    gov = BackfillGovernor(8)
    assert gov.target(False, False) == 8  # idle: full backfill
    assert gov.target(True, False) == 4   # interactive live: half
    assert gov.target(True, True) == 1    # interactive waiting: trickle
    assert BackfillGovernor(1).target(True, False) == 1
    # backfill_ok: drain gates claiming without touching shed counters.
    cfg = _cfg()
    eng = InferenceEngine(tiny_gpt_bundle(), cfg, ReplicaSet(make_mesh(1)))
    adm = AdmissionController(cfg, eng)
    assert adm.backfill_ok()
    adm.draining = True
    assert not adm.backfill_ok()


def test_jobs_disabled_default_builds_nothing(tmp_path):
    """JOBS_ENABLED unset: no JobManager, no /v1/batches routes —
    the serving surface is bit-identical to pre-jobs code.  Enabled
    without JOURNAL_DIR (or on a non-generative model) refuses at
    construction, not at first request."""
    bundle = tiny_gpt_bundle()
    cfg = _cfg()
    eng = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    batcher = Batcher(eng, cfg)
    assert batcher.jobs is None

    async def no_routes():
        client, b = _app_client(_cfg(), tiny_gpt_bundle())
        await client.start_server()
        try:
            assert b.jobs is None
            r = await client.post("/v1/batches", json={"lines": ["x"]})
            assert r.status == 404
        finally:
            await client.close()

    asyncio.run(no_routes())
    with pytest.raises(ValueError, match="JOURNAL_DIR"):
        Batcher(eng, _cfg(jobs_enabled=True))
    from helpers import tiny_bert_bundle

    bert = tiny_bert_bundle()
    beng = InferenceEngine(bert, cfg, ReplicaSet(make_mesh(1)))
    with pytest.raises(ValueError, match="generative"):
        Batcher(beng, _cfg(
            jobs_enabled=True, journal_dir=str(tmp_path / "j")
        ))


# ---------------------------------------------------------------------------
# HTTP surface + interactive-identity


def test_job_api_end_to_end_results_match_interactive(tmp_path):
    """Submit JSONL → completed → results; every line's text equals
    the interactive /predict completion of the same prompt (bulk is
    the same engine path, just batch-class); idempotency-key retries
    dedup; cancel stops a running job; malformed bodies 400."""
    bundle = tiny_gpt_bundle()
    cfg = _cfg(
        journal_dir=str(tmp_path / "j"), journal_fsync="off",
        jobs_enabled=True, job_max_concurrent_lines=2,
        max_stream_queue=4,
    )
    prompts = [f"bulk prompt number {i}" for i in range(5)]

    async def body():
        client, batcher = _app_client(cfg, bundle)
        await client.start_server()
        try:
            await _ready(client)
            # Interactive baseline first (greedy → deterministic).
            expected = []
            for p in prompts:
                r = await client.post("/predict", json={"text": p})
                assert r.status == 200
                expected.append((await r.json())["prediction"]["text"])
            payload = "\n".join(
                json.dumps({"text": p}) for p in prompts
            )
            r = await client.post(
                "/v1/batches", data=payload,
                headers={"Content-Type": "application/x-ndjson",
                         "Idempotency-Key": "same-key"},
            )
            assert r.status == 201, await r.text()
            job = await r.json()
            assert job["line_counts"]["total"] == 5
            # Retried POST (same key) observes the first job: 200, not
            # a second manifest.
            r2 = await client.post(
                "/v1/batches", data=payload,
                headers={"Content-Type": "application/x-ndjson",
                         "Idempotency-Key": "same-key"},
            )
            assert r2.status == 200
            assert (await r2.json())["id"] == job["id"]
            final = await _wait_job(client, job["id"])
            assert final["line_counts"] == {
                "total": 5, "completed": 5, "failed": 0,
            }
            r = await client.get(f"/v1/batches/{job['id']}/results")
            assert r.status == 200
            rows = [json.loads(x) for x in (await r.text()).splitlines()]
            assert [row["line"] for row in rows] == list(range(5))
            for row, exp in zip(rows, expected):
                assert row["text"] == exp, (row, exp)
            # List + status surfaces.
            lst = await (await client.get("/v1/batches")).json()
            assert any(j["id"] == job["id"] for j in lst["data"])
            st = await (await client.get("/status")).json()
            assert st["jobs"]["jobs_tracked"] >= 1
            # Cancel: a fresh long job flips to cancelled and stops.
            r = await client.post("/v1/batches", json={
                "lines": [{"text": f"cancel me {i}"} for i in range(8)],
            })
            assert r.status == 201
            j2 = await r.json()
            r = await client.post(f"/v1/batches/{j2['id']}/cancel")
            assert (await r.json())["status"] == "cancelled"
            await asyncio.sleep(0.3)
            got = await (
                await client.get(f"/v1/batches/{j2['id']}")
            ).json()
            assert got["status"] == "cancelled"
            # Errors: unknown id, malformed line, empty job.
            assert (await client.get("/v1/batches/nope")).status == 404
            r = await client.post(
                "/v1/batches", data="not-json\n",
                headers={"Content-Type": "application/x-ndjson"},
            )
            assert r.status == 400
            r = await client.post("/v1/batches", json={"lines": []})
            assert r.status == 400
        finally:
            await client.close()

    asyncio.run(body())


def test_job_resume_from_last_completed_line(tmp_path):
    """Startup replay: a store holding a half-done job re-admits ONLY
    the unfinished lines — recorded results are served verbatim (the
    sentinel text proves no re-run), the rest complete for real, and
    job_replays counts the resume."""
    bundle = tiny_gpt_bundle()
    jd = str(tmp_path / "j")
    prompts = [f"resume line {i}" for i in range(4)]
    store = JobStore(os.path.join(jd, "jobs"), fsync="off", model="gpt2")
    job, _ = store.create([_line(p) for p in prompts])
    store.set_state(job.id, "running")
    store.line_done(job.id, 0, "SENTINEL-0", 3, "stop")
    store.line_done(job.id, 2, "SENTINEL-2", 3, "stop")
    store.close()

    cfg = _cfg(
        journal_dir=jd, journal_fsync="off", jobs_enabled=True,
        job_max_concurrent_lines=2,
    )

    async def body():
        client, batcher = _app_client(cfg, bundle)
        await client.start_server()
        try:
            await _ready(client)
            final = await _wait_job(client, job.id)
            assert final["line_counts"]["completed"] == 4
            assert batcher.jobs.replayed == {
                "resumed": 1, "complete": 0, "failed": 0,
            }
            r = await client.get(f"/v1/batches/{job.id}/results")
            rows = {
                row["line"]: row for row in (
                    json.loads(x) for x in (await r.text()).splitlines()
                )
            }
            # Recorded lines served verbatim — never re-run.
            assert rows[0]["text"] == "SENTINEL-0"
            assert rows[2]["text"] == "SENTINEL-2"
            # Unfinished lines really ran: interactive identity.
            for i in (1, 3):
                rr = await client.post(
                    "/predict", json={"text": prompts[i]}
                )
                exp = (await rr.json())["prediction"]["text"]
                assert rows[i]["text"] == exp
        finally:
            await client.close()

    asyncio.run(body())


# ---------------------------------------------------------------------------
# chaos: real SIGKILL mid-job through a real server (scripts/check.sh
# JOB_SMOKE stage)


@pytest.mark.chaos
def test_job_crash_smoke(tmp_path):
    """kill -9 a real serving process mid-job; restart on the same
    JOURNAL_DIR; the job completes with exactly-once per-line results
    (no duplicates, no gaps, every text identical to the interactive
    completion) and the stream journal holds zero incomplete streams."""
    import signal
    import socket
    import subprocess
    import sys
    import urllib.error
    import urllib.request

    llama_cfg = json.dumps({
        "vocab_size": 300, "d_model": 32, "num_heads": 4,
        "num_kv_heads": 2, "num_layers": 2, "d_ff": 64,
        "max_position": 256,
    })

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    def env_for(port, jdir):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.update({
            "REPLICAS": "1",
            "JAX_PLATFORMS": "cpu", "DEVICE": "cpu", "WARMUP": "0",
            "MODEL_NAME": "llama", "LLAMA_CONFIG": llama_cfg,
            "HOST": "127.0.0.1", "PORT": str(port),
            "SEQ_BUCKETS": "16,32", "BATCH_BUCKETS": "1,2,4",
            "MAX_DECODE_LEN": "16", "STREAM_CHUNK_TOKENS": "4",
            "MAX_STREAM_QUEUE": "4", "PAGED_KV": "1",
            "PREFILL_CHUNK": "16", "KV_BLOCK_SIZE": "8",
            "JOURNAL_DIR": jdir, "JOURNAL_FSYNC": "always",
            "JOBS_ENABLED": "1", "JOB_MAX_CONCURRENT_LINES": "2",
            "LOG_LEVEL": "WARNING",
        })
        return env

    def start(port, jdir):
        return subprocess.Popen(
            [sys.executable, "-m", "mlmicroservicetemplate_tpu.serve"],
            env=env_for(port, jdir),
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )

    def wait_ready(port, timeout=120):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/readyz", timeout=2
                ) as r:
                    if r.status == 200:
                        return
            except Exception:
                pass
            time.sleep(0.25)
        raise RuntimeError("server never became ready")

    def get_json(port, path):
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=60
        ) as r:
            return json.loads(r.read().decode())

    prompts = [
        f"the quick brown fox jumps over the lazy dog {i}"
        for i in range(6)
    ]
    jdir = str(tmp_path / "journal")
    port1 = free_port()
    p1 = start(port1, jdir)
    try:
        wait_ready(port1)
        payload = "\n".join(
            json.dumps({"text": p}) for p in prompts
        ).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port1}/v1/batches", data=payload,
            headers={"Content-Type": "application/x-ndjson"},
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            job = json.loads(r.read().decode())
        jid = job["id"]
        # SIGKILL once at least one line finished but not all —
        # mid-job by construction.
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            got = get_json(port1, f"/v1/batches/{jid}")
            done = got["line_counts"]["completed"]
            if 1 <= done < len(prompts):
                break
            if got["status"] == "completed":
                pytest.skip("job finished before the kill landed")
            time.sleep(0.05)
        os.kill(p1.pid, signal.SIGKILL)
    finally:
        p1.wait(timeout=30)

    port2 = free_port()
    p2 = start(port2, jdir)
    try:
        wait_ready(port2)
        deadline = time.monotonic() + 180
        final = None
        while time.monotonic() < deadline:
            try:
                got = get_json(port2, f"/v1/batches/{jid}")
            except urllib.error.HTTPError as e:
                if e.code != 404:
                    raise
                time.sleep(0.5)  # replay may still be registering
                continue
            if got["status"] == "completed":
                final = got
                break
            time.sleep(0.25)
        assert final is not None, "job never completed after restart"
        assert final["line_counts"] == {
            "total": 6, "completed": 6, "failed": 0,
        }
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port2}/v1/batches/{jid}/results",
            timeout=60,
        ) as r:
            rows = [json.loads(x.decode()) for x in r]
        # Exactly-once: every line index appears once, no gaps.
        assert sorted(row["line"] for row in rows) == list(range(6))
        # Token identity: each line equals the interactive completion
        # (deterministic init + greedy → same text on any boot).
        for row, prompt in zip(sorted(rows, key=lambda r: r["line"]),
                               prompts):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port2}/predict",
                data=json.dumps({"text": prompt}).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=120) as r:
                exp = json.loads(r.read().decode())["prediction"]["text"]
            assert row["text"] == exp, (row["line"], row["text"], exp)
        # The journal ledger drained: no incomplete streams, and the
        # replay counters are visible in /metrics.
        status = get_json(port2, "/status")
        assert status["durability"]["journal"]["streams_incomplete"] == 0
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port2}/metrics", timeout=10
        ) as r:
            scrape = r.read().decode()
        assert "job_replays_total" in scrape
        assert 'outcome="resumed"' in scrape
        assert "job_lines_total" in scrape
    finally:
        p2.terminate()
        try:
            p2.wait(timeout=30)
        except subprocess.TimeoutExpired:
            # A CPU-starved box can stretch the SIGTERM drain past the
            # window; drain latency is not this smoke's contract
            # (exactly-once resume is), and a leaked half-drained
            # server poisons every later test on the port/core.
            p2.kill()
            p2.wait(timeout=10)
