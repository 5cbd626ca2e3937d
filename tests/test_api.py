"""HTTP integration tests (SURVEY.md §4): in-process aiohttp server,
real payloads, JSON schema + streaming chunk assertions."""

import asyncio
import io
import json
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from helpers import tiny_bert_bundle, tiny_resnet_bundle, tiny_t5_bundle
from mlmicroservicetemplate_tpu.api import build_app
from mlmicroservicetemplate_tpu.engine import InferenceEngine
from mlmicroservicetemplate_tpu.parallel import ReplicaSet, make_mesh
from mlmicroservicetemplate_tpu.scheduler import Batcher
from mlmicroservicetemplate_tpu.utils.config import ServiceConfig


def _cfg(**kw) -> ServiceConfig:
    kw.setdefault("device", "cpu")
    kw.setdefault("warmup", False)
    kw.setdefault("batch_buckets", (1, 2, 4, 8))
    kw.setdefault("seq_buckets", (16, 32, 64))
    kw.setdefault("max_decode_len", 8)
    kw.setdefault("stream_chunk_tokens", 4)
    kw.setdefault("batch_timeout_ms", 1.0)
    return ServiceConfig(**kw)


def _png_bytes(size: int = 32) -> bytes:
    from PIL import Image

    rng = np.random.default_rng(0)
    img = Image.fromarray(rng.integers(0, 255, (size, size, 3), dtype=np.uint8))
    buf = io.BytesIO()
    img.save(buf, format="PNG")
    return buf.getvalue()


def _run(bundle_fn, body, **cfg_kw):
    async def main():
        cfg = _cfg(**cfg_kw)
        bundle = bundle_fn()
        engine = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
        batcher = Batcher(engine, cfg)
        app = build_app(cfg, bundle, engine, batcher)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            # Wait for the canary/warmup readiness flip.
            for _ in range(200):
                resp = await client.get("/readyz")
                if resp.status == 200:
                    break
                await asyncio.sleep(0.05)
            return await body(client)
        finally:
            await client.close()

    return asyncio.run(main())


def test_image_predict_raw_and_multipart():
    async def body(client):
        png = _png_bytes()
        # Raw bytes
        resp = await client.post(
            "/predict", data=png, headers={"Content-Type": "image/png"}
        )
        assert resp.status == 200
        out = await resp.json()
        assert out["model"] == "resnet50"
        assert "class_id" in out["prediction"]
        assert len(out["topk"]) == 5
        # Multipart upload (the template's upload style)
        from aiohttp import FormData

        form = FormData()
        form.add_field("file", png, filename="x.png", content_type="image/png")
        resp2 = await client.post("/predict", data=form)
        assert resp2.status == 200
        out2 = await resp2.json()
        assert out2["prediction"]["class_id"] == out["prediction"]["class_id"]
        # Corrupt image bytes must 400, not 500 (PIL raises OSError).
        resp3 = await client.post(
            "/predict", data=b"not an image", headers={"Content-Type": "image/png"}
        )
        assert resp3.status == 400

    _run(tiny_resnet_bundle, body)


def test_text_predict_and_errors():
    async def body(client):
        resp = await client.post("/predict", json={"text": "hello world"})
        assert resp.status == 200
        out = await resp.json()
        assert out["prediction"]["label"] in ("a", "b", "c")
        assert abs(sum(out["probs"]) - 1.0) < 1e-3
        # Missing text -> 400
        resp = await client.post("/predict", json={"foo": 1})
        assert resp.status == 400
        # Image payload to a text model -> 400
        resp = await client.post(
            "/predict", data=b"\x89PNG not really", headers={"Content-Type": "image/png"}
        )
        assert resp.status == 400

    _run(tiny_bert_bundle, body)


def test_seq2seq_nonstream_and_stream():
    async def body(client):
        resp = await client.post("/predict", json={"text": "summarize: hello"})
        assert resp.status == 200
        out = await resp.json()
        assert "text" in out["prediction"]

        resp = await client.post(
            "/predict", json={"text": "summarize: hello", "stream": True}
        )
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("application/x-ndjson")
        lines = [json.loads(l) for l in (await resp.text()).strip().splitlines()]
        assert lines, "no ndjson lines"
        assert lines[-1].get("done") is True
        deltas = "".join(l.get("delta", "") for l in lines[:-1])
        assert lines[-1]["prediction"]["text"] == deltas

    _run(tiny_t5_bundle, body)


def test_stream_shedding_503():
    """Beyond max_streams concurrent generations, stream requests shed
    with 503 before any response bytes go out."""

    async def body(client):
        payload = {"text": "summarize: busy", "stream": True}
        tasks = [
            asyncio.create_task(client.post("/predict", json=payload))
            for _ in range(4)
        ]
        resps = await asyncio.gather(*tasks)
        statuses = sorted(r.status for r in resps)
        for r in resps:
            await r.read()
        assert 503 in statuses, statuses
        assert 200 in statuses, statuses

    _run(tiny_t5_bundle, body, max_streams=1, max_decode_len=32)


def test_health_status_metrics():
    async def body(client):
        assert (await client.get("/healthz")).status == 200
        resp = await client.get("/status")
        st = await resp.json()
        assert st["model"] == "bert-base"
        assert st["ready"] is True
        assert st["device"] == "cpu"
        assert st["device_kind"] == "cpu"  # beside `device`, as JAX reports it
        # issue one request so metrics have content
        await client.post("/predict", json={"text": "hi"})
        resp = await client.get("/metrics")
        text = await resp.text()
        assert "predict_requests_total" in text
        assert "batch_size" in text

    _run(tiny_bert_bundle, body)


def _serve(bundle_fn, main, **cfg_kw):
    """Like _run but hands (client, engine, batcher, app) to the body."""

    async def outer():
        cfg = _cfg(**cfg_kw)
        bundle = bundle_fn()
        engine = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
        batcher = Batcher(engine, cfg)
        app = build_app(cfg, bundle, engine, batcher)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            for _ in range(200):
                resp = await client.get("/readyz")
                if resp.status == 200:
                    break
                await asyncio.sleep(0.05)
            return await main(client, engine, batcher, app)
        finally:
            await client.close()

    return asyncio.run(outer())


def test_client_disconnect_midstream_releases_slot():
    """A client that drops mid-stream must not keep burning device
    dispatches: the stream slot frees and chunk dispatch stops at the
    next boundary (VERDICT weak #6).  Pinned to the legacy per-stream
    path (continuous_batching=False) — it instruments
    engine.generate_stream, which the continuous loop never calls; the
    loop's own disconnect behavior is covered by
    tests/test_streams.py::test_cancel_frees_slot and the HTTP-level
    test below."""

    async def main(client, engine, batcher, app):
        calls = {"n": 0}
        orig = engine.generate_stream

        def counting(feats):
            for c in orig(feats):
                calls["n"] += 1
                time.sleep(0.05)  # slow chunks so the disconnect races ahead
                yield c

        engine.generate_stream = counting
        resp = await client.post(
            "/predict", json={"text": "summarize: disconnect me", "stream": True}
        )
        assert resp.status == 200
        await resp.content.readline()  # first ndjson line arrives
        resp.close()  # hard client disconnect
        for _ in range(300):
            if batcher._active_streams == 0:
                break
            await asyncio.sleep(0.02)
        assert batcher._active_streams == 0
        n_after_release = calls["n"]
        await asyncio.sleep(0.3)
        # No further dispatches once the pump saw the cancel.
        assert calls["n"] == n_after_release
        # Far fewer chunks dispatched than the full decode budget.
        assert calls["n"] < 16

    _serve(tiny_t5_bundle, main, max_decode_len=64, stream_chunk_tokens=4,
           continuous_batching=False)


def test_disconnect_midstream_frees_continuous_slot():
    """Same disconnect scenario on the DEFAULT (continuous-batching)
    path: the admission counter returns to 0 so new streams are not
    shed."""

    async def main(client, engine, batcher, app):
        assert batcher._cdl is not None
        resp = await client.post(
            "/predict", json={"text": "summarize: disconnect me", "stream": True}
        )
        assert resp.status == 200
        await resp.content.readline()
        resp.close()  # hard client disconnect
        for _ in range(300):
            if batcher._cdl._admitted == 0:
                break
            await asyncio.sleep(0.02)
        assert batcher._cdl._admitted == 0
        # Slot is reusable: a fresh stream completes.
        resp = await client.post(
            "/predict", json={"text": "summarize: again", "stream": True}
        )
        assert resp.status == 200
        lines = (await resp.text()).strip().splitlines()
        assert json.loads(lines[-1]).get("done") is True

    _serve(tiny_t5_bundle, main, max_decode_len=32, stream_chunk_tokens=4,
           max_streams=1)


def test_predict_sampling_fields():
    """temperature/top_k/top_p/seed accepted and validated; seeded
    sampled responses reproduce exactly."""

    async def body(client):
        payload = {"text": "summarize: hello there", "temperature": 0.9,
                   "top_p": 0.95, "seed": 7}
        r1 = await client.post("/predict", json=payload)
        assert r1.status == 200
        r2 = await client.post("/predict", json=payload)
        out1, out2 = await r1.json(), await r2.json()
        assert out1["prediction"]["text"] == out2["prediction"]["text"]
        # Validation: bad ranges are 400s, counted like other parse 400s.
        bad = await client.post(
            "/predict", json={"text": "x", "temperature": -1}
        )
        assert bad.status == 400
        bad = await client.post(
            "/predict", json={"text": "x", "top_p": 0}
        )
        assert bad.status == 400
        bad = await client.post(
            "/predict", json={"text": "x", "top_k": "many"}
        )
        assert bad.status == 400

    _run(tiny_t5_bundle, body)


def test_engine_exception_maps_to_500():
    async def main(client, engine, batcher, app):
        def boom(feats):
            raise RuntimeError("device on fire")

        engine.run_batch = boom
        resp = await client.post("/predict", json={"text": "hello"})
        assert resp.status == 500
        body = await resp.text()
        assert "device on fire" not in body  # no internals leaked
        mtext = await (await client.get("/metrics")).text()
        assert 'status="500"' in mtext

    _serve(tiny_bert_bundle, main)


def test_readyz_surfaces_warmup_error():
    """If warmup dies, /readyz must say WHY instead of a silent
    never-ready server (ADVICE medium #1)."""

    async def outer():
        cfg = _cfg(warmup=True)
        bundle = tiny_bert_bundle()
        engine = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))

        def bad_warmup():
            raise RuntimeError("compile exploded")

        engine.warmup = bad_warmup
        batcher = Batcher(engine, cfg)
        app = build_app(cfg, bundle, engine, batcher)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            for _ in range(100):
                resp = await client.get("/readyz")
                body = await resp.json()
                if body.get("error"):
                    break
                await asyncio.sleep(0.05)
            assert resp.status == 503
            assert "compile exploded" in body["error"]
            st = await (await client.get("/status")).json()
            assert st["ready"] is False
            assert "compile exploded" in st["ready_error"]
        finally:
            await client.close()

    asyncio.run(outer())


def test_parse_errors_counted_in_metrics():
    async def main(client, engine, batcher, app):
        resp = await client.post(
            "/predict", data=b"{bad json", headers={"Content-Type": "application/json"}
        )
        assert resp.status == 400
        resp = await client.post("/predict", json={"nope": 1})
        assert resp.status == 400
        mtext = await (await client.get("/metrics")).text()
        assert 'status="400"' in mtext
        # /status reports the compiled-bucket inventory.
        st = await (await client.get("/status")).json()
        assert st["batch_buckets"] == [1, 2, 4, 8]
        assert st["seq_buckets"] == [16, 32, 64]

    _serve(tiny_bert_bundle, main)


def test_registration_client():
    """Parent-server registration: retry-POST until acked."""

    from aiohttp import web

    from mlmicroservicetemplate_tpu.api.registration import register_with_parent

    async def main():
        seen = []
        fails = {"n": 2}  # fail the first 2 attempts to exercise the retry loop

        async def register(request):
            if fails["n"] > 0:
                fails["n"] -= 1
                return web.Response(status=500)
            seen.append(await request.json())
            return web.json_response({"ok": True})

        parent = web.Application()
        parent.router.add_post("/register", register)
        server = TestServer(parent)
        await server.start_server()
        try:
            cfg = _cfg(
                server_url=f"http://localhost:{server.port}",
                register_retry_s=0.01,
                register_max_tries=10,
            )
            ok = await register_with_parent(cfg, "bert-base")
            assert ok
            assert seen and seen[0]["name"] == "bert-base"
        finally:
            await server.close()

    asyncio.run(main())


def test_registration_heartbeat_reregisters():
    """With REGISTER_HEARTBEAT_S set, the loop re-registers, so a
    restarted parent re-learns the service."""

    from aiohttp import web

    from mlmicroservicetemplate_tpu.api.registration import registration_loop

    async def main():
        count = {"n": 0}

        async def register(request):
            count["n"] += 1
            return web.json_response({"ok": True})

        parent = web.Application()
        parent.router.add_post("/register", register)
        server = TestServer(parent)
        await server.start_server()
        try:
            cfg = _cfg(
                server_url=f"http://localhost:{server.port}",
                register_retry_s=0.01,
                register_max_tries=3,
                register_heartbeat_s=0.05,
            )
            task = asyncio.create_task(registration_loop(cfg, "bert-base"))
            await asyncio.sleep(0.35)
            task.cancel()
            try:
                await task
            except asyncio.CancelledError:
                pass
            assert count["n"] >= 3, count  # initial + >=2 heartbeats
        finally:
            await server.close()

    asyncio.run(main())


def test_predict_max_tokens_and_stop():
    """max_tokens caps the generation exactly (stream and non-stream)
    and stop strings truncate at first occurrence; bad values are 400s."""

    async def body(client):
        # Non-stream with max_tokens: the returned text comes from a
        # trimmed token row (tiny t5 has an untied random head, so it
        # emits visible tokens).
        r_full = await client.post("/predict", json={"text": "summarize: hello"})
        r_capped = await client.post(
            "/predict", json={"text": "summarize: hello", "max_tokens": 2}
        )
        assert r_capped.status == 200
        full_text = (await r_full.json())["prediction"]["text"]
        capped_text = (await r_capped.json())["prediction"]["text"]
        assert len(capped_text) <= len(full_text)

        # Stream with max_tokens=3: at most 3 tokens reported.
        resp = await client.post(
            "/predict",
            json={"text": "summarize: hello", "stream": True, "max_tokens": 3},
        )
        assert resp.status == 200
        lines = [json.loads(l) for l in (await resp.text()).strip().splitlines()]
        assert lines[-1]["done"] is True
        assert lines[-1]["tokens_generated"] <= 3
        assert lines[-1]["finish_reason"] == "length"

        # Stop string: truncate the non-stream text at its first char.
        if full_text:
            stop_ch = full_text[0]
            r_stop = await client.post(
                "/predict", json={"text": "summarize: hello", "stop": stop_ch}
            )
            assert (await r_stop.json())["prediction"]["text"] == ""

        # Validation.
        bad = await client.post("/predict", json={"text": "x", "max_tokens": 0})
        assert bad.status == 400
        bad = await client.post("/predict", json={"text": "x", "stop": [1]})
        assert bad.status == 400

    _run(tiny_t5_bundle, body)


def test_stream_stop_deltas_consistent_and_device_budget():
    """Streamed deltas with a stop string concatenate to EXACTLY the
    final prediction.text (stop-prefix holdback — an emitted delta can
    never be retracted), and a fully-capped non-stream batch exits the
    device loop at the first chunk boundary."""

    async def main(client, engine, batcher, app):
        r = await client.post("/predict", json={"text": "summarize: hello"})
        full_text = (await r.json())["prediction"]["text"]
        if len(full_text) >= 2:
            stop = full_text[1]  # fires after at least one emitted char
            resp = await client.post(
                "/predict",
                json={"text": "summarize: hello", "stream": True, "stop": stop},
            )
            lines = [json.loads(l) for l in (await resp.text()).strip().splitlines()]
            assert lines[-1]["done"] is True
            deltas = "".join(l.get("delta", "") for l in lines[:-1])
            assert deltas == lines[-1]["prediction"]["text"]
            assert stop not in deltas

        # Device-side budget: max_tokens=1 must stop the while_loop at
        # the first chunk (4 steps), not the full budget (8).
        r = await client.post(
            "/predict", json={"text": "summarize: hello", "max_tokens": 1}
        )
        assert r.status == 200
        assert engine.last_decode_steps == 4

    _serve(tiny_t5_bundle, main)


def test_v1_completions_compat():
    """OpenAI-style /v1/completions rides the same serving path:
    non-stream returns choices[0].text == /predict's text; SSE stream
    deltas concatenate to the same; validation and model-kind 400s."""

    async def body(client):
        r_pred = await client.post("/predict", json={"text": "summarize: hi"})
        want = (await r_pred.json())["prediction"]["text"]

        r = await client.post("/v1/completions", json={"prompt": "summarize: hi"})
        assert r.status == 200
        out = await r.json()
        assert out["object"] == "text_completion"
        assert out["choices"][0]["text"] == want

        r = await client.post(
            "/v1/completions", json={"prompt": "summarize: hi", "stream": True}
        )
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("text/event-stream")
        raw = await r.text()
        events = [l[len("data: "):] for l in raw.splitlines()
                  if l.startswith("data: ")]
        assert events[-1] == "[DONE]"
        texts = "".join(
            json.loads(e)["choices"][0]["text"] for e in events[:-1]
        )
        assert texts == want

        # prompt list of one is accepted; empty prompt is a 400.
        r = await client.post("/v1/completions", json={"prompt": ["summarize: hi"]})
        assert r.status == 200
        r = await client.post("/v1/completions", json={"prompt": ""})
        assert r.status == 400
        # max_tokens caps like /predict.
        r = await client.post(
            "/v1/completions", json={"prompt": "summarize: hi", "max_tokens": 1}
        )
        assert r.status == 200

    _run(tiny_t5_bundle, body)


def test_v1_completions_rejects_non_generative():
    async def body(client):
        r = await client.post("/v1/completions", json={"prompt": "hi"})
        assert r.status == 400

    _run(tiny_bert_bundle, body)


def test_v1_chat_completions():
    """Chat endpoint: rendered messages ride the same path — content
    equals /v1/completions on the rendered prompt; SSE chunk deltas
    concatenate to it; malformed messages are 400s."""
    import os

    from mlmicroservicetemplate_tpu.api.app import _render_chat

    messages = [
        {"role": "system", "content": "be brief"},
        {"role": "user", "content": "summarize: hello"},
    ]
    rendered = _render_chat(messages)
    assert rendered.endswith("assistant:")

    async def body(client):
        r_ref = await client.post(
            "/v1/completions", json={"prompt": rendered}
        )
        want = (await r_ref.json())["choices"][0]["text"]

        r = await client.post("/v1/chat/completions", json={"messages": messages})
        assert r.status == 200
        out = await r.json()
        assert out["object"] == "chat.completion"
        msg = out["choices"][0]["message"]
        assert msg["role"] == "assistant" and msg["content"] == want

        r = await client.post(
            "/v1/chat/completions", json={"messages": messages, "stream": True}
        )
        assert r.status == 200
        events = [l[len("data: "):] for l in (await r.text()).splitlines()
                  if l.startswith("data: ")]
        assert events[-1] == "[DONE]"
        parsed = [json.loads(e) for e in events[:-1]]
        assert parsed[0]["choices"][0]["delta"] == {"role": "assistant"}
        content = "".join(
            p["choices"][0]["delta"].get("content", "") for p in parsed
        )
        assert content == want
        assert parsed[-1]["choices"][0]["finish_reason"] in ("stop", "length")

        # Validation.
        r = await client.post("/v1/chat/completions", json={"messages": []})
        assert r.status == 400
        r = await client.post(
            "/v1/chat/completions",
            json={"messages": [{"role": "wizard", "content": "x"}]},
        )
        assert r.status == 400

    _run(tiny_t5_bundle, body)


def test_chat_template_llama2(monkeypatch):
    from mlmicroservicetemplate_tpu.api.app import _render_chat

    monkeypatch.setenv("CHAT_TEMPLATE", "llama2")
    out = _render_chat([
        {"role": "system", "content": "be brief"},
        {"role": "user", "content": "hi"},
        {"role": "assistant", "content": "hello"},
        {"role": "user", "content": "more"},
    ])
    assert out.startswith("[INST] <<SYS>>\nbe brief\n<</SYS>>\n\nhi [/INST] hello")
    assert out.endswith("[INST] more [/INST]")
    monkeypatch.setenv("CHAT_TEMPLATE", "nope")
    import pytest

    # Unknown template = SERVER misconfiguration (handler maps to 500).
    with pytest.raises(LookupError, match="unknown CHAT_TEMPLATE"):
        _render_chat([{"role": "user", "content": "x"}])


def test_chat_template_llama2_edge_cases(monkeypatch):
    """Consecutive user messages accumulate; a transcript ending on an
    assistant turn does NOT append an empty open [INST]."""
    from mlmicroservicetemplate_tpu.api.app import _render_chat

    monkeypatch.setenv("CHAT_TEMPLATE", "llama2")
    out = _render_chat([
        {"role": "user", "content": "doc: abc"},
        {"role": "user", "content": "summarize it"},
    ])
    assert "doc: abc\nsummarize it" in out
    out = _render_chat([
        {"role": "user", "content": "hi"},
        {"role": "assistant", "content": "hello"},
    ])
    assert not out.endswith("[/INST]") or out.endswith("hello")
    assert "[INST]  [/INST]" not in out
    # Assistant-first transcripts continue as-is (no empty [INST]).
    out = _render_chat([
        {"role": "assistant", "content": "hello there"},
        {"role": "user", "content": "and?"},
    ])
    assert out.startswith("hello there") and "[INST]  [/INST]" not in out
    # No user message at all has no llama2 rendering — client error.
    import pytest

    with pytest.raises(ValueError, match="user message"):
        _render_chat([{"role": "system", "content": "sys only"}])


def test_chat_templates_chatml_zephyr_llama3(monkeypatch):
    """Golden rendered transcripts for the chat-tuned template family
    (VERDICT r3 item 5): each format's markers, ordering, and trailing
    assistant cue are exact."""
    from mlmicroservicetemplate_tpu.api.app import _render_chat

    messages = [
        {"role": "system", "content": "be brief"},
        {"role": "user", "content": "hi"},
        {"role": "assistant", "content": "hello"},
        {"role": "user", "content": "more"},
    ]

    monkeypatch.setenv("CHAT_TEMPLATE", "chatml")
    assert _render_chat(messages) == (
        "<|im_start|>system\nbe brief<|im_end|>\n"
        "<|im_start|>user\nhi<|im_end|>\n"
        "<|im_start|>assistant\nhello<|im_end|>\n"
        "<|im_start|>user\nmore<|im_end|>\n"
        "<|im_start|>assistant\n"
    )

    monkeypatch.setenv("CHAT_TEMPLATE", "zephyr")
    assert _render_chat(messages) == (
        "<|system|>\nbe brief</s>\n"
        "<|user|>\nhi</s>\n"
        "<|assistant|>\nhello</s>\n"
        "<|user|>\nmore</s>\n"
        "<|assistant|>\n"
    )

    monkeypatch.setenv("CHAT_TEMPLATE", "llama3")
    out = _render_chat(messages)
    assert out == (
        "<|start_header_id|>system<|end_header_id|>\n\nbe brief<|eot_id|>"
        "<|start_header_id|>user<|end_header_id|>\n\nhi<|eot_id|>"
        "<|start_header_id|>assistant<|end_header_id|>\n\nhello<|eot_id|>"
        "<|start_header_id|>user<|end_header_id|>\n\nmore<|eot_id|>"
        "<|start_header_id|>assistant<|end_header_id|>\n\n"
    )
    # BOS belongs to the tokenizer, never the rendered string (it
    # would be doubled by SentencePiece add_bos).
    assert "<|begin_of_text|>" not in out


def test_chat_template_validation_probe():
    """validate_chat_template flags markers the serving vocabulary
    shatters (wrong-template detector) and passes ones it knows."""
    from helpers import tiny_t5_bundle
    from mlmicroservicetemplate_tpu.api.chat import validate_chat_template

    tok = tiny_t5_bundle().tokenizer
    # plain has no markers: never warns, on any tokenizer.
    assert validate_chat_template("plain", tok) == []
    assert validate_chat_template("plain", None) == []
    # A byte/wordpiece-style tiny tokenizer shatters "<|im_start|>".
    warns = validate_chat_template("chatml", tok)
    assert warns and "<|im_start|>" in warns[0]


def test_build_app_rejects_unknown_template_and_warns_mismatch(monkeypatch):
    """Startup validation: unknown CHAT_TEMPLATE raises; a known
    template whose markers the tokenizer shatters surfaces warnings in
    app state (and /status)."""
    from mlmicroservicetemplate_tpu.api.app import K_STATE

    cfg = _cfg()
    bundle = tiny_t5_bundle()
    engine = InferenceEngine(bundle, cfg, ReplicaSet(make_mesh(1)))
    batcher = Batcher(engine, cfg)
    monkeypatch.setenv("CHAT_TEMPLATE", "nope")
    with pytest.raises(ValueError, match="unknown CHAT_TEMPLATE"):
        build_app(cfg, bundle, engine, batcher)
    monkeypatch.setenv("CHAT_TEMPLATE", "zephyr")
    app = build_app(cfg, bundle, engine, batcher)
    assert app[K_STATE]["chat_template"] == "zephyr"
    assert app[K_STATE]["chat_template_warnings"]  # tiny tok shatters <|user|>


def test_v1_models_usage_and_explicit_400s():
    """/v1/models lists the served model; usage appears on non-stream
    JSON and final SSE chunks of both /v1 endpoints; unsupported
    OpenAI fields (n>1, logprobs, best_of>1) 400 explicitly."""

    async def body(client):
        r = await client.get("/v1/models")
        assert r.status == 200
        out = await r.json()
        assert out["object"] == "list"
        assert out["data"][0]["id"] == "t5-small"
        assert out["data"][0]["object"] == "model"

        # Non-stream completions: usage consistent with the prompt.
        r = await client.post("/v1/completions", json={"prompt": "summarize: hi"})
        u = (await r.json())["usage"]
        assert u["prompt_tokens"] > 0 and u["completion_tokens"] >= 1
        assert u["total_tokens"] == u["prompt_tokens"] + u["completion_tokens"]

        # Streaming: usage appears ONLY when stream_options asks — the
        # OpenAI contract.  Unsolicited: no usage key on any chunk.
        r = await client.post(
            "/v1/completions", json={"prompt": "summarize: hi", "stream": True}
        )
        events = [l[len("data: "):] for l in (await r.text()).splitlines()
                  if l.startswith("data: ")]
        assert events[-1] == "[DONE]"
        assert all("usage" not in json.loads(e) for e in events[:-1])
        # Requested: every chunk has usage: null, and one extra final
        # chunk (empty choices) carries the numbers.
        r = await client.post(
            "/v1/completions",
            json={"prompt": "summarize: hi", "stream": True,
                  "stream_options": {"include_usage": True}},
        )
        events = [l[len("data: "):] for l in (await r.text()).splitlines()
                  if l.startswith("data: ")]
        assert events[-1] == "[DONE]"
        final = json.loads(events[-2])
        assert final["choices"] == []
        assert final["usage"]["total_tokens"] == (
            final["usage"]["prompt_tokens"] + final["usage"]["completion_tokens"]
        )
        assert final["usage"]["completion_tokens"] >= 1
        assert all(json.loads(e)["usage"] is None for e in events[:-2])

        # Chat: both shapes too.
        messages = [{"role": "user", "content": "summarize: hi"}]
        r = await client.post("/v1/chat/completions", json={"messages": messages})
        u = (await r.json())["usage"]
        assert u["completion_tokens"] >= 1 and u["prompt_tokens"] > 0
        r = await client.post(
            "/v1/chat/completions",
            json={"messages": messages, "stream": True,
                  "stream_options": {"include_usage": True}},
        )
        events = [l[len("data: "):] for l in (await r.text()).splitlines()
                  if l.startswith("data: ")]
        final = json.loads(events[-2])
        assert final["choices"] == [] and final["usage"]["completion_tokens"] >= 1
        r = await client.post(
            "/v1/chat/completions", json={"messages": messages, "stream": True}
        )
        events = [l[len("data: "):] for l in (await r.text()).splitlines()
                  if l.startswith("data: ")]
        assert all("usage" not in json.loads(e) for e in events[:-1])

        # max_tokens caps completion_tokens exactly.
        r = await client.post(
            "/v1/completions", json={"prompt": "summarize: hi", "max_tokens": 1}
        )
        assert (await r.json())["usage"]["completion_tokens"] <= 1

        # Unsupported OpenAI fields: explicit 400s, not silent drops.
        for bad in (
            {"prompt": "x", "n": 2},
            {"prompt": "x", "best_of": 3},
            {"prompt": "x", "logprobs": 5},
            {"prompt": "x", "top_logprobs": 1},
        ):
            r = await client.post("/v1/completions", json=bad)
            assert r.status == 400, bad
        r = await client.post(
            "/v1/chat/completions",
            json={"messages": [{"role": "user", "content": "x"}], "n": 2},
        )
        assert r.status == 400
        # n=1 / null are fine (clients send them explicitly).
        r = await client.post("/v1/completions", json={"prompt": "summarize: hi", "n": 1})
        assert r.status == 200

    _run(tiny_t5_bundle, body)


def test_usage_stop_truncation_consistent_and_logprobs_zero():
    """Stop-string truncation trims completion_tokens identically on
    stream and non-stream paths; logprobs=0 (a real legacy request we
    don't serve) is an explicit 400, not a silent drop."""

    async def body(client):
        r = await client.post("/predict", json={"text": "summarize: hello"})
        full_text = (await r.json())["prediction"]["text"]
        if len(full_text) >= 2:
            stop = full_text[1]
            r = await client.post(
                "/v1/completions",
                json={"prompt": "summarize: hello", "stop": stop},
            )
            out = await r.json()
            ns_usage = out["usage"]
            assert stop not in out["choices"][0]["text"]
            r = await client.post(
                "/v1/completions",
                json={"prompt": "summarize: hello", "stop": stop,
                      "stream": True,
                      "stream_options": {"include_usage": True}},
            )
            events = [l[len("data: "):] for l in (await r.text()).splitlines()
                      if l.startswith("data: ")]
            s_usage = json.loads(events[-2])["usage"]
            assert s_usage["completion_tokens"] == ns_usage["completion_tokens"]
            assert s_usage["prompt_tokens"] == ns_usage["prompt_tokens"]

        r = await client.post(
            "/v1/completions", json={"prompt": "x", "logprobs": 0}
        )
        assert r.status == 400
        # top_logprobs=0 means "none" — allowed.
        r = await client.post(
            "/v1/completions", json={"prompt": "summarize: hi", "top_logprobs": 0}
        )
        assert r.status == 200

    _run(tiny_t5_bundle, body)
