"""aiohttp application: the ``/predict`` endpoint + observability.

Request path parity with the reference (SURVEY.md §3.2): decode payload
(JSON text | multipart or raw image bytes) → preprocess (thread
offloaded — 1 vCPU, SURVEY.md §7.4.3) → dynamic-batching queue →
engine dispatch → postprocess → JSON.  Seq2seq requests with
``stream=true`` return an ``application/x-ndjson`` chunked body, one
``{"delta": ...}`` line per decoded token chunk (SURVEY.md §3.3).
"""

from __future__ import annotations

import asyncio
import json
import logging
import math
import os
import time
import uuid

import numpy as np
from aiohttp import web

from ..models.registry import KIND_SEQ2SEQ, ModelBundle, RawItem
from ..scheduler import Batcher, DeadlineExceededError, QueueFullError
from ..utils import metrics, pauses, tracing

log = logging.getLogger(__name__)

# Typed AppKeys (aiohttp's preferred registry): string keys work but
# emit a NotAppKeyWarning per lookup — noisy enough to bury real
# warnings in test runs and logs.
K_CFG = web.AppKey("cfg", object)
K_BUNDLE = web.AppKey("bundle", ModelBundle)
K_ENGINE = web.AppKey("engine", object)
K_BATCHER = web.AppKey("batcher", Batcher)
K_READY = web.AppKey("ready", asyncio.Event)
K_STARTED_AT = web.AppKey("started_at", float)
K_STATE = web.AppKey("state", dict)


def _error_body(etype: str, message: str, rid: str) -> dict:
    """The structured error shape every failure path speaks:
    ``{"error": {"type", "message", "request_id"}}``."""
    return {"error": {"type": etype, "message": message, "request_id": rid}}


def _internal_error(request: web.Request, message: str,
                    exc: BaseException | None = None) -> web.HTTPInternalServerError:
    """Structured 500: JSON error body + X-Request-Id, never a raw
    aiohttp error page."""
    rid = request.get("request_id", "")
    etype = type(exc).__name__ if exc is not None else "InternalServerError"
    return web.HTTPInternalServerError(
        text=json.dumps(_error_body(etype, message, rid)),
        content_type="application/json",
        headers={"X-Request-Id": rid},
    )


@web.middleware
async def request_id_middleware(request: web.Request, handler):
    """Echo (or mint) X-Request-Id on every response and convert any
    exception no handler mapped into the structured JSON 500 body —
    the log line and the client error share the same request_id, so an
    operator can find the traceback for any failed call.

    Also the TRACE=1 request-span anchor: one "request" span per call,
    keyed by the same request id every downstream span carries.  The
    span is recorded after the fact (``Tracer.add``) — event-loop
    coroutines interleave on one thread, so stack-based parenting
    would mis-attribute concurrent requests; correlation rides the
    request id instead."""
    rid = request.headers.get("X-Request-Id") or uuid.uuid4().hex[:16]
    request["request_id"] = rid
    tr = tracing.tracer()
    # The request's ONE start stamp: the "request" span, every latency
    # histogram and the first token's stages count from here.
    t0 = request["t0"] = time.monotonic()
    status = 500
    try:
        resp = await handler(request)
        status = resp.status
    except web.HTTPException as e:
        status = e.status
        e.headers.setdefault("X-Request-Id", rid)
        raise
    except asyncio.CancelledError:
        raise
    except Exception as e:
        bundle = request.app[K_BUNDLE]
        metrics.REQUESTS.labels(bundle.name, "500").inc()
        log.exception(
            "unhandled error on %s (request_id=%s)", request.path, rid,
            extra={"request_id": rid},
        )
        return web.json_response(
            _error_body(type(e).__name__, str(e) or "internal error", rid),
            status=500, headers={"X-Request-Id": rid},
        )
    finally:
        # Every exit of a request that was counted towards the decode
        # loop (400, shed, cancelled, a crash) stops being waited for.
        _settle_arrival(request)
        if tr is not None:
            tr.add(
                "request", cat="http", rid=rid, t0=t0,
                path=request.path, method=request.method, status=status,
            )
    if not resp.prepared:
        resp.headers.setdefault("X-Request-Id", rid)
    return resp


def _t0(request: web.Request) -> float:
    """When the middleware first saw this request (a handler driven
    without it stamps its own entry)."""
    t0 = request.get("t0")
    if t0 is None:
        t0 = request["t0"] = time.monotonic()
    return t0


async def _json_body(request: web.Request):
    """``request.json()`` with the parse itself as the phase
    ``api/parse`` (reading the body is the client's time, not in it)."""
    text = await request.text()
    with tracing.phase(
        "api/parse", cat="http", rid=request.get("request_id", "")
    ):
        return json.loads(text)


def _preprocess(bundle: ModelBundle, item: RawItem, rid: str) -> dict:
    """``bundle.preprocess`` on an executor thread, as the phase
    ``api/tokenize``: the wait for that thread is not in it."""
    with tracing.phase("api/tokenize", cat="http", rid=rid):
        return bundle.preprocess(item)


def _expect_stream(request: web.Request) -> None:
    """A streaming request's body is parsed (not earlier: a slow upload
    is never waited for): from here until its stream is queued, or the
    request ends without one, an idle decode loop counts it among what
    is still coming (``Batcher.expect_stream``) and admits the burst it
    belongs to as one wave."""
    request["arrival"] = request.app[K_BATCHER].expect_stream()


def _settle_arrival(request: web.Request) -> None:
    arrival = request.get("arrival")
    if arrival is not None:
        arrival.settle()


def build_app(cfg, bundle: ModelBundle, engine, batcher: Batcher) -> web.Application:
    app = web.Application(
        client_max_size=32 * 1024 * 1024,
        middlewares=[request_id_middleware],
    )
    app[K_CFG] = cfg
    app[K_BUNDLE] = bundle
    app[K_ENGINE] = engine
    app[K_BATCHER] = batcher
    app[K_READY] = asyncio.Event()
    app[K_STARTED_AT] = time.time()
    # Mutable runtime state lives in one dict: aiohttp freezes the app
    # mapping once started, so post-startup writes must go through this.
    app[K_STATE] = {"ready_error": None, "warmup_s": None, "tracing": False}

    app.router.add_post("/predict", handle_predict)
    app.router.add_post("/v1/completions", handle_completions)
    app.router.add_post("/v1/chat/completions", handle_chat_completions)
    app.router.add_get("/v1/streams/{rid}", handle_stream_attach)
    app.router.add_get("/v1/models", handle_models)
    app.router.add_get("/healthz", handle_healthz)
    app.router.add_get("/readyz", handle_readyz)
    app.router.add_get("/status", handle_status)
    app.router.add_get("/metrics", handle_metrics)
    app.router.add_get("/debug/trace", handle_trace)
    app.router.add_get("/debug/engine", handle_engine_debug)
    app.router.add_post("/debug/profile", handle_profile)

    # Bulk inference lane (JOBS_ENABLED; jobs/api.py): the /v1/batches
    # routes exist only when the Batcher built a JobManager — with the
    # knob unset the HTTP surface is bit-identical to pre-jobs serving.
    if getattr(batcher, "jobs", None) is not None:
        from ..jobs.api import add_job_routes

        add_job_routes(app, batcher.jobs)

    # A misconfigured CHAT_TEMPLATE must fail at STARTUP, not as
    # request-time 500s once the server already passed /readyz.
    from .chat import TEMPLATES, validate_chat_template

    template = os.environ.get("CHAT_TEMPLATE", "plain").lower()
    if template not in TEMPLATES:
        raise ValueError(
            f"unknown CHAT_TEMPLATE {template!r} ({'|'.join(TEMPLATES)})"
        )
    # Template↔model pairing check: probe the serving tokenizer for the
    # template's special markers; a vocabulary that shatters them was
    # not tuned on this format — serving would silently mis-prompt the
    # checkpoint (e.g. llama2 [INST] against a zephyr-tuned TinyLlama).
    tmpl_warnings = (
        validate_chat_template(template, bundle.tokenizer)
        if bundle.kind == KIND_SEQ2SEQ
        else []
    )
    for w in tmpl_warnings:
        log.warning("%s", w)
    app[K_STATE]["chat_template"] = template
    app[K_STATE]["chat_template_warnings"] = tmpl_warnings

    app.on_startup.append(_on_startup)
    app.on_cleanup.append(_on_cleanup)
    return app


async def _on_startup(app: web.Application) -> None:
    cfg, engine, batcher = app[K_CFG], app[K_ENGINE], app[K_BATCHER]
    await batcher.start()
    # The process's own pauses (utils/pauses.py), always on: this event
    # loop's lag and the collector's pauses.
    pauses.GC.install()
    app[K_STATE]["loop_lag"] = lag = pauses.EventLoopLag(app[K_BUNDLE].name)
    lag.start(asyncio.get_running_loop())

    async def warm_then_ready():
        # Failures here must be loud and visible: a swallowed warmup
        # exception leaves the server not-ready forever with zero
        # diagnostic.  The error is logged AND surfaced via /readyz.
        try:
            if cfg.warmup:
                loop = asyncio.get_running_loop()
                app[K_STATE]["warmup_s"] = await loop.run_in_executor(
                    None, engine.warmup
                )
                # Continuous-batching executables (slot insert, batched
                # chunk) compile in the same not-ready window.
                await loop.run_in_executor(None, batcher.warmup)
            else:
                # Canary dispatch: readiness means "the device answers",
                # not just "the process is up".
                await _canary(app)
        except asyncio.CancelledError:
            raise
        except Exception as e:
            app[K_STATE]["ready_error"] = f"{type(e).__name__}: {e}"
            log.exception("warmup/canary failed; server will stay not-ready")
            return
        # The boot timeline closes here: total, unnamed and the phases
        # go out (/status.compile.boot, boot_phase_seconds), and an
        # executable compiled from now on is a recompile, reported as one.
        from ..runtime.compile_cache import mark_ready

        boot = mark_ready(app[K_BUNDLE].name)
        app[K_READY].set()
        log.info("model %s ready (boot %.1f s, %.1f s of it under no phase)",
                 app[K_BUNDLE].name, boot["total_s"], boot["unnamed_s"])
        # Durable serving (JOURNAL_DIR): replay the write-ahead journal
        # AFTER warmup so resumed streams never pay request-path
        # compiles, re-admitting every incomplete stream for
        # token-identical continuation (runtime/durability.py).
        try:
            await _replay_journal(app)
        except Exception:
            log.exception("journal replay failed (serving continues)")
        # Bulk jobs (JOBS_ENABLED): re-admit every incomplete job from
        # its last completed line — after the stream replay, so resumed
        # interactive streams claim capacity before bulk backfill does.
        try:
            jobs = getattr(app[K_BATCHER], "jobs", None)
            if jobs is not None:
                jobs.replay()
        except Exception:
            log.exception("job replay failed (serving continues)")

    # Tasks land in the K_STATE dict, not the app mapping: aiohttp has
    # frozen the app by the time on_startup fires, and writes to a
    # frozen app raise a DeprecationWarning (slated to become an error).
    app[K_STATE]["_ready_task"] = asyncio.get_running_loop().create_task(
        warm_then_ready()
    )

    if cfg.server_url:
        from .registration import registration_loop

        app[K_STATE]["_register_task"] = asyncio.get_running_loop().create_task(
            registration_loop(cfg, app[K_BUNDLE].name)
        )


async def _canary(app: web.Application) -> None:
    bundle = app[K_BUNDLE]
    if bundle.kind == "image_classification":
        # uint8 like every real image path (the pipeline's wire dtype).
        feats = {"image": np.zeros((bundle.image_size, bundle.image_size, 3), np.uint8)}
    else:
        feats = {"input_ids": np.ones(8, np.int32), "length": np.int32(8)}
    # The probe dispatch runs under the engine watchdog (the batcher's
    # guarded batch path), so a wedged device raises
    # DispatchTimeoutError at DISPATCH_TIMEOUT_S and flips /readyz
    # unready via warm_then_ready's error capture.  The asyncio-level
    # bound is the backstop for a hang that wedges OUTSIDE guarded
    # code (margin: queue wait + transient retries).
    timeout = float(getattr(app[K_CFG], "dispatch_timeout_s", 0.0) or 0.0)
    coro = app[K_BATCHER].submit(feats)
    if timeout > 0:
        try:
            await asyncio.wait_for(coro, timeout * 2 + 5.0)
        except asyncio.TimeoutError:
            raise TimeoutError(
                f"canary dispatch exceeded DISPATCH_TIMEOUT_S={timeout}s; "
                "device wedged?"
            )
    else:
        await coro


async def _pump_resumed(rec, gen) -> None:
    """Drain one journal-resumed stream's continuation into its
    reconnect record (the stream runs headless — its original client
    connection died with the old process)."""
    try:
        async for chunk in gen:
            rec.extend(chunk)
    except Exception as e:
        rec.fail(str(e) or type(e).__name__)
    else:
        rec.complete()


async def _replay_journal(app: web.Application) -> None:
    """Re-admit every incomplete journaled stream through the resume
    machinery and expose all journaled streams (finished ones too —
    reconnects are idempotent) at ``GET /v1/streams/{request_id}``."""
    engine = app[K_ENGINE]
    journal = getattr(engine, "journal", None)
    if journal is None:
        return
    from ..runtime.durability import StreamRecord, StreamRegistry

    bundle: ModelBundle = app[K_BUNDLE]
    batcher = app[K_BATCHER]
    registry = StreamRegistry()
    app[K_STATE]["streams"] = registry
    resumed = 0
    tasks = app[K_STATE].setdefault("_resume_tasks", [])
    for rs in list(journal.streams.values()):
        rec = registry.add(StreamRecord(
            rs.rid, rs.tokens,
            max_tokens=rs.feats.get("max_tokens"), stop=rs.stop,
        ))
        if rs.done:
            rec.complete()
            continue
        try:
            gen = batcher.resume_stream(rs.np_feats(), rs.tokens)
        except Exception as e:
            log.exception("journal replay: could not resume %s", rs.rid)
            rec.fail(f"resume failed: {e}")
            metrics.JOURNAL_REPLAY.labels(bundle.name, "failed").inc()
            continue
        if gen is None:
            # The cursor already covers the whole budget: nothing left
            # to decode — the reconnect serves the journaled tokens.
            rec.complete()
            journal.done(rs.rid)
            metrics.JOURNAL_REPLAY.labels(bundle.name, "complete").inc()
            continue
        tasks.append(
            asyncio.get_running_loop().create_task(_pump_resumed(rec, gen))
        )
        metrics.JOURNAL_REPLAY.labels(bundle.name, "resumed").inc()
        resumed += 1
    if resumed:
        log.info(
            "journal replay: %d incomplete stream(s) re-admitted for "
            "token-identical resume (reconnect via GET /v1/streams/"
            "{request_id})", resumed,
        )


async def _on_cleanup(app: web.Application) -> None:
    lag = app[K_STATE].get("loop_lag")
    if lag is not None:
        lag.stop()
    for task in app[K_STATE].get("_resume_tasks", ()):
        task.cancel()
        try:
            await task
        except (asyncio.CancelledError, Exception):
            pass
    for key in ("_ready_task", "_register_task"):
        task = app[K_STATE].get(key)
        if task is not None:
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):
                pass
    await app[K_BATCHER].stop()
    # The app's life is over: what this process compiles next is no
    # recompile of a serving step, and a service built after this one
    # (chip_smoke.py, tests) boots into an open table.
    tracing.boot_table().begin(None)


# ---------------------------------------------------------------------------
# scheduling headers / shed responses


def _sched_fields(request: web.Request) -> dict:
    """X-Priority / X-Deadline-Ms / X-Api-Key / X-Adapter headers → the
    scheduling fields the admission controller reads off the feats
    dict.  Malformed headers are client errors (400), not
    silently-defaulted surprises."""
    out: dict = {}
    p = request.headers.get("X-Priority")
    if p is not None:
        p = p.strip().lower()
        if p not in ("interactive", "batch"):
            raise web.HTTPBadRequest(
                reason='X-Priority must be "interactive" or "batch"'
            )
        out["priority"] = p
    d = request.headers.get("X-Deadline-Ms")
    if d is not None:
        try:
            dv = float(d)
        except ValueError:
            raise web.HTTPBadRequest(reason="X-Deadline-Ms must be a number")
        if not dv > 0:  # also rejects NaN
            raise web.HTTPBadRequest(reason="X-Deadline-Ms must be > 0")
        out["deadline_ms"] = dv
    # Tenancy (tenancy/accounts.py): classify the request ONCE at the
    # HTTP edge; everything downstream (quota gate, fair-share queue,
    # per-tenant metrics) reads feats["tenant"].  No registry = no
    # tenant field at all — single-tenant feats stay bit-identical.
    batcher = request.app[K_BATCHER]
    tenants = getattr(batcher, "tenants", None)
    if tenants is not None:
        spec = tenants.classify(request.headers.get("X-Api-Key"))
        if spec.name:
            out["tenant"] = spec.name
        if spec.adapter:
            out["adapter_id"] = spec.adapter
    a = request.headers.get("X-Adapter")
    if a is not None:
        a = a.strip()
        if not a:
            # Explicit opt-out of the tenant's default adapter.
            out.pop("adapter_id", None)
        else:
            pool = getattr(batcher, "adapters", None)
            if pool is None:
                raise web.HTTPBadRequest(
                    reason="X-Adapter requires ADAPTER_DIR to be configured"
                )
            if not pool.known(a):
                raise web.HTTPBadRequest(
                    reason=f"unknown adapter {a!r} (available: "
                           f"{', '.join(pool.ids()) or 'none'})"
                )
            out["adapter_id"] = a
    return out


def _shed_response(e: QueueFullError) -> web.HTTPException:
    """503 with Retry-After derived from queue depth × observed batch
    latency (the batcher stamps retry_after_s on the error); quota
    sheds are the caller's fault, not the server's, so they map to 429
    with the tenant's own window-drain Retry-After."""
    ra = max(1, int(math.ceil(getattr(e, "retry_after_s", None) or 1.0)))
    if getattr(e, "reason", "") == "quota":
        return web.HTTPTooManyRequests(
            reason=str(e) or "tenant quota exhausted, retry later",
            headers={"Retry-After": str(ra)},
        )
    return web.HTTPServiceUnavailable(
        reason=str(e) or "overloaded, retry later",
        headers={"Retry-After": str(ra)},
    )


def _deadline_response() -> web.HTTPGatewayTimeout:
    return web.HTTPGatewayTimeout(
        reason="deadline passed before dispatch; request shed"
    )


# ---------------------------------------------------------------------------
# /predict


async def _parse_request(request: web.Request) -> RawItem:
    ctype = request.content_type
    if ctype == "application/json":
        try:
            body = await _json_body(request)
        except json.JSONDecodeError:
            raise web.HTTPBadRequest(reason="invalid JSON body")
        if not isinstance(body, dict):
            raise web.HTTPBadRequest(reason="JSON body must be an object")
        return _parse_json_item(body)
    if ctype.startswith("multipart/"):
        reader = await request.multipart()
        async for part in reader:
            if part.name in ("file", "image", "upload") or (
                part.filename is not None
            ):
                data = await part.read(decode=False)
                if data:
                    return RawItem(image=bytes(data))
            elif part.name == "text":
                text = (await part.text()).strip()
                if text:
                    return RawItem(text=text)
        raise web.HTTPBadRequest(reason="multipart body had no file/image/text part")
    # Raw image bytes (image/* or octet-stream).
    data = await request.read()
    if not data:
        raise web.HTTPBadRequest(reason="empty request body")
    return RawItem(image=data)


def _parse_json_item(body: dict) -> RawItem:
    """Validate a JSON /predict-shaped body into a RawItem (shared with
    the /v1/completions translation; all failures are HTTPBadRequest)."""
    text = body.get("text") or body.get("input")
    if not isinstance(text, str) or not text:
        raise web.HTTPBadRequest(reason='JSON body needs a non-empty "text" field')
    stream = bool(body.get("stream", False))
    # Sampling controls (generative models; greedy when absent).
    try:
        temperature = float(body.get("temperature") or 0.0)
        top_k = int(body.get("top_k") or 0)
        top_p = float(body.get("top_p") if body.get("top_p") is not None else 1.0)
        seed = body.get("seed")
        seed = int(seed) if seed is not None else None
    except (TypeError, ValueError):
        raise web.HTTPBadRequest(
            reason="temperature/top_p must be numbers, top_k/seed integers"
        )
    if temperature < 0 or not (0.0 < top_p <= 1.0) or top_k < 0:
        raise web.HTTPBadRequest(
            reason="need temperature >= 0, 0 < top_p <= 1, top_k >= 0"
        )
    if seed is not None and not (0 <= seed < 2**32):
        raise web.HTTPBadRequest(reason="seed must be in [0, 2**32)")
    try:
        max_tokens = body.get("max_tokens")
        max_tokens = int(max_tokens) if max_tokens is not None else None
    except (TypeError, ValueError):
        raise web.HTTPBadRequest(reason="max_tokens must be an integer")
    if max_tokens is not None and max_tokens < 1:
        raise web.HTTPBadRequest(reason="max_tokens must be >= 1")
    stop = body.get("stop")
    if stop is None:  # JSON null == absent (schema-generated clients)
        stop = ()
    if isinstance(stop, str):
        stop = (stop,)
    if not isinstance(stop, (list, tuple)) or len(stop) > 8 or not all(
        isinstance(s, str) and s for s in stop
    ):
        raise web.HTTPBadRequest(
            reason='"stop" must be a non-empty string or a list of up to 8'
        )
    return RawItem(
        text=text, stream=stream, temperature=temperature,
        top_k=top_k, top_p=top_p, seed=seed,
        max_tokens=max_tokens, stop=tuple(stop),
    )


async def handle_predict(request: web.Request) -> web.StreamResponse:
    app = request.app
    bundle: ModelBundle = app[K_BUNDLE]
    t0 = _t0(request)
    try:
        item = await _parse_request(request)
        sched = _sched_fields(request)
    except web.HTTPBadRequest:
        # Parse-level 400s must show up in /metrics like every other
        # terminal status — error rates are an observability surface.
        metrics.REQUESTS.labels(bundle.name, "400").inc()
        raise
    stream = item.stream or request.query.get("stream", "") in ("1", "true")
    if stream and bundle.kind == KIND_SEQ2SEQ:
        _expect_stream(request)

    loop = asyncio.get_running_loop()
    try:
        feats = await loop.run_in_executor(
            None, _preprocess, bundle, item, request.get("request_id", ""))
    except (ValueError, OSError) as e:
        # OSError covers PIL's UnidentifiedImageError on corrupt bytes.
        metrics.REQUESTS.labels(bundle.name, "400").inc()
        raise web.HTTPBadRequest(reason=str(e) or "undecodable payload")
    feats.update(sched)
    # Span/log correlation key for every downstream layer (scheduler
    # queue-wait, prefill windows, stream lifetime).
    feats["request_id"] = request.get("request_id", "")
    if getattr(app[K_ENGINE], "journal", None) is not None:
        # Durability annotations (runtime/durability.py): whether the
        # id came from the client (unary X-Request-Id dedup applies —
        # minted ids never repeat) and the stop strings (the reconnect
        # endpoint re-renders deltas with them after a restart).
        feats["rid_client"] = "X-Request-Id" in request.headers
        feats["stop_strs"] = list(item.stop)

    if stream and bundle.kind == KIND_SEQ2SEQ:
        return await _stream_predict(request, feats, t0, item)

    try:
        row = await app[K_BATCHER].submit(feats)
        if bundle.kind == KIND_SEQ2SEQ and item.max_tokens is not None:
            row = row[: item.max_tokens]
        # Postprocess sits inside the same try: EVERY terminal status on
        # /predict increments REQUESTS, including a postprocess crash.
        result = await loop.run_in_executor(None, bundle.postprocess, row)
        if bundle.kind == KIND_SEQ2SEQ and item.stop:
            result["prediction"]["text"] = _apply_stop(
                result["prediction"]["text"], item.stop
            )
    except QueueFullError as e:
        resp = _shed_response(e)
        metrics.REQUESTS.labels(bundle.name, str(resp.status)).inc()
        raise resp
    except DeadlineExceededError:
        metrics.REQUESTS.labels(bundle.name, "504").inc()
        raise _deadline_response()
    except Exception as e:
        # Engine/dispatch failure: surface as a structured 500 (with a
        # metric and a server-side traceback sharing the request_id),
        # not an opaque aiohttp error page.
        metrics.REQUESTS.labels(bundle.name, "500").inc()
        log.exception(
            "inference dispatch failed (request_id=%s)",
            request.get("request_id", ""),
        )
        raise _internal_error(request, "inference failed", e)
    dt = time.monotonic() - t0
    result["model"] = bundle.name
    result["timing_ms"] = round(dt * 1000.0, 3)
    metrics.REQUESTS.labels(bundle.name, "200").inc()
    metrics.LATENCY.labels(bundle.name).observe(dt)
    return web.json_response(result)


def _apply_stop(text: str, stops) -> str:
    """Truncate at the FIRST occurrence of any stop string."""
    cut = len(text)
    for s in stops:
        i = text.find(s)
        if i != -1:
            cut = min(cut, i)
    return text[:cut]


def _tokens_covering(decode, tokens, target_len: int) -> int:
    """Smallest n with len(decode(tokens[:n])) >= target_len — binary
    search plus a local walk-down, replacing the O(n^2) linear recount
    on the stop-string path (each decode is O(n); long generations with
    stop strings paid the square).

    Decoded length is monotone in the token count EXCEPT locally at
    multi-byte UTF-8 splits (a dangling prefix renders as replacement
    chars that a later byte can merge), so the bisection alone could
    land one token off the true minimum; the walk-down restores the
    smallest covering n through any such plateau.  Returns len(tokens)
    when even the full decode falls short (a truncated trailing byte
    sequence can decode shorter than the text it was cut from)."""
    if len(decode(tokens)) < target_len:
        return len(tokens)
    lo, hi = 0, len(tokens)
    while lo < hi:
        mid = (lo + hi) // 2
        if len(decode(tokens[:mid])) >= target_len:
            hi = mid
        else:
            lo = mid + 1
    while lo > 0 and len(decode(tokens[: lo - 1])) >= target_len:
        lo -= 1
    return lo


def _stop_holdback(text: str, stops) -> int:
    """Chars to withhold from streaming: the longest suffix of ``text``
    that is a strict prefix of some stop string — it may complete into
    a stop next chunk, and an emitted delta cannot be retracted."""
    hb = 0
    for s in stops:
        for k in range(min(len(s) - 1, len(text)), 0, -1):
            if text.endswith(s[:k]):
                hb = max(hb, k)
                break
    return hb


async def _delta_stream(bundle: ModelBundle, stream_iter, item: RawItem):
    """Shared token→text-delta machinery for BOTH streaming endpoints.

    Yields ``{"delta": str}`` events, then exactly one final
    ``{"done": True, "text", "tokens", "steps", "finish_reason"}``.
    Guarantees: concatenated deltas == final text; stop strings never
    appear in the output (prefix holdback — deltas are irrevocable —
    with the held-back suffix flushed when the stream ends for another
    reason); ``tokens`` never counts past a stop truncation;
    finish_reason is "stop" (EOS or stop string) or "length"
    (max_tokens / server decode budget).
    """
    eos, pad = bundle.cfg.eos_id, bundle.cfg.pad_id
    tokens: list[int] = []
    prev_text = ""
    steps = 0
    finished = False
    reason = "length"  # stream exhausting its budget = truncation

    def decode(toks: list[int]) -> str:
        return bundle.tokenizer.decode(np.array(toks, np.int32))

    async for chunk in stream_iter:
        steps += int(chunk.size)
        for t in chunk.tolist():
            if t == eos:
                finished, reason = True, "stop"
                break
            if item.max_tokens is not None and len(tokens) >= item.max_tokens:
                finished, reason = True, "length"
                break
            if t != pad or not tokens:
                tokens.append(int(t))
        text = decode(tokens)
        if item.stop:
            stopped = _apply_stop(text, item.stop)
            if stopped != text and len(stopped) >= len(prev_text):
                text, finished, reason = stopped, True, "stop"
                # tokens must not count past the truncation: keep the
                # smallest count whose decode covers the final text.
                tokens = tokens[: _tokens_covering(decode, tokens, len(text))]
            elif not finished:
                # Withhold any suffix that could complete into a stop
                # string next chunk.  (A "stop" inside already-emitted
                # text can only come from non-monotonic re-decodes of
                # partial byte sequences — emitted deltas are
                # irrevocable, so it is ignored above.)
                text = text[: len(text) - _stop_holdback(text, item.stop)]
        if len(text) < len(prev_text):
            text = prev_text  # emission only ever grows
        delta = text[len(prev_text):]
        prev_text = text
        yield {"delta": delta}
        if finished:
            break
    if not finished and item.stop:
        # Budget exhausted with a held-back suffix: it can no longer
        # complete into a stop string — flush it.
        text = _apply_stop(decode(tokens), item.stop)
        if len(text) > len(prev_text):
            yield {"delta": text[len(prev_text):]}
            prev_text = text
    yield {
        "done": True, "text": prev_text, "tokens": len(tokens),
        "steps": steps, "finish_reason": reason,
    }


async def _open_stream(request: web.Request, feats: dict, item: RawItem,
                       t0: float):
    """Open a stream and pull its FIRST event before any response bytes
    go out: a stream that queued under the scheduler and was then shed
    (evicted → 503, expired deadline → 504, drain → 503) still maps to
    a real HTTP status instead of a broken 200 body.  Also the TTFT
    observation point.  Returns (event_iterator, stream_iter)."""
    from ..engine.streams import StreamClosedError

    app = request.app
    bundle: ModelBundle = app[K_BUNDLE]
    rid = request.get("request_id", "")
    try:
        with tracing.phase("api/submit", cat="http", rid=rid):
            stream_iter = app[K_BATCHER].submit_stream(feats)
    except QueueFullError as e:
        resp = _shed_response(e)
        metrics.REQUESTS.labels(bundle.name, str(resp.status)).inc()
        raise resp
    finally:
        _settle_arrival(request)  # queued, or shed: no longer on its way
    events = _delta_stream(bundle, stream_iter, item)
    try:
        first = await events.__anext__()
    except QueueFullError as e:
        await stream_iter.aclose()
        resp = _shed_response(e)
        metrics.REQUESTS.labels(bundle.name, str(resp.status)).inc()
        raise resp
    except DeadlineExceededError:
        await stream_iter.aclose()
        metrics.REQUESTS.labels(bundle.name, "504").inc()
        raise _deadline_response()
    except StreamClosedError as e:
        await stream_iter.aclose()
        metrics.REQUESTS.labels(bundle.name, "503").inc()
        raise _shed_response(QueueFullError(str(e), reason="drain"))
    except StopAsyncIteration:
        # _delta_stream always yields a final event; defensive.
        metrics.REQUESTS.labels(bundle.name, "500").inc()
        raise _internal_error(request, "stream produced no events")
    # Admission-mode label: the continuous loop stamps "chunked" on the
    # feats dict it was handed when PREFILL_CHUNK routed this prompt to
    # windowed prefill; everything else is a monolithic prefill.
    now = time.monotonic()
    metrics.TTFT.labels(
        bundle.name, feats.get("prefill_mode", "monolithic")
    ).observe(now - t0)
    # The first token's sum closes here: handler entry to the queue
    # (stream_api), the queue (stream_queue_wait), the wave
    # (stream_admit), and the first emit's way back to this coroutine
    # (stream_handoff).  The decode loop stamps the two instants on the
    # feats dict it was handed; a path that does not (SPEC_DECODE's
    # per-stream route) observes neither.
    t_queued, t_emit = feats.get("t_queued"), feats.get("t_first_emit")
    if t_queued is not None and t_emit is not None:
        metrics.STREAM_API.labels(bundle.name).observe(max(0.0, t_queued - t0))
        metrics.STREAM_HANDOFF.labels(bundle.name).observe(
            max(0.0, now - t_emit))
        tr = tracing.tracer()
        if tr is not None:
            tr.add("api", cat="http", rid=rid, t0=t0, dur=t_queued - t0)
            tr.add("handoff", cat="http", rid=rid, t0=t_emit, dur=now - t_emit)

    async def chained():
        yield first
        async for ev in events:
            yield ev

    return chained(), stream_iter


async def _stream_predict(
    request: web.Request, feats: dict, t0: float, item: RawItem
) -> web.StreamResponse:
    """Chunked seq2seq streaming: ndjson lines of decoded-token deltas."""
    app = request.app
    bundle: ModelBundle = app[K_BUNDLE]
    rid = request.get("request_id", "")
    events, stream_iter = await _open_stream(request, feats, item, t0)
    resp = web.StreamResponse(
        status=200,
        headers={"Content-Type": "application/x-ndjson",
                 "X-Accel-Buffering": "no", "X-Request-Id": rid},
    )
    resp.enable_chunked_encoding()
    await resp.prepare(request)
    try:
        # On ANY exit — client disconnect mid-write included — close the
        # stream generator explicitly so the batcher's pump sees
        # `cancelled` now, not whenever GC finalizes the generator; an
        # abandoned stream must stop dispatching device chunks at the
        # next boundary.
        async for ev in events:
            if "delta" in ev:
                # One line per device chunk even when the decoded delta
                # is empty: clients get progress at chunk cadence.
                await resp.write(
                    (json.dumps({"delta": ev["delta"]}) + "\n").encode()
                )
                continue
            dt = time.monotonic() - t0
            await resp.write(
                (
                    json.dumps(
                        {
                            "done": True,
                            "prediction": {"text": ev["text"]},
                            "tokens_generated": ev["tokens"],
                            "decode_steps": ev["steps"],
                            "finish_reason": ev["finish_reason"],
                            "model": bundle.name,
                            "timing_ms": round(dt * 1000.0, 3),
                        }
                    )
                    + "\n"
                ).encode()
            )
            metrics.REQUESTS.labels(bundle.name, "200").inc()
            metrics.LATENCY.labels(bundle.name).observe(dt)
    except ConnectionError:
        pass  # client disconnected mid-write; nothing left to tell it
    except Exception as e:
        # Mid-stream failure AFTER the 200 went out: the only honest
        # signal left is a terminal in-band error line (same structured
        # shape as the unary JSON error body).
        metrics.REQUESTS.labels(bundle.name, "500").inc()
        log.exception("stream failed mid-flight (request_id=%s)", rid)
        try:
            await resp.write(
                (json.dumps(_error_body(
                    type(e).__name__, str(e) or "stream failed", rid
                )) + "\n").encode()
            )
        except ConnectionError:
            pass
    finally:
        await stream_iter.aclose()
        try:
            await resp.write_eof()
        except ConnectionError:
            pass  # client already gone; nothing left to finalize
    return resp


# ---------------------------------------------------------------------------
# /v1/completions — OpenAI-compatible alias over the same serving path


def _usage(feats: dict, completion_tokens: int) -> dict:
    """OpenAI ``usage`` object — the one response field nearly every
    client reads.  ``completion_tokens`` counts the tokens of the
    RETURNED text: capped by max_tokens and trimmed to a stop-string
    truncation — identical semantics on the stream and non-stream
    paths (the stream path trims in ``_delta_stream``)."""
    prompt = int(feats.get("length", 0))
    return {
        "prompt_tokens": prompt,
        "completion_tokens": int(completion_tokens),
        "total_tokens": prompt + int(completion_tokens),
    }


async def _generate_once(request: web.Request, feats: dict, item: RawItem):
    """Non-stream generation shared by /v1/completions and chat:
    submit → trim to max_tokens → apply stop strings → finish_reason.
    Returns (text, finish_reason, completion_token_count); maps
    failures to metered HTTP errors."""
    app = request.app
    bundle: ModelBundle = app[K_BUNDLE]
    loop = asyncio.get_running_loop()
    try:
        row = await app[K_BATCHER].submit(feats)
        full_len = int(np.count_nonzero(np.asarray(row) != bundle.cfg.pad_id))
        if item.max_tokens is not None:
            row = row[: item.max_tokens]
        result = await loop.run_in_executor(None, bundle.postprocess, row)
        text = result["prediction"]["text"]
        n_tok = min(full_len, item.max_tokens or full_len)
        stopped_by_string = False
        if item.stop:
            cut = _apply_stop(text, item.stop)
            stopped_by_string = cut != text
            if stopped_by_string:
                # Token count must not run past the truncation (same
                # rule as _delta_stream): smallest count whose decode
                # covers the final text.
                row_list = [int(t) for t in np.asarray(row).tolist()][:n_tok]
                n_tok = _tokens_covering(
                    lambda ts: bundle.tokenizer.decode(
                        np.array(ts, np.int32)
                    ),
                    row_list, len(cut),
                )
            text = cut
        finish = "stop" if (
            stopped_by_string
            or item.max_tokens is None
            or full_len <= item.max_tokens
        ) else "length"
        return text, finish, n_tok
    except QueueFullError as e:
        resp = _shed_response(e)
        metrics.REQUESTS.labels(bundle.name, str(resp.status)).inc()
        raise resp
    except DeadlineExceededError:
        metrics.REQUESTS.labels(bundle.name, "504").inc()
        raise _deadline_response()
    except Exception as e:
        metrics.REQUESTS.labels(bundle.name, "500").inc()
        log.exception(
            "completion failed (request_id=%s)",
            request.get("request_id", ""),
        )
        raise _internal_error(request, "inference failed", e)


async def _openai_prologue(request: web.Request, to_prompt):
    """Shared /v1 prologue: seq2seq gate, JSON parse, prompt derivation
    (``to_prompt(body) -> str`` — ValueError = client 400, LookupError =
    server-config 500), field translation onto /predict's validator,
    preprocess.  Returns (app, bundle, item, feats, t0, include_usage)."""
    app = request.app
    bundle: ModelBundle = app[K_BUNDLE]
    if bundle.kind != KIND_SEQ2SEQ:
        metrics.REQUESTS.labels(bundle.name, "400").inc()
        raise web.HTTPBadRequest(reason=f"{bundle.name} is not a generative model")
    t0 = _t0(request)
    try:
        body = await _json_body(request)
        assert isinstance(body, dict)
    except Exception:
        metrics.REQUESTS.labels(bundle.name, "400").inc()
        raise web.HTTPBadRequest(reason="invalid JSON body")
    # Unsupported OpenAI fields get an EXPLICIT 400, not a silent drop —
    # a client that asked for n=4 or logprobs and got neither would
    # otherwise misread the response as complete.
    unsupported = None
    if body.get("n") not in (None, 1):
        unsupported = '"n" > 1 is not supported (one choice per request)'
    elif body.get("best_of") not in (None, 1):
        unsupported = '"best_of" > 1 is not supported'
    elif (
        # logprobs=0 is a real legacy-completions request ("chosen
        # token's logprob, 0 alternatives") — only None/False mean
        # "not asked for"; top_logprobs=0 genuinely means none.
        # Identity checks: `in (None, False)` would eat 0 (0 == False).
        (body.get("logprobs") is not None and body.get("logprobs") is not False)
        or body.get("top_logprobs") not in (None, 0)
    ):
        unsupported = '"logprobs" is not supported'
    if unsupported:
        metrics.REQUESTS.labels(bundle.name, "400").inc()
        raise web.HTTPBadRequest(reason=unsupported)
    try:
        item = _parse_json_item({
            "text": to_prompt(body),
            "stream": bool(body.get("stream", False)),
            "temperature": body.get("temperature", 0.0),
            "top_k": body.get("top_k", 0),  # common extension field
            "top_p": body.get("top_p", 1.0),
            "seed": body.get("seed"),
            "max_tokens": body.get("max_tokens"),
            "stop": body.get("stop"),
        })
    except LookupError as e:
        metrics.REQUESTS.labels(bundle.name, "500").inc()
        log.error("%s (request_id=%s)", e, request.get("request_id", ""))
        raise _internal_error(request, str(e), e)
    except ValueError as e:
        metrics.REQUESTS.labels(bundle.name, "400").inc()
        raise web.HTTPBadRequest(reason=str(e))
    except web.HTTPBadRequest:
        metrics.REQUESTS.labels(bundle.name, "400").inc()
        raise
    try:
        sched = _sched_fields(request)
    except web.HTTPBadRequest:
        metrics.REQUESTS.labels(bundle.name, "400").inc()
        raise
    if item.stream:
        _expect_stream(request)
    loop = asyncio.get_running_loop()
    try:
        feats = await loop.run_in_executor(
            None, _preprocess, bundle, item, request.get("request_id", ""))
    except (ValueError, OSError) as e:
        metrics.REQUESTS.labels(bundle.name, "400").inc()
        raise web.HTTPBadRequest(reason=str(e) or "bad request")
    feats.update(sched)
    feats["request_id"] = request.get("request_id", "")
    if getattr(app[K_ENGINE], "journal", None) is not None:
        feats["rid_client"] = "X-Request-Id" in request.headers
        feats["stop_strs"] = list(item.stop)
    # OpenAI stream semantics: usage appears in a stream ONLY when the
    # client asked via stream_options.include_usage (then every chunk
    # carries "usage": null and one extra final chunk carries the
    # numbers) — an unsolicited usage chunk is a protocol deviation to
    # strict clients.  Non-stream responses always include usage.
    include_usage = bool(
        (body.get("stream_options") or {}).get("include_usage", False)
    )
    return app, bundle, item, feats, t0, include_usage


def _sse_frame(payload: dict) -> bytes:
    return (f"data: {json.dumps(payload)}\n\n").encode()


async def _sse_stream(request, feats, item, t0, events, preamble=None):
    """Shared SSE scaffolding for both /v1 streaming endpoints:
    503/504 shedding (with Retry-After), headers, the _delta_stream
    loop, [DONE], metrics and cleanup.  ``events(ev) -> list[bytes]``
    shapes each delta/final event; ``preamble`` is written first
    (chat's role chunk)."""
    app = request.app
    bundle: ModelBundle = app[K_BUNDLE]
    rid = request.get("request_id", "")
    ev_iter, stream_iter = await _open_stream(request, feats, item, t0)
    resp = web.StreamResponse(
        status=200,
        headers={"Content-Type": "text/event-stream",
                 "Cache-Control": "no-cache", "X-Accel-Buffering": "no",
                 "X-Request-Id": rid},
    )
    resp.enable_chunked_encoding()
    await resp.prepare(request)
    try:
        if preamble is not None:
            await resp.write(preamble)
        async for ev in ev_iter:
            for frame in events(ev):
                await resp.write(frame)
            if ev.get("done"):
                await resp.write(b"data: [DONE]\n\n")
                metrics.REQUESTS.labels(bundle.name, "200").inc()
                metrics.LATENCY.labels(bundle.name).observe(time.monotonic() - t0)
    except ConnectionError:
        pass  # client disconnected mid-write
    except Exception as e:
        # Terminal SSE error event before close — a structured signal
        # instead of an abrupt connection drop mid-200.
        metrics.REQUESTS.labels(bundle.name, "500").inc()
        log.exception("SSE stream failed mid-flight (request_id=%s)", rid)
        try:
            await resp.write(
                b"event: error\ndata: " + json.dumps(_error_body(
                    type(e).__name__, str(e) or "stream failed", rid
                )).encode() + b"\n\n"
            )
        except ConnectionError:
            pass
    finally:
        await stream_iter.aclose()
        try:
            await resp.write_eof()
        except ConnectionError:
            pass
    return resp


async def handle_completions(request: web.Request) -> web.StreamResponse:
    """Completions-API compatibility for generative models: the field
    names OpenAI-style clients already speak (``prompt``/``max_tokens``/
    ``temperature``/``top_p``/``stop``/``stream``), served by the exact
    same batcher/engine path as ``/predict``.  Streaming uses SSE
    (``data: {...}`` lines ending with ``data: [DONE]``)."""

    def to_prompt(body: dict) -> str:
        prompt = body.get("prompt")
        if isinstance(prompt, list):  # the API allows a singleton batch
            prompt = prompt[0] if len(prompt) == 1 else None
        if not isinstance(prompt, str) or not prompt:
            raise ValueError('"prompt" must be a non-empty string')
        return prompt

    app, bundle, item, feats, t0, include_usage = await _openai_prologue(
        request, to_prompt
    )

    if item.stream:
        def frame(text, finish) -> dict:
            payload = {
                "object": "text_completion", "model": bundle.name,
                "choices": [{"index": 0, "text": text,
                             "finish_reason": finish}],
            }
            if include_usage:
                payload["usage"] = None
            return payload

        def events(ev):
            if "delta" in ev:
                if not ev["delta"]:
                    return []
                return [_sse_frame(frame(ev["delta"], None))]
            frames = [_sse_frame(frame("", ev["finish_reason"]))]
            if include_usage:
                frames.append(_sse_frame({
                    "object": "text_completion", "model": bundle.name,
                    "choices": [],
                    "usage": _usage(feats, ev["tokens"]),
                }))
            return frames

        return await _sse_stream(request, feats, item, t0, events)

    text, finish, n_tok = await _generate_once(request, feats, item)
    metrics.REQUESTS.labels(bundle.name, "200").inc()
    metrics.LATENCY.labels(bundle.name).observe(time.monotonic() - t0)
    return web.json_response({
        "object": "text_completion",
        "model": bundle.name,
        "choices": [{"index": 0, "text": text, "finish_reason": finish}],
        "usage": _usage(feats, n_tok),
    })


# ---------------------------------------------------------------------------
# /v1/chat/completions — chat alias over the same generative path


def _render_chat(messages: list[dict], template: str | None = None) -> str:
    """Messages → one prompt string via the CHAT_TEMPLATE renderer
    (``api/chat.py``: plain|llama2|chatml|zephyr|llama3).  The handler
    passes the STARTUP-VALIDATED template from app state — re-reading
    the env per request would bypass build_app's validation (and the
    tokenizer probe) if the env mutated after startup.  The env
    fallback serves direct callers/tests only.  ValueError on malformed
    messages (handler maps to 400); LookupError on an unknown template
    (server misconfiguration → 500)."""
    from .chat import render_chat

    if template is None:
        template = os.environ.get("CHAT_TEMPLATE", "plain").lower()
    return render_chat(messages, template)


async def handle_chat_completions(request: web.Request) -> web.StreamResponse:
    """Chat-completions compatibility: render the message list to a
    prompt (CHAT_TEMPLATE) and serve it through the SAME path as
    /v1/completions, answering in the chat response shapes."""
    tmpl = request.app[K_STATE].get("chat_template")
    app, bundle, item, feats, t0, include_usage = await _openai_prologue(
        request, lambda body: _render_chat(body.get("messages"), tmpl)
    )

    if item.stream:
        def chunk(delta: dict, finish) -> bytes:
            payload = {
                "object": "chat.completion.chunk", "model": bundle.name,
                "choices": [{"index": 0, "delta": delta,
                             "finish_reason": finish}],
            }
            if include_usage:
                payload["usage"] = None
            return _sse_frame(payload)

        def events(ev):
            if "delta" in ev:
                return [chunk({"content": ev["delta"]}, None)] if ev["delta"] else []
            frames = [chunk({}, ev["finish_reason"])]
            if include_usage:
                frames.append(_sse_frame({
                    "object": "chat.completion.chunk", "model": bundle.name,
                    "choices": [],
                    "usage": _usage(feats, ev["tokens"]),
                }))
            return frames

        return await _sse_stream(
            request, feats, item, t0, events,
            preamble=chunk({"role": "assistant"}, None),
        )

    text, finish, n_tok = await _generate_once(request, feats, item)
    metrics.REQUESTS.labels(bundle.name, "200").inc()
    metrics.LATENCY.labels(bundle.name).observe(time.monotonic() - t0)
    return web.json_response({
        "object": "chat.completion",
        "model": bundle.name,
        "choices": [{
            "index": 0,
            "message": {"role": "assistant", "content": text},
            "finish_reason": finish,
        }],
        "usage": _usage(feats, n_tok),
    })


async def handle_stream_attach(request: web.Request) -> web.StreamResponse:
    """``GET /v1/streams/{request_id}`` — the crash-reconnect surface
    (runtime/durability.py): after a process restart, a client whose
    stream died mid-body re-attaches by request id and drains the
    journaled tokens plus the live continuation as ndjson deltas —
    each token exactly once (the resumed decode suppresses everything
    the journal already holds, so nothing double-emits)."""
    app = request.app
    bundle: ModelBundle = app[K_BUNDLE]
    rid = request.match_info["rid"]
    registry = app[K_STATE].get("streams")
    if registry is None:
        raise web.HTTPNotFound(
            reason="no journal replay ran (JOURNAL_DIR unset?)"
        )
    rec = registry.get(rid)
    if rec is None:
        # Never-seen vs finished-and-forgotten: a rid whose terminal
        # status is still journaled (live record or compacted
        # tombstone) gets 410 — "already finished, history gone" — so
        # a reconnecting client stops retrying; an unknown rid is a
        # plain 404 ("wrong id").
        journal = getattr(app[K_ENGINE], "journal", None)
        outcome = (
            journal.terminal_status(rid) if journal is not None else None
        )
        if outcome is not None:
            raise web.HTTPGone(
                text=json.dumps({
                    "request_id": rid,
                    "terminal": outcome,
                    "detail": "stream completed; its token history was "
                              "compacted out of the journal",
                }),
                content_type="application/json",
            )
        raise web.HTTPNotFound(reason=f"unknown stream {rid!r}")
    item = RawItem(
        text="", stream=True, max_tokens=rec.max_tokens,
        stop=tuple(rec.stop),
    )

    async def chunks():
        i = 0
        while True:
            cur = len(rec.tokens)
            if cur > i:
                yield np.asarray(rec.tokens[i:cur], np.int32)
                i = cur
                continue
            if rec.done:
                if rec.error:
                    raise RuntimeError(rec.error)
                return
            await rec.wait_past(i)

    events = _delta_stream(bundle, chunks(), item)
    resp = web.StreamResponse(
        status=200,
        headers={"Content-Type": "application/x-ndjson",
                 "X-Accel-Buffering": "no", "X-Request-Id": rid},
    )
    resp.enable_chunked_encoding()
    await resp.prepare(request)
    t0 = time.monotonic()
    try:
        async for ev in events:
            if "delta" in ev:
                await resp.write(
                    (json.dumps({"delta": ev["delta"]}) + "\n").encode()
                )
                continue
            await resp.write((json.dumps({
                "done": True,
                "prediction": {"text": ev["text"]},
                "tokens_generated": ev["tokens"],
                "decode_steps": ev["steps"],
                "finish_reason": ev["finish_reason"],
                "model": bundle.name,
                "timing_ms": round((time.monotonic() - t0) * 1000.0, 3),
            }) + "\n").encode())
            metrics.REQUESTS.labels(bundle.name, "200").inc()
    except ConnectionError:
        pass  # the client can reconnect again; the record persists
    except Exception as e:
        metrics.REQUESTS.labels(bundle.name, "500").inc()
        log.exception("stream reconnect failed (request_id=%s)", rid)
        try:
            await resp.write(
                (json.dumps(_error_body(
                    type(e).__name__, str(e) or "stream failed", rid
                )) + "\n").encode()
            )
        except ConnectionError:
            pass
    finally:
        try:
            await resp.write_eof()
        except ConnectionError:
            pass
    return resp


async def handle_models(request: web.Request) -> web.Response:
    """OpenAI ``/v1/models`` listing: one entry, the served model —
    clients use it for discovery/selection before their first call."""
    app = request.app
    bundle: ModelBundle = app[K_BUNDLE]
    return web.json_response({
        "object": "list",
        "data": [{
            "id": bundle.name,
            "object": "model",
            "created": int(app[K_STARTED_AT]),
            "owned_by": "mlmicroservicetemplate-tpu",
        }],
    })


# ---------------------------------------------------------------------------
# health / status / metrics


async def handle_healthz(request: web.Request) -> web.Response:
    """Liveness: stays 200 through drain (the process is healthy; it
    just stopped taking work) — only readiness flips."""
    body = {"alive": True, "draining": request.app[K_BATCHER].draining}
    fleet = getattr(request.app[K_BATCHER], "fleet", None)
    if fleet is not None:
        body["fleet_healthy"] = len(fleet.healthy_replicas())
        body["fleet_replicas"] = fleet.n
    return web.json_response(body)


async def handle_readyz(request: web.Request) -> web.Response:
    batcher = request.app[K_BATCHER]
    fleet = getattr(batcher, "fleet", None)
    if fleet is not None:
        # Fleet semantics: ready = ANY replica healthy.  One dead
        # replica must not pull the listener out of the LB — its
        # streams already failed over; degraded capacity is an
        # explicit header, not an outage.
        fleet.sweep()
        healthy = len(fleet.healthy_replicas())
        if healthy == 0:
            ra = max(1, int(math.ceil(fleet.retry_after_s())))
            down = {"healthy": 0, "replicas": fleet.n}
            lost = sorted(getattr(fleet, "lost_devices", ()))
            if lost:
                down["lost_devices"] = lost
            return web.json_response(
                {"ready": False,
                 "error": "every fleet replica is dead",
                 "fleet": down},
                status=503, headers={"Retry-After": str(ra)},
            )
        if batcher.draining:
            return web.json_response(
                {"ready": False, "draining": True}, status=503
            )
        if request.app[K_READY].is_set():
            body = {"ready": True,
                    "fleet": {"healthy": healthy, "replicas": fleet.n}}
            headers = {}
            if fleet.degraded:
                body["degraded"] = True
                headers["X-Fleet-Degraded"] = f"{healthy}/{fleet.n}"
                # A degraded multi-chip fleet names WHICH devices it
                # lost: the operator sees "chip 3 is gone" straight
                # from the LB probe, not a replica-count riddle.
                lost = sorted(getattr(fleet, "lost_devices", ()))
                if lost:
                    body["fleet"]["lost_devices"] = lost
            if getattr(fleet, "elastic", False):
                # Scale events are invisible to readiness (a spawning
                # replica is not routable until probed; a draining one
                # still finishes its streams) — but the LB operator can
                # see them in flight here and in /status.fleet.scaling.
                sc = fleet.scaling_status()
                body["fleet"]["scaling"] = {
                    "live": sc["live"], "min": sc["min"],
                    "max": sc["max"],
                    "in_progress": sc["in_progress"],
                    "draining": sc["draining"],
                }
            return web.json_response(body, headers=headers)
        body = {"ready": False}
        err = request.app[K_STATE]["ready_error"]
        if err:
            body["error"] = err
        return web.json_response(body, status=503)
    sup = getattr(batcher, "supervisor", None)
    if sup is not None and sup.failed:
        # The engine crash-looped through its whole restart budget:
        # permanently unready so the LB stops routing here for good.
        return web.json_response(
            {"ready": False,
             "error": "engine restart budget exhausted "
                      "(ENGINE_RESTARTS_MAX)"},
            status=503,
        )
    if batcher.draining:
        # Load balancers stop routing here while in-flight work drains.
        return web.json_response(
            {"ready": False, "draining": True}, status=503
        )
    if request.app[K_READY].is_set():
        return web.json_response({"ready": True})
    body = {"ready": False}
    err = request.app[K_STATE]["ready_error"]
    if err:
        body["error"] = err
    return web.json_response(body, status=503)


async def drain_app(app: web.Application, grace_s: float = 30.0) -> bool:
    """SIGTERM drain choreography: stop admitting (readyz → 503 so the
    LB stops routing; new requests shed 503 ``drain`` + Retry-After),
    then wait for everything already admitted — queued batches AND
    in-flight streams — up to ``grace_s``.  Returns True when fully
    drained.  serve.py calls this between the signal and process exit;
    tests call it directly."""
    batcher: Batcher = app[K_BATCHER]
    batcher.begin_drain()
    ok = await batcher.drained(grace_s)
    if ok:
        log.info("drain complete: all in-flight work finished")
    else:
        log.warning(
            "drain grace (%.0fs) expired with %d work items outstanding",
            grace_s, batcher.pending_work(),
        )
    return ok


async def handle_status(request: web.Request) -> web.Response:
    """Template-parity introspection endpoint (SURVEY.md §3.5)."""
    app = request.app
    bundle: ModelBundle = app[K_BUNDLE]
    import jax

    engine = app[K_ENGINE]
    body = {
        "model": bundle.name,
        "kind": bundle.kind,
        "ready": app[K_READY].is_set(),
        "device": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": engine.replicas.n_devices,
        "max_batch": app[K_CFG].max_batch,
        "uptime_s": round(time.time() - app[K_STARTED_AT], 1),
        # Compiled-executable inventory + startup cost: the operator-
        # facing answer to "what shapes are warm and what did warming
        # them cost" (each bucket is one XLA executable).
        "batch_buckets": list(engine.batch_buckets),
        "seq_buckets": list(engine.seq_buckets),
        "warmup_s": (
            round(app[K_STATE]["warmup_s"], 3)
            if app[K_STATE]["warmup_s"] is not None
            else None
        ),
    }
    batcher = app[K_BATCHER]
    body["scheduler"] = {
        "draining": batcher.draining,
        "queue_depth": batcher._queue.qsize(),
        "kv_committed_bytes": batcher.admission.committed_bytes,
        "kv_budget_bytes": batcher.admission.kv_budget_bytes,
    }
    if batcher.supervisor is not None:
        body["fault_tolerance"] = batcher.supervisor.stats()
    fleet = getattr(batcher, "fleet", None)
    if fleet is not None:
        # Per-replica health/breaker/load detail + failover count
        # (docs/replica-fleet.md).
        body["fleet"] = fleet.status()
    cdl = getattr(batcher, "_cdl", None)
    # The server process's own pauses: event-loop lag, collector pauses,
    # the decode loop thread's time runnable and on no CPU.
    body["process"] = process = pauses.snapshot(
        app[K_STATE].get("loop_lag"), getattr(cdl, "loop_time", None))
    if cdl is not None:
        loop_time = cdl.loop_time.snapshot()
        # Decode dispatch shape: the auto-tuned chunk-chain pipelining
        # depth (STREAM_PIPELINE=0 picks it from measured RTT/compute
        # at warmup).
        body["decode"] = {
            "chain_depth": cdl.chain_depth,
            "chain_depth_auto": cdl._auto_depth,
            "chunk_tokens": engine.chunk_tokens,
            "chunk_dispatches": cdl.chunk_dispatches,
            "tokens_emitted": getattr(cdl, "tokens_emitted", 0),
            # Double-buffered host prep (docs/compilation.md): staged
            # plans and how many were consumed as-is vs rolled back and
            # re-prepped inline.
            "prep_staged": getattr(cdl, "prep_staged", 0),
            "prep_hits": getattr(cdl, "prep_hits", 0),
            "prep_misses": getattr(cdl, "prep_misses", 0),
            "idle_admit": _idle_admit(cdl, loop_time),
            # Waves that met chunks in flight, the chunks the loop
            # delivered ahead of those waves' fetches (chunks / waves =
            # about the chain depth), and the waves whose start went
            # out ahead of their iteration's chunk.
            "ahead_of_wave": {
                "waves": cdl.waves_behind_chunks,
                "chunks": cdl.chunks_ahead_of_wave,
                "waves_ahead_of_chunk": cdl.waves_ahead_of_chunk,
            },
            # Where the loop thread's wall time went, by phase, since it
            # started: wall_s = sum of phases[*].s + unnamed_s; the
            # slowest iterations of the last 4096 (utils/tracing.LoopTable).
            "loop_time": loop_time,
            # /status.process again: a benchmark's window line prints
            # this block, and a stalled run is read from both.
            "process": process,
            # Per-site host-sync counts.
            "dispatch_counts": {
                site: a["count"]
                for site, a in engine.dispatch_attribution().items()
            },
        }
        # Pallas kernel selection (PALLAS_AUTOTUNE / PALLAS_VARIANT;
        # docs/kernel_tuning.md): the active variant ("" = default
        # kernel) and the autotuner's decision counters.
        if getattr(cdl, "_ssm_free", None) is not None:
            # Recurrent state beside the paged KV (a model with Mamba
            # layers): a fixed size a stream, rows by holder.
            held = cdl.n_slots - len(cdl._ssm_free)
            body["decode"]["recurrent_state"] = {
                "rows": cdl.n_slots, "rows_held": held,
                "row_bytes": engine.stream_fixed_bytes(),
                "bytes_held": held * engine.stream_fixed_bytes(),
                "kv_token_bytes": engine.kv_token_bytes(),
            }
            # The three stores apart, as allocated: the paged pool (the
            # layers that keep every key), the window layers' rings and the
            # recurrent rows (both a fixed size a stream).
            mcfg = engine.bundle.cfg
            pool = getattr(engine, "kv_pool", None)
            body["decode"]["stores"] = {
                "pool_bytes": (pool.num_blocks * engine.kv_block_bytes()
                               if pool is not None else 0),
                "window_store_bytes": cdl.n_slots * mcfg.window_row_bytes,
                "state_bytes": cdl.n_slots * mcfg.ssm_row_bytes,
                "pool_layers": len(mcfg.cache_layers),
                "window_store_layers": len(mcfg.ring_layers),
                "shared_pool_readers": sum(
                    1 for li in range(mcfg.num_layers)
                    if mcfg.layer_kind(li).store == "shared"),
            }
        if getattr(cdl, "moe_rows", None):
            # Expert FFN: assignment rows the block's row work ran over
            # and rows it skipped (ops/moe.row_rungs), and the held rows of
            # calls whose shuffles took the DMA kernels, by step kind.
            body["decode"]["expert_rows"] = {
                kind: {"ran": ran, "skipped": skipped,
                       "fused": cdl.moe_rows_fused.get(kind, 0)}
                for kind, (ran, skipped) in cdl.moe_rows.items()
            }
        kv_var = getattr(cdl, "kernel_variant", "")
        if kv_var or getattr(
                getattr(engine, "cfg", None), "pallas_autotune", False):
            from ..ops import autotune

            a_stats = autotune.stats()
            body["decode"]["kernel_variant"] = kv_var
            body["decode"]["autotune"] = a_stats["counts"]
            body["decode"]["autotune_table"] = a_stats["table"]
    tier = getattr(engine, "kv_host", None)
    if cdl is not None and tier is not None and tier.enabled:
        # Host KV tier (KV_HOST_BUDGET_MB; docs/kv-tiering.md): swap
        # traffic, prefetch overlap and the host pool/ledger state.
        total = getattr(cdl, "prefetch_blocks_total", 0)
        live = getattr(cdl, "prefetch_blocks_live", 0)
        body["kv_tier"] = {
            "swap_outs": getattr(cdl, "swap_outs", 0),
            "swap_resumes": getattr(cdl, "swap_ins", 0),
            "swap_fallbacks": getattr(cdl, "swap_fallbacks", 0),
            "swap_out_bytes": getattr(cdl, "swap_out_bytes", 0),
            "swap_in_bytes": getattr(cdl, "swap_in_bytes", 0),
            "prefetch_overlap_ratio": (
                round(live / total, 4) if total else None
            ),
            "host_prefix_promotes": getattr(
                cdl, "host_prefix_promotes", 0
            ),
            "prefetch_blocks": getattr(cdl, "swap_chunk_blocks", 0),
            "host_pool": tier.stats(),
        }
    if cdl is not None and getattr(cdl, "prefill_chunk", 0):
        body["prefill"] = {
            "chunk": cdl.prefill_chunk,
            "budget": cdl.prefill_budget,
            "max_prompt": cdl.max_prompt,
            "chunks_total": cdl.prefill_chunk_dispatches,
            "backlog_tokens": cdl.prefill_backlog_tokens(),
            "stall_seconds": round(cdl.prefill_stall_s, 4),
        }
    journal = getattr(engine, "journal", None)
    if journal is not None:
        # Durable serving (JOURNAL_DIR; docs/durability.md): journal
        # health, the disk KV rung, and the reconnect registry.
        dur = {"journal": journal.stats()}
        disk = getattr(engine, "kv_disk", None)
        if disk is not None:
            dur["kv_disk"] = disk.stats()
        reg = app[K_STATE].get("streams")
        if reg is not None:
            dur["reconnect"] = reg.stats()
        body["durability"] = dur
    jobs = getattr(batcher, "jobs", None)
    if jobs is not None:
        # Bulk inference lane (JOBS_ENABLED; docs/bulk-inference.md).
        body["jobs"] = jobs.stats()
    if hasattr(batcher, "compile_status"):
        # Compile economics (docs/compilation.md): executable-cache
        # hit/miss/insert counts, per-phase warm seconds, process XLA
        # compile totals — what a fleet spawn or restart actually paid.
        body["compile"] = batcher.compile_status()
    if hasattr(batcher, "tenancy_status"):
        # Multi-tenancy (TENANTS/ADAPTER_DIR; docs/multi-tenancy.md):
        # per-tenant usage/quota headroom, fair-share virtual clocks
        # and the adapter pool's slot residency.  Absent entirely when
        # tenancy is off.
        tstat = batcher.tenancy_status()
        if tstat is not None:
            body["tenancy"] = tstat
    # SLO burn rates (SLO_TTFT_MS / SLO_TBT_MS; scheduler/policy.py).
    # A fleet shares ONE tracker, and replica 0's loop holds it.
    slo = getattr(cdl, "slo", None)
    if slo is not None:
        body["slo"] = slo.snapshot()
    tr = tracing.tracer()
    body["observability"] = {
        "trace": tr is not None,
        "spans_created": tr.spans_created if tr is not None else 0,
        "flight_ring": getattr(
            getattr(engine, "flight", None), "size", 0
        ),
        "flight_dumps": getattr(
            getattr(engine, "flight", None), "dumps", 0
        ),
    }
    err = app[K_STATE]["ready_error"]
    if err:
        body["ready_error"] = err
    if bundle.kind == KIND_SEQ2SEQ:
        body["chat_template"] = app[K_STATE].get("chat_template", "plain")
        warns = app[K_STATE].get("chat_template_warnings") or []
        if warns:
            body["chat_template_warnings"] = warns
        if getattr(engine, "prefix_cache", None) is not None:
            body["prefix_cache"] = engine.prefix_cache.stats()
    return web.json_response(body)


def _idle_admit(cdl, loop_time: dict) -> dict:
    """Idle admission (the loop's ``_collect_burst``): the requests the
    server has read and not yet queued, the waits an idle loop made for
    such and their seconds (the loop table's ``idle_admit`` row), the
    rows those waits added to their waves, the waits that ended on
    their cap."""
    row = loop_time["inside"].get("idle_admit", {"n": 0, "s": 0.0})
    return {
        "expected": cdl.queue.expected(),
        "waits": row["n"],
        "rows": cdl.idle_wait_rows,
        "capped": cdl.idle_waits_capped,
        "wait_s": row["s"],
    }


async def handle_metrics(request: web.Request) -> web.Response:
    body, ctype = metrics.render()
    return web.Response(body=body, content_type=ctype.split(";")[0])


async def handle_trace(request: web.Request) -> web.Response:
    """``GET /debug/trace?last=N`` — the span tracer's ring as Chrome
    trace-event JSON (load in https://ui.perfetto.dev or
    chrome://tracing).  Empty ``traceEvents`` (with
    ``otherData.trace_enabled: false``) when TRACE=0."""
    tr = tracing.tracer()
    last = request.query.get("last")
    try:
        last = int(last) if last is not None else None
    except ValueError:
        raise web.HTTPBadRequest(reason='"last" must be an integer')
    if last is not None and last <= 0:
        raise web.HTTPBadRequest(reason='"last" must be > 0')
    if tr is None:
        return web.json_response({
            "traceEvents": [],
            "displayTimeUnit": "ms",
            "otherData": {"trace_enabled": False,
                          "hint": "start the server with TRACE=1"},
        })
    out = tr.chrome_trace(last)
    out["otherData"]["trace_enabled"] = True
    return web.json_response(out)


def _loop_summary(cdl) -> dict:
    return {
        "active": len(cdl.active),
        "queued": cdl.queue.qsize(),
        "prefilling": len(cdl._prefilling),
        "swapping": len(getattr(cdl, "_swapping", ())),
        "chunk_dispatches": cdl.chunk_dispatches,
        "prefill_dispatches": cdl.prefill_dispatches,
        "preemptions": cdl.preemptions,
    }


async def handle_engine_debug(request: web.Request) -> web.Response:
    """``GET /debug/engine`` — the engine flight recorder: the last N
    loop iterations (batch composition, slot occupancy, KV pool
    state), scheduling/fault events, and the last fatal-fault dump.

    ``?all=1`` (r20, fleet mode): every live replica's flight snapshot
    merged into ONE replica-tagged timeline — iterations and events
    from all loops plus the fleet's recent scale/failover events,
    sorted by timestamp — instead of the base engine's ring alone (a
    failover post-mortem spans the dead replica AND its adopter)."""
    engine = request.app[K_ENGINE]
    batcher = request.app[K_BATCHER]
    fleet = getattr(batcher, "fleet", None)
    want_all = request.query.get("all", "").lower() in ("1", "true", "yes")
    if want_all and fleet is not None:
        replicas: dict = {}
        timeline: list = []
        for rep in fleet.replicas:
            fl = getattr(rep.engine, "flight", None)
            if fl is None:
                continue
            snap = fl.snapshot()
            replicas[str(rep.id)] = {
                "breaker": "dead" if rep.dead else rep.breaker.state_name,
                "dumps": snap.get("dumps", 0),
                "loop": _loop_summary(rep.cdl),
                "dispatch_attribution": rep.engine.dispatch_attribution(),
            }
            for it in snap.get("iterations", ()):
                timeline.append({**it, "replica": rep.id, "kind": "iteration"})
            for ev in snap.get("events", ()):
                timeline.append({**ev, "replica": rep.id, "kind": "event"})
        # Scale/failover events carry no flight timestamp of their own;
        # tag them so the merged view shows WHEN the fleet moved
        # relative to each loop's iterations.
        for ev in fleet.scaling_status().get("recent", ()):
            timeline.append({**ev, "kind": "scale"})
        timeline.sort(key=lambda e: e.get("t", float("inf")))
        return web.json_response({
            "fleet": True,
            "replicas": replicas,
            "failovers": fleet.failovers,
            "timeline": timeline,
        })
    flight = getattr(engine, "flight", None)
    if flight is None:
        raise web.HTTPNotFound(reason="engine has no flight recorder")
    body = flight.snapshot()
    body["dispatch_attribution"] = (
        engine.dispatch_attribution()
        if hasattr(engine, "dispatch_attribution") else {}
    )
    cdl = getattr(batcher, "_cdl", None)
    if cdl is not None:
        body["loop"] = _loop_summary(cdl)
    return web.json_response(body)


async def handle_profile(request: web.Request) -> web.Response:
    """On-demand XLA device profiling (SURVEY.md §5 tracing plan):
    capture a jax.profiler trace for N seconds while traffic flows,
    write a perfetto-compatible dump, return its path.

    POST /debug/profile {"seconds": 2}  (dump dir: PROFILE_DIR knob)
    """
    try:
        body = await request.json()
    except Exception:
        body = {}
    seconds = body.get("seconds", request.query.get("seconds", 2.0))
    try:
        seconds = float(seconds)
    except (TypeError, ValueError):
        raise web.HTTPBadRequest(reason='"seconds" must be a number')
    if not (0.0 < seconds <= 30.0):  # also rejects NaN
        raise web.HTTPBadRequest(reason='"seconds" must be in (0, 30]')
    # The dump location is server-owned (PROFILE_DIR knob, legacy
    # JAX_TRACE_DIR fallback), never client-controlled — this endpoint
    # must not become an arbitrary-path file-write primitive.
    trace_dir = (
        getattr(request.app[K_CFG], "profile_dir", None)
        or os.environ.get("PROFILE_DIR")
        or os.environ.get("JAX_TRACE_DIR", "/tmp/jax-trace")
    )
    if request.app[K_STATE]["tracing"]:
        raise web.HTTPConflict(reason="a profile capture is already running")
    request.app[K_STATE]["tracing"] = True
    import jax

    try:
        jax.profiler.start_trace(trace_dir)
        await asyncio.sleep(seconds)
    finally:
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            log.warning("stop_trace failed: %s", e)
        request.app[K_STATE]["tracing"] = False
    return web.json_response(
        {"trace_dir": trace_dir, "seconds": seconds,
         "hint": "open in perfetto or tensorboard --logdir"}
    )
