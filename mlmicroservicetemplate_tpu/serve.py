"""Service entrypoint: config → device → model → engine → batcher → HTTP.

The reference's ``main.py`` equivalent (SURVEY.md §3.1): boots the whole
stack from env vars (12-factor) with optional CLI overrides, e.g.::

    DEVICE=tpu MODEL_NAME=resnet50 python -m mlmicroservicetemplate_tpu.serve
    python -m mlmicroservicetemplate_tpu.serve --model bert-base --device cpu --port 8080

Import discipline: ``apply_device_env`` runs before any model/engine
import touches a device, so DEVICE=cpu can still steer the platform
and DEVICE=tpu is verified before anything is built; torch never
appears on this path (BASELINE.json:5).
"""

from __future__ import annotations

import argparse
import logging
import sys
import time


def parse_args(argv: list[str] | None = None) -> dict:
    p = argparse.ArgumentParser(description="TPU-native inference microservice")
    p.add_argument(
        "--model", dest="MODEL_NAME",
        help="resnet50 | bert-base | bert-long | t5-small | gpt2 | llama",
    )
    p.add_argument("--device", dest="DEVICE", help="tpu | cpu")
    p.add_argument("--host", dest="HOST")
    p.add_argument("--port", dest="PORT")
    p.add_argument("--model-path", dest="MODEL_PATH")
    p.add_argument("--tokenizer-path", dest="TOKENIZER_PATH")
    p.add_argument("--max-batch", dest="MAX_BATCH")
    p.add_argument("--batch-timeout-ms", dest="BATCH_TIMEOUT_MS")
    p.add_argument("--replicas", dest="REPLICAS")
    p.add_argument(
        "--journal-dir", dest="JOURNAL_DIR",
        help="crash-safe stream journal directory (docs/durability.md)",
    )
    p.add_argument("--no-warmup", action="store_true")
    p.add_argument("--server-url", dest="SERVER_URL")
    args = p.parse_args(argv)
    overrides = {k: str(v) for k, v in vars(args).items() if v is not None and k != "no_warmup"}
    if args.no_warmup:
        overrides["WARMUP"] = "0"
    return overrides


def build_service(overrides: dict | None = None):
    """Assemble (cfg, bundle, engine, batcher, app) without running it.

    The boot timeline starts here (``utils/tracing.boot_phase``,
    ``/status.compile.boot``): every step below runs under a phase, the
    import blocks under ``boot/imports``."""
    t_entry = time.monotonic()
    # LOCKTRACE=1: install the lock-order detector BEFORE any engine
    # lock exists (docs/static-analysis.md) — locks created earlier
    # stay untraced.
    from .utils import locktrace

    locktrace.auto_install()
    from .utils import tracing
    from .utils.config import load_config

    tracing.boot_table().begin(t_entry)
    tracing.boot_table().add("boot/imports", t_entry,
                             time.monotonic() - t_entry)
    with tracing.boot_phase("boot/config"):
        cfg = load_config(overrides)
        logging.basicConfig(
            level=getattr(logging, cfg.log_level.upper(), logging.INFO),
            format="%(asctime)s %(levelname)s %(name)s: %(message)s",
        )
        if cfg.log_format == "json":
            # One JSON object per line, request_id-correlated with spans
            # and HTTP error bodies (utils/tracing.JsonLogFormatter).
            for h in logging.getLogger().handlers:
                h.setFormatter(tracing.JsonLogFormatter())
        # TRACE=1 installs the process span tracer before any engine or
        # request work so startup dispatches are attributable too.
        tracing.configure(cfg.trace, cfg.trace_ring)

    with tracing.boot_phase("boot/device"):
        # Multi-host rendezvous (JAX_COORDINATOR/NUM_PROCESSES/PROCESS_ID;
        # no-op single-host) — must precede apply_device_env, whose backend
        # probe would latch initialization before the processes rendezvous.
        from .runtime.distributed import maybe_init_distributed

        maybe_init_distributed()

        from .runtime.device import apply_device_env

        apply_device_env(cfg.device, cfg.compile_cache_dir)

    with tracing.boot_phase("boot/imports"):
        from .api import build_app
        from .engine import InferenceEngine
        from .models.registry import build_model
        from .scheduler import Batcher

    bundle = build_model(cfg)  # boot/tokenizer, boot/weights
    with tracing.boot_phase("boot/engine_build"):
        engine = InferenceEngine(bundle, cfg)
        batcher = Batcher(engine, cfg)
        app = build_app(cfg, bundle, engine, batcher)
    return cfg, bundle, engine, batcher, app


async def _serve_until_signalled(app, cfg) -> None:
    """``web.run_app`` replacement with the SLA-aware lifecycle: on
    SIGTERM/SIGINT the server flips into drain mode (readyz → 503 so
    load balancers stop routing; new admissions shed 503 ``drain`` with
    Retry-After) and only exits once in-flight streams and queued
    batches finished — or the DRAIN_GRACE_S window closed."""
    import asyncio
    import signal

    from aiohttp import web

    from .api.app import drain_app

    runner = web.AppRunner(app, access_log=None)
    await runner.setup()
    site = web.TCPSite(runner, cfg.host, cfg.port)
    await site.start()
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            loop.add_signal_handler(sig, stop.set)
        except (NotImplementedError, RuntimeError):
            pass  # non-unix platform or nested loop: no graceful drain
    await stop.wait()
    log = logging.getLogger("serve")
    log.info(
        "signal received: draining (grace %.0fs)", cfg.drain_grace_s
    )
    await drain_app(app, cfg.drain_grace_s)
    await runner.cleanup()


def main(argv: list[str] | None = None) -> None:
    import asyncio

    overrides = parse_args(argv)
    cfg, bundle, _, _, app = build_service(overrides)
    log = logging.getLogger("serve")
    log.info(
        "serving %s on %s:%d (device=%s, max_batch=%d)",
        bundle.name, cfg.host, cfg.port, cfg.device, cfg.max_batch,
    )
    mn = cfg.fleet_min_replicas or cfg.fleet_replicas
    mx = cfg.fleet_max_replicas or cfg.fleet_replicas
    if mn != cfg.fleet_replicas or mx != cfg.fleet_replicas:
        # Elastic fleet: capacity tracks traffic, not boot flags
        # (docs/autoscaling.md).
        log.info(
            "autoscaling: fleet starts at %d, governor keeps it in "
            "[%d, %d] (period=%gs, up: queue>=%g/replica or "
            "kv>=%d%% of budget%s; down: load<=%d%% of survivor "
            "slots for %gs)",
            cfg.fleet_replicas, mn, mx, cfg.scale_period_s,
            cfg.scale_up_queue, int(cfg.scale_up_kv_frac * 100),
            f" or ttft>={cfg.scale_up_ttft_ms:g}ms"
            if cfg.scale_up_ttft_ms else "",
            int(cfg.scale_down_load * 100), cfg.scale_down_cooldown_s,
        )
    if cfg.journal_dir:
        # Durable serving: the startup replay (api/app.py) re-admits
        # every incomplete journaled stream once the model is ready.
        log.info(
            "durability: write-ahead journal at %s (fsync=%s, "
            "disk KV tier=%s)",
            cfg.journal_dir, cfg.journal_fsync,
            f"{cfg.kv_disk_budget_mb:g}MB"
            if cfg.kv_disk_budget_mb else "off",
        )
    if cfg.jobs_enabled:
        # Bulk inference lane: incomplete jobs re-admit from their last
        # completed line at startup replay (api/app.py, after warmup).
        log.info(
            "bulk jobs: /v1/batches enabled (store=%s/jobs, "
            "max_concurrent_lines=%d, result_ttl=%gs)",
            cfg.journal_dir, cfg.job_max_concurrent_lines,
            cfg.job_result_ttl_s,
        )
    asyncio.run(_serve_until_signalled(app, cfg))


if __name__ == "__main__":
    main(sys.argv[1:])
