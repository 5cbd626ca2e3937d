"""The system under test, booted in this process through the normal
entry point (``serve.build_service``, ``WARMUP=1``) and bound to a
localhost port — the pattern of ``chip_smoke.py``.  The process that
calls this holds the chip; the load comes from a child that does not.
"""

from __future__ import annotations

import asyncio
import os
import socket
import time

from . import spec

READY_TIMEOUT_S = 1100.0


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def write_piece_table(path: str, vocab: int) -> None:
    """A synthetic SentencePiece table covering the model's whole
    vocabulary: ids 0..2 = <unk>/<s>/</s>, then one word piece
    ``▁w<i>`` per remaining id.  A prompt of such words encodes one
    token per word (plus BOS), and a streamed text spells out every
    token it was decoded from — which is how the load generator counts
    the tokens each event carried."""
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        f.write("<unk>\t0\n<s>\t0\n</s>\t0\n")
        for i in range(3, vocab):
            f.write(f"▁w{i}\t-1\n")
    os.replace(tmp, path)


def memory() -> dict:
    """bytes_in_use / peak_bytes_in_use of the fullest device."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()]
    return {
        "bytes_in_use": max((s.get("bytes_in_use") or 0) for s in stats),
        "peak_bytes_in_use": max((s.get("peak_bytes_in_use") or 0) for s in stats),
    }


class Service:
    """One ``build_service`` stack on a localhost port."""

    def __init__(self, config: dict, work_dir: str, extra_env: dict | None = None):
        self.config = config
        self.env = spec.service_env(config)
        self.env.update(extra_env or {})
        tok = config.get("tokenizer")
        if tok and tok["kind"] == "pieces":
            path = os.path.join(work_dir, f"pieces_{int(config['vocab_size'])}.tsv")
            write_piece_table(path, int(config["vocab_size"]))
            self.env["TOKENIZER_PATH"] = path
        self.facts: dict = {}

    async def __aenter__(self) -> "Service":
        import aiohttp
        from aiohttp import web

        os.environ.update(self.env)
        from mlmicroservicetemplate_tpu.serve import build_service

        t0 = time.monotonic()
        self.port = free_port()
        (self.cfg, self.bundle, self.engine, self.batcher,
         self.app) = build_service({"PORT": str(self.port)})
        self.facts["build_s"] = time.monotonic() - t0
        self.facts["memory_after_params"] = memory()
        self.runner = web.AppRunner(self.app, access_log=None)
        await self.runner.setup()
        await web.TCPSite(self.runner, "127.0.0.1", self.port).start()
        self.base = f"http://127.0.0.1:{self.port}"
        self.http = aiohttp.ClientSession(
            base_url=self.base, timeout=aiohttp.ClientTimeout(total=900))
        while True:
            async with self.http.get("/readyz") as r:
                if r.status == 200:
                    break
                err = (await r.json()).get("error")
            if err:
                raise RuntimeError(f"warm-up failed: {err}")
            if time.monotonic() - t0 > READY_TIMEOUT_S:
                raise RuntimeError("service never became ready")
            await asyncio.sleep(0.1)
        self.facts["ready_s"] = time.monotonic() - t0
        self.facts["memory_after_warmup"] = memory()
        self._expect_cfg()
        return self

    def _expect_cfg(self) -> None:
        """The model the service built has the sizes the file states."""
        for field, want in self.config.get("expect_cfg", {}).items():
            want = spec.subst(want, self.config)
            got = getattr(self.bundle.cfg, field)
            if got != want:
                raise RuntimeError(
                    f"service built {field}={got!r}, the configuration file "
                    f"says {want!r}")

    async def __aexit__(self, *exc) -> None:
        from mlmicroservicetemplate_tpu.api.app import drain_app

        await self.http.close()
        await drain_app(self.app, 10.0)
        await self.runner.cleanup()

    async def status(self) -> dict:
        async with self.http.get("/status") as r:
            return await r.json()

    def prom(self) -> str:
        """The process's Prometheus registry, read in-process."""
        from mlmicroservicetemplate_tpu.utils import metrics

        body, _ = metrics.render()
        return body.decode("utf-8")
