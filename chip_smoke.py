#!/usr/bin/env python3
"""Chip smoke: serve on the attached TPU through the normal entry points.

The quickest proof that the system still starts on the chip.  It builds
the service with ``serve.build_service`` (``WARMUP=1``), binds it to a
localhost port, and talks real HTTP to it:

- **stream phase** — ``MODEL_NAME=llama`` at its default widths (22
  layers, d_model 2048, 32/4 heads, d_ff 5632, vocab 32000, bf16),
  ``PAGED_KV=1 USE_PALLAS_DECODE=1 PALLAS_AUTOTUNE=1``, one 512-token
  bucket, eight concurrent greedy streams of 32 tokens.  The same
  prompts are then served by a second service on the ``gather_pages``
  path (``USE_PALLAS_DECODE=0``) and the token streams compared.
- **unary phase** — ``MODEL_NAME=bert-base``: ``/predict`` sequentially
  and in a concurrent burst (``/metrics`` must show a batch > 1), then
  the same texts with ``USE_PALLAS_ATTENTION=0`` and the class
  probabilities compared.
- ``--chips 4`` runs ONLY the four-chip path: Llama as ``TP=2
  FLEET_REPLICAS=2 FLEET_TP_GROUPS=2,2`` (two tensor-parallel groups
  behind the router), compared with the same prompts at ``TP=1``.

Weights are the server's own deterministic random init (no MODEL_PATH:
``registry._load_or_init`` seeds it); prompts and texts are made from
``--seed``, and a synthetic SentencePiece table written to a temporary
directory lets a streamed TEXT spell out its token ids.  Nothing is
read that git would not commit.

Process rule: EVERY phase runs inside this one process, which holds
the chip(s) from its first device call to its exit.  No child process
is started; services run one after another, each torn down (drained,
its loops joined) before the next is built.

Output: one JSON line per phase on stdout, then as the LAST line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
with the device as JAX reports it.  Any failed phase, or a backend
other than ``tpu``, exits non-zero without that line.  ``--rehearse``
is the builder's CPU rehearsal (tiny Llama, interpret-mode kernels);
without it a CPU is never accepted.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import gc
import json
import os
import random
import socket
import sys
import tempfile
import time

N_STREAMS = 8
WAVE_ROWS = 64  # the benchmark cells' largest wave: one insert lands it
N_SPECIAL = 3  # <unk>, <s>, </s>
STREAM_TOKENS = 32
N_UNARY_SEQ = 4
N_UNARY_BURST = 16
# Stated tolerances of the comparisons.  Weights are random, so the
# logits are nearly flat: the bf16 kernel and the XLA path accumulate
# in different orders, greedy argmax flips on a near tie every few dozen
# steps (first chip run, PR 22: 6 of 8 streams parted ways somewhere in
# 32 tokens) and a stream is free from there.  A wrong mask, block or
# scale is wrong from token one on EVERY stream.  So: most streams
# agree on their first token, and a fair share of all tokens lies in
# the streams' common prefixes; plus the kernel itself against the jnp
# reference on the chip, at the serving shapes, to the autotuner's own
# bf16 tolerance.
FIRST_TOKEN_AGREE = 0.75  # share of streams whose first tokens match
COMMON_PREFIX_SHARE = 0.25  # matched-prefix tokens / all tokens
KERNEL_ATOL = 3e-2  # ops/autotune._verify's bf16 tolerance
PROB_ATOL = 2e-2

TINY_LLAMA = {
    "vocab_size": 512, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
    "num_layers": 2, "d_ff": 128, "max_position": 256,
}


# A latent-cache Llama (attention='mla'; models/llama.py): DeepSeek-V2's
# head sizes and ranks on a narrow, shallow, dense-FFN stack — the pool is
# [NB, BS, 640] a layer, one leaf, no V pool.  Tiny under ``--rehearse``.
LATENT_LLAMA = {
    "vocab_size": 32000, "d_model": 1024, "num_heads": 16, "num_kv_heads": 16,
    "num_layers": 2, "d_ff": 2816, "max_position": 4096, "attention": "mla",
    "q_lora_rank": 384, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128,
}
TINY_LATENT_LLAMA = {
    **TINY_LLAMA, "num_kv_heads": 4, "attention": "mla", "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "v_head_dim": 8,
}


class PhaseFailed(RuntimeError):
    pass


T0 = time.perf_counter()


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def note(msg: str) -> None:
    """Progress on stderr (stdout carries only the JSON lines)."""
    print(f"chip_smoke [{time.perf_counter() - T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def write_piece_table(path: str, vocab: int) -> None:
    """A synthetic SentencePiece table covering the model's whole
    vocab: ids 0..2 = <unk>/<s>/</s> (the Llama layout), then one
    word piece ``▁w<i>`` per remaining id — so a prompt of such words
    encodes one token per word, and a streamed text names every token
    it was decoded from."""
    with open(path, "w", encoding="utf-8") as f:
        f.write("<unk>\t0\n<s>\t0\n</s>\t0\n")
        for i in range(N_SPECIAL, vocab):
            f.write(f"▁w{i}\t-1\n")


def make_prompts(rng: random.Random, n: int, vocab: int, lo: int, hi: int):
    return [
        " ".join(f"w{rng.randrange(N_SPECIAL, vocab)}"
                 for _ in range(rng.randrange(lo, hi)))
        for _ in range(n)
    ]


def metric_hist(text: str, name: str, model: str) -> tuple[float, float]:
    """(sum, count) of one Prometheus histogram for one model label."""
    out = {"sum": 0.0, "count": 0.0}
    for line in text.splitlines():
        for k in out:
            if line.startswith(f"{name}_{k}{{") and f'model="{model}"' in line:
                out[k] = float(line.rsplit(" ", 1)[1])
    return out["sum"], out["count"]


@contextlib.contextmanager
def environ(**kv):
    """Knobs the registry/kernels read straight from the environment
    (USE_PALLAS_*, LLAMA_CONFIG): set for one service, then restored."""
    old = {k: os.environ.get(k) for k in kv}
    for k, v in kv.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = str(v)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


class Service:
    """One ``build_service`` stack bound to a localhost port."""

    def __init__(self, overrides: dict, env: dict):
        self.overrides, self.env = overrides, env

    async def __aenter__(self):
        import aiohttp
        from aiohttp import web

        from mlmicroservicetemplate_tpu.serve import build_service

        from mlmicroservicetemplate_tpu.runtime.compile_cache import (
            compile_counters,
        )

        self._env = environ(**self.env)
        self._env.__enter__()
        # The XLA compile counters are process totals: keep this
        # service's starting point so its own share can be reported.
        self._compiled = compile_counters()
        note(f"building {self.overrides['MODEL_NAME']} service")
        t0 = time.perf_counter()
        port = free_port()
        (self.cfg, self.bundle, self.engine, self.batcher,
         self.app) = build_service({**self.overrides, "PORT": str(port)})
        self.runner = web.AppRunner(self.app, access_log=None)
        await self.runner.setup()
        await web.TCPSite(self.runner, "127.0.0.1", port).start()
        self.http = aiohttp.ClientSession(
            base_url=f"http://127.0.0.1:{port}",
            timeout=aiohttp.ClientTimeout(total=900),
        )
        while True:
            async with self.http.get("/readyz") as r:
                if r.status == 200:
                    break
                err = (await r.json()).get("error")
            check(not err, f"warmup failed: {err}")
            check(time.perf_counter() - t0 < 1000, "service never became ready")
            await asyncio.sleep(0.25)
        self.ready_s = time.perf_counter() - t0
        note(f"{self.overrides['MODEL_NAME']} ready in {self.ready_s:.1f}s "
             f"(env {self.env})")
        return self

    async def __aexit__(self, *exc):
        from mlmicroservicetemplate_tpu.api.app import drain_app

        await self.http.close()
        await drain_app(self.app, 30.0)
        await self.runner.cleanup()
        note(f"{self.overrides['MODEL_NAME']} service torn down")
        self._env.__exit__(None, None, None)
        self.bundle = self.engine = self.batcher = self.app = None
        gc.collect()

    async def status(self) -> dict:
        async with self.http.get("/status") as r:
            return await r.json()

    async def compiled(self) -> dict:
        """XLA compiles (count, seconds) this service paid so far."""
        comp = (await self.status()).get("compile", {})
        return {
            "xla_compiles": comp["xla_compiles"] - self._compiled["count"],
            "xla_compile_s": round(
                comp["xla_compile_s"] - self._compiled["seconds"], 3),
        }

    async def metrics(self) -> str:
        async with self.http.get("/metrics") as r:
            return await r.text()

    async def stream(self, prompt: str) -> dict:
        body = {"text": prompt, "stream": True, "max_tokens": STREAM_TOKENS}
        async with self.http.post("/predict", json=body) as r:
            if r.status != 200:
                raise PhaseFailed(f"stream HTTP {r.status}: {await r.text()}")
            lines = [json.loads(ln) async for ln in r.content if ln.strip()]
        check(lines and lines[-1].get("done"), f"stream ended early: {lines[-1:]}")
        return lines[-1]

    async def predict(self, text: str) -> dict:
        async with self.http.post("/predict", json={"text": text}) as r:
            if r.status != 200:
                raise PhaseFailed(f"predict HTTP {r.status}: {await r.text()}")
            return await r.json()

    def decode_loops(self) -> list:
        fleet = getattr(self.batcher, "fleet", None)
        if fleet is not None:
            return [rep.cdl for rep in fleet.replicas]
        return [self.batcher._cdl]


def device_facts() -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def cache_dir() -> str | None:
    import jax

    return jax.config.jax_compilation_cache_dir


def require_device(status: dict, want: str) -> None:
    check(status.get("device") == want,
          f"/status says device={status.get('device')!r}, want {want!r}")


def compare_streams(a: list[dict], b: list[dict]) -> dict:
    """Greedy token streams of two paths on the same prompts."""
    prefix, total, identical, first = [], 0, 0, 0
    for x, y in zip(a, b):
        wx, wy = (r["prediction"]["text"].split() for r in (x, y))
        n = 0
        while n < min(len(wx), len(wy)) and wx[n] == wy[n]:
            n += 1
        prefix.append(n)
        total += max(len(wx), len(wy))
        identical += int(wx == wy)
        first += int(wx[:1] == wy[:1])
    out = {
        "streams": len(a), "identical": identical,
        "first_token_agree": first / len(a),
        "common_prefix_tokens": prefix,
        "common_prefix_share": round(sum(prefix) / max(total, 1), 3),
        "tolerance": f"first tokens agree on >= {FIRST_TOKEN_AGREE:.0%} of "
                     f"streams and common prefixes hold >= "
                     f"{COMMON_PREFIX_SHARE:.0%} of all tokens",
    }
    check(out["first_token_agree"] >= FIRST_TOKEN_AGREE
          and out["common_prefix_share"] >= COMMON_PREFIX_SHARE,
          f"streams disagree beyond bf16 near-tie flips: {out}")
    return out


def kernel_vs_reference(svc: "Service", variant: str) -> dict:
    """The paged kernel against ``paged_attention_ref`` on this device,
    at the decode loop's serving shapes, on seeded random pools."""
    import jax.numpy as jnp
    import numpy as np

    from mlmicroservicetemplate_tpu.ops.paged_attention import (
        paged_attention_ref,
        paged_decode_attention,
    )

    cdl, cfg = svc.decode_loops()[0], svc.bundle.cfg
    b, t, bs = cdl.n_slots, cdl.nb_max, cdl.block_size
    kvh, d = cfg.num_kv_heads, cfg.head_dim
    dt = svc.bundle.policy.compute_jnp
    rng = np.random.default_rng(0)
    nb = b * t
    q = jnp.asarray(rng.normal(size=(b, cfg.num_heads, d)), dt)
    # The pool's layout: [NB, BS, KVH*D] (ops/paged_attention.py).
    k, v = (jnp.asarray(rng.normal(size=(nb, bs, kvh * d)), dt) for _ in "kv")
    table = jnp.asarray(rng.permutation(nb).reshape(b, t), jnp.int32)
    valid = jnp.asarray(rng.random((b, t * bs)) < 0.9, jnp.int32)
    got = paged_decode_attention(
        q, k, v, table, valid, bs, variant=variant,
        interpret=cfg.pallas_interpret,
    )
    ref = paged_attention_ref(q, k, v, table, valid, bs)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    check(np.isfinite(err) and err <= KERNEL_ATOL,
          f"paged kernel {variant!r} vs jnp reference: max |d| {err}")
    return {"variant": variant, "shape": {"b": b, "t": t, "bs": bs},
            "max_abs_err": err, "tolerance": KERNEL_ATOL}


def latent_kernel_vs_reference(svc: "Service", variant: str) -> dict:
    """The latent kernel against ``paged_attention_ref``'s latent form on
    this device, at the decode loop's serving shapes, on a seeded pool."""
    import jax.numpy as jnp
    import numpy as np

    from mlmicroservicetemplate_tpu.ops.paged_attention import (
        latent_decode_attention,
        paged_attention_ref,
    )

    cdl, cfg = svc.decode_loops()[0], svc.bundle.cfg
    b, t, bs = cdl.n_slots, cdl.nb_max, cdl.block_size
    dt = svc.bundle.policy.compute_jnp
    rng = np.random.default_rng(0)
    nb = b * t
    q = jnp.asarray(rng.normal(size=(b, cfg.num_heads, cfg.latent_lanes)) * 0.3, dt)
    pool = jnp.asarray(rng.normal(size=(nb, bs, cfg.latent_lanes)), dt)
    table = jnp.asarray(rng.permutation(nb).reshape(b, t), jnp.int32)
    valid = jnp.asarray(rng.random((b, t * bs)) < 0.9, jnp.int32)
    got = latent_decode_attention(
        q, pool, table, valid, bs, cfg.kv_lora_rank, cfg.attn_scale,
        variant=variant, interpret=cfg.pallas_interpret)
    ref = paged_attention_ref(q, pool, None, table, valid, bs,
                              scale=cfg.attn_scale, v_dim=cfg.kv_lora_rank)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    check(np.isfinite(err) and err <= KERNEL_ATOL,
          f"latent kernel {variant!r} vs jnp reference: max |d| {err}")
    return {"variant": variant, "shape": {"b": b, "t": t, "bs": bs,
                                          "lanes": cfg.latent_lanes},
            "max_abs_err": err, "tolerance": KERNEL_ATOL}


def pool_moves(cdl, bucket: int) -> tuple[list[str], list[str]]:
    """Pool-sized ``reshape``/``copy``/``transpose`` instructions of the
    compiled serving programs: (inside the paged chunk's step loop —
    the pool's layout rule, ops/paged_attention.py, says there are none;
    anywhere in the chunk and in the insert of a lone start and of a
    wave of ``WAVE_ROWS`` rows, of ``bucket`` tokens, ENTRY included — the
    decode state is donated, engine/streams.py, so the compiler aliases
    every pool's input to its output and none is copied on the way in)."""
    import jax

    from mlmicroservicetemplate_tpu.ops.paged_attention import pool_relayouts

    st = cdl._state
    sizes = {int(x.size) for x in jax.tree.leaves((st.cache_k, st.cache_v))}
    progs = cdl.programs
    chunk = progs.paged_chunk_hlo(st, cdl._table, compiled=True)
    return (
        pool_relayouts(chunk, sizes, in_loop_only=True),
        pool_relayouts(chunk, sizes)
        + pool_relayouts(progs.paged_insert_hlo(st, bucket), sizes)
        + pool_relayouts(progs.paged_insert_hlo(st, bucket, WAVE_ROWS), sizes),
    )


async def run_streams(svc: Service, prompts: list[str], model: str,
                      bucket: int) -> dict:
    """Serve ``prompts`` concurrently (at most MAX_STREAMS per replica
    in flight: past that the server sheds 503 by design); return the
    phase facts."""
    import jax

    gate = asyncio.Semaphore(N_STREAMS * len(svc.decode_loops()))

    async def one(prompt: str) -> dict:
        async with gate:
            return await svc.stream(prompt)

    s0, c0 = metric_hist(await svc.metrics(), "stream_batch_size", model)
    finals = await asyncio.gather(*(one(p) for p in prompts))
    s1, c1 = metric_hist(await svc.metrics(), "stream_batch_size", model)
    note(f"{len(finals)} streams served")
    st = await svc.status()
    dec = st.get("decode", {})
    counts = dec.get("autotune", {})
    hlo_calls = [cdl.programs.paged_chunk_hlo(cdl._state, cdl._table)
                 .count("tpu_custom_call")
                 for cdl in svc.decode_loops()]
    moves = [pool_moves(cdl, bucket) for cdl in svc.decode_loops()]
    mean_batch = (s1 - s0) / max(c1 - c0, 1.0)
    check(all(f["tokens_generated"] > 0 for f in finals), "a stream was empty")
    check(mean_batch > 1.0,
          f"continuous loop never batched streams (mean {mean_batch})")
    stats = [d.memory_stats() or {} for d in jax.devices()]
    return {
        "finals": finals,
        "facts": {
            "ready_s": round(svc.ready_s, 2),
            **await svc.compiled(),
            "tokens_received": sum(f["tokens_generated"] for f in finals),
            "mean_streams_per_chunk": round(mean_batch, 3),
            "kernel_variant": dec.get("kernel_variant"),
            "variant_from": (
                "pin" if counts.get("pins") else
                "sweep" if counts.get("sweeps") else
                "table" if counts.get("hits") else None
            ),
            "autotune": counts,
            "tpu_custom_calls_in_decode_step": hlo_calls,
            "pool_relayouts_in_decode_step": [m[0] for m in moves],
            "pool_copies_at_entry": [m[1] for m in moves],
            "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
            "device": st.get("device"),
            "device_kind": st.get("device_kind"),
        },
    }


def llama_setup(a, n_prompts: int) -> tuple[list[str], dict, dict, int]:
    """(prompts, config overrides, env knobs, seq bucket) of a Llama
    service at default widths — tiny under ``--rehearse``."""
    vocab = TINY_LLAMA["vocab_size"] if a.rehearse else 32000
    bucket = 64 if a.rehearse else 512
    tok = os.path.join(a.tmp, "pieces.tsv")
    write_piece_table(tok, vocab)
    prompts = make_prompts(random.Random(a.seed), n_prompts, vocab,
                           bucket // 2, bucket - 8)
    # WARMUP=1 compiles one program per (batch bucket x seq bucket x
    # greedy/sampled): keep the warm set to what the phase serves —
    # batch bucket 1 for the unary generate path, greedy only — or
    # compiling alone outlasts the 1200 s the run has (first chip run,
    # PR 22: 84 compiles, 421 s, at the default buckets).
    base = {
        "MODEL_NAME": "llama", "DEVICE": a.device, "WARMUP": "1",
        "PAGED_KV": "1", "SEQ_BUCKETS": str(bucket), "BATCH_BUCKETS": "1",
        "TOKENIZER_PATH": tok, "LOG_LEVEL": "WARNING",
    }
    env = {"WARMUP_SAMPLING": "0", "USE_PALLAS_ATTENTION": None,
           "LLAMA_CONFIG": json.dumps(TINY_LLAMA) if a.rehearse else None}
    if a.rehearse:
        base["PALLAS_INTERPRET"] = "1"
    return prompts, base, env, bucket


async def stream_phase(a) -> None:
    prompts, base, env, bucket = llama_setup(a, N_STREAMS)
    kern = {**base, "PALLAS_AUTOTUNE": "1"}
    async with Service(kern, {**env, "USE_PALLAS_DECODE": "1"}) as svc:
        require_device(await svc.status(), a.device)
        got = await run_streams(svc, prompts, "llama", bucket)
        kernel = kernel_vs_reference(svc, got["facts"]["kernel_variant"])
    facts = got["facts"]
    if not a.rehearse:  # interpret mode lowers no custom call
        check(all(n > 0 for n in facts["tpu_custom_calls_in_decode_step"]),
              "the Pallas paged kernel is not in the compiled decode step")
        # (the interpreter's emulation copies its operands: chip only)
        check(not any(facts["pool_relayouts_in_decode_step"]),
              "the compiled paged chunk moves a whole KV pool every step: "
              f"{facts['pool_relayouts_in_decode_step']}")
        check(not any(facts["pool_copies_at_entry"]),
              "the compiled paged chunk or insert copies a KV pool it "
              f"should alias (state not donated?): "
              f"{facts['pool_copies_at_entry']}")
    async with Service(base, {**env, "USE_PALLAS_DECODE": "0"}) as svc:
        require_device(await svc.status(), a.device)
        ref = await run_streams(svc, prompts, "llama", bucket)
    check(not any(ref["facts"]["tpu_custom_calls_in_decode_step"]),
          "the reference service is not on the gather_pages path")
    emit({"phase": "stream", "model": "llama",
          "widths": "tiny (rehearsal)" if a.rehearse else "default",
          "seq_bucket": bucket, "streams": N_STREAMS,
          "compile_cache_dir": cache_dir(), **facts,
          "kernel_vs_jnp_reference": kernel,
          "vs_gather_pages": compare_streams(got["finals"], ref["finals"]),
          "gather_pages_ready_s": ref["facts"]["ready_s"],
          "gather_pages_xla_compile_s": ref["facts"]["xla_compile_s"]})


async def latent_phase(a) -> None:
    """The same streams through a latent-cache Llama: the absorbed decode
    step through the latent kernel against the gathered path, the kernel
    against its reference, and the layout and donation rules on the ONE
    pool leaf a layer."""
    prompts, base, env, bucket = llama_setup(a, N_STREAMS)
    env = {**env, "LLAMA_CONFIG": json.dumps(
        TINY_LATENT_LLAMA if a.rehearse else LATENT_LLAMA)}
    kern = {**base, "PALLAS_AUTOTUNE": "1"}
    async with Service(kern, {**env, "USE_PALLAS_DECODE": "1"}) as svc:
        require_device(await svc.status(), a.device)
        got = await run_streams(svc, prompts, "llama", bucket)
        kernel = latent_kernel_vs_reference(svc, got["facts"]["kernel_variant"])
        st = svc.decode_loops()[0]._state
        check(st.cache_v == [] and all(
            c.shape[2] == svc.bundle.cfg.latent_lanes for c in st.cache_k),
            "the latent model's state holds more than one latent pool a layer")
    facts = got["facts"]
    if not a.rehearse:
        check(all(n > 0 for n in facts["tpu_custom_calls_in_decode_step"]),
              "the latent kernel is not in the compiled decode step")
        check(not any(facts["pool_relayouts_in_decode_step"]),
              "the compiled paged chunk moves a whole latent pool every "
              f"step: {facts['pool_relayouts_in_decode_step']}")
        check(not any(facts["pool_copies_at_entry"]),
              "the compiled paged chunk or insert copies a latent pool it "
              f"should alias: {facts['pool_copies_at_entry']}")
    async with Service(base, {**env, "USE_PALLAS_DECODE": "0"}) as svc:
        require_device(await svc.status(), a.device)
        ref = await run_streams(svc, prompts, "llama", bucket)
    check(not any(ref["facts"]["tpu_custom_calls_in_decode_step"]),
          "the latent reference service is not on the gathered path")
    emit({"phase": "latent_stream", "model": "llama (attention='mla')",
          "widths": "tiny (rehearsal)" if a.rehearse else "latent 512 + 64, 16 heads",
          "seq_bucket": bucket, "streams": N_STREAMS, **facts,
          "kernel_vs_jnp_reference": kernel,
          "vs_gathered_path": compare_streams(got["finals"], ref["finals"])})


async def unary_phase(a) -> None:
    rng = random.Random(a.seed + 1)
    texts = [
        " ".join(f"word{rng.randrange(1000)}" for _ in range(rng.randrange(8, 40)))
        for _ in range(N_UNARY_SEQ + N_UNARY_BURST)
    ]
    base = {"MODEL_NAME": "bert-base", "DEVICE": a.device, "WARMUP": "1",
            "BATCH_BUCKETS": "1,8", "SEQ_BUCKETS": "128,512",
            "LOG_LEVEL": "WARNING"}

    async def serve(svc: Service) -> tuple[list, dict]:
        st = await svc.status()
        require_device(st, a.device)
        s0, c0 = metric_hist(await svc.metrics(), "batch_size", "bert-base")
        out = [await svc.predict(t) for t in texts[:N_UNARY_SEQ]]
        out += await asyncio.gather(
            *(svc.predict(t) for t in texts[N_UNARY_SEQ:]))
        s1, c1 = metric_hist(await svc.metrics(), "batch_size", "bert-base")
        return [r["probs"] for r in out], {
            "ready_s": round(svc.ready_s, 2),
            **await svc.compiled(),
            "dispatched_batches": c1 - c0,
            "mean_batch": round((s1 - s0) / max(c1 - c0, 1.0), 3),
            "device": st.get("device"), "device_kind": st.get("device_kind"),
        }

    async with Service(base, {"USE_PALLAS_ATTENTION": None}) as svc:
        probs, facts = await serve(svc)
    check(facts["dispatched_batches"] < len(texts) and facts["mean_batch"] > 1.0,
          f"the burst never formed a batch larger than 1: {facts}")
    async with Service(base, {"USE_PALLAS_ATTENTION": "0"}) as svc:
        ref, _ = await serve(svc)
    import math

    flat = [p for row in probs for p in row]
    check(all(math.isfinite(p) for p in flat), "non-finite probabilities")
    worst = max(abs(x - y) for r, s in zip(probs, ref) for x, y in zip(r, s))
    check(worst <= PROB_ATOL,
          f"kernel vs jnp attention: max |dp| {worst} > {PROB_ATOL}")
    emit({"phase": "unary", "model": "bert-base", "requests": len(texts),
          **facts, "vs_jnp_attention": {
              "max_abs_prob_diff": worst, "tolerance": PROB_ATOL}})


async def four_chip_phase(a) -> None:
    """Two TP=2 groups behind the router vs the same prompts at TP=1."""
    import jax

    prompts, base, env, bucket = llama_setup(a, 2 * N_STREAMS)
    env = {**env, "USE_PALLAS_DECODE": "1"}
    fleet = {**base, "TP": "2", "FLEET_REPLICAS": "2",
             "FLEET_TP_GROUPS": "2,2"}
    async with Service(fleet, env) as svc:
        require_device(await svc.status(), a.device)
        got = await run_streams(svc, prompts, "llama", bucket)
        st = await svc.status()
        groups = [tuple(r["devices"]) for r in st["fleet"]["per_replica"]]
        served = [cdl.tokens_emitted for cdl in svc.decode_loops()]
        check(len(groups) == 2 and all(len(g) == 2 for g in groups)
              and not set(groups[0]) & set(groups[1]),
              f"TP groups are not disjoint device pairs: {groups}")
        mem = [(d.memory_stats() or {}).get("bytes_in_use", 0)
               for d in jax.devices()]
        holders = _device_holders(svc)
    check(all(holders[d.id]["params"] and holders[d.id]["kv"]
              for d in jax.devices()),
          f"not every device holds parameters and KV: {holders}")
    if not a.rehearse:
        check(all(m > 0 for m in mem), f"a device holds nothing: {mem}")
        check(all(n > 0 for n in got["facts"]["tpu_custom_calls_in_decode_step"]),
              "the Pallas paged kernel is not in a group's decode step")
    check(all(served) and len(served) == 2,
          f"a replica served no stream: {served}")
    # TP=1 on ONE chip: REPLICAS=1 keeps the placement off the other
    # three (the default spreads data-parallel over every visible one).
    async with Service({**base, "REPLICAS": "1"}, env) as svc:
        ref = await run_streams(svc, prompts, "llama", bucket)
    emit({"phase": "four_chip", "model": "llama",
          "widths": "tiny (rehearsal)" if a.rehearse else "default",
          "placement": "TP=2 FLEET_REPLICAS=2 FLEET_TP_GROUPS=2,2",
          "replica_devices": groups, "tokens_per_replica": served,
          "bytes_in_use_per_device": mem, "holders": holders,
          "compile_cache_dir": cache_dir(), **got["facts"],
          "vs_tp1": compare_streams(got["finals"], ref["finals"])})


def _device_holders(svc: Service) -> dict:
    """Per device id: does it hold a shard of some replica's parameters,
    and of some replica's KV pool?  Read off the live arrays."""
    import jax

    out = {d.id: {"params": False, "kv": False} for d in jax.devices()}
    for rep in svc.batcher.fleet.replicas:
        for what, tree in (("params", rep.engine.params),
                           ("kv", rep.cdl._state.cache_k)):
            for leaf in jax.tree.leaves(tree):
                for d in leaf.devices():
                    out[d.id][what] = True
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="builder's CPU rehearsal: tiny Llama, interpret-"
                         "mode kernels; the result says platform cpu")
    a = ap.parse_args()
    if a.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={a.chips}"
        ).strip()
    a.device = "cpu" if a.rehearse else "tpu"

    import mlmicroservicetemplate_tpu  # noqa: F401  (fail before the chip)

    dev = device_facts()
    if dev["platform"] != a.device or dev["count"] < a.chips:
        print(f"chip_smoke: need {a.chips} {a.device} device(s), JAX reports "
              f"{dev}", file=sys.stderr)
        return 1
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as a.tmp:
            if a.chips == 4:
                asyncio.run(four_chip_phase(a))
            else:
                asyncio.run(stream_phase(a))
                asyncio.run(latent_phase(a))
                asyncio.run(unary_phase(a))
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device_facts()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
