"""Digest of what a benchmark configuration's serving steps lower to.

    python3 -m tools.lowered_text [--root DIR] [--config NAME ...] [--dump DIR]

For each llama configuration of ``cellbench/configs`` at its cell's shapes
(``MAX_STREAMS`` rows, the pool ``KV_BUDGET_MB`` buys, ``PREFILL_CHUNK`` x
the widths a dispatch has), the sha256 of the StableHLO text — debug
metadata stripped, lowered for the TPU, nothing compiled or run — of
``generate_chunk_paged`` (the paged decode chunk), ``paged_prefill_chunk``
(a prompt-window dispatch, alone and at the boundary's width) and a wave's
``start`` (``init_decode_state`` + the first chunk).  Parameters and state
are shapes only.  ``--root`` imports the program from another checkout (the
parent commit's), so the same script digests both sides of a PR: equal
digests = the configuration's executables were left as they were.  Give
both sides the SAME path (a symlink pointed at one checkout, then the
other): a kernel's serialised body carries its source file's name.  Only
what both sides have is used of the program (``models/llama.py`` functions
that PR 36 had, and ``zero_ssm`` / ``ssm_rows`` for a configuration with
recurrent layers, PR 40's).

A line also carries ``kernels``: a digest for each Pallas kernel of the
step, of its Mosaic module printed WITHOUT locations (``kernel_texts``) —
equal there = the kernel is the same program even where an edit moved its
source lines, which the whole text's digest cannot say — and
``sha256_sans_locations``: the text's digest with every kernel's body
replaced by that digest (``sans_locations``), equal wherever a PR left a
step's program as it was and only moved lines of a kernel's file.
"""

from __future__ import annotations

import argparse
import base64
import functools
import hashlib
import json
import os
import re
import sys


_BACKEND_CONFIG = re.compile(r'backend_config = "((?:[^"\\]|\\.)*)"')


@functools.lru_cache(maxsize=None)
def _kernel_text(config: str) -> str | None:
    """A ``backend_config``'s Mosaic module printed without debug
    locations; None for another custom call's configuration."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    try:
        body = json.loads(config.replace("\\22", '"').replace("\\5C", "\\"))[
            "custom_call_config"]["body"]
    except (ValueError, KeyError, TypeError):
        return None
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True  # the serialised dialect
    with ctx:
        module = ir.Module.parse(base64.b64decode(body))
        return module.operation.get_asm(enable_debug_info=False)


def kernel_texts(lowered: str) -> list:
    """The Mosaic module of every ``tpu_custom_call`` in a lowered text
    (``.lower(lowering_platforms=("tpu",)).as_text()``), in order, each
    printed without debug locations: what the kernel IS, whatever file
    and line its operations came from."""
    texts = map(_kernel_text, _BACKEND_CONFIG.findall(lowered))
    return [t for t in texts if t is not None]


def sans_locations(lowered: str) -> str:
    """A lowered text with each kernel's serialised body (which carries
    its operations' source lines) replaced by the digest of its module
    printed without them."""
    def plain(match):
        text = _kernel_text(match.group(1))
        return match.group(0) if text is None else f'kernel = "{digest(text)}"'

    return _BACKEND_CONFIG.sub(plain, lowered)


def digest(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--config", action="append", default=[])
    ap.add_argument("--dump", default=None, help="write the texts here")
    a = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, a.root)
    import jax
    import jax.numpy as jnp

    # A Pallas kernel lowers to a serialised module that KEEPS its
    # operations' locations: one frame each (the kernel's own file, the
    # same on both sides) instead of a call stack through files a PR edits.
    jax.config.update("jax_traceback_in_locations_limit", 1)

    from mlmicroservicetemplate_tpu.models import llama
    from mlmicroservicetemplate_tpu.models.gpt import PagedState
    from mlmicroservicetemplate_tpu.models.sampling import greedy_params

    cfg_dir = os.path.join(a.root, "cellbench", "configs")
    names = a.config or sorted(
        f[:-5] for f in os.listdir(cfg_dir) if f.endswith(".json"))
    for name in names:
        with open(os.path.join(cfg_dir, name + ".json"), encoding="utf-8") as f:
            config = json.load(f)
        env = config.get("env", {})
        if env.get("MODEL_NAME") != "llama":
            continue
        kw = {k: (config[v[1:]] if isinstance(v, str) and v.startswith("$") else v)
              for k, v in config["env_json"]["LLAMA_CONFIG"].items()}
        cfg = llama.LlamaConfig(**kw, pallas_decode=env.get(
            "USE_PALLAS_DECODE") == "1", eos_id=2, pad_id=0)
        dt = jnp.bfloat16
        params = jax.eval_shape(
            lambda: llama.init_params(jax.random.PRNGKey(0), cfg, dtype=dt))
        b, bs = int(env["MAX_STREAMS"]), int(env["KV_BLOCK_SIZE"])
        max_len = int(env["MAX_DECODE_LEN"])
        bucket = max(int(s) for s in env["SEQ_BUCKETS"].split(","))
        prompt = int(env.get("PREFILL_MAX_PROMPT", bucket))
        t_w = -(-(prompt + max_len + 4) // bs)
        tmpl = jax.eval_shape(lambda: llama.init_decode_state(
            params_zeros(params), cfg, jnp.ones((1, bucket), jnp.int32),
            jnp.ones((1, bucket), jnp.int32), max_len, dtype=dt))
        tok_bytes = sum(
            int(jnp.prod(jnp.asarray(x.shape[2:]))) * x.dtype.itemsize
            for x in jax.tree.leaves((tmpl.cache_k, tmpl.cache_v)))
        nb = int(float(env["KV_BUDGET_MB"]) * 2 ** 20) // (tok_bytes * bs)

        def pool(x):
            return jax.ShapeDtypeStruct(
                (nb, bs, int(jnp.prod(jnp.asarray(x.shape[2:])))), x.dtype)

        def rows(x):
            return jax.ShapeDtypeStruct((b,) + tuple(x.shape[1:]), x.dtype)

        state = PagedState(
            cache_k=jax.tree.map(pool, tmpl.cache_k),
            cache_v=jax.tree.map(pool, tmpl.cache_v),
            key_valid=jax.ShapeDtypeStruct((b, t_w * bs), jnp.int32),
            write_idx=rows(tmpl.write_idx), pos=rows(tmpl.pos),
            last_token=rows(tmpl.last_token), done=rows(tmpl.done),
            tokens=rows(tmpl.tokens),
            sample=jax.tree.map(rows, jax.eval_shape(lambda: greedy_params(1))),
        )
        # Recurrent layers (PR 40 on): a state row a slot beside the pool,
        # and a window dispatch names each prompt's row.
        ssm = jax.eval_shape(lambda: llama.zero_ssm(cfg, b, dt)) if hasattr(
            llama, "zero_ssm") else ()
        if ssm != ():
            state = state._replace(ssm=ssm)
        i32 = jnp.int32
        steps = {"paged_chunk": (
            lambda p, s, t: llama.generate_chunk_paged(p, cfg, s, t, 4, False),
            (params, state, jax.ShapeDtypeStruct((b, t_w), i32)))}
        c = int(env.get("PREFILL_CHUNK", 0))
        if c:
            for w in sorted({1, -(-int(env.get("PREFILL_BUDGET", c)) // c)}):
                steps[f"paged_prefill_chunk_b{w}"] = (
                    lambda p, s, t, i, m, st, *row: llama.paged_prefill_chunk(
                        p, cfg, s, t, i, m, st, dtype=dt,
                        **({"ssm_rows": row[0]} if row else {})),
                    (params, state, jax.ShapeDtypeStruct((w, t_w), i32),
                     jax.ShapeDtypeStruct((w, c), i32),
                     jax.ShapeDtypeStruct((w, c), i32),
                     jax.ShapeDtypeStruct((w,), i32),
                     *([jax.ShapeDtypeStruct((w, 2), i32)] if ssm != () else [])))
        for w in sorted({1, min(4, b), b}):
            steps[f"start_b{w}"] = (
                lambda p, i, m: llama.generate_chunk(p, cfg, llama.init_decode_state(
                    p, cfg, i, m, max_len, dtype=dt), 4, False),
                (params, jax.ShapeDtypeStruct((w, bucket), i32),
                 jax.ShapeDtypeStruct((w, bucket), i32)))
        for step, (fn, args) in steps.items():
            text = jax.jit(fn).trace(*args).lower(
                lowering_platforms=("tpu",)).as_text()
            if a.dump:
                os.makedirs(a.dump, exist_ok=True)
                with open(os.path.join(a.dump, f"{name}.{step}.mlir"), "w") as f:
                    f.write(text)
            print(json.dumps({"config": name, "step": step, "bytes": len(text),
                              "sha256": digest(text),
                              "sha256_sans_locations": digest(
                                  sans_locations(text)),
                              "kernels": [digest(k)[:16]
                                          for k in kernel_texts(text)]}),
                  flush=True)
    return 0


def params_zeros(shapes):
    """Abstract leaves stand in for the parameters under ``eval_shape``."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


if __name__ == "__main__":
    sys.exit(main())
