"""Pallas decode-kernel autotuner: measured variant search at warmup.

The paged/whole-slab decode kernels (``ops/paged_attention.py``,
``ops/attention.py``) are parameterized by a :class:`Variant` — grid
block folding, head batching, native-MXU input width, int8 scale
folding (docs/kernel_tuning.md).  Which point wins depends on the
serving shape (B, KVH, n_rep, D, block size, table width) and dtype,
so instead of hardcoding one choice this module measures:

1. **Enumerate** the variant space for the shape, filtered by a VMEM
   cost model (``paged_vmem_bytes``, generalizing
   ``attention.decode_kernel_fits``) against the
   ``DECODE_KERNEL_VMEM_BUDGET_MB`` budget, and by block-table
   divisibility (``blocks_per_step`` must divide the table width — no
   pad-block path exists, by design).
2. **Verify** every candidate against the jnp reference on synthetic
   probe data at the REAL serving shapes — a variant that fails
   verification is rejected and counted, never timed.  Variants are
   token-identical to the reference by construction (same f32 masked
   online softmax, work only rearranged); this step enforces it at
   runtime against compiler surprises.
3. **Time** survivors with the two-scan-length method
   (``_time_per_call``: K vs 2K iterations inside one executable,
   differenced so the dispatch RTT cancels exactly), and
4. **Install** the winner into the fleet-shared
   ``runtime/compile_cache.ExecutableCache`` keyed by (shape key,
   variant) and journal it into a persistent tuning table, so replica
   spawns, supervised rebuilds and journal replays look the variant up
   and hit the SAME cached executable — zero extra compiles (the r19
   invariant; pinned by tests/test_pallas_autotune.py).

The sweep runs once per (model, kind, shape, dtype) key per process —
at warm time, before serving traffic — and ``PALLAS_VARIANT`` pins a
variant explicitly, skipping the sweep (validated, so a typo fails at
boot).  The lossy ``accbf16`` scratch axis is never enumerated; it is
reachable only through a pin.

Import-light (no jax at module import), thread-safe, and counters-
first: every decision (sweep/hit/pin/install/reject) increments a
process counter surfaced through ``stats()`` -> /status.decode, the
``pallas_autotune_events_total`` metric and the structural-counter
tests (tests/test_structural_counters.py).
"""

from __future__ import annotations

import json
import os
import threading
import time

from .paged_attention import Variant, parse_variant

#: blocks-per-step folds the sweep considers (further filtered by
#: table-width divisibility and the VMEM model).
BLOCK_FOLDS = (1, 2, 4, 8)

#: the latent kernel's folds (``latent_decode``): one pool, 128 query rows
#: a key, so a trip of 8 blocks is a quarter of a microsecond of MXU work
#: and of copy alike under ~0.7 us of trip overhead — the folds reach up to
#: the divisors of the serving table's width (392 = 8 x 49: 14, 28, 56).
LATENT_BLOCK_FOLDS = (4, 8, 14, 16, 28, 32, 56)
#: softmax scale of the latent probe (the model's own is its config's).
LATENT_PROBE_SCALE = 0.1

#: scan lengths for the two-scan timing (small: the sweep times a
#: single fused kernel, not a serving chunk; interpret-mode CPU sweeps
#: stay affordable).  PALLAS_AUTOTUNE_ITERS overrides.
SWEEP_ITERS = 4
SWEEP_REPS = 3

#: Revision of the paged and whole-slab kernels AS THE SWEEP TIMES THEM
#: (``_make_call`` times the whole jitted wrapper), carried in their
#: ``tune_key``: the table file lives in the compile-cache directory and
#: outlives a checkout's code, and a winner timed on one kernel must not be
#: served to another.  2 (PR 59): the head-batched kernels lay out their
#: own q in VMEM — revision 1's ``-hb`` timings held XLA's block-diagonal q
#: and read-out around the call.  Revision 1 keys carry no token, so the
#: tables written before stay valid for the code that wrote them, and the
#: two keep separate entries in one file.  The latent kernel has not
#: changed: its keys carry none.
KERNEL_REVISION = 2

_LOCK = threading.RLock()
_TABLE: dict[str, str] = {}
_RESULTS: dict[str, dict] = {}
_LOADED: set[str] = set()
_COUNTS = {
    "sweeps": 0,          # measured sweeps run (one per new key)
    "candidates": 0,      # variants enumerated across all sweeps
    "timed": 0,           # variants that survived to measurement
    "hits": 0,            # table lookups answered without a sweep
    "pins": 0,            # PALLAS_VARIANT pins honored
    "installs": 0,        # winners installed into the ExecutableCache
    "reject_vmem": 0,     # candidates over the VMEM budget
    "reject_verify": 0,   # candidates that mismatched the reference
    "reject_error": 0,    # candidates that failed to build/run
    "persist_errors": 0,  # tuning-table write/load failures (non-fatal)
}


def _event(name: str) -> None:
    try:
        from ..utils import metrics

        metrics.PALLAS_AUTOTUNE_EVENTS.labels(name).inc()
    except Exception:
        pass  # ops stays importable without the service metric surface


def tune_key(kind: str, *, b: int, kvh: int, n_rep: int,
             d: int, block_size: int, t: int, dtype: str,
             quant: bool, tp: int = 1) -> str:
    """Stable string key for one tuning problem.  Everything the
    kernel's cost surface depends on is spelled out, and NOTHING else:
    two models (or replicas) with identical decode shapes intentionally
    share an entry (λScale: tuning results are fleet artifacts keyed by
    workload, not by replica) — and because every field is derivable
    from the tensors at a kernel call site, the model code can
    reconstruct the key at trace time (:func:`lookup`) without any
    side-channel through its frozen config.  ``tp`` is the tensor-
    parallel width the kernel runs under: each shard's kernel sees
    kvh/tp local heads AND a different compute/VMEM surface (the
    shard_map body), so TP entries must never alias single-device ones.
    tp=1 appends nothing — every pre-TP persisted table stays valid.
    The kind carries its kernel's revision (``KERNEL_REVISION``:
    ``paged_decode.r2/...``), on every kind but the latent kernel's."""
    q8 = "-q8" if quant else ""
    tps = f"-tp{tp}" if int(tp) > 1 else ""
    rev = "" if kind == "latent_decode" else f".r{KERNEL_REVISION}"
    return (
        f"{kind}{rev}/B{b}-G{kvh}-R{n_rep}-D{d}"
        f"-bs{block_size}-T{t}-{dtype}{q8}{tps}"
    )


def lookup(kind: str, *, b: int, kvh: int, n_rep: int, d: int,
           block_size: int, t: int, dtype: str, quant: bool,
           tp: int = 1, default: str = "") -> str:
    """Trace-time variant resolution for kernel call sites: the winner
    ``ensure_tuned`` recorded for this shape, else ``default``.  The
    table only ever changes by gaining entries (warm-time sweeps/pins,
    before the shapes they describe are traced), so a serving-time
    RE-trace at a tuned shape resolves the same variant the warm trace
    did — variant choice is deterministic per (process, shape)."""
    key = tune_key(kind, b=b, kvh=kvh, n_rep=n_rep, d=d,
                   block_size=block_size, t=t, dtype=dtype, quant=quant,
                   tp=tp)
    with _LOCK:
        return _TABLE.get(key, default)


def paged_vmem_bytes(var: Variant, *, bs: int, kvh: int, d: int,
                     n_rep: int, payload_bytes: int, quant: bool,
                     t: int = 0) -> int:
    """VMEM of one paged program (a row: ops/paged_attention.
    _paged_kernel_v) under ``var`` — generalizes
    ``attention.decode_kernel_fits`` to the tuned axes.  Tiles are
    lane-dense ``[K*BS, KVH*D]`` (``_fold_block``), so bytes are what the
    arrays hold, with no (8, 128) padding blow-up to model: TWO slots of
    K raw K/V blocks (a trip's, and the next trip's in flight); the
    dequant/upcast f32 copies (``native_mxu`` skips them); the q and
    output blocks ``[H, D]``, double-buffered by the pipeline (at the
    payload's width when ``head_batched``: q crosses as it lies; a
    ``[KVH, R, D]`` block pads R to a sublane tile, counted at 4 bytes);
    when ``head_batched`` over more than one KV head ONE block-diagonal
    q ``[H, KVH*D]`` scratch, built in VMEM once a row, and its f32 copy
    where the fold upcasts; online-softmax scratch — acc ``[H, KVH*D]``
    wide when ``head_batched``, ``[H, D]`` otherwise; and the score/prob
    temporaries.  What rides a row at a
    time grows with the table's width ``t`` (0: not counted), double-
    buffered by the pipeline with its minor dim padded to a lane tile:
    the mask ``[T/K, K*BS]`` and, when quant, the row's gathered K and V
    scales ``[T*BS, KVH]``.  The model agrees with the v5e's compiler on
    every enumerated variant at the default Llama decode shapes and at
    the benchmark cells' (tests/test_chip_compile.py)."""
    kb = var.blocks_per_step * bs
    payload = 2 * 2 * kb * kvh * d * payload_bytes
    mask = 2 * (t // var.blocks_per_step) * max(kb, 128) * 4
    scales = 2 * 2 * t * bs * max(kvh, 128) * 4 if quant else 0
    upcast = not (var.native_mxu and not quant)
    f32_copies = 2 * kb * kvh * d * 4 if upcast else 0
    h = kvh * n_rep
    cols = kvh * d if var.head_batched else d
    q_bytes = payload_bytes if var.head_batched and not quant else 4
    q_out = 2 * 2 * h * d * q_bytes
    if var.head_batched and kvh > 1:
        q_out += h * cols * (q_bytes + (4 if upcast else 0))
    acc = 4 if var.acc_dtype == "f32" else 2
    scratch = (2 * h + h * cols) * acc
    scores = 2 * h * kb * 4  # s and p live together briefly
    return payload + mask + scales + f32_copies + q_out + scratch + scores


def _latent_v_dim(d: int) -> int:
    """Values of a PROBE's latent row of ``d`` lanes: its lanes but the
    last tile's rotary part (DeepSeek-V2: 512 of 640).  The serving call
    takes the model's own ``kv_lora_rank``."""
    return d - 128 if d > 128 else max(d // 2, 1)


def latent_vmem_bytes(var: Variant, *, bs: int, d: int, n_rep: int,
                      payload_bytes: int, t: int = 0) -> int:
    """VMEM of one program of the LATENT kernel
    (ops/paged_attention.latent_decode_attention): ONE slot pair of K
    raw blocks ``[K*BS, d]`` (keys and values are the same tile), its
    f32 copy unless ``native_mxu``, q ``[H, d]`` and the output ``[H, v]``
    double-buffered, m / l / acc ``[H, v]`` scratch, the score and
    probability temporaries ``[H, K*BS]`` and the row's mask."""
    kb = var.blocks_per_step * bs
    v = _latent_v_dim(d)
    payload = 2 * kb * d * payload_bytes
    f32_copy = 0 if var.native_mxu else kb * d * 4
    mask = 2 * (t // var.blocks_per_step) * max(kb, 128) * 4
    q_out = 2 * n_rep * (d + v) * payload_bytes
    scratch = (2 * n_rep + n_rep * v) * 4
    scores = 2 * n_rep * kb * 4
    return payload + f32_copy + mask + q_out + scratch + scores


def variant_fits(var: Variant, *, bs: int, kvh: int, d: int, n_rep: int,
                 payload_bytes: int, quant: bool,
                 budget: int | None = None, t: int = 0,
                 latent: bool = False) -> bool:
    from .attention import decode_vmem_budget_bytes

    if budget is None:
        budget = decode_vmem_budget_bytes()
    if latent:
        return latent_vmem_bytes(
            var, bs=bs, d=d, n_rep=n_rep, payload_bytes=payload_bytes, t=t,
        ) <= budget
    return paged_vmem_bytes(
        var, bs=bs, kvh=kvh, d=d, n_rep=n_rep,
        payload_bytes=payload_bytes, quant=quant, t=t,
    ) <= budget


def enumerate_variants(kind: str, *, t: int, bs: int, kvh: int, d: int,
                       n_rep: int, dtype: str, quant: bool,
                       budget: int | None = None) -> list[Variant]:
    """The feasible sweep set for one shape, default variant first.
    ``nat`` only exists for bf16 payloads, ``fs`` only for int8, the
    block fold only for the paged kernel (and only at divisors of the
    table width) — axes that would be no-ops are never enumerated, so
    every candidate the sweep times is a genuinely distinct kernel."""
    payload_bytes = 1 if quant else (2 if dtype == "bfloat16" else 4)
    latent = kind == "latent_decode"
    folds = [1]
    if kind == "paged_decode" or latent:
        folds = [k for k in (LATENT_BLOCK_FOLDS if latent else BLOCK_FOLDS)
                 if k <= max(t, 1) and t % k == 0]
        if not folds:
            folds = [1]
    nats = [False, True] if (dtype == "bfloat16" and not quant) else [False]
    fss = [False, True] if quant else [False]
    out: list[Variant] = []
    for k in folds:
        # the latent kernel has one KV head: nothing to batch heads over
        for hb in ((False,) if latent else (False, True)):
            for nat in nats:
                for fs in fss:
                    var = Variant(k, hb, nat, fs)
                    if variant_fits(
                        var, bs=bs, kvh=kvh, d=d, n_rep=n_rep,
                        payload_bytes=payload_bytes, quant=quant,
                        budget=budget,
                        t=t if kind in ("paged_decode", "latent_decode") else 0,
                        latent=latent,
                    ):
                        out.append(var)
                    else:
                        with _LOCK:
                            _COUNTS["reject_vmem"] += 1
                        _event("reject_vmem")
    return out


def _time_per_call(fn, args, iters: int, reps: int):
    """Two-scan-length device time: the wall time of K iterations
    inside ONE executable is K x device time + one round trip, so the
    difference of a K and a 2K scan cancels the round trip exactly.
    The scan body carries a scalar dependency into the next iteration
    (input + carry*0: a no-op XLA must still honor), so the loop can
    be neither collapsed nor hoisted.  Returns (seconds a call, noisy):
    ``noisy`` = the 2K scan measured no slower than the K scan, and
    the value is wall_K / K, an upper bound."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def make(n: int):
        def scan_k(*xs):
            def body(carry, _):
                xs2 = list(xs)
                xs2[0] = xs2[0] + (carry * 0).astype(xs2[0].dtype)
                out = fn(*xs2)
                return out.astype(jnp.float32).ravel()[0], ()

            carry, _ = lax.scan(body, jnp.float32(0), None, length=n)
            return carry

        return jax.jit(scan_k)

    s1, s2 = make(iters), make(2 * iters)
    dev = jax.device_put(tuple(args))
    float(jax.device_get(s1(*dev)))
    float(jax.device_get(s2(*dev)))

    def med(f) -> float:
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            float(jax.device_get(f(*dev)))
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2]

    w1, w2 = med(s1), med(s2)
    noisy = w2 <= w1
    per = (max(w1, 1e-9) / iters) if noisy else (w2 - w1) / iters
    return per, noisy


def _probe(kind: str, *, b: int, kvh: int, n_rep: int, d: int, bs: int,
           t: int, dtype: str, quant: bool, seed: int = 0):
    """Synthetic probe tensors at the real serving shapes, plus the jnp
    reference output: (args_without_variant_call, ref).  Deterministic
    (fixed seed) so every replica's sweep measures the same problem."""
    import numpy as np

    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    h = kvh * n_rep
    jdt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    q = jnp.asarray(rng.normal(size=(b, h, d)).astype(np.float32), dtype=jdt)
    if kind == "latent_decode":
        # Rows of unequal lengths in a table allocated ahead of them, as a
        # serving batch is (the kernel's time follows the live keys): from
        # a third of the table's width up, a slot in eight empty.
        from .paged_attention import paged_attention_ref

        nb_pool = b * t + 2
        pool = jnp.asarray(rng.normal(size=(nb_pool, bs, d)).astype(np.float32) * 0.3,
                           dtype=jdt)
        table = rng.permutation(nb_pool)[: b * t].reshape(b, t).astype(np.int32)
        lens = rng.integers(max(t * bs // 3, 1), t * bs, b)
        lens[::8] = 0
        valid = (np.arange(t * bs)[None, :] < lens[:, None]).astype(np.int32)
        table[np.arange(t)[None, :] * bs >= lens[:, None] + 4 * bs] = nb_pool
        args = (q, pool, jnp.asarray(table), jnp.asarray(valid))
        ref = paged_attention_ref(q, pool, None, args[2], args[3], bs,
                                  scale=LATENT_PROBE_SCALE,
                                  v_dim=_latent_v_dim(d))
        return args, jnp.where((lens > 0)[:, None, None], ref, 0)
    if kind == "paged_decode":
        nb_pool = t + 2  # a couple of free blocks, like a live pool
        kf = rng.normal(size=(nb_pool, bs, kvh, d)).astype(np.float32)
        vf = rng.normal(size=(nb_pool, bs, kvh, d)).astype(np.float32)
        table = np.stack(
            [rng.permutation(nb_pool)[:t] for _ in range(b)]
        ).astype(np.int32)
        valid = np.ones((b, t * bs), np.int32)
        valid[:, -max(bs // 2, 1):] = 0  # a part-filled tail block
        def pool(x, dt):  # the pool's layout: [NB, BS, C], token dims merged
            return jnp.asarray(x.reshape(nb_pool, bs, -1), dtype=dt)

        if quant:
            ks = (np.abs(kf).max(axis=3, keepdims=True) / 127.0 + 1e-6)
            vs = (np.abs(vf).max(axis=3, keepdims=True) / 127.0 + 1e-6)
            k8 = np.clip(np.round(kf / ks), -127, 127)
            v8 = np.clip(np.round(vf / vs), -127, 127)
            args = (q, pool(k8, jnp.int8), pool(v8, jnp.int8),
                    jnp.asarray(table), jnp.asarray(valid),
                    pool(ks, jnp.float32), pool(vs, jnp.float32))
        else:
            args = (q, pool(kf, jdt), pool(vf, jdt),
                    jnp.asarray(table), jnp.asarray(valid), None, None)
        from .paged_attention import paged_attention_ref

        ref = paged_attention_ref(args[0], args[1], args[2], args[3],
                                  args[4], bs, k_scale=args[5],
                                  v_scale=args[6])
        return args, ref
    # whole-slab decode
    kf = rng.normal(size=(b, t, kvh, d)).astype(np.float32)
    vf = rng.normal(size=(b, t, kvh, d)).astype(np.float32)
    mask = np.ones((b, t), np.int32)
    mask[:, -max(t // 8, 1):] = 0
    if quant:
        ks = (np.abs(kf).max(axis=3, keepdims=True) / 127.0 + 1e-6)
        vs = (np.abs(vf).max(axis=3, keepdims=True) / 127.0 + 1e-6)
        k8 = np.clip(np.round(kf / ks), -127, 127).astype(np.int8)
        v8 = np.clip(np.round(vf / vs), -127, 127).astype(np.int8)
        args = (q, jnp.asarray(k8), jnp.asarray(v8), jnp.asarray(mask),
                jnp.asarray(ks.astype(np.float32)),
                jnp.asarray(vs.astype(np.float32)))
    else:
        args = (q, jnp.asarray(kf, dtype=jdt), jnp.asarray(vf, dtype=jdt),
                jnp.asarray(mask), None, None)
    ref = _slab_ref(*args)
    return args, ref


def _slab_ref(q, k, v, mask, ks, vs):
    """jnp reference for the whole-slab kernel (mirrors
    ``paged_attention_ref`` on the dense [B, T, KVH, D] layout)."""
    import math

    import jax
    import jax.numpy as jnp

    b, h, d = q.shape
    kvh = k.shape[2]
    n_rep = h // kvh
    kd = k.astype(jnp.float32)
    vd = v.astype(jnp.float32)
    if ks is not None:
        kd = kd * ks.astype(jnp.float32)
        vd = vd * vs.astype(jnp.float32)
    qg = q.reshape(b, kvh, n_rep, d).astype(jnp.float32)
    s = jnp.einsum("bgrd,btgd->bgrt", qg, kd) / math.sqrt(d)
    s = jnp.where(mask[:, None, None, :] != 0, s, jnp.float32(-1e9))
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrt,btgd->bgrd", p, vd)
    return o.reshape(b, h, d).astype(q.dtype)


def _make_call(kind: str, vkey: str, block_size: int, interpret: bool):
    """A positional-args callable running the kernel at one variant —
    the object the sweep times and the ExecutableCache installs."""
    if kind == "latent_decode":
        from .paged_attention import latent_decode_attention

        def call(q, pool, tbl, valid):
            return latent_decode_attention(
                q, pool, tbl, valid, block_size, _latent_v_dim(q.shape[-1]),
                LATENT_PROBE_SCALE, interpret=interpret, variant=vkey,
            )

        return call
    if kind == "paged_decode":
        from .paged_attention import paged_decode_attention

        def call(q, kp, vp, tbl, valid, ks=None, vs=None):
            return paged_decode_attention(
                q, kp, vp, tbl, valid, block_size, k_scale=ks, v_scale=vs,
                interpret=interpret, variant=vkey,
            )

        return call
    from .attention import decode_attention

    def call(q, k, v, mask, ks=None, vs=None):
        return decode_attention(
            q, k, v, mask, k_scale=ks, v_scale=vs, interpret=interpret,
            variant=vkey,
        )

    return call


def _verify(out, ref, dtype: str) -> bool:
    import numpy as np

    a = np.asarray(out, dtype=np.float32)
    b = np.asarray(ref, dtype=np.float32)
    tol = 3e-2 if dtype == "bfloat16" else 1e-4
    return bool(np.allclose(a, b, rtol=tol, atol=tol))


def default_table_path(device: str | None = None,
                       cache_dir: str | None = None) -> str | None:
    """PALLAS_TUNE_TABLE, else ``pallas_tune.json`` inside the resolved
    compile-cache directory (``runtime/device.resolve_cache_dir``: the
    JAX_COMPILATION_CACHE_DIR / COMPILE_CACHE_DIR / DEVICE=tpu-default
    chain), so the tuned-variant choices and the executables they
    select survive restarts TOGETHER — a table entry whose executable
    is also disk-cached costs a restart zero compiles
    (docs/kernel_tuning.md).  No cache dir -> None, in-memory only: the
    sweep re-runs per process, the right default for tests and CPU
    golden runs that want cold, hermetic state.  Config-less callers
    leave the arguments None and get the DEVICE / COMPILE_CACHE_DIR
    env vars."""
    p = os.environ.get("PALLAS_TUNE_TABLE")
    if p:
        return p
    from ..runtime.device import resolve_cache_dir

    if device is None:
        device = os.environ.get("DEVICE", "tpu").lower()
    resolved = resolve_cache_dir(device, cache_dir)
    return os.path.join(resolved, "pallas_tune.json") if resolved else None


def _load_table(path: str | None) -> None:
    if not path:
        return
    with _LOCK:
        if path in _LOADED:
            return
        _LOADED.add(path)
    try:
        with open(path) as f:
            data = json.load(f)
        entries = data.get("table", {})
        if not isinstance(entries, dict):
            raise ValueError("tuning table is not an object")
        for key, vkey in entries.items():
            parse_variant(vkey)  # junk on disk must not reach a trace
            with _LOCK:
                _TABLE.setdefault(key, vkey)
    except FileNotFoundError:
        pass
    except Exception:
        with _LOCK:
            _COUNTS["persist_errors"] += 1
        _event("persist_error")


def _persist_table(path: str | None) -> None:
    if not path:
        return
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with _LOCK:
            body = {"version": 1, "table": dict(sorted(_TABLE.items()))}
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(body, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)  # atomic: concurrent readers see old or new
    except Exception:
        with _LOCK:
            _COUNTS["persist_errors"] += 1
        _event("persist_error")


def _install(kind: str, bundle, replicas, key: str, vkey: str,
             block_size: int, interpret: bool):
    """Winner -> fleet-shared ExecutableCache, keyed (shape key,
    variant).  Every replica resolving the same key gets the SAME
    wrapper object, so spawns/rebuilds/replays reuse its jit cache —
    the zero-extra-compile inheritance path."""
    import jax

    from ..runtime.compile_cache import shared_executable

    fn = shared_executable(
        f"{kind}_kernel", bundle, replicas,
        lambda: jax.jit(_make_call(kind, vkey, block_size, interpret)),
        statics=(key, vkey),
    )
    with _LOCK:
        _COUNTS["installs"] += 1
    _event("install")
    return fn


def _sweep(kind: str, key: str, *, b, kvh, n_rep, d, block_size, t,
           dtype, quant, interpret) -> str:
    iters = int(os.environ.get("PALLAS_AUTOTUNE_ITERS", str(SWEEP_ITERS)))
    cands = enumerate_variants(
        kind, t=t, bs=block_size or t, kvh=kvh, d=d, n_rep=n_rep,
        dtype=dtype, quant=quant,
    )
    with _LOCK:
        _COUNTS["sweeps"] += 1
        _COUNTS["candidates"] += len(cands)
    _event("sweep")
    args, ref = _probe(kind, b=b, kvh=kvh, n_rep=n_rep, d=d,
                       bs=block_size or t, t=t, dtype=dtype, quant=quant)
    call_args = tuple(a for a in args if a is not None)
    timings: dict[str, float] = {}
    errors: dict[str, str] = {}
    any_noisy = False
    best_key, best_t = "b1", float("inf")
    for var in cands:
        vkey = var.key()
        fn = _make_call(kind, vkey, block_size, interpret)
        try:
            out = fn(*call_args)
            if not _verify(out, ref, dtype):
                with _LOCK:
                    _COUNTS["reject_verify"] += 1
                _event("reject_verify")
                continue
            per, noisy = _time_per_call(fn, call_args, iters, SWEEP_REPS)
        except Exception as e:
            with _LOCK:
                _COUNTS["reject_error"] += 1
            _event("reject_error")
            errors[vkey] = f"{type(e).__name__}: {e}"
            continue
        with _LOCK:
            _COUNTS["timed"] += 1
        any_noisy = any_noisy or noisy
        timings[vkey] = per
        if per < best_t:
            best_key, best_t = vkey, per
    if not timings:
        # Nothing survived to measurement: there is no winner to
        # install or persist.  "b1" by default would put an unverified
        # (possibly uncompilable) kernel into the tuning table.
        first = next(iter(errors.items()), None)
        raise RuntimeError(
            f"autotune sweep for {key}: none of {len(cands)} candidate "
            f"variant(s) could be verified and timed "
            f"({len(errors)} raised, {len(cands) - len(errors)} "
            f"mismatched the reference)"
            + (f"; first error [{first[0]}]: {first[1][:500]}" if first else "")
        )
    with _LOCK:
        _RESULTS[key] = {
            "winner": best_key,
            "candidates": len(cands),
            "timed": len(timings),
            "noisy": any_noisy,
            "per_call_us": {
                k: round(v * 1e6, 2) for k, v in sorted(timings.items())
            },
        }
    return best_key


def ensure_tuned(kind: str, bundle, replicas, *, b: int, kvh: int,
                 n_rep: int, d: int, block_size: int = 0, t: int = 0,
                 dtype: str = "float32", quant: bool = False,
                 interpret: bool = False, pin: str | None = None,
                 table_path: str | None = "") -> str:
    """Resolve the tuned variant for one serving shape: honor a pin,
    answer from the (persisted) tuning table, or run a measured sweep —
    then install the winner into the ExecutableCache.  Returns the
    variant key the caller should thread into its serving executables'
    static descriptors.  ``table_path``: ``""`` = resolve the default
    (PALLAS_TUNE_TABLE / COMPILE_CACHE_DIR), None = no persistence."""
    # The placement's TP width keys the table entry (tp=1 placements
    # add nothing): sweeps under a TP mesh measure the SHARDED kernel,
    # and their winners must never be served to single-device traces.
    tp = int(getattr(replicas, "tp_width", 1) or 1)
    key = tune_key(kind, b=b, kvh=kvh, n_rep=n_rep, d=d,
                   block_size=block_size, t=t, dtype=dtype, quant=quant,
                   tp=tp)
    path = default_table_path() if table_path == "" else table_path
    if pin:
        var = parse_variant(pin)  # ValueError on junk: fail at boot
        if (kind in ("paged_decode", "latent_decode") and t
                and t % var.blocks_per_step != 0):
            raise ValueError(
                f"PALLAS_VARIANT={pin!r}: blocks_per_step="
                f"{var.blocks_per_step} does not divide table width {t}"
            )
        vkey = var.key()
        with _LOCK:
            _TABLE[key] = vkey
            _COUNTS["pins"] += 1
        _event("pin")
        _install(kind, bundle, replicas, key, vkey, block_size, interpret)
        return vkey
    _load_table(path)
    with _LOCK:
        got = _TABLE.get(key)
    if got is not None:
        with _LOCK:
            _COUNTS["hits"] += 1
        _event("hit")
        _install(kind, bundle, replicas, key, got, block_size, interpret)
        return got
    winner = _sweep(kind, key, b=b, kvh=kvh, n_rep=n_rep, d=d,
                    block_size=block_size, t=t, dtype=dtype, quant=quant,
                    interpret=interpret)
    with _LOCK:
        _TABLE[key] = winner
    _persist_table(path)
    _install(kind, bundle, replicas, key, winner, block_size, interpret)
    return winner


def stats() -> dict:
    """Counters + table + last sweep details: /status.decode.autotune
    and tests/test_structural_counters.py read this one snapshot."""
    with _LOCK:
        return {
            "counts": dict(_COUNTS),
            "table": dict(sorted(_TABLE.items())),
            "sweeps": {k: dict(v) for k, v in sorted(_RESULTS.items())},
        }


def clear() -> None:
    """Test hook: forget tables, results and counters (files on disk
    stay; pass a fresh table_path to isolate persistence tests)."""
    with _LOCK:
        _TABLE.clear()
        _RESULTS.clear()
        _LOADED.clear()
        for k in _COUNTS:
            _COUNTS[k] = 0
