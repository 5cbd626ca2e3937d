"""Shared device-time measurement: the two-scan-length method.

Wall time of K on-device iterations inside ONE executable is
``K x device_time + RTT``.  Timing scans of K and 2K iterations and
differencing makes the per-dispatch round-trip cancel EXACTLY —
instead of subtracting a separately-sampled RTT that jitters ±10 ms
(the weakness of pallas_ab.py's old method).

Every scan body carries a scalar data dependency into the next
iteration (input + carry*0 — numerically a no-op XLA must still
honor), so the loop cannot be collapsed or hoisted.
"""

from __future__ import annotations

import time

REPS = 5


def device_time_per_call(fn, args, carry_idx: int = -1, iters: int = 8,
                         reps: int = REPS):
    """Median device-seconds per ``fn(*args)`` call.

    Returns (per_call_s, noisy): ``noisy`` means the 2K scan measured
    no slower than the K scan (host jitter swamped the signal) and the
    value fell back to wall_K / K — an UPPER bound, flagged so tables
    can say so.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    def make(n: int):
        def scan_k(*xs):
            def body(carry, _):
                xs2 = list(xs)
                xs2[carry_idx] = xs2[carry_idx] + (carry * 0).astype(
                    xs2[carry_idx].dtype
                )
                out = fn(*xs2)
                return out.astype(jnp.float32).ravel()[0], ()

            carry, _ = lax.scan(body, jnp.float32(0), None, length=n)
            return carry

        return jax.jit(scan_k)

    s1, s2 = make(iters), make(2 * iters)
    dev = jax.device_put(tuple(args))
    float(jax.device_get(s1(*dev)))  # compile
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


def chunked_time_per_step(jit_chunk, params, state, iters: int | None = None,
                          reps: int = REPS):
    """Per-decode-step device seconds for a generate_chunk-style
    executable (``jit_chunk(params, state, n_steps) -> (state, toks)``,
    n_steps static).  Same differencing idea: the chunk IS the scan, so
    time n_steps=K vs 2K calls and difference.

    The state is NOT threaded between timed calls (each call re-decodes
    from the same state — steady-state work per step, no drift in shapes
    or content), so ``jit_chunk`` must not donate its state argument.

    iters defaults to CHUNK_ITERS (64): per-step times are fractions of
    a millisecond, so short chunks drown in host jitter — K must be
    large enough that K x step_time clears ±10 ms.  Steps past the
    decode budget are harmless (token/cache writes are mode="drop").
    """
    import os

    import jax

    if iters is None:
        iters = int(os.environ.get("CHUNK_ITERS", "64"))

    def wall(n: int) -> float:
        jax.device_get(jit_chunk(params, state, n)[1])  # compile
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.device_get(jit_chunk(params, state, n)[1])
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2]

    w1, w2 = wall(iters), wall(2 * iters)
    noisy = w2 <= w1
    per = (max(w1, 1e-9) / iters) if noisy else (w2 - w1) / iters
    return per, noisy
