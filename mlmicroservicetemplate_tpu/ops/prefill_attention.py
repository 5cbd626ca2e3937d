"""Prompt-window attention: a tiled online-softmax Pallas kernel.

A chunked prefill window (``models/llama.paged_prefill_chunk``) attends
``C`` queries at positions ``start .. start+C-1`` over the row's keys:
every key before the window plus the causal, pad-gated part of the
window itself, and on a window layer only the ``window`` keys up to each
query's own — ``models/llama._prefill_mask``.  XLA runs that as einsum →
float32 ``[H, C, K]`` scores in HBM → where → softmax → bf16 → einsum:
the score tensor (0.4-0.8 GB a layer at the served widths) crosses HBM
four or five times.  Here it never exists: a program holds a tile of
queries, walks the key tiles that can hold a visible key and folds each
into a running max, sum and accumulator in VMEM, FlashAttention-style —
the decode kernel's fold (``ops/paged_attention._attend_tile``) with
many query rows.

- **Grid** ``(KVH, C/tq)``: a program serves the ``n_rep`` query heads
  of one KV head over ``tq`` queries as ONE ``[n_rep*tq, Dk]`` tile (a
  GQA group's heads ride as rows: no repeated copy of K and V), or one
  expanded head of a latent configuration (``KVH = H``, ``n_rep`` 1).
- **The key loop is inside the program** (PERF.md section 6, PR 32: a
  grid step's index maps run on the scalar core whether the step is live
  or not): a ``fori_loop`` over the q tile's LIVE key tiles, K and V
  copied ``[tk, D]`` at a time from the dense, already gathered keys
  where they lie into two VMEM slots, the next trip's copy in flight
  under this trip's fold.
- **The mask comes from positions.**  The wrapper lays the keys'
  positions out a tile a row (``kp [K/tk, tk]``, a dead key — pad inside
  the window, past its end — as ``DEAD_KEY``), the kernel compares them
  with its queries' (``start`` arrives as a scalar, so one executable
  serves every window of every prompt) for the causal side and the
  band.  ``live_tiles`` gives each q tile its ``[first, last]`` key
  tile and whether a tile is visible to every query row whole (no mask
  to compute): a tile above the diagonal, behind a window layer's band
  or past the window's last key costs no trip, no copy and no fold.
- ``K`` need not be a multiple of ``tk``: the last tile is copied from
  ``K - tk`` on and the keys an earlier tile already folded are dead in
  its row of ``kp``.

Arithmetic as ``common.mha_attention``: scores, max, sum and the
accumulator in float32, probabilities cast to the values' dtype before
the second matmul; only the order of summation differs.  A query that
sees no key at all (a pad query behind a band of pad keys) reads a
finite average, as XLA's softmax over a row of fill values does.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

#: A dead key's position in ``kp``: after every query, so the causal
#: comparison alone masks it.
DEAD_KEY = 2**30

#: Rows of a program's q tile (``n_rep * tq``) and keys of a key tile, where the
#: window is wide enough: a ``[1024, 1024]`` float32 score tile is 4 MB of VMEM.
#: On a v5e (PERF.md section 6, PR 34) 1024 keys a trip beat 512 and 256 at
#: every q tile (0.90 / 1.26-1.55 / 2.07-2.80 ms, Trinity's full layer at 5120).
Q_TILE_ROWS = 1024
KEY_TILE = 1024

#: Scoped VMEM a program may use: the score tile and its float32
#: temporaries at ``[Q_TILE_ROWS, KEY_TILE]`` (4 MB each) pass the
#: compiler's 16 MiB default.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def tile_sizes(c: int, n_rep: int, k_len: int, q_tile: int = 0,
               key_tile: int = 0) -> tuple[int, int]:
    """``(tq, tk)`` for a window of ``c`` queries over ``k_len`` keys — the
    arguments where given, else ``KEY_TILE`` keys (at most ``k_len``) and, of
    the divisors of ``c`` within ``Q_TILE_ROWS / n_rep`` queries, that cap or
    the largest of whole 8-row sublane tiles (32 at ``n_rep`` 20), else any."""
    cap = min(c, max(Q_TILE_ROWS // n_rep, 8))
    under = [] if q_tile or c % cap == 0 else [t for t in range(cap, 0, -1) if c % t == 0]
    tq = q_tile or next((t for t in under if t % 8 == 0), under[0] if under else cap)
    return tq, min(key_tile or KEY_TILE, k_len)


def _tile_keys(k_len: int, tk: int):
    """``(kidx [nkt, tk], fresh [nkt, tk])``: the key a tile's lane
    holds and whether no earlier tile held it (the last tile starts at
    ``k_len - tk``)."""
    nkt = -(-k_len // tk)
    t0 = jnp.arange(nkt, dtype=jnp.int32)[:, None] * tk
    kidx = jnp.minimum(t0, k_len - tk) + jnp.arange(tk, dtype=jnp.int32)[None, :]
    return kidx, kidx >= t0


def live_tiles(kpos0, start, chunk_mask, window: int, k_len: int, tq: int,
               tk: int):
    """``(kp [nkt, tk], live [nqt, 2], whole [nqt, nkt])`` int32 for a
    window of ``C = chunk_mask.shape[0]`` queries from position ``start``
    over ``k_len`` keys from position ``kpos0``: the keys' positions a
    tile a row (``DEAD_KEY`` where ``_prefill_mask`` shows the key to no
    query), per q tile the first and last key tile that holds a key some
    query of it sees (``(1, 0)``: none), and per pair whether EVERY query
    row of the q tile sees EVERY key of the tile."""
    c = chunk_mask.shape[0]
    kidx, fresh = _tile_keys(k_len, tk)
    kpos = kpos0 + kidx
    off = kpos - start
    wvalid = jnp.take(chunk_mask.astype(jnp.int32), jnp.clip(off, 0, c - 1))
    alive = fresh & ((off < 0) | ((off < c) & (wvalid != 0)))
    kp = jnp.where(alive, kpos, DEAD_KEY).astype(jnp.int32)
    nkt = kp.shape[0]
    q_lo = start + jnp.arange(c // tq, dtype=jnp.int32)[:, None] * tq  # [nqt, 1]
    q_hi = q_lo + tq - 1
    t_min = kp.min(axis=1)[None, :]  # DEAD_KEY: a tile with no key alive
    t_max = jnp.where(alive, kpos, -1).max(axis=1)[None, :]
    seen = t_min <= q_hi
    whole = alive.all(axis=1)[None, :] & (t_max <= q_lo)
    if window:
        seen &= t_max > q_lo - window
        whole &= t_min > q_hi - window
    j = jnp.arange(nkt, dtype=jnp.int32)[None, :]
    first = jnp.min(jnp.where(seen, j, nkt), axis=1)
    last = jnp.max(jnp.where(seen, j, -1), axis=1)
    some = last >= 0
    live = jnp.stack(
        [jnp.where(some, first, 1), jnp.where(some, last, 0)], axis=1)
    return kp, live.astype(jnp.int32), whole.astype(jnp.int32)


def count_live_tiles(start: int, n_valid: int, c: int, kpos0: int,
                     k_len: int, window: int, tq: int, tk: int) -> tuple[int, int]:
    """``(live, total)`` (q tile, key tile) pairs of one window of one
    layer on the HOST, from the numbers the dispatch knows — ``start``,
    the window's ``n_valid`` real tokens (a prefix of its ``c``), and the
    keys' geometry: what ``live_tiles`` reads for a prefix mask
    (tests/test_prefill_attention.py), for the
    ``prefill_key_tiles_*_total`` counters."""
    nqt, nkt = c // tq, -(-k_len // tk)
    i = np.arange(nqt)
    # Positions a q tile's queries see: up to its last query's own (never
    # past the window's last real token), from its first query's band on.
    hi = start + np.minimum((i + 1) * tq, n_valid) - 1
    lo = np.maximum(start + i * tq - window + 1, kpos0) if window else np.full(nqt, kpos0)
    hi = np.minimum(hi, kpos0 + k_len - 1)
    t_hi = (hi - kpos0) // tk
    t_lo = (lo - kpos0) // tk
    live = int(np.where(hi >= lo, t_hi - t_lo + 1, 0).sum())
    return live, nqt * nkt


def _prefill_kernel(live_ref, whole_ref, start_ref, q_ref, kp_ref, k_hbm,
                    v_hbm, o_ref, m_scr, l_scr, a_scr, kbuf, vbuf, sem, *,
                    scale: float, n_rep: int, tq: int, tk: int, dk: int,
                    dv: int, window: int, k_len: int):
    """Program (g, i): KV head ``g``'s ``n_rep`` query heads over q tile
    ``i``.  Refs: ``live [nqt, 2]``, ``whole [nqt, nkt]``, ``start [1]``
    (prefetch, SMEM); q ``[tq, n_rep*dk]`` (the group's lane slice of
    ``[C, H*dk]``); ``kp [nkt, tk]``; k ``[K, KVH*dk]`` and v
    ``[K, KVH*dv]`` where they lie; the output ``[tq, n_rep*dv]``; then
    m / l ``[n_rep*tq, 1]`` and acc ``[n_rep*tq, dv]`` float32, the two
    K and V slots and their semaphores ``[2, 2]``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    g, i = pl.program_id(0), pl.program_id(1)
    first = live_ref[i, 0]
    n = live_ref[i, 1] - first + 1  # 0: no key tile is live
    align = math.gcd(tk, k_len, 16)  # of a key tile's first row

    def copies(t, slot):
        rows = pl.ds(pl.multiple_of(jnp.minimum(t * tk, k_len - tk), align), tk)
        return [
            pltpu.make_async_copy(
                hbm.at[rows, pl.ds(pl.multiple_of(g * d, d), d)],
                buf.at[slot], sem.at[which, slot])
            for which, (hbm, buf, d) in enumerate(
                ((k_hbm, kbuf, dk), (v_hbm, vbuf, dv)))
        ]

    @pl.when(n > 0)
    def _first():
        for cp in copies(first, 0):
            cp.start()

    m_scr[...] = jnp.full_like(m_scr, -1e30)
    l_scr[...] = jnp.zeros_like(l_scr)
    a_scr[...] = jnp.zeros_like(a_scr)
    # The group's heads as rows: [tq, n_rep*dk] -> [n_rep*tq, dk].
    q = jnp.concatenate(
        [q_ref[:, r * dk:(r + 1) * dk] for r in range(n_rep)], axis=0)
    qi = jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
    qpos = start_ref[0] + i * tq + jnp.concatenate([qi] * n_rep, axis=0)

    def fold(t, slot, masked: bool):
        s = jax.lax.dot_general(
            q, kbuf[slot], dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=f32,
        ) * scale  # [rows, tk]
        if masked:
            kp = kp_ref[pl.ds(t, 1), :]  # [1, tk]
            see = kp <= qpos
            if window:
                see &= qpos - kp < window
            s = jnp.where(see, s, f32(-1e30))
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        v = vbuf[slot]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=f32,
        )  # [rows, dv]
        a_scr[...] = a_scr[...] * corr + pv
        m_scr[...] = m_new

    def trip(s, carry):
        t, slot = first + s, s % 2

        @pl.when(s + 1 < n)
        def _next():
            for cp in copies(t + 1, 1 - slot):
                cp.start()

        for cp in copies(t, slot):
            cp.wait()
        whole = whole_ref[i, t] != 0

        @pl.when(whole)
        def _plain():
            fold(t, slot, masked=False)

        @pl.when(jnp.logical_not(whole))
        def _masked():
            fold(t, slot, masked=True)

        return carry

    jax.lax.fori_loop(0, n, trip, 0)
    out = a_scr[...] / jnp.maximum(l_scr[...], 1e-20)
    for r in range(n_rep):
        o_ref[:, r * dv:(r + 1) * dv] = out[r * tq:(r + 1) * tq].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "scale", "q_tile", "key_tile", "interpret"),
)
def prefill_attention(
    q: jax.Array,  # [C, H, Dk]: the window's queries, position start + row
    k: jax.Array,  # [K, KVH, Dk]: dense keys, position kpos0 + row
    v: jax.Array,  # [K, KVH, Dv]
    kpos0,  # traced scalar: the first key's position
    start,  # traced scalar: the first query's position
    chunk_mask: jax.Array,  # [C] 1 = a real token of the window
    window: int = 0,
    scale: float | None = None,
    q_tile: int = 0,
    key_tile: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """One prompt window's attention under ``models/llama._prefill_mask``;
    returns ``[C, H, Dv]`` (the module docstring).  A head dim that is no
    multiple of 128 lanes (64; a latent configuration's 192 for scores) is
    padded with zero lanes here — the kernel slices a KV head's lanes out
    of ``[K, KVH*D]``, and the MXU contracts 256 in the passes 192 take."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c, h, dk = q.shape
    k_len, kvh, dv_out = v.shape
    n_rep = h // kvh
    if scale is None:
        scale = 1.0 / math.sqrt(dk)

    def lanes(x):
        return jnp.pad(x, ((0, 0), (0, 0), (0, -x.shape[-1] % 128)))

    q, k, v = lanes(q), lanes(k), lanes(v)
    dk, dv = q.shape[-1], v.shape[-1]
    tq, tk = tile_sizes(c, n_rep, k_len, q_tile, key_tile)
    kp, live, whole = live_tiles(
        jnp.asarray(kpos0, jnp.int32), jnp.asarray(start, jnp.int32),
        chunk_mask, window, k_len, tq, tk)
    rows = n_rep * tq
    kernel = functools.partial(
        _prefill_kernel, scale=scale, n_rep=n_rep, tq=tq, tk=tk, dk=dk,
        dv=dv, window=window, k_len=k_len)
    where = pl.BlockSpec(memory_space=pl.ANY)  # read where it lies
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(kvh, c // tq),
        in_specs=[
            pl.BlockSpec((tq, n_rep * dk), lambda g, i, *_: (i, g)),
            pl.BlockSpec(kp.shape, lambda g, i, *_: (0, 0)),
            where, where,
        ],
        out_specs=pl.BlockSpec((tq, n_rep * dv), lambda g, i, *_: (i, g)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, dv), jnp.float32),
            pltpu.VMEM((2, tk, dk), k.dtype),
            pltpu.VMEM((2, tk, dv), v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((c, h * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="prefill_attention",
    )(live, whole, jnp.asarray(start, jnp.int32).reshape(1),
      q.reshape(c, h * dk), kp, k.reshape(k_len, kvh * dk),
      v.reshape(k_len, kvh * dv))
    return out.reshape(c, h, dv)[..., :dv_out]


def scores_in_hbm(hlo_text: str, n_elems: int, exact: bool = False) -> list:
    """The instructions of a COMPILED program's text whose result holds a
    float32 array of at least (``exact``: of just) ``n_elems`` elements —
    a window's ``[H, C, K]`` scores, where XLA runs its attention; the
    prompt scan's decay matrices, where XLA runs that: what
    ``tests/test_chip_compile.py`` holds the prompt-window executables to
    (``prefill_scores_in_hbm: []``)."""
    import re

    hits = []
    for line in hlo_text.splitlines():
        m = re.search(r" = (\(.*?\)|\S+) [\w\-]+\(", line)  # the result's type(s)
        if m and any((n == n_elems if exact else n >= n_elems) for n in (
                math.prod(map(int, dims.split(",")))
                for dims in re.findall(r"f32\[([\d,]+)\]", m.group(1)))):
            hits.append(line.strip()[:200])
    return hits


def prefill_attention_ref(q, k, v, kpos0, start, chunk_mask, window: int = 0,
                          scale: float | None = None):
    """The XLA form the kernel replaces, for tests: ``mha_attention``
    under ``_prefill_mask`` with K and V repeated a query head."""
    from ..models.common import mha_attention
    from ..models.llama import _prefill_mask, _repeat_kv

    n_rep = q.shape[1] // k.shape[1]
    mask = _prefill_mask(
        kpos0 + jnp.arange(k.shape[0]), chunk_mask[None], start, window)
    return mha_attention(
        q[None], _repeat_kv(k[None], n_rep), _repeat_kv(v[None], n_rep),
        mask=mask, scale=scale)[0]
