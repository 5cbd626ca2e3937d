"""Paged-attention decode: fused attention over a block-paged KV pool.

Paged mode (``PAGED_KV=1``) stores the KV cache as a pool of
fixed-size token blocks shared by every live stream, with a per-row
block table mapping logical position
``p -> pool[table[row, p // BS], p % BS]``.

**The pool's layout** (stated here once; docs/kernel_tuning.md repeats
it): a pool leaf is ``[NB, BS, C]``, ``C`` the product of a token's
trailing dims — ``KVH*D`` for a payload, ``KVH`` for an int8 pool's
scales — from allocation (``engine/streams._build_empty_paged``) to the
last reader.  That is the kernel's own block view: a ``[BS, KVH*D]``
tile is lane-dense, and the TPU tiles a ``[.., KVH, D]`` array over
(KV head, lane) but ``[.., BS, KVH*D]`` over (token, lane), so merging
the trailing dims of a whole pool is a relayout of every byte of it,
not a bitcast.  No 4-D pool exists beside this one.  Writers merge the
few rows they write (``scatter_rows``); readers that want
``[.., KVH, D]`` unmerge the rows they gathered, never the pool.

This module is the device-side half:

- ``gather_pages``: XLA fallback — materialize a row's dense
  ``[B, W, KVH, D]`` view through the table (one ``take``; XLA fuses
  it into the consumer; ``tail`` unmerges the gathered rows).  The
  models' paged decode steps attend over this view with their EXISTING
  attention code, which is what makes paged decode token-identical to
  the contiguous layout by construction.
- ``paged_decode_attention``: Pallas kernel — grid ``(B,)``, a row a
  program, with the block table as a scalar-prefetch operand and the
  pools left where they lie: the program copies exactly its row's LIVE
  blocks HBM->VMEM itself, K at a time into two slots (the gather never
  materializes in HBM), and folds each into an online-softmax
  accumulator, FlashAttention-style.  Composes with ``QUANT_KV=int8``:
  payloads cross at int8 width and dequantize in VMEM like
  ``ops/attention.decode_attention``; their per-token-head scales (a
  ``1/D`` of the bytes) ride as the rows' gathered ``[B, T*BS, KVH]``.
  ``interpret=True`` runs the same kernel on CPU (the test/fallback
  path, same pattern as ``parallel/ring.py``).
- ``latent_decode_attention``: the same kernel body over a LATENT pool
  (multi-head latent attention, ``models/llama.py`` ``attention="mla"``):
  one leaf a layer, ``C`` = the latent row's lanes, one KV head that
  every query head shares; a block is copied once and folded as keys
  (all lanes) and values (its first ``v_dim`` lanes).

**The kernel's work follows each row's live keys** (PR 32), not the
table's width.  A serving table is mostly not keys: the loop pads a
row past its stream's blocks with the sentinel and points a freed
slot's whole row at it, and a live row's blocks are allocated a chunk
ahead of its keys.  ``live_programs`` reads, from the two operands the
kernel gets anyway, each row's ``[first, last]`` range of K-block
programs that hold a key which is valid in ``key_valid`` AND lies in a
real block of the UNCLAMPED ``table`` (``0 <= id < NB``) — both,
because nothing clears a freed slot's mask on the device, and a range,
because a window view (``models/llama.window_view``) has dead keys at
its head too; a hole inside the range is folded masked.  The range is
the second scalar-prefetch operand and the trip count of the row's
block loop: a table entry outside it costs no program, no copy and no
fold, and a row with no live entry costs one empty grid step (~0.6 us
on a v5e: its q, mask and output blocks still ride — ``[H, D]`` each,
whatever the variant) and reads zeros.
A fully masked fold after live ones leaves ``m``, ``l`` and ``acc``
bit for bit as they were, so every live row's output is what a walk of
the whole table computes, to the last bit
(tests/test_pallas_autotune.py).  The walk itself — grid ``(B, T/K)``,
a pipelined program every K table entries — ran ~0.5 us of grid-step
bookkeeping a program, live or not (PERF.md section 6, PR 32).

The kernel is parameterized by a :class:`Variant` (docs/
kernel_tuning.md): the axes ``ops/autotune.py`` sweeps at warmup.
Every variant computes the same masked online softmax in the same
f32 accumulators — variants rearrange WHERE work happens (grid
folding, head batching, dequant placement, MXU input width), never
WHAT is accumulated, which is what keeps each one token-identical to
``paged_attention_ref`` by construction.  q crosses HBM as ``[B, H, D]``
and so does the output, under every variant: the ``head_batched`` fold's
block-diagonal ``[H, KVH*D]`` operand is built (``block_diagonal_q``),
and its accumulator's diagonal read out (``diagonal_out``), in VMEM, once
a row (PR 59; XLA built and undid that layout around the call before).
The only lossy axis (``accbf16`` scratch) is excluded from sweeps and
reachable solely through an explicit ``PALLAS_VARIANT`` pin.

Sentinel table entries (freed slots, a row's tail) come to the kernel
as they are — out of range is how it tells them from blocks; it clamps
them itself before any lookup, and no program reads one.  ``key_valid``
masks within the live range; ``gather_pages`` clamps internally.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Variant:
    """One point in the paged/slab decode-kernel tuning space.

    - ``blocks_per_step``: K sequential pool blocks copied and folded
      per trip of a row's block loop — the online-softmax fold then runs
      over ``K*BS`` keys at once (fewer, larger MXU issues; a row's live
      range is counted in K-block programs; K must divide the table
      width so no pad-block path exists).  Paged kernel only; the
      whole-slab kernel has no block axis.
    - ``head_batched``: replace the static ``for g in range(kvh)``
      Python loop with ONE kvh-batched ``dot_general`` so every head's
      ``n_rep x D`` tile is in flight together (packs full 128-lane
      registers when a single group's R·D tile is narrow).
    - ``native_mxu``: feed bf16 payloads to the MXU at storage width
      (bf16 x bf16 -> f32 via ``preferred_element_type``) instead of
      upcasting to f32 copies in VMEM first.  The QK products are exact
      and every accumulation is f32; the probabilities are rounded to
      bf16 for the PV issue (Mosaic wants one operand dtype), exactly as
      the XLA path does (``common.mha_attention``).  A no-op unless q
      and the pools are bf16.
    - ``fold_scales``: int8 path — keep payloads UNscaled through the
      QK/PV dots and fold the per-token-head scales into the score
      matrix / probability weights instead of dequantizing whole
      ``[KB, KVH, D]`` tiles ((q·k8)·ks == q·(k8·ks) in real
      arithmetic; the broadcast multiply shrinks from KB·D to R·KB
      elements per head).
    - ``acc_dtype``: online-softmax scratch width.  ``"f32"`` always;
      ``"bf16"`` is lossy, never enumerated by the sweep, and exists
      only for an explicit operator pin.
    """

    blocks_per_step: int = 1
    head_batched: bool = False
    native_mxu: bool = False
    fold_scales: bool = False
    acc_dtype: str = "f32"

    def key(self) -> str:
        parts = [f"b{self.blocks_per_step}"]
        if self.head_batched:
            parts.append("hb")
        if self.native_mxu:
            parts.append("nat")
        if self.fold_scales:
            parts.append("fs")
        if self.acc_dtype != "f32":
            parts.append(f"acc{self.acc_dtype}")
        return "-".join(parts)


DEFAULT_VARIANT = Variant()


def parse_variant(key: str | None) -> Variant:
    """``"b4-hb-fs"`` -> Variant; ``""``/None -> the default (the
    pre-autotuner kernel, exactly).  Raises ``ValueError`` on junk so
    a typo'd ``PALLAS_VARIANT`` pin fails at boot, not at trace."""
    if not key:
        return DEFAULT_VARIANT
    blocks, hb, nat, fs, acc = 1, False, False, False, "f32"
    for part in key.split("-"):
        if part.startswith("b") and part[1:].isdigit():
            blocks = int(part[1:])
            if blocks < 1:
                raise ValueError(f"variant {key!r}: blocks_per_step < 1")
        elif part == "hb":
            hb = True
        elif part == "nat":
            nat = True
        elif part == "fs":
            fs = True
        elif part.startswith("acc") and part[3:] in ("f32", "bf16"):
            acc = part[3:]
        else:
            raise ValueError(
                f"unknown variant token {part!r} in {key!r} (grammar: "
                f"b<K>[-hb][-nat][-fs][-accbf16])"
            )
    return Variant(blocks, hb, nat, fs, acc)


def scatter_rows(pool: jax.Array, dest: jax.Array, values: jax.Array) -> jax.Array:
    """Write token rows ``values`` ``[N, ...]`` at flat positions
    ``dest`` ``[N]`` (``block * BS + offset``; out of range drops) of a
    pool ``[NB, BS, C]``, where it lies: the N rows merge their token
    dims (``[N, KVH, D]`` payload, ``[N, KVH, 1]`` scale rows), the
    pool is only re-viewed ``[NB*BS, C]`` (a bitcast on the chip at
    the serving block size: tests/test_chip_compile.py) and scattered
    into in place."""
    nb, bs, c = pool.shape
    flat = pool.reshape(nb * bs, c).at[dest].set(
        values.reshape(values.shape[0], c).astype(pool.dtype), mode="drop"
    )
    return flat.reshape(pool.shape)


def gather_pages(pool: jax.Array, table: jax.Array, block_size: int,
                 tail: tuple = ()) -> jax.Array:
    """Dense view of each row's blocks: ``[NB, BS, C] x [B, T]`` ->
    ``[B, T*BS, C]``, or ``[B, T*BS, *tail]`` when the caller names the
    token dims to unmerge (``(KVH, D)``; ``(KVH, 1)`` for scales) — on
    the gathered rows, never on the pool.  Out-of-range table ids (the
    freed-slot sentinel) clamp to the last block; callers mask those
    positions with ``key_valid``, and clamped garbage is finite (pools
    are zero-initialized), so a masked softmax stays well-behaved."""
    nb, _, c = pool.shape
    flat = pool.reshape(nb * block_size, c)
    idx = (
        jnp.clip(table, 0, nb - 1)[:, :, None] * block_size
        + jnp.arange(block_size)[None, None, :]
    )  # [B, T, BS]
    b, t, _ = idx.shape
    out = jnp.take(flat, idx.reshape(b, t * block_size), axis=0)
    return out.reshape(out.shape[:2] + tuple(tail)) if tail else out


def scatter_pages(
    pool: jax.Array, table_row: jax.Array, values: jax.Array,
    block_size: int, start: int = 0,
) -> jax.Array:
    """Write ``values`` ``[W, ...]`` (token dims merged here, on the W
    rows) at logical positions ``start..start+W-1`` of ONE row's
    blocks.  Positions whose table entry is out of range (sentinel)
    drop — the paged insert relies on this for pad regions and freed
    slots."""
    nb, w = pool.shape[0], values.shape[0]
    p = start + jnp.arange(w)
    blk = jnp.take(table_row, p // block_size, mode="fill", fill_value=nb)
    # OOB where sentinel
    return scatter_rows(pool, blk * block_size + p % block_size, values)


def block_diagonal_q(q, kvh: int, n_rep: int):
    """In VMEM: a row's ``[H, D]`` q -> the ``head_batched`` fold's
    operand ``[H, KVH*D]`` (see ``_fold_block``) — head h's vector in its
    KV group's lane slice, zeros elsewhere: q laid KVH times along lanes,
    piece g kept on group g's rows (``g*R <= row < (g+1)*R``, a 2-D iota:
    no sublane slice at any ``n_rep``).  At one KV head the operand IS q."""
    if kvh == 1:
        return q
    row = jax.lax.broadcasted_iota(jnp.int32, q.shape, 0)
    zero = jnp.zeros_like(q)
    return jnp.concatenate(
        [jnp.where((row >= g * n_rep) & (row < (g + 1) * n_rep), q, zero)
         for g in range(kvh)], axis=1,
    )


def diagonal_out(acc, kvh: int, n_rep: int):
    """The inverse read-out, in VMEM: ``[H, KVH*D]`` -> ``[H, D]``, row
    h's lanes taken from its group's slice ``[g(h)*D, (g(h)+1)*D)`` — the
    diagonal ``[R, D]`` blocks of the ``head_batched`` accumulator, by a
    chain of selects on the same row-group iota (exact: every output is
    one element of ``acc``)."""
    if kvh == 1:
        return acc
    d = acc.shape[1] // kvh
    row = jax.lax.broadcasted_iota(jnp.int32, (acc.shape[0], d), 0)
    out = acc[:, (kvh - 1) * d:]
    for g in reversed(range(kvh - 1)):
        out = jnp.where(row < (g + 1) * n_rep, acc[:, g * d:(g + 1) * d], out)
    return out


def softmax_scratch(acc_block: tuple, dtype) -> list:
    """m/l/acc VMEM scratch for an accumulator ``[*rows, C]`` (a q block
    with its leading batch-1 dim dropped, or ``[H, KVH*D]`` under
    ``head_batched``): statistics ``[*rows, 1]``, accumulator
    ``[*rows, C]``."""
    from jax.experimental.pallas import tpu as pltpu

    rows = tuple(acc_block[:-1])
    return [
        pltpu.VMEM(rows + (1,), dtype),
        pltpu.VMEM(rows + (1,), dtype),
        pltpu.VMEM(tuple(acc_block), dtype),
    ]


def _group_onehot(rows: int, kvh: int, n_rep: int, g: int | None):
    """[rows, KVH] f32 one-hot of each query row's KV group: row r of
    the head-batched tile belongs to group ``r // n_rep``; a single
    group's tile (``g`` given) belongs to ``g`` throughout.  Built from
    2-D iotas (Mosaic has no 1-D iota)."""
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, kvh), 1)
    if g is not None:
        return (col == g).astype(jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, kvh), 0)
    lo = col * n_rep
    return ((row >= lo) & (row < lo + n_rep)).astype(jnp.float32)


def _scales_t(onehot, s_blk):
    """Per-key scales laid along lanes: ``[rows, KVH] x [KB, KVH] ->
    [rows, KB]``, each row carrying its group's column of ``s_blk``.
    The pool keeps scales key-major ([KB, KVH], keys on sublanes); the
    score matrix wants them key-minor.  An NT matmul against a one-hot
    is the transpose Mosaic lowers at any KVH — exact at HIGHEST
    precision (one non-zero product per output)."""
    return jax.lax.dot_general(
        onehot, s_blk, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


def _attend_tile(q, k, v, ks_t, vs_t, valid, m_prev, l_prev, a_prev, *,
                 scale: float):
    """One online-softmax fold of a ``[rows, C] x [KB, C]`` tile pair.
    ``ks_t``/``vs_t`` ([rows, KB], or None) are folded scales; ``valid``
    is the block's [1, KB] mask.  Everything stays 2-D with keepdims
    statistics — Mosaic's layout inference rejects 1-D vectors."""
    f32 = jnp.float32
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=f32,
    )  # [rows, KB]
    if ks_t is not None:
        s = s * ks_t
    s = s * scale
    s = jnp.where(valid != 0, s, f32(-1e30))
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
    if vs_t is not None:
        p = p * vs_t
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=f32,
    )  # [rows, C]
    return m_new, l_new, a_prev * corr + pv


def _fold_block(q_ref, k_blk, ks_blk, v_blk, vs_blk, valid, m_scr, l_scr,
                a_scr, *, scale: float, kvh: int, n_rep: int, d: int,
                var: Variant, q_diag=None):
    """Fold one key/value block into the online-softmax accumulators.

    Tiles are 2-D and lane-dense: ``k_blk``/``v_blk`` are ``[KB, KVH*D]``
    (the pool's own layout — see the module docstring),
    raw payloads (f32/bf16, or int8 when ``ks_blk``/``vs_blk`` carry
    the ``[KB, KVH]`` f32 scales); ``valid`` is the block's ``[1, KB]``
    mask.  A ``[KB, KVH, D]`` tile would pad its (KVH, D) minor dims to
    a full (8, 128) register tile — 4x the VMEM at KVH=4, D=64 — and
    its per-head slices are sublane-strided.

    - default: static loop over groups; group g's keys are the lane
      slice ``[g*D, (g+1)*D)``, q/out tiles ``[R, D]`` of the
      ``[1, KVH, R, D]`` blocks, scratch m/l ``[KVH, R, 1]`` and acc
      ``[KVH, R, D]``.
    - ``head_batched``: the q operand is block-diagonal ``[H, KVH*D]``
      (head h's vector in its group's lane slice, zeros elsewhere) —
      ``q_diag``, which the kernel built in VMEM from the row's
      ``[1, H, D]`` block (``block_diagonal_q``; a ref or a value), or
      ``q_ref[0]`` itself at one KV head — so ONE ``[H, KVH*D] x
      [KB, KVH*D]`` MXU issue scores every head and one ``[H, KB] x
      [KB, KVH*D]`` issue forms every head's output in the diagonal
      ``[R, D]`` blocks of acc ``[H, KVH*D]`` (the kernel reads the
      diagonal out at finalize, ``diagonal_out``; off-diagonal blocks
      are finite garbage).  Scratch m/l are ``[H, 1]``.
    """
    f32 = jnp.float32
    quant = ks_blk is not None
    native = var.native_mxu and not quant and (
        q_ref.dtype == jnp.bfloat16 and k_blk.dtype == jnp.bfloat16
    )

    def up(x):  # payload -> dot operand
        return x if native else x.astype(f32)

    def cols(x, g):  # group g's lane slice of a [KB, KVH*D] tile
        return x[:, g * d:(g + 1) * d]

    def dequant(x, s_blk, groups):
        # [KB, n*D] int8 payload x its [KB, KVH] scales, group by group
        # (each scale column lane-broadcasts over its D lanes).
        parts = [
            cols(x, g).astype(f32) * s_blk[:, g:g + 1] for g in groups
        ]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)

    def fold(sl, q, k, v, ks_t, vs_t):
        m, l, a = _attend_tile(
            q, k, v, ks_t, vs_t, valid,
            m_scr[sl].astype(f32), l_scr[sl].astype(f32),
            a_scr[sl].astype(f32), scale=scale,
        )
        m_scr[sl] = m.astype(m_scr.dtype)
        l_scr[sl] = l.astype(l_scr.dtype)
        a_scr[sl] = a.astype(a_scr.dtype)

    fold_scales = quant and var.fold_scales

    def tiles(g):
        """(k, v, ks_t, vs_t) dot operands: group ``g``'s lane slice,
        or every group's (the whole tile) when ``g`` is None."""
        groups = range(kvh) if g is None else (g,)
        sel = (lambda x: x) if g is None else (lambda x: cols(x, g))
        if fold_scales:
            onehot = _group_onehot(len(groups) * n_rep, kvh, n_rep, g)
            return (sel(k_blk).astype(f32), sel(v_blk).astype(f32),
                    _scales_t(onehot, ks_blk), _scales_t(onehot, vs_blk))
        if quant:
            return (dequant(k_blk, ks_blk, groups),
                    dequant(v_blk, vs_blk, groups), None, None)
        return up(sel(k_blk)), up(sel(v_blk)), None, None

    if var.head_batched:
        q = q_ref[0] if q_diag is None else q_diag[...]
        fold(slice(None), up(q), *tiles(None))
        return
    for g in range(kvh):
        fold(g, up(q_ref[0, g]), *tiles(g))


def live_programs(table: jax.Array, key_valid: jax.Array, nb_pool: int,
                  block_size: int, k: int) -> jax.Array:
    """``[B, 2]`` int32: per row the first and last grid program (``k``
    table entries each) that holds a key which is valid AND lies in a
    real block (``0 <= table < NB``: the freed-slot sentinel is out of
    range on purpose).  Both operands are needed — a freed slot's
    ``key_valid`` is stale (nothing clears it on the device), and a live
    row's table is allocated a chunk ahead of its keys.  A range, not a
    length: a window view (``models/llama.window_view``) has dead keys
    at its head too.  A row with no such key reads ``(1, 0)``: a range
    of ``last - first + 1 = 0`` programs."""
    b, t = table.shape
    real = (table >= 0) & (table < nb_pool)
    held = (key_valid != 0).reshape(b, t // k, k, block_size)
    live = (held & real.reshape(b, t // k, k, 1)).any(axis=(2, 3))
    j = jnp.arange(t // k, dtype=jnp.int32)
    first = jnp.min(jnp.where(live, j, t // k), axis=1)
    last = jnp.max(jnp.where(live, j, -1), axis=1)
    some = last >= 0
    return jnp.stack(
        [jnp.where(some, first, 1), jnp.where(some, last, 0)], axis=1
    ).astype(jnp.int32)


def _paged_kernel_v(*refs, scale: float, kvh: int, n_rep: int, d: int,
                    quant: bool, var: Variant, bs: int, latent: int = 0):
    """Grid step b: fold row b's live programs (``live_programs``) into
    its accumulators, K blocks ``table[b, j*K .. j*K+K-1]`` a trip of a
    ``fori_loop`` whose trip count is the row's own, then finalize.  The
    K+V blocks come by ``make_async_copy`` from the pools where they lie
    into two VMEM slots: a trip starts the next trip's copies — on the
    row's last trip the NEXT row's first — before it waits for its own,
    so a copy always flies under a fold; the slot parity runs on across
    rows (a row's first trip is the batch's ``base``-th).  A row with no
    live program starts no copy, folds nothing and writes zeros (acc = 0,
    l = 0).  Ref layout: tbl and the rows' ``(first, last, base)``
    (prefetch), q ([1, KVH, R, D], or [1, H, D] as it lies when
    head-batched), the k pool, the v pool (any memory space), when quant
    the row's k and v scales [1, T*BS, KVH], the row's mask
    [1, T/K, K*BS], the output (shaped like q), then m/l/acc scratch
    (acc [H, KVH*D] when head-batched), when head-batched over more than
    one KV head the block-diagonal q [H, KVH*D] — built once a row before
    the block loop, its diagonal read out of acc once a row at finalize:
    the layout exists in VMEM only —, the k and v slots [2, K*BS, KVH*D]
    and the DMA semaphores [2].  ``latent`` (``latent_decode_attention``):
    ONE pool and one slot pair — no v pool and no v slot among the refs; a
    block is copied once and its first ``latent`` lanes are the fold's
    values."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    K = var.blocks_per_step
    it = iter(refs)
    tbl_ref, live_ref, q_ref, k_hbm = (next(it) for _ in range(4))
    v_hbm = None if latent else next(it)
    ks_ref, vs_ref = (next(it), next(it)) if quant else (None, None)
    valid_ref, o_ref = next(it), next(it)
    m_scr, l_scr, a_scr = next(it), next(it), next(it)
    qd_scr = next(it) if var.head_batched and kvh > 1 else None
    kbuf = next(it)
    vbuf = None if latent else next(it)
    sem = next(it)
    pools = ((k_hbm, kbuf),) if latent else ((k_hbm, kbuf), (v_hbm, vbuf))

    i, rows = pl.program_id(0), pl.num_programs(0)
    first, base = live_ref[i, 0], live_ref[i, 2]
    n = live_ref[i, 1] - first + 1  # 0 for a row with no live program

    def copies(row, j, slot):
        return [
            pltpu.make_async_copy(
                pool.at[tbl_ref[row, j * K + m]],
                buf.at[slot, pl.ds(m * bs, bs)], sem.at[slot],
            )
            for m in range(K) for pool, buf in pools
        ]

    def is_live(row):
        return live_ref[row, 1] >= live_ref[row, 0]

    nxt = jnp.minimum(i + 1, rows - 1)

    # The row before started this row's first copies, if it had a trip.
    @pl.when((n > 0) & ((i == 0) | jnp.logical_not(is_live(jnp.maximum(i - 1, 0)))))
    def _own_first():
        for c in copies(i, first, base % 2):
            c.start()

    m_scr[...] = jnp.full_like(m_scr, -1e30)
    l_scr[...] = jnp.zeros_like(l_scr)
    a_scr[...] = jnp.zeros_like(a_scr)
    if qd_scr is not None:
        qd_scr[...] = block_diagonal_q(q_ref[0], kvh, n_rep)

    def trip(s, carry):
        j, slot = first + s, (base + s) % 2

        @pl.when(s + 1 < n)
        def _next_trip():
            for c in copies(i, j + 1, 1 - slot):
                c.start()

        @pl.when((s + 1 == n) & (i + 1 < rows) & is_live(nxt))
        def _next_row():
            for c in copies(nxt, live_ref[nxt, 0], 1 - slot):
                c.start()

        for c in copies(i, j, slot):
            c.wait()
        ks_blk = vs_blk = None
        if quant:
            keys = pl.ds(pl.multiple_of(j * (K * bs), K * bs), K * bs)
            ks_blk = ks_ref[0, keys, :].astype(jnp.float32)
            vs_blk = vs_ref[0, keys, :].astype(jnp.float32)
        valid = valid_ref[0, pl.ds(j, 1), :]  # [1, K*BS]
        k_blk = kbuf[slot]
        v_blk = k_blk[:, :latent] if latent else vbuf[slot]
        _fold_block(q_ref, k_blk, ks_blk, v_blk, vs_blk, valid,
                    m_scr, l_scr, a_scr, scale=scale, kvh=kvh, n_rep=n_rep,
                    d=d, var=var, q_diag=qd_scr)
        return carry

    jax.lax.fori_loop(0, n, trip, 0)
    acc = a_scr[...].astype(jnp.float32)
    if qd_scr is not None:
        acc = diagonal_out(acc, kvh, n_rep)
    l = l_scr[...].astype(jnp.float32)
    o_ref[0] = (acc / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)


def _row_spec(shape):
    """BlockSpec of one row's whole ``[1, ...]`` slice of a ``[B, ...]``
    array under the kernels' grid ``(B,)`` and two prefetch operands."""
    from jax.experimental import pallas as pl

    zeros = (0,) * (len(shape) - 1)
    return pl.BlockSpec((1, *shape[1:]), lambda i, tb, lv: (i, *zeros))


def _table_operands(table, key_valid, nb_pool: int, bs: int, K: int):
    """``(tbl, live, validb)`` of a kernel call: the table clamped for the
    copies' lookups, the rows' ``(first, last, base)`` live ranges (``base``
    = the trips of the rows before: the slot parity), and the rows' masks
    ``[B, T/K, K*BS]`` — trip j reads sublane j."""
    b, t = table.shape
    live = live_programs(table, key_valid, nb_pool, bs, K)
    trips = live[:, 1] - live[:, 0] + 1
    live = jnp.concatenate(
        [live, (jnp.cumsum(trips) - trips)[:, None]], axis=1
    )
    tbl = jnp.clip(table, 0, nb_pool - 1).astype(jnp.int32)
    return tbl, live, key_valid.astype(jnp.int32).reshape(b, t // K, K * bs)


def tp_shard_attention(
    fn, tp: int, q, kv_args: tuple, rep_args: tuple,
    scale_args: tuple = (), *, kvh: int,
):
    """Run a decode-attention kernel under ``shard_map`` over the
    serving TP mesh: each shard attends over its LOCAL heads (q axis 1,
    KV heads axis 2 — a slab's ``[B, S, KVH, D]`` heads axis, or a
    pool leaf's merged ``[NB, BS, KVH*D]`` axis, which splits on head
    boundaries because ``kvh % tp == 0``) — attention is embarrassingly
    parallel across heads, so the body carries no collective; the
    row-parallel all-reduce lands after the attn-out matmul, where
    XLA's sharding propagation puts it.  ``rep_args`` (tables, masks)
    replicate.

    The wrapper is only reachable at TP>1 — TP=1 call sites never
    build a mesh (the no-mesh pin in tests/test_tp_serving.py)."""
    from jax.sharding import AbstractMesh, PartitionSpec as P

    h = q.shape[1]
    if h % tp or kvh % tp:
        raise ValueError(
            f"TP={tp} must divide query heads ({h}) and KV heads ({kvh})"
        )

    def heads(a):  # axis 2 over 'tp', whatever trails it
        return P(None, None, "tp", *([None] * (a.ndim - 3)))

    args = (q,) + tuple(kv_args) + tuple(rep_args) + tuple(scale_args)
    in_specs = (
        [P(None, "tp", None)]
        + [heads(a) for a in kv_args]
        + [P(*([None] * a.ndim)) for a in rep_args]
        + [heads(a) for a in scale_args]
    )
    # The ABSTRACT mesh: axis names and sizes, no devices.  The traced
    # program is then the same for every TP group of a fleet — jit
    # shares one trace of a model fn across its wrappers, so a mesh of
    # concrete devices baked in by whichever group traced first would
    # pin every later group to that group's chips — and each group's
    # executable takes its devices from its own committed operands.
    mesh = AbstractMesh((1, tp), ("replica", "tp"))
    return jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=P(None, "tp", None), check_vma=False,
    )(*args)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "scale", "interpret", "variant", "tp"),
)
def paged_decode_attention(
    q: jax.Array,  # [B, H, D] — one query per row
    k_pool: jax.Array,  # [NB, BS, KVH*D] dense, or int8 payload
    v_pool: jax.Array,
    table: jax.Array,  # [B, T] block ids, unclamped: out of range = no block
    key_valid: jax.Array,  # [B, T*BS] 1 = attend
    block_size: int,
    k_scale: jax.Array | None = None,  # [NB, BS, KVH] -> int8 path
    v_scale: jax.Array | None = None,
    scale: float | None = None,
    interpret: bool = False,
    variant: str = "",
    tp: int = 1,
) -> jax.Array:
    """Fused paged decode attention; returns ``[B, H, D]``.

    Grid (B,): program b copies the live blocks of ``table[b]`` out of
    the pool into VMEM, K (``variant``'s ``blocks_per_step``) a trip,
    and accumulates FlashAttention-style — HBM traffic is exactly the
    row's live blocks (``live_programs``; the module docstring), never a
    materialized dense gather, never an entry past the row's last key.
    ``table`` comes UNCLAMPED (the sentinel marks what is no block).
    ``variant`` selects a tuning point (see :class:`Variant`); K must
    divide the table width T (``ops/autotune.py`` only enumerates
    divisors, so serving never needs a pad-block path).  VMEM per
    program is two slots of K lane-dense [BS, KVH*D] K+V block pairs +
    f32 accumulators + the row's mask — ``autotune.paged_vmem_bytes`` is
    the budget model.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    nb_pool, bs, gd = k_pool.shape
    kvh = gd // d
    if tp > 1:
        opt = () if k_scale is None else (k_scale, v_scale)

        def local(q_l, kp, vp, tbl, valid, *sc):
            ks, vs = sc if sc else (None, None)
            return paged_decode_attention(
                q_l, kp, vp, tbl, valid, block_size, ks, vs,
                scale=scale, interpret=interpret, variant=variant,
            )

        return tp_shard_attention(
            local, tp, q, (k_pool, v_pool), (table, key_valid), opt,
            kvh=kvh,
        )

    var = parse_variant(variant)
    K = var.blocks_per_step
    t = table.shape[1]
    n_rep = h // kvh
    if t % K != 0:
        raise ValueError(
            f"variant {var.key()!r}: blocks_per_step={K} does not divide "
            f"table width {t}"
        )
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    quant = k_scale is not None
    acc_jnp = jnp.float32 if var.acc_dtype == "f32" else jnp.bfloat16
    tbl, live, validb = _table_operands(table, key_valid, nb_pool, bs, K)
    # head-batched: q crosses as it lies (the kernel lays it out in VMEM)
    qk = q if var.head_batched else q.reshape(b, kvh, n_rep, d)
    acc_block = (h, gd) if var.head_batched else qk.shape[1:]

    row_spec = _row_spec
    q_spec = row_spec(qk.shape)
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)  # read where it lies
    kernel = functools.partial(
        _paged_kernel_v, scale=scale, kvh=kvh, n_rep=n_rep, d=d,
        quant=quant, var=var, bs=bs,
    )
    args = [tbl, live, qk, k_pool, v_pool]
    in_specs = [q_spec, pool_spec, pool_spec]
    if quant:
        # Mosaic copies no [BS, KVH] slab out of a pool whose minor dim
        # is under a lane tile: the rows' scales (1/D of the payload's
        # bytes) are gathered here and ride a row at a time.
        scales = [gather_pages(sc, tbl, bs) for sc in (k_scale, v_scale)]
        args += scales
        in_specs += [row_spec(sc.shape) for sc in scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[*in_specs, row_spec(validb.shape)],
        out_specs=q_spec,
        scratch_shapes=[
            *softmax_scratch(acc_block, acc_jnp),
            *([pltpu.VMEM((h, gd), q.dtype)]
              if var.head_batched and kvh > 1 else []),
            pltpu.VMEM((2, K * bs, gd), k_pool.dtype),
            pltpu.VMEM((2, K * bs, gd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qk.shape, q.dtype),
        interpret=interpret,
    )(*args, validb)
    return out.reshape(b, h, d)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "v_dim", "scale", "interpret", "variant"),
)
def latent_decode_attention(
    q: jax.Array,  # [B, H, C] — one query per row, as it lies
    pool: jax.Array,  # [NB, BS, C]: one latent row a token
    table: jax.Array,  # [B, T] block ids, unclamped: out of range = no block
    key_valid: jax.Array,  # [B, T*BS] 1 = attend
    block_size: int,
    v_dim: int,
    scale: float,
    interpret: bool = False,
    variant: str = "",
) -> jax.Array:
    """Paged decode attention over a LATENT pool (multi-head latent
    attention, absorbed form); returns ``[B, H, v_dim]``.

    The cache holds one row a token, shared by every head: ``C`` lanes
    (DeepSeek-V2: ``[c_kv (512) ; k_rope (64)]`` = 576) that are the KEY
    of all ``H`` query rows, and whose first ``v_dim`` lanes are their
    VALUE.  So there is one pool operand and one KV head: ``q`` is
    ``[H, C]`` as it lies (``n_rep`` = H: nothing to make block-diagonal),
    the program copies each live block ONCE into one slot pair and folds
    it as keys (all ``C`` lanes: an ``[H, C] x [K*BS, C]`` score issue)
    and as values (the tile's first ``v_dim`` lanes: ``[H, K*BS] x
    [K*BS, v_dim]``) — ``paged_decode_attention`` called with the latent
    as K and again as V would read every cached byte twice.  The row loop,
    the live range (``live_programs``), the slot parity across rows, the
    masked online softmax in f32 and the zeros of a row with no live key
    are ``_paged_kernel_v``'s, unchanged.  ``scale`` has no default: the
    score width is not the softmax scale's (DeepSeek-V2: 192^-1/2 x
    YaRN's mscale^2).  ``variant``: ``b<K>[-nat]``; K divides T.  VMEM:
    ``autotune.latent_vmem_bytes``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, c = q.shape
    nb_pool, bs, _ = pool.shape
    var = dataclasses.replace(parse_variant(variant), head_batched=True)
    K = var.blocks_per_step
    t = table.shape[1]
    if t % K != 0:
        raise ValueError(
            f"variant {var.key()!r}: blocks_per_step={K} does not divide "
            f"table width {t}"
        )
    tbl, live, validb = _table_operands(table, key_valid, nb_pool, bs, K)
    row_spec = _row_spec
    acc = jnp.float32 if var.acc_dtype == "f32" else jnp.bfloat16
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[row_spec(q.shape), pl.BlockSpec(memory_space=pl.ANY),
                  row_spec(validb.shape)],
        out_specs=row_spec((b, h, v_dim)),
        scratch_shapes=[
            *softmax_scratch((h, v_dim), acc),
            pltpu.VMEM((2, K * bs, c), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _paged_kernel_v, scale=scale, kvh=1, n_rep=h, d=c, quant=False,
            var=var, bs=bs, latent=v_dim,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, v_dim), q.dtype),
        interpret=interpret,
        name="latent_decode_attention",
    )(tbl, live, q, pool, validb)


def paged_attention_ref(
    q: jax.Array, k_pool: jax.Array, v_pool: jax.Array | None,
    table: jax.Array, key_valid: jax.Array, block_size: int,
    k_scale: jax.Array | None = None, v_scale: jax.Array | None = None,
    scale: float | None = None, v_dim: int = 0,
) -> jax.Array:
    """jnp reference for the kernel: gather the dense view, dequantize,
    and run masked softmax attention in f32.  Also the XLA serving
    fallback shape the models reproduce inline.  With ``v_dim`` (and no
    ``v_pool``) the reference of ``latent_decode_attention``: one pool,
    one KV head, values = the first ``v_dim`` lanes of the keys; returns
    ``[B, H, v_dim]``."""
    b, h, d = q.shape
    kvh = k_pool.shape[2] // d
    n_rep = h // kvh
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    def dense(pool, tail):
        return gather_pages(pool, table, block_size, tail).astype(jnp.float32)

    kd = dense(k_pool, (kvh, d))
    vd = kd[..., :v_dim] if v_dim else dense(v_pool, (kvh, d))
    if k_scale is not None:
        kd = kd * dense(k_scale, (kvh, 1))
        vd = vd * dense(v_scale, (kvh, 1))
    qg = q.reshape(b, kvh, n_rep, d).astype(jnp.float32)
    s = jnp.einsum("bgrd,btgd->bgrt", qg, kd) * scale
    s = jnp.where(key_valid[:, None, None, :] != 0, s, jnp.float32(-1e30))
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrt,btgd->bgrd", p, vd)
    return o.reshape(b, h, vd.shape[-1]).astype(q.dtype)


def pool_relayouts(hlo_text: str, pool_elems, in_loop_only: bool = False) -> list:
    """The instructions of a COMPILED program's text (``.compile()
    .as_text()``) that move a whole pool: every ``reshape``, ``copy`` or
    ``transpose`` with a result of a pool leaf's element count
    (``pool_elems``: the counts to look for).  On the chip a reshape
    that survives to the optimised HLO is a relayout (the free ones
    become ``bitcast``).  With the state donated (engine/streams.py) no
    payload pool may show at all, ENTRY included; ``in_loop_only`` skips
    the ENTRY computation for the one thing left there — an int8 pair's
    small scale pool, which the compiler re-tiles once on the way in and
    out of a program.  What ``tests/test_chip_compile.py`` and
    ``chip_smoke.py`` hold the layout and the donation rule to."""
    import re

    want = {int(n) for n in pool_elems}
    inst = re.compile(r"=\s+(.*?)\s([a-z][a-z0-9\-]*)\(")
    hits, in_entry = [], False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY "):
            in_entry = True
        elif line.startswith("}"):
            in_entry = False
        m = inst.search(line)
        if not m or m.group(2) not in ("reshape", "copy", "transpose"):
            continue
        if in_entry and in_loop_only:
            continue
        for dims in re.findall(r"\[([\d,]+)\]", m.group(1)):
            if math.prod(int(x) for x in dims.split(",")) in want:
                hits.append(line.strip()[:200])
                break
    return hits
