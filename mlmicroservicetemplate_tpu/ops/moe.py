"""Sparse expert FFN: top-k routing, one grouped matmul over the
assignments sorted by expert, weighted combine back into token order.

One implementation for every step kind (a 64-row decode step has 512
assignments, a 64 x 512 prefill rung 262 144): compute is the chosen
experts' only, never a dense pass over all experts under a mask.

    p = softmax_f32(h W_r)  |  sigmoid_f32(h W_r)    router scores, over ALL
                                               experts (``score``)
    e_1..e_k = top_k(p + b)                    b: a per-expert bias, in the
                                               SELECTION only (``router_bias``)
                                               under a group limit (``n_group``
                                               groups of consecutive experts, a
                                               group's score the max of its
                                               experts'): among the experts of
                                               the ``topk_group`` best groups
    w_j = route_scale * p[e_j]                 as they are, or renormalised
                                               over the chosen (norm_topk)
    out = sum_j w_j * down_{e_j}(silu(gate_{e_j} h) * up_{e_j} h)
          + down_s(silu(gate_s h) * up_s h)    a shared expert, every token

The block's shape is data.  ``limit`` > 0: every SwiGLU is clamped,
``silu(min(gate, limit)) * clip(up, -limit, limit)``.  ``act`` "relu2": an expert is the non-gated
``down(relu(up v)^2)`` and the tree holds no ``gate`` stack, the shared
expert likewise.  ``latent_down`` / ``latent_up`` in the tree (LatentMoE):
the routed experts live in a narrower latent between one down- and one
up-projection the layer shares — ``v = h W_down`` BEFORE the row gather,
so latent-wide rows are gathered and sorted, ``W_up`` after the weighted
combine; the router and the shared expert read the full-width ``h``.

Rows that are padding or finished (``valid`` false) are sorted past the
last group: the grouped matmul gives them no expert, their (undefined)
output rows are zeroed before the combine, and they are not counted.

**A chip's share of the experts.**  The router is as wide as the
PUBLISHED expert count and the selection runs over all of them; the
stacks ``gate`` / ``up`` / ``down`` may hold fewer (``[held, ...]``:
experts ``expert_first .. expert_first + held - 1``, one chip of an
expert-parallel deployment).  An assignment to an expert not held takes
the same seam as an invalid row — past the last group, zeroed, adding
nothing — but IS counted: ``counts`` stays ``[E]`` over the published
experts, so held and absent assignments (what the exchange would carry)
are both known.  Nothing stands in for the absent chips.

**Row work by the rows held** (``row_rungs``).  The sort puts the held
assignments first, so what XLA does around the grouped matmuls — the
gather into expert order, the activation, the mask and the combine —
needs only as many rows as this chip holds (the kernel already runs the
row tiles its groups reach and no other).  That count is data, so the
call carries a short ladder of STATIC row counts (``LADDER``: one below
the whole call, as the cells run it) and each of the three
pieces branches on the device (``lax.switch`` on ``sum(sizes)``) to the
lowest rung that holds them all; the last rung is the whole ``t * k``, so
no assignment is ever dropped or clipped: a router that sends everything
here runs the top rung, which is the one-rung program.  Where the tree
holds every expert, or the call is a few row tiles (a decode step), the
ladder has ONE rung and the traced program holds no conditional.

**The two row shuffles as DMA kernels** (``row_kernels_fit``).  Where the
call's static shape says so — thousands of rows of 8 KB and more: a
prompt dispatch, never a decode step — the gather into expert order and
the weighted combine are Mosaic kernels that move rows by DMA over the
LIVE rows alone, driven by the sort's index arrays and the held count as
data (``sorted_rows``, ``combine_rows``): no ``[t * k, D]`` array is
padded or copied, no float32 ``[k, t, D]`` gather is ever in HBM, and
neither is a branch of the ladder, which then runs the activation alone.
Every other call keeps XLA's form, which is also the tests' reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


#: Row tile of ``grouped_matmul``: the MXU's height.  The kernel visits
#: ``M / tile + groups - 1`` row tiles and multiplies every one of them in
#: full, so a smaller tile pads less; with K whole a group's weights stay
#: put over its visits and a larger tile saves nothing (PERF.md section 6,
#: PR 45: 128 beat 512 at every shape a cell runs, a 64 x 128 wave's 1024
#: rows a group included).
ROW_TILE = 128

#: What the kernel's blocks may take of the 16 MiB of scoped VMEM a Pallas
#: call gets on a v5e, by ``tile_bytes``'s count.  The widest blocks the
#: cells' shapes take count 13.6 MiB and compile; 16.0 MiB by this count is
#: refused (Mosaic adds ~0.6 MiB of its own; compiled for a described v5e,
#: ``tests/test_chip_compile.py``).
VMEM_BUDGET = 14 * 2**20


def tile_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """VMEM the grouped matmul's blocks hold at a tiling: weight, row and
    out blocks twice (the pipeline's two slots) and the float32
    accumulator."""
    return 2 * itemsize * (tk * tn + tm * tk + tm * tn) + 4 * tm * tn


def _tile_sizes(size: int) -> list[int]:
    """The tiles an axis may take, widest first: the axis whole, then its
    divisors that are multiples of 128 (a lane tile)."""
    return [size] + [t for t in range(size - size % 128, 0, -128)
                     if t < size and size % t == 0]


def matmul_tiles(k: int, n: int, itemsize: int) -> tuple[int, int, int]:
    """``(tm, tk, tn)`` of ``grouped_matmul`` for ``[M, K] x [G, K, N]``:
    K WHOLE and the widest N tile whose blocks fit ``VMEM_BUDGET``; a K so
    wide that no N tile fits beside it is tiled as well, widest first."""
    for tk in _tile_sizes(k):
        for tn in _tile_sizes(n):
            if tile_bytes(ROW_TILE, tk, tn, itemsize) <= VMEM_BUDGET:
                return ROW_TILE, tk, tn
    raise ValueError(f"no tiling of a [{k}, {n}] expert fits {VMEM_BUDGET} B")


#: Rungs of ``row_rungs`` below the whole call, as (numerator, denominator)
#: multiples of the rows an even router sends this chip's share of the
#: experts (``n * held / published``): ONE, a quarter more than that share
#: (the cells' routers send 0.98-1.04 of it), then every row.  A rung is
#: three small XLA branches an expert layer of an executable — the grouped
#: matmuls and the sorts stay outside them — and still ~1.6 MiB of it,
#: mostly its two gathers: a second rung at twice the share cost as much
#: again in every boot for rows no cell's router sends (PERF.md section 6,
#: PR 52).
LADDER = ((5, 4),)

#: Fewest rows a rung has to leave out to be a rung: below it (a decode
#: step's 192-704 rows, a small wave, a lone 1024-token window of eight
#: experts a token) the block is bound by the experts' bytes, not by its
#: rows, a rung's branches (~1.6 MiB of executable an expert layer, mostly
#: its two gathers) buy little, and the ladder has one rung.
LADDER_MIN_SKIP = 64 * ROW_TILE


def row_rungs(n: int, n_exp: int, n_pub: int) -> tuple[int, ...]:
    """The static row counts ``expert_ffn`` may run its row work over, for
    ``n = t * k`` assignments and a tree that holds ``n_exp`` of ``n_pub``
    published experts: rising, each below the last a multiple of
    ``ROW_TILE``, the last always ``n`` itself.  ``(n,)`` — no branch —
    where every expert is held or no rung would leave out
    ``LADDER_MIN_SKIP`` rows."""
    rungs: list[int] = []
    if n_exp != n_pub:
        for num, den in LADDER:
            r = -(-n * n_exp * num // (n_pub * den * ROW_TILE)) * ROW_TILE
            if n - r >= LADDER_MIN_SKIP and (not rungs or r > rungs[-1]):
                rungs.append(r)
    return (*rungs, n)


#: Fewest assignment rows, and fewest bytes a row, of a call whose two row
#: shuffles take the DMA kernels (``sorted_rows``, ``combine_rows``); below
#: either XLA's gather and combine stay (docs/kernel_tuning.md has the
#: chip's table, kernel against XLA a layer at each cell's shapes: rows of
#: 4 KB and 2 KB lose; DeepSeek-V2's 12 288-row window wins 3 %, less than
#: the second a boot spends lowering the kernels into one more executable).
ROW_KERNELS_MIN_ROWS = 128 * ROW_TILE
ROW_KERNELS_MIN_ROW_BYTES = 8192


def rows_fit_kernels(d: int, dtype) -> bool:
    """The width's part of ``row_kernels_fit``: rows of whole 32-bit lanes
    in bfloat16 or float32 (what the kernels' slabs pack), at least
    ``ROW_KERNELS_MIN_ROW_BYTES`` each."""
    dtype = jnp.dtype(dtype)
    row_bytes = d * dtype.itemsize
    return (dtype in (jnp.bfloat16, jnp.float32)
            and row_bytes % (4 * LANES) == 0
            and row_bytes >= ROW_KERNELS_MIN_ROW_BYTES)


def row_kernels_fit(n: int, d: int, dtype) -> bool:
    """Whether a call of ``n = t * k`` assignment rows ``d`` wide takes the
    two DMA kernels — a function of the call's static shape alone, read by
    the traced program (``expert_ffn``) and by the host's counter
    (``engine/streams._note_moe_rows``) alike: at least
    ``ROW_KERNELS_MIN_ROWS`` rows in whole row tiles (a decode step's few
    hundred rows are bound by the experts' bytes and keep XLA's form), of
    a width ``rows_fit_kernels``."""
    return (n >= ROW_KERNELS_MIN_ROWS and n % ROW_TILE == 0
            and rows_fit_kernels(d, dtype))


def rung_index(held, rungs: tuple[int, ...]):
    """Index of the lowest rung of ``rungs`` that holds ``held`` rows
    (``held`` at most the last rung): the one rule for the device's branch
    (``held`` a traced scalar) and the host's count of the rows that ran
    (an int or a numpy array of counts; the index has ``held``'s shape)."""
    return sum(((held > r) * 1 for r in rungs[:-1]), held * 0)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   interpret: bool = False) -> jax.Array:
    """``lhs`` [M, K] rows sorted by group, ``rhs`` [G, K, N], ``group_sizes``
    [G] int32 -> [M, N] in lhs's dtype: row i of group g is multiplied by
    ``rhs[g]`` (float32 accumulation).  Rows past ``sum(group_sizes)``
    belong to no group and come back UNDEFINED: the caller masks them.

    The Pallas grouped matmul ``megablox.gmm``, kept over
    ``jax.lax.ragged_dot`` by a chip measurement at the two shapes the
    benchmark's expert cell runs (PERF.md section 6, PR 27: 1.21 against
    2.61 ms for a decode step's 512 assignments, 7.48 against 9.64 ms for
    a 64 x 128 wave's 65 536; d 2048, width 1024, 64 experts, one layer's
    three matmuls).

    **Tiles** (``matmul_tiles``; PERF.md section 6, PR 45).  The kernel's
    grid is (N tiles, row-tile visits, K tiles), K innermost, and its
    pipeline fetches a weight block again whenever the block's index
    changes between two steps.  With ONE K tile the consecutive row tiles
    of a group keep the index ``(group, 0, n)`` and an expert's ``[K, tn]``
    slab crosses HBM once a group; with two or more the index changes
    every step and the slab is fetched again for every row tile the
    group's rows touch — a prompt dispatch's 77-192 rows an expert against
    a 128-row tile read each expert twice or more.  So K is never tiled
    while a block of it fits: the N tile shrinks instead (N is the
    outermost axis: a narrower tile re-reads the rows, megabytes against
    the experts' gigabytes).  On the chip, one layer's matmuls at the
    shapes the cells run, the tiles of before (K and N by 1024, rows by
    512 from 512 rows a group) against these: a three-window prompt
    dispatch 5.62 -> 4.32 ms (Trinity), 5.19 -> 3.66 (DeepSeek-V2's share),
    4.97 -> 3.17 (Nemotron's); a decode step level or a few percent
    better; the kernel wants M a multiple of the row tile."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = lhs.shape
    tm, tk, tn = matmul_tiles(k, rhs.shape[2], lhs.dtype.itemsize)
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = gmm(
        lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
        tiling=(tm, tk, tn), interpret=interpret,
    )
    return out[:m] if pad else out


# ---------------------------------------------------------------------------
# the row shuffles as DMA kernels
#
# Mosaic slices an array in HBM only at whole memory tiles — 8 rows of 128
# 32-bit words, 16 rows of a 16-bit dtype packed two to a word — so ONE row
# of a ``[rows, d]`` array is out of a DMA's reach.  The kernels below move
# rows as SLABS: a row's 32-bit words (a float32 each, or two bfloat16:
# column ``c`` in the low half beside column ``d / 2 + c`` in the high) as
# ``[row_slabs, LANES]`` whole tiles of a ``[rows * row_slabs, LANES]``
# uint32 array, contiguous in HBM and addressable row by row.  What the
# grouped matmuls read and write stays ``[rows, d]``; the change of layout
# happens in VMEM, a row tile at a time, by strided loads and stores
# (``_relayout``: shifts and masks, no shuffle).

#: Lanes of a vector register, and the 32-bit sublanes of a memory tile.
LANES, SUBLANES = 128, 8


def row_slabs(d: int, itemsize: int) -> int:
    """Sublanes of the slab a ``d``-wide row makes: its 32-bit words by
    ``LANES``, up to whole tiles (``SUBLANES``)."""
    return -(-d * itemsize // (4 * LANES * SUBLANES)) * SUBLANES


def as_slabs(a: jax.Array) -> jax.Array:
    """``a`` [n, d] (float32 or bfloat16) -> its rows as slabs,
    ``[n * row_slabs, LANES]`` uint32 (zeros behind a row's last word
    where ``d`` makes no whole tiles)."""
    n, d = a.shape
    if a.dtype.itemsize == 2:
        bits = jax.lax.bitcast_convert_type(a, jnp.uint16).astype(jnp.uint32)
        u = bits[:, :d // 2] | (bits[:, d // 2:] << 16)
    else:
        u = jax.lax.bitcast_convert_type(a, jnp.uint32)
    s = row_slabs(d, a.dtype.itemsize)
    if s * LANES != u.shape[1]:
        u = jnp.pad(u, ((0, 0), (0, s * LANES - u.shape[1])))
    return u.reshape(n * s, LANES)


def from_slabs(u: jax.Array, d: int, dtype) -> jax.Array:
    """``as_slabs`` back: ``u`` [n * row_slabs, LANES] -> [n, d]."""
    itemsize = jnp.dtype(dtype).itemsize
    s = row_slabs(d, itemsize)
    u = u.reshape(-1, s * LANES)[:, :d * itemsize // 4]
    if itemsize == 4:
        return jax.lax.bitcast_convert_type(u, dtype)
    halves = [(u & 0xffff), u >> 16]
    return jnp.concatenate([jax.lax.bitcast_convert_type(
        x.astype(jnp.uint16), dtype) for x in halves], axis=1)


_HIGH = 0xffff0000


def _halves(x):
    """A slab word's two bfloat16 as exact float32: (low, high)."""
    from jax.experimental.pallas import tpu as pltpu

    return (pltpu.bitcast(x << 16, jnp.float32),
            pltpu.bitcast(x & jnp.uint32(_HIGH), jnp.float32))


def _relayout(tile_ref, slabs, s: int, into_tile: bool) -> None:
    """A row tile between its two layouts in VMEM: ``tile_ref`` ``[tile,
    d]`` as the grouped matmuls have it, ``slabs`` ``[tile * s, LANES]``
    uint32, its rows as slabs of ``s`` sublanes — ``into_tile`` says which
    way (a slab's sublanes past its row's last word are never touched).
    Eight rows a vector register at a sublane stride of ``s``; a packed
    dtype pairs two registers and swaps halves between them — words of
    rows (2r, 2r + 1) at one column against words of columns (c, d/2 + c)
    of one row: the same exchange both ways.  The row groups are a loop on
    the device: unrolled in Python the body is thousands of operations,
    seconds of tracing and lowering an executable."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile, d = tile_ref.shape
    dt, pack = tile_ref.dtype, 4 // tile_ref.dtype.itemsize  # rows a word
    rows, chunks = SUBLANES * pack, d // (LANES * pack)

    def group(q, carry):
        r0 = pl.multiple_of(q * rows, rows)
        for c in range(chunks):
            cols = [(pl.ds(r0, rows), pl.ds((c + h * chunks) * LANES, LANES))
                    for h in range(pack)]
            at = [(pl.ds((r0 + h) * s + c, SUBLANES, stride=pack * s), slice(None))
                  for h in range(pack)]
            src, dst = (slabs, tile_ref) if into_tile else (tile_ref, slabs)
            words = [pltpu.bitcast(src[i], jnp.uint32)
                     for i in (at if into_tile else cols)]
            if pack == 2:
                a, b = words
                words = [(a & 0xffff) | (b << 16),
                         (a >> 16) | (b & jnp.uint32(_HIGH))]
            for i, word in zip(cols if into_tile else at, words):
                dst[i] = pltpu.bitcast(word, dst.dtype)
        return carry
    jax.lax.fori_loop(0, tile // rows, group, 0)


def _last_live(i, live_ref, tile: int):
    """Block index of row tile ``i``, a tile at or past the live count that
    of the last live tile: the pipeline neither fetches nor writes a block
    whose index stays."""
    return jnp.minimum(i, jnp.maximum((live_ref[0] + tile - 1) // tile - 1, 0))


def _sorted_rows_kernel(src_ref, live_ref, slabs_hbm, o_ref, buf, sem, *,
                        s: int):
    """Program i: sorted assignments ``[i * tile, (i + 1) * tile)`` — slab
    ``src[a]`` of ``slabs_hbm`` into ``buf[slot]`` for each, all of a
    tile's copies in flight at once and the NEXT tile's started before
    this tile's are waited for, then the slabs into the ``[tile, d]``
    output block.  A tile at or past the live count copies nothing and
    writes nothing."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i, n = pl.program_id(0), pl.num_programs(0)
    tile = o_ref.shape[0]

    def is_live(j):
        return j * tile < live_ref[0]

    def slab(ref, at):
        return ref.at[pl.ds(pl.multiple_of(at * s, SUBLANES), s)]

    def start(j, slot):
        def eight(r8, carry):
            for r in range(8):
                row = r8 * 8 + r
                pltpu.make_async_copy(
                    slab(slabs_hbm, src_ref[j * tile + row]),
                    slab(buf.at[slot], row), sem.at[slot]).start()
            return carry
        jax.lax.fori_loop(0, tile // 8, eight, 0)

    @pl.when((i == 0) & is_live(i))
    def _first():
        start(i, 0)

    @pl.when((i + 1 < n) & is_live(i + 1))
    def _next():
        start(i + 1, (i + 1) % 2)

    @pl.when(is_live(i))
    def _tile():
        slot = i % 2

        def wait(r, carry):  # every copy moves one slab: any slab's wait
            pltpu.make_async_copy(
                slab(slabs_hbm, 0), slab(buf.at[slot], 0), sem.at[slot]).wait()
            return carry
        jax.lax.fori_loop(0, tile, wait, 0)
        _relayout(o_ref, buf.at[slot], s, into_tile=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sorted_rows(rows: jax.Array, src: jax.Array, n_live: jax.Array,
                interpret: bool = False) -> jax.Array:
    """``rows`` [T, D], ``src`` [M] int32 (M a multiple of ``ROW_TILE``),
    ``n_live`` a scalar -> ``xs`` [M, D] with ``xs[a] = rows[src[a]]`` for
    every ``a`` below ``n_live`` rounded up to a row tile; the rows behind
    are left as the allocation had them (UNDEFINED: the grouped matmul
    gives them no group).  Mosaic kernel ``moe_sorted_rows``: one DMA a
    row, driven by the index array, over the live row tiles alone."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, d = src.shape[0], rows.shape[1]
    s = row_slabs(d, rows.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_sorted_rows_kernel, s=s),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(m // ROW_TILE,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(
                (ROW_TILE, d),
                lambda i, src, live: (_last_live(i, live, ROW_TILE), 0)),
            scratch_shapes=[
                pltpu.VMEM((2, ROW_TILE * s, LANES), jnp.uint32),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((m, d), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="moe_sorted_rows",
    )(src.astype(jnp.int32), n_live.astype(jnp.int32).reshape(1),
      as_slabs(rows))


def _row_slabs_kernel(live_ref, x_ref, o_ref, *, s: int):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) * x_ref.shape[0] < live_ref[0])
    def _tile():
        _relayout(x_ref, o_ref, s, into_tile=False)


def _live_slabs(ys: jax.Array, n_live: jax.Array, interpret: bool):
    """``as_slabs(ys)`` for the row tiles below ``n_live`` (Mosaic kernel
    ``moe_row_slabs``: a dead tile is neither read nor written)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, d = ys.shape
    s = row_slabs(d, ys.dtype.itemsize)

    def block(i, live):
        return (_last_live(i, live, ROW_TILE), 0)

    return pl.pallas_call(
        functools.partial(_row_slabs_kernel, s=s),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(m // ROW_TILE,),
            in_specs=[pl.BlockSpec((ROW_TILE, d), block)],
            out_specs=pl.BlockSpec((ROW_TILE * s, LANES), block)),
        out_shape=jax.ShapeDtypeStruct((m * s, LANES), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="moe_row_slabs",
    )(n_live.astype(jnp.int32).reshape(1), ys)


#: What the combine kernel's two slots of row slabs may take of VMEM.
COMBINE_VMEM = 8 * 2**20


def combine_tile(k: int, slab_bytes: int) -> int:
    """Tokens a program of ``moe_combine_rows`` sums: the most (a power of
    two, at most 64) whose ``k`` slabs each, twice, fit ``COMBINE_VMEM``."""
    tt = 64
    while tt > 1 and 2 * tt * k * slab_bytes > COMBINE_VMEM:
        tt //= 2
    return tt


def _combine_rows_kernel(pos_ref, live_ref, held_ref, w_ref, ys_hbm, o_ref,
                         buf, sem, *, k: int, s: int, packed: bool):
    """Program i: tokens ``[i * tt, (i + 1) * tt)``.  Slab ``pos[t, j]`` of
    ``ys_hbm`` into ``buf[slot, j, t]`` for every assignment below the live
    count — the NEXT tile's started before this tile's ``held[i]`` are
    waited for, so the copies run under the sums — then, a token at a
    time, ``sum_j w[t, j] * slab_j`` in float32 in slot order (an
    assignment at or past the live count a zero slab by a select: its
    place in ``buf`` holds whatever was there), rounded once into the
    output's slab."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i, n = pl.program_id(0), pl.num_programs(0)
    tt = o_ref.shape[0]

    def start(tile, slot):
        def token(t, carry):
            for j in range(k):
                p = pos_ref[(tile * tt + t) * k + j]

                @pl.when(p < live_ref[0])
                def _():
                    pltpu.make_async_copy(
                        ys_hbm.at[pl.ds(pl.multiple_of(p * s, SUBLANES), s)],
                        buf.at[slot, j, t], sem.at[slot]).start()
            return carry
        jax.lax.fori_loop(0, tt, token, 0)

    @pl.when(i == 0)
    def _first():
        start(i, 0)

    @pl.when(i + 1 < n)
    def _next():
        start(i + 1, (i + 1) % 2)

    slot = i % 2

    def wait(r, carry):  # every copy moves one slab: any slab's wait
        pltpu.make_async_copy(
            ys_hbm.at[pl.ds(0, s)], buf.at[slot, 0, 0], sem.at[slot]).wait()
        return carry
    jax.lax.fori_loop(0, held_ref[i], wait, 0)

    def token(t, carry):
        acc = None
        for j in range(k):
            a = (i * tt + t) * k + j
            x = buf[slot, j, t]
            parts = _halves(x) if packed else (pltpu.bitcast(x, jnp.float32),)
            terms = [jnp.where(pos_ref[a] < live_ref[0], v, 0.0) * w_ref[a]
                     for v in parts]
            acc = terms if acc is None else [p + q for p, q in zip(acc, terms)]
        if packed:  # each sum rounded to bfloat16: its high 16 bits
            lo, hi = (pltpu.bitcast(
                v.astype(jnp.bfloat16).astype(jnp.float32), jnp.uint32)
                for v in acc)
            o_ref[t] = (lo >> 16) | (hi & jnp.uint32(_HIGH))
        else:
            o_ref[t] = pltpu.bitcast(acc[0], jnp.uint32)
        return carry
    jax.lax.fori_loop(0, tt, token, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def combine_rows(ys: jax.Array, pos: jax.Array, w: jax.Array,
                 n_live: jax.Array, interpret: bool = False) -> jax.Array:
    """``ys`` [M, D] rows in expert order, ``pos`` [T, k] int32 where each
    token's assignments lie among them, ``w`` [T, k] float32, ``n_live`` a
    scalar -> [T, D] in ys's dtype: ``sum_j w[t, j] * ys[pos[t, j]]`` over
    the assignments with ``pos < n_live`` (the others add exactly zero,
    whatever their rows hold), product and sum in float32 in slot order,
    rounded once.  Two Mosaic kernels: ``moe_row_slabs`` (the live row
    tiles of ``ys`` as slabs) and ``moe_combine_rows`` (one DMA a live
    assignment into VMEM, never a ``[k, T, D]`` array in HBM)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, k = pos.shape
    d = ys.shape[1]
    s = row_slabs(d, ys.dtype.itemsize)
    tt = combine_tile(k, s * LANES * 4)
    pad = -t % tt
    if pad:  # tokens behind the call's: every assignment dead
        pos = jnp.pad(pos, ((0, pad), (0, 0)), constant_values=ys.shape[0])
        w = jnp.pad(w, ((0, pad), (0, 0)))
    n_live = n_live.astype(jnp.int32)
    pos = pos.astype(jnp.int32)
    held = jnp.sum((pos < n_live).reshape(-1, tt * k), axis=1, dtype=jnp.int32)
    out = pl.pallas_call(
        functools.partial(_combine_rows_kernel, k=k, s=s,
                          packed=ys.dtype.itemsize == 2),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=((t + pad) // tt,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tt, s, LANES), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, k, tt, s, LANES), jnp.uint32),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((t + pad, s, LANES), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="moe_combine_rows",
    )(pos.reshape(-1), n_live.reshape(1), held,
      w.astype(jnp.float32).reshape(-1), _live_slabs(ys, n_live, interpret))
    return from_slabs(out.reshape(-1, LANES), d, ys.dtype)[:t]


def _relu2(x: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(x))


def group_limited(sel: jax.Array, n_group: int, topk_group: int) -> jax.Array:
    """``sel`` [T, E] selection scores with every expert outside a
    token's ``topk_group`` best groups set to 0 (``group_limited_greedy``:
    ``n_group`` groups of E / n_group CONSECUTIVE experts, a group's score
    the max of its experts'; a masked score is 0, never -inf, as the
    model code has it, so a tie at 0 can only choose a masked expert when
    fewer than k scores in the kept groups are positive)."""
    t, e = sel.shape
    g = jnp.max(sel.reshape(t, n_group, e // n_group), axis=-1)  # [T, G]
    _, gi = jax.lax.top_k(g, topk_group)
    keep = jnp.any(gi[:, :, None] == jnp.arange(n_group)[None, None, :], axis=1)
    return jnp.where(jnp.repeat(keep, e // n_group, axis=1), sel, 0.0)


def expert_ffn(h: jax.Array, mlp, k: int, norm_topk: bool, valid: jax.Array,
               interpret: bool = False, score: str = "softmax",
               route_scale: float = 1.0, n_group: int = 0,
               topk_group: int = 0, expert_first: int = 0,
               act: str = "silu", limit: float = 0.0):
    """h [T, D] normed tokens, ``mlp`` the layer's expert leaves (router
    [D, E]; gate, up [held, D, W]; down [held, W, D], held = E unless the
    tree holds a chip's share; where the model has them ``router_bias``
    [E], ``shared`` (a dense expert's gate/up/down) and the latent pair
    ``latent_down`` [D, Dl] / ``latent_up`` [Dl, D], the stacks then Dl
    wide where they were D; ``act`` "relu2": no ``gate``), valid [T] bool
    -> (out [T, D] in h's dtype, counts [E] int32 of valid assignments
    over the PUBLISHED experts).  ``interpret`` runs the kernel in
    interpret mode (CPU tests).  ``limit`` > 0 clamps every SwiGLU, routed
    and shared: ``silu(min(gate, limit)) * clip(up, -limit, limit)``."""
    t, d = h.shape

    def gate_act(gate):  # silu of a SwiGLU's gate, and its other factor
        return jax.nn.silu(jnp.minimum(gate, limit) if limit else gate)

    def clip(up_):
        return jnp.clip(up_, -limit, limit) if limit else up_

    up, down = mlp["up"]["kernel"], mlp["down"]["kernel"]
    n_exp = up.shape[0]  # held
    n_pub = mlp["router"]["kernel"].shape[1]
    rows = h
    if "latent_down" in mlp:
        with jax.named_scope("moe_latent_down"):
            rows = h @ mlp["latent_down"]["kernel"].astype(h.dtype)
    with jax.named_scope("moe_route"):
        logits = h.astype(jnp.float32) @ mlp["router"]["kernel"].astype(jnp.float32)
        p = jax.nn.softmax(logits, axis=-1) if score == "softmax" else (
            jax.nn.sigmoid(logits))
        sel = p + mlp["router_bias"].astype(jnp.float32) if (
            "router_bias" in mlp) else p
        if n_group > 1:
            sel = group_limited(sel, n_group, topk_group)
        if sel is p:
            w, e = jax.lax.top_k(p, k)  # [T, k]
        else:
            _, e = jax.lax.top_k(sel, k)
            w = jnp.take_along_axis(p, e, axis=-1)
        if norm_topk:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        if route_scale != 1.0:
            w = w * route_scale
        # An invalid row's assignments take group id E: past every real
        # group in the sort, in no group's count.
        e = jnp.where(valid[:, None], e, n_pub).reshape(-1)
        counts = jnp.sum(
            e[:, None] == jnp.arange(n_pub, dtype=e.dtype)[None, :], axis=0,
            dtype=jnp.int32,
        )
        sizes = counts
        if n_exp != n_pub:
            # A chip's share: an assignment to an expert held elsewhere
            # joins no group here either (counted above, computed nowhere).
            held = (e >= expert_first) & (e < expert_first + n_exp)
            e = jnp.where(held, e - expert_first, n_exp)
            sizes = counts[expert_first:expert_first + n_exp]
        order = jnp.argsort(e)  # stable: assignment i of token i // k
    mm = functools.partial(grouped_matmul, interpret=interpret)
    n_all, rungs = t * k, row_rungs(t * k, n_exp, n_pub)
    # The two row shuffles by DMA kernels over the live rows, or XLA's.
    fused = row_kernels_fit(n_all, rows.shape[1], rows.dtype)

    def on_rung(work):
        """``work(n)`` — a piece of the block's row work over the first
        ``n`` sorted assignments, its result the whole call's shape — on
        the lowest rung that holds every held assignment.  The grouped
        matmuls stay OUTSIDE the branches, over all ``t * k`` rows (the
        kernel runs the row tiles the groups reach and no other, whatever
        M is), so a rung adds three small XLA branches an expert layer to
        an executable and no kernel."""
        if len(rungs) == 1:
            return work(n_all)
        return jax.lax.switch(
            rung_index(jnp.sum(sizes), rungs),
            [functools.partial(work, n) for n in rungs])

    def head(a, n: int):  # a's first n rows
        return a if n == n_all else a[:n]

    def whole(a, n: int):  # n rows -> the call's t * k, zeros behind them
        return a if n == n_all else jnp.pad(a, ((0, n_all - n), (0, 0)))

    def over(base, a, n: int):  # base's first n rows replaced by a
        return a if n == n_all else base.at[:n].set(a)

    def combine(n: int):
        if n == n_all:
            in_group = jnp.arange(n_all) < jnp.sum(sizes)
            back = jnp.take(
                jnp.where(in_group[:, None], ys, 0),
                jnp.argsort(order) if pos is None else pos, axis=0).reshape(
                    t, k, rows.shape[1])
            return jnp.sum(back.astype(jnp.float32) * w[:, :, None], axis=1)
        # An assignment that joined no group reads the zero row behind the
        # rung's rows, so a row past the last group is never read.
        # Slot-major: a token's k rows are k slabs of [T, D], summed slab
        # by slab.
        at = pos.reshape(t, k).T
        at = jnp.where(at < jnp.sum(sizes), at, n)
        src = jnp.concatenate([ys[:n], jnp.zeros_like(ys[:1])])
        back = jnp.take(src, at.reshape(-1), axis=0).reshape(
            k, t, rows.shape[1])
        return jnp.sum(back.astype(jnp.float32) * w.T[:, :, None], axis=0)

    with jax.named_scope("moe_route"):
        # [T*k, D], sorted by expert
        if fused:
            xs = sorted_rows(rows, order // k, jnp.sum(sizes), interpret)
        else:
            xs = on_rung(lambda n: whole(
                jnp.take(rows, head(order, n) // k, axis=0), n))
    with jax.named_scope("moe_experts"):
        # A lower rung activates its rows where they lie: the rows behind
        # them stay what the kernel left there (no group's, read by none).
        if act == "relu2":
            ups = mm(xs, up, sizes)
            mid = on_rung(lambda n: over(ups, _relu2(head(ups, n)), n))
        else:
            gates = gate_act(mm(xs, mlp["gate"]["kernel"], sizes))
            ups = mm(xs, up, sizes)
            mid = on_rung(lambda n: over(
                gates, head(gates, n) * clip(head(ups, n)), n))
        ys = mm(mid, down, sizes)  # [T*k, D]
    with jax.named_scope("moe_combine"):
        # Where each assignment's row lies in expert order — with rungs,
        # sorted once outside the branches: a sort is megabytes of
        # executable (one rung sorts where it always has: the program as
        # it was, digest for digest, tools/lowered_text).
        if fused:
            out = combine_rows(ys, jnp.argsort(order).reshape(t, k), w,
                               jnp.sum(sizes), interpret)
        else:
            pos = None if len(rungs) == 1 else jnp.argsort(order)
            out = on_rung(combine)
    out = out.astype(h.dtype)
    if "latent_up" in mlp:
        with jax.named_scope("moe_latent_up"):
            out = out @ mlp["latent_up"]["kernel"].astype(h.dtype)
    if "shared" in mlp:
        with jax.named_scope("moe_shared"):
            sh = mlp["shared"]

            def proj(name):
                return h @ sh[name]["kernel"].astype(h.dtype)

            mid = _relu2(proj("up")) if act == "relu2" else (
                gate_act(proj("gate")) * clip(proj("up")))
            out = out + mid @ sh["down"]["kernel"].astype(h.dtype)
    return out, counts
