"""Three recurrences kept in the same state rows, each twice — a scan for
prompt windows and waves, a one-token update for the decode step: Mamba-2
(here), Gated DeltaNet and Mamba-1 (the comments before theirs, below).
Head ``h`` of H (``P`` wide) reads group ``h // (H / G)`` of the G groups
of B and C (``N`` wide); ``S`` [H, P, N] is a row's recurrent state:

    a_t = exp(A_h dt_t)                       A_h = -exp(A_log_h) < 0, dt_t > 0
    S_t = a_t S_{t-1} + dt_t x_t (x) B_t
    y_t = S_t C_t + D_h x_t

``ssm_scan`` is the chunked (SSD) form: within a chunk of ``chunk`` tokens
the masked decay matrix ``exp(cs_q - cs_k)`` (``cs`` the running sum of
``A dt``) times ``C B^T`` weighs the chunk's own inputs, across chunks a
carried state; each row takes its initial state and gives back its final
one.  ``ssm_step`` is the recurrence itself, one token a row.  A masked
token (padding, a filled-up row of a batched dispatch, a finished row of
a decode step) has ``dt = 0``: ``a = 1`` and no input, so it moves no
state.  The state, the running sums, every exponential and accumulator
are float32 (a bfloat16 state would round at every one of thousands of
steps).

The scan has two forms behind one name.  ``jax.numpy`` (``_scan_xla``): the
tests' reference and the path without kernels; XLA writes its decay
matrices ``[B, nc, G, r, Q, K]`` and the state every chunk starts from to
HBM.  The fused chunk kernel (``_scan_kernel``, where the decode step runs
its kernels): a program is one row's one block of a group's heads
(``_head_block``: the whole group at Nemotron's 16 heads a group, 16 of
Granite's 128 heads in ONE group) and one chunk, the chunks in order; the
carried state ``[r P, N]`` lives in VMEM from the
row's initial state to its final one, ``C B^T`` and the decay matrices
never leave VMEM, x, B and C are read from the convolution's output where
it lies and y is written token-major — docs/kernel_tuning.md.  Both take
their matmuls' operands at ``jax_default_matmul_precision`` (on the chip's
default one bfloat16 pass, XLA's and Mosaic's alike); sums differ in
order only.

``conv_scan`` / ``conv_step`` are the short causal depthwise convolution
in front of it, whose state is a row's last ``K - 1`` inputs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _bias(b):
    """A convolution's bias in float32 (0 for ``None``: Gated DeltaNet's)."""
    return 0.0 if b is None else b.astype(jnp.float32)


def conv_scan(x: jax.Array, state: jax.Array, w: jax.Array, b: jax.Array,
              mask: jax.Array):
    """x [B, L, C] inputs, ``state`` [B, K-1, C] each row's last inputs
    before them, ``w`` [K, C], ``b`` [C] | None, ``mask`` [B, L] (1 on a PREFIX of
    real tokens) -> (silu(b + sum_j w_j x_{t-K+1+j}) [B, L, C] in x's
    dtype, the new state: the last K-1 inputs up to each row's last real
    token — the old state for a row with none)."""
    k, length = w.shape[0], x.shape[1]
    full = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = _bias(b) + sum(
        w[j].astype(jnp.float32) * full[:, j:j + length].astype(jnp.float32)
        for j in range(k))
    n = jnp.sum(mask != 0, axis=-1).astype(jnp.int32)  # [B] real tokens
    new = jax.vmap(
        lambda f, s: jax.lax.dynamic_slice_in_dim(f, s, k - 1))(full, n)
    return jax.nn.silu(y).astype(x.dtype), new.astype(state.dtype)


def conv_step(x: jax.Array, state: jax.Array, w: jax.Array, b: jax.Array,
              live: jax.Array):
    """One token a row: x [B, C], ``state`` [B, K-1, C], ``live`` [B] ->
    (the convolution's output [B, C], the taps shifted by one where the
    row is live and as they were where it is not)."""
    full = jnp.concatenate([state.astype(x.dtype), x[:, None]], axis=1)
    y = _bias(b) + jnp.sum(
        w.astype(jnp.float32)[None] * full.astype(jnp.float32), axis=1)
    new = jnp.where(live[:, None, None], full[:, 1:].astype(state.dtype), state)
    return jax.nn.silu(y).astype(x.dtype), new


def split_xbc(xbc, h: int, g: int, n: int):
    """The convolution's output [B, L, H P + 2 G N] as x [B, L, H, P], B and
    C [B, L, G, N]."""
    bsz, length = xbc.shape[:2]
    inner = xbc.shape[-1] - 2 * g * n
    return (xbc[..., :inner].reshape(bsz, length, h, inner // h),
            xbc[..., inner:inner + g * n].reshape(bsz, length, g, n),
            xbc[..., inner + g * n:].reshape(bsz, length, g, n))


def ssm_scan(xbc: jax.Array, dt: jax.Array, a: jax.Array, d: jax.Array,
             s0: jax.Array, mask: jax.Array, *, groups: int, state: int,
             chunk: int = 128, kernel: bool = False, interpret: bool = False):
    """``xbc`` [B, L, H P + 2 G N] — x, B and C side by side as
    ``conv_scan`` leaves them —, dt [B, L, H] (after softplus), ``a`` [H]
    (negative), ``d`` [H], ``s0`` [B, H, P, N] float32, ``mask`` [B, L] ->
    (y [B, L, H P] float32, final state [B, H, P, N] float32).  Any L: the
    tail is padded with masked tokens.  With ``kernel`` (and widths the
    chip's tiles divide) the fused chunk kernel below, else — the tests'
    reference and the path without kernels — ``jax.numpy``."""
    length, h = dt.shape[1:]
    pad = -length % chunk
    if pad:
        xbc, dt, mask = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (xbc, dt, mask))
    dt = dt.astype(jnp.float32) * (mask != 0)[..., None]
    p = (xbc.shape[-1] - 2 * groups * state) // h
    if kernel and _kernel_fits(h, p, groups, state, chunk, interpret):
        y, s = _scan_kernel_call(xbc, dt, a, d, s0, mask, groups, state,
                                 chunk, interpret)
    else:
        y, s = _scan_xla(*split_xbc(xbc, h, groups, state), dt, a, d, s0, chunk)
    return y[:, :length], s


def _chunk_sums(dt, a, chunk: int):
    """dt [B, L, H] (float32, masked) and the running sum of ``A dt``
    within each chunk, each heads-major within a chunk: [B, nc, H, Q]."""
    bsz, length, h = dt.shape
    dts = dt.reshape(bsz, length // chunk, chunk, h)
    cum = jnp.cumsum(dts * a.astype(jnp.float32), axis=2)
    return jnp.swapaxes(dts, 2, 3), jnp.swapaxes(cum, 2, 3)


def _scan_xla(x, b, c, dt, a, d, s0, chunk: int):
    """The chunked scan in ``jax.numpy``: x [B, L, H, P], ``b`` / ``c``
    [B, L, G, N], dt [B, L, H] float32 and masked, L a multiple of
    ``chunk`` -> (y [B, L, H P], final state)."""
    f32 = jnp.float32
    bsz, length, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g
    nc = length // chunk

    def chunks(t, *tail):  # [B, L, ...] -> [B, nc, Q, *tail]
        return t.astype(f32).reshape(bsz, nc, chunk, *tail)

    xs, dts = chunks(x, g, r, p), chunks(dt, g, r)
    bs, cs_ = chunks(b, g, n), chunks(c, g, n)
    xdt = xs * dts[..., None]
    cum = _chunk_sums(dt, a, chunk)[1].reshape(bsz, nc, g, r, chunk)
    # Within a chunk: token q reads token k <= q through exp(cum_q - cum_k).
    seg = cum[..., :, None] - cum[..., None, :]  # [B, nc, G, r, Q, K]
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((chunk, chunk), bool)), seg, -jnp.inf))
    cb = jnp.einsum("bzqgn,bzkgn->bzgqk", cs_, bs)
    y = jnp.einsum("bzgrqk,bzkgrp->bzqgrp", cb[:, :, :, None] * decay, xdt)
    # What a chunk leaves behind, and the state each chunk starts from.
    to_end = jnp.exp(cum[..., -1:] - cum)  # [B, nc, G, r, Q]
    left = jnp.einsum("bzkgn,bzgrk,bzkgrp->bzgrpn", bs, to_end, xdt)
    whole = jnp.exp(cum[..., -1])  # [B, nc, G, r]

    def carry(s, step):
        st, dec = step
        return s * dec[..., None, None] + st, s

    s_last, s_in = jax.lax.scan(
        carry, s0.astype(f32).reshape(bsz, g, r, p, n),
        (jnp.moveaxis(left, 1, 0), jnp.moveaxis(whole, 1, 0)))
    s_in = jnp.moveaxis(s_in, 0, 1)  # [B, nc, G, r, P, N]
    y = y + jnp.einsum("bzqgn,bzgrpn,bzgrq->bzqgrp", cs_, s_in, jnp.exp(cum))
    y = y + xs * d.astype(f32).reshape(g, r)[:, :, None]
    return y.reshape(bsz, length, h * p), s_last.reshape(bsz, h, p, n)


# ---------------------------------------------------------------------------
# the fused chunk kernel

#: Lanes of a vector register: heads narrower than this share a lane tile
#: (two of Nemotron's 64-wide heads), so every load, store and matmul
#: operand of the kernel is whole tiles.
LANES = 128


def _heads_a_tile(r: int, p: int) -> int:
    """Heads of a group the kernel handles side by side in one lane tile:
    as many as fit ``LANES`` and divide the group's ``r``."""
    t = max(1, min(r, LANES // p))
    while r % t:
        t -= 1
    return t


#: Lanes of x and y a program's block of heads spans at most: Nemotron's
#: block, 16 heads of 64 — x ``[128, 1024]`` bfloat16, y and the two copies of
#: the state ``[1024, 128]`` float32, each twice for the pipeline's two slots:
#: 3.5 MB of the 16 MB of scoped VMEM beside the chunk's ``[Q, Q]`` decay
#: matrices.  A whole group of Granite's (128 heads: x and y ``[128, 8192]``,
#: the state ``[8192, 128]``) would take over 20 MB.  Blocks of 32 and 64
#: heads compile too and read the SAME time on the chip (0.456 / 0.471 /
#: 0.456 ms a layer at ``[3, 1024]``: PERF.md section 6, PR 56), so the bound
#: stays where the accepted configuration's blocks are.
HEAD_BLOCK_LANES = 1024


def _head_block(r: int, p: int) -> int:
    """Heads of a group one program takes: the group whole where it spans
    at most ``HEAD_BLOCK_LANES`` lanes (Nemotron's: its programs, and so its
    executable, are what they were before groups were tiled), else the most
    heads that do, divide ``r`` and are whole sublane tiles of the running
    sums (a multiple of 8; a group no such count divides stays whole and
    ``_kernel_fits`` says no)."""
    if r * p <= HEAD_BLOCK_LANES:
        return r
    fit = [b for b in range(8, HEAD_BLOCK_LANES // p + 1, 8) if r % b == 0]
    return fit[-1] if fit else r


def _kernel_fits(h: int, p: int, g: int, n: int, chunk: int,
                 interpret: bool) -> bool:
    """Whether the kernel's blocks are whole tiles of the chip and fit its
    VMEM: B and C are read ``n`` lanes at a time from lane ``H P`` on, a
    block of a group's heads (``_head_block``) ``rb P`` lanes at a time — at
    most ``HEAD_BLOCK_LANES`` —, a chunk's tokens as sublanes of x and as
    lanes of the running sums, whose rows are the block's heads.  The
    interpreter takes any widths whose blocks start on a block."""
    if h % g or (h * p) % n:
        return False
    if interpret:
        return True
    rb = _head_block(h // g, p)
    return not (n % LANES or (rb * p) % LANES or chunk % LANES or rb % 8
                or rb * p > HEAD_BLOCK_LANES
                or (_heads_a_tile(rb, p) * p) % LANES)


def _pick(per_head, head_of, t: int):
    """``per_head(i)`` broadcast against ``head_of`` (an iota of lanes or
    sublanes // P): each position takes its own head's value."""
    out = per_head(t - 1)
    for i in range(t - 2, -1, -1):
        out = jnp.where(head_of == i, per_head(i), out)
    return out


def _scan_kernel(real_ref, x_ref, b_ref, c_ref, dt_ref, cum_ref, d_ref, s0_ref,
                 y_ref, s_ref, *, r: int, p: int, t: int):
    """One chunk of one row's one block of ``r`` heads of a group.  ``x_ref``
    [Q, r P], ``b_ref`` / ``c_ref`` [Q, N] (their group's), ``dt_ref`` /
    ``cum_ref`` [r, Q] (a head a row), ``d_ref`` [1, r P]; ``s_ref`` (the
    output block, resident over the row's chunks) is the carried state
    [r P, N]."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    q = x_ref.shape[0]
    bi, z = pl.program_id(0), pl.program_id(2)
    real = real_ref[bi, z]

    @pl.when(z == 0)
    def _():
        s_ref[...] = s0_ref[...].astype(f32)

    @pl.when(real == 0)  # no token to fold: no matmul, the state as it was
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(real > 0)
    def _():
        def dot(lhs, rhs, contract):
            return jax.lax.dot_general(
                lhs.astype(f32), rhs.astype(f32), ((contract[:1], contract[1:]), ((), ())),
                preferred_element_type=f32)

        bm, cm = b_ref[...], c_ref[...]
        cb = dot(cm, bm, (1, 1))  # C B^T [Q, K], once a block of heads
        tril = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
                >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
        dt, cum = dt_ref[...], cum_ref[...]  # [r, Q]: a head's tokens a row
        cum_q = cum.T  # [Q, r]: a head's tokens a column
        e_in = jnp.exp(cum_q)
        # what token k still weighs at the chunk's end, and the whole chunk
        w_end = dt * jnp.exp(cum[:, q - 1:q] - cum)
        whole = jnp.exp(cum[:, q - 1:q])  # [r, 1]
        lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, t * p), 1) // p
        row_head = jax.lax.broadcasted_iota(jnp.int32, (t * p, 1), 0) // p
        for j0 in range(0, r, t):
            at = slice(j0 * p, (j0 + t) * p)
            x = x_ref[:, at]  # [Q, t P]: t heads side by side
            xf = x.astype(f32)

            def within(i):  # token q reads k <= q: the difference first
                j = j0 + i
                seg = cum_q[:, j:j + 1] - cum[j:j + 1, :]
                decay = jnp.exp(jnp.where(tril, seg, -jnp.inf))
                return dot(cb * decay * dt[j:j + 1, :], x, (1, 0))

            s = s_ref[at, :]  # [t P, N]
            y_ref[:, at] = (
                _pick(within, lane_head, t)
                + dot(cm, s, (1, 1)) * _pick(
                    lambda i: e_in[:, j0 + i:j0 + i + 1], lane_head, t)
                + xf * d_ref[:, at])
            x_end = xf.T * _pick(
                lambda i: w_end[j0 + i:j0 + i + 1, :], row_head, t)  # [t P, K]
            s_ref[at, :] = (
                s * _pick(lambda i: whole[j0 + i:j0 + i + 1, :], row_head, t)
                + dot(x_end, bm, (1, 0)))


@functools.partial(jax.jit, static_argnames=("g", "n", "chunk", "interpret"))
def _scan_kernel_call(xbc, dt, a, d, s0, mask, g: int, n: int, chunk: int,
                      interpret: bool):
    """The kernel over ``(B, G x nb, L / chunk)`` — ``nb`` blocks of
    ``_head_block`` heads a group, a group's blocks side by side as its
    heads lie, so a head block's index IS its place among all of them and
    only B and C's maps ask which group it belongs to; one block a group
    (``nb`` 1) is the grid ``(B, G, L / chunk)`` —, the chunks innermost and
    in order: x, B and C through block index maps from ``xbc`` where it lies,
    dt and its running sums (computed here, exact float32) a head a row,
    the count of real tokens a chunk as a scalar-prefetch operand.  dt is
    float32 and masked, L a multiple of ``chunk``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    bsz, length, h = dt.shape
    nc = length // chunk
    inner = xbc.shape[-1] - 2 * g * n
    p = inner // h
    r = _head_block(h // g, p)  # heads a program
    nb = h // g // r  # programs a group
    dt_t, cum_t = _chunk_sums(dt, a, chunk)  # [B, nc, H, Q]
    real = jnp.sum((mask != 0).reshape(bsz, nc, chunk), axis=-1, dtype=jnp.int32)
    d_lanes = jnp.repeat(d.astype(f32), p).reshape(g * nb, 1, r * p)

    def spec(block, index):  # index(b, head block, z) -> block indices
        return pl.BlockSpec(block, lambda bi, gi, z, real: index(bi, gi, z))

    def group(gi):  # the group head block ``gi`` reads its B and C from
        return gi if nb == 1 else gi // nb

    per_head = spec((None, None, r, chunk), lambda bi, gi, z: (bi, z, gi, 0))
    state = spec((None, None, r * p, n), lambda bi, gi, z: (bi, gi, 0, 0))
    heads = spec((None, chunk, r * p), lambda bi, gi, z: (bi, z, gi))
    y, s = pl.pallas_call(
        functools.partial(_scan_kernel, r=r, p=p, t=_heads_a_tile(r, p)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, g * nb, nc),
            in_specs=[
                heads,
                spec((None, chunk, n),
                     lambda bi, gi, z: (bi, z, inner // n + group(gi))),
                spec((None, chunk, n),
                     lambda bi, gi, z: (bi, z, inner // n + g + group(gi))),
                per_head, per_head,
                spec((None, 1, r * p), lambda bi, gi, z: (gi, 0, 0)),
                state,
            ],
            out_specs=[heads, state],
        ),
        out_shape=[jax.ShapeDtypeStruct((bsz, length, inner), f32),
                   jax.ShapeDtypeStruct((bsz, g * nb, r * p, n), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_scan",
    )(real, xbc, xbc, xbc, dt_t, cum_t, d_lanes,
      s0.reshape(bsz, g * nb, r * p, n))
    return y, s.reshape(bsz, h, p, n)


def ssm_step(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, d: jax.Array, s: jax.Array, live: jax.Array):
    """One token a row: x [B, H, P], dt [B, H], ``b`` / ``c`` [B, G, N],
    ``s`` [B, H, P, N] float32, ``live`` [B] -> (y [B, H, P] float32, the
    state: updated where the row is live, as it was where it is not)."""
    f32 = jnp.float32
    bsz, h, p = x.shape
    g = b.shape[1]
    x, dt = x.astype(f32), dt.astype(f32)
    bh = jnp.repeat(b.astype(f32), h // g, axis=1)  # [B, H, N]
    ch = jnp.repeat(c.astype(f32), h // g, axis=1)
    decay = jnp.exp(dt * a.astype(f32))[..., None, None]
    new = decay * s + (x * dt[..., None])[..., None] * bh[:, :, None, :]
    new = jnp.where(live[:, None, None, None], new, s)
    y = jnp.sum(new * ch[:, :, None, :], axis=-1) + x * d.astype(f32)[:, None]
    return y, new


# ---------------------------------------------------------------------------
# Gated DeltaNet (arXiv:2412.06464; ``gdn_scan`` / ``gdn_step``) is the other
# recurrence kept in the same state rows: a head's state is a MATRIX ``S``
# [Dv, Dk] float32 updated by a delta rule,
#
#     S_t = a_t S_{t-1} + b_t (v_t - a_t S_{t-1} k_t) k_t^T      o_t = S_t q_t
#
# ``a_t = exp(g_t)`` in (0, 1] a head's decay, ``b_t`` in (0, 1) how much of
# the value the state held for ``k_t`` is replaced.  A masked token has
# ``g = 0`` and ``b = 0`` and moves no state.  ``gdn_step`` is the rule
# itself; ``gdn_scan`` its chunked form (the UT transform): within a chunk
# the ``u_t = b_t (v_t - a_t S_{t-1} k_t)`` of all tokens at once through
# the inverse of a unit lower-triangular matrix, across chunks the carried
# ``S``.  The scan has two forms behind one name, as ``ssm_scan`` has.
# ``jax.numpy`` (``_gdn_scan_xla``): the tests' reference and the path
# without kernels or at widths the chip's tiles do not divide; q and k are
# normalised and repeated a value head in float32 and every ``[Q, Q]``
# matrix goes through HBM.  The fused chunk kernel (``_gdn_kernel``, at the
# bottom of this file, where the decode step runs its kernels): a program
# is one row's ``GDN_STEP_HEADS`` KEY heads and ``GDN_STEP_CHUNKS`` chunks in
# order; it reads q, k and their value heads' v from the convolution's
# output where it lies, normalises q and k once a key head, and ``K K^T``,
# ``Q K^T``, the decays, the inverse, ``U`` and the carried state never
# leave VMEM.
# Every matmul of both forms takes float32 operands at
# ``Precision.HIGHEST`` whatever the default: the inverse feeds every later
# token of the chunk and the state every later chunk, so one bfloat16 pass
# would round what thousands of tokens then read; sums differ in order only.


def _hi(spec: str, *ops):
    return jnp.einsum(spec, *ops, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


#: Side of the diagonal blocks the triangular inverse starts from (by
#: substitution); pairs of inverted blocks are merged upwards by matmuls.
INVERSE_BLOCK = 16
#: Tokens a chunk of ``gdn_scan``: the triangular inverse is within a chunk,
#: the carried state across chunks.  The answer does not depend on it (no
#: published key gives one); 64 keeps the ``[Q, Q]`` matrices small beside
#: the ``[Q, 128]`` operands.
GDN_CHUNK = 64


def _unit_lower_inverse(m: jax.Array) -> jax.Array:
    """``(I + M)^-1`` for ``m`` [..., Q, Q] strictly lower triangular: the
    diagonal blocks of ``INVERSE_BLOCK`` rows by forward substitution (row
    i is ``e_i - M[i, :i] X[:i]``: ``INVERSE_BLOCK - 1`` small steps, every
    block of every chunk at once), then pairs of inverted blocks merged
    upwards, ``[[A, 0], [C, D]]^-1 = [[A^-1, 0], [-D^-1 C A^-1, D^-1]]``:
    two matmuls a level.  Each step is a substitution, so the error is the
    inverse's own conditioning's.  (The finite Neumann series ``prod_j (I +
    (-M)^(2^j))`` is all matmuls and exact on paper, but ``M^p`` sums
    ``C(Q, p)`` paths that cancel: with the correlated keys a convolution
    leaves, float32 overflowed at Q = 64 — my chip run, PR 47.)"""
    lead, q = m.shape[:-2], m.shape[-1]
    b = _inverse_block(q)
    blocks = m.reshape(*lead, q // b, b, q // b, b)
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(q // b)], axis=-3)
    x = jnp.broadcast_to(jnp.eye(b, dtype=m.dtype), diag.shape)
    for i in range(1, b):  # rows past i are still the identity's: no share
        x = x.at[..., i, :].add(-jnp.sum(diag[..., i, :, None] * x, axis=-2))
    while b < q:
        pairs = x.reshape(*lead, q // (2 * b), 2, b, b)
        a_inv, d_inv = pairs[..., 0, :, :], pairs[..., 1, :, :]
        blocks = m.reshape(*lead, q // (2 * b), 2, b, q // (2 * b), 2, b)
        c = jnp.stack([blocks[..., i, 1, :, i, 0, :]
                       for i in range(q // (2 * b))], axis=-3)
        low = -_hi("...ij,...jk->...ik", _hi("...ij,...jk->...ik", d_inv, c), a_inv)
        x = jnp.concatenate([
            jnp.concatenate([a_inv, jnp.zeros_like(a_inv)], axis=-1),
            jnp.concatenate([low, d_inv], axis=-1)], axis=-2)
        b *= 2
    return x[..., 0, :, :]


def _inverse_block(q: int) -> int:
    """Side of the diagonal blocks a chunk of ``q`` tokens starts from."""
    b = min(INVERSE_BLOCK, q)
    if q % b or (q // b) & (q // b - 1):
        raise ValueError(f"a chunk of {q} is not {b} times a power of two")
    return b


def gdn_heads(qkv: jax.Array, hk: int, hv: int, dk: int):
    """The convolution's output [..., Hk Dk | Hk Dk | Hv Dv] as the delta
    rule's operands: q and k [..., Hv, Dk] float32 at unit length a KEY
    head (q scaled ``Dk^-1/2``), value head h reading key head ``h // (Hv /
    Hk)``, and v [..., Hv, Dv] as it is."""
    lead = qkv.shape[:-1]

    def unit(t):
        t = t.astype(jnp.float32).reshape(*lead, hk, dk)
        t = t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)
        return jnp.repeat(t, hv // hk, axis=-2)

    return (unit(qkv[..., :hk * dk]) * dk ** -0.5,
            unit(qkv[..., hk * dk:2 * hk * dk]),
            qkv[..., 2 * hk * dk:].reshape(*lead, hv, -1))


def gdn_scan(qkv: jax.Array, g: jax.Array, beta: jax.Array, s0: jax.Array,
             mask: jax.Array, *, chunk: int = GDN_CHUNK, kernel: bool = False,
             interpret: bool = False):
    """``qkv`` [B, L, Hk Dk | Hk Dk | Hv Dv] — q, k and v side by side as
    ``conv_scan`` leaves them, q and k not yet normalised (``gdn_heads``) —,
    ``g`` [B, L, Hv] (log decay, <= 0), ``beta`` [B, L, Hv], ``s0``
    [B, Hv, Dv, Dk] float32, ``mask`` [B, L] (1 on a PREFIX of real tokens)
    -> (o [B, L, Hv, Dv] float32, final state).  Any L: the tail is padded
    with masked tokens.  With ``M[i, j] = b_i exp(G_i - G_j) k_i . k_j``
    below the diagonal (``G`` the running sum of ``g`` in the chunk) and
    ``T = (I + M)^-1``:

        U = T (b V) - T (b e^G K) S_0^T            the chunk's u_t, [Q, Dv]
        O = (e^G Q) S_0^T + tril(Q K^T e^(G_i - G_j)) U
        S = e^(G_Q) S_0 + U^T (e^(G_Q - G) K)

    ``chunk`` is ``INVERSE_BLOCK`` times a power of two, or at most
    ``INVERSE_BLOCK``.  With ``kernel`` (and widths the chip's tiles
    divide) the fused chunk kernel at the bottom of this file, else — the
    tests' reference and the path without kernels — ``jax.numpy``."""
    f32 = jnp.float32
    length = qkv.shape[1]
    hv, dv, dk = s0.shape[1:]
    hk = (qkv.shape[-1] - hv * dv) // (2 * dk)
    fused = kernel and _gdn_kernel_fits(hk, hv, dk, dv, chunk, interpret)
    pad = -length % (chunk * GDN_STEP_CHUNKS if fused else chunk)
    if pad:
        qkv, g, beta, mask = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (qkv, g, beta, mask))
    live = (mask != 0)[..., None]
    g = jnp.where(live, g.astype(f32), 0.0)
    beta = jnp.where(live, beta.astype(f32), 0.0)
    if fused:
        o, s = _gdn_kernel_call(qkv, g, beta, s0, mask, chunk, interpret)
    else:
        o, s = _gdn_scan_xla(*gdn_heads(qkv, hk, hv, dk), g, beta, s0, chunk)
    return o[:, :length], s


def _gdn_scan_xla(q, k, v, g, beta, s0, chunk: int):
    """The chunked delta rule in ``jax.numpy``: q, k [B, L, Hv, Dk] float32
    (``gdn_heads``), v [B, L, Hv, Dv], ``g`` / ``beta`` [B, L, Hv] float32
    and masked, L a multiple of ``chunk`` -> (o [B, L, Hv, Dv], final
    state); ``T`` by ``_unit_lower_inverse``."""
    f32 = jnp.float32
    bsz, length, h, dk = q.shape
    nc = length // chunk

    def chunks(t):  # [B, L, H, ...] -> [B, nc, H, Q, ...]
        t = t.astype(f32).reshape(bsz, nc, chunk, *t.shape[2:])
        return jnp.moveaxis(t, 2, 3)

    qs, ks, vs, gs, bs = (chunks(t) for t in (q, k, v, g, beta))
    cum = jnp.cumsum(gs, axis=-1)  # [B, nc, H, Q]
    seg = cum[..., :, None] - cum[..., None, :]
    idx = jnp.arange(chunk)
    below, upto = idx[:, None] > idx[None, :], idx[:, None] >= idx[None, :]
    decay = jnp.exp(jnp.where(upto, seg, -jnp.inf))  # [.., Q, K], 0 above
    kk = _hi("bzhqd,bzhkd->bzhqk", ks, ks)
    t = _unit_lower_inverse(jnp.where(below, kk * decay, 0.0) * bs[..., None])
    w = _hi("bzhqk,bzhkv->bzhqv", t, vs * bs[..., None])
    kc = _hi("bzhqk,bzhkd->bzhqd", t, ks * (bs * jnp.exp(cum))[..., None])
    qk = _hi("bzhqd,bzhkd->bzhqk", qs, ks) * decay
    q_in = qs * jnp.exp(cum)[..., None]
    k_end = ks * jnp.exp(cum[..., -1:] - cum)[..., None]
    whole = jnp.exp(cum[..., -1])  # [B, nc, H]

    def carry(s, step):
        w_z, kc_z, qk_z, q_z, k_z, a_z = step
        u = w_z - _hi("bhqd,bhvd->bhqv", kc_z, s)
        o = _hi("bhqd,bhvd->bhqv", q_z, s) + _hi("bhqk,bhkv->bhqv", qk_z, u)
        s = s * a_z[..., None, None] + _hi("bhqv,bhqd->bhvd", u, k_z)
        return s, o

    s_last, o = jax.lax.scan(
        carry, s0.astype(f32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (w, kc, qk, q_in, k_end, whole)))
    # [nc, B, H, Q, Dv] -> [B, L, H, Dv]
    return jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(bsz, length, h, -1), s_last


def gdn_step(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             beta: jax.Array, s: jax.Array, live: jax.Array):
    """One token a row: q, k [B, H, Dk], v [B, H, Dv], ``g`` / ``beta``
    [B, H], ``s`` [B, H, Dv, Dk] float32, ``live`` [B] -> (o [B, H, Dv]
    float32, the state: updated where the row is live, as it was where it
    is not).  Products and sums on the vector unit, exact float32."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    kept = jnp.exp(g.astype(f32))[..., None, None] * s
    u = beta.astype(f32)[..., None] * (v - jnp.sum(kept * k[:, :, None, :], axis=-1))
    new = kept + u[..., None] * k[:, :, None, :]
    new = jnp.where(live[:, None, None, None], new, s)
    return jnp.sum(new * q[:, :, None, :], axis=-1), new


# ---------------------------------------------------------------------------
# the fused chunk kernel of the delta rule

#: Chunks and key heads a grid step of the kernel: ``GDN_STEP_HEADS`` key
#: heads' value heads over ``GDN_STEP_CHUNKS * chunk`` tokens.  A chunk of a
#: key head is one long chain of dependent steps (the elimination, the
#: merges, then U, O and the state, which the next chunk waits for), and
#: the compiler keeps a chain in program order: the kernel walks it stage
#: by stage over the step's (key head, chunk) pairs, so four independent
#: chains stand side by side and fill each other's latencies (for a v5e a
#: step of one key head and four chunks in chunk order held 3500 bundles a
#: chunk, an MXU operation in 58 % of them; this form 1970 and 88 % —
#: docs/kernel_tuning.md).
GDN_STEP_CHUNKS, GDN_STEP_HEADS = 2, 2


def _gdn_step_heads(hk: int, hv: int, dk: int, dv: int) -> int:
    """Key heads a grid step takes: as many of ``GDN_STEP_HEADS`` as divide
    ``hk`` with their value heads' v starting on a block of the
    convolution's output; 0 where not even one does."""
    for p in range(GDN_STEP_HEADS, 0, -1):
        if not (hv % hk or hk % p or (2 * hk * dk) % (p * hv // hk * dv)):
            return p
    return 0


def _gdn_kernel_fits(hk: int, hv: int, dk: int, dv: int, chunk: int,
                     interpret: bool) -> bool:
    """Whether the kernel's blocks are whole tiles of the chip: q, k and a
    key head's value heads' v are read ``dk`` and ``r dv`` lanes at a time
    from the convolution's output, a chunk's ``[Q, Q]`` matrices stand a
    value head beside the other across the lanes, the diagonal blocks of
    the inverse are whole sublane tiles.  The interpreter takes any widths
    whose blocks start on a block."""
    if not _gdn_step_heads(hk, hv, dk, dv):
        return False
    if _inverse_block(chunk) % 8 and not interpret:
        return False
    return interpret or not (dk % LANES or dv % LANES or (hv // hk * chunk) % LANES)


def _gdn_kernel(real_ref, q_ref, k_ref, v_ref, gate_ref, s0_ref, o_ref, s_ref,
                *, p: int, r: int, chunk: int):
    """``GDN_STEP_CHUNKS`` chunks of one row's ``p`` key heads and their
    ``r`` value heads each.  ``q_ref`` / ``k_ref`` [T, p Dk] and ``v_ref``
    [T, p r Dv] as the convolution left them, ``gate_ref`` [p, 2 n (+
    padding), r Q]: row c the running sum of ``g`` over chunk c and row
    n + c its ``beta``, a value head beside the other across the lanes;
    ``s_ref`` (the output block, resident over the row's steps) the carried
    states [p, r Dv, Dk].

    A chunk's ``[Q, Q]`` matrices are built ONCE for a key head's ``r``
    value heads, side by side across the lanes ([Q, r Q]: ``K K^T`` and
    ``Q K^T`` are the key head's, the decays a value head's), and enter the
    matmuls block-diagonal ([r Q, r Q]) against operands that stack the
    heads down the sublanes, so every matmul is whole tiles.  ``(I +
    M)^-1``: the diagonal blocks of ``INVERSE_BLOCK`` rows by elimination a
    column at a time — ``I + M`` is the product of ``I + m_c e_c^T`` over
    its columns, so its inverse applies ``X <- X - m_c X[c]`` for c = 0, 1,
    ...: an exact substitution, a rank-one step on the vector unit — with
    every block of every head PACKED side by side across the lanes
    ([base, r Q]: column c of each block spread over its block's lanes is
    one lane gather), then pairs of blocks merged as ``_unit_lower_inverse``
    merges them, ``X <- X - X C X``.  A step none of whose tokens is real
    does no matmul; a masked chunk inside a live step folds nothing
    (``beta = 0``, ``g = 0``: ``U = 0``, the state times one) and writes
    zeros."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    n = q_ref.shape[0] // chunk
    dk, dv, w = q_ref.shape[1] // p, v_ref.shape[1] // (p * r), r * chunk
    base = _inverse_block(chunk)
    bi, z = pl.program_id(0), pl.program_id(2)
    items = [(h, c) for c in range(n) for h in range(p)]  # (key head, chunk)

    def dot(lhs, rhs, contract=(1, 0)):
        return jax.lax.dot_general(
            lhs, rhs, ((contract[:1], contract[1:]), ((), ())),
            precision=jax.lax.Precision.HIGHEST, preferred_element_type=f32)

    def iota(shape, axis):
        return jax.lax.broadcasted_iota(jnp.int32, shape, axis)

    def every(stage):  # one stage of the walk, over the step's chains
        return {i: stage(*i) for i in items}

    def heads_down(per_head):  # r arrays [Q, ...] -> [r Q, ...]
        return jnp.concatenate([per_head(j) for j in range(r)], axis=0)

    def live_step():
        # Every mask once a step, and ``lax.div`` / ``lax.rem`` on the iotas
        # (non-negative: ``//`` and ``%`` would lower a sign fix-up each):
        # what a grid step's program holds is lowered anew for every
        # executable of a boot (docs/kernel_tuning.md).
        def div(x, by):
            return jax.lax.div(x, jnp.int32(by))

        lane = iota((1, w), 1)
        lane_tok = jax.lax.rem(lane, jnp.int32(chunk))
        tok = iota((chunk, 1), 0)
        upto, below = tok >= lane_tok, tok > lane_tok
        in_head = [div(lane, chunk) == j for j in range(r)]
        in_block = [div(lane_tok, base) == b for b in range(chunk // base)]
        in_state = [div(iota((r * dv, 1), 0), dv) == j for j in range(r)]
        eye = jnp.where(iota((base, 1), 0) == jax.lax.rem(lane, jnp.int32(base)), 1.0, 0.0)
        first_lane = jnp.broadcast_to(div(lane, base) * base, (base, w))
        at = [slice(c * chunk, (c + 1) * chunk) for c in range(n)]
        gates = [gate_ref[h] for h in range(p)]
        gates_t = [g.T for g in gates]  # [r Q, rows]: a head's tokens down

        def pick(per_head, among):  # each position its own head's value
            out = per_head(r - 1)
            for j in range(r - 2, -1, -1):
                out = jnp.where(among[j], per_head(j), out)
            return out

        def block_diagonal(x):  # [Q, r Q], a head beside the other -> [r Q, r Q]
            return heads_down(lambda j: jnp.where(in_head[j], x, 0.0))

        def column(h, j, rows=slice(None)):  # of a key head's gates: [rows, 1]
            return gates_t[h][rows, j:j + 1]

        def last(h, c, j):  # the running sum at the end of chunk c, head j
            return column(h, c, slice((j + 1) * chunk - 1, (j + 1) * chunk))

        def beside(h, j):  # a gate's column a head, each across its head's lanes
            return pick(lambda i: column(h, j, slice(i * chunk, (i + 1) * chunk)), in_head)

        def unit(ref, h, c):
            x = ref[at[c], h * dk:(h + 1) * dk].astype(f32)
            return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

        # what does not read the state: K K^T, Q K^T, the decays, the inverse
        km = every(lambda h, c: unit(k_ref, h, c))
        kq_down = every(lambda h, c: jnp.concatenate(
            [km[h, c], unit(q_ref, h, c) * dk ** -0.5], axis=0))
        k_down = every(lambda h, c: heads_down(lambda j: km[h, c]))
        kq = every(lambda h, c: dot(kq_down[h, c], k_down[h, c], (1, 1)))  # [2 Q, r Q]
        decay = every(lambda h, c: jnp.exp(jnp.where(
            upto, beside(h, c) - gates[h][c:c + 1, :], -jnp.inf)))
        m = every(lambda h, c: jnp.where(
            below, kq[h, c][:chunk] * decay[h, c], 0.0) * beside(h, n + c))
        within = every(lambda h, c: block_diagonal(kq[h, c][chunk:] * decay[h, c]))

        def packed(x):  # the diagonal blocks [base, base] of [Q, r Q], each at its lanes
            out = x[chunk - base:]
            for b in range(chunk // base - 2, -1, -1):
                out = jnp.where(in_block[b], x[b * base:(b + 1) * base], out)
            return out

        mp = every(lambda h, c: packed(m[h, c]))
        x = every(lambda h, c: eye)
        for j in range(base - 1):
            lanes_j = first_lane + j  # column j of each block, over its lanes
            x = every(lambda h, c: x[h, c] - jnp.take_along_axis(
                mp[h, c], lanes_j, axis=1, mode="promise_in_bounds"
            ) * x[h, c][j:j + 1, :])
        x = every(lambda h, c: jnp.concatenate(
            [jnp.where(blk, x[h, c], 0.0) for blk in in_block], axis=0))
        size = base
        while size < chunk:  # the lower-left block of each pair of blocks
            lower = ((div(tok, 2 * size) == div(lane_tok, 2 * size))
                     & (div(tok, size) > div(lane_tok, size)))
            xc = every(lambda h, c: dot(
                x[h, c], block_diagonal(jnp.where(lower, m[h, c], 0.0))))
            x = every(lambda h, c: x[h, c] - dot(xc[h, c], block_diagonal(x[h, c])))
            size *= 2
        x = every(lambda h, c: block_diagonal(x[h, c]))
        # what does: U, O and the state a value head, the heads down the rows
        s = [s_ref[h] for h in range(p)]
        for c in range(n):
            live = (real_ref[bi, z * n + c] > 0).astype(f32)
            e_in = [jnp.exp(column(h, c)) for h in range(p)]
            from_s = {(h, j): dot(kq_down[h, c], s[h][j * dv:(j + 1) * dv, :], (1, 1))
                      for h in range(p) for j in range(r)}
            u = []
            for h in range(p):
                v = heads_down(lambda j: v_ref[
                    at[c], (h * r + j) * dv:(h * r + j + 1) * dv].astype(f32))
                u.append(dot(x[h, c], column(h, n + c) * (v - e_in[h] * heads_down(
                    lambda j: from_s[h, j][:chunk]))))
            for h in range(p):
                o = live * (e_in[h] * heads_down(
                    lambda j: from_s[h, j][chunk:]) + dot(within[h, c], u[h]))
                for j in range(r):
                    o_ref[at[c], (h * r + j) * dv:(h * r + j + 1) * dv] = (
                        o[j * chunk:(j + 1) * chunk])
            for h in range(p):
                g_end = heads_down(lambda j: jnp.broadcast_to(last(h, c, j), (chunk, 1)))
                u_end = (u[h] * jnp.exp(g_end - column(h, c))).T  # [Dv, r Q]
                whole = pick(lambda j: jnp.exp(last(h, c, j)), in_state)
                s[h] = s[h] * whole + dot(heads_down(
                    lambda j: jnp.where(in_head[j], u_end, 0.0)), k_down[h, c])
        for h in range(p):
            s_ref[h] = s[h]

    @pl.when(z == 0)
    def _():
        s_ref[...] = s0_ref[...].astype(f32)

    real = real_ref[bi, z * n]  # of the step's first chunk: the mask is a prefix

    @pl.when(real == 0)  # no token to fold: no matmul, the state as it was
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, f32)

    @pl.when(real > 0)
    def _():
        live_step()


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _gdn_kernel_call(qkv, g, beta, s0, mask, chunk: int, interpret: bool):
    """The kernel over ``(B, Hk / p, L / T)``, ``p`` key heads and ``T =
    GDN_STEP_CHUNKS * chunk`` tokens a step, a row's steps innermost and in
    order: q, k and v through block index maps from ``qkv`` where it lies,
    the gates and the running sum of ``g`` (computed here, exact float32) a
    token a lane, the count of real tokens a chunk as a scalar-prefetch
    operand.  ``g`` and ``beta`` are float32 and masked, L a multiple of
    T."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    bsz, length, hv = g.shape
    dv, dk = s0.shape[2:]
    hk = (qkv.shape[-1] - hv * dv) // (2 * dk)
    r, n, p = hv // hk, GDN_STEP_CHUNKS, _gdn_step_heads(hk, hv, dk, dv)
    t, steps = n * chunk, length // (n * chunk)
    one = jnp.ones((), f32)
    rows = -(-2 * n // 8) * 8

    def a_step(x):  # [B, nc, Hv, Q] -> [B, Hk, steps, n, r Q]
        x = x.reshape(bsz, steps, n, hk, r * chunk)
        return jnp.transpose(x, (0, 3, 1, 2, 4))

    gates = jnp.concatenate(
        [a_step(_chunk_sums(g, one, chunk)[1]), a_step(_chunk_sums(beta, one, chunk)[0]),
         jnp.zeros((bsz, hk, steps, rows - 2 * n, r * chunk), f32)], axis=3)
    real = jnp.sum((mask != 0).reshape(bsz, steps * n, chunk), axis=-1,
                   dtype=jnp.int32)

    def spec(block, index):  # index(b, h, z) -> block indices
        return pl.BlockSpec(block, lambda bi, hi, z, real: index(bi, hi, z))

    state = spec((None, p, r * dv, dk), lambda bi, hi, z: (bi, hi, 0, 0))
    values = spec((None, t, p * r * dv), lambda bi, hi, z: (bi, z, hi))
    o, s = pl.pallas_call(
        functools.partial(_gdn_kernel, p=p, r=r, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, hk // p, steps),
            in_specs=[
                spec((None, t, p * dk), lambda bi, hi, z: (bi, z, hi)),
                spec((None, t, p * dk), lambda bi, hi, z: (bi, z, hk // p + hi)),
                spec((None, t, p * r * dv),
                     lambda bi, hi, z: (bi, z, 2 * hk * dk // (p * r * dv) + hi)),
                spec((None, p, None, rows, r * chunk),
                     lambda bi, hi, z: (bi, hi, z, 0, 0)),
                state,
            ],
            out_specs=[values, state],
        ),
        out_shape=[jax.ShapeDtypeStruct((bsz, length, hv * dv), f32),
                   jax.ShapeDtypeStruct((bsz, hk, r * dv, dk), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="gdn_scan",
    )(real, qkv, qkv, qkv, gates, s0.reshape(bsz, hk, r * dv, dk))
    return o.reshape(bsz, length, hv, dv), s.reshape(bsz, hv, dv, dk)


# ---------------------------------------------------------------------------
# Mamba-1 (arXiv:2312.00752; ``mamba1_scan`` / ``mamba1_step``) is the third
# recurrence kept in the same state rows: no heads, a decay for every one of
# ``C`` channels x ``N`` states,
#
#     h_t[n, c] = exp(D_t[c] A[n, c]) h_{t-1}[n, c] + D_t[c] B_t[n] x_t[c]
#     y_t[c]    = sum_n h_t[n, c] C_t[n] + D[c] x_t[c]
#
# ``D_t`` (``delta``) the token's step a channel, after its softplus; ``A`` <
# 0; ``B_t`` and ``C_t`` ``N`` wide, shared by every channel.  A masked token
# has ``delta = 0``: a decay of 1 and no input.  The state is ``[N, C]``
# float32, the channels the minor axis (lanes on the chip: ``[C, 16]`` would
# pad every 16 states to a 128-lane tile, eight times its bytes in HBM).
# Because the decay differs a channel AND a state, a chunk has no matmul
# form (Mamba-2's ``C B^T`` times one decay matrix a head), and factoring it
# as ``exp(cs_t) sum_s exp(-cs_s) ..`` overflows float32 inside one chunk
# (``A`` to -16, an unbounded softplus).  So the scan is the recurrence
# itself: a ``lax.scan`` over chunks of ``MAMBA1_CHUNK`` tokens whose body is
# the token update (``_mamba1_token``, the decode step's) unrolled over the
# chunk's tokens — elementwise operations on ``[B, N, C]`` and a sum over the
# ``N`` sublanes, nothing else.  No ``[B, Q, N, C]`` array exists, and on the
# chip the compiler keeps the trip's state and rows in VMEM between its
# fusions (``S(1)`` in the compiled text): 1.98 ms a layer for three
# 1024-token windows at the served widths (my chip run, PR 51).  Unrolled
# state by state as well (sixteen ``[B, C]`` arrays, ONE fusion a trip) it
# took 4.53 ms — three rows fill 3 of a tile's 8 sublanes — and five times
# as long to compile, 26 layers an executable: the cold boot's largest part.
# Where the decode step runs its kernels the scan is ONE fused vector-unit
# kernel a layer instead (``_mamba1_kernel``, at the bottom of this file: a
# row's state stays in VMEM over a whole window); this loop stays the tests'
# reference and the path without kernels.

#: Tokens a trip of the prompt scan's loop folds (its body is unrolled over
#: them).  The answer does not depend on it; 16 and 32 ran alike, 8 a tenth
#: slower, 64 a third (my chip run, PR 51).
MAMBA1_CHUNK = 16


def _mamba1_token(h, x, delta, a, b, c, d):
    """One token of the recurrence: ``h`` [B, N, C] float32, ``x`` / ``delta``
    [B, C] float32, ``a`` [N, C], ``b`` / ``c`` [B, N], ``d`` [C] -> (the new
    state, y [B, C])."""
    h = jnp.exp(delta[:, None, :] * a) * h + (delta * x)[:, None, :] * b[:, :, None]
    return h, jnp.sum(h * c[:, :, None], axis=1) + d * x


def mamba1_scan(x: jax.Array, delta: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, d: jax.Array, s0: jax.Array, mask: jax.Array, *,
                chunk: int = MAMBA1_CHUNK, kernel: bool = False,
                interpret: bool = False):
    """``x`` [B, L, C] (the convolution's output), ``delta`` [B, L, C] (after
    softplus), ``a`` [N, C] (negative), ``b`` / ``c`` [B, L, N], ``d`` [C],
    ``s0`` [B, N, C] float32, ``mask`` [B, L] -> (y [B, L, C] float32, final
    state [B, N, C] float32).  Any L: the tail is padded with masked
    tokens.  With ``kernel`` (and widths the chip's tiles divide) the fused
    kernel at the bottom of this file, else — the tests' reference and the
    path without kernels — the ``jax.numpy`` loop over chunks of ``chunk``
    tokens."""
    f32 = jnp.float32
    bsz, length, ch = x.shape
    fused = kernel and _mamba1_kernel_fits(ch, a.shape[0], interpret)
    if fused:  # a grid step's tokens; a short wave is ONE block, in whole
        # sublane tiles of bfloat16 x
        chunk = min(MAMBA1_BLOCK, -(-length // 16) * 16)
    pad = -length % chunk
    if pad:
        x, delta, b, c, mask = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, delta, b, c, mask))
    delta = delta.astype(f32) * (mask != 0)[..., None]
    if fused:
        y, h = _mamba1_kernel_call(x, delta, a, b, c, d, s0, mask, chunk, interpret)
        return y[:, :length], h
    a, d = a.astype(f32), d.astype(f32)

    def chunks(t):  # [B, L, W] -> [L / Q, B, Q, W]: a chunk a trip
        return jnp.moveaxis(
            t.reshape(bsz, (length + pad) // chunk, chunk, t.shape[-1]), 1, 0)

    def trip(h, step):
        xq, dq, bq, cq = step
        ys = []
        for j in range(chunk):
            h, y = _mamba1_token(h, xq[:, j].astype(f32), dq[:, j], a,
                                 bq[:, j].astype(f32), cq[:, j].astype(f32), d)
            ys.append(y)
        return h, jnp.stack(ys, axis=1)

    h, y = jax.lax.scan(trip, s0.astype(f32),
                        (chunks(x), chunks(delta), chunks(b), chunks(c)))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, length + pad, ch)
    return y[:, :length], h


def mamba1_step(x: jax.Array, delta: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, d: jax.Array, s: jax.Array, live: jax.Array):
    """One token a row: ``x`` / ``delta`` [B, C], ``a`` [N, C], ``b`` / ``c``
    [B, N], ``d`` [C], ``s`` [B, N, C] float32, ``live`` [B] -> (y [B, C]
    float32 — a live row's —, the state: updated where the row is live, as
    it was where it is not)."""
    new, y = _mamba1_token(s, *(t.astype(jnp.float32) for t in (x, delta, a, b, c, d)))
    return y, jnp.where(live[:, None, None], new, s)


# ---------------------------------------------------------------------------
# the fused vector-unit kernel of the selective scan

# A channel's 16 states stand in 16 different vector registers and 1024
# channels fill one of them: a token's channel tile FOLDED, its 8 lane tiles
# down the 8 sublanes (``[8, 128]``).  So B_t[n] and C_t[n] are one scalar a
# register (read as a row of equal lanes loaded onto every sublane: no vector
# slot), the sum over the states is 15 register adds (no sublane traffic) and
# the per-token work is the recurrence's own 7 operations a state element.
# (States down the sublanes — ``[16, 1024]`` as two registers a lane tile —
# read 49 bundles a token a tile against this form's 42: the sum over the
# sublanes is rotations and selects, and 64 registers do not hold its
# partial sums.  docs/kernel_tuning.md.)

#: Channels a program of the kernel holds and tokens a grid step folds.  At
#: 1024 channels the state is 16 vector registers and ``A`` 16 more of the
#: chip's 64: both stay in registers over the token loop.  256 tokens x 1024
#: channels of x (bfloat16), Delta and y (float32) are 0.5 + 1 + 1 MB,
#: double-buffered 5 MB; x in float32 1 MB and the rows of B and C 2 x 2 MB
#: beside them: 10 MB of the 16 a kernel may take, and a grid step's ~8 us of
#: vector work hides its ~0.4 us of overhead.
MAMBA1_TILE, MAMBA1_BLOCK = 1024, 256
#: Tokens the token loop's body is unrolled over: one sublane tile of Delta,
#: x and y.  States the body can be unrolled over as well: each is a register
#: of the carried state (no published Mamba-1 has more than 16).
_GROUP, _MAX_STATES = 8, 32
_LOG2E = 1.4426950408889634


def _mamba1_tile(channels: int) -> int:
    """The channel tile: ``MAMBA1_TILE`` halved until it divides the channels
    (down to one lane tile; all the channels where that does not either: the
    interpreter's widths)."""
    tile = MAMBA1_TILE
    while tile > LANES and channels % tile:
        tile //= 2
    return channels if channels % tile else tile


def _mamba1_kernel_fits(channels: int, states: int, interpret: bool) -> bool:
    """Whether the kernel's blocks are whole tiles of the chip — the channels
    whole lane tiles — and its loop's body holds the states in registers.
    The interpreter takes any widths."""
    return interpret or not (channels % LANES or states > _MAX_STATES)


def _mamba1_fold(tile: int) -> tuple:
    """``(sublanes, lanes)`` a token's ``tile`` channels fold to: lane tiles
    down the sublanes (one lane tile where the lanes do not divide the tile:
    the interpreter's widths)."""
    lanes = LANES if tile % LANES == 0 else tile
    return tile // lanes, lanes


def _mamba1_kernel(upto_ref, x_ref, delta_ref, b_ref, c_ref, a_ref, d_ref,
                   s0_ref, y_ref, s_ref, xf_ref, b_rows, c_rows):
    """One block of tokens of one row's one channel tile.  ``x_ref`` /
    ``delta_ref`` / ``y_ref`` [T, tile] (a token a sublane), ``b_ref`` /
    ``c_ref`` [T, N], ``a_ref`` [N, fold, lanes] (this tile's ``A log2 e``,
    folded), ``d_ref`` [1, tile]; ``s_ref`` [tiles, N, fold, lanes] (the
    output block, resident over ALL of the row's grid steps) holds the
    row's carried state, folded; ``xf_ref`` [T, tile] is scratch for x in
    float32, ``b_rows`` / ``c_rows`` [N T, lanes] for B and C with each
    value spread over a whole row of lanes (filled at the row's first
    channel tile of a token block, read by all of them)."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    t, tile = y_ref.shape
    n, fold, lanes = a_ref.shape
    bi, z, ci = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    upto = upto_ref[bi, z]

    @pl.when(z == 0)
    def _():
        s_ref[ci] = s0_ref[ci].astype(f32)

    @pl.when(upto < t)  # rows past the last group: all of them where no token is real
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when((upto > 0) & (ci == 0))
    def _():
        for i in range(n):
            b_rows[i * t:(i + 1) * t] = jnp.broadcast_to(b_ref[:, i:i + 1], (t, lanes))
            c_rows[i * t:(i + 1) * t] = jnp.broadcast_to(c_ref[:, i:i + 1], (t, lanes))

    @pl.when(upto > 0)
    def _():
        xf_ref[...] = x_ref[...].astype(f32)
        a2, d = [a_ref[i] for i in range(n)], d_ref[...]
        row = jax.lax.broadcasted_iota(jnp.int32, (_GROUP, tile), 0)

        def group(g, h):
            r0 = pl.multiple_of(g * _GROUP, _GROUP)
            at = pl.ds(r0, _GROUP)
            h = list(h)
            xg, dg = xf_ref[at, :], delta_ref[at, :]
            deltas = dg.reshape(_GROUP, fold, lanes)
            dxs = (dg * xg).reshape(_GROUP, fold, lanes)
            ys = []
            for j in range(_GROUP):
                ps = []
                for i in range(n):
                    col = pl.ds(i * t + r0 + j, 1)  # one row, loaded onto every sublane
                    h[i] = (jnp.exp2(deltas[j] * a2[i]) * h[i]
                            + dxs[j] * jnp.broadcast_to(b_rows[col, :], (fold, lanes)))
                    ps.append(h[i] * jnp.broadcast_to(c_rows[col, :], (fold, lanes)))
                while len(ps) > 1:  # pairs: a chain of adds would be the schedule
                    ps = [u + v for u, v in zip(ps[::2], ps[1::2])] + ps[len(ps) & ~1:]
                ys.append(ps[0])
            y = jnp.stack(ys).reshape(_GROUP, tile) + d * xg
            y_ref[at, :] = jnp.where(row + r0 < upto, y, 0.0)
            return tuple(h)

        groups = jax.lax.div(upto + (_GROUP - 1), jnp.int32(_GROUP))
        h = jax.lax.fori_loop(0, groups, group, tuple(s_ref[ci, i] for i in range(n)))
        for i in range(n):
            s_ref[ci, i] = h[i]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _mamba1_kernel_call(x, delta, a, b, c, d, s0, mask, block: int,
                        interpret: bool):
    """The kernel over ``(B, L / block, C / tile)``, a row's token blocks in
    order and the channel tiles innermost (B and C spread over the lanes
    once a token block, for all the tiles): x and Delta through block index
    maps where they lie, one past each block's last real token as a
    scalar-prefetch operand; ``A log2 e`` and the state folded here (a
    channel tile's lane tiles down the sublanes: 0.3 MB a row, against the
    60 MB of x and Delta no copy is made of).  Delta is float32 and masked,
    L a multiple of ``block``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    bsz, length, ch = x.shape
    n = a.shape[0]
    tile, nb = _mamba1_tile(ch), length // block
    fold, lanes = _mamba1_fold(tile)
    tiles = ch // tile
    real = (mask != 0).reshape(bsz, nb, block)
    upto = jnp.max(real * jnp.arange(1, block + 1, dtype=jnp.int32), axis=-1)

    def folded(m):  # [..., N, C] -> [..., tiles, N, fold, lanes]
        m = m.astype(f32).reshape(*m.shape[:-1], tiles, fold, lanes)
        return jnp.moveaxis(m, -4, -3)

    def spec(shape, index):  # index(b, z, c) -> block indices
        return pl.BlockSpec(shape, lambda bi, z, ci, upto: index(bi, z, ci))

    tokens = spec((None, block, tile), lambda bi, z, ci: (bi, z, ci))
    columns = spec((None, block, n), lambda bi, z, ci: (bi, z, 0))
    state = spec((None, tiles, n, fold, lanes), lambda bi, z, ci: (bi, 0, 0, 0, 0))
    y, s = pl.pallas_call(
        _mamba1_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, nb, tiles),
            in_specs=[
                tokens, tokens, columns, columns,
                spec((None, n, fold, lanes), lambda bi, z, ci: (ci, 0, 0, 0)),
                spec((1, tile), lambda bi, z, ci: (0, ci)),
                state,
            ],
            out_specs=[tokens, state],
            scratch_shapes=[pltpu.VMEM((block, tile), f32)]
            + [pltpu.VMEM((n * block, lanes), f32)] * 2,
        ),
        out_shape=[jax.ShapeDtypeStruct((bsz, length, ch), f32),
                   jax.ShapeDtypeStruct((bsz, tiles, n, fold, lanes), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="mamba1_scan",
    )(upto, x, delta, b.astype(f32), c.astype(f32), folded(a * _LOG2E),
      d.astype(f32).reshape(1, ch), folded(s0))
    return y, jnp.moveaxis(s, -4, -3).reshape(bsz, n, ch)
