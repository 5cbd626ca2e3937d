"""Selective state-space recurrence (Mamba-2), twice: a chunked scan for
prompt windows and waves, a one-token update for the decode step.

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
its kernels): a program is one row's one head group and one chunk, the
chunks in order; the carried state ``[r P, N]`` lives in VMEM from the
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


def conv_scan(x: jax.Array, state: jax.Array, w: jax.Array, b: jax.Array,
              mask: jax.Array):
    """x [B, L, C] inputs, ``state`` [B, K-1, C] each row's last inputs
    before them, ``w`` [K, C], ``b`` [C], ``mask`` [B, L] (1 on a PREFIX of
    real tokens) -> (silu(b + sum_j w_j x_{t-K+1+j}) [B, L, C] in x's
    dtype, the new state: the last K-1 inputs up to each row's last real
    token — the old state for a row with none)."""
    k, length = w.shape[0], x.shape[1]
    full = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = b.astype(jnp.float32) + sum(
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
    y = b.astype(jnp.float32) + jnp.sum(
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


def _kernel_fits(h: int, p: int, g: int, n: int, chunk: int,
                 interpret: bool) -> bool:
    """Whether the kernel's blocks are whole tiles of the chip: B and C
    are read ``n`` lanes at a time from lane ``H P`` on, a group's heads
    ``r P`` lanes at a time, a chunk's tokens as sublanes of x and as
    lanes of the running sums, whose rows are a group's heads.  The
    interpreter takes any widths whose blocks start on a block."""
    if h % g or (h * p) % n:
        return False
    if interpret:
        return True
    r = h // g
    return not (n % LANES or (r * p) % LANES or chunk % LANES or r % 8
                or (_heads_a_tile(r, p) * p) % LANES)


def _pick(per_head, head_of, t: int):
    """``per_head(i)`` broadcast against ``head_of`` (an iota of lanes or
    sublanes // P): each position takes its own head's value."""
    out = per_head(t - 1)
    for i in range(t - 2, -1, -1):
        out = jnp.where(head_of == i, per_head(i), out)
    return out


def _scan_kernel(real_ref, x_ref, b_ref, c_ref, dt_ref, cum_ref, d_ref, s0_ref,
                 y_ref, s_ref, *, r: int, p: int, t: int):
    """One chunk of one row's one head group.  ``x_ref`` [Q, r P], ``b_ref``
    / ``c_ref`` [Q, N], ``dt_ref`` / ``cum_ref`` [r, Q] (a head a row),
    ``d_ref`` [1, r P]; ``s_ref`` (the output block, resident over the
    row's chunks) is the carried state [r P, N]."""
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
        cb = dot(cm, bm, (1, 1))  # C B^T [Q, K], once a group
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
    """The kernel over ``(B, G, L / chunk)``, the chunks innermost and in
    order: x, B and C through block index maps from ``xbc`` where it lies,
    dt and its running sums (computed here, exact float32) a head a row,
    the count of real tokens a chunk as a scalar-prefetch operand.  dt is
    float32 and masked, L a multiple of ``chunk``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    bsz, length, h = dt.shape
    r, nc = h // g, length // chunk
    inner = xbc.shape[-1] - 2 * g * n
    p = inner // h
    dt_t, cum_t = _chunk_sums(dt, a, chunk)  # [B, nc, H, Q]
    real = jnp.sum((mask != 0).reshape(bsz, nc, chunk), axis=-1, dtype=jnp.int32)
    d_lanes = jnp.repeat(d.astype(f32), p).reshape(g, 1, r * p)

    def spec(block, index):  # index(b, g, z) -> block indices
        return pl.BlockSpec(block, lambda bi, gi, z, real: index(bi, gi, z))

    per_head = spec((None, None, r, chunk), lambda bi, gi, z: (bi, z, gi, 0))
    state = spec((None, None, r * p, n), lambda bi, gi, z: (bi, gi, 0, 0))
    heads = spec((None, chunk, r * p), lambda bi, gi, z: (bi, z, gi))
    y, s = pl.pallas_call(
        functools.partial(_scan_kernel, r=r, p=p, t=_heads_a_tile(r, p)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, g, nc),
            in_specs=[
                heads,
                spec((None, chunk, n), lambda bi, gi, z: (bi, z, inner // n + gi)),
                spec((None, chunk, n), lambda bi, gi, z: (bi, z, inner // n + g + gi)),
                per_head, per_head,
                spec((None, 1, r * p), lambda bi, gi, z: (gi, 0, 0)),
                state,
            ],
            out_specs=[heads, state],
        ),
        out_shape=[jax.ShapeDtypeStruct((bsz, length, inner), f32),
                   jax.ShapeDtypeStruct((bsz, g, r * p, n), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_scan",
    )(real, xbc, xbc, xbc, dt_t, cum_t, d_lanes,
      s0.reshape(bsz, g, r * p, n))
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
