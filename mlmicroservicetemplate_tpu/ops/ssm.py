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
state.  Everything here is float32 ``jax.numpy``; the state stays float32
(a bfloat16 state would round at every one of thousands of steps).

``conv_scan`` / ``conv_step`` are the short causal depthwise convolution
in front of it, whose state is a row's last ``K - 1`` inputs.
"""

from __future__ import annotations

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


def ssm_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, d: jax.Array, s0: jax.Array, mask: jax.Array,
             chunk: int = 128):
    """x [B, L, H, P], dt [B, L, H] (after softplus), ``a`` [H] (negative),
    ``b`` / ``c`` [B, L, G, N], ``d`` [H], ``s0`` [B, H, P, N] float32,
    ``mask`` [B, L] -> (y [B, L, H, P] float32, final state [B, H, P, N]
    float32).  Any L: the tail is padded with masked tokens."""
    f32 = jnp.float32
    bsz, length, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g
    pad = -length % chunk
    if pad:
        x, dt, b, c, mask = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, b, c, mask))
    nc = (length + pad) // chunk
    dt = dt.astype(f32) * (mask != 0)[..., None]

    def chunks(t, *tail):  # [B, L, ...] -> [B, nc, Q, *tail]
        return t.astype(f32).reshape(bsz, nc, chunk, *tail)

    xs, dts = chunks(x, g, r, p), chunks(dt, g, r)
    bs, cs_ = chunks(b, g, n), chunks(c, g, n)
    xdt = xs * dts[..., None]
    # Heads lead, a chunk's tokens are the minor dims: [B, nc, G, r, Q].
    cum = jnp.cumsum(
        jnp.moveaxis(dts, 2, -1) * a.astype(f32).reshape(g, r, 1), axis=-1)
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
    y = y.reshape(bsz, nc * chunk, h, p)[:, :length]
    return y, s_last.reshape(bsz, h, p, n)


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
