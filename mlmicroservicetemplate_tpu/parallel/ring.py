"""Ring attention: sequence-parallel attention over the device mesh.

Long-context capability (the reference has none — SURVEY.md §2 lists
every parallelism strategy as absent except replica-DP — but
long-sequence serving shapes the core design, so it is first-class
here): the sequence axis is sharded across a ``('sp',)`` mesh axis;
each device keeps its local Q block resident and the K/V (+ key mask)
blocks rotate around the ring via ``lax.ppermute`` over ICI, with
online-softmax accumulators merging each hop's partial attention.

Peak memory per device is O(S/n · S/n) for scores instead of O(S²),
and the ppermute of the next K/V block overlaps with compute of the
current one under XLA's async collectives — the standard TPU recipe
for million-token attention, here at serving scale.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def _hop_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref, l_ref,
                o_out, m_out, l_out, *, scale: float):
    """One ring hop's online-softmax update for one (batch, head) cell:
    the [S_loc, S_loc] score tile, mask, exp and the rescaled
    accumulator updates all stay VMEM-resident — the unfused path
    writes+reads the f32 score tensor through HBM on EVERY hop, n-1
    times per layer."""
    q = q_ref[0, 0].astype(jnp.float32)  # [Sq, D]
    k = k_ref[0, 0].astype(jnp.float32)  # [Sk, D]
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    s = jnp.where(mask_ref[0][0][None, :] != 0, s, jnp.float32(-1e9))
    # m/l ride as [B, H, 1, S] (TPU block tiling wants the trailing two
    # dims to equal the array's); index the singleton away here.
    m_prev = m_ref[0, 0, 0]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_out[0, 0, 0] = l_ref[0, 0, 0] * corr + p.sum(axis=-1)
    pv = jax.lax.dot_general(
        p, v_ref[0, 0].astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_out[0, 0] = o_ref[0, 0] * corr[:, None] + pv
    m_out[0, 0, 0] = m_new


def _hop_pallas(qf, kc, vc, mc, o, m, l, *, scale: float, interpret: bool):
    """Pallas dispatch of one hop: grid (B, H); accumulators in f32.

    Shapes: qf/kc/vc [B, S, H, D] (q pre-transposed NOT needed — blocks
    index [b, :, h, :] views via transpose outside), o [B,H,Sq,D],
    m/l [B,H,Sq]."""
    import functools

    from jax.experimental import pallas as pl

    b, s, h, d = qf.shape
    qt = jnp.transpose(qf, (0, 2, 1, 3))
    kt = jnp.transpose(kc, (0, 2, 1, 3))
    vt = jnp.transpose(vc, (0, 2, 1, 3))
    bhsd = pl.BlockSpec((1, 1, s, d), lambda i, j: (i, j, 0, 0))
    bh1s = pl.BlockSpec((1, 1, 1, s), lambda i, j: (i, j, 0, 0))
    mask_spec = pl.BlockSpec((1, 1, s), lambda i, j: (i, 0, 0))
    o2, m2, l2 = pl.pallas_call(
        functools.partial(_hop_kernel, scale=scale),
        grid=(b, h),
        in_specs=[bhsd, bhsd, bhsd, mask_spec, bhsd, bh1s, bh1s],
        out_specs=[bhsd, bh1s, bh1s],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt, mc.astype(jnp.int32)[:, None, :],
      o, m[:, :, None, :], l[:, :, None, :])
    return o2, m2[:, :, 0, :], l2[:, :, 0, :]


def _ring_attn_local(q, k, v, key_mask, *, axis_name: str, scale: float,
                     use_pallas: bool = False, interpret: bool = False):
    """Per-device body under shard_map.

    q, k, v: [B, S_loc, H, D] (local shard); key_mask: [B, S_loc].
    Returns [B, S_loc, H, D].
    """
    n = lax.psum(1, axis_name)
    qf = q.astype(jnp.float32)
    b, s_loc, h, d = q.shape

    def step(i, carry):
        o, m, l, kc, vc, mc = carry
        if use_pallas:
            o, m, l = _hop_pallas(
                qf, kc, vc, mc, o, m, l, scale=scale, interpret=interpret
            )
        else:
            s = jnp.einsum("bqhd,bkhd->bhqk", qf, kc.astype(jnp.float32)) * scale
            s = jnp.where(mc[:, None, None, :] != 0, s, jnp.float32(-1e9))
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            o = o * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, vc.astype(jnp.float32)
            )
            m = m_new
        # The final iteration's rotation would only be discarded — skip
        # it so each call pays n-1 K/V-block hops, not n.  (i is uniform
        # across the mesh, so every device takes the same branch and the
        # collectives stay collective.)
        def rotate(ops):
            perm = [(j, (j + 1) % n) for j in range(n)]
            return tuple(lax.ppermute(x, axis_name, perm) for x in ops)

        kc, vc, mc = lax.cond(i < n - 1, rotate, lambda ops: ops, (kc, vc, mc))
        return (o, m, l, kc, vc, mc)

    o0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    m0 = jnp.full((b, h, s_loc), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc), jnp.float32)
    o, m, l, *_ = lax.fori_loop(0, n, step, (o0, m0, l0, k, v, key_mask))
    o = o / jnp.maximum(l, 1e-20)[..., None]  # fully-masked rows stay finite
    return jnp.transpose(o, (0, 2, 1, 3)).astype(q.dtype)


def make_ring_attention(mesh, axis: str = "sp"):
    """Build a sequence-sharded attention fn over ``mesh[axis]``.

    Returns ``fn(q, k, v, key_mask) -> ctx`` with q/k/v [B, S, H, D] and
    key_mask [B, S]; S must divide evenly by the axis size.  Call it
    inside jit with inputs sharded seq-over-``axis`` (it is a
    shard_map, so it composes with the surrounding program).

    On a 2-D ``('replica', 'sp')`` mesh the batch axis additionally
    shards over 'replica'; the ppermute ring stays within each replica
    row (axis_name scopes the collective), so data-parallel groups run
    independent rings — batch DP × sequence SP composed.
    """
    batch_axis = "replica" if "replica" in mesh.axis_names else None

    def fn(q, k, v, key_mask, *, use_pallas: bool = False,
           interpret: bool = False):
        scale = 1.0 / math.sqrt(q.shape[-1])
        body = functools.partial(
            _ring_attn_local, axis_name=axis, scale=scale,
            use_pallas=use_pallas, interpret=interpret,
        )
        seq_sharded = P(batch_axis, axis, None, None)
        in_specs = (seq_sharded, seq_sharded, seq_sharded, P(batch_axis, axis))
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=seq_sharded,
            check_vma=False,
        )(q, k, v, key_mask)

    return fn
