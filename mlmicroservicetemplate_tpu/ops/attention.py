"""Fused multi-head attention as a Pallas TPU kernel.

One grid program per (batch, head): Q/K/V tiles stream HBM→VMEM once,
the [S, S] score matrix, mask, softmax, and the probs·V matmul all stay
in VMEM, and only the [S, D] context tile goes back to HBM.  The
un-fused XLA path materializes the f32 score tensor in HBM twice
(write after QK^T, read for softmax·V) — at S=512, H=12 that is
2·B·12·512·512·4B of HBM traffic this kernel never pays.

Encoder sizes here (S ≤ 512, D = 64) fit whole heads in VMEM
(512·512·4B scores + 3·512·64 tiles ≈ 1.3 MB of ~16 MB), so no online
softmax is needed; this is the single-block regime, not FlashAttention.

Serving-shape contract: optional additive bias [1, H, S, S] (T5's
relative-position bias, shared across batch), optional padding mask,
Sq == Sk.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp


# The kernel materializes full [S, S] f32 scores (plus [S, S] bias for
# T5) in VMEM per grid step — the single-block regime.  Past this
# sequence length the block no longer fits and compiles would fail at
# warmup, so default-on falls back to the jnp path instead.  Default
# for the PALLAS_SINGLE_BLOCK_MAX_SEQ env knob (validated range in
# ``single_block_max_seq``; mirrored by ServiceConfig so a typo'd
# value fails at boot).
PALLAS_SINGLE_BLOCK_MAX_SEQ = 512


def single_block_max_seq() -> int:
    """The PALLAS_SINGLE_BLOCK_MAX_SEQ knob, range-checked.  Raises
    ``ValueError`` on junk — a silent fallback here would flip the
    kernel off (or VMEM-overflow warmup) with no operator signal."""
    raw = os.environ.get("PALLAS_SINGLE_BLOCK_MAX_SEQ")
    if raw in (None, ""):
        return PALLAS_SINGLE_BLOCK_MAX_SEQ
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(
            f"PALLAS_SINGLE_BLOCK_MAX_SEQ={raw!r} is not an integer"
        ) from None
    if not 64 <= v <= 8192:
        raise ValueError(
            f"PALLAS_SINGLE_BLOCK_MAX_SEQ={v} outside [64, 8192] — the "
            f"single-block VMEM regime cannot hold more"
        )
    return v


def use_pallas_attention(max_seq: int | None = None) -> bool:
    """Default ON for TPU serving; USE_PALLAS_ATTENTION=0 disables.

    The kernel is checked against the jnp path at every serving seq
    bucket in interpret mode (tests/test_ops.py) and compiled for the
    v5e at BERT-base / T5-small widths (tests/test_chip_compile.py);
    chip_smoke.py compares the two paths on the chip.  Serving call
    sites only — no VJP, so training/tp consumers stay on jnp.

    ``max_seq`` is the largest configured seq bucket: beyond
    ``PALLAS_SINGLE_BLOCK_MAX_SEQ`` (single-block VMEM regime) the
    default flips off so raising SEQ_BUCKETS never turns into a
    VMEM-overflow compile failure at warmup.  USE_PALLAS_ATTENTION=1
    forces the kernel on regardless (operator overrides the guard) and
    raises off-TPU: the kernel has no CPU lowering, and an explicit
    request that quietly served the jnp path would hide which path a
    run measured.
    """
    env = os.environ.get("USE_PALLAS_ATTENTION", "").lower()
    if env in ("0", "false", "no"):
        return False
    on_tpu = jax.default_backend() == "tpu"
    if env in ("1", "true", "yes"):
        if not on_tpu:
            raise RuntimeError(
                "USE_PALLAS_ATTENTION=1 but the backend is "
                f"{jax.default_backend()!r}: the fused attention kernel "
                "only lowers on TPU (unset the knob to follow the backend)"
            )
        return True
    if max_seq is not None and max_seq > single_block_max_seq():
        return False
    return on_tpu


def _attn_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, *, scale: float):
    # Block shapes: q/k/v [1, 1, S, D]; mask [1, 1, S]; o [1, 1, S, D].
    _attn_body(q_ref, k_ref, v_ref, mask_ref, None, o_ref, scale=scale)


def _attn_kernel_bias(q_ref, k_ref, v_ref, mask_ref, bias_ref, o_ref, *, scale: float):
    # As _attn_kernel plus an additive [1, 1, S, S] bias block (one head
    # of the shared rel-pos bias); bias also stays VMEM-resident.
    _attn_body(q_ref, k_ref, v_ref, mask_ref, bias_ref, o_ref, scale=scale)


def _attn_body(q_ref, k_ref, v_ref, mask_ref, bias_ref, o_ref, *, scale: float):
    q = q_ref[0, 0].astype(jnp.float32)  # [S, D]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0]
    scores = jax.lax.dot_general(
        q, k,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # [S, S]
    if bias_ref is not None:
        scores = scores + bias_ref[0, 0].astype(jnp.float32)
    mask = mask_ref[0]  # [1, S] int32, 1 = keep (key-side padding mask)
    scores = jnp.where(mask[0][None, :] != 0, scores, jnp.float32(-1e9))
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    ctx = jax.lax.dot_general(
        probs, v,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[0, 0] = ctx.astype(o_ref.dtype)


def _decode_body(q_ref, k_ref, v_ref, ks_ref, vs_ref, mask_ref, o_ref, *,
                 scale: float, kvh: int):
    # Blocks: q/o [1, KVH, R, D] (R = GQA group width), k/v
    # [1, T, KVH, D], scales (int8 path) [1, T, KVH], mask [1, 1, T].
    # One program = one batch row: the whole row's cache slab streams
    # HBM->VMEM exactly ONCE and the (static) kv-head loop serves
    # every query group from it — the XLA path's _repeat_kv costs one
    # cache read per QUERY head.  (Blocking the KVH axis instead would
    # need a sublane-divisible block there, which Mosaic's
    # (8, 128)-or-whole-dim rule rejects for small head counts;
    # whole-slab blocks satisfy it trivially.)
    #
    # With scale refs the payloads are int8 and dequantize IN VMEM —
    # the hypothesis test for the measured XLA kv-quant loss
    # (the pre-round BASELINE record (removed in PR 22) r4: materialized
    # int8->bf16 converts feeding the
    # cache einsums).  Scales fold into the dequantized tiles
    # ((q·k8)·ks == q·(k8·ks) exactly in real arithmetic); everything
    # stays >=2-D — Mosaic's layout inference rejects 1-D vector
    # extractions like [1,T,1,1]->[T].
    mask = mask_ref[0]  # [1, T]
    ks_all = None if ks_ref is None else ks_ref[0].astype(jnp.float32)
    vs_all = None if vs_ref is None else vs_ref[0].astype(jnp.float32)
    for g in range(kvh):
        q = q_ref[0, g].astype(jnp.float32)  # [R, D]
        k = k_ref[0, :, g].astype(jnp.float32)  # [T, D]
        if ks_all is not None:
            k = k * ks_all[:, g:g + 1]
        scores = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [R, T]
        scores = jnp.where(mask[0][None, :] != 0, scores, jnp.float32(-1e9))
        probs = jax.nn.softmax(scores, axis=-1)
        v = v_ref[0, :, g]  # [T, D]
        if vs_all is not None:
            v = v.astype(jnp.float32) * vs_all[:, g:g + 1]
            probs_t = probs
        else:
            probs_t = probs.astype(v.dtype)
        ctx = jax.lax.dot_general(
            probs_t, v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        o_ref[0, g] = ctx.astype(o_ref.dtype)


def _decode_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, *, scale: float,
                   kvh: int):
    _decode_body(q_ref, k_ref, v_ref, None, None, mask_ref, o_ref,
                 scale=scale, kvh=kvh)


def _decode_kernel_kv8(q_ref, k8_ref, ks_ref, v8_ref, vs_ref, mask_ref,
                       o_ref, *, scale: float, kvh: int):
    _decode_body(q_ref, k8_ref, v8_ref, ks_ref, vs_ref, mask_ref, o_ref,
                 scale=scale, kvh=kvh)


def _decode_kernel_v(*refs, scale: float, kvh: int, n_rep: int, d: int,
                     quant: bool, var):
    """Variant-parameterized whole-slab kernel (docs/kernel_tuning.md):
    the paged kernel's fold (``paged_attention._fold_block``) applied
    ONCE to the row's whole ``[T, KVH*D]`` slab, so every autotuner
    axis means here exactly what it means there — ``head_batched``
    scores every head in one block-diagonal MXU issue, ``native_mxu``
    feeds bf16 slabs at storage width, ``fold_scales`` keeps int8
    payloads unscaled through the dots.  The block axis
    (``blocks_per_step``) has no meaning — there is no block table.
    Refs: q ([1, KVH, R, D], or [1, H, D] as it lies when head-batched:
    its block-diagonal operand is built, and the diagonal read out, here
    in VMEM by the paged kernel's two helpers), k [1, T, KVH*D] (+
    [1, T, KVH] scales when quant), v (+ scales), mask [1, 1, T], output
    (shaped like q), m/l/acc scratch."""
    from .paged_attention import _fold_block, block_diagonal_q, diagonal_out

    it = iter(refs)
    q_ref, k_ref = next(it), next(it)
    ks_ref = next(it) if quant else None
    v_ref = next(it)
    vs_ref = next(it) if quant else None
    mask_ref, o_ref = next(it), next(it)
    m_scr, l_scr, a_scr = next(it), next(it), next(it)
    m_scr[...] = jnp.full_like(m_scr, -1e30)
    l_scr[...] = jnp.zeros_like(l_scr)
    a_scr[...] = jnp.zeros_like(a_scr)
    f32 = jnp.float32
    hb = var.head_batched
    _fold_block(
        q_ref, k_ref[0], ks_ref[0].astype(f32) if quant else None,
        v_ref[0], vs_ref[0].astype(f32) if quant else None, mask_ref[0],
        m_scr, l_scr, a_scr, scale=scale, kvh=kvh, n_rep=n_rep, d=d, var=var,
        q_diag=block_diagonal_q(q_ref[0], kvh, n_rep) if hb else None,
    )
    acc = diagonal_out(a_scr[...], kvh, n_rep) if hb else a_scr[...]
    o_ref[0] = (acc / jnp.maximum(l_scr[...], 1e-20)).astype(o_ref.dtype)


# Per-program VMEM for the whole-slab decode kernel: the raw K+V slabs,
# double-buffered by the pipeline, dominate (2·2·T·KVH·D·2B); the f32
# upcasts are one KV head's [T, D] at a time (``_decode_body``'s loop).
# Guard the auto-enable against configs whose slabs cannot fit, mirroring
# use_pallas_attention's single-block guard.  Default for the
# DECODE_KERNEL_VMEM_BUDGET_MB env knob (``decode_vmem_budget_bytes``
# validates; ServiceConfig mirrors).
DECODE_KERNEL_VMEM_BUDGET = 10 * 1024 * 1024


def decode_vmem_budget_bytes() -> int:
    """The DECODE_KERNEL_VMEM_BUDGET_MB knob in bytes, range-checked.
    Also the budget ``ops/autotune.py`` filters kernel variants
    against, so one number bounds both auto-enable and the sweep."""
    raw = os.environ.get("DECODE_KERNEL_VMEM_BUDGET_MB")
    if raw in (None, ""):
        return DECODE_KERNEL_VMEM_BUDGET
    try:
        mb = int(raw)
    except ValueError:
        raise ValueError(
            f"DECODE_KERNEL_VMEM_BUDGET_MB={raw!r} is not an integer"
        ) from None
    if not 1 <= mb <= 256:
        raise ValueError(
            f"DECODE_KERNEL_VMEM_BUDGET_MB={mb} outside [1, 256] — VMEM "
            f"is ~16 MB/core; budgets past 256 MB are fiction"
        )
    return mb * 1024 * 1024


def decode_kernel_fits(t: int, kvh: int, d: int) -> bool:
    """True when the per-program slabs of ``decode_attention`` fit the
    VMEM budget at cache width ``t``: the raw K and V payloads, each
    double-buffered (8 bytes an element at bf16 — int8 payloads + scales
    stay under it), plus ONE KV head's f32 K and V upcasts: the kernel
    upcasts ``[T, D]`` a head inside its loop, never the whole slab.
    The default kernel's ``[T, KVH, D]`` slab pads D to the 128 lanes,
    so a head of 64 costs what one of 128 does.  The v5e's compiler
    agrees: its own 16 MiB limit reads as these 8 bytes an element at 8
    and 16 KV heads of 128 (T up to 1984 and 1024) and as 16 at 4 KV
    heads of 64, and it accepts the kernel wherever this says "fits"
    (the boundaries at the default 10 MB and at 12 MB are pinned in
    tests/test_chip_compile.py)."""
    lanes = -(-d // 128) * 128
    payloads = 2 * t * kvh * lanes * 4  # K + V, double-buffered, <= 2 B each
    f32_head = 2 * t * lanes * 4
    return payloads + f32_head <= decode_vmem_budget_bytes()


@functools.partial(
    jax.jit, static_argnames=("scale", "interpret", "variant", "tp")
)
def decode_attention(
    q: jax.Array,  # [B, H, D] — one query per row (the decode step)
    k: jax.Array,  # [B, T, KVH, D] dense, or int8 payload
    v: jax.Array,  # [B, T, KVH, D]
    mask: jax.Array,  # [B, T] 1 = attend
    k_scale: jax.Array | None = None,  # [B, T, KVH, 1] -> int8 path
    v_scale: jax.Array | None = None,
    scale: float | None = None,
    interpret: bool = False,
    variant: str = "",
    tp: int = 1,
) -> jax.Array:
    """Decode-side fused attention over the KV cache; returns [B, H, D].

    Grid (B,): each program serves one batch row — its whole KV slab
    crosses HBM once (the XLA path's ``_repeat_kv`` costs one read per
    query head), and with ``k_scale``/``v_scale`` the payload crosses
    at int8 width with in-kernel dequant.  The kernel never cares where
    rows came from: cached prefixes (PREFIX_CACHE / PROMPT_PREFIX under
    QUANT_KV) are written into the slab as int8 + scale like prefill
    rows, so prefix hits ride through unchanged.  VMEM: the [T, KVH, D]
    slab + f32 copies ~= 4.6 MB at T=2048, KVH=4, D=64 — comfortable."""
    from jax.experimental import pallas as pl

    from .paged_attention import parse_variant

    if tp > 1:
        # Each shard runs this kernel over its local heads; the
        # row-parallel all-reduce lands after attn-out via sharding
        # propagation (ops/paged_attention.tp_shard_attention).
        from .paged_attention import tp_shard_attention

        opt = () if k_scale is None else (k_scale, v_scale)

        def local(q_l, kl, vl, m, *sc):
            ks, vs = sc if sc else (None, None)
            return decode_attention(
                q_l, kl, vl, m, ks, vs, scale=scale,
                interpret=interpret, variant=variant,
            )

        return tp_shard_attention(
            local, tp, q, (k, v), (mask,), opt, kvh=k.shape[2]
        )

    var = parse_variant(variant)
    b, h, d = q.shape
    _, t, kvh, _ = k.shape
    n_rep = h // kvh
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    quant = k_scale is not None
    mask3 = mask.astype(jnp.int32)[:, None, :]
    mask_spec = pl.BlockSpec((1, 1, t), lambda i: (i, 0, 0))
    if not (var.head_batched or var.native_mxu or var.fold_scales):
        # The pre-autotuner kernel, bit-identical: [1, T, KVH, D] slabs.
        qk = q.reshape(b, kvh, n_rep, d)
        q_spec = pl.BlockSpec((1, kvh, n_rep, d), lambda i: (i, 0, 0, 0))
        kv_spec = pl.BlockSpec((1, t, kvh, d), lambda i: (i, 0, 0, 0))
        kernel = functools.partial(
            _decode_kernel_kv8 if quant else _decode_kernel,
            scale=scale, kvh=kvh,
        )
        slabs, scratch = (k, v), []
    else:
        # Variant kernels take lane-dense [1, T, KVH*D] slabs (trailing
        # dims merged, a bitcast in HBM) — see _fold_block.
        from .paged_attention import softmax_scratch

        qk = q if var.head_batched else q.reshape(b, kvh, n_rep, d)
        q_spec = pl.BlockSpec(
            (1,) + qk.shape[1:], lambda i: (i,) + (0,) * (qk.ndim - 1)
        )
        kv_spec = pl.BlockSpec((1, t, kvh * d), lambda i: (i, 0, 0))
        kernel = functools.partial(
            _decode_kernel_v, scale=scale, kvh=kvh, n_rep=n_rep, d=d,
            quant=quant, var=var,
        )
        slabs = (k.reshape(b, t, kvh * d), v.reshape(b, t, kvh * d))
        scratch = softmax_scratch(
            (h, kvh * d) if var.head_batched else qk.shape[1:], jnp.float32
        )
    if not quant:
        in_specs = [q_spec, kv_spec, kv_spec, mask_spec]
        args = (qk, *slabs, mask3)
    else:
        sc_spec = pl.BlockSpec((1, t, kvh), lambda i: (i, 0, 0))
        in_specs = [q_spec, kv_spec, sc_spec, kv_spec, sc_spec, mask_spec]
        args = (
            qk, slabs[0], k_scale[..., 0], slabs[1], v_scale[..., 0], mask3
        )
    out = pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qk.shape, q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(*args)
    return out.reshape(b, h, d)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def fused_attention(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, S, H, D]
    v: jax.Array,  # [B, S, H, D]
    mask: jax.Array,  # [B, S] 1 = keep
    bias: jax.Array | None = None,  # [1, H, S, S] additive (T5 rel-pos)
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Drop-in for ``common.mha_attention(q, k, v, mask=broadcast)`` on
    the encoder self-attention shapes; returns [B, S, H, D]."""
    from jax.experimental import pallas as pl

    b, s, h, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    # [B, S, H, D] -> [B, H, S, D]: per-(b,h) tiles are contiguous for
    # the grid; XLA fuses the transposes into neighbors.
    qt, kt, vt = (jnp.transpose(x, (0, 2, 1, 3)) for x in (q, k, v))
    bhsd = pl.BlockSpec((1, 1, s, d), lambda i, j: (i, j, 0, 0))
    # TPU tiling wants the mask block's trailing dims to equal the array
    # dims, so carry it as [B, 1, S] with a (1, 1, S) block.
    mask3 = mask.astype(jnp.int32)[:, None, :]
    mask_spec = pl.BlockSpec((1, 1, s), lambda i, j: (i, 0, 0))
    if bias is None:
        kernel = functools.partial(_attn_kernel, scale=scale)
        in_specs = [bhsd, bhsd, bhsd, mask_spec]
        args = (qt, kt, vt, mask3)
    else:
        # One [S, S] head-slice of the shared bias per grid step.
        kernel = functools.partial(_attn_kernel_bias, scale=scale)
        in_specs = [
            bhsd, bhsd, bhsd, mask_spec,
            pl.BlockSpec((1, 1, s, s), lambda i, j: (0, j, 0, 0)),
        ]
        args = (qt, kt, vt, mask3, bias)
    out = pl.pallas_call(
        kernel,
        grid=(b, h),
        in_specs=in_specs,
        out_specs=bhsd,
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        interpret=interpret,
    )(*args)
    return jnp.transpose(out, (0, 2, 1, 3))
