"""Pallas decode-kernel autotuner (ISSUE 16, r21).

Five layers:

1. **Variant identity**: every variant the sweep can enumerate —
   block folds × head-batching × int8 scale folding — is
   token-identical to ``paged_attention_ref`` in interpret mode,
   including the edge shapes the grammar must survive: all-invalid
   sentinel tables, kvh=1, GQA n_rep>1, a part-filled tail block.
2. **Grammar + cost model units**: parse/validation errors surface at
   boot (bad pin, non-divisor fold), ``enumerate_variants`` prunes
   no-op axes and counts VMEM rejections, ``paged_vmem_bytes`` moves
   in the directions the axes promise.
3. **Autotuner flows**: sweep → winner installed in the
   ExecutableCache + counters move; second call is a table *hit* (no
   re-sweep); a JSON table round-trips a process restart; a pin skips
   the sweep; a pinned warm pays zero serve-time compiles.
4. **graftlint exec-cache rule**: positive / waived / clean fixtures
   for the new rule keeping serving-layer jits on the cache route.
5. **bench weather probe** (r05 regression): ``sanity_check_weather``
   rejects the impossible 0.0 probe unconditionally.
"""

from __future__ import annotations

import json
import textwrap

import numpy as np
import pytest

import jax.numpy as jnp

from mlmicroservicetemplate_tpu.ops import autotune
from mlmicroservicetemplate_tpu.ops.attention import decode_attention
from mlmicroservicetemplate_tpu.ops.paged_attention import (
    Variant,
    paged_attention_ref,
    paged_decode_attention,
    parse_variant,
)


@pytest.fixture(autouse=True)
def _fresh_autotuner():
    autotune.clear()
    yield
    autotune.clear()


def _paged_problem(b=2, kvh=2, n_rep=2, d=8, bs=4, t=4, quant=False,
                   seed=0, all_invalid=False, tail=True, dtype=jnp.float32):
    """Deterministic paged decode problem + its jnp reference (``dtype``:
    q's and a dense pool's; an int8 pool's scales stay float32)."""
    rng = np.random.default_rng(seed)
    h = kvh * n_rep
    nb_pool = t + 2
    q = jnp.asarray(rng.normal(size=(b, h, d)).astype(np.float32), dtype=dtype)
    kf = rng.normal(size=(nb_pool, bs, kvh, d)).astype(np.float32)
    vf = rng.normal(size=(nb_pool, bs, kvh, d)).astype(np.float32)
    table = np.stack(
        [rng.permutation(nb_pool)[:t] for _ in range(b)]
    ).astype(np.int32)
    valid = np.ones((b, t * bs), np.int32)
    if tail:
        valid[:, -max(bs // 2, 1):] = 0
    if all_invalid:
        table[0] = -1  # sentinel: no block mapped for this row at all
        valid[0] = 0
    ks = vs = None
    if quant:
        ksf = np.abs(kf).max(axis=3, keepdims=True) / 127.0 + 1e-6
        vsf = np.abs(vf).max(axis=3, keepdims=True) / 127.0 + 1e-6
        kf = np.clip(np.round(kf / ksf), -127, 127).astype(np.int8)
        vf = np.clip(np.round(vf / vsf), -127, 127).astype(np.int8)
        ks = jnp.asarray(ksf.astype(np.float32))
        vs = jnp.asarray(vsf.astype(np.float32))

    def pool(x):  # the pool's layout: [NB, BS, C], token dims merged
        x = x.reshape(nb_pool, bs, -1)
        return jnp.asarray(x, dtype=dtype if x.dtype == np.float32 else None)

    if quant:
        ks, vs = (jnp.asarray(x).reshape(nb_pool, bs, -1) for x in (ks, vs))
    args = (q, pool(kf), pool(vf), jnp.asarray(table), jnp.asarray(valid))
    ref = paged_attention_ref(*args, bs, k_scale=ks, v_scale=vs)
    return args, ks, vs, ref


# ---------------------------------------------------------------------------
# 1. every enumerable variant is token-identical to the reference


def _enumerable_keys(t, quant):
    keys = []
    for k in autotune.BLOCK_FOLDS:
        if t % k != 0 or k > t:
            continue
        for hb in ("", "-hb"):
            for fs in (("", "-fs") if quant else ("",)):
                keys.append(f"b{k}{hb}{fs}")
    return keys


@pytest.mark.parametrize("quant", [False, True])
def test_every_variant_matches_reference(quant):
    args, ks, vs, ref = _paged_problem(t=4, quant=quant)
    for vkey in _enumerable_keys(4, quant):
        got = paged_decode_attention(
            *args, 4, k_scale=ks, v_scale=vs, interpret=True, variant=vkey
        )
        # fs reassociates the scale multiply (same products, different
        # order) — rtol, not bit-equality, is the honest pin there.
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-6, atol=2e-5,
            err_msg=f"variant {vkey!r} diverged from reference",
        )


def test_default_variant_is_bit_identical_to_empty_key():
    """"" and "b1" are the same (pre-autotuner) kernel, bitwise."""
    args, ks, vs, _ = _paged_problem()
    base = paged_decode_attention(*args, 4, interpret=True, variant="")
    b1 = paged_decode_attention(*args, 4, interpret=True, variant="b1")
    np.testing.assert_array_equal(np.asarray(base), np.asarray(b1))


@pytest.mark.parametrize("vkey", ["b1", "b2-hb", "b4"])
def test_all_invalid_row_stays_finite(vkey):
    """A stream whose whole table is the -1 sentinel (admitted but not
    yet prefilled) must produce finite output — the no-pad-block design
    exists exactly so folded variants cannot read a phantom block.  No
    program of such a row is live: it ends at acc = 0, l = 0 and writes
    zeros (the reference's mean of a clamped block is as arbitrary; both
    are discarded); the row beside it is untouched."""
    args, ks, vs, ref = _paged_problem(all_invalid=True)
    got = np.asarray(
        paged_decode_attention(*args, 4, interpret=True, variant=vkey)
    )
    assert np.isfinite(got).all()
    assert (got[0] == 0).all()
    np.testing.assert_allclose(got[1:], np.asarray(ref)[1:], rtol=2e-6, atol=2e-5)


# Ragged rows: what a serving table looks like.  Keys a row (BS = 4, the
# table 16 entries wide, allocated one block past the keys, the rest the
# sentinel NB): one block; a part-filled tail block; the whole table; a
# freed slot (all sentinel, the last tenant's mask still set); a window
# view (dead keys at its head and its tail); no valid key at all.
_RAGGED = {"one_block": (0, 3), "part_tail": (0, 14), "full": (0, 64),
           "freed": None, "window": (9, 22), "no_key": (0, 0)}
_RAGGED_T, _RAGGED_BS = 16, 4


def _ragged_problem(quant: bool, t: int = _RAGGED_T, rows=_RAGGED, seed=5,
                    **shape):
    """``(args, ks, vs, live)``: the rows of ``rows`` over a pool of
    ``64`` blocks, in a table ``t`` entries wide; ``live`` marks the rows
    that hold a key.  The same rows get the same blocks at every ``t``.
    ``shape``: ``_paged_problem``'s ``kvh`` / ``n_rep`` / ``d`` / ``dtype``."""
    bs, nb = _RAGGED_BS, 64
    base, ks, vs, _ = _paged_problem(
        b=len(rows), t=nb - 2, bs=bs, quant=quant, seed=seed, **shape
    )
    q, kp, vp = base[0], base[1], base[2]
    rng = np.random.default_rng(seed)
    table = np.full((len(rows), t), nb, np.int32)
    valid = np.zeros((len(rows), t * bs), np.int32)
    for r, span in enumerate(rows.values()):
        if span is None:
            valid[r, : 5 * bs] = 1  # stale: nothing clears a freed slot's mask
            continue
        lo, hi = span
        nblk = min(hi // bs + 1, t)
        table[r, :nblk] = rng.permutation(nb)[:nblk]
        valid[r, lo:hi] = 1
    live = np.asarray([s is not None and s[1] > s[0] for s in rows.values()])
    return (q, kp, vp, jnp.asarray(table), jnp.asarray(valid)), ks, vs, live


def _variant_cases():
    return [
        pytest.param(quant, vkey, id=f"{'int8' if quant else 'dense'}-{vkey}")
        for quant in (False, True)
        for vkey in _enumerable_keys(_RAGGED_T, quant)
    ]


@pytest.mark.parametrize("quant,vkey", _variant_cases())
def test_ragged_rows_match_reference(quant, vkey):
    """Every enumerated variant on ragged rows: a live row reads the
    reference's answer whatever lies past (or before) its keys, a row
    with no live program reads zeros."""
    args, ks, vs, live = _ragged_problem(quant)
    ref = np.asarray(paged_attention_ref(*args, _RAGGED_BS, k_scale=ks, v_scale=vs))
    got = np.asarray(paged_decode_attention(
        *args, _RAGGED_BS, k_scale=ks, v_scale=vs, interpret=True, variant=vkey
    ))
    np.testing.assert_allclose(got[live], ref[live], rtol=2e-6, atol=2e-5)
    assert (got[~live] == 0).all()


@pytest.mark.parametrize("quant,vkey", _variant_cases())
def test_live_rows_do_not_see_the_tables_width(quant, vkey):
    """The property the live bounds rest on: a row's output is bit for
    bit the same in a table padded with sentinels as in one exactly as
    wide as the longest row — entries past a stream's last key add no
    program that changes m, l or acc."""
    rows = {k: v for k, v in _RAGGED.items() if k != "full"}  # longest: 22 keys
    wide = _ragged_problem(quant, t=_RAGGED_T, rows=rows)
    snug = _ragged_problem(quant, t=8, rows=rows)
    assert np.array_equal(np.asarray(wide[0][3])[:, :8], np.asarray(snug[0][3]))

    def run(problem):
        args, ks, vs, _ = problem
        return np.asarray(paged_decode_attention(
            *args, _RAGGED_BS, k_scale=ks, v_scale=vs, interpret=True,
            variant=vkey,
        ))

    np.testing.assert_array_equal(run(wide), run(snug))


def test_live_programs_need_a_valid_key_in_a_real_block():
    """The range is read from BOTH operands: the mask alone makes a freed
    slot look as long as its last tenant, the table alone a live row a
    block longer than it is."""
    from mlmicroservicetemplate_tpu.ops.paged_attention import live_programs

    args, _, _, _ = _ragged_problem(False)
    table, valid = args[3], args[4]
    for k, want in [
        (1, [(0, 0), (0, 3), (0, 15), (1, 0), (2, 5), (1, 0)]),
        (4, [(0, 0), (0, 0), (0, 3), (1, 0), (0, 1), (1, 0)]),
        (16, [(0, 0), (0, 0), (0, 0), (1, 0), (0, 0), (1, 0)]),
    ]:
        got = np.asarray(live_programs(table, valid, 64, _RAGGED_BS, k))
        assert [tuple(r) for r in got.tolist()] == want, k


@pytest.mark.parametrize("kvh,n_rep", [(1, 4), (2, 1), (2, 4)])
def test_variant_identity_across_head_layouts(kvh, n_rep):
    """kvh=1 (max GQA), n_rep=1 (MHA — the gpt corner) and a wide GQA
    repeat all hold across the fold/head-batch grammar."""
    args, ks, vs, ref = _paged_problem(kvh=kvh, n_rep=n_rep, seed=3)
    for vkey in ("b1", "b2", "b4-hb"):
        got = paged_decode_attention(*args, 4, interpret=True, variant=vkey)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-6, atol=2e-5,
            err_msg=f"kvh={kvh} n_rep={n_rep} variant={vkey}",
        )


def test_slab_decode_variants_match_reference():
    b, t, kvh, n_rep, d = 2, 16, 2, 2, 8
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(b, kvh * n_rep, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, t, kvh, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, t, kvh, d)).astype(np.float32))
    mask = np.ones((b, t), np.int32)
    mask[:, -3:] = 0
    mask = jnp.asarray(mask)
    ref = decode_attention(q, k, v, mask, interpret=True, variant="")
    got = decode_attention(q, k, v, mask, interpret=True, variant="b1-hb")
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-6, atol=2e-5
    )


# The head-batched kernels lay out their own q (PR 59): q and the output
# cross as [B, H, D], the block-diagonal operand is built and its diagonal
# read out in VMEM (``block_diagonal_q`` / ``diagonal_out``).  Head layouts:
# Mistral's, OLMoE's (MHA: a row a group), a wide repeat, half-tile heads
# (the default Llama's D = 64) and Jamba's one KV head (the operand IS q).
_HB_SHAPES = [(8, 4, 128), (16, 1, 128), (2, 16, 128), (4, 8, 64), (1, 20, 128)]
#: (id, q's dtype, int8 pool, the variant's flags)
_HB_MODES = [
    ("f32", jnp.float32, False, ""), ("bf16", jnp.bfloat16, False, ""),
    ("bf16-nat", jnp.bfloat16, False, "-nat"),
    ("f32-int8", jnp.float32, True, ""), ("f32-int8-fs", jnp.float32, True, "-fs"),
    ("bf16-int8", jnp.bfloat16, True, ""),
    ("bf16-int8-fs", jnp.bfloat16, True, "-fs"),
]


def _hb_cases(folds):
    return [
        pytest.param(shape, mode, k, id=f"G{shape[0]}R{shape[1]}D{shape[2]}-"
                                        f"{mode[0]}-b{k}")
        for shape in _HB_SHAPES for mode in _HB_MODES for k in folds
    ]


def _hb_tol(dtype):
    """(against the f32 reference, against the other layout of the same
    fold): bf16 outputs differ by a rounding of the last bit at most."""
    if dtype == jnp.bfloat16:
        return dict(rtol=3e-2, atol=3e-2), dict(rtol=1e-2, atol=1e-2)
    return dict(rtol=1e-5, atol=2e-5), dict(rtol=1e-5, atol=2e-5)


@pytest.mark.parametrize("kvh,n_rep,d", _HB_SHAPES)
def test_the_two_layout_helpers_element_for_element(kvh, n_rep, d):
    """``block_diagonal_q`` is the operand XLA used to build around the
    call — head h's vector in its group's lane slice, zeros elsewhere —
    and ``diagonal_out`` takes each head's ``[D]`` back out of its group's
    slice, bit for bit (selects only: no arithmetic touches a value)."""
    from mlmicroservicetemplate_tpu.ops.paged_attention import (
        block_diagonal_q,
        diagonal_out,
    )

    h = kvh * n_rep
    rng = np.random.default_rng(kvh)
    q = rng.normal(size=(h, d)).astype(np.float32)
    want = np.zeros((h, kvh, d), np.float32)
    want[np.arange(h), np.arange(h) // n_rep] = q
    got = np.asarray(block_diagonal_q(jnp.asarray(q, jnp.bfloat16), kvh, n_rep))
    np.testing.assert_array_equal(
        got, np.asarray(jnp.asarray(want.reshape(h, kvh * d), jnp.bfloat16)))
    acc = rng.normal(size=(h, kvh, d)).astype(np.float32)  # off-diagonal: garbage
    acc[0, 0, 0] = -0.0
    out = np.asarray(diagonal_out(jnp.asarray(acc.reshape(h, kvh * d)), kvh, n_rep))
    np.testing.assert_array_equal(out, acc[np.arange(h), np.arange(h) // n_rep])
    assert np.signbit(out[0, 0])


@pytest.mark.parametrize("shape,mode,fold", _hb_cases((1, 4)))
def test_head_batched_paged_kernel_lays_out_its_own_q(shape, mode, fold):
    """Every ``-hb`` variant on ragged rows — a whole table, a part-filled
    tail, a window view's dead head, a freed slot, no key at all — reads
    the reference's answer and the per-group variant's of the same fold;
    a row with no live key reads zeros."""
    kvh, n_rep, d = shape
    _, dtype, quant, flags = mode
    args, ks, vs, live = _ragged_problem(
        quant, kvh=kvh, n_rep=n_rep, d=d, dtype=dtype)
    ref = np.asarray(paged_attention_ref(
        *args, _RAGGED_BS, k_scale=ks, v_scale=vs), np.float32)

    def run(vkey):
        return np.asarray(paged_decode_attention(
            *args, _RAGGED_BS, k_scale=ks, v_scale=vs, interpret=True,
            variant=vkey), np.float32)

    got = run(f"b{fold}-hb{flags}")
    assert got.shape == (len(live), kvh * n_rep, d)
    to_ref, to_twin = _hb_tol(dtype)
    np.testing.assert_allclose(got[live], ref[live], **to_ref)
    np.testing.assert_allclose(got, run(f"b{fold}{flags}"), **to_twin)
    assert (got[~live] == 0).all()


@pytest.mark.parametrize("shape,mode,fold", _hb_cases((1,)))
def test_head_batched_slab_kernel_lays_out_its_own_q(shape, mode, fold):
    """The whole-slab kernel takes the same two helpers: every ``-hb``
    variant against the jnp reference and the per-group variant, a row
    with a dead head among the rows."""
    kvh, n_rep, d = shape
    _, dtype, quant, flags = mode
    t = 24
    (q, k, v, _, ks, vs), _ = autotune._probe(  # the sweep's own slab problem
        "decode", b=3, kvh=kvh, n_rep=n_rep, d=d, bs=t, t=t,
        dtype=jnp.dtype(dtype).name, quant=quant, seed=11)
    mask = np.ones((3, t), np.int32)
    mask[0, -5:] = 0
    mask[1, :9] = 0  # a window view: dead keys at the head
    mask[1, 20:] = 0
    mask = jnp.asarray(mask)
    ref = np.asarray(autotune._slab_ref(q, k, v, mask, ks, vs), np.float32)

    def run(vkey):
        return np.asarray(decode_attention(
            q, k, v, mask, ks, vs, interpret=True, variant=vkey), np.float32)

    got = run(f"b{fold}-hb{flags}")
    to_ref, to_twin = _hb_tol(dtype)
    np.testing.assert_allclose(got, ref, **to_ref)
    np.testing.assert_allclose(got, run(f"b{fold}{flags}"), **to_twin)


#: The latent kernel's Mosaic module at DeepSeek-V2's serving shapes
#: (32 rows x 128 heads x 640 lanes, 392 table entries), a digest a
#: variant, taken on PR 58's tree: ``tools.lowered_text.kernel_texts``
#: prints a module without locations, so the digest is of the program
#: and not of the lines its source stood on.
_LATENT_KERNEL_DIGESTS = {
    "b8": "ecd03b47ef0646ace157c7996a69de6461eb0bb4dc0a66f9c35d580e148df9fc",
    "b8-nat": "b4ce8c460b723ea74a6b820b0c2415a59d5d4bd09f8f8980f49453e59cc0c893",
    "b56-nat": "c6cf1e8c08b5d0f718943ef6cac6d8bd718febcd27fce94bd2c21a394c81fc9e",
}


@pytest.mark.parametrize("vkey", list(_LATENT_KERNEL_DIGESTS))
def test_the_latent_kernel_lowers_as_it_did(vkey):
    """One KV head: the block-diagonal operand IS q, so the kernel body
    that builds it elsewhere (PR 59) must leave the latent kernel the
    program it was — no scratch, no mask, no read-out."""
    import jax

    from mlmicroservicetemplate_tpu.ops.paged_attention import (
        latent_decode_attention,
    )
    from tools.lowered_text import digest, kernel_texts

    b, h, c, v, bs, t = 32, 128, 640, 512, 16, 392
    shape = jax.ShapeDtypeStruct
    with jax.default_matmul_precision(None):  # serving's, not conftest's
        lowered = jax.jit(
            lambda q, pool, tbl, valid: latent_decode_attention(
                q, pool, tbl, valid, bs, v, 0.1, variant=vkey)
        ).trace(
            shape((b, h, c), jnp.bfloat16),
            shape((b * t, bs, c), jnp.bfloat16),
            shape((b, t), jnp.int32), shape((b, t * bs), jnp.int32),
        ).lower(lowering_platforms=("tpu",)).as_text()
    (kernel,) = kernel_texts(lowered)
    assert "latent_decode_attention" in kernel
    assert digest(kernel) == _LATENT_KERNEL_DIGESTS[vkey]


# ---------------------------------------------------------------------------
# 2. grammar + cost model


def test_parse_variant_grammar():
    assert parse_variant("") == Variant(1, False, False, False)
    assert parse_variant("b1") == Variant(1, False, False, False)
    v = parse_variant("b4-hb-fs")
    assert (v.blocks_per_step, v.head_batched, v.fold_scales) == (4, True, True)
    assert parse_variant("b2-accbf16").acc_dtype == "bf16"
    with pytest.raises(ValueError):
        parse_variant("b0")
    with pytest.raises(ValueError):
        parse_variant("b2-warp")  # unknown axis token


def test_nondivisor_fold_rejected_at_call():
    args, *_ = _paged_problem(t=4)
    with pytest.raises(ValueError, match="divide"):
        paged_decode_attention(*args, 4, interpret=True, variant="b3")


def test_pin_validated_at_ensure_tuned():
    with pytest.raises(ValueError, match="does not divide"):
        autotune.ensure_tuned(
            "paged_decode", None, None, b=1, kvh=1, n_rep=1, d=8,
            block_size=4, t=4, interpret=True, pin="b3", table_path=None,
        )
    with pytest.raises(ValueError):
        autotune.ensure_tuned(
            "paged_decode", None, None, b=1, kvh=1, n_rep=1, d=8,
            block_size=4, t=4, interpret=True, pin="junk", table_path=None,
        )


def test_enumerate_prunes_noop_axes():
    # f32 dense: no nat, no fs; folds are divisors of t only.
    vs = autotune.enumerate_variants(
        "paged_decode", t=6, bs=4, kvh=2, d=8, n_rep=2,
        dtype="float32", quant=False, budget=1 << 30,
    )
    keys = {v.key() for v in vs}
    assert keys == {"b1", "b1-hb", "b2", "b2-hb"}  # 4,8 don't divide 6
    # int8: fs doubles the set; nat still absent (quantized payloads).
    vq = autotune.enumerate_variants(
        "paged_decode", t=2, bs=4, kvh=2, d=8, n_rep=2,
        dtype="bfloat16", quant=True, budget=1 << 30,
    )
    kq = {v.key() for v in vq}
    assert kq == {"b1", "b1-fs", "b1-hb", "b1-hb-fs",
                  "b2", "b2-fs", "b2-hb", "b2-hb-fs"}
    # bf16 dense: nat appears, fs doesn't.
    vb = autotune.enumerate_variants(
        "slab_decode", t=8, bs=0, kvh=2, d=8, n_rep=2,
        dtype="bfloat16", quant=False, budget=1 << 30,
    )
    assert {v.key() for v in vb} == {"b1", "b1-hb", "b1-nat", "b1-hb-nat"}
    # accbf16 is never enumerated anywhere.
    assert not any("accbf16" in v.key() for v in vs + vq + vb)


def test_vmem_model_directions():
    base = dict(bs=16, kvh=4, d=64, n_rep=2, payload_bytes=2, quant=False)
    b1 = autotune.paged_vmem_bytes(Variant(1, False, False, False), **base)
    b4 = autotune.paged_vmem_bytes(Variant(4, False, False, False), **base)
    assert b4 > b1  # more blocks per step = more VMEM
    nat = autotune.paged_vmem_bytes(Variant(1, False, True, False), **base)
    assert nat < b1  # native width skips the f32 upcast copies
    acc = autotune.paged_vmem_bytes(
        Variant(1, False, False, False, acc_dtype="bf16"), **base
    )
    assert acc < b1  # halved scratch


def test_enumerate_counts_vmem_rejections():
    before = autotune.stats()["counts"]["reject_vmem"]
    vs = autotune.enumerate_variants(
        "paged_decode", t=8, bs=16, kvh=4, d=64, n_rep=2,
        dtype="float32", quant=False, budget=100_000,  # tiny budget
    )
    after = autotune.stats()["counts"]["reject_vmem"]
    assert after > before
    assert all(
        autotune.paged_vmem_bytes(
            v, bs=16, kvh=4, d=64, n_rep=2, payload_bytes=4, quant=False
        ) <= 100_000
        for v in vs
    )


def test_tune_key_is_shape_only():
    """The key has no model/replica component — two bundles with the
    same decode shape share one tuning entry (the λScale property)."""
    k = autotune.tune_key("paged_decode", b=2, kvh=2, n_rep=2, d=8,
                          block_size=4, t=4, dtype="float32", quant=False)
    assert k == "paged_decode.r2/B2-G2-R2-D8-bs4-T4-float32"
    kq = autotune.tune_key("paged_decode", b=2, kvh=2, n_rep=2, d=8,
                           block_size=4, t=4, dtype="float32", quant=True)
    assert kq.endswith("-q8") and kq != k
    # the slab kind carries the revision too; the latent kernel, which
    # has not changed, keeps the keys its tables hold
    assert autotune.tune_key("decode", b=2, kvh=2, n_rep=2, d=8, block_size=0,
                             t=8, dtype="float32", quant=False
                             ).startswith("decode.r2/")
    assert autotune.tune_key(
        "latent_decode", b=32, kvh=1, n_rep=128, d=640, block_size=16, t=392,
        dtype="bfloat16", quant=False,
    ) == "latent_decode/B32-G1-R128-D640-bs16-T392-bfloat16"


# ---------------------------------------------------------------------------
# 3. autotuner flows


class _Bundle:
    name = "autotune-test"


_SHAPE = dict(b=2, kvh=2, n_rep=2, d=8, block_size=4, t=4)


def test_sweep_then_hit_then_lookup():
    winner = autotune.ensure_tuned(
        "paged_decode", _Bundle(), None, **_SHAPE,
        interpret=True, table_path=None,
    )
    c = autotune.stats()["counts"]
    assert c["sweeps"] == 1 and c["installs"] == 1 and c["hits"] == 0
    assert c["timed"] == c["candidates"] > 1  # all candidates verified
    assert c["reject_verify"] == 0 and c["reject_error"] == 0
    # the winner is a legal enumerable variant for this shape
    assert parse_variant(winner).blocks_per_step in (1, 2, 4)
    # second call: table hit, no second sweep
    again = autotune.ensure_tuned(
        "paged_decode", _Bundle(), None, **_SHAPE,
        interpret=True, table_path=None,
    )
    c = autotune.stats()["counts"]
    assert again == winner and c["sweeps"] == 1 and c["hits"] == 1
    # trace-time resolution sees the same winner; unknown shape -> ""
    assert autotune.lookup(
        "paged_decode", **_SHAPE, dtype="float32", quant=False
    ) == winner
    assert autotune.lookup(
        "paged_decode", **{**_SHAPE, "t": 8}, dtype="float32", quant=False
    ) == ""


def test_winner_installed_in_executable_cache():
    from mlmicroservicetemplate_tpu.runtime import compile_cache as cc

    cc.clear()
    bundle = _Bundle()  # one bundle object, like one serving process
    try:
        autotune.ensure_tuned(
            "paged_decode", bundle, None, **_SHAPE,
            interpret=True, table_path=None,
        )
        assert cc.cache_kinds().get("paged_decode_kernel") == 1
        # the same key re-resolved does NOT mint a second entry
        autotune.ensure_tuned(
            "paged_decode", bundle, None, **_SHAPE,
            interpret=True, table_path=None,
        )
        assert cc.cache_kinds().get("paged_decode_kernel") == 1
    finally:
        cc.clear()


def test_table_persists_across_restart(tmp_path):
    path = str(tmp_path / "tune.json")
    winner = autotune.ensure_tuned(
        "paged_decode", _Bundle(), None, **_SHAPE,
        interpret=True, table_path=path,
    )
    data = json.load(open(path))
    assert list(data["table"].values()) == [winner]
    # "restart": fresh process state, same table file -> hit, no sweep
    autotune.clear()
    again = autotune.ensure_tuned(
        "paged_decode", _Bundle(), None, **_SHAPE,
        interpret=True, table_path=path,
    )
    c = autotune.stats()["counts"]
    assert again == winner and c["sweeps"] == 0 and c["hits"] == 1


@pytest.mark.parametrize("pin", [None, "b2-hb"], ids=["swept", "pinned"])
def test_a_winner_timed_on_the_old_kernel_is_not_served(tmp_path, pin):
    """The table file outlives a checkout's code (the compile-cache
    directory): an entry under the key the kernel had BEFORE it laid out
    its own q — timed with XLA's layout around the call — answers nothing
    now.  The new kernel sweeps once and both entries stay in the file;
    a pinned ``PALLAS_VARIANT`` is served whatever the file holds."""
    path = str(tmp_path / "tune.json")
    new = autotune.tune_key("paged_decode", **_SHAPE, dtype="float32",
                            quant=False)
    old = new.replace("paged_decode.r2/", "paged_decode/")
    assert old != new
    with open(path, "w") as f:
        json.dump({"version": 1, "table": {old: "b4-hb"}}, f)
    got = autotune.ensure_tuned(
        "paged_decode", _Bundle(), None, **_SHAPE,
        interpret=True, table_path=path, pin=pin,
    )
    c = autotune.stats()["counts"]
    assert c["hits"] == 0
    assert autotune.lookup("paged_decode", **_SHAPE, dtype="float32",
                           quant=False) == got
    if pin:
        assert got == pin and c["pins"] == 1 and c["sweeps"] == 0
        return
    assert c["sweeps"] == 1
    table = json.load(open(path))["table"]
    assert table == {old: "b4-hb", new: got}


def test_corrupt_table_is_nonfatal(tmp_path):
    path = str(tmp_path / "tune.json")
    with open(path, "w") as f:
        f.write("{not json")
    winner = autotune.ensure_tuned(
        "paged_decode", _Bundle(), None, **_SHAPE,
        interpret=True, table_path=path,
    )
    c = autotune.stats()["counts"]
    assert winner and c["persist_errors"] >= 1 and c["sweeps"] == 1
    # the sweep's rewrite leaves a valid table behind
    assert json.load(open(path))


def test_pin_skips_sweep_and_zero_serve_compiles():
    from mlmicroservicetemplate_tpu.runtime.compile_cache import CompileWindow

    vkey = autotune.ensure_tuned(
        "paged_decode", _Bundle(), None, **_SHAPE,
        interpret=True, pin="b2-hb", table_path=None,
    )
    c = autotune.stats()["counts"]
    assert vkey == "b2-hb" and c["pins"] == 1 and c["sweeps"] == 0
    # warm the installed executable once, then serving-shaped calls
    # must not compile: the r19 invariant extended to tuned kernels.
    args, ks, vs, ref = _paged_problem()
    from mlmicroservicetemplate_tpu.runtime.compile_cache import (
        shared_executable,
    )

    key = autotune.tune_key("paged_decode", **_SHAPE,
                            dtype="float32", quant=False)
    import jax

    fn = shared_executable(
        "paged_decode_kernel", _Bundle(), None,
        lambda: jax.jit(lambda *a: paged_decode_attention(
            *a, 4, interpret=True, variant=vkey)),
        statics=(key, vkey),
    )
    out = fn(*args)  # warm trace
    with CompileWindow() as w:
        out2 = fn(*args)
    assert w.compiles == 0
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-6, atol=2e-5
    )


def test_sweep_records_timings_for_ab():
    """/status.decode.autotune serves per-variant µs out of stats() —
    the sweep must journal them."""
    autotune.ensure_tuned(
        "paged_decode", _Bundle(), None, **_SHAPE,
        interpret=True, table_path=None,
    )
    key = autotune.tune_key("paged_decode", **_SHAPE,
                            dtype="float32", quant=False)
    sweep = autotune.stats()["sweeps"][key]
    per = sweep["per_call_us"]
    assert sweep["winner"] in per and "b1" in per
    assert all(us > 0 for us in per.values())


# ---------------------------------------------------------------------------
# 4. graftlint exec-cache rule


def _lint(src: str, rel: str = "mlmicroservicetemplate_tpu/engine/x.py"):
    from tools.graftlint import lint_source

    return lint_source(textwrap.dedent(src), rel, "exec-cache")


def _unwaived(fs):
    return [f for f in fs if not f.waived]


def test_exec_cache_positive_hit():
    fs = _lint("""
        import jax

        def warm_thing(self):
            self._fn = jax.jit(lambda x: x + 1)
    """)
    assert len(_unwaived(fs)) == 1


def test_exec_cache_builder_lambda_clean():
    fs = _lint("""
        import jax

        def warm_thing(self):
            self._fn = self._shared_jit(
                "chunk", lambda: jax.jit(step), statics=(self.kernel_variant,)
            )
            other = shared_executable("k", b, r, lambda: jax.jit(f))
    """)
    assert _unwaived(fs) == []


def test_exec_cache_waiver_and_scope():
    fs = _lint("""
        import jax

        def probe(self):
            # graftlint: uncached-jit(one-shot boot probe, never re-traced)
            return jax.jit(lambda x: x)(1)
    """)
    assert _unwaived(fs) == []
    # out of scope: ops/ and models/ build kernels freely
    fs = _lint(
        "import jax\nf = jax.jit(lambda x: x)\n",
        rel="mlmicroservicetemplate_tpu/ops/y.py",
    )
    assert fs == []
