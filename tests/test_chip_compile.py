"""The serving kernels compile for the chip — asked of the chip's own
compiler, without the chip.

libtpu is installed here and compiles for a *described* TPU v5e
(``topologies.get_topology_desc``), so every case below raises exactly
what the attached chip's compiler would raise: a block shape Mosaic's
(8, 128) tiling rule refuses, a kernel that wants more VMEM than it
may take.  Interpret mode (every other kernel test in this suite) sees
neither.  Nothing runs and nothing is timed: a compile that passes is
not a chip run (``chip_smoke.py`` is).

This is the ONLY file that describes the chip, and it does so inside a
module-scoped fixture, never at import: one process at a time may load
libtpu, the tier-1 command runs six xdist workers that each import
every test file, and a module that touched the TPU library (or decided
which tests exist) while being imported would give the workers
different collections — xdist then runs nothing at all.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from mlmicroservicetemplate_tpu.models.llama import LlamaConfig
from mlmicroservicetemplate_tpu.ops import autotune
from mlmicroservicetemplate_tpu.ops.attention import (
    decode_attention,
    fused_attention,
)
from mlmicroservicetemplate_tpu.ops.paged_attention import (
    paged_decode_attention,
    parse_variant,
)

# Default Llama decode shapes (models/llama.py LlamaConfig; the
# continuous loop's defaults: MAX_STREAMS=8, KV_BLOCK_SIZE=16).
_CFG = LlamaConfig()
H, KVH, D = _CFG.num_heads, _CFG.num_kv_heads, _CFG.head_dim
B, BS = 8, 16
T_BLOCKS, POOL = 64, 1024  # table width / pool size of the paged cases
T_SLAB = 1024  # cache width of the whole-slab cases


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described device is written to the persistent
    # cache but cannot be read back without the chip (the next one
    # warns and recompiles): keep the cache off around this module.
    # conftest's matmul precision ("highest", for CPU goldens) is not
    # what serving compiles on the chip either.
    prev_cache = jax.config.jax_enable_compilation_cache
    prev_prec = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    jax.config.update("jax_default_matmul_precision", prev_prec)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """``chip(shape, dtype)`` -> a ShapeDtypeStruct on the first
    described v5e device, plus a per-module memo of compiled texts."""
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    spec.device_kind = topo.devices[0].device_kind
    spec.memo = {}
    return spec


def _compiled_text(chip, key, fn, *args) -> str:
    """Compile ``fn`` for the described chip (memoized per ``key``) and
    return the executable's text."""
    if key not in chip.memo:
        chip.memo[key] = jax.jit(fn).lower(*args).compile().as_text()
    return chip.memo[key]


def _paged_text(chip, quant: bool, variant: str, t: int = T_BLOCKS,
                pool: int = POOL) -> str:
    variant = parse_variant(variant).key()  # "" and "b1": one memo entry
    q = chip((B, H, D), jnp.bfloat16)
    # The pool's layout (ops/paged_attention.py): [NB, BS, KVH*D].
    payload = chip((pool, BS, KVH * D), jnp.int8 if quant else jnp.bfloat16)
    table = chip((B, t), jnp.int32)
    valid = chip((B, t * BS), jnp.int32)
    if quant:
        # Scale pools [NB, BS, KVH] ride at the compute dtype, as
        # init_paged_state allocates them.
        sc = chip((pool, BS, KVH), jnp.bfloat16)
        return _compiled_text(
            chip, ("paged", True, variant, t),
            lambda q, k, v, tb, m, ks, vs: paged_decode_attention(
                q, k, v, tb, m, BS, ks, vs, variant=variant),
            q, payload, payload, table, valid, sc, sc,
        )
    return _compiled_text(
        chip, ("paged", False, variant, t),
        lambda q, k, v, tb, m: paged_decode_attention(
            q, k, v, tb, m, BS, variant=variant),
        q, payload, payload, table, valid,
    )


def _slab_text(chip, quant: bool, variant: str, t: int = T_SLAB) -> str:
    variant = parse_variant(variant).key()
    q = chip((B, H, D), jnp.bfloat16)
    payload = chip((B, t, KVH, D), jnp.int8 if quant else jnp.bfloat16)
    mask = chip((B, t), jnp.int32)
    if quant:
        sc = chip((B, t, KVH, 1), jnp.bfloat16)
        return _compiled_text(
            chip, ("slab", True, variant, t),
            lambda q, k, v, m, ks, vs: decode_attention(
                q, k, v, m, ks, vs, variant=variant),
            q, payload, payload, mask, sc, sc,
        )
    return _compiled_text(
        chip, ("slab", False, variant, t),
        lambda q, k, v, m: decode_attention(q, k, v, m, variant=variant),
        q, payload, payload, mask,
    )


def test_described_chip_is_a_v5e(chip):
    assert "v5" in chip.device_kind.lower()


@pytest.mark.parametrize("quant,variant", [
    (False, ""), (False, "b4"), (False, "b4-hb"), (False, "b8-hb-nat"),
    (True, ""), (True, "b2-fs"), (True, "b1-hb"), (True, "b4-hb-fs"),
])
def test_paged_decode_kernel_compiles(chip, quant, variant):
    assert "tpu_custom_call" in _paged_text(chip, quant, variant)


@pytest.mark.parametrize("group", [(0, 1), (2, 3)])
def test_paged_decode_kernel_compiles_under_tp2(topo, group):
    """The kernel under ``shard_map`` (TP=2: each shard sees H=16,
    KVH=2) on a fleet's device pairs — the non-prefix pair included:
    the traced program names no device, each group's executable takes
    them from its operands."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mlmicroservicetemplate_tpu.parallel.tpserve import kv_head_spec

    mesh = Mesh(
        np.array([topo.devices[i] for i in group]).reshape(1, 2),
        ("replica", "tp"),
    )

    def spec(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*axes)))

    pool = spec((POOL, BS, KVH * D), jnp.bfloat16, *kv_head_spec(paged=True))
    compiled = jax.jit(
        lambda q, k, v, tb, m: paged_decode_attention(
            q, k, v, tb, m, BS, variant="b2-hb", tp=2)
    ).lower(
        spec((B, H, D), jnp.bfloat16, None, "tp", None), pool, pool,
        spec((B, T_BLOCKS), jnp.int32), spec((B, T_BLOCKS * BS), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("quant,variant", [
    (False, ""), (False, "b1-hb"), (True, ""), (True, "b1-hb"),
])
def test_slab_decode_kernel_compiles(chip, quant, variant):
    assert "tpu_custom_call" in _slab_text(chip, quant, variant)


@pytest.mark.parametrize("b,s,h,bias", [
    (32, 128, 12, False),  # BERT-base
    (32, 512, 12, False),
    (8, 512, 8, True),  # T5-small encoder + relative-position bias
])
def test_fused_attention_compiles(chip, b, s, h, bias):
    q = chip((b, s, h, 64), jnp.bfloat16)
    mask = chip((b, s), jnp.int32)
    if bias:
        text = _compiled_text(
            chip, ("fused", b, s, h, True),
            lambda q, k, v, m, bi: fused_attention(q, k, v, m, bi),
            q, q, q, mask, chip((1, h, s, s), jnp.float32),
        )
    else:
        text = _compiled_text(
            chip, ("fused", b, s, h, False),
            lambda q, k, v, m: fused_attention(q, k, v, m),
            q, q, q, mask,
        )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kind,quant", [
    ("paged_decode", False), ("paged_decode", True),
    ("decode", False), ("decode", True),
])
def test_every_enumerated_variant_compiles(chip, kind, quant):
    """What the autotuner may sweep (and so install) at the default
    Llama decode shapes, the chip's compiler accepts: no variant is
    refused for tiling or VMEM behind the cost model's back.  The paged
    table width is the default deployment's (512-token bucket + 64
    decode tokens at BS=16)."""
    paged = kind == "paged_decode"
    t = 36 if paged else T_SLAB
    cands = autotune.enumerate_variants(
        kind, t=t, bs=BS if paged else t, kvh=KVH, d=D, n_rep=H // KVH,
        dtype="bfloat16", quant=quant,
    )
    want = 12 if paged else 4  # folds {1,2,4} x hb x (nat | fs); hb x (nat | fs)
    assert len(cands) == want, [v.key() for v in cands]
    refused = {}
    for var in cands:
        try:
            text = (
                _paged_text(chip, quant, var.key(), t=t, pool=8 * t)
                if paged else _slab_text(chip, quant, var.key(), t=t)
            )
            assert "tpu_custom_call" in text
        except Exception as e:  # collect them all: one run, whole picture
            refused[var.key()] = str(e).splitlines()[0][:200]
    assert not refused, refused


# -- OLMoE-1B-7B shapes (cellbench/configs/olmoe-1b-7b-d8.json) -----------
# 16 heads = 16 KV heads of 128 (the paged kernel's first MHA run:
# n_rep 1), 64 streams, a 512-token bucket + 192 decode tokens at BS 16;
# 64 experts of width 1024 over d 2048, top-8.


@pytest.mark.parametrize("variant", ["", "b4-hb"])
def test_paged_decode_kernel_compiles_at_16_kv_heads(chip, variant):
    from mlmicroservicetemplate_tpu.ops.paged_attention import (
        paged_decode_attention as kernel,
    )

    b, h, t = 64, 16, 44
    q = chip((b, h, 128), jnp.bfloat16)
    pool = chip((b * t, 16, h * 128), jnp.bfloat16)
    text = _compiled_text(
        chip, ("paged-mha", variant),
        lambda q, k, v, tb, m: kernel(q, k, v, tb, m, 16, variant=variant),
        q, pool, pool, chip((b, t), jnp.int32), chip((b, t * 16), jnp.int32),
    )
    assert "tpu_custom_call" in text


#: The paged kernel's problem in each benchmark cell: slots, KV heads,
#: n_rep, table width, pool blocks (head 128, blocks of 16, bf16, the
#: cells' DECODE_KERNEL_VMEM_BUDGET_MB=12).
_KERNEL_CELLS = {
    "mistral": (64, 8, 4, 44, 3051),
    "olmoe": (64, 16, 1, 28, 1810),
    "trinity-full": (32, 4, 8, 392, 12573),
    "trinity-view": (32, 4, 8, 136, 12573),
    # 20 query heads on ONE KV head: 20 rows are no multiple of the 8 sublanes
    "jamba": (32, 1, 20, 392, 13184),
    # Phi-4-mini-flash's differential pairs: 10 KV pairs 128 wide, 4 query
    # heads a pair — the one pool (eight layers' reads) and a window layer's
    # view of its ring (40 of a row's 97 blocks; 32 rows of 97)
    "phi4flash-pool": (32, 10, 4, 392, 12545),
    "phi4flash-ring": (32, 10, 4, 40, 3104),
}


@pytest.mark.parametrize("nat", [False, True], ids=["", "nat"])
@pytest.mark.parametrize("hb", [False, True], ids=["", "hb"])
@pytest.mark.parametrize("cell,fold", [
    (c, k) for c, shape in _KERNEL_CELLS.items()
    for k in autotune.BLOCK_FOLDS if shape[3] % k == 0
])
def test_the_cells_kernels_compile_at_every_enumerated_variant(
        chip, cell, fold, hb, nat):
    """The live-bounded kernel (a row a program, the block loop and its
    copies inside) at each cell's shapes, for every variant the tuner
    enumerates there: the chip's compiler takes what
    ``autotune.paged_vmem_bytes`` admits under the cells' budget."""
    b, kvh, n_rep, t, nb = _KERNEL_CELLS[cell]
    var = autotune.Variant(fold, hb, nat)
    assert var in autotune.enumerate_variants(
        "paged_decode", t=t, bs=BS, kvh=kvh, d=128, n_rep=n_rep,
        dtype="bfloat16", quant=False, budget=12 << 20,
    )
    pool = chip((nb, BS, kvh * 128), jnp.bfloat16)
    text = _compiled_text(
        chip, ("cell-kernel", cell, var.key()),
        lambda q, k, v, tb, m: paged_decode_attention(
            q, k, v, tb, m, BS, variant=var.key()),
        chip((b, kvh * n_rep, 128), jnp.bfloat16), pool, pool,
        chip((b, t), jnp.int32), chip((b, t * BS), jnp.int32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kvh,h,nb,t,quant", [
    (8, 32, 3051, 44, False),  # mistral-7b-d8: KV_BUDGET_MB 1600, 512 + 192
    (16, 16, 1810, 28, False),  # olmoe-1b-7b-d8: 1900, 256 + 192
    (8, 32, 3051, 44, True),  # QUANT_KV=int8: payload + its scale pool
], ids=["mistral", "olmoe", "mistral-int8"])
def test_no_pool_sized_relayout_in_a_decode_step(chip, kvh, h, nb, t, quant):
    """The decode chunk in small: a scan whose carry is a layer's K and
    V pool, each step one token write through the table and one
    ``paged_decode_attention``, pools donated, at both cells' shapes.
    The chip's compiler must take the pool into the kernel as it lies
    and scatter into it in place: no ``reshape``, ``copy`` or
    ``transpose`` in the optimised HLO has a pool's element count (with
    ``[NB, BS, KVH, D]`` pools it held two ``reshape(bf16[48816,8,128]
    -> bf16[3051,16,1024])`` a step, 27-30 % of both cells' step:
    PERF.md section 6, PR 28)."""
    from mlmicroservicetemplate_tpu.models import llama as llama_mod
    from mlmicroservicetemplate_tpu.ops.paged_attention import pool_relayouts

    b, bs, d, dt = 64, 16, 128, jnp.bfloat16
    payload = chip((nb, bs, kvh * d), jnp.int8 if quant else dt)
    entry = (payload, chip((nb, bs, kvh), dt)) if quant else payload

    def chunk(k_entry, v_entry, q, k1, v1, table, valid, t0):
        def step(carry, i):
            ck, cv = carry
            pos = t0 + i
            ck = llama_mod._paged_write_kv(ck, table, pos, k1, bs, dt)
            cv = llama_mod._paged_write_kv(cv, table, pos, v1, bs, dt)
            scales = dict(k_scale=ck[1], v_scale=cv[1]) if quant else {}
            ctx = paged_decode_attention(
                q, ck[0] if quant else ck, cv[0] if quant else cv, table,
                valid, bs, variant="b4-hb", **scales)
            return (ck, cv), ctx

        return jax.lax.scan(step, (k_entry, v_entry), jnp.arange(4))

    text = jax.jit(chunk, donate_argnums=(0, 1)).lower(
        entry, entry, chip((b, h, d), dt), chip((b, kvh, d), dt),
        chip((b, kvh, d), dt), chip((b, t), jnp.int32),
        chip((b, t * bs), jnp.int32), chip((b,), jnp.int32),
    ).compile().as_text()
    assert text.count("tpu_custom_call") >= 1
    payload_n, scale_n = nb * bs * kvh * d, nb * bs * kvh
    assert not pool_relayouts(text, [payload_n])
    # The 0.8 MB scale pool the compiler may re-tile on the way in and
    # out (a `copy` in ENTRY); never in a step.
    assert not pool_relayouts(text, [payload_n, scale_n], in_loop_only=True)


#: The cells' serving shapes (cellbench/configs/*.json): LlamaConfig
#: overrides, pool blocks (KV_BUDGET_MB), table width, largest bucket.
_CELLS = {
    "mistral": (dict(vocab_size=32000, d_model=4096, num_heads=32,
                     num_kv_heads=8, num_layers=8, d_ff=14336,
                     max_position=32768), 3051, 44, 512),
    "olmoe": (dict(vocab_size=50304, d_model=2048, num_heads=16,
                   num_kv_heads=16, num_layers=8, d_ff=1024,
                   max_position=4096, num_experts=64, experts_per_token=8,
                   qk_norm=True, add_bos=False), 1811, 28, 256),
}
_CELLS["mistral-int8"] = (
    dict(_CELLS["mistral"][0], kv_quant=True), *_CELLS["mistral"][1:])
# Trinity-Mini cut to 5 layers (PR 31): a per-layer pattern, 32 slots of
# up to 6272 tokens (392 table entries; a window layer's view holds 136).
_CELLS["trinity"] = (
    dict(vocab_size=200192, d_model=2048, num_heads=32, num_kv_heads=4,
         head_dim=128, num_layers=5, d_ff=1024, num_dense_layers=1,
         d_ff_dense=6144, max_position=131072, window=2048,
         layer_types=("window", "window", "window", "full", "window"),
         num_experts=128, experts_per_token=8, num_shared_experts=1,
         router_score="sigmoid", norm_topk_prob=True, route_scale=2.826,
         router_bias=True, qk_norm="head", sandwich_norm=True, attn_gate=True,
         nope_on_full=True, mup_embed=True, add_bos=False), 12573, 392, 128)
_SLOTS = {"trinity": 32, "dsv2": 32}  # MAX_STREAMS where it is not 64
#: PREFILL_CHUNK of the cells that set one (a prompt window's queries).
_WINDOWS = {"trinity": 1024, "dsv2": 2048}


def _cell_config(cell: str) -> tuple:
    """A cell's ``_CELLS`` entry; DeepSeek-V2's from the benchmark's own
    configuration file (32 slots x 392 table entries of 16 latent rows)."""
    if cell != "dsv2":
        return _CELLS[cell]
    import json

    from cellbench import spec as bench_spec

    config = bench_spec.load_json(
        bench_spec.HERE + "/configs/deepseek-v2-ep4-d5.json")
    over = json.loads(bench_spec.service_env(config)["LLAMA_CONFIG"])
    return over, 32 * 392, 392, 128


def _serving_program(chip, cell: str, what: str, donate: bool = True,
                     windows: int = 1) -> tuple:
    """The text of the loop's own executable compiled for the described
    chip at a cell's shapes — ``what`` = the paged chunk
    (``chunk_with_done(generate_chunk_paged)``, as ``paged_chunk_fn``
    jits it), the insert of a lone start or (``insert_wave``) of a wave at
    the slot count (``programs.paged_insert``) or the prompt
    windows of ``windows`` prompts in one dispatch — and the element counts of a payload and a scale pool.  Shapes only: no
    weight is made."""
    from mlmicroservicetemplate_tpu.engine.engine import chunk_with_done
    from mlmicroservicetemplate_tpu.engine.programs import paged_insert
    from mlmicroservicetemplate_tpu.models import llama as llama_mod
    from mlmicroservicetemplate_tpu.models.gpt import PagedState
    from mlmicroservicetemplate_tpu.models.sampling import greedy_params

    over, nb, t, s_max = _cell_config(cell)
    cfg = LlamaConfig(**over, pallas_decode=True, pallas_variant="b4-hb")
    b, bs, dt, budget, steps = _SLOTS.get(cell, 64), 16, jnp.bfloat16, 192, 4
    c, kvh = cfg.num_kv_heads * cfg.head_dim, cfg.num_kv_heads
    if cfg.mla:  # one latent pool a layer, no V
        c = cfg.latent_lanes
    counts = (nb * bs * c, nb * bs * kvh)
    key = ("serving", cell, what, donate, windows)
    if key in chip.memo:
        return chip.memo[key], counts

    def on_chip(tree):
        return jax.tree.map(lambda x: chip(x.shape, x.dtype), tree)

    def pool():
        if cfg.kv_quant:
            return (jnp.zeros((nb, bs, c), jnp.int8), jnp.ones((nb, bs, kvh), dt))
        return jnp.zeros((nb, bs, c), dt)

    def batched():
        return PagedState(
            cache_k=[pool() for _ in range(cfg.num_layers)],
            cache_v=[] if cfg.mla else [pool() for _ in range(cfg.num_layers)],
            key_valid=jnp.zeros((b, t * bs), jnp.int32),
            write_idx=jnp.zeros((b,), jnp.int32),
            pos=jnp.zeros((b,), jnp.int32),
            last_token=jnp.zeros((b,), jnp.int32),
            done=jnp.ones((b,), bool),
            tokens=jnp.zeros((b, budget), jnp.int32),
            sample=greedy_params(b),
        )

    params = on_chip(jax.eval_shape(
        lambda: llama_mod.init_params(jax.random.PRNGKey(0), cfg, dtype=dt)))
    state = on_chip(jax.eval_shape(batched))
    insert = what.startswith("insert")
    donated = dict(donate_argnums=(0 if insert else 1,)) if donate else {}
    if what == "chunk":
        lowered = jax.jit(
            chunk_with_done(lambda p, s, tb, n, sample: (
                llama_mod.generate_chunk_paged(p, cfg, s, tb, n, sample))),
            static_argnums=(3, 4), **donated,
        ).lower(params, state, chip((b, t), jnp.int32), steps, False)
    elif what == "prefill":  # registry.paged_prefill_chunk_fn, as programs.paged_prefill_fn jits it
        w = _WINDOWS[cell]
        lowered = jax.jit(
            lambda p, s, rows, ids, mask, starts: llama_mod.paged_prefill_chunk(
                p, cfg, s, rows, ids, mask, starts, dtype=dt), **donated,
        ).lower(params, state, chip((windows, t), jnp.int32),
                chip((windows, w), jnp.int32), chip((windows, w), jnp.int32),
                chip((windows,), jnp.int32))
    else:
        rows = b if what == "insert_wave" else 1
        ones = jnp.ones((rows, s_max), jnp.int32)
        single = on_chip(jax.eval_shape(
            lambda p: llama_mod.generate_chunk(p, cfg, llama_mod.init_decode_state(
                p, cfg, ones, ones, budget, dtype=dt), steps, False)[0], params))
        lowered = jax.jit(
            paged_insert(bs), static_argnums=(4, 5), **donated,
        ).lower(state, single, chip((rows, t), jnp.int32),
                chip((rows,), jnp.int32), 0, s_max + steps)
    chip.memo[key] = lowered.compile().as_text()
    return chip.memo[key], counts


@pytest.mark.parametrize("what", ["chunk", "insert", "insert_wave"])
@pytest.mark.parametrize("cell", list(_CELLS))
def test_donated_state_is_not_copied_at_entry(chip, cell, what):
    """The decode state is donated (engine/streams.py's rule), so the
    loop's chunk and an insert — a lone start's, and a wave's at the slot
    count: one scatter a pool over all its rows (PR 41) — compiled at the
    cells' own shapes, alias every pool's input to its output: no ``copy`` / ``reshape`` /
    ``transpose`` of a payload pool's element count anywhere in the
    optimised program, ENTRY included.  Until PR 30 neither donated:
    ``copy(bf16[3051,16,1024])`` x 16 at the chunk's entry (1.11 / 1.45
    ms a step) and in every insert (4.9-5.8 of its 5.3-6.2 ms): PERF.md
    section 6."""
    from mlmicroservicetemplate_tpu.ops.paged_attention import pool_relayouts

    text, (payload_n, scale_n) = _serving_program(chip, cell, what)
    if what == "chunk":
        assert text.count("tpu_custom_call") >= 1
    assert "input_output_alias" in text
    assert not pool_relayouts(text, [payload_n])
    # An int8 pair's 0.8 MB scale pool is given another tiling on the
    # way in and out (a `copy` in ENTRY); never inside a step.
    assert not pool_relayouts(text, [payload_n, scale_n], in_loop_only=True)


@pytest.mark.parametrize("cell,b,h,kvh", [
    ("mistral", 64, 32, 8), ("olmoe", 64, 16, 16), ("trinity", 32, 32, 4),
])
def test_the_block_diagonal_q_stays_in_the_kernel(chip, cell, b, h, kvh):
    """The head-batched kernel lays out its own q (PR 59): the compiled
    decode chunk at the cells' shapes, ``b4-hb`` pinned, holds no array of
    the block-diagonal operand's shape ``[B, H, KVH*D]`` (nor its 4-D form
    ``[B, H, KVH, D]``) anywhere outside the kernel — q enters and the
    output leaves the call as ``[B, H, D]``.  With XLA's layout around the
    call Mistral's chunk held a ``bf16[64,32,8,128]`` broadcast-multiply,
    its reshape and the read-out's gather a layer: 0.58 of the 1.73 ms of
    its ``attn`` scope (PERF.md section 6, PR 59)."""
    import re

    text, _ = _serving_program(chip, cell, "chunk")
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
             and "paged_decode_attention" in ln.split(" = ")[0]]
    assert calls and all(
        f" = bf16[{b},{h},128]" in ln for ln in calls), calls[:1]
    wide = re.findall(
        rf"\w+\[{b},{h},(?:{kvh * 128}|{kvh},128)\]", text)
    assert not wide, sorted(set(wide))


def test_window_layers_run_the_kernel_at_their_views_width(chip):
    """Trinity's chunk at the cell's shapes holds the paged kernel at BOTH
    table widths: the full layer's rows are T = 392 entries (98 trips at
    most at K = 4), the four window layers' their view's 136 (34) —
    the row's mask rides as [B, T/K, K*BS].  The view is a gather of table
    ENTRIES: no pool moves (the parametrised case above)."""
    import re

    text, _ = _serving_program(chip, "trinity", "chunk")
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln and " = " in ln]
    masks = [re.findall(r"s32\[32,(\d+),64\]", ln) for ln in calls]
    widths = sorted(int(m[0]) for m in masks if m)
    assert widths == [34, 34, 34, 34, 98], widths


@pytest.mark.parametrize("cell,heads,windows", [
    ("trinity", 32, 1), ("dsv2", 16, 1),
    ("trinity", 32, 3),  # PREFILL_BUDGET / PREFILL_CHUNK: a boundary's dispatch
], ids=["trinity", "dsv2", "trinity-x3"])
def test_a_prompt_windows_scores_stay_on_the_chip(chip, cell, heads, windows):
    """The prompt-window executable at the cells' shapes (Trinity: 1024
    queries, 32 heads, a window layer over 3088 gathered keys and the full
    layer over the table's 6272; DeepSeek-V2: 2048 queries, 16 expanded
    heads a static block over 6272) holds the prompt-window kernel at every
    layer and NO float32 array of heads x queries x keys
    (``prefill_scores_in_hbm: []``): until PR 34 XLA wrote and re-read one
    a layer (0.4-0.8 GB each).  The ``lax.switch`` over key widths is
    gone with it: one attention a layer, no ``conditional`` there.  A batched
    dispatch (``windows`` prompts' windows, PR 36) holds the kernel once a
    row a layer and still writes every row's keys into the pool in
    place: donated, aliased, no pool-sized copy."""
    from mlmicroservicetemplate_tpu.ops.prefill_attention import scores_in_hbm

    text, (payload_n, _) = _serving_program(chip, cell, "prefill", windows=windows)
    w = _WINDOWS[cell]
    # (three windows' 24 576 routed rows x 2048 in float32, inside the
    # expert block's combine fusion, are as many elements and no score)
    assert [h for h in scores_in_hbm(text, heads * w * w) if "/mlp/" not in h] == []
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and "prefill_attention" in ln]
    assert calls, "the prompt-window kernel is not in the executable"
    if windows > 1:  # the kernel once a row wherever one window holds it
        alone, _ = _serving_program(chip, cell, "prefill")
        assert len(calls) == windows * sum(
            "tpu_custom_call" in ln and "prefill_attention" in ln
            for ln in alone.splitlines())
    # None but the expert block's of a chip's share of the experts, which
    # branches on its held rows (ops/moe.row_rungs, PR 52): DeepSeek-V2's
    # window, at most its gather, activation and combine of 4 layers (XLA
    # folds some of the two-way branches).
    assert text.count(" conditional(") <= (12 if cell == "dsv2" else 0)
    assert "input_output_alias" in text
    from mlmicroservicetemplate_tpu.ops.paged_attention import pool_relayouts

    assert not pool_relayouts(text, [payload_n])


@pytest.mark.parametrize("h,kvh,d,dt", [
    (H, KVH, D, jnp.bfloat16),  # the default widths: heads of 64 (half a lane tile)
    (32, 8, 128, jnp.float32),  # Mistral's heads past a bucket, float32
    (16, 16, 128, jnp.bfloat16),  # OLMoE's: a KV head a query head
    (20, 1, 128, jnp.bfloat16),  # Jamba's: 20 query heads on ONE KV head
    (40, 10, 128, jnp.bfloat16),  # Phi-4-mini-flash's: 10 differential pairs of 128
], ids=["d64", "f32", "n_rep1", "n_rep20", "diff_pairs"])
def test_prompt_window_kernel_compiles_at_other_widths(chip, h, kvh, d, dt):
    """Any Llama-shaped deployment with ``PREFILL_CHUNK`` past its buckets
    reaches the kernel: heads narrower than the 128 lanes (padded in the
    wrapper — Mosaic slices no 64-lane KV head out of ``[K, KVH*64]``),
    float32 operands, ``n_rep`` 1, and an ``n_rep`` that is no power of two
    (20: ``tile_sizes`` gives 32 queries a tile, the largest divisor of the
    window under 1024 // 20 that is whole sublane tiles — the halving rule
    it replaces ended at ONE query a tile, a block the compiler refuses)."""
    from mlmicroservicetemplate_tpu.ops.prefill_attention import prefill_attention

    text = _compiled_text(
        chip, ("prefill_kernel", h, kvh, d, str(dt)),
        lambda q, k, v, kp0, st, cm: prefill_attention(q, k, v, kp0, st, cm),
        chip((512, h, d), dt), chip((2048, kvh, d), dt), chip((2048, kvh, d), dt),
        chip((), jnp.int32), chip((), jnp.int32), chip((512,), jnp.int32))
    assert "tpu_custom_call" in text


def test_the_scores_reader_sees_xlas_scores(chip):
    """What the reader is for: the XLA form of one Trinity window layer
    (``prefill_attention_ref``) holds the ``[32, 1024, 3088]`` float32
    scores — so an empty list above is the kernel, not a blind spot."""
    from mlmicroservicetemplate_tpu.ops.prefill_attention import (
        prefill_attention_ref, scores_in_hbm)

    bf = jnp.bfloat16
    text = _compiled_text(
        chip, ("prefill_ref",),
        lambda q, k, v, kp0, st, cm: prefill_attention_ref(q, k, v, kp0, st, cm, 2048),
        chip((1024, 32, 128), bf), chip((3088, 4, 128), bf),
        chip((3088, 4, 128), bf), chip((), jnp.int32), chip((), jnp.int32),
        chip((1024,), jnp.int32))
    assert scores_in_hbm(text, 32 * 1024 * 1024)


@pytest.mark.parametrize("rows,g", [(3, 8), (1, 8), (3, 1)],
                         ids=["3", "1", "granite-one-group-3"])
def test_the_prompt_scans_decay_matrices_stay_on_the_chip(chip, rows, g):
    """The chunked scan of a Mamba-2 layer at Nemotron's widths (128 heads
    of 64 in 8 groups, state 128, chunks of 128) — and at Granite's, the same
    heads in ONE group, whose 128 heads the kernel tiles over its grid 16 at
    a time (a whole group's blocks would not fit the scoped VMEM: Mosaic
    refuses them here, not in a cell's boot) — over a dispatch of
    ``rows`` windows of 1024 tokens, x, B and C read from the
    convolution's ``[rows, 1024, 10240]`` bfloat16 where they lie: with
    ``kernel`` ONE Mosaic kernel named ``ssm_scan`` and no float32 array
    of ``rows * 8 * 128 * 128 * 128`` elements (the decay matrices
    ``[B, nc, G, r, Q, K]``, 201 MB at three windows, which the
    ``jax.numpy`` form writes and reads back: the reader sees them
    there)."""
    from mlmicroservicetemplate_tpu.ops import ssm
    from mlmicroservicetemplate_tpu.ops.prefill_attention import scores_in_hbm

    h, p, n, q, length = 128, 64, 128, 128, 1024
    assert ssm._kernel_fits(h, p, g, n, q, False)
    f32 = jnp.float32
    args = (chip((rows, length, h * p + 2 * g * n), jnp.bfloat16),
            chip((rows, length, h), f32), chip((h,), f32), chip((h,), f32),
            chip((rows, h, p, n), f32), chip((rows, length), jnp.int32))

    def text(kernel):
        return _compiled_text(
            chip, ("ssm_scan", rows, g, kernel),
            lambda *a: ssm.ssm_scan(*a, groups=g, state=n, chunk=q, kernel=kernel),
            *args)

    decay = rows * (length // q) * h * q * q
    fused = text(True)
    calls = [ln for ln in fused.splitlines()
             if "tpu_custom_call" in ln and "ssm_scan" in ln]
    assert len(calls) == 1 and scores_in_hbm(fused, decay) == []
    assert scores_in_hbm(text(False), decay)


@pytest.mark.parametrize("rows", [3, 1])
def test_the_selective_scan_holds_no_state_a_token(chip, rows):
    """The prompt scan of a Mamba-1 layer at Jamba2-3B's widths (5120 channels
    x 16 states, a decay for each: no matmul form) over a dispatch of ``rows``
    windows of 1024 tokens compiles for the chip as ONE loop over chunks of
    ``MAMBA1_CHUNK`` tokens and holds no float32 array of ``rows x chunk x 16
    x 5120`` elements or more: the state ``[rows, 16, 5120]`` is carried, never
    kept a token (a pair-composing ``associative_scan`` would write ``[rows,
    Q, 16, 5120]`` several times a chunk)."""
    from mlmicroservicetemplate_tpu.ops import ssm
    from mlmicroservicetemplate_tpu.ops.prefill_attention import scores_in_hbm

    ch, n, length = 5120, 16, 1024
    f32 = jnp.float32
    text = _compiled_text(
        chip, ("mamba1_scan", rows), ssm.mamba1_scan,
        chip((rows, length, ch), jnp.bfloat16), chip((rows, length, ch), f32),
        chip((n, ch), f32), chip((rows, length, n), f32),
        chip((rows, length, n), f32), chip((ch,), f32), chip((rows, n, ch), f32),
        chip((rows, length), jnp.int32))
    assert text.count(" while(") == 1 and "tpu_custom_call" not in text
    # y itself is [rows, 1024, 5120]: anything of a chunk's states is larger
    assert scores_in_hbm(text, rows * ssm.MAMBA1_CHUNK * n * ch) == [
        h for h in scores_in_hbm(text, rows * length * ch, exact=True)]


@pytest.mark.parametrize("rows,length,ch,n", [
    (3, 1024, 5120, 16), (1, 1024, 5120, 16), (2, 48, 5120, 16),
    (2, 512, 1536, 8), (1, 256, 384, 8)])
def test_the_selective_scans_kernel_compiles(chip, rows, length, ch, n):
    """With ``kernel`` the same scan is ONE Mosaic kernel named ``mamba1_scan``
    and no loop of XLA's: at Jamba2-3B's widths over a dispatch of three
    windows and of one and over a short wave (one block of 48 tokens), and at
    widths whose channel tile is 512 and 128 (a tile's lane tiles fold down
    4 sublanes, 1): the in-kernel relayout ``[8, tile] -> [8, tile / 128,
    128]`` is the chip's compiler's to take or refuse."""
    from mlmicroservicetemplate_tpu.ops import ssm

    f32 = jnp.float32
    assert ssm._mamba1_kernel_fits(ch, n, False)
    text = _compiled_text(
        chip, ("mamba1_scan", rows, length, ch, "kernel"),
        lambda *a: ssm.mamba1_scan(*a, kernel=True),
        chip((rows, length, ch), jnp.bfloat16), chip((rows, length, ch), f32),
        chip((n, ch), f32), chip((rows, length, n), f32),
        chip((rows, length, n), f32), chip((ch,), f32), chip((rows, n, ch), f32),
        chip((rows, length), jnp.int32))
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and "mamba1_scan" in ln]
    assert len(calls) == 1 and " while(" not in text


@pytest.mark.parametrize("rows", [3, 1])
def test_the_delta_rules_chunk_matrices_stay_on_the_chip(chip, rows):
    """The chunked scan of a Gated-DeltaNet layer at GigaChat3.5's widths (32
    key / 64 value heads of 128, chunks of 64) over a dispatch of ``rows``
    windows of 1024 tokens, q, k and v read from the convolution's
    ``[rows, 1024, 16384]`` bfloat16 where they lie: with ``kernel`` ONE
    Mosaic kernel named ``gdn_scan`` and no float32 array of ``rows * 1024 *
    64 * 64`` elements or more apart from ``o`` and the states (the ``[Q,
    Q]`` chunk matrices a value head — 50 MB each at three windows — and
    q / k / v repeated a value head, 100 MB each, which the ``jax.numpy``
    form writes and reads back: the reader sees them there)."""
    from mlmicroservicetemplate_tpu.ops import ssm
    from mlmicroservicetemplate_tpu.ops.prefill_attention import scores_in_hbm

    hk, hv, d, q, length = 32, 64, 128, ssm.GDN_CHUNK, 1024
    f32 = jnp.float32
    args = (chip((rows, length, (2 * hk + hv) * d), jnp.bfloat16),
            chip((rows, length, hv), f32), chip((rows, length, hv), f32),
            chip((rows, hv, d, d), f32), chip((rows, length), jnp.int32))

    def text(kernel):
        return _compiled_text(
            chip, ("gdn_scan", rows, kernel),
            lambda *a: ssm.gdn_scan(*a, kernel=kernel), *args)

    chunk_matrices = rows * length * hv * q
    fused = text(True)
    calls = [ln for ln in fused.splitlines()
             if "tpu_custom_call" in ln and "gdn_scan" in ln]
    assert len(calls) == 1
    # o [rows, 1024, 64 x 128] and the states [rows, 64, 128, 128] are the
    # kernel's results and its one state operand: nothing else is that large
    large = [ln for ln in scores_in_hbm(fused, chunk_matrices)
             if "gdn_scan" not in ln and "parameter(" not in ln]
    assert [ln for ln in large if "bitcast" not in ln and "tuple" not in ln] == []
    assert len(scores_in_hbm(text(False), chunk_matrices)) > 8


def test_the_toy_windows_scan_holds_no_decay_matrix_with_kernels_on():
    """The prompt-window executable of the toy Nemotron configuration
    (``tests/test_nemotron_serving.py``'s: 8 heads of 8 in 2 groups, state
    16, here chunks of 10), three windows of 30 tokens — sizes at which no
    other array of the program has either element count —, compiled here: with
    kernels on (interpret mode) no float32 array of ``B nc H Q Q``
    elements (the decay matrices) nor of ``B nc H P N`` (the state every
    chunk starts from, ``s_in``) is in the program; with kernels off both
    are — the reference is the reference."""
    import json

    from cellbench import spec as bench_spec
    from mlmicroservicetemplate_tpu.models import llama as llama_mod
    from mlmicroservicetemplate_tpu.models.gpt import PagedState
    from mlmicroservicetemplate_tpu.models.sampling import greedy_params
    from mlmicroservicetemplate_tpu.ops.prefill_attention import scores_in_hbm
    from test_nemotron_block import TOY

    real = bench_spec.load_json(
        bench_spec.HERE + "/configs/nemotron3-super-ep4-d11.json")
    kw = json.loads(bench_spec.service_env(
        {**real, **TOY, "chunk_size": 10})["LLAMA_CONFIG"])
    b, c, bs, nb, t_w, q = 3, 30, 4, 40, 12, 10

    def text(kernels: bool) -> str:
        cfg = llama_mod.LlamaConfig(**{
            **kw, "layer_pattern": "ME*M", "num_layers": 4, "eos_id": 1,
            "pad_id": 0, "pallas_interpret": True, "pallas_decode": kernels})
        params = jax.eval_shape(
            lambda: llama_mod.init_params(jax.random.PRNGKey(0), cfg))
        width = cfg.num_kv_heads * cfg.head_dim
        state = jax.eval_shape(lambda: PagedState(
            cache_k=[jnp.zeros((nb, bs, width))], cache_v=[jnp.zeros((nb, bs, width))],
            key_valid=jnp.zeros((b, t_w * bs), jnp.int32),
            write_idx=jnp.zeros((b,), jnp.int32), pos=jnp.zeros((b,), jnp.int32),
            last_token=jnp.zeros((b,), jnp.int32), done=jnp.ones((b,), bool),
            tokens=jnp.zeros((b, 8), jnp.int32), sample=greedy_params(b),
            ssm=llama_mod.zero_ssm(cfg, 5, jnp.float32)))
        i32 = jnp.int32
        return jax.jit(
            lambda p, s, tabs, ids, mask, starts, rows: llama_mod.paged_prefill_chunk(
                p, cfg, s, tabs, ids, mask, starts, ssm_rows=rows),
        ).lower(params, state, jax.ShapeDtypeStruct((b, t_w), i32),
                jax.ShapeDtypeStruct((b, c), i32), jax.ShapeDtypeStruct((b, c), i32),
                jax.ShapeDtypeStruct((b,), i32),
                jax.ShapeDtypeStruct((b, 2), i32)).compile().as_text()

    h, p, n = kw["ssm_heads"], kw["ssm_head_dim"], kw["ssm_state"]
    decay, s_in = b * (c // q) * h * q * q, b * (c // q) * h * p * n
    on, off = text(True), text(False)
    for count in (decay, s_in):
        assert scores_in_hbm(on, count, exact=True) == []
        assert scores_in_hbm(off, count, exact=True)


def test_an_undonated_insert_copies_every_pool(chip):
    """What the reader is for: the same insert without donation copies
    the pools (the program every insert ran until PR 30; 14 of the 16
    show as a plain ``copy``) — so an empty list above is the donation,
    not a blind spot."""
    from mlmicroservicetemplate_tpu.ops.paged_attention import pool_relayouts

    text, (payload_n, _) = _serving_program(chip, "mistral", "insert", False)
    hits = pool_relayouts(text, [payload_n])
    assert len(hits) >= 8 and all(" copy(" in h for h in hits)


@pytest.mark.parametrize("rows,experts,d,w", [
    (512, 64, 2048, 1024), (65536, 64, 2048, 1024), (24576, 128, 2048, 1024),
    (12288, 40, 5120, 1536), (67584, 128, 1024, 2688),
    (30720, 36, 4096, 768), (320, 36, 4096, 768),
], ids=["olmoe-step", "olmoe-wave", "trinity-dispatch", "dsv2-window",
        "nemotron-dispatch", "granite-dispatch", "granite-step"])
def test_grouped_matmul_compiles(chip, rows, experts, d, w):
    """A decode step's 512 assignments, a 64 x 128 wave's 65 536 and the
    prompt dispatches of the long-document cells (three windows of 1024
    tokens x top-8 over Trinity's 128 experts, one of 2048 x top-6 over
    DeepSeek-V2's 40 held, three x top-22 over Nemotron's 128 held, three x
    top-10 over Granite's 36 held experts 768 wide and its 32-row step)
    through both expert matmul shapes (gate / up and down): a tiling
    over the scoped VMEM limit fails here, not in a cell's boot."""
    from mlmicroservicetemplate_tpu.ops.moe import grouped_matmul

    sizes = chip((experts,), jnp.int32)
    for k, n in ((d, w), (w, d)):
        text = _compiled_text(
            chip, ("gmm", rows, experts, k, n), grouped_matmul,
            chip((rows, k), jnp.bfloat16), chip((experts, k, n), jnp.bfloat16),
            sizes,
        )
        assert "tpu_custom_call" in text


@pytest.mark.parametrize("tokens,k,held,pub,d,latent,w,act", [
    (3072, 22, 128, 512, 4096, 1024, 2688, "relu2"),
    (3072, 8, 16, 256, 7168, 0, 2048, "silu"),
    (2048, 6, 40, 160, 5120, 0, 1536, "silu"),
    (3072, 10, 36, 72, 4096, 0, 768, "silu"),
], ids=["nemotron-dispatch", "gigachat-dispatch", "dsv2-window",
        "granite-dispatch"])
def test_the_expert_blocks_ladder_compiles_at_a_shares_prompt_shapes(
        chip, tokens, k, held, pub, d, latent, w, act):
    """One expert layer's block of the three cells that hold a chip's share,
    at a prompt dispatch's tokens and the served widths: the gather, the
    activation and the combine each a conditional with a branch a rung
    (``ops/moe.row_rungs``: 5/4 of the share an even router sends the
    held experts, then every row), the grouped matmuls outside them — as many kernels as the
    one-rung program has (a kernel a branch was 5.7 MiB of executable a
    rung a layer: PERF.md section 6, PR 52)."""
    from mlmicroservicetemplate_tpu.ops import moe

    rungs = moe.row_rungs(tokens * k, held, pub)
    assert len(rungs) == 2 and rungs[0] * 16 * pub == tokens * k * held * 20
    bf, wide = jnp.bfloat16, latent or d
    mlp = {"router": {"kernel": chip((d, pub), jnp.float32)},
           "up": {"kernel": chip((held, wide, w), bf)},
           "down": {"kernel": chip((held, w, wide), bf)}}
    if act == "silu":
        mlp["gate"] = mlp["up"]
    if latent:
        mlp["latent_down"] = {"kernel": chip((d, latent), bf)}
        mlp["latent_up"] = {"kernel": chip((latent, d), bf)}
    text = _compiled_text(
        chip, ("ladder", tokens, k, held, pub, d, w),
        lambda h, m, valid: moe.expert_ffn(
            h, m, k, True, valid, act=act, score="sigmoid"),
        chip((tokens, d), bf), mlp, chip((tokens,), jnp.bool_))
    # 16 384 rows and more of 8 KB and more (Granite, GigaChat; not
    # Nemotron's latent 2 KB, not DeepSeek-V2's 12 288 rows): the gather
    # and the combine are DMA kernels outside the conditionals — the row
    # gather, the slabs and the combine beside the grouped matmuls.
    fused = moe.row_kernels_fit(tokens * k, wide, jnp.bfloat16)
    assert fused == (latent == 0 and tokens * k >= 16384)
    assert text.count(" conditional(") == (1 if fused else 3)
    assert text.count("tpu_custom_call") == (
        (3 if act == "silu" else 2) + (3 if fused else 0))


@pytest.mark.parametrize("tokens,k,wide", [
    (3072, 10, 4096), (2048, 6, 5120), (3072, 8, 7168), (3072, 22, 1024),
    (3072, 8, 2048), (8192, 8, 2048),
], ids=["granite-dispatch", "dsv2-window", "gigachat-dispatch",
        "nemotron-dispatch", "trinity-dispatch", "olmoe-wave"])
def test_the_expert_blocks_row_kernels_compile(chip, tokens, k, wide):
    """``sorted_rows`` and ``combine_rows`` (with its ``moe_row_slabs``) at
    the rows the six expert cells' prompt dispatches and OLMoE's 64-row wave
    move, whether the shape rule gives them the call or not: the index
    arrays — up to 67 584 int32 for Nemotron, with as many float32 weights
    — ride as scalar-prefetch operands, a row is a slab of whole tiles
    (5120 and 7168 lanes pad to 24 and 32 sublanes, 1024 to 8), the
    combine's two slots of ``combine_tile`` tokens x k slabs fit beside its
    output block."""
    from mlmicroservicetemplate_tpu.ops import moe

    bf, m = jnp.bfloat16, tokens * k
    live = chip((), jnp.int32)
    text = _compiled_text(
        chip, ("moe-sorted-rows", tokens, k, wide), moe.sorted_rows,
        chip((tokens, wide), bf), chip((m,), jnp.int32), live)
    assert text.count("tpu_custom_call") == 1
    text = _compiled_text(
        chip, ("moe-combine-rows", tokens, k, wide), moe.combine_rows,
        chip((m, wide), bf), chip((tokens, k), jnp.int32),
        chip((tokens, k), jnp.float32), live)
    assert text.count("tpu_custom_call") == 2
    assert f"f32[{k},{tokens},{wide}]" not in text  # no [k, T, D] in HBM


def _largest_fit(kvh: int, d: int) -> int:
    """The widest cache (a multiple of 64) the boot's gate lets the
    whole-slab kernel take at these heads, under the budget in force."""
    from mlmicroservicetemplate_tpu.ops.attention import decode_kernel_fits

    t = 64
    while decode_kernel_fits(t + 64, kvh, d):
        t += 64
    return t


def test_slab_gate_counts_what_the_kernel_holds(monkeypatch):
    """K and V at bf16, double-buffered, and ONE head's f32 upcasts: 16
    KV heads of 128 at a 256 bucket + 192 tokens (OLMoE's start) fit
    Mistral's 12 MB, as 8 KV heads at 704 do; the count stays a limit."""
    from mlmicroservicetemplate_tpu.ops.attention import decode_kernel_fits

    monkeypatch.setenv("DECODE_KERNEL_VMEM_BUDGET_MB", "12")
    assert decode_kernel_fits(448, 16, 128)  # 7.34 MB + 0.46 MB
    assert decode_kernel_fits(704, 16, 128)  # 11.53 MB + 0.72 MB of 12.58
    assert decode_kernel_fits(704, 8, 128)
    assert not decode_kernel_fits(768, 16, 128)
    assert _largest_fit(8, 128) == 1344  # 12.58e6 / (8*1024*8 + 1024)


@pytest.mark.parametrize("kvh,n_rep,d,budget_mb,variant", [
    (16, 1, 128, 12, ""), (16, 1, 128, 12, "b1-hb"),  # OLMoE, Mistral's budget
    (8, 4, 128, 12, ""),  # Mistral-7B
    (KVH, H // KVH, D, 10, ""),  # the defaults: a head of 64 pads to the lanes
])
def test_compiler_takes_the_slab_kernel_wherever_the_gate_says_fits(
        chip, monkeypatch, kvh, n_rep, d, budget_mb, variant):
    """At the gate's own boundary for each head layout, 64 rows."""
    monkeypatch.setenv("DECODE_KERNEL_VMEM_BUDGET_MB", str(budget_mb))
    t = _largest_fit(kvh, d)
    kv = chip((64, t, kvh, d), jnp.bfloat16)
    text = _compiled_text(
        chip, ("slab-gate", kvh, d, t, variant),
        lambda q, k, v, m: decode_attention(q, k, v, m, variant=variant),
        chip((64, kvh * n_rep, d), jnp.bfloat16), kv, kv,
        chip((64, t), jnp.int32),
    )
    assert "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# The latent kernel (PR 33) at the DeepSeek-V2 cell's shapes: 32 slots, 128
# query rows a key, a 392-entry table, blocks of 16 tokens.


def _latent_text(chip, lanes: int, variant: str) -> str:
    from mlmicroservicetemplate_tpu.ops.paged_attention import latent_decode_attention

    b, h, bs, t = 32, 128, 16, 392
    return _compiled_text(
        chip, ("latent", lanes, variant),
        lambda q, p, tb, m: latent_decode_attention(
            q, p, tb, m, bs, 512, 0.1147, variant=variant),
        chip((b, h, lanes), jnp.bfloat16), chip((b * t, bs, lanes), jnp.bfloat16),
        chip((b, t), jnp.int32), chip((b, t * bs), jnp.int32),
    )


def test_every_enumerated_latent_variant_compiles(chip):
    """Each fold the sweep would time at the cell's shapes, within the
    cell's VMEM budget by the model and by the chip's compiler alike; no
    pool-sized copy beside the kernel."""
    from mlmicroservicetemplate_tpu.ops.paged_attention import pool_relayouts

    cands = autotune.enumerate_variants(
        "latent_decode", t=392, bs=16, kvh=1, d=640, n_rep=128,
        dtype="bfloat16", quant=False, budget=12 << 20)
    assert {v.blocks_per_step for v in cands} == {4, 8, 14, 28, 56}
    assert not any(v.head_batched for v in cands)  # one KV head: no such axis
    for var in cands:
        text = _latent_text(chip, 640, var.key())
        assert "tpu_custom_call" in text, var.key()
        assert pool_relayouts(text, [32 * 392 * 16 * 640]) == [], var.key()


def test_a_576_lane_latent_pool_is_refused_by_the_chips_compiler(chip):
    """Why the pool is 640 lanes wide (``LlamaConfig.latent_lanes``): the
    chip lays 576 lanes out as 640 in HBM and Mosaic will not slice a
    block out of it at 576."""
    with pytest.raises(Exception, match="aligned to tiling"):
        _latent_text(chip, 576, "b8-nat")


def test_the_deltanet_cells_chunk_compiles_at_its_shapes(chip):
    """GigaChat3.5's paged chunk at the cell's own shapes (32 slots, state
    rows [32, 64, 128, 128] float32 a DeltaNet layer beside a [12544, 16,
    640] latent pool): the one-token delta rule over the state rows and the
    latent kernel at 64 heads compile for the described chip, and the
    donated state is aliased.  (The three-window prompt dispatch compiles
    too, 47 s here: ``tools/lowered_text`` lowers it, the chip runs it.)"""
    import json

    from cellbench import spec as bench_spec
    from mlmicroservicetemplate_tpu.engine.engine import chunk_with_done
    from mlmicroservicetemplate_tpu.models import llama as llama_mod
    from mlmicroservicetemplate_tpu.models.gpt import PagedState
    from mlmicroservicetemplate_tpu.models.sampling import greedy_params

    config = bench_spec.load_json(
        bench_spec.HERE + "/configs/gigachat35-ep16-d5.json")
    over = json.loads(bench_spec.service_env(config)["LLAMA_CONFIG"])
    cfg = LlamaConfig(**over, pallas_decode=True, pallas_variant="b4-hb")
    b, bs, dt, t = 32, 16, jnp.bfloat16, 392

    def on_chip(tree):
        return jax.tree.map(lambda x: chip(x.shape, x.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: llama_mod.init_params(jax.random.PRNGKey(0), cfg, dtype=dt)))
    state = on_chip(jax.eval_shape(lambda: PagedState(
        cache_k=[jnp.zeros((b * t, bs, cfg.latent_lanes), dt)], cache_v=[],
        key_valid=jnp.zeros((b, t * bs), jnp.int32),
        write_idx=jnp.zeros((b,), jnp.int32), pos=jnp.zeros((b,), jnp.int32),
        last_token=jnp.zeros((b,), jnp.int32), done=jnp.ones((b,), bool),
        tokens=jnp.zeros((b, 256), jnp.int32), sample=greedy_params(b),
        ssm=llama_mod.zero_ssm(cfg, b, dt))))
    assert [s.shape for s in state.ssm.state] == [(32, 64, 128, 128)] * 4
    text = jax.jit(
        chunk_with_done(lambda p, s, tb, n, sample: (
            llama_mod.generate_chunk_paged(p, cfg, s, tb, n, sample))),
        static_argnums=(3, 4), donate_argnums=(1,),
    ).lower(params, state, chip((b, t), jnp.int32), 4, False).compile().as_text()
    assert "input_output_alias" in text and text.count("tpu_custom_call") >= 5
