"""The decode loop's executables: one table, built in one place.

``LoopPrograms`` takes an engine and the loop's static shapes and builds
every jitted program the loop dispatches beside the engine's own starts
and chunks.  It knows nothing of streams, slots, queues or locks, and
imports nothing from ``streams.py``: the loop reaches its executables
through ``loop.programs``, warm-up (``engine/warm.py``) walks them, and a
test lowers them with no loop at all.

Every wrapper routes through the engine's process-level ExecutableCache
(``_shared_jit``), keyed on its kind string and statics, and is built at
its first use, once a ``LoopPrograms`` (``built``: kind -> wrapper).
Accessors end in ``_fn`` and are called where they are used
(``programs.paged_chunk_fn()(...)``): ``tools/graftlint``'s dispatch-guard
rule knows a dispatch by that idiom.

One rule for the decode state (``streams.py``'s docstring): a program that
takes the batched state and returns its successor donates it.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..utils import tracing

# kind -> (the bundle's function, ``jax.jit`` arguments): the programs that
# are a bundle function jitted as it stands.
_BUNDLE_PROGRAMS = {
    "prefill_chunk": ("prefill_chunk_fn", {"donate_argnums": (1,)}),
    "paged_prefill_chunk": (
        "paged_prefill_chunk_fn", {"donate_argnums": (1,)}),
    "empty_state": ("empty_state_fn", {"static_argnums": (1, 2, 3)}),
    "init_spec_template": ("init_spec_fn", {}),
}


def _ins_row(dst, src, slot, row):
    """Row ``row`` of ``src`` (a lone or batched prefill state's leaf:
    a wave prefills as one batch and each row lands in its own slot),
    zero-padded to the slot shape, written into row ``slot`` of ``dst``.
    One row only: a full-width dynamic_update_slice would clobber the
    adjacent live slots."""
    src = lax.dynamic_slice_in_dim(src, row, 1, axis=0)
    pad = [(0, 0)] + [
        (0, int(d) - int(s)) for d, s in zip(dst.shape[1:], src.shape[1:])
    ]
    srcp = jnp.pad(src.astype(dst.dtype), pad)
    return lax.dynamic_update_slice(
        dst, srcp, (slot,) + (0,) * (dst.ndim - 1)
    )


def _ins_rows(dst, src, slots):
    """Every row of ``src`` (a wave's prefill state's leaf), zero-padded
    to the slot shape, written into row ``slots[i]`` of ``dst``; a row
    whose slot is out of range drops."""
    pad = [(0, 0)] + [
        (0, int(d) - int(s)) for d, s in zip(dst.shape[1:], src.shape[1:])
    ]
    return dst.at[slots].set(jnp.pad(src.astype(dst.dtype), pad), mode="drop")


def paged_insert(block_size: int):
    """The paged slot insert as a function to jit: a wave's rows land in
    their slots in ONE dispatch.  Row ``i`` of ``single`` (the wave's
    prefill state, ``Bw`` rows: its rung; a lone start is the rung of 1)
    scatters its positions [s_lo, s_cut) into the blocks ``table_rows[i]``
    names (CoW prefix rows [0, s_lo) are the donor's blocks and are never
    rewritten) — one ``scatter_rows`` a pool over the wave's ``Bw * W``
    positions — and its per-row fields land in slot ``slots[i]``.  A row
    that does not insert (a pad row of the rung, a row that finished in
    its first chunk or was re-queued) carries an all-sentinel table row
    and an out-of-range slot (and state row): every one of its writes
    drops."""
    from ..models.gpt import PagedState
    from ..ops.paged_attention import scatter_rows

    def insert(batched, single, table_rows, slots, s_lo: int, s_cut: int,
               ssm_rows=None):
        # One destination a position of the wave, for every pool (one
        # pool geometry serves all layers): out of range where sentinel.
        p = s_lo + jnp.arange(s_cut - s_lo)
        blk = jnp.take(  # [Bw, W]
            table_rows, p // block_size, axis=1, mode="fill",
            fill_value=jax.tree.leaves(batched.cache_k)[0].shape[0],
        )
        dest = (blk * block_size + p % block_size).reshape(-1)

        def scat(pool, src):
            vals = src[:, s_lo:s_cut]
            return scatter_rows(
                pool, dest, vals.reshape((-1,) + vals.shape[2:])
            )

        def scat_entry(pc, sc):
            if isinstance(pc, tuple):
                return (scat(pc[0], sc[0]), scat(pc[1], sc[1]))
            return scat(pc, sc)

        def rows(d, s):
            return _ins_rows(d, s, slots)

        return PagedState(
            cache_k=[
                scat_entry(d, s)
                for d, s in zip(batched.cache_k, single.cache_k)
            ],
            cache_v=[
                scat_entry(d, s)
                for d, s in zip(batched.cache_v, single.cache_v)
            ],
            key_valid=rows(batched.key_valid, single.key_valid),
            write_idx=rows(batched.write_idx, single.write_idx),
            pos=rows(batched.pos, single.pos),
            last_token=rows(batched.last_token, single.last_token),
            done=rows(batched.done, single.done),
            tokens=rows(batched.tokens, single.tokens),
            sample=jax.tree.map(rows, batched.sample, single.sample),
            **_insert_ssm(batched, single, slots, ssm_rows),
        )

    return insert


def _insert_ssm(batched, single, slots, ssm_rows) -> dict:
    """Each wave row's state — a recurrent layer's taps and state, a window
    layer's ring: every per-row leaf of ``SsmState`` — into state row
    ``ssm_rows[i]`` (past the last row: dropped — warm-up, a row that does
    not insert), and its slot pointed at it; nothing for a model without
    state rows (its ``ssm`` is the empty default)."""
    if ssm_rows is None:
        return {"ssm": batched.ssm}

    def put(dst, src):  # [R, ...] <- [Bw, ...]
        return dst.at[ssm_rows].set(src.astype(dst.dtype), mode="drop")

    b, s = batched.ssm, single.ssm
    return {"ssm": b._replace(
        row=b.row.at[slots].set(ssm_rows, mode="drop"),
        **{f: [put(d, x) for d, x in zip(dst, getattr(s, f))]
           for f, dst in b.leaves.items()},
    )}


class LoopPrograms:
    """The executables of one decode loop over ``engine``.

    ``n_slots`` rows of batched state; ``spec`` picks the insert that
    recasts through ``init_spec_fn``; a paged loop adds ``block_size``,
    ``nb_max`` (a table row's width) and ``state_rows`` (the model keeps
    recurrent state rows).  ``params_for(n)`` is the parameter tree of a
    dispatch of ``n`` all-base rows (the loop's ``_mp``; without one, the
    engine's).  ``kernel_variant`` is the tuned Pallas decode kernel
    ("" = default): warm-up resolves it BEFORE the paged chunk traces, and
    it keys that executable in the shared cache (docs/kernel_tuning.md)."""

    def __init__(self, engine, *, n_slots: int, spec: bool = False,
                 block_size: int = 0, nb_max: int = 0,
                 state_rows: bool = False, params_for=None):
        self.engine = engine
        self.n_slots = n_slots
        self.spec = spec
        self.block_size = block_size
        self.nb_max = nb_max
        self.state_rows = state_rows
        self.params_for = params_for or (lambda n: engine.params)
        self.kernel_variant = ""
        self.built: dict[Any, Any] = {}

    def _shared_jit(self, kind: str, build, statics: tuple = ()):
        """Loop-owned executables route through the engine's
        process-level ExecutableCache too (runtime/compile_cache.py):
        every replica's loop shares one wrapper per (bundle, kind,
        statics, placement), so a spawned replica's warm() re-traces
        nothing.  Duck-typed test engines without the helper keep
        private wrappers."""
        shared = getattr(self.engine, "_shared_jit", None)
        if shared is None:
            return build()
        return shared(kind, build, statics)

    def _bundle_fn(self, kind: str):
        if kind not in self.built:
            name, jit_args = _BUNDLE_PROGRAMS[kind]
            self.built[kind] = self._shared_jit(
                kind,
                lambda: jax.jit(getattr(self.engine.bundle, name), **jit_args),
            )
        return self.built[kind]

    def prefill_fn(self):
        return self._bundle_fn("prefill_chunk")

    def paged_prefill_fn(self):
        return self._bundle_fn("paged_prefill_chunk")

    def empty_prefill_fn(self):
        return self._bundle_fn("empty_state")

    def init_spec_template_fn(self):
        return self._bundle_fn("init_spec_template")

    def seed_prefix_fn(self, p_len: int):
        """Copy a contiguous prefix-cache hit's KV into rows [0, p_len)
        of a fresh chunked-prefill state and mark them valid — the
        chunked counterpart of ``_start_prefixed``'s cache seeding; the
        suffix then prefills window by window from position p_len."""
        if ("seed_prefix", p_len) not in self.built:
            def seed(st, pk):
                def put(c, e):
                    if isinstance(c, tuple):  # (int8 payload, scale)
                        return tuple(
                            ci.at[:, :p_len].set(ei.astype(ci.dtype))
                            for ci, ei in zip(c, e)
                        )
                    return c.at[:, :p_len].set(e.astype(c.dtype))

                return st._replace(
                    cache_k=[put(c, e) for c, e in zip(st.cache_k, pk["k"])],
                    cache_v=[put(c, e) for c, e in zip(st.cache_v, pk["v"])],
                    key_valid=st.key_valid.at[:, :p_len].set(1),
                )

            self.built["seed_prefix", p_len] = self._shared_jit(
                "seed_prefix", lambda: jax.jit(seed, donate_argnums=(0,)),
                statics=(p_len,),
            )
        return self.built["seed_prefix", p_len]

    def paged_handoff_fn(self):
        """Paged handoff: the stream's KV already lives in its blocks
        (the windows wrote it), so going live is pure row-field
        surgery — key_valid/write_idx/pos/last_token/done/tokens/sample
        of one slot row."""
        if "paged_handoff" not in self.built:
            def ins_row(dst, src, slot):
                pad = [(0, 0)] + [
                    (0, int(d) - int(s))
                    for d, s in zip(dst.shape[1:], src.shape[1:])
                ]
                srcp = jnp.pad(src.astype(dst.dtype), pad)
                start = (slot,) + (0,) * (dst.ndim - 1)
                return lax.dynamic_update_slice(dst, srcp, start)

            def handoff(batched, kv_row, w_idx, pos, last, done, toks, sp,
                        slot, ssm_row=None):
                if ssm_row is not None:
                    # The state is the stream's already (its windows wrote
                    # its row): the slot is pointed at it, nothing moves.
                    batched = batched._replace(ssm=batched.ssm._replace(
                        row=batched.ssm.row.at[slot].set(ssm_row)))
                return batched._replace(
                    key_valid=ins_row(batched.key_valid, kv_row, slot),
                    write_idx=ins_row(batched.write_idx, w_idx, slot),
                    pos=ins_row(batched.pos, pos, slot),
                    last_token=ins_row(batched.last_token, last, slot),
                    done=ins_row(batched.done, done, slot),
                    tokens=ins_row(batched.tokens, toks, slot),
                    sample=jax.tree.map(
                        lambda d, s: ins_row(d, s, slot), batched.sample, sp
                    ),
                )

            self.built["paged_handoff"] = self._shared_jit(
                "paged_handoff",
                lambda: jax.jit(handoff, donate_argnums=(0,)),
            )
        return self.built["paged_handoff"]

    def insert_fn(self):
        if "insert" not in self.built:
            if self.spec:
                bundle = self.engine.bundle

                def insert_spec(batched, single, ids, mask, hist_row,
                                slot, row):
                    # The family's init_spec_fn recasts the prefill
                    # state to the spec base (adds key_valid/write_idx
                    # for T5; identity for decoder-only).  Its device-
                    # built history is DISCARDED: per-bucket widths and
                    # the encoder-decoder layout offset don't pad to
                    # the slot shape — the host-built ``hist_row``
                    # already has the slot's exact layout.
                    ss = bundle.init_spec_fn(single, ids, mask)
                    base = jax.tree.map(
                        lambda d, s: _ins_row(d, s, slot, row),
                        batched.base, ss.base,
                    )
                    hist = lax.dynamic_update_slice(
                        batched.history, hist_row.astype(jnp.int32),
                        (slot, 0),
                    )
                    return type(batched)(base=base, history=hist)

                self.built["insert"] = self._shared_jit(
                    "insert_spec", lambda: jax.jit(
                        tracing.scoped("slot_insert", insert_spec),
                        donate_argnums=(0,),
                    )
                )
            else:
                def insert(batched, single, slot, row):
                    return jax.tree.map(
                        lambda d, s: _ins_row(d, s, slot, row),
                        batched, single,
                    )

                # The batched state is donated (the module docstring's
                # rule): one row is written in place.  In-flight chunks
                # hold outputs of their own (toks, done), never a leaf
                # of the pre-insert state.  ``single`` is a wave's
                # prefill state, read by every row's insert: not donated.
                self.built["insert"] = self._shared_jit(
                    "insert", lambda: jax.jit(
                        tracing.scoped("slot_insert", insert),
                        donate_argnums=(0,),
                    )
                )
        return self.built["insert"]

    # -- paged executables ---------------------------------------------

    def paged_chunk_fn(self):
        if "paged_chunk" not in self.built:
            from .engine import chunk_with_done

            self.built["paged_chunk"] = self._shared_jit(
                "paged_chunk",
                lambda: jax.jit(
                    tracing.scoped(
                        "decode_chunk",
                        chunk_with_done(self.engine.bundle.paged_chunk_fn),
                    ),
                    static_argnums=(3, 4), donate_argnums=(1,),
                ),
                # The traced program embeds the tuned kernel variant
                # (resolved at trace time via ops/autotune.lookup) —
                # replicas tuned differently must not share a wrapper.
                statics=(self.kernel_variant,),
            )
        return self.built["paged_chunk"]

    def paged_chunk_hlo(self, state, table, debug_info: bool = False,
                        compiled: bool = False) -> str:
        """Lowered text of the paged decode chunk over ``state`` and the
        block ``table`` at the loop's serving shapes — the program the
        chunk dispatches run.  What ``chip_smoke.py`` reads to show which
        attention path is in the step: the Pallas kernel lowers to a
        ``tpu_custom_call``, the ``gather_pages`` path to none.
        ``debug_info`` adds each operation's location, which carries its
        ``named_scope`` path; ``compiled`` gives the backend's optimised
        text instead (layouts assigned: where a pool-sized relayout would
        show)."""
        with self.engine._lock:
            lowered = self.paged_chunk_fn().lower(
                self.params_for(self.n_slots), state,
                jnp.asarray(table), self.engine.chunk_tokens, False,
            )
            if compiled:
                return lowered.compile().as_text()
            return lowered.as_text(debug_info=debug_info)

    def paged_insert_hlo(self, state, s: int, rows: int = 1) -> str:
        """The backend's optimised text of the insert of a ``rows``-row
        wave of a ``s``-token bucket (1: a lone prefill) into ``state``
        — the chunk's twin for ``chip_smoke.py``: with the state donated
        no pool is copied in it either."""
        with self.engine._lock:
            state1 = self.warm_wave(s, rows)[0]
            return self.paged_insert_fn().lower(
                *self.warm_insert_args(state, state1, s)
            ).compile().as_text()

    def placed_batch(self, feats_list: list) -> tuple:
        """Rows collated and placed as a start takes them: (ids, mask, sp)."""
        eng = self.engine
        ids, mask, _ = eng._collate_text(feats_list)
        sp, _ = eng._collate_sample(feats_list, ids.shape[0])
        return *eng.replicas.place_batch(ids, mask), sp

    def warm_wave(self, s: int, n_batch: int, sampled: bool = False):
        """Run the batched start for ``n_batch`` full rows of bucket
        ``s`` (caller holds ``eng._lock``): (state1, ids, mask)."""
        eng = self.engine
        ids, mask, sp = self.placed_batch([
            {"input_ids": np.ones(s, np.int32), "length": np.int32(s)}
        ] * n_batch)
        state1, _ = eng._start(
            self.params_for(int(ids.shape[0])), ids, mask, sp,
            eng.max_decode_len, eng.chunk_tokens, sampled,
        )
        return state1, ids, mask

    def warm_insert_args(self, state, state1, s: int, ids=()) -> tuple:
        """The arguments of a warm-up insert into ``state`` of ``state1``,
        a wave of bucket ``s``: row 0 into slot 0 and the blocks ``ids``,
        every other row — and every row's recurrent state — dropped."""
        rows = int(state1.done.shape[0])
        table_rows = np.full(
            (rows, self.nb_max), self.engine.kv_pool.num_blocks, np.int32
        )
        table_rows[0, : len(ids)] = ids
        past = np.full(rows, self.n_slots, np.int32)
        slots = past.copy()
        slots[0] = 0
        ssm = (past,) if self.state_rows else ()
        return (state, state1, table_rows, slots,
                0, s + self.engine.chunk_tokens, *ssm)

    def paged_insert_fn(self):
        """Paged wave insert (``paged_insert``): one executable per
        wave rung and static (s_lo, s_cut) pair — the (prefix bucket,
        suffix bucket) grid, like the prefixed starts.  The batched
        state is donated (the module docstring's rule): the scatters
        write the streams' blocks in place; ``single``, the wave's
        prefill state, is not: no leaf of it has an output's shape to
        alias, and it dies with the wave's ``started`` entries."""
        if "paged_insert" not in self.built:
            bs = self.block_size
            self.built["paged_insert"] = self._shared_jit(
                "paged_insert",
                lambda: jax.jit(
                    tracing.scoped("slot_insert", paged_insert(bs)),
                    static_argnums=(4, 5), donate_argnums=(0,),
                ),
                statics=(bs,),
            )
        return self.built["paged_insert"]

    def prefix_rows_fn(self, p_len: int, tails):
        """Dense ``{"k": [...], "v": [...]}`` view of the first ``p_len``
        positions of a run of blocks, gathered from a state's pools — what
        the prefixed start executables consume on a paged cache hit
        (``tails``: a token's dims a pool leaf, which the gather unmerges).
        It only reads the state: not donated."""
        if ("gather_prefix", p_len) not in self.built:
            from ..ops.paged_attention import gather_pages

            bs = self.block_size

            def gather(state, blocks):
                pools, treedef = jax.tree.flatten(
                    (state.cache_k, state.cache_v)
                )
                k, v = jax.tree.unflatten(treedef, [
                    gather_pages(pool, blocks[None], bs, tail)[:, :p_len]
                    for pool, tail in zip(pools, tails)
                ])
                return {"k": k, "v": v}

            self.built["gather_prefix", p_len] = self._shared_jit(
                "gather_prefix", lambda: jax.jit(gather),
                statics=(p_len, bs),
            )
        return self.built["gather_prefix", p_len]

    def swap_gather_fn(self):
        """Jitted device-side block gather: pool[ids] per KV leaf.
        ``ids`` is padded to a power of two (repeating the last id) so
        the executable grid stays log2(nb_max), not one per length."""
        if "swap_gather" not in self.built:
            def gather(state, ids):
                return jax.tree.map(
                    lambda pool: pool[ids], (state.cache_k, state.cache_v)
                )

            self.built["swap_gather"] = self._shared_jit(
                "swap_gather", lambda: jax.jit(gather)
            )
        return self.built["swap_gather"]

    def swap_scatter_fn(self):
        """Jitted host→device block write: pool.at[ids].set(vals) per
        KV leaf.  One executable total — every call is padded to the
        fixed KV_PREFETCH_BLOCKS chunk width."""
        if "swap_scatter" not in self.built:
            def scatter(state, ids, vals):
                flat, treedef = jax.tree.flatten(
                    (state.cache_k, state.cache_v)
                )
                new = [
                    p.at[ids].set(v.astype(p.dtype))
                    for p, v in zip(flat, vals)
                ]
                ck, cv = jax.tree.unflatten(treedef, new)
                return state._replace(cache_k=ck, cache_v=cv)

            self.built["swap_scatter"] = self._shared_jit(
                "swap_scatter",
                lambda: jax.jit(scatter, donate_argnums=(0,)),
            )
        return self.built["swap_scatter"]
