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
        xs = jnp.take(rows, order // k, axis=0)  # [T*k, D], sorted by expert
    with jax.named_scope("moe_experts"):
        mm = functools.partial(grouped_matmul, interpret=interpret)
        if act == "relu2":
            mid = _relu2(mm(xs, up, sizes))
        else:
            mid = gate_act(mm(xs, mlp["gate"]["kernel"], sizes)) * clip(mm(xs, up, sizes))
        ys = mm(mid, down, sizes)  # [T*k, D]
    with jax.named_scope("moe_combine"):
        in_group = jnp.arange(t * k) < jnp.sum(sizes)
        ys = jnp.where(in_group[:, None], ys, 0)
        back = jnp.take(ys, jnp.argsort(order), axis=0).reshape(
            t, k, rows.shape[1])
        out = jnp.sum(back.astype(jnp.float32) * w[:, :, None], axis=1)
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
