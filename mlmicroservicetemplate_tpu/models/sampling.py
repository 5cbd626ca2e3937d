"""Per-row token sampling for generative decode (temperature/top-k/top-p).

Serving contract: every request carries its own sampling knobs, so a
single batched decode dispatch mixes greedy and sampled rows freely —
essential for continuous batching, where one `generate_chunk` serves
many concurrent streams.  All controls are therefore PER-ROW arrays
([B]-shaped) living inside the decode state:

- ``temperature`` (f32): 0 = greedy argmax (the default); >0 scales
  logits before sampling.
- ``top_k`` (i32): keep only the k highest logits (0 = off).
- ``top_p`` (f32): nucleus sampling — keep the smallest set of tokens
  whose cumulative probability reaches p (>= 1.0 = off).
- ``rng`` ([B, 2] u32): per-row threefry key.  Keys derive from the
  request's ``seed`` only, and each step's key is split from the row's
  own chain — so a seeded request reproduces its tokens exactly
  regardless of which other rows share the batch (batched == solo).

Determinism note: greedy rows never touch the rng, and a seeded
sampled row's trajectory is a pure function of (seed, step, logits).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

# Python float, NOT jnp.float32: this module can be first imported
# from inside a jit trace (model fns import it lazily), and a
# module-level jnp constant created under an active trace would be a
# tracer — leaking into every later executable that reads it.  A weak
# float promotes to the logits' f32 in jnp.where identically.
_NEG_INF = -1e9


class SampleParams(NamedTuple):
    """Per-row sampling state carried inside GPT/T5 decode states."""

    rng: jax.Array  # [B, 2] uint32 threefry keys
    temperature: jax.Array  # [B] f32, 0 = greedy
    top_k: jax.Array  # [B] i32, 0 = off
    top_p: jax.Array  # [B] f32, >= 1 = off


def greedy_params(batch: int) -> SampleParams:
    """All-greedy defaults (what init_decode_state uses when the caller
    passes no sampling request)."""
    return SampleParams(
        rng=jnp.zeros((batch, 2), jnp.uint32),
        temperature=jnp.zeros((batch,), jnp.float32),
        top_k=jnp.zeros((batch,), jnp.int32),
        top_p=jnp.ones((batch,), jnp.float32),
    )


def make_params(seed, temperature, top_k, top_p) -> SampleParams:
    """Build per-row params from [B] request arrays.

    Pure numpy on purpose: this runs on the request path, where every
    eager jax op would cost a device dispatch (a full host<->device
    round-trip).  The key layout matches threefry2x32's PRNGKey(seed) —
    [hi32, lo32] — which ``select_token`` wraps explicitly.
    """
    import numpy as np

    seed64 = np.asarray(seed, np.uint64)
    rng = np.stack(
        [(seed64 >> np.uint64(32)).astype(np.uint32),
         (seed64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)],
        axis=-1,
    )
    return SampleParams(
        rng=rng,
        temperature=np.asarray(temperature, np.float32),
        top_k=np.asarray(top_k, np.int32),
        top_p=np.asarray(top_p, np.float32),
    )


def _filter_top_k(logits: jax.Array, top_k: jax.Array, sorted_desc: jax.Array) -> jax.Array:
    """Mask logits below each row's k-th largest (top_k == 0 keeps all)."""
    v = sorted_desc.shape[-1]
    k_idx = jnp.clip(top_k - 1, 0, v - 1)  # [B]
    kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)  # [B, 1]
    keep = (logits >= kth) | (top_k <= 0)[:, None]
    return jnp.where(keep, logits, _NEG_INF)


def _filter_top_p(logits: jax.Array, top_p: jax.Array, sorted_desc: jax.Array) -> jax.Array:
    """Nucleus filter: keep the smallest prefix of the sorted
    distribution whose cumulative probability reaches top_p (the
    first token is always kept).  top_p >= 1 keeps all."""
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # A sorted position is kept while the mass BEFORE it is < p.
    keep_sorted = (cum - probs) < top_p[:, None]  # [B, V] monotone prefix
    # Cutoff = smallest kept logit value in sorted order.
    cutoff = jnp.min(
        jnp.where(keep_sorted, sorted_desc, jnp.float32(jnp.inf)), axis=-1
    )  # [B]
    keep = (logits >= cutoff[:, None]) | (top_p >= 1.0)[:, None]
    return jnp.where(keep, logits, _NEG_INF)


def filtered_logits(
    logits: jax.Array,  # [B, V]
    temperature: jax.Array,  # [B]
    top_k: jax.Array,  # [B]
    top_p: jax.Array,  # [B]
) -> jax.Array:
    """The temperature/top-k/top-p transform as f32 logits (filtered
    entries at -inf): softmax of the result IS the distribution a
    sampled row draws from.  One home for the filter order (HF:
    temperature, then top-k, then top-p) — the sequential sampler and
    the speculative rejection sampler (spec.py) must agree exactly or
    spec stops being distribution-identical."""
    # Temperature first, guarded against div-by-zero for greedy rows
    # whose sampled value is discarded anyway.
    z = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)[:, None]
    v = z.shape[-1]
    sorted_desc = -jnp.sort(-z, axis=-1)  # descending — the ONE sort
    z = _filter_top_k(z, top_k, sorted_desc)
    # The sorted view of the top-k-filtered dist is derivable from the
    # first sort by masking its tail — no second O(V log V) sort on the
    # per-token hot path.
    eff_k = jnp.where(top_k > 0, top_k, v)[:, None]
    sorted_desc2 = jnp.where(
        jnp.arange(v)[None, :] < eff_k, sorted_desc, _NEG_INF
    )
    return _filter_top_p(z, top_p, sorted_desc2)


def row_split(k):
    """Per-row key chain: split -> (next chain, this step's key), so a
    row's randomness is independent of batch composition.  ``k`` is a
    [2] u32 raw key; returns ([2] u32 next chain, typed step key)."""
    nk, sk = jax.random.split(jax.random.wrap_key_data(k, impl="threefry2x32"))
    return jax.random.key_data(nk), sk


def select_token(logits: jax.Array, sp: SampleParams) -> tuple[jax.Array, SampleParams]:
    """Pick the next token per row: argmax where temperature <= 0,
    filtered categorical sample elsewhere.  Returns (tokens [B] i32,
    params with advanced rng chains).

    The full [B, V] sort this costs per step is why the engine keeps a
    separate greedy executable (static ``sample=False``) for the
    no-sampling fast path.
    """
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    z = filtered_logits(logits, sp.temperature, sp.top_k, sp.top_p)
    next_rng, step_keys = jax.vmap(row_split)(sp.rng)
    sampled = jax.vmap(jax.random.categorical)(step_keys, z).astype(jnp.int32)
    tok = jnp.where(sp.temperature > 0.0, sampled, greedy_tok)
    return tok, sp._replace(rng=next_rng.astype(jnp.uint32))
