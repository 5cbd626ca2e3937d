"""Self-drafting speculative decoding (prompt-lookup / n-gram).

At batch=1 a decoder's step time is pinned to the HBM ceiling: every
token streams the full weight set once (measured in the pre-round BASELINE
record (removed in PR 22) —
llama-1.1B at 2.58 ms/step bf16 ≈ 853 GB/s, the v5e wire).  No tuning
beats that wall except not paying one weight pass PER token: draft
several candidate tokens cheaply, then verify them all in ONE forward
whose weight traffic is the same as a single step.  With m drafts
accepted, one weight pass yields m+1 tokens.

This module is the drafter-free variant (no second checkpoint exists in
this offline environment): drafts come from *prompt lookup* — the last
``ngram_n`` generated tokens are matched against the prompt + generation
history, and the ``spec_k`` tokens that followed the most recent match
become the draft.  Free to compute (a masked compare over an int32
buffer already on device), highly effective whenever output re-uses
input spans (summarization, extraction, code edits, chat quoting), and
harmless when it misses: a rejected draft costs only MXU idle lanes in
the verify forward, which is HBM-bound at these shapes anyway.

Correctness contract (greedy only): every emitted token equals the
verify forward's own greedy argmax at its position, so the output
token sequence is EXACTLY what non-speculative greedy decoding would
produce under the same numerics (tested token-identical in
tests/test_spec.py).  Acceptance never depends on where a draft came
from — a garbage draft that happens to match argmax is a correct
emission by construction.

All control flow is static-shape: each verify step processes a fixed
``spec_k + 1`` token window and returns a fixed-width output row plus a
per-row valid count; the host slices counts off the fetched buffer.
Works on any decoder family exposing a ``multi_step`` window forward
(gpt.py, llama.py — the GPTState contract) AND on encoder-decoders
(t5.py): the history buffer may be WIDER than the KV cache by a
constant prefix that holds the encoder input ids — cache position p
maps to history position p + (hist_width - cache_width).  For T5 that
prefix is the document being summarized, exactly where summaries quote
from, so prompt-lookup drafts land at their highest-acceptance
workload.  Decoder-only families have equal widths and a zero offset.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp


class SpecState(NamedTuple):
    """Decode state + token history for drafting.

    ``base`` is the family's GPTState (per-row caches/write_idx/done —
    models/gpt.py); ``history`` is an int32 [B, total] buffer where
    position p holds the token id EMBEDDED at cache position p (-1
    where no real token lives: bucket padding, unwritten future, the
    startup-cached PROMPT_PREFIX region whose ids were never seen
    here).  Invariant: history[b, write_idx[b]] == last_token[b]."""

    base: Any
    history: jax.Array


def init_history(
    state, input_ids, attention_mask, p_len: int, prefix_ids=None
) -> SpecState:
    """Build the drafting history from the (right-padded) prompt.

    ``p_len`` is the cached-prefix length.  When the caller KNOWS the
    prefix token ids (per-request prefix caching: the prefix is the
    request's own leading tokens), pass them as ``prefix_ids`` [1, P]
    so the n-gram lookup drafts from the full prompt; a startup-global
    PROMPT_PREFIX's ids are unknown at this layer and that region
    stays -1 (no matches land there)."""
    b, s = input_ids.shape
    total = state.key_valid.shape[1]
    hist = jnp.full((b, total), -1, jnp.int32)
    ids = jnp.where(attention_mask != 0, input_ids, -1).astype(jnp.int32)
    hist = hist.at[:, p_len : p_len + s].set(ids)
    if prefix_ids is not None:
        pref = jnp.broadcast_to(
            jnp.asarray(prefix_ids, jnp.int32).reshape(1, -1), (b, p_len)
        )
        hist = hist.at[:, :p_len].set(pref)
    return SpecState(base=state, history=hist)


def make_init_spec_fn(p_len: int = 0):
    """THE ``init_spec_fn`` implementation for DECODER-ONLY families
    (the GPTState layout): ``(state, input_ids, attention_mask,
    prefix_ids=None) -> SpecState``.  ``prefix_ids`` arrives on
    per-request prefix-cache hits (its length wins over the builder's
    global ``p_len``); decoder-only builders and custom families should
    use this instead of hand-rolling the closure.  Encoder-decoders
    have a different history layout (the encoder ids prepend the
    buffer) — see ``t5.init_spec_state``."""

    def init_spec_fn(state, input_ids, attention_mask, prefix_ids=None):
        pl = prefix_ids.shape[-1] if prefix_ids is not None else p_len
        return init_history(state, input_ids, attention_mask, pl, prefix_ids)

    return init_spec_fn


def draft_ngram(
    history: jax.Array,  # [B, total] int32, -1 invalid
    write_idx: jax.Array,  # [B]
    spec_k: int,
    ngram_n: int,
) -> jax.Array:
    """Prompt-lookup draft: [B, spec_k] continuation of the most recent
    earlier occurrence of the trailing n-gram, matched LARGEST n first
    (``ngram_n`` down to 1): longer patterns give higher-precision
    continuations, and rows they miss fall back to shorter ones —
    a fallback match that verification rejects costs nothing in the
    HBM-bound regime (the verify window runs either way), while a
    fallback match that holds is pure extra acceptance.  -1 rows where
    no n matches (-1 never equals an argmax → rejected for free).

    One incremental pass: the depth-d candidate mask refines the
    depth-(d-1) mask, and each depth's most-recent match position is
    recorded along the way — every n in one sweep, no recomputation."""
    b, total = history.shape
    posv = jnp.arange(total)[None]  # [1, total]
    t = write_idx[:, None]  # [B, 1]
    cand = posv < t  # strictly before the current position
    j_by_n = []  # most-recent match position per pattern length 1..N
    for d in range(ngram_n):
        tgt = jnp.take_along_axis(
            history, jnp.clip(t - d, 0, total - 1), axis=1
        )  # [B, 1] token at position t-d (the pattern's d-th-last)
        if d == 0:
            hd = history
        else:
            hd = jnp.pad(
                history[:, :-d], ((0, 0), (d, 0)), constant_values=-1
            )
        cand = cand & (hd == tgt) & (tgt >= 0) & (posv >= d)
        j_by_n.append(jnp.where(cand, posv, -1).max(axis=1).astype(jnp.int32))
    # Largest n wins; rows it missed fall back toward n=1.
    j = jnp.full((b,), -1, jnp.int32)
    for j_n in reversed(j_by_n):
        j = jnp.where(j >= 0, j, j_n)
    gather = jnp.clip(
        j[:, None] + 1 + jnp.arange(spec_k)[None], 0, total - 1
    )
    draft = jnp.take_along_axis(history, gather, axis=1)  # [B, spec_k]
    return jnp.where(j[:, None] >= 0, draft, jnp.int32(-1))


def _sampled_emission(logits, draft, sp, spec_k: int):
    """Rejection-sampling acceptance for deterministic (point-mass)
    drafts — the standard speculative-sampling result specialized to
    prompt-lookup: the draft proposal q is a point mass at draft_i, so

    - accept draft_i with prob p_{i-1}(draft_i), where p is the row's
      temperature/top-k/top-p-FILTERED distribution (must be the same
      transform the sequential sampler applies — sampling.filtered_logits);
    - on first rejection, resample from the residual norm(max(0, p - q))
      = p with the rejected token's mass removed, renormalized;
    - if all K accepted, the bonus token samples from p_K directly.

    Marginally each emitted position is distributed EXACTLY as
    sequential ancestral sampling (the accepted-mass + residual-mass
    split reconstructs p), so the output distribution is identical —
    only the randomness CONSUMPTION differs, which is why seeded
    sequences differ across the spec/non-spec paths while each path
    stays deterministic per seed (tested in test_spec_sampled.py).

    A -1 draft slot (no n-gram match) never had a proposal: acceptance
    is forced false and the "residual" keeps full p (nothing to remove).
    Returns (cand [B, K+1] emission candidates, m [B] accepted counts,
    next_rng [B, 2])."""
    from .sampling import filtered_logits, row_split

    b, width, v = logits.shape
    rep = lambda a: jnp.repeat(a, width, axis=0)
    z = filtered_logits(
        logits.reshape(b * width, v),
        rep(sp.temperature), rep(sp.top_k), rep(sp.top_p),
    ).reshape(b, width, v)
    probs = jax.nn.softmax(z, axis=-1)  # [B, W, V] f32
    clip_d = jnp.clip(draft, 0, v - 1)
    p_draft = jnp.take_along_axis(
        probs[:, :spec_k, :], clip_d[:, :, None], axis=-1
    )[..., 0]  # [B, K]

    next_rng, step_keys = jax.vmap(row_split)(sp.rng)
    u = jax.vmap(
        lambda k: jax.random.uniform(jax.random.fold_in(k, 0), (spec_k,))
    )(step_keys)  # [B, K]
    accept = (u < p_draft) & (draft >= 0)
    m = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1)  # [B]

    # Final token: residual at the rejection slot, or bonus at slot K.
    probs_m = jnp.take_along_axis(probs, m[:, None, None], axis=1)[:, 0]  # [B, V]
    rej_slot = jnp.minimum(m, spec_k - 1)[:, None]
    rej_tok = jnp.take_along_axis(clip_d, rej_slot, axis=1)[:, 0]  # [B]
    rej_valid = (m < spec_k) & (
        jnp.take_along_axis(draft, rej_slot, axis=1)[:, 0] >= 0
    )
    final_p = jnp.where(
        (jnp.arange(v)[None] == rej_tok[:, None]) & rej_valid[:, None],
        0.0, probs_m,
    )
    final_logits = jnp.log(jnp.maximum(final_p, jnp.float32(1e-38)))
    e = jax.vmap(
        lambda k, lg: jax.random.categorical(jax.random.fold_in(k, 1), lg)
    )(step_keys, final_logits).astype(jnp.int32)

    # Candidate emissions: the m accepted drafts, then the sampled token.
    offs = jnp.arange(width)[None]
    draft_pad = jnp.concatenate([draft, draft[:, :1]], axis=1)  # [B, W]
    cand = jnp.where(offs == m[:, None], e[:, None], draft_pad)
    return cand, m, next_rng.astype(jnp.uint32)


def verify_step(
    params,
    spec_state: SpecState,
    spec_k: int,
    ngram_n: int,
    multi_fn: Callable,  # (params, base_state, tokens [B,D]) -> (k, v, logits [B,D,V])
    eos_id: int,
    pad_id: int,
    sample: bool = False,
):
    """One draft→verify→accept round.  Returns (state', out [B, K+1],
    n_emit [B]): ``out[:, :n_emit]`` are the emitted tokens (padded with
    pad_id past the count).

    Window semantics: input x_0 = last_token (recomputed at its own
    position, identical to the single-step path's uniform-step trick),
    x_1..x_K = draft.  g_i = argmax of the logits after x_i.  g_0 is
    unconditionally correct (it is THE next greedy token); draft_i is
    accepted iff it equals g_i's predecessor chain — the longest prefix
    where draft == g[:, :K] — because only then was x_{i+1} the token
    greedy would have fed next.  m accepted drafts ⇒ m+1 emitted tokens
    (the bonus token g_m comes free from the verify logits).

    ``sample`` (static) additionally runs rejection-sampling acceptance
    for rows with temperature>0 (``_sampled_emission``): accepted
    drafts ARE the emissions there, and the (m+1)-th token is sampled
    from the residual/bonus distribution — distribution-identical to
    sequential sampling.  Greedy rows in the same batch keep the argmax
    rule; cache discipline is unchanged either way because the window
    K/V at position t+1+j always came from draft_{j+1}, which is
    exactly the token emitted at offset j on both rules.

    Cache/state discipline: K/V for ALL window positions are written
    before acceptance is known; only accepted positions get key_valid
    set, so rejected-position K/V is invisible and gets overwritten by
    later (sequential) writes before its position is ever marked valid.
    Rows already done emit nothing and freeze (their writes re-write
    position t with identical values)."""
    st = spec_state.base
    hist = spec_state.history
    b = st.last_token.shape[0]
    width = spec_k + 1
    rows = jnp.arange(b)[:, None]  # [B, 1]
    offs = jnp.arange(width)[None]  # [1, width]

    # Cache→history index offset: encoder-decoder families prepend the
    # encoder input ids to the history buffer (t5.init_spec_state), so
    # cache position p lives at history position p + hoff.  Both widths
    # are static, so this is a trace-time constant (0 for decoder-only).
    hoff = hist.shape[1] - st.key_valid.shape[1]

    draft = draft_ngram(hist, st.write_idx + hoff, spec_k, ngram_n)
    tokens = jnp.concatenate([st.last_token[:, None], draft], axis=1)
    # Draft slots may hold -1 (no match): embedding lookups need a real
    # id — feed pad instead; acceptance still compares the RAW draft,
    # so these can never be accepted.
    feed = jnp.where(tokens >= 0, tokens, jnp.int32(pad_id))
    new_k, new_v, logits = multi_fn(params, st, feed)
    g = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, width]

    match = draft == g[:, :spec_k]
    # Longest accepted prefix: count of leading True.
    m = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)  # [B]
    cand = g
    sp = st.sample
    if sample:
        cand_s, m_s, next_rng = _sampled_emission(logits, draft, sp, spec_k)
        is_samp = sp.temperature > 0.0
        cand = jnp.where(is_samp[:, None], cand_s, g)
        m = jnp.where(is_samp, m_s, m)
        sp = sp._replace(rng=next_rng)
    emit_raw = offs <= m[:, None]  # candidates cand_0..cand_m
    is_eos = (cand == jnp.int32(eos_id)) & emit_raw
    has_eos = is_eos.any(axis=1)
    eos_idx = jnp.where(has_eos, jnp.argmax(is_eos, axis=1), width)
    # Emit through the first EOS inclusive, like the sequential path.
    n_emit = jnp.minimum(m + 1, eos_idx + 1)
    n_emit = jnp.where(st.done, 0, n_emit).astype(jnp.int32)
    emit = offs < n_emit[:, None]  # [B, width]
    out = jnp.where(emit, cand, jnp.int32(pad_id))

    total = st.key_valid.shape[1]
    sentinel_tok = st.tokens.shape[1]  # OOB ⇒ mode="drop"
    tokens_buf = st.tokens.at[
        rows, jnp.where(emit, st.pos[:, None] + offs, sentinel_tok)
    ].set(out, mode="drop")
    posv = jnp.arange(total)[None]
    newly_valid = (posv >= st.write_idx[:, None]) & (
        posv < (st.write_idx + n_emit)[:, None]
    )
    key_valid = jnp.where(newly_valid, 1, st.key_valid)
    # Token g_i will be embedded at cache position t+1+i — history
    # position hoff+t+1+i (history invariant); sentinel = hist width.
    hist = hist.at[
        rows,
        jnp.where(emit, st.write_idx[:, None] + hoff + 1 + offs, hist.shape[1]),
    ].set(out, mode="drop")
    last = jnp.where(
        n_emit > 0,
        jnp.take_along_axis(cand, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)[:, 0],
        st.last_token,
    )
    base = st._replace(
        cache_k=new_k,
        cache_v=new_v,
        key_valid=key_valid,
        write_idx=st.write_idx + n_emit,
        pos=st.pos + n_emit,
        last_token=last,
        done=st.done | has_eos,
        tokens=tokens_buf,
        sample=sp,
    )
    return SpecState(base=base, history=hist), out, n_emit


def spec_chunk(
    params,
    spec_state: SpecState,
    n_verify: int,
    spec_k: int,
    ngram_n: int,
    multi_fn: Callable,
    eos_id: int,
    pad_id: int,
    sample: bool = False,
):
    """``n_verify`` verify rounds in one compiled scan — the spec-path
    chunk contract.  Returns (state', out [B, n_verify, K+1], n_emit
    [B, n_verify]): each round emits between 1 and K+1 tokens per live
    row (0 once done), so one dispatch yields ≥ n_verify tokens and up
    to n_verify·(K+1).  ``sample`` is STATIC: True compiles the
    rejection-sampling acceptance path for temperature>0 rows."""

    def step(s, _):
        s2, out, n = verify_step(
            params, s, spec_k, ngram_n, multi_fn, eos_id, pad_id, sample
        )
        return s2, (out, n)

    spec_state, (outs, ns) = jax.lax.scan(
        step, spec_state, None, length=n_verify
    )
    return spec_state, jnp.transpose(outs, (1, 0, 2)), jnp.transpose(ns)


def flatten_emitted(out_np, n_np, row: int = 0):
    """Host-side: ordered emitted tokens for one row from a fetched
    (out [B, n_verify, K+1], n_emit [B, n_verify]) pair."""
    import numpy as np

    parts = [
        out_np[row, v, : int(n_np[row, v])] for v in range(out_np.shape[1])
    ]
    return np.concatenate(parts) if parts else np.zeros((0,), np.int32)
