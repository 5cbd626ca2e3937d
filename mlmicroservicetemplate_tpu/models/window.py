"""Fused decode windows: W chunk scans in ONE dispatch, with
on-device EOS early exit.

The continuous loop's dispatch unit so far was one chunk
(``generate_chunk``: a ``lax.scan`` of ``chunk_tokens`` decode steps).
Every dispatch boundary costs a host round-trip; where that
round-trip is long next to a chunk's compute (pre-round records,
removed in PR 22, had host_share ≈ 1.0 at the chunk/fetch sites; to
be re-measured on the attached chip) the boundaries, not the compute,
are the serving ceiling.  A fused window lifts the unit to W
chunks: a ``lax.while_loop`` whose body is one whole chunk scan, so
the host submits once, fetches once and reconciles once per W chunks
instead of per chunk.

Why a while_loop and not one W·chunk scan: the loop carries the chunk
STRUCTURE into the fused dispatch — the condition re-checks
``state.done`` at every chunk boundary and stops the moment every row
is finished (on-device EOS early exit), so a window is never charged
for chunks past the batch's last EOS.  The host learns how many chunks
actually ran from the returned counter and routes exactly those.

Token identity is by construction: the body calls the SAME chunk
function the per-chunk path dispatches, on the same state, in the same
order — fusing changes where the host/device boundary sits, never the
math.  The per-chunk ``done`` history rides out with the tokens so the
host can replay its per-chunk routing (budget cursor, EOS at chunk
granularity) bit-for-bit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def decode_window(chunk_fn, state, n_steps: int, max_chunks: int, pad_id: int):
    """Run up to ``max_chunks`` invocations of ``chunk_fn`` (one chunk
    scan each: ``state -> (state, [B, n_steps] tokens)``) inside a
    single ``lax.while_loop``, stopping early once every row is done.

    Returns ``(state, tokens [B, max_chunks*n_steps], done_hist
    [max_chunks, B], n_chunks)``:

    - ``tokens``: chunk c's tokens at columns [c·n_steps, (c+1)·n_steps);
      unexecuted chunks stay ``pad_id``.
    - ``done_hist[c]``: ``state.done`` AFTER chunk c — what the
      per-chunk path's fetch would have seen at that boundary;
      unexecuted rows read all-done.
    - ``n_chunks``: chunks actually executed (< max_chunks on early
      exit; 0 when every row was already done at entry).
    """
    b = state.done.shape[0]
    buf = jnp.full((b, max_chunks * n_steps), pad_id, jnp.int32)
    hist = jnp.ones((max_chunks, b), bool)

    def cond(carry):
        s, _, _, i = carry
        return (i < max_chunks) & jnp.logical_not(jnp.all(s.done))

    def body(carry):
        s, buf, hist, i = carry
        s, toks = chunk_fn(s)
        buf = jax.lax.dynamic_update_slice(
            buf, toks.astype(jnp.int32), (0, i * n_steps)
        )
        hist = jax.lax.dynamic_update_slice(hist, s.done[None], (i, 0))
        return s, buf, hist, i + 1

    state, buf, hist, n = jax.lax.while_loop(
        cond, body, (state, buf, hist, jnp.int32(0))
    )
    return state, buf, hist, n
