"""Per-request prompt-prefix KV cache (the vLLM-class feature the
global PROMPT_PREFIX knob approximates).

Real chat traffic shares prefixes PER CONVERSATION — system prompt +
growing history — not one global system prompt.  This cache lets every
request reuse the KV of the longest previously-computed prefix of its
own token sequence: TTFT then pays only the suffix prefill, the same
O(S)-not-O(P+S) economics the global knob measured at 1.52× on
llama-1.1B (the pre-round BASELINE record (removed in PR 22) round 3), but
granted at request time to any
recurring prefix.

TPU-first constraints shape the design:

- **Static shapes**: a cached prefix's length P selects an XLA
  executable, so P is quantized to the engine's existing seq buckets —
  the executable grid stays |seq_buckets|² at worst, warmable, and a
  request matches the LARGEST bucket P ≤ len(prompt)-1 whose token
  hash hits (≥1 real suffix token must remain: generation needs it).
- **Keys are content hashes** of the exact token ids
  (blake2b(tokens[:P])), so a hit is exact-prefix identity — no
  false sharing between conversations.
- **Capture is free compute**: after any full prefill, cache rows
  0..P already hold the prefix KV — insertion is ONE jitted slice
  dispatch of [1, P] per layer stack, not a recompute.
- **No hard refcounts needed**: JAX arrays are immutable, so an
  in-flight request keeps its prefix arrays alive past eviction; the
  LRU byte budget (``PREFIX_CACHE_MB``) bounds what the CACHE pins,
  not what requests hold.
- **Entries inherit the serving cache's dtype**: under QUANT_KV the
  engine's capture slicer cuts the int8 cache rows themselves, so each
  per-layer entry is an (int8 payload, per-token scale) tuple — about
  half the budget bytes of a dense bf16 entry (twice the conversations
  per MB), re-absorbed bit-exactly on hits, and the same pytree rides
  through match/insert/evict unchanged (byte accounting walks leaves).

Mutually exclusive with the global PROMPT_PREFIX (its KV occupies
positions 0..P_global, which per-request prefixes would collide with);
the engine enables this cache only when no global prefix is attached.
"""

from __future__ import annotations

import hashlib
import logging
import threading
from collections import OrderedDict
from typing import Any

import numpy as np

log = logging.getLogger(__name__)


def _key(ids: np.ndarray, p: int) -> bytes:
    return hashlib.blake2b(
        np.ascontiguousarray(ids[:p].astype(np.int32)).tobytes(), digest_size=16
    ).digest()


class PrefixCache:
    """LRU {(P, hash(tokens[:P])) -> per-layer KV pytree [1, P, H, D]
    (dense) or ([1, P, H, D] int8, [1, P, H, 1] scale) under QUANT_KV}."""

    def __init__(self, buckets: tuple[int, ...], budget_mb: float = 256.0,
                 on_evict=None):
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        self.budget_bytes = int(budget_mb * 1e6)
        self._entries: OrderedDict[tuple[int, bytes], Any] = OrderedDict()
        self._bytes = 0
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        # Paged mode: entries are kv_blocks.PagedPrefix block-ref pins,
        # not KV copies; eviction must DROP the pin (pool refcount),
        # which this callback does.  Refcounting keeps eviction safe
        # for in-flight sharers — they hold their own refs.  The
        # callback receives ``(entry, key)`` — the key lets a host
        # tier (KV_HOST_BUDGET_MB) demote the evicted entry and still
        # find it again on a later match.
        self.on_evict = on_evict
        # Arity detected ONCE: a TypeError raised inside the callback
        # itself must never trigger a second (double-freeing) call.
        self._evict_two_arg = False
        if on_evict is not None:
            import inspect

            try:
                self._evict_two_arg = (
                    len(inspect.signature(on_evict).parameters) >= 2
                )
            except (TypeError, ValueError):
                self._evict_two_arg = False

    def _evict_cb(self, entry: Any, key) -> None:
        if self.on_evict is None:
            return
        if self._evict_two_arg:
            self.on_evict(entry, key)
        else:  # legacy single-arg callback (tests, duck-typed engines)
            self.on_evict(entry)

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def bytes_used(self) -> int:
        return self._bytes

    def match(self, ids: np.ndarray, length: int, usable=None):
        """Longest cached prefix of ``ids[:length]``: (P, kv) or None.
        P ≤ length-1 so at least one real token remains to prefill.

        ``usable(P) -> bool`` lets the caller impose its static-shape
        guards BEFORE a candidate counts: an entry the engine cannot
        actually serve from must not register a hit or get LRU-promoted
        (it would skew stats and evict genuinely-serving entries)."""
        with self._lock:
            for p in reversed(self.buckets):
                if p > length - 1 or (usable is not None and not usable(p)):
                    continue
                key = (p, _key(ids, p))
                kv = self._entries.get(key)
                if kv is not None:
                    self._entries.move_to_end(key)
                    self.hits += 1
                    return p, kv
            self.misses += 1
            return None

    def peek(self, ids: np.ndarray, length: int) -> int:
        """Longest cached prefix bucket of ``ids[:length]`` WITHOUT
        touching stats or LRU recency — the fleet router's
        prefix-affinity probe (scheduler/router.py) must not register
        hits on replicas the request never routes to.  Returns 0 on
        no match."""
        with self._lock:
            for p in reversed(self.buckets):
                if p > length - 1:
                    continue
                if (p, _key(ids, p)) in self._entries:
                    return p
            return 0

    def host_lookup(self, ids: np.ndarray, length: int, tier,
                    usable=None):
        """Longest HOST-TIER prefix of ``ids[:length]`` — consulted
        after the device entries miss, so an entry demoted under
        device-budget pressure (KV_HOST_BUDGET_MB) still matches and
        can be promoted back.  Returns (P, SwapEntry) or None; the
        caller owns the device-side promotion (block alloc + host→
        device copy + re-insert) — this cache cannot dispatch."""
        for p in reversed(self.buckets):
            if p > length - 1 or (usable is not None and not usable(p)):
                continue
            e = tier.prefix_get((p, _key(ids, p)))
            if e is not None:
                return p, e
        return None

    def bucket_for_insert(self, length: int) -> int | None:
        """Largest bucket ≤ length-1 (the most reusable prefix a prompt
        of this length can donate), or None when it's too short."""
        cands = [p for p in self.buckets if p <= length - 1]
        return max(cands) if cands else None

    def contains(self, ids: np.ndarray, p: int) -> bool:
        with self._lock:
            return (p, _key(ids, p)) in self._entries

    @staticmethod
    def _entry_bytes(kv: Any) -> int:
        if hasattr(kv, "nbytes"):  # paged block-ref entries carry their own
            return int(kv.nbytes)
        import jax

        return sum(
            int(np.prod(x.shape)) * x.dtype.itemsize
            for x in jax.tree.leaves(kv)
        )

    def insert(self, ids: np.ndarray, p: int, kv: Any) -> None:
        """Store prefix KV (a pytree of device arrays, or a paged
        block-ref pin); LRU-evict past the byte budget.  Evicted
        arrays stay alive for any in-flight request that already
        fetched them (immutability); evicted paged entries drop the
        cache's pool ref via ``on_evict`` (sharers keep theirs)."""
        key = (p, _key(ids, p))
        with self._lock:
            if key in self._entries:
                return
            self._entries[key] = kv
            self._bytes += self._entry_bytes(kv)
            while self._bytes > self.budget_bytes and len(self._entries) > 1:
                okey, old = self._entries.popitem(last=False)
                self._bytes -= self._entry_bytes(old)
                self._evict_cb(old, okey)

    def pop_lru(self) -> Any | None:
        """Evict the least-recently-used entry unconditionally (the
        paged loop's reclaim path when the pool runs dry: pinned
        prefix blocks are the first memory to give back).  Returns the
        evicted entry or None when the cache is empty."""
        with self._lock:
            if not self._entries:
                return None
            okey, old = self._entries.popitem(last=False)
            self._bytes -= self._entry_bytes(old)
            self._evict_cb(old, okey)
            return old

    def stats(self) -> dict:
        with self._lock:
            return {
                "entries": len(self._entries),
                "bytes": self._bytes,
                "hits": self.hits,
                "misses": self.misses,
            }
