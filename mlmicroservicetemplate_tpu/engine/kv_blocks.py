"""Block-paged KV-cache bookkeeping: free-list allocator, per-stream
block tables, refcounts, copy-on-write prefix sharing.

The contiguous layout reserves ``seq_bucket + MAX_DECODE_LEN`` KV rows
per slot for a stream's whole lifetime, so concurrency under
``KV_BUDGET_MB`` is bounded by the WORST case.  Paged mode
(``PAGED_KV=1``) carves the budget into fixed-size token blocks
(``KV_BLOCK_SIZE``) and accounts at block granularity instead:

- a stream is admitted holding only its prompt blocks plus the blocks
  the first chunk needs,
- it grows block-by-block at chunk boundaries as decode proceeds,
- every block returns to the free list the moment the stream finishes
  (early EOS, cancel, preemption checkpoint) — not at slot release.

Everything here is HOST-side: block ids index the device-resident
pools (``models/gpt.PagedState``); the tables ride into each dispatch
as a traced int32 array.  The allocator is the single source of truth
for committed KV bytes in paged mode (``scheduler/admission.py`` reads
it instead of running its own ceiling ledger).

Copy-on-write prefix sharing: KV is append-only, so "CoW" degenerates
to pure sharing — a prefix-cache hit pins the donor's prompt blocks by
refcount (no copy; the sharer never writes positions < P because
prefix lengths are block-aligned seq buckets), and a block is freed
only when its LAST holder (streams and the cache pin alike) derefs it.
"""

from __future__ import annotations

import threading
from collections import deque
from dataclasses import dataclass, field


def blocks_for(tokens: int, block_size: int) -> int:
    """Blocks needed to hold ``tokens`` KV rows (ceil; 0 for 0)."""
    if tokens <= 0:
        return 0
    return -(-int(tokens) // int(block_size))


class OutOfBlocks(Exception):
    """The pool cannot satisfy an allocation (caller reclaims/preempts)."""


class BlockPool:
    """Thread-safe free-list allocator with per-block refcounts.

    ``alloc`` hands out blocks at refcount 1; ``ref`` adds holders
    (CoW prefix sharing: the cache pin and every sharer each hold one
    ref); ``free`` drops one ref per id and returns a block to the
    free list when its count hits zero.  All-or-nothing: a partial
    allocation never leaks."""

    def __init__(self, num_blocks: int, block_bytes: int = 0):
        self.num_blocks = int(num_blocks)
        self.block_bytes = int(block_bytes)
        self._free: deque[int] = deque(range(self.num_blocks))
        self._ref: dict[int, int] = {}
        self._lock = threading.Lock()

    # -- queries -------------------------------------------------------

    @property
    def free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def used_blocks(self) -> int:
        with self._lock:
            return self.num_blocks - len(self._free)

    @property
    def used_bytes(self) -> int:
        return self.used_blocks * self.block_bytes

    def refcount(self, block: int) -> int:
        with self._lock:
            return self._ref.get(block, 0)

    # -- mutation ------------------------------------------------------

    def alloc(self, n: int) -> list[int]:
        """Take ``n`` blocks (refcount 1 each) or raise ``OutOfBlocks``
        without taking any."""
        if n <= 0:
            return []
        with self._lock:
            if len(self._free) < n:
                raise OutOfBlocks(
                    f"need {n} blocks, {len(self._free)} free of "
                    f"{self.num_blocks}"
                )
            ids = [self._free.popleft() for _ in range(n)]
            for b in ids:
                self._ref[b] = 1
            return ids

    def ref(self, ids: list[int]) -> None:
        """Add one holder to each block (shared-prefix pin)."""
        with self._lock:
            for b in ids:
                if self._ref.get(b, 0) <= 0:
                    raise ValueError(f"ref of unallocated block {b}")
                self._ref[b] += 1

    def take(self, ids: list[int]) -> None:
        """Claim SPECIFIC free blocks at refcount 1 — the restart-
        restore path (``runtime/durability.KVDiskTier``): a persisted
        index names exact block ids, so reconstruction must allocate
        those ids, not whatever the free list pops.  All-or-nothing;
        raises on ids that are out of range or already held."""
        with self._lock:
            want = set()
            for b in ids:
                b = int(b)
                if not (0 <= b < self.num_blocks):
                    raise ValueError(f"take of out-of-range block {b}")
                if self._ref.get(b, 0) > 0 or b in want:
                    raise ValueError(f"take of already-held block {b}")
                want.add(b)
            self._free = deque(b for b in self._free if b not in want)
            for b in want:
                self._ref[b] = 1

    def free(self, ids: list[int]) -> None:
        """Drop one holder per id; zero-ref blocks rejoin the free
        list.  Unknown/already-free ids raise (a double free is a
        ledger bug, never silently absorbed)."""
        with self._lock:
            for b in ids:
                c = self._ref.get(b, 0)
                if c <= 0:
                    raise ValueError(f"double free of block {b}")
                if c == 1:
                    del self._ref[b]
                    self._free.append(b)
                else:
                    self._ref[b] = c - 1

    def stats(self) -> dict:
        with self._lock:
            shared = sum(1 for c in self._ref.values() if c > 1)
            return {
                "num_blocks": self.num_blocks,
                "free": len(self._free),
                "used": self.num_blocks - len(self._free),
                "shared": shared,
            }


@dataclass
class StreamBlocks:
    """One stream's block table: ids in logical-position order.

    The first ``shared`` entries are CoW prefix blocks adopted from a
    donor (this stream holds one ref on each, like any other holder);
    the rest were alloc'd for this stream.  ``release`` derefs
    everything exactly once."""

    pool: BlockPool
    block_size: int
    ids: list[int] = field(default_factory=list)
    shared: int = 0
    released: bool = False

    @property
    def tokens_capacity(self) -> int:
        return len(self.ids) * self.block_size

    def adopt(self, shared_ids: list[int]) -> None:
        """Prepend a donor's prefix blocks (caller guarantees the
        logical prefix is block-aligned).  Takes one ref per block."""
        if self.ids:
            raise ValueError("adopt must precede any allocation")
        self.pool.ref(shared_ids)
        self.ids = list(shared_ids)
        self.shared = len(shared_ids)

    def ensure(self, n_tokens: int) -> list[int]:
        """Grow the table to cover ``n_tokens`` positions; returns the
        newly-allocated ids ([] when already covered).  Raises
        ``OutOfBlocks`` leaving the table unchanged."""
        need = blocks_for(n_tokens, self.block_size) - len(self.ids)
        if need <= 0:
            return []
        fresh = self.pool.alloc(need)
        self.ids.extend(fresh)
        return fresh

    def trim(self, n_tokens: int) -> list[int]:
        """Return tail blocks past what ``n_tokens`` positions need —
        the window-boundary reconcile for fused decode: blocks
        pre-provisioned for chunks an early-exited window never ran go
        back to the pool instead of riding the stream until it ends.
        Never trims into the adopted CoW prefix.  Returns the freed
        ids ([] when already exact)."""
        keep = max(blocks_for(n_tokens, self.block_size), self.shared)
        if keep >= len(self.ids):
            return []
        tail = self.ids[keep:]
        self.ids = self.ids[:keep]
        self.pool.free(tail)
        return tail

    def release(self) -> None:
        if not self.released:
            self.released = True
            if self.ids:
                self.pool.free(self.ids)
            self.ids = []
            self.shared = 0


class HostBlockPool(BlockPool):
    """Host-RAM block tier (KV_HOST_BUDGET_MB): the device pool's
    free-list/refcount discipline PLUS the storage itself — one
    preallocated numpy buffer per pool leaf, mirroring the device
    pool's per-layer layout ([num_blocks, block_size, heads, dim]
    payloads, plus scale leaves under QUANT_KV=int8), so a block's
    content round-trips device↔host by id with no reshaping.

    Swapped-out streams and demoted prefix-cache entries live here
    instead of being recomputed: copying KV back over PCIe/ICI is the
    ChunkFlow trade — bandwidth is cheaper than re-prefill compute
    (arXiv 2605.11335).  Buffers are plain numpy: "pinned" in the
    practical sense that they are allocated once up front and written
    in place, never reallocated per swap."""

    def __init__(self, num_blocks: int, block_bytes: int, leaf_specs):
        import numpy as np

        super().__init__(num_blocks, block_bytes)
        # leaf_specs: [(per-block shape, dtype)] in jax.tree.leaves
        # order over (cache_k, cache_v) — the canonical order the
        # loop's gather/scatter executables flatten to.
        self.leaves = [
            np.zeros((self.num_blocks,) + tuple(shape), dtype)
            for shape, dtype in leaf_specs
        ]

    def write(self, ids: list[int], leaf_vals) -> None:
        """Store block rows: ``leaf_vals[i]`` is [len(ids), bs, ...]."""
        import numpy as np

        idx = np.asarray(ids, np.int64)
        for buf, vals in zip(self.leaves, leaf_vals):
            buf[idx] = vals

    def read(self, ids: list[int]):
        """Fetch block rows, one [len(ids), bs, ...] array per leaf."""
        import numpy as np

        idx = np.asarray(ids, np.int64)
        return [buf[idx] for buf in self.leaves]


class SwapEntry:
    """One swapped-out unit in the host tier: the host block ids
    holding a stream's resume-prompt KV (kind ``stream``) or a demoted
    prefix pin's KV (kind ``prefix``), plus the token count they
    cover.  ``alive`` flips False at eviction — a waiting stream whose
    entry died falls back to recompute; ``ready`` flips True once the
    async device→host copy has materialized into the buffers."""

    __slots__ = (
        "ids", "tokens", "kind", "key", "alive", "ready", "pool", "ledger",
    )

    def __init__(self, ids: list[int], tokens: int, kind: str, key=None,
                 pool=None, ledger=None):
        self.ids = list(ids)
        self.tokens = int(tokens)
        self.kind = kind
        self.key = key
        self.alive = True
        self.ready = False
        # Backrefs: which tier holds these ids (an adopting loop checks
        # the pool identity — a non-shared tier's entry is unusable)
        # and which ledger frees them (release routes through it, so a
        # foreign entry can never free into the wrong pool).
        self.pool = pool
        self.ledger = ledger


class SwapLedger:
    """The cross-tier map: which host blocks hold which stream/prefix
    KV.  Conservation invariant (pinned by test): every host-pool
    block is owned by exactly ONE alive entry at refcount 1, so
    releasing every entry drains the host pool to zero and a double
    release is absorbed exactly once (the underlying pool still raises
    on a true double free).  LRU eviction prefers demoted prefix
    entries over stream swaps — a waiting stream's resume is hotter
    than a cache entry's maybe-reuse."""

    def __init__(self, pool: HostBlockPool):
        from collections import OrderedDict

        self.pool = pool
        self._lru: "OrderedDict[SwapEntry, None]" = OrderedDict()
        self._prefix: dict = {}
        self._lock = threading.Lock()
        self.evictions = 0
        # Tier hooks (runtime/durability.py): ``spill(entry)`` is
        # offered the victim at LRU eviction so cold blocks demote to
        # the next tier down instead of dying (best-effort — a spill
        # failure still evicts); ``on_release`` mirrors entry lifecycle
        # into the disk tier's persistent index.  Both None (the
        # default) keep the round-14 behavior exactly.
        self.spill = None
        self.on_release = None

    def __len__(self) -> int:
        with self._lock:
            return len(self._lru)

    def reserve(self, n_blocks: int, tokens: int, kind: str,
                key=None) -> SwapEntry | None:
        """Allocate ``n_blocks`` host blocks as a new entry, LRU-
        evicting older entries (prefix first) to make room; None when
        the tier cannot hold it even empty."""
        if n_blocks <= 0 or n_blocks > self.pool.num_blocks:
            return None
        with self._lock:
            while True:
                try:
                    ids = self.pool.alloc(n_blocks)
                    break
                except OutOfBlocks:
                    if not self._evict_one_locked():
                        return None
            entry = SwapEntry(
                ids, tokens, kind, key=key, pool=self.pool, ledger=self,
            )
            self._lru[entry] = None
            if key is not None:
                self._prefix[key] = entry
            return entry

    def restore(self, ids: list[int], tokens: int, kind: str,
                key=None) -> SwapEntry:
        """Reconstruct one entry at SPECIFIC block ids (restart replay
        of a persistent tier's index — the blocks' payload already sits
        in the backing store, so the entry is born ``ready``)."""
        with self._lock:
            self.pool.take(ids)
            entry = SwapEntry(
                ids, tokens, kind, key=key, pool=self.pool, ledger=self,
            )
            entry.ready = True
            self._lru[entry] = None
            if key is not None:
                self._prefix[key] = entry
            return entry

    def _evict_one_locked(self) -> bool:
        victim = None
        for e in self._lru:  # oldest-first; prefer prefix entries
            if e.kind == "prefix":
                victim = e
                break
            if victim is None:
                victim = e
        if victim is None:
            return False
        if self.spill is not None and victim.ready:
            # Demote the cold blocks a tier down before they die.
            try:
                self.spill(victim)
            except Exception:  # pragma: no cover - defensive
                import logging

                logging.getLogger(__name__).exception(
                    "KV tier spill failed; evicting without demotion"
                )
        self._release_locked(victim)
        self.evictions += 1
        return True

    def _release_locked(self, entry: SwapEntry) -> None:
        if not entry.alive:
            return
        entry.alive = False
        self._lru.pop(entry, None)
        if entry.key is not None:
            self._prefix.pop(entry.key, None)
        self.pool.free(entry.ids)
        if self.on_release is not None:
            try:
                self.on_release(entry)
            except Exception:  # pragma: no cover - defensive
                pass

    def release(self, entry: SwapEntry) -> None:
        with self._lock:
            self._release_locked(entry)

    def touch(self, entry: SwapEntry) -> None:
        with self._lock:
            if entry.alive:
                self._lru.move_to_end(entry)

    def prefix_get(self, key) -> SwapEntry | None:
        """Host-tier prefix lookup by (bucket, content-hash) key;
        touches LRU recency on hit."""
        return self.get(key)

    def get(self, key) -> SwapEntry | None:
        """Keyed lookup for ANY entry kind (stream checkpoints key as
        ``("stream", rid)`` when a disk tier needs to find them);
        touches LRU recency on hit."""
        with self._lock:
            e = self._prefix.get(key)
            if e is not None and e.alive:
                self._lru.move_to_end(e)
                return e
            return None

    def stats(self) -> dict:
        with self._lock:
            streams = sum(1 for e in self._lru if e.kind == "stream")
            return {
                "entries": len(self._lru),
                "stream_entries": streams,
                "prefix_entries": len(self._lru) - streams,
                "evictions": self.evictions,
                "used_blocks": self.pool.used_blocks,
                "free_blocks": self.pool.free_blocks,
            }


class KVHostTier:
    """Holder for one host-RAM KV tier: budget + lazily-built pool and
    ledger (leaf shapes are only known once the paged device pools are
    built).  Shared by every fleet replica of one process — the host
    copies are replica-agnostic (same params produce the same KV), so
    a failed-over stream can swap-resume on its adopter and a demoted
    prefix serves the whole fleet."""

    def __init__(self, budget_mb: float, block_bytes: int):
        self.budget_bytes = int(float(budget_mb) * 1e6)
        self.block_bytes = int(block_bytes)
        self.num_blocks = self.budget_bytes // max(1, self.block_bytes)
        self.pool: HostBlockPool | None = None
        self.ledger: SwapLedger | None = None
        self._lock = threading.Lock()

    @property
    def enabled(self) -> bool:
        return self.num_blocks > 0

    def ensure_pool(self, leaf_specs) -> bool:
        """Build the buffers on first use; False when the budget holds
        no whole block (tier effectively off)."""
        if not self.enabled:
            return False
        with self._lock:
            if self.pool is None:
                self.pool = HostBlockPool(
                    self.num_blocks, self.block_bytes, leaf_specs
                )
                self.ledger = SwapLedger(self.pool)
        return True

    def reserve(self, n_blocks: int, tokens: int, kind: str,
                key=None) -> SwapEntry | None:
        return (
            self.ledger.reserve(n_blocks, tokens, kind, key=key)
            if self.ledger is not None else None
        )

    def release(self, entry: SwapEntry) -> None:
        if self.ledger is not None:
            self.ledger.release(entry)

    def prefix_get(self, key) -> SwapEntry | None:
        return (
            self.ledger.prefix_get(key) if self.ledger is not None else None
        )

    def prefix_resident(self, key) -> bool:
        return self.prefix_get(key) is not None

    def stats(self) -> dict:
        base = {
            "budget_bytes": self.budget_bytes,
            "block_bytes": self.block_bytes,
            "num_blocks": self.num_blocks,
        }
        if self.ledger is not None:
            base.update(self.ledger.stats())
        return base


@dataclass(frozen=True)
class PagedPrefix:
    """A prefix-cache entry in paged mode: no KV copy, just the
    donor's prompt-block ids with one pool ref held by the cache (the
    CoW pin).  Sharers take their own ref at adoption; eviction drops
    only the cache's ref, so in-flight sharers keep the blocks alive.
    ``nbytes`` feeds the cache's byte budget (the bytes these pinned
    blocks occupy in the POOL — pins spend serving budget, which is
    exactly the trade the LRU bounds)."""

    p_len: int
    block_ids: tuple[int, ...]
    nbytes: int


def kv_token_bytes(
    layers: int, kv_heads: int, head_dim: int, elt_bytes: int,
    quant_int8: bool = False, latent_lanes: int = 0, scale_bytes: int = 4,
) -> int:
    """KV bytes per token position: K and V across all layers, at the
    cache element width (int8 payload + one scale per token-head under
    QUANT_KV=int8) — or, for a latent cache (``latent_lanes``: multi-head
    latent attention), ONE row of that many lanes a layer, no heads and
    no V.  Shared by the admission estimate and the paged block ledger so
    the two accountings can never drift."""
    if latent_lanes:
        return layers * latent_lanes * elt_bytes
    if quant_int8:
        per_head = head_dim * 1 + scale_bytes
    else:
        per_head = head_dim * elt_bytes
    return 2 * layers * kv_heads * per_head
