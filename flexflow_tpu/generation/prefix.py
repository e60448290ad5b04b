"""Cross-request prefix caching: a radix index over token-block content
with refcounted copy-on-write blocks and a host-RAM offload tier.

At scale most requests share long common prefixes (system prompts,
few-shot templates), yet the block cache (cache.py) was slot-private:
every admission prefilled from scratch and preemption discarded KV for
full recompute. This module makes the cache an explicit
content-addressed structure — the vLLM/SGLang lineage (PagedAttention
block sharing, SOSP'23; RadixAttention prefix trees, SGLang) applied to
the existing block-structured cache:

* **Radix index.** Full blocks of prompt (and, after preemption,
  prompt+generated) content are registered in a trie keyed by
  ``(parent entry, block's token tuple)`` — exact-match edges, so a
  hash collision can never alias two different prefixes onto one
  block's KV. Admission walks the trie over the new prompt's full
  blocks and reuses every matched block instead of recomputing it; the
  engine then prefills only the *suffix* (O(suffix), not O(prompt)).

* **Refcounted copy-on-write blocks.** A block referenced by the index
  is immutable and shared: live sequences hold refcounts, and sharing
  is at full-block granularity so the append path never writes into a
  shared block — except the one genuine divergence: a prompt whose
  tokens are FULLY covered by cached blocks must still recompute its
  last position (the sampled first token needs that position's logits,
  which are not cached), and that write lands inside the last matched
  block. That block is COW-copied on device (one fixed-shape jitted
  copy, admission-time only) and the copy becomes sequence-private.

* **Host-RAM offload tier.** Cold blocks (refcount 0, LRU by last
  touch: the index keeps them in that order as it goes, so an eviction
  pops its victim and never walks the entries) swap out to host buffers
  instead of being dropped — including
  preempt-evicted blocks, so a preempted request's re-admission can
  swap its KV back in instead of recomputing it. Swap-in vs recompute
  is decided by the PR 7 cost-model roofline (transfer bytes over the
  host link vs recompute FLOPs/bytes over the chip roofline), and every
  executed swap-in logs its (predicted, measured) transfer time to the
  engine's PredictionLedger so calibration-drift telemetry covers the
  swap heuristic like every other prediction. Host buffers carry a CRC
  so a corrupted swap-in is detected and falls back to recompute —
  byte-exact output either way (the ``generation.kv_offload`` chaos
  site proves it).

Exactness invariant: token streams are byte-identical with caching on
and off — greedy, seeded temperature, and speculative. Sampling keys
are indexed by generated-token count (scheduler.py), so position is the
only state that matters, and reused blocks hold exactly the K/V the
suffix prefill would have recomputed.

Threading: all mutation happens on the scheduler loop thread
(admission, preemption, reclaim); the fleet router's affinity probe
reads from other threads. One lock guards the trie; steady-state decode
never takes it (prefix work is admission-time only).
"""
from __future__ import annotations

import heapq
import threading
import time
import zlib
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .cache import BlockAllocator, CacheConfig

# Host<->device link bandwidth estimate for the swap-vs-recompute
# decision (PCIe gen4 x16 order of magnitude; deliberately conservative
# — a wrong "swap" costs one transfer, a wrong "recompute" costs a full
# prefill). The decision is pure arithmetic on sizes, so it is
# deterministic run to run; drift between this constant and reality is
# exactly what the PredictionLedger pairs surface.
DEFAULT_HOST_LINK_BYTES_PER_S = 16e9
# per-swap fixed cost (dispatch + host sync), same order as the cost
# model's KERNEL_OVERHEAD
SWAP_OVERHEAD_S = 20e-6


class PrefixEntry:
    """One cached block of prefix content: a radix-trie node.

    ``block`` is the device block id while resident; ``host_k/host_v``
    hold the content while offloaded (exactly one tier is populated),
    and ``host_s`` beside them the convolution state stored with the
    block, for a configuration whose blocks carry one (cache.py): a
    block is resumed from only together with it, so it moves with it.
    ``refs`` counts live sequences whose block tables include this
    block; the index itself keeps the entry alive at refs == 0 until
    eviction. ``children`` counts child entries (any tier) — an entry
    with children is never dropped from the trie, or its descendants
    would become unreachable.

    A configuration with sliding-window layers caches a block in two
    halves (cache.py): ``block`` in the full layers' pool and ``wblock``
    (0: not held) in the window layers'. The window half is kept only as
    long as something may still want it: ``wrefs`` counts the live
    sequences whose window tables include it (a sequence drops that
    reference as it decodes past the block, long before it drops
    ``refs``), and at ``wrefs == 0`` the half is the first thing the
    window pool takes back (:meth:`PrefixCache.reclaim_window`). On the
    host tier the window half rides in ``host_s``. A prefix can be
    resumed at a boundary only if the entries of the window behind it
    still have their halves (:meth:`PrefixCache.has_window`).

    ``queued`` says the entry has its key in the index's victim order
    (:meth:`PrefixCache._evictable_now`): at most one key an entry."""

    __slots__ = (
        "eid", "parent_eid", "tokens", "depth", "block", "wblock", "wrefs",
        "host_k", "host_v", "host_s", "crc", "refs", "children", "last_touch",
        "queued",
    )

    def __init__(self, eid: int, parent_eid: int, tokens: Tuple[int, ...],
                 depth: int, block: int):
        self.eid = eid
        self.parent_eid = parent_eid
        self.tokens = tokens
        self.depth = depth  # block index within the prefix (0-based)
        self.block: Optional[int] = block
        self.wblock = 0
        self.wrefs = 0
        self.host_k: Optional[np.ndarray] = None
        self.host_v: Optional[np.ndarray] = None
        self.host_s: Optional[np.ndarray] = None
        self.crc: Optional[int] = None
        self.refs = 0
        self.children = 0
        self.last_touch = 0.0
        self.queued = False

    @property
    def resident(self) -> bool:
        return self.block is not None


def _crc(k: np.ndarray, v: np.ndarray, s: Optional[np.ndarray] = None) -> int:
    crc = zlib.crc32(v.tobytes(), zlib.crc32(k.tobytes()))
    return crc if s is None else zlib.crc32(s.tobytes(), crc)


class PackedBlock:
    """One KV block on the prefill->decode handoff wire.

    The host tier's pack format promoted to a wire format: full-head
    ``[layers, block_size, heads, head_dim]`` host arrays (the jitted
    block reader gathers all heads regardless of the source engine's
    sharding, so the wire is TP-degree-agnostic) plus the same CRC seam
    the offload tier uses — a block corrupted in flight is detected at
    the decode side before any device write happens."""

    __slots__ = ("host_k", "host_v", "crc")

    def __init__(self, host_k: np.ndarray, host_v: np.ndarray,
                 crc: Optional[int] = None):
        self.host_k = host_k
        self.host_v = host_v
        self.crc = _crc(host_k, host_v) if crc is None else crc

    def verify(self) -> bool:
        """True when the payload still matches its packing-time CRC."""
        try:
            return _crc(self.host_k, self.host_v) == self.crc
        except Exception:
            return False

    @property
    def nbytes(self) -> int:
        return int(self.host_k.nbytes) + int(self.host_v.nbytes)


class KVHandoffPayload:
    """A prefilled prompt's KV state in transit between pools.

    ``n_positions`` is the number of cache positions the payload covers
    (the full prompt length — the prefill side packs every block the
    prompt wrote, including the trailing partial one). ``geometry`` is
    the full-head per-block shape ``(layers, block_size, heads,
    head_dim)``; the importing engine checks it against its own cache
    config, NOT against the source's TP degree — head-axis resharding
    is implicit because the wire carries all heads and the target's
    jitted block writer commits into its own sharded cache."""

    __slots__ = ("n_positions", "block_size", "blocks", "geometry")

    def __init__(self, n_positions: int, block_size: int,
                 blocks: List[PackedBlock]):
        self.n_positions = n_positions
        self.block_size = block_size
        self.blocks = blocks
        self.geometry: Tuple[int, ...] = (
            tuple(blocks[0].host_k.shape) if blocks else ()
        )

    def verify(self) -> bool:
        return all(b.verify() for b in self.blocks)

    @property
    def nbytes(self) -> int:
        return sum(b.nbytes for b in self.blocks)


class PrefixCache:
    """Radix prefix index + host tier over one engine's block cache.

    Owns no device memory itself: resident entries hold block ids from
    the shared :class:`BlockAllocator` (an index-owned block is
    *outstanding* from the allocator's point of view until eviction
    frees it), and the engine performs all device reads/writes through
    the jitted block-copy programs it passes in.

    The resident, unreferenced entries (the evictable ones) are kept in
    eviction order: a binary heap of ``(last_touch, -depth, eid)`` keys,
    one key an entry at most, pushed when the entry becomes evictable. A
    touch or a reference does not move the key: ``last_touch`` only
    grows, so a stored key is a lower bound of the entry's true one, and
    :meth:`reclaim` pops the least key, pushes it back under its true
    value if the entry was touched since, and discards it if the entry
    is referenced or gone. The victims are those a scan for the least
    ``(last_touch, -depth)`` over the entries in the order of their
    ``eid`` would pick, one after the other; an orphan (its parent
    dropped from the trie) stays in the order, so its block comes back
    under pressure like any other. The heap never holds more keys than
    there are resident entries, and ``evictable_blocks``,
    ``resident_blocks`` and ``offloaded_blocks`` are counts kept where
    the entries change, so nothing a scheduler iteration calls walks
    the entries.
    """

    ROOT = 0  # parent_eid of depth-0 entries

    def __init__(
        self,
        allocator: BlockAllocator,
        config: CacheConfig,
        *,
        enabled: bool = True,
        host_budget_bytes: Optional[int] = None,
        host_link_bytes_per_s: float = DEFAULT_HOST_LINK_BYTES_PER_S,
        clock: Callable[[], float] = time.monotonic,
        state_bytes_per_block: int = 0,
    ):
        self.allocator = allocator
        # the window layers' pool, where the configuration has one (the
        # engine sets it): entries then cache a block in two halves
        self.window_allocator: Optional[BlockAllocator] = None
        self.window_dropped_total = 0
        self.config = config
        self.enabled = enabled
        # what one block occupies on either tier: its K/V and, for a
        # configuration whose blocks carry one, the convolution state
        # stored with it
        self.bytes_per_block = config.bytes_per_block + state_bytes_per_block
        # default host tier: as large as the device cache — every
        # evicted block has somewhere to go until real pressure
        self.host_budget_bytes = (
            config.num_blocks * self.bytes_per_block
            if host_budget_bytes is None else host_budget_bytes
        )
        self.host_link_bytes_per_s = host_link_bytes_per_s
        self.swap_overhead_s = SWAP_OVERHEAD_S
        self.clock = clock
        self._lock = threading.Lock()
        self._eid = 0
        # (parent_eid, token tuple) -> entry; entries by id — guarded-by: _lock
        self._edges: Dict[Tuple[int, Tuple[int, ...]], PrefixEntry] = {}
        self._by_id: Dict[int, PrefixEntry] = {}
        # the victim order: (last_touch, -depth, eid, entry), the key of
        # every evictable entry and of some that no longer are — guarded-by: _lock
        self._victims: List[Tuple[float, int, int, PrefixEntry]] = []
        # entries on the device, and those of them that are evictable:
        # written under _lock, read without it like the telemetry below
        self._resident = 0
        self._evictable = 0
        # telemetry (admission-path writes; gauges read without the
        # lock — plain ints under the GIL, same idiom as CacheTelemetry)
        self.lookups = 0
        self.hits = 0
        self.tokens_reused_total = 0
        self.blocks_reused_total = 0
        self.cow_copies_total = 0
        self.swaps_in_total = 0
        self.swaps_out_total = 0
        self.swap_in_failures = 0
        self.recompute_fallbacks = 0
        self.registered_total = 0
        self.evicted_total = 0
        self.dropped_total = 0
        self.host_bytes = 0
        # keys popped off the victim order, and those of them that named
        # no victim (pushed back under a later touch, or discarded):
        # evicted_total over victim_pops_total is the order's hit share
        self.victim_pops_total = 0
        self.victim_stale_total = 0
        # where the seconds of the host tier's two spans go (the engine
        # opens ff.cache.offload around reclaim() and ff.cache.restore
        # around a swap-in): the scheduler points this at its model's
        # ServingStats.observe; standalone, nothing listens
        self.observe: Callable[[str, float], None] = lambda name, seconds: None

    # ------------------------------------------------------------- queries
    @property
    def resident_blocks(self) -> int:
        return self._resident

    @property
    def offloaded_blocks(self) -> int:
        with self._lock:
            return len(self._by_id) - self._resident

    @property
    def evictable_blocks(self) -> int:
        """Device blocks reclaimable on demand (resident, unreferenced)
        — counted as available by the pressure telemetry, once a
        scheduler iteration."""
        return self._evictable

    @property
    def victim_keys(self) -> int:
        """Keys the victim order holds now, stale ones included."""
        with self._lock:
            return len(self._victims)

    def hit_ratio(self) -> float:
        return self.hits / self.lookups if self.lookups else 0.0

    def register_gauges(self, stats) -> None:
        """Prefix-cache telemetry (flexflow_serving_prefix_cache_*):
        hit ratio, reuse volume, COW copies, host-tier swaps and
        residency — counters ride as gauges like the cache_* family."""
        stats.add_gauge("prefix_cache_hit_ratio", self.hit_ratio)
        for name in ("blocks_reused_total", "tokens_reused_total", "cow_copies_total", "swaps_in_total",
                     "swaps_out_total", "host_bytes", "resident_blocks", "offloaded_blocks",
                     "victim_pops_total", "victim_stale_total", "victim_keys"):
            stats.add_gauge("prefix_cache_" + name, lambda name=name: getattr(self, name))

    def match(self, prompt: Sequence[int]) -> List[PrefixEntry]:
        """The longest cached run of full blocks along ``prompt``
        (resident and offloaded entries mixed), touched for LRU. Walks
        at most the blocks whose reuse could cover position
        ``len(prompt) - 2`` — the last position is ALWAYS recomputed so
        its logits exist to sample the first generated token from."""
        if not self.enabled or len(prompt) < 2:
            return []
        bs = self.config.block_size
        max_entries = (len(prompt) - 2) // bs + 1
        run: List[PrefixEntry] = []
        now = self.clock()
        with self._lock:
            parent = self.ROOT
            for j in range(max_entries):
                tok = tuple(prompt[j * bs:(j + 1) * bs])
                if len(tok) < bs:
                    break
                entry = self._edges.get((parent, tok))
                if entry is None:
                    break
                entry.last_touch = now
                run.append(entry)
                parent = entry.eid
        return run

    def probe(self, prompt: Sequence[int]) -> int:
        """Read-only matched-token count (fleet router affinity): how
        many of ``prompt``'s leading tokens are covered by cached
        blocks, capped at ``len(prompt) - 1``. No LRU touch, no
        counters — a routing probe must not look like traffic."""
        if not self.enabled or len(prompt) < 2:
            return 0
        bs = self.config.block_size
        matched = 0
        with self._lock:
            parent = self.ROOT
            for j in range((len(prompt) - 2) // bs + 1):
                tok = tuple(prompt[j * bs:(j + 1) * bs])
                if len(tok) < bs:
                    break
                entry = self._edges.get((parent, tok))
                if entry is None:
                    break
                matched += bs
                parent = entry.eid
        return min(matched, len(prompt) - 1)

    @staticmethod
    def has_window(entry: PrefixEntry) -> bool:
        """The entry still has its window half, on either tier."""
        return bool(entry.wblock) if entry.resident else entry.host_s is not None

    # ------------------------------------------------------------ refcounts
    def acquire(self, entries: Sequence[PrefixEntry]) -> None:
        now = self.clock()
        with self._lock:
            for e in entries:
                if e.refs == 0 and e.resident and self._by_id.get(e.eid) is e:
                    self._evictable -= 1  # its key goes stale where it lies
                e.refs += 1
                e.last_touch = now

    def release(self, entries: Sequence[PrefixEntry]) -> None:
        """Drop one reference per entry. Tolerates entries invalidated
        by a wholesale reset (engine crash recovery) — a stale decref
        must not corrupt the fresh index."""
        with self._lock:
            for e in entries:
                if self._by_id.get(e.eid) is e and e.refs > 0:
                    e.refs -= 1
                    if e.refs == 0 and e.resident:
                        self._evictable_now(e)

    # ----------------------------------------------------------- registration
    def register_chain(
        self,
        tokens: Sequence[int],
        blocks: Sequence[int],
        shared_idx: set,
        entries: List[PrefixEntry],
        upto_tokens: int,
    ) -> int:
        """Register ``tokens``' full blocks below ``upto_tokens`` into
        the trie, transferring ownership of the newly registered blocks
        from the sequence to the index (the sequence keeps a ref).

        ``blocks``/``shared_idx``/``entries`` are the owning sequence's
        block table, its set of already-index-owned table positions, and
        its held entries — updated in place. Existing entries are left
        alone (the sequence's own copy of that content stays private),
        except an offloaded entry holding the same content, which
        adopts the sequence's resident block (free device promotion:
        the host copy is dropped). Returns the number of entries
        registered or promoted."""
        if not self.enabled:
            return 0
        bs = self.config.block_size
        n_new = 0
        now = self.clock()
        with self._lock:
            parent = self.ROOT
            for j in range(upto_tokens // bs):
                tok = tuple(tokens[j * bs:(j + 1) * bs])
                if len(tok) < bs:
                    break
                entry = self._edges.get((parent, tok))
                if entry is None:
                    if j in shared_idx:
                        # chain broken upstream of a block we believed
                        # shared (reset raced us): stop registering
                        break
                    self._eid += 1
                    entry = PrefixEntry(self._eid, parent, tok, j, blocks[j])
                    self._edges[(parent, tok)] = entry
                    self._by_id[entry.eid] = entry
                    if parent != self.ROOT:
                        self._by_id[parent].children += 1
                    entry.refs += 1
                    entry.last_touch = now
                    self._resident += 1
                    shared_idx.add(j)
                    entries.append(entry)
                    self.registered_total += 1
                    n_new += 1
                elif j not in shared_idx and not entry.resident:
                    # promote: the index already knows this content but
                    # only on the host tier; adopt our resident block
                    self._drop_host(entry)
                    entry.block = blocks[j]
                    entry.refs += 1
                    entry.last_touch = now
                    self._resident += 1
                    shared_idx.add(j)
                    entries.append(entry)
                    n_new += 1
                entry.last_touch = now
                parent = entry.eid
        return n_new

    # ------------------------------------------------------------- eviction
    def _drop_host(self, entry: PrefixEntry) -> None:
        # what a reset() left behind is in host_bytes no longer
        if entry.host_k is not None and self._by_id.get(entry.eid) is entry:  # flexlint: disable=lock-discipline — every caller holds _lock
            self.host_bytes -= self.bytes_per_block
        entry.host_k = None
        entry.host_v = None
        entry.host_s = None
        entry.crc = None

    def _evictable_now(self, entry: PrefixEntry) -> None:
        """``entry`` (in the trie, resident) has just lost its last
        reference, or come back to the device with none: count it and
        see that the victim order has its key. A key already there is
        left where it is, a lower bound of the true one. Caller holds
        _lock."""
        self._evictable += 1
        if not entry.queued:
            entry.queued = True
            heapq.heappush(self._victims, (entry.last_touch, -entry.depth, entry.eid, entry))  # flexlint: disable=lock-discipline — caller holds _lock (see docstring)

    def _pop_victim(self) -> Optional[PrefixEntry]:
        """Take the next victim off the victim order: the evictable
        entry of least ``(last_touch, -depth, eid)``, None when nothing
        is evictable. Caller holds _lock."""
        victims = self._victims  # flexlint: disable=lock-discipline — caller holds _lock (see docstring)
        while victims:
            touch, _, eid, entry = victims[0]
            self.victim_pops_total += 1
            if entry.refs == 0 and entry.resident and self._by_id.get(eid) is entry:
                if entry.last_touch == touch:
                    heapq.heappop(victims)
                    entry.queued = False
                    return entry
                # touched since its key was stored: back under the true one
                heapq.heapreplace(victims, (entry.last_touch, -entry.depth, eid, entry))
            else:
                # referenced (release() queues it again), or gone
                heapq.heappop(victims)
                entry.queued = False
            self.victim_stale_total += 1
        return None

    def _remove(self, entry: PrefixEntry) -> None:
        """Drop ``entry``, which no longer holds a device block, from
        the trie entirely. Caller holds _lock (reclaim and
        _enforce_host_budget both invoke this inside their ``with
        self._lock:`` blocks)."""
        self._drop_host(entry)
        del self._edges[(entry.parent_eid, entry.tokens)]  # flexlint: disable=lock-discipline — caller holds _lock (see docstring)
        del self._by_id[entry.eid]
        parent = self._by_id.get(entry.parent_eid)
        if parent is not None:
            parent.children -= 1
        self.dropped_total += 1

    def reclaim(
        self,
        n_blocks: int,
        read_block: Optional[Callable[[int], Tuple[np.ndarray, ...]]] = None,
    ) -> int:
        """Free up to ``n_blocks`` device blocks by evicting refcount-0
        resident entries, LRU by last touch. Each eviction offloads the
        block's content to the host tier when ``read_block`` is given
        and the host budget allows (the caller wraps the device read
        with the ``generation.kv_offload`` fault site and may raise to
        simulate a failed swap-out — the entry is then dropped instead,
        which is always safe: a dropped block is just a future
        recompute). Returns blocks actually freed."""
        if not self.enabled:
            return 0
        freed = 0
        while freed < n_blocks:
            with self._lock:
                victim = self._pop_victim()
                if victim is None:
                    break
                # an orphan (its parent already dropped from the trie)
                # can never be reached by match() again: drop it free
                # instead of paying a device read + host budget for
                # permanently dead content
                reachable = (
                    victim.parent_eid == self.ROOT
                    or victim.parent_eid in self._by_id
                )
            offloaded = False
            if (
                reachable
                and read_block is not None
                and self.host_bytes + self.bytes_per_block
                <= self.host_budget_bytes
            ):
                try:
                    # (K, V) or, where blocks carry one, (K, V, state);
                    # a window half held is read out with its block
                    hk, hv, *hs = read_block(victim.block, victim.wblock) if victim.wblock else read_block(victim.block)
                    with self._lock:
                        victim.host_k = np.asarray(hk)
                        victim.host_v = np.asarray(hv)
                        victim.host_s = np.asarray(hs[0]) if hs else None
                        victim.crc = _crc(victim.host_k, victim.host_v, victim.host_s)
                        self.host_bytes += self.bytes_per_block
                        self.swaps_out_total += 1
                    offloaded = True
                except Exception:
                    offloaded = False  # failed swap-out: drop instead
            with self._lock:
                block, victim.block = victim.block, None
                wblock, victim.wblock = victim.wblock, 0
                self._resident -= 1
                self._evictable -= 1
                if not offloaded:
                    # dropped: no tier holds the content, so the node
                    # leaves the trie. Descendants are orphaned (the
                    # match walk can no longer reach them) but stay
                    # evictable — their keys stay in the victim order,
                    # so their blocks still come back under pressure and
                    # their own removal tolerates the missing parent.
                    self._remove(victim)
                self.evicted_total += 1
            self.allocator.free([block])
            if wblock:
                self.window_allocator.free([wblock])
            freed += 1
        self._enforce_host_budget()
        return freed

    def reclaim_window(self, n_blocks: int) -> int:
        """Free up to ``n_blocks`` blocks of the WINDOW pool by dropping
        the window halves no live sequence's table includes (``wrefs ==
        0``), LRU by last touch. The entry stays, with its full half: it
        still serves as the history of a longer prefix, and can be
        resumed FROM only while the halves of the window behind the
        boundary are held. Nothing is read out: a half alone has no
        place on the host tier."""
        with self._lock:
            cands = [e for e in self._by_id.values() if e.resident and e.wblock and e.wrefs == 0]
            cands.sort(key=lambda e: (e.last_touch, -e.depth))
            blocks = []
            for victim in cands[:max(0, n_blocks)]:
                blocks.append(victim.wblock)
                victim.wblock = 0
            self.window_dropped_total += len(blocks)
        self.window_allocator.free(blocks)
        return len(blocks)

    def _enforce_host_budget(self) -> None:
        """Drop LRU offloaded leaves until the host tier fits its
        budget. Internal offloaded entries (with children) are kept —
        dropping them would strand reachable descendants; the overshoot
        is bounded by the trie's internal-node count and drains as
        children age out."""
        while True:
            with self._lock:
                if self.host_bytes <= self.host_budget_bytes:
                    return
                leaves = [
                    e for e in self._by_id.values()
                    if not e.resident and e.children == 0 and e.refs == 0
                    and e.host_k is not None
                ]
                if not leaves:
                    return
                victim = min(leaves, key=lambda e: e.last_touch)
                self._remove(victim)

    # --------------------------------------------------------------- tiers
    def take_host_copy(
        self, entry: PrefixEntry
    ) -> Optional[Tuple[np.ndarray, ...]]:
        """The entry's host buffers — (K, V) or, where blocks carry one,
        (K, V, state) — CRC-verified. None (and the entry
        dropped from the trie entirely) when the content is corrupt —
        the caller falls back to recompute. Removal, not just a host
        drop: a tier-less node would still count as ``offloaded`` and
        break the host-bytes conservation invariant on the next scrape
        (a held ref is safe — release() ignores removed entries)."""
        with self._lock:
            hk, hv, hs, crc = entry.host_k, entry.host_v, entry.host_s, entry.crc
        if hk is None or hv is None:
            return None
        if _crc(hk, hv, hs) != crc:
            with self._lock:
                if self._by_id.get(entry.eid) is entry:
                    self._remove(entry)
                else:
                    self._drop_host(entry)
            return None
        return (hk, hv) if hs is None else (hk, hv, hs)

    def note_swapped_in(self, entry: PrefixEntry, block: int, wblock: int = 0) -> None:
        """The entry's content was written into device ``block``: it is
        resident again; the host copy is retained only if budget is
        slack (re-offload is then free) — dropped here for simplicity
        and budget honesty."""
        with self._lock:
            self._drop_host(entry)
            entry.block = block
            entry.wblock = wblock
            entry.last_touch = self.clock()
            self.swaps_in_total += 1
            if self._by_id.get(entry.eid) is entry:
                self._resident += 1
                if entry.refs == 0:
                    self._evictable_now(entry)

    # ------------------------------------------------------ decision model
    def swap_in_cost_s(self, n_blocks: int) -> float:
        bytes_total = n_blocks * self.bytes_per_block
        return self.swap_overhead_s + bytes_total / self.host_link_bytes_per_s

    # ------------------------------------------------------------ lifecycle
    def reset(self) -> None:
        """Wholesale invalidation after an engine crash/reset: the
        allocator's free list was restored and the device cache
        rezeroed, so every entry — resident ids AND host copies (their
        provenance is the dead cache) — is dropped without per-block
        frees. Journal replay then re-matches against an empty index,
        which is trivially correct (recompute)."""
        with self._lock:
            self._edges.clear()
            self._by_id.clear()
            self._victims.clear()
            self._resident = self._evictable = 0
            self.host_bytes = 0

    # -------------------------------------------------------------- report
    def snapshot(self) -> Dict:
        with self._lock:
            resident = self._resident
            offloaded = len(self._by_id) - resident
            shared = sum(1 for e in self._by_id.values() if e.refs > 0)
        return {
            "enabled": self.enabled,
            "resident_blocks": resident,
            "offloaded_blocks": offloaded,
            "shared_blocks": shared,  # resident entries referenced by >=1 stream
            "host_bytes": self.host_bytes,
            "host_budget_bytes": self.host_budget_bytes,
            "lookups": self.lookups,
            "hits": self.hits,
            "hit_ratio": self.hit_ratio(),
            "tokens_reused_total": self.tokens_reused_total,
            "blocks_reused_total": self.blocks_reused_total,
            "cow_copies_total": self.cow_copies_total,
            "swaps_in_total": self.swaps_in_total,
            "swaps_out_total": self.swaps_out_total,
            "swap_in_failures": self.swap_in_failures,
            "recompute_fallbacks": self.recompute_fallbacks,
            "registered_total": self.registered_total,
            "evicted_total": self.evicted_total,
            "victim_pops_total": self.victim_pops_total,
            "victim_stale_total": self.victim_stale_total,
            "victim_keys": self.victim_keys,
            **({"window_dropped_total": self.window_dropped_total} if self.window_allocator is not None else {}),
        }

    def tier_residency(self) -> List[Dict]:
        """Per-entry tier table for ``obsreport cache`` (bounded: the
        trie never exceeds the allocator's block count plus the host
        budget's block count)."""
        with self._lock:
            return [
                {
                    "depth": e.depth,
                    "tier": "device" if e.resident else "host",
                    "block": e.block,
                    "refs": e.refs,
                    "last_touch": e.last_touch,
                }
                for e in sorted(
                    self._by_id.values(), key=lambda e: (e.depth, e.eid)
                )
            ]
