"""The prefix index's victim order (ISSUE 56): ``PrefixCache`` keeps its
evictable entries in eviction order and its counts as it goes, and no
path of a scheduler iteration walks every entry.

  * the victims are the plain scan's, one after the other, on seeded
    random walks over every operation that changes the order — the scan
    (what ``reclaim`` did before it kept an order) lives here as the oracle
  * after every operation the counts equal a recount over the entries
  * the order holds no more keys than there are resident entries
  * cost by count: an eviction inspects O(1 + stale) keys and the counts
    touch no entry
"""
import itertools
import random

import numpy as np
import pytest

from flexflow_tpu.generation.cache import BlockAllocator, CacheConfig
from flexflow_tpu.generation.prefix import PrefixCache
from flexflow_tpu.obs.capacity import CacheTelemetry

from conftest import FakeClock  # noqa: E402

pytestmark = pytest.mark.generation

BLOCK = 4


def scan_victim(pc, gone=()):
    """The plain scan: every resident unreferenced entry, the least
    ``(last_touch, -depth)``, ties as ``min`` over ``_by_id`` breaks them."""
    cands = [e for e in pc._by_id.values() if e.resident and e.refs == 0 and e.eid not in gone]
    return min(cands, key=lambda e: (e.last_touch, -e.depth)) if cands else None


def recount(pc):
    entries = list(pc._by_id.values())
    return {
        "resident": sum(1 for e in entries if e.resident),
        "offloaded": sum(1 for e in entries if not e.resident),
        "evictable": sum(1 for e in entries if e.resident and e.refs == 0),
        "host_bytes": pc.bytes_per_block * sum(1 for e in entries if e.host_k is not None),
    }


def assert_counts(pc):
    assert {
        "resident": pc.resident_blocks, "offloaded": pc.offloaded_blocks,
        "evictable": pc.evictable_blocks, "host_bytes": pc.host_bytes,
    } == recount(pc)
    snap = pc.snapshot()
    assert (snap["resident_blocks"], snap["offloaded_blocks"]) == (pc.resident_blocks, pc.offloaded_blocks)
    # the order: a key for every evictable entry, one an entry at most,
    # each a lower bound of its entry's true key
    keyed = [entry for *_, entry in pc._victims]
    assert len(keyed) == len({id(e) for e in keyed}) == pc.victim_keys <= pc.resident_blocks
    assert all(e.queued and touch <= e.last_touch for touch, _, _, e in pc._victims)
    assert all(e.queued for e in pc._by_id.values() if e.resident and e.refs == 0)


class Recording(BlockAllocator):
    """An allocator that remembers the order blocks came back in."""

    def __init__(self, config):
        super().__init__(config)
        self.freed = []

    def free(self, blocks):
        self.freed.extend(blocks)
        super().free(blocks)


class Walk:
    """A seeded walk over one ``PrefixCache``, driven as the engine
    drives it: admissions that match, acquire, swap in and register;
    sequences that finish; reclaims of every kind; resets."""

    def __init__(self, seed, budget_blocks, window):
        self.rng = random.Random(seed)
        self.config = CacheConfig(num_layers=1, num_heads=1, head_dim=2, num_blocks=40, block_size=BLOCK)
        self.alloc = Recording(self.config)
        self.clock = FakeClock()
        self.pc = PrefixCache(
            self.alloc, self.config, clock=self.clock,
            host_budget_bytes=None if budget_blocks is None else budget_blocks * self.config.bytes_per_block,
        )
        self.walloc = None
        if window:
            self.walloc = self.pc.window_allocator = BlockAllocator(
                CacheConfig(num_layers=1, num_heads=1, head_dim=2, num_blocks=16, block_size=BLOCK))
        self.live, self.stale, self.seen = [], [], []
        self.victims = self.orphans = self.ties = self.read_failures = 0

    # ---------------------------------------------------------------- device
    @staticmethod
    def fails(block):
        return block % 7 == 3

    def read(self, block, wblock=0):
        if self.fails(block):
            raise RuntimeError("swap-out failed")
        k = np.full((1, BLOCK, 1, 2), block, np.float32)
        return (k, -k, np.full((3,), wblock, np.float32)) if wblock else (k, -k)

    # ---------------------------------------------------------------- oracle
    def reclaim(self, n, read):
        """``pc.reclaim(n, read)``, every victim and its fate held
        against what the plain scan would pick and do."""
        pc, expect, gone, dropped = self.pc, [], set(), set()
        host = pc.host_bytes
        for _ in range(n):
            victim = scan_victim(pc, gone)
            if victim is None:
                break
            gone.add(victim.eid)
            rivals = [e for e in pc._by_id.values() if e.resident and e.refs == 0 and e.eid not in gone]
            self.ties += any((e.last_touch, e.depth) == (victim.last_touch, victim.depth) for e in rivals)
            reachable = victim.parent_eid == pc.ROOT or (victim.parent_eid in pc._by_id and victim.parent_eid not in dropped)
            self.orphans += not reachable
            offload = (reachable and read is not None and host + pc.bytes_per_block <= pc.host_budget_bytes
                       and not (read == self.read and self.fails(victim.block)))
            self.read_failures += bool(reachable and read == self.read and self.fails(victim.block))
            if offload:
                host += pc.bytes_per_block
            else:
                dropped.add(victim.eid)
            expect.append((victim, victim.block, victim.wblock, offload))
        before, evicted = len(self.alloc.freed), pc.evicted_total
        wfree = self.walloc.num_free if self.walloc else 0
        assert pc.reclaim(n, read) == len(expect)
        assert self.alloc.freed[before:] == [block for _, block, _, _ in expect]
        assert pc.evicted_total - evicted == len(expect)
        if self.walloc:
            assert self.walloc.num_free - wfree == sum(1 for _, _, wblock, _ in expect if wblock)
        for victim, block, wblock, offload in expect:
            assert not victim.resident and not victim.wblock
            if offload:
                assert pc._by_id[victim.eid] is victim and float(victim.host_k[0, 0, 0, 0]) == block
                assert (victim.host_s is not None) == bool(wblock)
            else:
                assert victim.eid not in pc._by_id and victim.host_k is None
        self.victims += len(expect)
        return len(expect)

    # ------------------------------------------------------------ operations
    def take(self, n):
        """``n`` blocks of the pool, evicting for them as the engine does."""
        blocks = self.alloc.allocate(n)
        if blocks is None:
            self.reclaim(n - self.alloc.num_free, self.rng.choice([self.read, None]))
            blocks = self.alloc.allocate(n)
        return blocks

    def prompt(self):
        """A whole number of blocks and one token more: a fresh chain, a
        chain seen before, one cut short (its parents alone are touched)
        or one grown longer (children under entries another sequence owns)."""
        rng = self.rng
        kind = rng.random() if self.seen else 0.0
        if kind < 0.3:
            family = rng.randrange(5)
            blocks = [(family, depth, rng.randrange(2)) for depth in range(rng.randint(1, 4))]
        else:
            blocks = list(rng.choice(self.seen))
            if kind < 0.45:
                del blocks[rng.randint(1, len(blocks)):]
            elif kind < 0.9 and len(blocks) < 6:
                blocks += [(blocks[0][0], len(blocks) + i, rng.randrange(2)) for i in range(rng.randint(1, 2))]
        self.seen = self.seen[-30:] + [tuple(blocks)]
        return [t for b in blocks for t in (*b, 0)] + [0]

    def admit(self):
        pc, rng = self.pc, self.rng
        prompt = self.prompt()
        # (a failed lookup degrades to a miss: the sequence then registers
        # children under entries it holds no reference to)
        run = pc.match(prompt) if rng.random() < 0.5 else []
        pc.acquire(run)
        kept = []
        for entry in run:
            if not entry.resident:
                dst = self.take(1)
                if dst is None:
                    break
                if rng.random() < 0.15:
                    entry.host_k = entry.host_k + 1  # corrupted on the host
                buf = pc.take_host_copy(entry)
                if buf is None:
                    assert entry.eid not in pc._by_id
                    self.alloc.free(dst)
                    break
                wblock = 0
                if len(buf) == 3 and rng.random() < 0.7:
                    wblock = (self.walloc.allocate(1) or [0])[0]
                pc.note_swapped_in(entry, dst[0], wblock)
            kept.append(entry)
        pc.release(run[len(kept):])
        private = self.take(len(prompt) // BLOCK - len(kept))
        if private is None:
            pc.release(kept)
            return
        table, shared, entries = [e.block for e in kept] + private, set(range(len(kept))), list(kept)
        pc.register_chain(prompt, table, shared, entries, len(prompt))
        held = []
        if self.walloc:
            for entry in entries[len(kept):]:
                if not entry.wblock and rng.random() < 0.7:
                    if not self.walloc.num_free:
                        pc.reclaim_window(2)
                    got = self.walloc.allocate(1)
                    if got:
                        entry.wblock, entry.wrefs = got[0], entry.wrefs + 1
                        held.append(entry)
        self.live.append((table, shared, entries, held))

    def finish(self, seq):
        table, shared, entries, held = seq
        for entry in held:
            entry.wrefs -= 1
        self.pc.release(entries)
        self.alloc.free([b for j, b in enumerate(table) if j not in shared])

    def step(self):
        rng, pc = self.rng, self.pc
        if rng.random() < 0.25:
            self.clock.advance(1.0)  # otherwise this operation ties with the last
        op = rng.random()
        if op < 0.40:
            self.admit()
        elif op < 0.65 and self.live:
            self.finish(self.live.pop(rng.randrange(len(self.live))))
        elif op < 0.75:
            run = pc.match(self.prompt())  # a touch alone
            if run and rng.random() < 0.5:
                # the boundary block held alone while it is copied: from
                # here on it is younger than its parents
                self.clock.advance(1.0)
                pc.acquire(run[-1:])
                pc.release(run[-1:])
        elif op < 0.93:
            self.reclaim(rng.randint(1, 4), rng.choice([self.read, self.read, None]))
        elif op < 0.96 and self.walloc:
            pc.reclaim_window(rng.randint(1, 3))
        elif op < 0.975:
            pc.reset()
            self.alloc.reset()
            if self.walloc:
                self.walloc.reset()
            self.stale, self.live = self.stale + self.live, []
        elif self.stale:
            # what a reset left behind lets go late: nothing may move
            _, _, entries, _ = self.stale.pop()
            pc.release(entries)
            for entry in entries:
                if entry.host_k is not None:
                    entry.host_k = entry.host_k + 1
                    assert pc.take_host_copy(entry) is None


@pytest.mark.parametrize("window", [False, True], ids=["plain", "window"])
@pytest.mark.parametrize("budget_blocks", [3, None], ids=["tight", "default"])
@pytest.mark.parametrize("seed", range(6))
def test_walk_victims_are_the_scans_and_counts_hold(seed, budget_blocks, window):
    walk = Walk(seed, budget_blocks, window)
    for _ in range(3000):
        walk.step()
        assert_counts(walk.pc)
    # conservation: what the pool misses is the live sequences' and the index's
    private = sum(1 for table, shared, _, _ in walk.live for j in range(len(table)) if j not in shared)
    assert walk.alloc.num_total - walk.alloc.num_free == private + walk.pc.resident_blocks
    for seq in walk.live:
        walk.finish(seq)
    walk.reclaim(100, walk.read)
    assert_counts(walk.pc)
    assert walk.pc.resident_blocks == walk.pc.evictable_blocks == walk.pc.victim_keys == 0
    assert walk.alloc.num_free == walk.alloc.num_total
    # the walk met what it was built to meet
    assert walk.victims > 500 and walk.ties > 40 and walk.orphans >= 3 and walk.read_failures > 40
    assert walk.pc.swaps_in_total >= 5 and walk.pc.swaps_out_total > 100
    assert walk.pc.victim_stale_total > 100 and walk.pc.victim_pops_total == walk.pc.evicted_total + walk.pc.victim_stale_total


def filled(n, budget_blocks=0):
    """An index of ``n`` chains of one block each, all evictable, and its clock."""
    config = CacheConfig(num_layers=1, num_heads=1, head_dim=2, num_blocks=n + 1, block_size=BLOCK)
    alloc = BlockAllocator(config)
    clock = itertools.count(1.0)  # every touch at a time of its own
    pc = PrefixCache(alloc, config, clock=lambda: next(clock),
                     host_budget_bytes=budget_blocks * config.bytes_per_block)
    chains = []
    for i in range(n):
        prompt = [i, 0, 0, 0, 0]
        entries = []
        pc.register_chain(prompt, alloc.allocate(1), set(), entries, len(prompt))
        chains.append((prompt, entries))
    for _, entries in chains:
        pc.release(entries)
    return pc, chains


@pytest.mark.parametrize("touch", ["match", "acquire_release"])
def test_order_stays_bounded_under_touches(touch):
    pc, chains = filled(1000)
    rng = random.Random(0)
    for _ in range(100_000):
        prompt, entries = chains[rng.randrange(1000)]
        if touch == "match":
            assert pc.match(prompt) == entries
        else:
            pc.acquire(entries)
            pc.release(entries)
    assert pc.victim_keys == pc.evictable_blocks == 1000
    # and the victims still come in the order of the last touch
    order = sorted(chains, key=lambda c: c[1][0].last_touch)
    blocks = [entries[0].block for _, entries in order[:10]]
    freed = []
    pc.allocator.free = freed.extend
    assert pc.reclaim(10) == 10 and freed == blocks


class NoWalk(dict):
    """An entry table that cannot be walked."""

    def _refuse(self, *a):
        raise AssertionError("the entries were walked")

    __iter__ = values = items = keys = _refuse


@pytest.mark.parametrize("stale", [0, 7, 300])
def test_eviction_cost_is_counted_in_keys_not_entries(stale):
    """Freeing ``k`` blocks among ``N`` evictable entries inspects
    ``k`` keys and those gone stale before them, and neither that nor
    the scheduler's tick walks the entries."""
    n, k = 5000, 50
    pc, chains = filled(n)
    for prompt, entries in chains[:stale]:  # the oldest: each key now lies too low
        pc.match(prompt)
    pc._by_id = NoWalk(pc._by_id)
    telemetry = CacheTelemetry(pc.allocator, reclaimable=lambda: pc.evictable_blocks)
    telemetry.tick()
    assert (pc.evictable_blocks, pc.resident_blocks, pc.offloaded_blocks) == (n, n, 0)
    assert pc.reclaim(k) == k
    assert pc.victim_pops_total == k + stale and pc.victim_stale_total == stale
    assert (pc.evictable_blocks, pc.resident_blocks, pc.victim_keys) == (n - k, n - k, n - k)
    assert not telemetry.under_pressure
    # referenced entries' keys are found stale once, and not again
    held = [e for _, entries in chains[stale + k:stale + k + 20] for e in entries]
    pc.acquire(held)
    assert pc.reclaim(1) == 1 and pc.victim_pops_total == k + stale + 21
    assert pc.reclaim(1) == 1 and pc.victim_pops_total == k + stale + 22
    pc.release(held)
    assert pc.victim_keys == pc.evictable_blocks == n - k - 2
