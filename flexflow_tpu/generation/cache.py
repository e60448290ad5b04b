"""Block-structured KV cache: preallocated device storage + host-side
block accounting.

vLLM/PagedAttention (SOSP'23) adapted to XLA's static-shape constraint:
the cache is ONE preallocated array per K/V — ``[L, num_blocks,
block_size, H, D]`` — and a sequence's cache is a *block table* (list of
block ids) into it. Appending a token writes one ``(block, offset)``
slot; nothing is ever moved or reallocated, so every jitted step sees
the same cache shape regardless of how many sequences are live or how
long they've grown. The reference has no KV cache at all (its attention
is a one-shot cuDNN call, SURVEY §2.2).

"Nothing is moved" holds on the device because of three things that only
work together (ISSUE 24; with any one missing the compiler puts
cache-sized copies back into every decode step):

* the arrays are **stored in a shape whose default device layout is the
  one the paged kernel reads**: ``[L, num_blocks, block_size, R, LW]``,
  a position's ``H x D`` values laid head after head over ``R`` rows of
  ``LW`` lanes (``CacheConfig.row_shape``; two heads of 64 share a
  128-lane row, so 16 x 64 is stored as 8 x 128). Stored as ``[..., 16,
  64]`` the TPU compiler lays the array out with ``num_blocks`` on the
  lanes, because a ``head_dim`` of 64 half-fills them; a Mosaic kernel
  reads its operands row-major, so every program that called the paged
  kernel converted both arrays on entry and back on exit. The default
  layout of ``[..., 8, 128]`` is row-major and unpadded, and the default
  is what every program agrees on without being told — including one
  loaded from the persistent compile cache, which drops a layout
  declared through ``jax.experimental.layout`` (measured, PR 24). A
  row-major reshape gives the logical ``[..., H, D]`` view wherever one
  is wanted (the XLA composition, the block programs' host side);
* the decode/verify programs **donate** both arrays and write a step's
  rows with one scatter on the whole 5-D operand
  (``decoder.py::write_rows``) — no layer is ever sliced out, rebuilt
  and written back;
* the paged kernel takes the **whole cache and a static layer index**
  (``ops/kernels/decode_attention.py``) and DMAs single blocks out of it.

**Two kinds of state in one manager.** The K/V arrays hold the
ATTENTION layers only (``CacheConfig.num_layers`` counts those, and
``num_heads`` the K/V heads: grouped queries store the heads they read).
A layer whose operator is a gated short convolution
(generation/decoder.py) keeps instead the last ``K - 1`` rows of its
gated input per sequence, which is not paged: :class:`StateConfig`
describes it, and :class:`KVCache` holds it beside K/V as

* ``conv`` ``[n_conv, slots, K - 1, E]`` — every batch slot's state: a
  prefill writes its slot's at the sequence's own length, a decode step
  reads, shifts and writes all of it in place (donated, like K/V);
* ``snap`` ``[n_conv, num_blocks, K - 1, E]`` — the state at the END of
  a block, indexed by block id exactly as K/V is, written by the prefill
  programs for every full block they write. A block can be resumed from
  only together with it, so it lives, moves to the host tier and comes
  back with the block (generation/prefix.py); a decode step never
  touches it.

A configuration without such layers has neither array, and its programs
are what they were.

**Two kinds of attention layer in one manager.** A sliding-window layer
(``DecoderConfig.window``) never reads a position more than ``window``
behind its query, so keeping its K/V for a sequence's whole history
would hold blocks nothing can reach. Its K/V lives in arrays of its own,
``wk`` / ``wv`` ``[n_window, window_blocks, block_size, R, LW]`` (held in
``KVCache.state`` beside the convolution arrays, carried and donated
with them), over a POOL of its own (a second :class:`CacheConfig` and
:class:`BlockAllocator`) and a table of its own per sequence
(:class:`WindowTable`): the blocks of consecutive block indices from
``first`` on, where ``first`` moves up as the sequence decodes. Before a
step is dispatched, every block wholly behind ``position - window + 1``
is released (back to the pool, or to the prefix entry that owns it) and
a block is taken where the step's position starts one: a live sequence
holds at most ``ceil(window / block_size) + 1`` blocks of the window
pool, and the window layers' paged call walks that many columns and not
the history's (ops/kernels/decode_attention.py takes the table with the
position of its column 0). The full layers keep ``k`` / ``v`` and the
one table a sequence has always had. The pools are sized by what a
sequence can hold of each (:meth:`CacheConfig.for_slots` with
``window=``, :func:`pools_from_budget`). A configuration without window
layers has one pool, and builds the cache it has always built.

**Latent rows.** A latent-attention layer (generation/decoder.py) caches
ONE row a position — ``[c, k_r]``, ``kv_lora_rank + qk_rope_head_dim``
values shared by all heads — from which the absorbed form reads scores
and values alike. Such a configuration's :class:`CacheConfig` is
``latent``: ``k`` is ``[L, num_blocks, block_size, RW]`` with ``RW`` the
row's width at the next multiple of 128 lanes (576 -> 640, the fill
zero; ``CacheConfig.row_shape``), ``v`` has NO width (``[L, num_blocks,
block_size, 0]``: every program carries it as it carries an empty
pytree, and block reads, copies and the host tier move nothing for it),
and everything that counts bytes — blocks, pools, the host tier's budget
— counts the one stored row. Why this shape and not ``[..., 5, 128]`` or
a second array for the rotary part: ops/kernels/decode_attention.py. The
three conditions above hold for it as they do for K/V: default layout
row-major, donated, one scatter on the whole operand, the kernel taking
the whole array and a static layer index.

**State of named parts, per slot only.** A state-space layer
(generation/decoder.py ``ssm``) keeps two arrays of different shape and
type a sequence (:class:`SlotStateConfig`), megabytes of them: they live
in ``KVCache.state`` under their names, ``[n_layers, slots, *shape]``,
carried and donated with K/V by the decode step, written for one slot by
a prefill's hand-over, and never snapshotted a block: an engine with such
layers keeps no prefix index.

Block 0 is reserved as a **scratch block**: padded prompt positions and
inactive decode slots scatter their (meaningless) K/V there, so the
jitted steps never need dynamic shapes or masked scatters to avoid
corrupting live sequences. The allocator simply never hands out
block 0.
"""
from __future__ import annotations

import dataclasses
import math
import threading
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.types import DataType
from ..ops.kernels.decode_attention import cache_row_shape, latent_row_width


@dataclasses.dataclass(frozen=True)
class CacheConfig:
    """Geometry of the block-structured cache.

    ``num_blocks`` INCLUDES the reserved scratch block 0, so the usable
    capacity is ``(num_blocks - 1) * block_size`` token positions.
    ``kv_shards`` is the serving mesh's tensor-parallel degree: the
    arrays shard their row axis over it, so heads are packed into rows
    shard by shard (:attr:`row_shape`).
    """

    num_layers: int
    num_heads: int
    head_dim: int
    num_blocks: int
    block_size: int = 16
    dtype: DataType = DataType.FLOAT
    kv_shards: int = 1
    # > 0: the pool of the sliding-window layers, whose sequences keep
    # only the blocks the `window` positions behind a query can touch
    window: int = 0
    # a latent layer's cache (module docstring): ONE row of `num_heads x
    # head_dim` values a position (one "head" of the row's width), no V
    latent: bool = False

    def __post_init__(self):
        if self.num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is scratch)")
        if self.block_size < 1:
            raise ValueError("block_size must be >= 1")

    @property
    def row_shape(self) -> Tuple[int, int]:
        """``(R, LW)``: one position's ``H x D`` values as the arrays
        store them (ops/kernels/decode_attention.py::cache_row_shape,
        applied to one shard's heads so that sharding the row axis is
        sharding the heads)."""
        if self.latent:
            return (latent_row_width(self.num_heads * self.head_dim),)
        rows, lanes = cache_row_shape(self.num_heads // self.kv_shards, self.head_dim)
        return rows * self.kv_shards, lanes

    @property
    def value_row_shape(self) -> Tuple[int, ...]:
        """What ``v`` stores a position: K's shape, or nothing where the
        values are read out of K's row (a latent cache)."""
        return (0,) if self.latent else self.row_shape

    @property
    def bytes_per_token(self) -> int:
        """Bytes one cached position occupies across all layers, as
        stored: K + V, or a latent cache's one row at its lane-filled
        width."""
        return _token_bytes(self.num_layers, self.num_heads, self.head_dim, self.dtype, self.latent)

    @property
    def bytes_per_block(self) -> int:
        """Bytes one block occupies across all layers (K + V; a latent
        cache: its rows)."""
        return self.block_size * self.bytes_per_token

    @property
    def total_bytes(self) -> int:
        return self.num_blocks * self.bytes_per_block

    @property
    def usable_tokens(self) -> int:
        return (self.num_blocks - 1) * self.block_size

    def blocks_for(self, num_tokens: int) -> int:
        """Blocks needed to hold ``num_tokens`` cache positions."""
        return -(-max(0, num_tokens) // self.block_size)

    def blocks_per_sequence(self, max_seq_len: int) -> int:
        """The most blocks one live sequence holds of this pool: its
        whole length or, in a window pool, the window and the rest of
        the block it starts in."""
        return _per_sequence(max_seq_len, self.block_size, self.window)

    @classmethod
    def from_budget(
        cls,
        budget_bytes: int,
        num_layers: int,
        num_heads: int,
        head_dim: int,
        block_size: int = 16,
        dtype: DataType = DataType.FLOAT,
        kv_shards: int = 1,
        latent: bool = False,
    ) -> "CacheConfig":
        """Size the cache against a PER-DEVICE HBM budget:

            num_blocks = budget * kv_shards
                         // (2 * L * block_size * H * D * dtype_bytes)

        (the README's cache-budget sizing formula). ``kv_shards`` is the
        serving mesh's tensor-parallel degree: the cache shards along
        the head axis (generation/sharding.py), so each device holds
        ``H / kv_shards`` heads of every block and the SAME byte budget
        per chip buys ``kv_shards`` x the block count — the whole point
        of sharded serving. Raises when the heads don't divide across
        the shards, or when the budget cannot hold even scratch + one
        usable block.
        """
        from .sharding import validate_kv_shards

        validate_kv_shards(num_heads, kv_shards)
        per_block = block_size * _token_bytes(num_layers, num_heads, head_dim, dtype, latent)
        num_blocks = budget_bytes * kv_shards // per_block
        if num_blocks < 2:
            raise ValueError(
                f"cache budget {budget_bytes}B x {kv_shards} shard(s) holds "
                f"{num_blocks} blocks of {per_block}B; need >= 2 "
                f"(scratch + one usable)"
            )
        return cls(
            num_layers=num_layers,
            num_heads=num_heads,
            head_dim=head_dim,
            num_blocks=int(num_blocks),
            block_size=block_size,
            dtype=dtype,
            kv_shards=kv_shards,
            latent=latent,
        )

    @classmethod
    def for_slots(
        cls,
        num_layers: int,
        num_heads: int,
        head_dim: int,
        max_seq_len: int,
        max_batch_slots: int,
        block_size: int = 16,
        dtype: DataType = DataType.FLOAT,
        expected_prefix_sharing: float = 0.0,
        window: int = 0,
        extra_blocks: int = 0,
        latent: bool = False,
    ) -> "CacheConfig":
        """Worst-case slot sizing with the sharing-aware discount
        (ROADMAP item 2): the default bound gives every slot room to
        reach ``max_seq_len``, but a fleet of templated traffic shares
        long prompt prefixes through the radix cache
        (generation/prefix.py) and needs far fewer private blocks per
        slot. ``expected_prefix_sharing`` in [0, 1) discounts the
        aggregate bound by the fraction of cache positions expected to
        be shared — 0.5 on a two-template workload roughly halves the
        reservation — floored at one slot's full bound plus one block
        per remaining slot, so a single unshared stream can always run
        to ``max_seq_len`` and every slot can hold at least its COW
        boundary block. ``window`` > 0 sizes a window pool: a slot
        holds :meth:`blocks_per_sequence` and no more, whatever its
        length (no sharing discount: the bound is what the release
        relies on), plus ``extra_blocks`` for the one admission whose
        suffix prefill reads a matched prefix's window beside its own
        blocks.
        """
        if not 0.0 <= expected_prefix_sharing < 1.0:
            raise ValueError(
                f"expected_prefix_sharing must be in [0, 1), got "
                f"{expected_prefix_sharing}"
            )
        per_seq = _per_sequence(max_seq_len, block_size, window)
        worst = per_seq * max_batch_slots
        if window:
            return cls(
                num_layers=num_layers, num_heads=num_heads, head_dim=head_dim,
                num_blocks=1 + worst + extra_blocks, block_size=block_size, dtype=dtype, window=window,
            )
        discounted = int(-(-worst * (1.0 - expected_prefix_sharing) // 1))
        floor = per_seq + max(0, max_batch_slots - 1)
        return cls(
            num_layers=num_layers,
            num_heads=num_heads,
            head_dim=head_dim,
            num_blocks=1 + max(floor, discounted),
            block_size=block_size,
            dtype=dtype,
            latent=latent,
        )


def _token_bytes(num_layers: int, num_heads: int, head_dim: int, dtype: DataType, latent: bool) -> int:
    if latent:
        return num_layers * latent_row_width(num_heads * head_dim) * dtype.size_bytes
    return 2 * num_layers * num_heads * head_dim * dtype.size_bytes


def _per_sequence(max_seq_len: int, block_size: int, window: int) -> int:
    whole = -(-max_seq_len // block_size)
    return min(whole, -(-window // block_size) + 1) if window else whole


def pools_from_budget(
    budget_bytes: int, max_seq_len: int, full: Dict, window: Dict, kv_shards: int = 1
) -> Tuple[CacheConfig, CacheConfig]:
    """The two pools of a configuration with window layers against ONE
    per-device HBM budget, sized by what a sequence can really hold of
    each: a sequence of ``max_seq_len`` holds ``ceil(max_seq_len /
    block_size)`` blocks of the full layers' pool and ``ceil(window /
    block_size) + 1`` of the window layers', so the budget buys

        sequences = budget / (per_seq_full * bytes_full + per_seq_window * bytes_window)

    and each pool that many sequences' blocks (plus scratch). ``full`` /
    ``window``: the keyword arguments of :class:`CacheConfig` less
    ``num_blocks`` (``window`` with its ``window``)."""
    probe_f = CacheConfig(num_blocks=2, kv_shards=kv_shards, **full)
    probe_w = CacheConfig(num_blocks=2, kv_shards=kv_shards, **window)
    per_f, per_w = probe_f.blocks_per_sequence(max_seq_len), probe_w.blocks_per_sequence(max_seq_len)
    per_seq_bytes = per_f * probe_f.bytes_per_block + per_w * probe_w.bytes_per_block
    sequences = budget_bytes * kv_shards / per_seq_bytes
    blocks_f, blocks_w = 1 + int(sequences * per_f), 1 + int(sequences * per_w)
    if blocks_f < 2 or blocks_w < 2:
        raise ValueError(
            f"cache budget {budget_bytes}B x {kv_shards} shard(s) holds {sequences:.3f} sequences of "
            f"{per_seq_bytes}B ({per_f} full + {per_w} window blocks); need a block of each pool beside scratch"
        )
    return dataclasses.replace(probe_f, num_blocks=blocks_f), dataclasses.replace(probe_w, num_blocks=blocks_w)


class WindowTable:
    """One live sequence's blocks of the window pool (module docstring):
    ``blocks[i]`` holds block index ``first + i`` of the sequence;
    ``shared`` maps the block indices whose block a prefix entry owns
    (generation/prefix.py: the sequence holds a reference on the entry's
    window half, not the block) to that entry."""

    __slots__ = ("first", "blocks", "shared")

    def __init__(self, first: int = 0):
        self.first = first
        self.blocks: List[int] = []
        self.shared: Dict[int, object] = {}

    @property
    def end(self) -> int:
        """The block index after the last held."""
        return self.first + len(self.blocks)

    def block_at(self, index: int) -> int:
        """The pool block of block index ``index``; 0 (scratch) where
        the sequence does not hold it."""
        return self.blocks[index - self.first] if self.first <= index < self.end else 0


@dataclasses.dataclass(frozen=True)
class StateConfig:
    """Geometry of the per-sequence state of the layers that are not
    attention (module docstring): ``num_layers`` such layers, each
    keeping ``rows`` rows of ``width`` values per sequence, for ``slots``
    batch slots."""

    num_layers: int
    rows: int
    width: int
    slots: int
    dtype: DataType = DataType.FLOAT

    @property
    def bytes_per_sequence(self) -> int:
        """One slot's state (and one block's snapshot) over all layers."""
        return self.num_layers * self.rows * self.width * self.dtype.size_bytes

    def total_bytes(self, num_blocks: int) -> int:
        """``conv`` + ``snap`` on the device."""
        return (self.slots + num_blocks) * self.bytes_per_sequence


@dataclasses.dataclass(frozen=True)
class SlotStateConfig:
    """Per-sequence state of NAMED PARTS, kept per batch slot and nowhere
    else: ``num_layers`` layers each keep, for each of ``slots`` slots,
    the arrays ``parts`` names: ``(name, shape a layer a slot, dtype)``.
    A state-space layer keeps two of different shape and type (the last
    rows of its convolution's input, bfloat16, and its recurrent state,
    float32: ops/ssm.py) and megabytes of them a sequence, where a
    convolution layer keeps two rows: no snapshot a cached block exists
    (it would be a sequence's whole state a block), and an engine with
    such layers keeps no prefix index. A prefill hands a slot its parts
    at the sequence's own length; a decode step carries all of them."""

    num_layers: int
    slots: int
    parts: Tuple[Tuple[str, Tuple[int, ...], DataType], ...]

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(name for name, _, _ in self.parts)

    def part_bytes(self, name: str) -> int:
        """One slot's bytes of part ``name`` over all layers."""
        shape, dtype = next((s, d) for n, s, d in self.parts if n == name)
        return self.num_layers * math.prod(shape) * dtype.size_bytes

    @property
    def bytes_per_sequence(self) -> int:
        return sum(self.part_bytes(name) for name in self.names)

    @property
    def total_bytes(self) -> int:
        return self.slots * self.bytes_per_sequence

    def zeros(self) -> Dict[str, jax.Array]:
        return {n: jnp.zeros((self.num_layers, self.slots, *shape), d.jnp) for n, shape, d in self.parts}


class KVCache:
    """Device storage: ``k``/``v`` of shape [L, num_blocks, block_size,
    R, LW] (``CacheConfig.row_shape``; [..., H, D] after a row-major
    reshape) and ``state``: with a :class:`StateConfig` the arrays
    ``{"conv": [n, slots, rows, width], "snap": [n, num_blocks, rows,
    width]}``, with a ``window_config`` the window layers' ``{"wk",
    "wv"}``, with a ``slot_state`` (:class:`SlotStateConfig`) its named
    parts ``[n, slots, *shape]`` (empty with none of them: an empty
    pytree adds nothing to a program). Functional updates — jitted steps take the arrays and
    return replacements; this object just holds the current ones.

    ``sharding`` (a NamedSharding over the serving mesh, rows — that is,
    heads — sharded: generation/sharding.py) commits the arrays across
    the mesh at creation AND at every :meth:`reset`: crash recovery must
    hand the jits a cache with the exact sharding they were compiled
    for, or the first replay step would silently recompile every
    program."""

    def __init__(self, config: CacheConfig, k: jax.Array, v: jax.Array,
                 sharding=None, state_config: Optional[StateConfig] = None,
                 window_config: Optional[CacheConfig] = None,
                 slot_state: Optional[SlotStateConfig] = None):
        self.config = config
        self.slot_state = slot_state
        self.k = k
        self.v = v
        self.sharding = sharding
        self.state_config = state_config
        self.window_config = window_config
        self.state: Dict[str, jax.Array] = self._state_zeros()

    def _state_zeros(self) -> Dict[str, jax.Array]:
        state: Dict[str, jax.Array] = {}
        sc = self.state_config
        if sc is not None:
            state.update(
                conv=jnp.zeros((sc.num_layers, sc.slots, sc.rows, sc.width), sc.dtype.jnp),
                snap=jnp.zeros((sc.num_layers, self.config.num_blocks, sc.rows, sc.width), sc.dtype.jnp),
            )
        if self.window_config is not None:
            # the window layers' K and V, a pool of their own (module docstring)
            state.update(wk=self._zeros(self.window_config, None), wv=self._zeros(self.window_config, None))
        if self.slot_state is not None:
            state.update(self.slot_state.zeros())
        return state

    @staticmethod
    def _zeros(config: CacheConfig, sharding, value: bool = False) -> jax.Array:
        """One zeroed cache array, allocated where it will live: with a
        sharding each device materializes only its own head shard (never
        the whole array on device 0, then moved). K and V each get their
        own call — the decode/verify jits donate both, and XLA refuses
        one buffer donated twice. ``value``: V's shape (a latent cache's
        has no width)."""
        shape = (
            config.num_layers,
            config.num_blocks,
            config.block_size,
            *(config.value_row_shape if value else config.row_shape),
        )
        return jnp.zeros(shape, config.dtype.jnp, device=sharding)

    @classmethod
    def create(cls, config: CacheConfig, sharding=None,
               state_config: Optional[StateConfig] = None,
               window_config: Optional[CacheConfig] = None,
               slot_state: Optional[SlotStateConfig] = None) -> "KVCache":
        return cls(
            config,
            cls._zeros(config, sharding),
            cls._zeros(config, sharding, value=True),
            sharding=sharding,
            state_config=state_config,
            window_config=window_config,
            slot_state=slot_state,
        )

    def update(self, k: jax.Array, v: jax.Array, **state: jax.Array) -> None:
        """Take a program's replacements: K, V and whichever of the
        ``state`` arrays it carried (``conv=``, ``snap=``)."""
        self.k = k
        self.v = v
        self.state.update(state)

    def reset(self) -> None:
        """Drop all cached K/V and every sequence's state (engine crash
        recovery): every position is rewritten by recompute-replay
        prefills, and rezeroing also clears any NaN a poisoned batch may
        have written."""
        self.k = self._zeros(self.config, self.sharding)
        self.v = self._zeros(self.config, self.sharding, value=True)
        # (the old state goes first: state-space layers' is gigabytes, and a zeroed second copy beside it
        # is what ran 192 slots of the Nemotron cell out of memory at the reset behind `warm`, PR 48)
        self.state = {}
        self.state = self._state_zeros()


class BlockAllocator:
    """Host-side free list over the cache's blocks. Thread-safe: the
    scheduler's admission path and the serving layer's cancellation path
    may free concurrently. Block 0 (scratch) is never handed out.

    Telemetry (obs/capacity.py reads these; all maintained under the
    existing lock so they cost a few integer ops): cumulative
    ``total_allocated`` / ``total_freed`` block counts,
    ``total_reset_reclaimed`` (blocks reclaimed wholesale by
    :meth:`reset` — NOT counted in ``total_freed``, so conservation is
    ``total_allocated == total_freed + total_reset_reclaimed +
    outstanding``), and free-list ``low_water`` / ``high_water`` marks.
    """

    def __init__(self, config: CacheConfig):
        self.config = config
        self._lock = threading.Lock()
        self._free: List[int] = list(range(config.num_blocks - 1, 0, -1))
        self.total_allocated = 0
        self.total_freed = 0
        self.total_reset_reclaimed = 0
        self.low_water = len(self._free)
        self.high_water = len(self._free)

    def reset(self) -> None:
        """Restore the full free list (engine crash recovery): every
        outstanding block table is invalidated wholesale, so per-block
        frees — which would double-free against the fresh list — must
        not follow."""
        with self._lock:
            outstanding = (self.config.num_blocks - 1) - len(self._free)
            self.total_reset_reclaimed += outstanding
            self._free = list(range(self.config.num_blocks - 1, 0, -1))
            self.high_water = len(self._free)

    @property
    def num_free(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def num_total(self) -> int:
        return self.config.num_blocks - 1

    def can_allocate(self, n: int) -> bool:
        return self.num_free >= n

    def allocate(self, n: int) -> Optional[List[int]]:
        """Pop ``n`` blocks, or None (atomically — no partial grabs)."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} blocks")
        with self._lock:
            if len(self._free) < n:
                return None
            taken, self._free = self._free[:n], self._free[n:]
            self.total_allocated += n
            if len(self._free) < self.low_water:
                self.low_water = len(self._free)
            return taken

    def free(self, blocks: List[int]) -> None:
        with self._lock:
            for b in blocks:
                if b == 0:
                    raise ValueError("block 0 is scratch; it is never allocated")
                if b in self._free:
                    raise ValueError(f"double free of block {b}")
                self._free.append(b)
            self.total_freed += len(blocks)
            if len(self._free) > self.high_water:
                self.high_water = len(self._free)


def slot_mapping(
    block_table: jnp.ndarray, positions: jnp.ndarray, block_size: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Cache slot ``(block, offset)`` of each position: the two index
    arrays a step's rows are scattered at (``cache.at[layer, block,
    offset]``), so a write never needs the cache reshaped or a layer
    taken out.

    ``block_table``: [max_blocks] int32; ``positions``: [...] int32 of
    cache positions. Positions past the table's coverage land in the
    scratch block (block 0, offset 0) instead of indexing out of bounds
    — callers mask those positions out of attention anyway.
    """
    block_idx = positions // block_size
    in_range = block_idx < block_table.shape[0]
    block = jnp.where(in_range, block_table[jnp.clip(block_idx, 0, block_table.shape[0] - 1)], 0)
    return block, jnp.where(in_range, positions % block_size, 0)
