"""Paged decode/append attention: a window of query tokens per sequence
attending over a block-structured KV cache.

Every entry point takes the WHOLE cache — ``k_cache`` / ``v_cache`` as
generation/cache.py holds them — and a **static layer index**: the
caller never slices a layer out, so a decode program carries the two
donated arrays from its parameters to its results untouched but for the
rows it writes (ISSUE 24; a sliced-out layer handed to a Mosaic call is
a layer-sized copy, 48 of them a step).

**Stored shape.** The cache is ``[L, num_blocks, block_size, R, LW]``:
one position's ``H x D`` values laid out, head after head, over ``R``
rows of ``LW`` lanes (:func:`cache_row_shape`). Where ``D`` divides the
128 lanes of a TPU vector register, ``128 // D`` heads share a row
(``R = H * D / 128``, ``LW`` = 128: two heads of 64 per row); otherwise
a row is one head (``R, LW = H, D``: the plain layout). A row-major
reshape turns either into ``[..., H, D]``, so nothing but the kernel's
body knows which it is. The reason is the device's default layout, which
is the only one a program loaded from JAX's persistent compile cache
keeps (a layout declared through ``jax.experimental.layout`` is honoured
by a fresh compile and dropped by a cached one — measured on the v5e,
jax 0.9.0, PR 24): for ``[..., 16, 64]`` the TPU compiler puts
``num_blocks`` on the lanes, since 64 would half-fill them, and every
program calling this kernel then converted the whole cache to row-major
on entry and back on exit; for ``[..., 8, 128]`` the default IS row-major
and unpadded, the kernel DMAs blocks straight out of the arrays the
engine carries, and it reads half the bytes it read when each 64-wide
head was padded to 128 lanes.

The generation engine's decode step calls this once per layer with a
one-token window (``q`` [B, H, D]); the speculative-verification step
calls the generalized *chunked-append* form with a W = k+1 token window
(``q`` [B, W, H, D]) — the W drafted-window tokens are scored against
the cache in ONE forward instead of W sequential decode steps. Each
window query has its own cache position; masking keeps only cache
positions ``<= q_position`` in its softmax (causal within the window,
full history before it), so chunked verification reproduces the
sequential decode logits exactly. ``q_position < 0`` marks a padding
query (fixed-shape windows with fewer real draft tokens): it attends to
nothing and emits zeros.

**Grouped queries** (ISSUE 27; the body of PR 33). The cache holds the
K/V heads, and a query may have ``group`` times as many (query head
``i`` reads K/V head ``i // group``; :func:`query_group` reads the group
off the shapes). The window is folded, ``[B, W, Hkv * G, D] -> [B, W * G,
Hkv, D]``, so that window query ``w * G + g`` carries the ``g``-th query
head of every group, laid out over the K/V heads exactly as a cache
position is (:func:`_fold_group`): a block of K/V is DMA'd ONCE for all
the query heads that read it. LFM2-8B-A1B's 32 query heads over 8 K/V
heads of 64 are a window of 4 over rows ``[4, 128]``, Mellum2's 32 over
4 heads of 128 a window of 8 over the same rows.

**Two bodies, picked by the group the shapes show** (:func:`kernel_body`;
no flag, no environment variable, no model's name). The block tables,
the scalar prefetch, the online-softmax state and the Pallas calls'
names are the same for both; what differs is how the table is walked
and how what a step holds is folded into that state.

* ``group == 1`` (plain multi-head attention: GPT-2's decode and verify
  calls, the head-sharded wrappers) — :func:`_accumulate_block`, on the
  VPU, one window query at a time in float32. A group of one has nothing
  to batch into a matrix product (one query row a K/V head), its cells
  serve float32 weights whose ``correct`` leaves a float32 product on
  the MXU no room (PR 32 was refused there), and this call lowers to
  what it lowered to before the other body existed
  (``tests/test_attention_kernels.py`` pins the kernel's jaxpr).
* ``group > 1`` — :func:`_accumulate_block_mxu`: the block is scored for
  ALL query rows of its K/V heads by one ``dot_general`` on the stored
  dtype with float32 accumulation (no block-sized conversion), one
  update of the running max and denominator for all rows, and a second
  product, the probabilities in the cache's dtype times V, accumulated
  in float32. For a bfloat16 cache this changes no stated arithmetic: a
  product of two bfloat16 values is exact in float32, and the
  configurations' references state the weighted values as rounded
  probabilities times V with float32 accumulation. On the v5e (PR 33,
  ``chip_smoke.py``) the call of Mellum2's full layers took 1.09 ms
  where the VPU body took 5.32, its windowed call 0.51 against 2.96,
  LFM2's 1.51 against 2.35, at one block a grid step. Since PR 43 the
  grouped call's grid step is a SEQUENCE and the kernel walks that
  sequence's table itself (the section "The grouped call's walk over
  the block table", below): several consecutive columns a step, each an
  asynchronous copy of its own into one buffer, folded as ONE matrix
  of lines by one pair of products and one softmax update, two buffers
  so that the next step's copies fly meanwhile, and no step, copy or
  table look-up past the sequence's live context: 0.41, 0.25 and 0.32
  ms for the three calls. What a step costs now is the MXU loading
  every K and V tile as weights for a few dozen query rows.

**A window** (PR 31). A sliding-window layer's call (``window`` > 0)
adds a lower bound a query (it attends the ``window`` positions up to
its own) and takes a table that starts at the sequence's first HELD
block, with the cache position of column 0 a sequence
(``first_positions``; generation/cache.py releases the blocks behind the
window). Both lowerings take the two arguments. The kernel prefetches
``first_positions`` and the least position any query of the window still
attends as two further scalars, skips a column wholly behind that as it
skips one past every query (the group-1 call; the grouped call's walk
starts at column 0, which such a table's sequence still attends, and
masks), and runs as a Pallas call of its own name,
``paged_window_attention`` (a device trace carries no scope path: a
reader finds a kernel by its name). The table holds the columns the
window holds, so such a call walks those and not the history's.

**Latent rows** (a latent-attention layer, generation/decoder.py). Such a
layer caches ONE row a position, ``[c, k_r]`` of ``kv_lora_rank +
qk_rope_head_dim`` values shared by all heads, stored at the next
multiple of 128 lanes (:func:`latent_row_width`: 576 -> 640, the fill
zero) as ``[L, num_blocks, block_size, RW]``, whose default device
layout is row-major and unpadded. (``[..., 5, 128]`` is not: the TPU
compiler puts the 5 before ``block_size``; and a second array for the 64
rotary values would be laid out with ``num_blocks`` on the lanes and
padded to 1.5 x, as any array whose last axis is 64 is. Both read off a
deviceless v5e compile, PR 34.) The ABSORBED form scores every query
head against the same row and takes its values from the row's first
``value_width`` columns, so :func:`paged_latent_attention` — a Pallas
call of its own name — reads a block ONCE for scores and values: the
grouped body at a group of all the heads, ``[W * H, RW] x [RW, bs]`` on
the MXU, probabilities ``x [bs, value_width]`` out of the block already
in VMEM, float32 softmax state. Its grid is ``(batch, table columns /
columns a step)``: the same array is handed to the call once per column
of a step, each with its own index map, so one grid step DMAs several
blocks (consecutive positions) and folds them as ONE matrix of rows: the
grid's fixed cost a step (0.4-0.5 us, what the grouped calls above are
left with) is paid once for up to 16 blocks, and the MXU's tiles are
filled (a block of 64 rows alone half-fills them). :func:`reference_paged_latent_attention`
is the same arithmetic as an XLA composition.

Two lowerings:

* :func:`reference_paged_append_attention` — gather the table'd blocks
  of the layer (ONE gather, ``cache[layer, block_tables]``) and run a
  masked softmax in plain XLA. This is the CPU/test path and
  the parity oracle. :func:`reference_paged_attention` is its W = 1
  wrapper (the original decode form).
* :func:`paged_append_attention` — a Pallas TPU kernel with the block
  tables AND per-query positions scalar-prefetched
  (``pltpu.PrefetchScalarGridSpec``). A group-1 call is gridded over
  (batch, cache blocks): each grid step DMAs exactly one cache block —
  ``(layer, table[b, j])`` of the 5-D array — into VMEM (the
  PagedAttention access pattern) and accumulates per-query
  online-softmax state in scratch across the sequential grid;
  out-of-range table entries point at the scratch block 0 and are
  masked, never read out of bounds. A grouped call is gridded over the
  batch and copies its sequence's live blocks itself, several a step.
  :func:`paged_decode_attention` is its W = 1 wrapper.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128  # of a TPU vector register: the width a cache row fills


def cache_row_shape(num_heads: int, head_dim: int) -> Tuple[int, int]:
    """``(R, LW)``: how one cache position's ``num_heads x head_dim``
    values are stored (see the module docstring). ``num_heads`` is what
    ONE device holds: a head-sharded cache packs each shard's heads. A
    head wider than the lanes that does not fill whole rows of them has
    no stored shape here (a latent layer's row goes through
    :func:`latent_row_width`)."""
    if head_dim > LANES and head_dim % LANES:
        raise ValueError(
            f"a head of {head_dim} values neither packs into nor fills rows of {LANES} lanes: K/V heads are stored "
            f"at widths that divide or are multiples of {LANES}; a latent layer's row is stored by latent_row_width"
        )
    per_row = LANES // head_dim if LANES % head_dim == 0 else 1
    if per_row > 1 and num_heads % per_row == 0:
        return num_heads // per_row, LANES
    return num_heads, head_dim


def latent_row_width(width: int) -> int:
    """Lanes a latent layer's cache row of ``width`` values is stored
    at: the next multiple of :data:`LANES` (576 -> 640)."""
    return -(-width // LANES) * LANES


def query_group(num_heads: int, head_dim: int, row_shape) -> int:
    """Query heads per stored K/V head: the cache's rows hold ``R * LW /
    head_dim`` K/V heads, and ``num_heads`` query heads read them in
    groups (1: plain multi-head attention)."""
    if len(row_shape) != 2:
        raise ValueError(
            f"cache rows of shape {tuple(row_shape)} hold no K/V heads: a latent layer's rows are read by "
            f"paged_latent_attention, whose every query head reads the one row"
        )
    r, lw = row_shape
    kv_heads = r * lw // head_dim
    if kv_heads * head_dim != r * lw or num_heads % kv_heads or (lw != head_dim and lw % head_dim):
        raise ValueError(
            f"cache rows {r} x {lw} do not hold {num_heads} heads of {head_dim}, nor the K/V heads of groups of them"
        )
    return num_heads // kv_heads


def _fold_group(q: jax.Array, group: int) -> jax.Array:
    """[B, W, Hkv * G, D] -> [B, W * G, Hkv, D]: window query ``w * G +
    g`` carries, for every K/V head, the ``g``-th query head of its
    group. Beside the K/V heads the folded window is laid out exactly as
    a cache position is, which is all the kernel asks of a query."""
    b, w, h, d = q.shape
    return q.reshape(b, w, h // group, group, d).transpose(0, 1, 3, 2, 4).reshape(b, w * group, h // group, d)


def _unfold_group(out: jax.Array, group: int) -> jax.Array:
    """The inverse of :func:`_fold_group`, for the attention output."""
    b, wg, hk, d = out.shape
    return out.reshape(b, wg // group, group, hk, d).transpose(0, 1, 3, 2, 4).reshape(b, wg // group, hk * group, d)


def _query_rows(q: jax.Array, rows: int, lanes: int) -> jax.Array:
    """[B, W, Hkv, D] (a folded window) -> [B, W * R * P, LW], the
    grouped body's matrix of query rows: row ``(w * R + r) * P + p`` is
    cache row ``r`` of window query ``w`` with every lane but those of
    its ``p``-th head zeroed (``P = LW // D`` heads share a row), so a
    product over a row's ``LW`` lanes is ONE head's ``q . k``."""
    b, w, _, d = q.shape
    per_row = lanes // d
    q = q.reshape(b, w, rows, per_row, 1, d)
    if per_row > 1:
        own = jnp.eye(per_row, dtype=bool)[:, :, None]  # [p, head of the row, 1]
        q = jnp.where(own, q, jnp.zeros((), q.dtype))
    return q.reshape(b, w * rows * per_row, lanes)


def _head_rows(out: jax.Array, w: int, rows: int, head_dim: int) -> jax.Array:
    """The inverse of :func:`_query_rows` for the grouped body's result
    [B, W * R * P, LW]: query row ``p`` of a cache row summed V over all
    its lanes; its own head's are kept. Returns [B, W, Hkv, D]."""
    b, _, lanes = out.shape
    per_row = lanes // head_dim
    out = out.reshape(b, w, rows, per_row, per_row, head_dim)
    return jnp.einsum("bwrppd->bwrpd", out).reshape(b, w, rows * per_row, head_dim)


def reference_paged_append_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    layer: int,
    block_tables: jax.Array,
    q_positions: jax.Array,
    scale: Optional[float] = None,
    window: int = 0,
    first_positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Masked window attention over gathered cache blocks, in plain XLA.

    q: [B, W, H, D] (a W-token append window per sequence, K/V already
    written into the cache); k_cache/v_cache: [L, num_blocks,
    block_size, R, LW] (R x LW = H x D, row-major), of which static
    ``layer`` is read; block_tables: [B, max_blocks] int32; q_positions:
    [B, W] int32 — each window query's cache position. Query (b, w)
    attends to
    cache positions ``<= q_positions[b, w]`` (its own history including
    itself); ``q_positions[b, w] < 0`` marks a padding query, which
    produces zeros, not NaN. Returns [B, W, H, D].

    ``window`` > 0 (a sliding-window layer) adds a lower bound a query:
    only positions ``> q_positions[b, w] - window`` are attended, and
    ``first_positions`` [B] is the cache position of each table's column
    0 (a multiple of the block size: such a table starts at the
    sequence's first HELD block, the blocks behind the window having
    been released; generation/cache.py).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    bs = k_cache.shape[2]
    b, max_blocks = block_tables.shape
    group = query_group(q.shape[2], q.shape[3], k_cache.shape[3:])
    if group > 1:
        # each of a group's query heads as one more window query over
        # the K/V heads (query head i reads K/V head i // group)
        out = reference_paged_append_attention(
            _fold_group(q, group), k_cache, v_cache, layer, block_tables,
            jnp.repeat(q_positions, group, axis=1), scale, window, first_positions,
        )
        return _unfold_group(out, group)
    # one gather out of the whole cache: [B, max_blocks, bs, R, LW] -> [B, S_max, H, D]
    k = k_cache[layer, block_tables].reshape(b, max_blocks * bs, *q.shape[2:])
    v = v_cache[layer, block_tables].reshape(b, max_blocks * bs, *q.shape[2:])
    s = jnp.einsum("bwhd,bkhd->bhwk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    pos = jnp.arange(max_blocks * bs)[None, None, None, :]  # key positions
    if first_positions is not None:
        pos = pos + first_positions[:, None, None, None]
    valid = pos <= q_positions[:, None, :, None]  # [B, 1, W, S_max]
    if window:
        valid = valid & (pos > q_positions[:, None, :, None] - window)
    s = jnp.where(valid, s, NEG_INF)
    # max over an all-masked row is NEG_INF; subtracting keeps exp at 1
    # on masked lanes, so zero the probabilities explicitly instead of
    # relying on exp(-inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(valid, jnp.exp(s - m), 0.0)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhwk,bkhd->bwhd", p / l, v.astype(jnp.float32))
    return out.astype(q.dtype)


def reference_paged_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    layer: int,
    block_tables: jax.Array,
    context_lens: jax.Array,
    scale: Optional[float] = None,
    window: int = 0,
    first_positions: Optional[jax.Array] = None,
) -> jax.Array:
    """One-token (decode) form: q [B, H, D], context_lens [B] int32 (the
    number of valid cache positions INCLUDING the current token's
    already-written K/V; 0 marks an inactive slot). The W = 1 special
    case of :func:`reference_paged_append_attention`."""
    out = reference_paged_append_attention(
        q[:, None], k_cache, v_cache, layer, block_tables, context_lens[:, None] - 1, scale,
        window, first_positions,
    )
    return out[:, 0]


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------
#
# The group-1 body (:func:`_accumulate_block`). Everything inside it keeps
# the cache's own [block_size, R, LW] arrangement: rows on sublanes, a
# row's heads side by side on lanes. Scores are a lane reduction of k * q
# (VPU + XLU) — one reduction per head of the row, each over its own
# lanes, the result left on those lanes — and the value sum is a
# reduction over the block's leading axis, so the body needs no
# transposes, no batched matmul and no vector loads from SMEM — the three
# things Mosaic refused in the first version of this kernel, which
# computed [H, W, block_size] scores with a dot_general batched over a
# non-leading K axis. Per-query state is [W, R, LW] (a head's running max
# and denominator repeated over its lanes: a [.., 1] column occupies
# whole vector registers anyway) and the window is walked one query at a
# time; each query's cache position is a scalar SMEM read. The grouped
# body, further down, needs none of the three either: its two products
# are plain 2-D matmuls over the block read as the matrix it is in memory.


def _head_scores(prod, head_dim):
    """Per-head sums of ``prod`` ([bs, R, LW]) over each head's own
    ``head_dim`` lanes, left on those lanes. One head per row: a plain
    lane reduction, kept as a [bs, R, 1] column."""
    lanes = prod.shape[-1]
    if lanes == head_dim:
        return jnp.sum(prod, axis=-1, keepdims=True)
    head = jax.lax.broadcasted_iota(jnp.int32, prod.shape, 2) // head_dim
    s = jnp.zeros_like(prod)
    for g in range(lanes // head_dim):
        mine = head == g
        s = jnp.where(mine, jnp.sum(jnp.where(mine, prod, 0.0), axis=-1, keepdims=True), s)
    return s


def _accumulate_block(
    qpos_ref, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, b, block_start, *, scale, head_dim, window=0
):
    """Fold one cache block into every window query's online-softmax
    state (m/l [W, R, LW or 1], acc [W, R, LW]). ``window`` > 0: a query
    attends only the ``window`` positions up to its own."""
    k = k_ref[:].astype(jnp.float32)  # [bs, R, LW]
    v = v_ref[:].astype(jnp.float32)
    pos = block_start + jax.lax.broadcasted_iota(
        jnp.int32, (k.shape[0], k.shape[1], 1), 0
    )

    def one_query(w, carry):
        qp = qpos_ref[b, w]  # scalar: this query's cache position
        q = q_ref[w].astype(jnp.float32) * scale  # [R, LW]
        s = _head_scores(k * q[None], head_dim)  # [bs, R, LW or 1]
        valid = pos <= qp  # causal-within-window + history
        if window:
            valid = jnp.logical_and(valid, pos > qp - window)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[w]  # [R, LW or 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        p = jnp.where(valid, jnp.exp(s - m_new[None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        m_ref[w] = m_new
        l_ref[w] = l_ref[w] * corr + jnp.sum(p, axis=0)
        acc_ref[w] = acc_ref[w] * corr + jnp.sum(p * v, axis=0)  # [R, LW]
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0], one_query, 0)


# The grouped body (PR 33). A group's query heads read the SAME K/V head,
# so a block can be scored for all of them by one matrix product, which
# the group-1 call has no use for (one query row a K/V head is a matrix
# of one row). The wrapper stacks the folded window into a matrix of
# QUERY ROWS, ``[M, LW]`` with ``M = W * G * R * (LW // D)``: row ``((w *
# R + r) * P + p)`` holds cache row ``r`` of window query ``w`` with every
# lane but head ``p``'s zeroed (``P`` heads share a row; 1 where a head
# fills it). The block is read as the matrix it already is in memory,
# ``[bs * R, LW]`` (line ``t * R + r`` is row ``r`` of position ``t``: no
# transpose, no strided load, no conversion to float32), and ``Q x K^T``
# is ONE product for every K/V head of the block. A query row meets the
# lines of the other ``R - 1`` cache rows too; those scores are masked
# with the causal mask, so the second product, ``P x V``, sums a row's
# own head alone. The MXU computes ``R`` times the scores that are
# needed, on matrices of a few vector registers: what it replaces is
# ``W * G`` passes of the VPU over the whole block.


def _line_index(lines, rows):
    """``(lines // rows, lines % rows)`` of a non-negative int32 array;
    shifts where ``rows`` is a power of two (a vector integer division
    is emulated on the VPU)."""
    if rows & (rows - 1) == 0:
        return lines >> (rows.bit_length() - 1), lines & (rows - 1)
    return lines // rows, lines % rows


def _accumulate_step_mxu(
    q_ref, rowpos_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, start, end, *, scale, head_dim, window=0
):
    """:func:`_accumulate_block` for a grouped call: one step of the walk
    (``k_ref`` / ``v_ref`` [n, R, LW]: the ``n`` positions ``start ..``
    of the step's consecutive table columns, one block under the other)
    folded into the state of ALL query rows (m/l [M, 1], acc [M, LW]) as
    ONE matrix of lines, by two matrix products and one softmax update.
    ``rowpos_ref`` [M, 1] is each query row's cache position. ``end`` (a
    split's call): the first position that is not this split's, dead
    here whatever the queries see."""
    n, r, lw = k_ref.shape
    q = q_ref[...]  # [M, LW], the stored dtype
    k = k_ref[...].reshape(n * r, lw).astype(q.dtype)  # the step's lines, as they are in memory
    v = v_ref[...].reshape(n * r, lw)
    # Q x K^T, float32 accumulation: [M, n * R]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
    # line t * R + r of the step against query row (w * R + r') * P + p
    t, line_row = _line_index(jax.lax.broadcasted_iota(jnp.int32, s.shape, 1), r)
    query_line, _ = _line_index(jax.lax.broadcasted_iota(jnp.int32, s.shape, 0), lw // head_dim)
    _, query_row = _line_index(query_line, r)
    pos = start + t
    qp = rowpos_ref[...]  # [M, 1]
    valid = jnp.logical_and(line_row == query_row, pos <= qp)
    if window:
        valid = jnp.logical_and(valid, pos > qp - window)
    if end is not None:
        valid = jnp.logical_and(valid, pos < end)
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    # P x V: probabilities in the cache's dtype, float32 accumulation
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _init_state(m_ref, l_ref, acc_ref):
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)


def _block_span(col, b, maxpos_ref, bounds, block_size):
    """Where table column ``col`` of sequence ``b`` starts in the cache,
    and whether any of the window's queries can see it. ``bounds`` (the
    windowed call's two further scalar-prefetched refs): ``minlow`` [B],
    the least position any query of the window still attends, and
    ``first`` [B], the position of column 0."""
    if bounds is None:
        start = col * block_size
        return start, start <= maxpos_ref[b]
    minlow_ref, first_ref = bounds
    start = first_ref[b] + col * block_size
    return start, jnp.logical_and(start <= maxpos_ref[b], start + block_size > minlow_ref[b])


def _with_bounds(kernel):
    """``kernel`` behind the windowed call's five scalar-prefetched refs
    (tables, positions, max position, least attended position, first
    position): the last two reach it as ``bounds``."""
    def windowed(bt_ref, qpos_ref, maxpos_ref, minlow_ref, first_ref, *refs, **static):
        return kernel(bt_ref, qpos_ref, maxpos_ref, *refs, bounds=(minlow_ref, first_ref), **static)
    return windowed


def _append_kernel(
    bt_ref,  # scalar-prefetch: [B, max_blocks] block tables
    qpos_ref,  # scalar-prefetch: [B, W] per-query cache positions (-1 = pad)
    maxpos_ref,  # scalar-prefetch: [B] max over the window's positions
    q_ref,  # [W, R, LW] the window, laid out as the cache lays a position out
    k_ref,  # [block_size, R, LW] the grid step's cache block
    v_ref,
    o_ref,  # [W, R, LW]
    m_ref,  # scratch [W, R, LW or 1] running max per query
    l_ref,  # scratch [W, R, LW or 1] running denominator
    acc_ref,  # scratch [W, R, LW] running numerator
    scale,
    block_size,
    head_dim,
    window=0,
    bounds=None,
):
    """The group-1 call's kernel (a grouped call runs
    :func:`_grouped_kernel`): ONE cache block a grid step."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    start, live = _block_span(j, b, maxpos_ref, bounds, block_size)

    # whole block past every query's position: nothing to accumulate
    # (its DMA read the scratch block; the data is ignored)
    @pl.when(live)
    def _accum():
        _accumulate_block(
            qpos_ref, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
            b, start, scale=scale, head_dim=head_dim, window=window,
        )

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        # a padding query (qp < 0) accumulated nothing: acc = l = 0, so
        # it emits exact zeros
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[:] = (acc_ref[:] / l).astype(o_ref.dtype)


def _append_kernel_split(
    bt_ref,  # scalar-prefetch: [B, max_blocks] block tables
    qpos_ref,  # scalar-prefetch: [B, W] per-query cache positions (-1 = pad)
    maxpos_ref,  # scalar-prefetch: [B] max over the window's positions
    q_ref,  # as in :func:`_append_kernel`
    k_ref,
    v_ref,
    acc_out_ref,  # this split's UNNORMALIZED numerator [W, R, LW]
    m_out_ref,  # its running max [W, R, LW or 1]
    l_out_ref,  # its running denominator
    m_ref,  # the three scratch refs of the same shapes
    l_ref,
    acc_ref,
    scale,
    block_size,
    head_dim,
    blocks_per_split,
    max_blocks,
    window=0,
    bounds=None,
):
    """Split-KV (flash-decoding) variant of :func:`_append_kernel`: the
    grid gains a KV-split axis, each split accumulates online-softmax
    state over its contiguous slice of cache blocks INDEPENDENTLY (the
    splits can run in parallel — the sequential-grid data dependence is
    broken), and emits unnormalized partials (acc, m, l) that
    :func:`_combine_splits` recombines exactly. Long-context
    single-stream decode stops serializing over the whole block table."""
    b = pl.program_id(0)
    j = pl.program_id(2)
    jj = pl.program_id(1) * blocks_per_split + j  # global block-table column

    @pl.when(j == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    # skip padding grid steps (the split axis may overshoot the table;
    # their DMA re-read a clamped block — the data is ignored) and
    # whole blocks past every query's position
    start, live = _block_span(jj, b, maxpos_ref, bounds, block_size)

    @pl.when(jnp.logical_and(jj < max_blocks, live))
    def _accum():
        _accumulate_block(
            qpos_ref, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
            b, start, scale=scale, head_dim=head_dim, window=window,
        )

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        # UNNORMALIZED partials out: the cheap [B, S, W, H(, D)] combine
        # in plain XLA finishes the softmax exactly
        m_out_ref[:] = m_ref[:]
        l_out_ref[:] = l_ref[:]
        acc_out_ref[:] = acc_ref[:]


def _combine_splits(acc, m, l, q_positions, out_dtype):
    """Exact partial-softmax recombination across the KV-split axis.

    acc: [B, S, W, R, LW] unnormalized numerators; m/l: [B, S, W, R,
    LW or 1] per-split running max / denominator (cache-row layout).
    An empty split carries
    (m=NEG_INF, l=0, acc=0) and contributes nothing; a padding query
    (q_position < 0) has EVERY split empty and emits zeros, matching
    the single-pass kernel."""
    m_max = jnp.max(m, axis=1, keepdims=True)  # [B, 1, W, R, LW or 1]
    # all-empty guard: exp(NEG_INF - NEG_INF) is NaN; rescale against 0
    # instead (every alpha then underflows to exp(NEG_INF) = 0)
    safe_max = jnp.where(m_max > NEG_INF / 2, m_max, 0.0)
    alpha = jnp.exp(m - safe_max)  # [B, S, W, R, LW or 1]
    denom = jnp.sum(l * alpha, axis=1)  # [B, W, R, LW or 1]
    numer = jnp.sum(acc * alpha, axis=1)  # [B, W, R, LW]
    out = numer / jnp.maximum(denom, 1e-30)
    out = jnp.where(q_positions[:, :, None, None] >= 0, out, 0.0)
    return out.astype(out_dtype)


def _least_attended(q_positions, window):
    """[B]: the least position any query of a window of ``window``
    positions still attends. A padding query (-1) attends nothing: it
    must not hold the window's lower edge down."""
    low = jnp.where(q_positions >= 0, q_positions - (window - 1), jnp.iinfo(jnp.int32).max)
    return jnp.min(low, axis=1)


# ---------------------------------------------------------------------------
# The grouped call's walk over the block table (PR 43)
# ---------------------------------------------------------------------------
# One cache block a grid step, over every column of the table, left the
# grouped body waiting on the grid: a step cost 0.4-0.6 us whatever it
# held, 128 KB of K/V is 0.16 us of HBM time, and a column past the
# context still paid 0.3 us for the step and a copy of the scratch block
# (my chip runs, PR 33, PR 39 and PR 43). So a grouped call's grid step is
# a SEQUENCE (a split of one), and the kernel walks that sequence's table
# itself: ``k`` consecutive columns a step (:func:`grouped_columns_per_step`),
# each an asynchronous copy of its own out of the cache in HBM, all in
# flight together, into ONE buffer that holds the step's positions one
# under the other, so the step is folded as one matrix of lines
# (:func:`_accumulate_step_mxu`) with nothing moved inside VMEM. Two such
# buffers: the next step's copies (at a sequence's last step, the next
# sequence's first) fly while this one is folded. The walk is bounded by
# the live context outright: a sequence takes ``ceil(live columns / k)``
# steps, a column past its largest position is never copied nor looked up
# in the table, and a sequence of context 0 takes none. The table's width
# need not divide by ``k``: a last step's missing columns keep what an
# earlier step left in the buffer (zeros before any), dead under the mask.
#
# The other walk tried (my chip runs, PR 43; `chip_smoke.py --walk-sweep`
# has the table): the same ``k`` columns as ``k`` BlockSpecs a grid step,
# copied and double-buffered by the pipeline as the latent call's are, a
# dead slot's index clamped to the block it held the step before so that
# no copy is issued. Mellum2's full call 1.09 -> 0.57 ms where this one
# reads 0.41, its windowed 0.51 -> 0.36 against 0.25, LFM2's 1.51 -> 0.57
# against 0.32: every BlockSpec costs ~65 ns of scalar work a grid step
# whether or not it copies (a DEAD step of 16 specs 1.0 us), and the
# blocks had to be concatenated in VMEM.

# Positions a grid step of the grouped call folds, where the VMEM budget
# allows. Mellum2's full call (48 sequences of 1.1-2.7 k positions in
# blocks of 64), ms a call by columns a step (my chip runs, PR 43): 1:
# 0.86, 2: 0.63, 4: 0.43, 8: 0.41, 16: 0.45, 32: 0.48; LFM2's (blocks of
# 16): 4: 0.46, 8: 0.38, 16: 0.34, 32: 0.35. A step costs ~0.24 us and
# ~0.23 us a column of 128 KB (the MXU loads every K and V tile as
# weights for 32 query rows), and its last step's dead columns are folded
# with the rest: more columns a step are fewer steps and more dead ones.
GROUPED_STEP_POSITIONS = 512
# Copies of K (and as many of V) one step may have in flight.
MAX_COLUMNS_PER_STEP = 32


def _grouped_vmem_bytes(rows: int, lanes: int, block_size: int, query_rows: int, itemsize: int, columns: int) -> int:
    """Upper estimate of the grouped call's VMEM footprint at ``columns``
    table columns a step: the two buffers of K and of V (a position's
    (R, LW) rows padded to the (8, 128) tile) and a step's lines once
    more as the products' operands, Q / O / the rows' positions
    double-buffered, the ``[M, ...]`` softmax state, and the ``[M,
    columns * block_size * R]`` scores four times over (scores, mask,
    probabilities, their rounded copy), for ``query_rows`` = M."""
    padded = -(-rows // 8) * 8
    line = -(-lanes // LANES) * LANES
    m = -(-query_rows // 8) * 8
    step = 2 * columns * block_size * padded * line * itemsize  # a step's K and V
    qo = 2 * (2 * m * line * itemsize + m * LANES * 4)
    scratch = m * (line + 2 * LANES) * 4
    scores = 4 * m * -(-columns * block_size * rows // LANES) * LANES * 4
    return 3 * step + qo + scratch + scores


def grouped_columns_per_step(block_size: int, row_shape, query_rows: int, itemsize: int, max_blocks: int = 0) -> int:
    """Table columns one step of the grouped call's walk folds: what
    fills the step with :data:`GROUPED_STEP_POSITIONS` positions (blocks
    of 16 positions: 32 columns; of 64: 8), halved until
    :func:`_grouped_vmem_bytes` fits :data:`_VMEM_BUDGET_BYTES` (a block
    of 8 K/V heads under 128 query rows: 4). For a table of
    ``max_blocks`` columns (where given), the least that walks it in as
    few steps (17 columns at no more than 8 a step are three steps: 6 a
    step, not 8 + 8 + 1 with seven dead columns folded in the last). The
    call's shapes decide, nothing else: ``row_shape`` (R, LW),
    ``query_rows`` M."""
    rows, lanes = row_shape
    columns = max(1, min(GROUPED_STEP_POSITIONS // block_size, MAX_COLUMNS_PER_STEP))
    while columns > 1 and _grouped_vmem_bytes(rows, lanes, block_size, query_rows, itemsize, columns) > _VMEM_BUDGET_BYTES:
        columns //= 2
    if max_blocks:
        columns = -(-max_blocks // -(-max_blocks // columns))
    return columns


def paged_grid(group: int, batch: int, max_blocks: int, kv_splits: int = 1) -> Tuple[int, ...]:
    """The grid of :func:`paged_append_attention` for ``batch`` sequences
    over tables of ``max_blocks`` columns. A grouped call: a step a
    sequence, ``(batch,)``, split ``(batch, splits)`` (the walk is the
    kernel's own). A group-1 call: a step a column, ``(batch,
    max_blocks)``, split ``(batch, splits, columns a split)``."""
    kv_splits = max(1, min(int(kv_splits), max_blocks))
    grid = (batch, kv_splits) if kv_splits > 1 else (batch,)
    if kernel_body(group) == "mxu":
        return grid
    return grid + (-(-max_blocks // kv_splits),)


def paged_walk(
    kv_heads: int, head_dim: int, block_size: int, window: int, itemsize: int, group: int, batch: int, max_blocks: int,
    kv_splits: int = 1,
) -> dict:
    """``{"columns_per_step", "grid_steps", "walk_steps_at_most"}`` of
    the call that :func:`paged_append_attention` makes for these shapes
    (``kv_heads`` what one device holds, ``window`` the window queries
    the kernel holds, W x ``group``): the table columns a step of the
    walk folds (a group-1 call: 1), the grid steps of one call, and the
    steps its walk takes at most, at full tables (a group-1 call walks
    every column whatever the contexts, a step of the grid each; a
    grouped call's sequence ``ceil(live columns / columns a step)``)."""
    splits = max(1, min(int(kv_splits), max_blocks))
    split_columns = -(-max_blocks // splits)
    columns = 1
    if kernel_body(group) == "mxu":
        rows, lanes = cache_row_shape(kv_heads, head_dim)
        columns = grouped_columns_per_step(block_size, (rows, lanes), window * rows * (lanes // head_dim), itemsize, split_columns)
    return {
        "columns_per_step": columns,
        "grid_steps": math.prod(paged_grid(group, batch, max_blocks, splits)),
        "walk_steps_at_most": batch * splits * -(-split_columns // columns),
    }


def _grouped_kernel(
    bt_ref,  # scalar-prefetch: [B, max_blocks] block tables
    live_ref,  # scalar-prefetch: [B] table columns up to the last one any query sees (0: an inactive sequence)
    *refs,  # a windowed call's first_ref [B] (the position of column 0); q_ref [M, LW] the query rows, rowpos_ref
    # [M, 1] their positions; k_hbm, v_hbm the WHOLE caches where they are (the kernel copies out of them itself);
    # o_ref [M, LW] (a split call: this split's UNNORMALIZED numerator [M, LW], running max and denominator
    # [M, 1]); scratch: kbuf, vbuf [2, columns * block_size, R, LW] a step's blocks one under the other, twice;
    # sem DMA [2, 2] (buffer, K or V); carry SMEM [2] (the buffer the next step goes to, whether the next
    # program's first step is in flight); m / l [M, 1], acc [M, LW]
    scale,
    block_size,
    head_dim,
    columns,
    layer,
    window=0,
    splits=1,
    split_columns=0,
):
    """The grouped call's kernel. A program (grid ``(B,)``; split ``(B,
    splits)``) is one sequence's walk over its live columns (a split:
    over its share of them), ``columns`` a step; the grid is sequential,
    so a program hands the next one its first step already in flight."""
    first_ref = None
    if window:
        first_ref, *refs = refs
    q_ref, rowpos_ref, k_hbm, v_hbm, *refs = refs
    *outs, kbuf, vbuf, sem, carry, m_ref, l_ref, acc_ref = refs
    program = pl.program_id(0) * splits + (pl.program_id(1) if splits > 1 else 0)
    programs = pl.num_programs(0) * splits

    def walk_of(p):
        """Program ``p``'s sequence, its first column and the column its walk stops before."""
        if splits == 1:
            return p, 0, live_ref[p]
        seq, s = p // splits, p % splits
        return seq, s * split_columns, jnp.minimum((s + 1) * split_columns, live_ref[seq])

    def steps_of(p):
        _, c0, c1 = walk_of(p)
        return jnp.maximum(c1 - c0 + columns - 1, 0) // columns

    def each_copy(p, step, buffer, do):
        """``do`` the copy of every live column of step ``step`` of program ``p`` into ``buffer`` (a loop, not
        ``columns`` copies spelt out: the kernel's text does not grow with the columns a step)."""
        seq, c0, c1 = walk_of(p)
        column = c0 + step * columns

        def one(c, _):
            block = bt_ref[seq, column + c]
            rows = pl.ds(pl.multiple_of(c * block_size, block_size), block_size)
            for which, (cache, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                do(pltpu.make_async_copy(cache.at[layer, block], buf.at[buffer, rows], sem.at[buffer, which]))
            return 0

        jax.lax.fori_loop(0, jnp.clip(c1 - column, 0, columns), one, 0)

    start = lambda p, step, buffer: each_copy(p, step, buffer, lambda copy: copy.start())
    wait = lambda p, step, buffer: each_copy(p, step, buffer, lambda copy: copy.wait())

    @pl.when(program == 0)
    def _first():
        # a step's dead columns keep what an earlier step left in the buffer: never uninitialised memory
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        carry[0] = 0
        carry[1] = 0

    _init_state(m_ref, l_ref, acc_ref)
    seq, c0, c1 = walk_of(program)
    steps = steps_of(program)
    buffer0 = carry[0]

    @pl.when(jnp.logical_and(steps > 0, carry[1] == 0))
    def _own_first():  # the program before had nothing to fold, or there was none
        start(program, 0, buffer0)

    base = 0 if first_ref is None else first_ref[seq]
    end = base + c1 * block_size if splits > 1 else None
    follows = jnp.logical_and(program + 1 < programs, steps_of(jnp.minimum(program + 1, programs - 1)) > 0)

    def one_step(step, _):
        buffer = (buffer0 + step) % 2
        more = step + 1 < steps  # else the next program's first step flies while this one's last is folded

        @pl.when(jnp.logical_or(more, follows))
        def _next():
            start(jnp.where(more, program, program + 1), jnp.where(more, step + 1, 0), 1 - buffer)

        wait(program, step, buffer)
        _accumulate_step_mxu(
            q_ref, rowpos_ref, kbuf.at[buffer], vbuf.at[buffer], m_ref, l_ref, acc_ref,
            base + (c0 + step * columns) * block_size, end, scale=scale, head_dim=head_dim, window=window,
        )
        return 0

    jax.lax.fori_loop(0, steps, one_step, 0)
    carry[0] = (buffer0 + steps) % 2
    carry[1] = jnp.logical_and(steps > 0, follows).astype(jnp.int32)
    if splits > 1:
        # UNNORMALIZED partials out, as :func:`_append_kernel_split`
        acc_out_ref, m_out_ref, l_out_ref = outs
        m_out_ref[...] = m_ref[...]
        l_out_ref[...] = l_ref[...]
        acc_out_ref[...] = acc_ref[...]
    else:
        # a padding query (qp < 0) accumulated nothing: acc = l = 0, so it emits exact zeros
        (o_ref,) = outs
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _live_columns(q_positions, first_positions, block_size: int, max_blocks: int):
    """[B]: the table columns up to the last one any query of a sequence
    sees (its largest position's, from the position of column 0), 0
    where every query is a padding one."""
    last = jnp.max(q_positions, axis=1)
    from_first = last if first_positions is None else last - first_positions
    return jnp.where(last >= 0, jnp.clip(from_first // block_size + 1, 0, max_blocks), 0)


def _grouped_append_attention(
    q, k_cache, v_cache, layer, block_tables, q_positions, scale, interpret, kv_splits, window, first_positions, group
):
    """:func:`paged_append_attention` for a grouped call (its arguments
    already normalised): a block of K/V is read ONCE for all the query
    heads of its groups, each one more window query at the same
    position, and scored for all of them on the MXU, several table
    columns a step of a walk that ends where the context does."""
    out_dtype = q.dtype
    block_size, r, lw = k_cache.shape[2:]
    q = _fold_group(q, group)
    q_positions = jnp.repeat(q_positions, group, axis=1)
    b, w, _, d = q.shape
    per_query = r * (lw // d)  # query rows a window query: one a K/V head
    m = w * per_query
    row_positions = jnp.repeat(q_positions, per_query, axis=1)
    max_blocks = block_tables.shape[1]
    split_columns = -(-max_blocks // kv_splits)  # the table's width, of a sequential call
    columns = grouped_columns_per_step(block_size, (r, lw), m, k_cache.dtype.itemsize, split_columns)
    grid = paged_grid(group, b, max_blocks, kv_splits)
    name, static, bounds = "paged_append_attention", {}, ()
    if window:
        first_positions = first_positions.astype(jnp.int32)
        name, static, bounds = "paged_window_attention", {"window": window}, (first_positions,)
    prefetch = (block_tables, _live_columns(q_positions, first_positions if window else None, block_size, max_blocks), *bounds)

    def whole(shape):
        """A sequence's (and split's) whole ``shape``."""
        return pl.BlockSpec((None,) * len(grid) + shape, lambda i, *at: (i, *at[:len(grid) - 1]) + (0,) * len(shape))

    in_specs = [pl.BlockSpec((None, m, lw), lambda i, *_: (i, 0, 0)), pl.BlockSpec((None, m, 1), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)]
    step = (2, columns * block_size, r, lw)
    scratch_shapes = [
        pltpu.VMEM(step, k_cache.dtype), pltpu.VMEM(step, v_cache.dtype), pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.SMEM((2,), jnp.int32),
        pltpu.VMEM((m, 1), jnp.float32), pltpu.VMEM((m, 1), jnp.float32), pltpu.VMEM((m, lw), jnp.float32),
    ]
    kernel = functools.partial(
        _grouped_kernel, scale=scale, block_size=block_size, head_dim=d, columns=columns, layer=layer,
        splits=kv_splits, split_columns=split_columns, **static,
    )
    operands = (*prefetch, _query_rows(q, r, lw), row_positions[:, :, None], k_cache, v_cache)

    def unstack(out):
        """The kernel's result [B, M, LW] as [B, W, H, D]."""
        return _unfold_group(_head_rows(out, w, r, d), group)

    def call(out_specs, out_shape, name):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetch), grid=grid, in_specs=in_specs, out_specs=out_specs,
                scratch_shapes=scratch_shapes,
            ),
            out_shape=out_shape,
            interpret=interpret,
            name=name,
        )(*operands)

    if kv_splits > 1:
        partial_ = lambda width: jax.ShapeDtypeStruct((b, kv_splits, m, width), jnp.float32)
        acc, mx, l = call([whole((m, lw)), whole((m, 1)), whole((m, 1))], [partial_(lw), partial_(1), partial_(1)], name + "_split")
        # a query row as a window query of one cache row
        acc, mx, l = (x[:, :, :, None] for x in (acc, mx, l))
        return unstack(_combine_splits(acc, mx, l, row_positions, out_dtype)[:, :, 0])
    return unstack(call(whole((m, lw)), jax.ShapeDtypeStruct((b, m, lw), out_dtype), name))


def paged_append_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    layer: int,
    block_tables: jax.Array,
    q_positions: jax.Array,
    scale: Optional[float] = None,
    interpret: bool = False,
    kv_splits: int = 1,
    window: int = 0,
    first_positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Pallas paged chunked-append attention (shapes as in
    :func:`reference_paged_append_attention`). Compiled by Mosaic; only
    a test passes ``interpret=True``, and without it Pallas refuses to
    lower for a CPU backend. ``kv_splits > 1`` selects the
    flash-decoding split-KV kernel: the cache-block grid axis splits
    into ``kv_splits`` independent slices whose partial softmaxes
    recombine exactly — parallelism across the KV length for
    long-context, small-batch decode, where the sequential walk
    otherwise serializes the whole chip on one sequence's history.

    ``window`` > 0 is the sliding-window layer's call, a Pallas call of
    its own name (``paged_window_attention``: a device trace finds
    kernels by name): ``first_positions`` [B], the cache position of
    each table's column 0, and the least position any query of a window
    still attends are scalar-prefetched beside the tables, a query masks
    what lies ``window`` or more positions behind it, and a column wholly
    behind every query's window is skipped as one past its position is.
    The table holds only the columns a sequence keeps (generation/
    cache.py), so the call walks the window and not the history.

    A grouped call (the cache holds fewer heads than ``q``) goes to
    :func:`_grouped_append_attention`: the same names and arguments, a
    grid step a sequence, and a walk of its own over that sequence's
    live columns."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    group = query_group(q.shape[2], q.shape[3], k_cache.shape[3:])
    q_positions = q_positions.astype(jnp.int32)
    block_tables = block_tables.astype(jnp.int32)
    layer = int(layer)  # static: part of the index map, not an operand
    kv_splits = max(1, min(int(kv_splits), block_tables.shape[1]))
    if kernel_body(group) == "mxu":
        return _grouped_append_attention(
            q, k_cache, v_cache, layer, block_tables, q_positions, float(scale), interpret, kv_splits,
            int(window), first_positions, group,
        )
    out_dtype = q.dtype
    block_size, r, lw = k_cache.shape[2:]
    b, w, h, d = q.shape
    sw = lw if lw != d else 1  # a head's max / denominator: on its lanes, or a column
    rows = q.reshape(b, w, r, lw)  # the window, laid out as the cache lays a position out
    state, acc_shape = (w, r, sw), (w, r, lw)
    max_blocks = block_tables.shape[1]
    prefetch = (block_tables, q_positions, jnp.max(q_positions, axis=1))
    name, static, behind = "paged_append_attention", {}, (lambda kernel: kernel)
    if window:
        prefetch += (_least_attended(q_positions, window), first_positions.astype(jnp.int32))
        name, static, behind = "paged_window_attention", {"window": int(window)}, _with_bounds
    n_prefetch = len(prefetch)
    scratch_shapes = [
        pltpu.VMEM(state, jnp.float32),
        pltpu.VMEM(state, jnp.float32),
        pltpu.VMEM(acc_shape, jnp.float32),
    ]

    def whole(shape, lead):
        """A sequence's (and split's) whole ``shape``, whatever the
        grid step's cache block."""
        return pl.BlockSpec(
            (None,) * lead + shape, lambda i, *at: (i, *at[:lead - 1]) + (0,) * len(shape)
        )

    if kv_splits > 1:
        bps = -(-max_blocks // kv_splits)  # blocks per split (ceil)

        def kv_map(i, s, j, bt, *_):
            return (layer, bt[i, jnp.minimum(s * bps + j, max_blocks - 1)], 0, 0, 0)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            grid=(b, kv_splits, bps),
            in_specs=[
                whole(acc_shape, 1),
                pl.BlockSpec((None, None, block_size, r, lw), kv_map),
                pl.BlockSpec((None, None, block_size, r, lw), kv_map),
            ],
            out_specs=[whole(acc_shape, 2), whole(state, 2), whole(state, 2)],
            scratch_shapes=scratch_shapes,
        )
        kernel = functools.partial(
            behind(_append_kernel_split), scale=float(scale), block_size=block_size,
            head_dim=d, blocks_per_split=bps, max_blocks=max_blocks, **static,
        )
        acc, m, l = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((b, kv_splits) + acc_shape, jnp.float32),
                jax.ShapeDtypeStruct((b, kv_splits) + state, jnp.float32),
                jax.ShapeDtypeStruct((b, kv_splits) + state, jnp.float32),
            ],
            interpret=interpret,
            name=name + "_split",
        )(*prefetch, rows, k_cache, v_cache)
        return _combine_splits(acc, m, l, q_positions, out_dtype).reshape(b, w, h, d)

    def kv_map(i, j, bt, *_):
        return (layer, bt[i, j], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(b, max_blocks),
        in_specs=[
            whole(acc_shape, 1),
            pl.BlockSpec((None, None, block_size, r, lw), kv_map),
            pl.BlockSpec((None, None, block_size, r, lw), kv_map),
        ],
        out_specs=whole(acc_shape, 1),
        scratch_shapes=scratch_shapes,
    )
    kernel = functools.partial(
        behind(_append_kernel), scale=float(scale), block_size=block_size, head_dim=d, **static,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b,) + acc_shape, out_dtype),
        interpret=interpret,
        name=name,
    )(*prefetch, rows, k_cache, v_cache).reshape(b, w, h, d)


# ---------------------------------------------------------------------------
# Latent rows (module docstring)
# ---------------------------------------------------------------------------
# Query rows a latent call may hold in its score matrix: W window queries
# of H heads each. A decode call is 32; a suffix-prefill bucket of
# hundreds of queries belongs to the XLA composition.
MAX_LATENT_QUERY_ROWS = 256
# Table columns one grid step folds (each a DMA of its own, all in flight
# together, then ONE matrix of rows in VMEM): the largest divisor of the
# table's width that is at most this. A call of 64 rows over 48 columns
# of 64 positions (`chip_smoke.py --latent-kernel`, my chip runs, PR 34),
# ms a call: 4 columns folded block by block 1.09; 8 as one matrix 0.45;
# 16 as one matrix 0.39 (the rows of 16 blocks are 1.3 MB, 1.6 us of HBM
# time a step), and 0.25 at the 32 rows the benchmark's cell runs.
LATENT_COLUMNS_PER_STEP = 16


def latent_columns_per_step(max_blocks: int) -> int:
    """Table columns a grid step of the latent call folds, for a table
    of ``max_blocks`` columns."""
    return max(k for k in range(1, LATENT_COLUMNS_PER_STEP + 1) if max_blocks % k == 0)


def reference_paged_latent_attention(
    q: jax.Array,
    cache: jax.Array,
    layer: int,
    block_tables: jax.Array,
    q_positions: jax.Array,
    value_width: int,
    scale: float,
) -> jax.Array:
    """The absorbed latent attention over gathered rows, in plain XLA.

    q: [B, W, H, RW], each query laid out as a cache row is (the
    absorbed unrotated part, the rotated part, zeros); cache: [L,
    num_blocks, block_size, RW], of which static ``layer`` is read;
    block_tables [B, max_blocks]; q_positions [B, W] (``< 0``: a padding
    query, which emits zeros). Scores are ``q . row * scale`` over the
    rows at positions ``<= q_positions``, the softmax is float32, the
    probabilities are rounded to the cache's type and the values are the
    rows' first ``value_width`` columns, accumulated in float32 (what
    the kernel computes). Returns [B, W, H, value_width]."""
    b, max_blocks = block_tables.shape
    bs = cache.shape[2]
    rows = cache[layer, block_tables].reshape(b, max_blocks * bs, cache.shape[-1])
    s = jnp.einsum("bwhc,bkc->bhwk", q.astype(cache.dtype), rows, preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(max_blocks * bs)[None, None, None, :] <= q_positions[:, None, :, None]
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(valid, jnp.exp(s - m), 0.0)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum(
        "bhwk,bkc->bwhc", p.astype(cache.dtype), rows[..., :value_width], preferred_element_type=jnp.float32
    ) / jnp.swapaxes(l, 1, 2)
    return out.astype(q.dtype)


def _latent_kernel(
    bt_ref,  # scalar-prefetch: [B, max_blocks] block tables
    maxpos_ref,  # scalar-prefetch: [B] the window's largest position
    q_ref,  # [M, RW] query rows, M = W x H
    rowpos_ref,  # [M, 1] each query row's cache position
    *refs,  # the step's blocks [bs, RW] (one ref a column), o_ref [M, VW], scratch m / l [M, 1], acc [M, VW]
    scale,
    block_size,
    value_width,
):
    *blocks, o_ref, m_ref, l_ref, acc_ref = refs
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    start = j * len(blocks) * block_size  # a step's columns are consecutive positions

    # a step whose first block lies past every query's position holds nothing to fold in
    @pl.when(start <= maxpos_ref[b])
    def _accum():
        q = q_ref[...]
        # the step's blocks as ONE matrix of rows: scores AND values come out of this one read, and
        # the MXU takes its tiles filled (a block of 64 rows alone half-fills them)
        rows = jnp.concatenate([rows_ref[...] for rows_ref in blocks], axis=0) if len(blocks) > 1 else blocks[0][...]
        s = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = pos <= rowpos_ref[...]
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :value_width], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_latent_attention(
    q: jax.Array,
    cache: jax.Array,
    layer: int,
    block_tables: jax.Array,
    q_positions: jax.Array,
    value_width: int,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Pallas paged latent attention (shapes and arithmetic as in
    :func:`reference_paged_latent_attention`; module docstring). The
    Pallas call is named ``paged_latent_attention``."""
    b, w, h, rw = q.shape
    block_size = cache.shape[2]
    max_blocks = block_tables.shape[1]
    per_step = latent_columns_per_step(max_blocks)
    layer = int(layer)
    m = w * h
    q_positions = q_positions.astype(jnp.int32)
    row_positions = jnp.repeat(q_positions, h, axis=1)[:, :, None]  # [B, M, 1]

    def column(c):
        return pl.BlockSpec(
            (None, None, block_size, rw), lambda i, j, bt, mp: (layer, bt[i, j * per_step + c], 0, 0)
        )

    def whole(shape):
        return pl.BlockSpec((None,) + shape, lambda i, j, bt, mp: (i,) + (0,) * len(shape))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, max_blocks // per_step),
        in_specs=[whole((m, rw)), whole((m, 1)), *(column(c) for c in range(per_step))],
        out_specs=whole((m, value_width)),
        scratch_shapes=[
            pltpu.VMEM((m, 1), jnp.float32), pltpu.VMEM((m, 1), jnp.float32), pltpu.VMEM((m, value_width), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _latent_kernel, scale=float(scale), block_size=block_size, value_width=int(value_width)
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, m, value_width), q.dtype),
        interpret=interpret,
        name="paged_latent_attention",
    )(
        block_tables.astype(jnp.int32), jnp.max(q_positions, axis=1),
        q.reshape(b, m, rw).astype(cache.dtype), row_positions, *([cache] * per_step),
    )
    return out.reshape(b, w, h, value_width)


def latent_kernel_refusal(query_rows: int, row_width: int, block_size: int, itemsize: int) -> Optional[str]:
    """Why :func:`paged_latent_attention` will not take this shape, or
    None when it will (the dispatch in ops/attention.py sends a refused
    shape to the XLA composition)."""
    if query_rows > MAX_LATENT_QUERY_ROWS:
        return (
            f"{query_rows} query rows > {MAX_LATENT_QUERY_ROWS}: the kernel holds every query row of the window "
            f"in one score matrix"
        )
    # a step's blocks double-buffered and once more as one matrix; the scores of all its positions
    blocks = 3 * LATENT_COLUMNS_PER_STEP * block_size * row_width * itemsize
    state = query_rows * (3 * row_width * itemsize + 2 * row_width * 4 + 6 * max(LATENT_COLUMNS_PER_STEP * block_size, LANES) * 4)
    if blocks + state > _VMEM_BUDGET_BYTES:
        return f"~{(blocks + state) >> 20} MiB of VMEM exceeds the {_VMEM_BUDGET_BYTES >> 20} MiB budget"
    return None


def default_kv_splits(batch: int, max_blocks: int) -> int:
    """Flash-decoding split heuristic: split the KV axis only where the
    sequential walk over one sequence's blocks is the bottleneck (a
    group-1 call: a grid step a block; a grouped call: a step of its own
    walk every few blocks) — small batch (little batch-axis parallelism)
    over a long table. Capped so each split still covers >= 4 blocks
    (partials below that are overhead-bound).

    STATIC shapes only: the grid must be fixed at trace time, so
    ``batch`` is the engine's padded slot count and ``max_blocks`` its
    table width — NOT live occupancy or live context. The auto path
    therefore engages for <=2-slot engine configurations (the dedicated
    long-context single-stream deployment shape flash-decoding exists
    for); wider-batch engines can opt in explicitly via ``kv_splits``,
    accepting the recombination overhead when their batches run
    under-occupied."""
    if batch > 2 or max_blocks < 16:
        return 1
    return max(1, min(8, max_blocks // 4))


def paged_decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    layer: int,
    block_tables: jax.Array,
    context_lens: jax.Array,
    scale: Optional[float] = None,
    interpret: bool = False,
    kv_splits: Optional[int] = None,
    window: int = 0,
    first_positions: Optional[jax.Array] = None,
) -> jax.Array:
    """One-token (decode) form of :func:`paged_append_attention`
    (shapes as in :func:`reference_paged_attention`). ``kv_splits``
    None auto-selects via :func:`default_kv_splits` — the
    flash-decoding path for long-context single/dual-stream decode."""
    if kv_splits is None:
        kv_splits = default_kv_splits(q.shape[0], block_tables.shape[1])
    bounds = {"window": window, "first_positions": first_positions} if window else {}
    out = paged_append_attention(
        q[:, None],
        k_cache,
        v_cache,
        layer,
        block_tables,
        context_lens[:, None] - 1,
        scale=scale,
        interpret=interpret,
        kv_splits=kv_splits,
        **bounds,
    )
    return out[:, 0]


def sharded_paged_append_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    layer: int,
    block_tables: jax.Array,
    q_positions: jax.Array,
    mesh,
    axis: str = "model",
    scale: Optional[float] = None,
    interpret: bool = False,
    kv_splits: int = 1,
) -> jax.Array:
    """Head-sharded paged append attention over a serving mesh (ISSUE
    15): each shard runs the SINGLE-DEVICE Pallas kernel over its local
    slice of KV heads — attention is embarrassingly parallel across
    heads, so no collective runs inside the kernel at all. The one
    cross-shard boundary lives at the attention OUTPUT projection,
    where the decoder's head-sharded ``wo`` contraction produces
    partial sums and GSPMD inserts the psum (the same reduction
    ops/parallel_ops.py's ``ReductionOp`` annotates in the training
    path). Shapes as in :func:`paged_append_attention`; ``q`` is
    [B, W, H, D] with H sharded on ``axis``, the caches shard their head
    dim (the layer axis is whole on every shard, so the static ``layer``
    passes straight through), tables/positions are replicated, and the
    output keeps H sharded.

    ``scale`` must be passed explicitly when H is sharded — the default
    would be computed from a LOCAL shape inside shard_map; head_dim is
    unsharded so the usual ``d ** -0.5`` default stays correct."""
    from jax.sharding import PartitionSpec as P

    if scale is None:
        scale = q.shape[-1] ** -0.5

    def local(q_, k_, v_, bt_, qp_):
        return paged_append_attention(
            q_, k_, v_, layer, bt_, qp_, scale=scale, interpret=interpret,
            kv_splits=kv_splits,
        )

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(None, None, axis, None),  # q [B, W, H, D]
            P(None, None, None, axis, None),  # k_cache [L, nb, bs, R, LW], rows sharded
            P(None, None, None, axis, None),  # v_cache
            P(None, None),  # block_tables (replicated)
            P(None, None),  # q_positions (replicated)
        ),
        out_specs=P(None, None, axis, None),
        check_vma=False,
    )(q, k_cache, v_cache, block_tables, q_positions)


def sharded_paged_decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    layer: int,
    block_tables: jax.Array,
    context_lens: jax.Array,
    mesh,
    axis: str = "model",
    scale: Optional[float] = None,
    interpret: bool = False,
    kv_splits: Optional[int] = None,
) -> jax.Array:
    """One-token (decode) form of :func:`sharded_paged_append_attention`
    (q [B, H, D], H sharded on ``axis``)."""
    if kv_splits is None:
        kv_splits = default_kv_splits(q.shape[0], block_tables.shape[1])
    out = sharded_paged_append_attention(
        q[:, None],
        k_cache,
        v_cache,
        layer,
        block_tables,
        context_lens[:, None] - 1,
        mesh,
        axis=axis,
        scale=scale,
        interpret=interpret,
        kv_splits=kv_splits,
    )
    return out[:, 0]


def kernel_body(group: int) -> str:
    """Which body folds a cache block into the softmax state of a call
    whose shapes show ``group`` query heads a K/V head (:func:`query_group`):
    ``"mxu"`` (:func:`_accumulate_block_mxu`) for a grouped call,
    ``"vpu"`` (:func:`_accumulate_block`) for plain multi-head attention.
    The one rule; :func:`paged_append_attention` applies it."""
    return "mxu" if group > 1 else "vpu"


# A group-1 call walks the window one query at a time on the VPU, so its
# cost is linear in W; a grouped call holds W * G query rows a K/V head in
# its score matrix. Either way speculative windows are a handful of
# tokens, while a suffix-prefill bucket of hundreds belongs to the XLA
# composition, whose scores are one matmul over the whole context.
MAX_KERNEL_WINDOW = 32
# Mosaic's only refusal of this kernel is running out of VMEM (v5e,
# libtpu 0.0.34: 1-16 heads, head_dim 32-256, block sizes 1-64, f32 and
# bf16 all compiled; footprints that :func:`_vmem_bytes` puts above
# ~28 MiB did not; the grouped body, PR 33: 8 and 4 K/V heads of 64 and
# 128, groups 4 and 8, blocks of 16 and 64, W = 1 and 5, split and
# windowed, bf16 and f32 compiled for the v5e and ran there). The gate
# keeps well inside the 16 MiB default scoped limit, so a refusal is a
# dispatch decision and never a compiler error inside a serving step.
_VMEM_BUDGET_BYTES = 12 << 20


def _vmem_bytes(
    num_heads: int, head_dim: int, block_size: int, window: int, itemsize: int, group: int = 1
) -> int:
    """Upper estimate of the kernel's VMEM footprint, for the body that
    will run (:func:`kernel_body`) and the walk it will take
    (:func:`grouped_columns_per_step`). ``window`` counts the window
    queries the kernel holds (W x group). The VPU body: double-buffered
    K/V and Q/O blocks, the online-softmax scratch and three block-sized
    float32 temporaries, with a position's (R, LW) rows padded to the
    (8, 128) tile. The MXU body: :func:`_grouped_vmem_bytes` for
    ``window * R * heads a row`` query rows."""
    rows, lanes = cache_row_shape(num_heads, head_dim)
    if kernel_body(group) == "mxu":
        query_rows = window * rows * (lanes // head_dim)
        columns = grouped_columns_per_step(block_size, (rows, lanes), query_rows, itemsize)
        return _grouped_vmem_bytes(rows, lanes, block_size, query_rows, itemsize, columns)
    padded = -(-rows // 8) * 8
    row = padded * -(-lanes // LANES) * LANES  # one position's slab, in elements
    kv = 2 * 2 * block_size * row * itemsize
    qo = 2 * 2 * window * row * itemsize
    scratch = window * (row + 2 * padded * LANES) * 4  # acc + m + l
    return kv + qo + scratch + 3 * block_size * row * 4


def paged_kernel_refusal(
    num_heads: int, head_dim: int, block_size: int, window: int = 1, itemsize: int = 4, group: int = 1
) -> Optional[str]:
    """Why :func:`paged_append_attention` will not take this shape
    (per-shard K/V head count; ``window`` = the window queries the kernel
    holds, W x ``group``: 1 for a plain decode call), or None when it
    will. The dispatch in ops/attention.py sends a refused shape to the
    XLA reference and logs the reason once."""
    if window > MAX_KERNEL_WINDOW:
        how = (
            "the kernel scores one query at a time on the VPU" if kernel_body(group) == "vpu" else
            f"the kernel holds every query row of a block's K/V heads (group {group}) in one score matrix"
        )
        return f"window {window} > {MAX_KERNEL_WINDOW}: {how}"
    need = _vmem_bytes(num_heads, head_dim, block_size, window, itemsize, group)
    if need > _VMEM_BUDGET_BYTES:
        return (
            f"~{need >> 20} MiB of VMEM for heads={num_heads} head_dim={head_dim} "
            f"block_size={block_size} window={window} exceeds the "
            f"{_VMEM_BUDGET_BYTES >> 20} MiB budget"
        )
    return None
