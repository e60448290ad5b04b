"""The routed experts' sum over (row, chosen expert) pairs grouped by expert.

``generation/decoder.py::expert_ffn`` computes ``sum_{i in I} g_i W2_i
(silu(W1_i v) * W3_i v)`` for rows ``v`` [T, E]. Its dense lowering
multiplies every row by every held expert and masks by the gate: right
where the weights' reads set the time (a decode step, a short bucket),
``held / k`` times the needed arithmetic where the rows do (a long
prefill). This module is the other lowering of the same sum, and the
rule that picks between them from what a call's shapes show:

* :func:`expert_form` — ``"dense"`` or ``"grouped"`` from the static row
  count, the experts held and ``k``. No flag, no environment
  variable, no model name: one algorithm whose two lowerings want
  different row counts, and the row count is in the shape.
* :func:`grouped_expert_sum` — the (row, expert) pairs of the rows'
  top-k choices ordered by expert, the rows gathered, ONE grouped
  product (:func:`grouped_matmul`) for each of ``ew1`` / ``ew3``, the
  gated product, one for ``ew2``, the k results of a row summed in
  float32. The rounding points are the dense form's: bfloat16 operands
  with float32 accumulation in both up-products, ``silu(up) * gate_up *
  g`` cast to the activations' type BEFORE the down-product, float32
  accumulation there, the partial results added in float32, one cast at
  the end. Exact top-k: no capacity, no dropped pair.

Pairs that belong to no group — an expert this chip does not hold, a row
that is not live (padding behind a prompt's length) — sort behind the
last group and are not multiplied; a grouped kernel leaves the rows it
never visits UNWRITTEN, so their results are set to zero with a
``where``, never by a multiplication.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm

from ..device import on_tpu

# a grouped product's row tile (the pairs are padded to a multiple of it)
# and what one of its grid steps may hold in fast memory: two buffers of
# each operand block, the float32 result block twice and its accumulator.
# The chip allows a kernel 16 MiB; 13 MiB by this count leaves the room the
# compiler's own count has wanted (chip_smoke.py --expert-product)
_TILE_ROWS = 128
_TILE_BYTES = 13 * 2 ** 20


def expert_form(rows: int, held: int, k: int) -> str:
    """Which lowering of the routed sum a call of ``rows`` rows over
    ``held`` experts takes, ``k`` chosen a row.

    One expert layer alone on the v5e, ms dense / grouped, router
    included (``chip_smoke.py --expert-product``; my chip run, PR 35),
    at the widths and held counts of the three expert cells::

        rows   LFM2 32 of 32, k 4   Mellum2 64 of 64, k 8   JoyAI 16 of 256, k 8
                 2,048 x 1,792          2,304 x 896            2,048 x 768
          32     0.98 / 1.01            1.11 / 1.15            0.28 / 0.26
          64     0.99 / 1.05            1.12 / 1.20            0.28 / 0.31
         256     1.09 / 1.21            1.27 / 1.37            0.37 / 0.34
         512     2.05 / 1.42            2.23 / 1.64            0.55 / 0.49
        1024     3.96 / 1.89            4.40 / 2.26            1.05 / 0.80
        1536     5.96 / 2.30            7.01 / 2.93            1.56 / 1.16
        2048     7.88 / 2.75            8.75 / 4.17            2.06 / 1.99

    (With ``lax.ragged_dot`` as its product the grouped form reads 1.6
    to 3.1 x this one from 1,024 rows for the first two and 1.2 to 1.4
    x for the third: not kept.) Up to 256 rows the weights' reads set both forms' time, and
    the dense one does nothing else. From 512 the dense form's arithmetic
    (every row through every held expert) outgrows them; the grouped
    form multiplies ``k`` pairs a row and moves them twice (the gather,
    the un-sort) whatever is held. With every expert held (``held`` = 8
    ``k``) it is ahead by a quarter at 512 rows and 2 to 2.9 x from
    1,024: the row count here is the lowest at which it is well ahead for
    both, and the buckets up to 512 (gen-batch's) keep the program they
    have. With two held experts for each a row chooses (JoyAI's share)
    the two forms are within a quarter of each other and even at 2,048
    rows: dense stays."""
    return "grouped" if rows >= 1024 and held >= 4 * k else "dense"


def expert_lowering(rows: int, held: int, k: int) -> str:
    """:func:`expert_form` where the grouped product's kernel can run (a
    TPU backend); elsewhere the dense form, as the paged kernels' calls
    take their XLA composition there."""
    return expert_form(rows, held, k) if on_tpu() else "dense"


def _tiling(m: int, k: int, n: int):
    """The grouped product's (rows, depth, columns) tile for an [m, k] x
    [groups, k, n] call. The depth is never split: a group's weight
    block then stays put while the row tiles of the group walk past it,
    so every weight is read once. The columns are split (into equal
    parts of whole lanes) only as far as :data:`_TILE_BYTES` asks."""
    tm = min(_TILE_ROWS, m)
    step_bytes = lambda tn: 2 * 2 * (tm * k + k * tn) + 3 * 4 * tm * tn  # noqa: E731
    parts = [p for p in range(1, n // 128 + 1) if n % (128 * p) == 0] if n % 128 == 0 else [1]
    return tm, k, n // next((p for p in parts if step_bytes(n // p) <= _TILE_BYTES), parts[-1])


def grouped_matmul(lhs, rhs, group_sizes, interpret: bool = False):
    """``lhs[start_g : start_g + group_sizes[g]] @ rhs[g]`` for every
    group ``g`` (the groups' rows consecutive from row 0), float32 out of
    a float32 accumulation. Rows behind the last group are NOT written."""
    return gmm(lhs, rhs, group_sizes, preferred_element_type=jnp.float32, tiling=_tiling, interpret=interpret)


def grouped_expert_sum(
    v,
    gates,
    chosen,
    w1,
    w3,
    w2,
    held: Optional[Sequence[int]] = None,
    live=None,
    product: Optional[Callable] = None,
):
    """The routed sum of rows ``v`` [T, E] in the activations' type, from
    ``route``'s ``gates`` [T, N] and ``chosen`` [T, k] and the stacked
    weights of the held experts (``held`` names them in stacking order;
    None: all N). ``live`` [T] bool: rows that are not get zeros and cost
    nothing. ``product``: the grouped product (:func:`grouped_matmul`)."""
    product = product or grouped_matmul
    t, k = chosen.shape
    h, e = w1.shape[0], v.shape[1]
    pairs = -(-t * k // _TILE_ROWS) * _TILE_ROWS  # (the places behind t * k are padding: no pair lands there)
    stack = jnp.arange(h, dtype=jnp.int32) if held is None else jnp.asarray(tuple(held), jnp.int32)
    mine = gates if held is None else gates[:, stack]
    # hot[t, j, i]: row t's j-th choice is the i-th expert of the stack. A
    # pair in no group (an expert not held, a row not live) is hot nowhere.
    # Everything a pair needs is read through `hot` by compare-and-sum: a
    # gather of t * k scalars costs the chip as much as one of whole rows
    hot = chosen[:, :, None] == stack
    if live is not None:
        hot = hot & live[:, None, None]
    # a counting sort, the pairs of a group in the order of their rows (a
    # row's choices are distinct, so `picked` is 0 / 1)
    picked = jnp.any(hot, axis=1).astype(jnp.int32)  # [T, h]
    before = jnp.cumsum(picked, axis=0) - picked  # the rows ahead of row t that picked expert i
    sizes = jnp.sum(picked, axis=0)
    place = before + (jnp.cumsum(sizes) - sizes)  # [T, h]: where row t's pair with expert i sorts to
    at = jnp.sum(jnp.where(hot, place[:, None, :], 0), axis=2)  # [T, k]
    gate = jnp.sum(jnp.where(hot, mine[:, None, :], 0.0), axis=2)  # [T, k]
    grouped = jnp.any(hot, axis=2)  # [T, k]: the pair has a group
    # ONE scatter carries a pair's row (exact in float32) and gate to its place
    source = jnp.stack([jnp.repeat(jnp.arange(t, dtype=jnp.float32), k), gate.reshape(-1)], axis=1)
    sorted_ = jnp.zeros((pairs, 2), jnp.float32).at[jnp.where(grouped, at, pairs).reshape(-1)].set(source, mode="drop")
    # (rows gathered as [.., lanes]: the TPU compiler's gather of whole
    # [pairs, E] rows runs out of fast memory at some row counts, 1,536
    # x 8 of 2,304 among them; tests/test_generation.py compiles the cells')
    lanes = 128 if e % 128 == 0 else e
    xs = v.reshape(t, -1, lanes)[sorted_[:, 0].astype(jnp.int32)].reshape(pairs, e)
    up = product(xs, w1, sizes)
    gate_up = product(xs, w3, sizes)
    hidden = (jax.nn.silu(up) * gate_up * sorted_[:, 1:]).astype(v.dtype)
    down = product(hidden, w2, sizes)
    # a row's k results added in float32; a pair without a group adds zero
    # by a `where` (its place was never visited, or holds another pair).
    # (ONE gather of all the pairs: k gathers of T rows added one by one
    # read 6 % ahead at Mellum2's 2,048 rows, 3.77 against 4.02 ms a
    # layer, and `prefill[2048]` then compiles 61 MiB larger)
    results = jnp.where(grouped.reshape(-1)[:, None], down[at.reshape(-1)], 0.0)
    return jnp.sum(results.reshape(t, k, e), axis=1).astype(v.dtype)
