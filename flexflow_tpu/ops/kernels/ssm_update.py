"""A state-space layer's decode step as ONE pass over the slots' state.

``ops/ssm.py::update`` calls this for static ``layer`` of the WHOLE stored
state ``[n_layers, slots, H / pack, N, 128]`` float32 (that module says
why the heads lie ``pack`` to a row of lanes): a grid step takes one
(slot, group) block of it, ``[rows, N, 128]``, computes::

    S <- decay * S + B^T (dt x)          y = sum_n S C

and writes the block back where it came from (``input_output_aliases``:
the array goes through the call in place, the blocks of other layers
untouched). ``decay = exp(dt A)`` and ``dt x`` arrive as lane-dense rows
``[slots, H / pack, 128]`` and ``y`` leaves as one; a group's ``B`` and
``C`` [N] are broadcast over the lanes once a grid step (a broadcast over
the sublanes and one ``[128, 128]`` transpose each). Everything is float32
on the VPU: the call moves the state once each way and is bound by that.

In XLA the update and the product with ``C`` are two passes over the
state (``ops/ssm.py::update_reference``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(state_ref, decay_ref, xd_ref, b_ref, c_ref, y_ref, out_ref):
    rows, n, lanes = state_ref.shape[2:]
    # B and C of this (slot, group) with N on the sublanes, the same in every lane
    bt = jnp.broadcast_to(b_ref[0, 0], (lanes, n)).T
    ct = jnp.broadcast_to(c_ref[0, 0], (lanes, n)).T
    for r in range(rows):  # a row of `pack` heads: one [N, lanes] tile
        new = decay_ref[0, r : r + 1, :] * state_ref[0, 0, r] + bt * xd_ref[0, r : r + 1, :]
        out_ref[0, 0, r] = new
        y_ref[0, r : r + 1, :] = jnp.sum(new * ct, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("layer", "interpret"))
def ssm_state_update(state, layer: int, decay, xd, b, c, interpret: bool = False):
    """``state`` [L, slots, HP, N, lanes] float32, ``decay`` / ``xd``
    [slots, HP, lanes] float32, ``b`` / ``c`` [slots, G, N] float32 ->
    (``y`` [slots, HP, lanes] float32, the state with ``layer`` updated)."""
    _, slots, hp, n, lanes = state.shape
    g = b.shape[1]
    rows = hp // g
    row = pl.BlockSpec((1, rows, lanes), lambda s, j: (s, j, 0))
    vec = pl.BlockSpec((1, 1, 1, n), lambda s, j: (s, j, 0, 0))
    block = pl.BlockSpec((1, 1, rows, n, lanes), lambda s, j: (layer, s, j, 0, 0))
    return pl.pallas_call(
        _kernel,
        grid=(slots, g),
        in_specs=[block, row, row, vec, vec],
        out_specs=[row, block],
        out_shape=[jax.ShapeDtypeStruct((slots, hp, lanes), jnp.float32), jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={0: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=5 * slots * hp * n * lanes, transcendentals=0,
            bytes_accessed=2 * 4 * slots * hp * n * lanes + 3 * 4 * slots * hp * lanes + 2 * 4 * slots * g * n,
        ),
        interpret=interpret,
        name="ssm_state_update",
    )(state, decay, xd, b[:, :, None, :], c[:, :, None, :])


# ------------------------------------------------------------- Mamba-1
# A Mamba-1 layer's decay is one a (channel, state) pair, ``exp(dt[d] A[d, n])``:
# it cannot arrive as a lane row, so the call below forms it inside, from
# ``dt`` [1, D] and ``A`` [N, D], and the state-sized decay is never written.
SELECTIVE_SLOTS_PER_STEP = (4, 2, 1)  # slots a grid step takes: the largest that divides the slots


def _selective_kernel(state_ref, dt_ref, x_ref, a_ref, b_ref, c_ref, y_ref, out_ref):
    a = a_ref[...]  # [N, D]: the rates, the same every grid step
    for s in range(state_ref.shape[1]):
        dt = dt_ref[s]  # [1, D]
        new = jnp.exp(dt * a) * state_ref[0, s] + b_ref[s] * (dt * x_ref[s])  # b: [N, 1], over the lanes
        out_ref[0, s] = new
        y_ref[s] = jnp.sum(new * c_ref[s], axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("layer", "interpret"))
def selective_state_update(state, layer: int, dt, x, a, b, c, interpret: bool = False):
    """A Mamba-1 layer's decode step as ONE pass over the slots' state:
    ``state`` [L, slots, N, D] float32 (``N`` on the sublanes, the ``D``
    channels on the lanes), ``dt`` / ``x`` [slots, D] float32 (both 0 in a
    slot that is not live: ``exp(0) S + 0`` is ``S``, bit for bit), ``a``
    [N, D] float32 (negative), ``b`` / ``c`` [slots, N] float32 ->
    (``y`` [slots, D] float32, the state with static ``layer`` updated in
    place: ``input_output_aliases``, the other layers' blocks untouched)::

        S[n, d] <- exp(dt[d] a[n, d]) S[n, d] + b[n] dt[d] x[d]      y[d] = sum_n c[n] S[n, d]

    A grid step takes ``SELECTIVE_SLOTS_PER_STEP`` slots' blocks; the
    decay is formed in registers. Float32 on the VPU and the EUP: the call
    moves the state once each way and is bound by that."""
    _, slots, n, d = state.shape
    per = next(k for k in SELECTIVE_SLOTS_PER_STEP if slots % k == 0)
    row = pl.BlockSpec((per, 1, d), lambda i: (i, 0, 0))
    col = pl.BlockSpec((per, n, 1), lambda i: (i, 0, 0))
    block = pl.BlockSpec((1, per, n, d), lambda i: (layer, i, 0, 0))
    y, state = pl.pallas_call(
        _selective_kernel,
        grid=(slots // per,),
        in_specs=[block, row, row, pl.BlockSpec((n, d), lambda i: (0, 0)), col, col],
        out_specs=[row, block],
        out_shape=[jax.ShapeDtypeStruct((slots, 1, d), jnp.float32), jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={0: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        cost_estimate=pl.CostEstimate(
            flops=6 * slots * n * d, transcendentals=slots * n * d,
            bytes_accessed=2 * 4 * slots * n * d + 3 * 4 * slots * d + 4 * n * d + 2 * 4 * slots * n,
        ),
        interpret=interpret,
        name="selective_state_update",
    )(state, dt[:, None, :], x[:, None, :], a, b[:, :, None], c[:, :, None])
    return y[:, 0], state
