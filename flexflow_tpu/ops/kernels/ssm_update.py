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
