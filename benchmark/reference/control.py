"""The control of the decoder cells' ``correct``: the plain reference
put in the program's place, computed one step of precision below what
the configuration states (float32 served -> bfloat16 weights,
activations and matmuls), the step a later PR would be tempted by; and
a step further, int8-rounded weights under bfloat16 arithmetic. Its
greedy choices, judged by the float32 reference as served tokens are,
have to come out as NOT correct.

The control chooses after the same prefixes as the program did: one
forward pass of ``decoder.logits_at`` over ``prompt + served tokens``
in the lower type gives, at every position, the token the control
would have produced there. A greedy token is judged given its prefix
and nothing else, so this reads what a stream of the control's own
would, at a hundredth of the cost.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import decoder


def _int8(path, a):
    """A weight as int8 storage would hold it: one symmetric scale per
    output channel (per row for the two embedding tables), rounded to
    255 levels; vectors (biases, LayerNorm) are left alone."""
    if a.ndim < 2:
        return a
    axis = -1 if "embed" in jax.tree_util.keystr(path) else 0
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    return jnp.round(a / scale) * scale


@functools.partial(jax.jit, static_argnames="precision")
def cast_params(params: Dict, precision: str) -> Dict:
    """The weights as the control holds them. ``bfloat16``: cast, and
    every intermediate of ``decoder.logits_at`` is then in that type
    too. ``int8``: rounded to int8 levels, the arithmetic in bfloat16."""
    if precision == "int8":
        params = jax.tree_util.tree_map_with_path(_int8, params)
    return jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)


_choose = jax.jit(lambda params, tokens, at: jnp.argmax(decoder.logits_at(params, tokens, at), -1))


def choices(params: Dict, tokens: np.ndarray, at: np.ndarray, precision: str) -> np.ndarray:
    """[N, T] greedy tokens of the control after each position ``at`` of
    ``tokens`` (``decoder.layout``), ``decoder.ROWS`` requests a call."""
    cast = cast_params(params, precision)
    out = []
    for lo in range(0, len(tokens), decoder.ROWS):
        rows = [np.resize(a[lo : lo + decoder.ROWS], (decoder.ROWS,) + a.shape[1:]) for a in (tokens, at)]
        out.append(np.asarray(_choose(cast, *map(jnp.asarray, rows)))[: len(tokens) - lo])
    return np.concatenate(out)
