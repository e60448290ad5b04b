"""Loss functions.

Reference: include/flexflow/loss_functions.h:27, src/loss_functions/
loss_functions.cc:41 (+ loss_functions.cu). The reference's Loss seeds
output gradients manually with a 1/batch scale factor; here losses are
scalar-valued and autodiff produces those gradients — the scale factor
matches (mean over batch). Where a graph ends in a softmax that only the
loss reads, the executor takes ``softmax_crossentropy`` from the
softmax's input instead (``CompiledExecutor.loss_form``), which seeds
``prob - onehot`` as the reference does.
"""
from __future__ import annotations

import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp

from ..core.types import LossType

# log of the floor that the composed losses clip a probability at
_LOG_FLOOR = math.log(1e-8)


def categorical_crossentropy(logits_or_probs: jax.Array, labels: jax.Array) -> jax.Array:
    """Labels are one-hot/probability distributions [B, C]. Input is the
    softmax output (parity: the reference pairs this with a softmax op)."""
    p = jnp.clip(logits_or_probs.astype(jnp.float32), 1e-8, 1.0)
    return -jnp.mean(jnp.sum(labels.astype(jnp.float32) * jnp.log(p), axis=-1))


def sparse_categorical_crossentropy(probs: jax.Array, labels: jax.Array) -> jax.Array:
    """Labels are int class ids [B] (or [B, 1]); input is softmax output."""
    if labels.ndim == probs.ndim:
        labels = labels[..., 0]
    p = jnp.clip(probs.astype(jnp.float32), 1e-8, 1.0)
    ll = jnp.take_along_axis(jnp.log(p), labels.astype(jnp.int32)[..., None], axis=-1)
    return -jnp.mean(ll)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def softmax_crossentropy(logits: jax.Array, labels: jax.Array, sparse: bool) -> jax.Array:
    """``(sparse_)categorical_crossentropy(softmax(logits), labels)`` as
    one chain over the logits (the reference's pairing: its loss writes
    ``prob - onehot`` scaled by 1/batch into the logits' gradient and its
    softmax backward is a copy, loss_functions.cu beside softmax.cu).

    Forward, in float32 from the logits' own dtype: one log-sum-exp a
    row, the label's log-probability ``logits[label] - lse`` floored at
    ``log(1e-8)`` where the composed ``clip(p, 1e-8, 1)`` floors it.
    Kept for the backward pass: the logits, ``lse`` and the labels' live
    weight, one number a row each; no probability array. Backward:
    ``(exp(logits - lse) - onehot) * g / rows``, zero in a row on the
    floor, computed in float32 and written once in the logits' dtype;
    the one-hot is a comparison with an iota, so nothing scatters.
    ``sparse``: labels are class ids ``[...]`` or ``[..., 1]``; else
    distributions ``[..., C]`` (and ``labels`` stands where the one-hot
    does). Labels are data: no gradient flows to them.

    On the chip (v5e; ``chip_smoke.py --loss-chain``, PR 49): value and
    gradient in one program at ``[8192, 30522]`` bfloat16 logits, a label
    a row, on the device's clock; the least traffic is two reads of the
    logits and one write of their gradient, 1.5 GB::

                   ms a call   of 819 GB/s   temporaries   value's error   gradient's
        composed     9.08        20 %         1.0 GB         4.6e-7          1.8e-3
        fused        2.85        64 %         0              7.7e-8          1.8e-3

    (errors against the composed chain on the same logits in float32: the
    value's relative, the gradient's over its largest entry, which is
    bfloat16's own rounding of the result). In the cells' train step the
    row maximum rides in the head's product and the compiler computes the
    gradient inside the operand reads of the head's two backward
    products, so no gradient array is written at all: 8.4 -> 1.5 ms a
    step of 159.6 (PERF.md, PR 49).
    """
    return _softmax_ce_fwd(logits, labels, sparse)[0]


def _label_weights(logits: jax.Array, labels: jax.Array, sparse: bool) -> jax.Array:
    """The labels as float32 weights over the classes, ``[..., C]``."""
    if not sparse:
        return labels.astype(jnp.float32)
    if labels.ndim == logits.ndim:
        labels = labels[..., 0]
    classes = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    return (classes == labels.astype(jnp.int32)[..., None]).astype(jnp.float32)


def _softmax_ce_fwd(logits, labels, sparse):
    x = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(x, axis=-1)
    y = _label_weights(logits, labels, sparse)
    if sparse:
        # one class a row: its logit asks for no log-sum-exp first, so
        # both sums ride one pass over the logits
        logp = jnp.sum(jnp.where(y > 0, x, 0.0), axis=-1) - lse
        ll = jnp.maximum(logp, _LOG_FLOOR)
        live = (logp > _LOG_FLOOR).astype(jnp.float32)
    else:
        logp = x - lse[..., None]
        ll = jnp.sum(y * jnp.maximum(logp, _LOG_FLOOR), axis=-1)
        live = jnp.sum(y * (logp > _LOG_FLOOR), axis=-1)
    # a class on the floor passes no gradient, as the clip passes none
    return -jnp.mean(ll), (logits, labels, lse, live)


def _softmax_ce_bwd(sparse, res, g):
    logits, labels, lse, live = res
    logp = logits.astype(jnp.float32) - lse[..., None]
    y = _label_weights(logits, labels, sparse)
    d = (jnp.exp(logp) * live[..., None] - y * (logp > _LOG_FLOOR)) * (g / lse.size)
    return d.astype(logits.dtype), None


softmax_crossentropy.defvjp(_softmax_ce_fwd, _softmax_ce_bwd)


def mean_squared_error(preds: jax.Array, labels: jax.Array) -> jax.Array:
    return jnp.mean(jnp.square(preds.astype(jnp.float32) - labels.astype(jnp.float32)))


def identity_loss(preds: jax.Array, labels: jax.Array) -> jax.Array:
    """Reference: LOSS_IDENTITY — the model's output *is* the loss."""
    return jnp.mean(preds.astype(jnp.float32))


def get_loss_fn(loss_type: LossType) -> Callable[[jax.Array, jax.Array], jax.Array]:
    return {
        LossType.CATEGORICAL_CROSSENTROPY: categorical_crossentropy,
        LossType.SPARSE_CATEGORICAL_CROSSENTROPY: sparse_categorical_crossentropy,
        LossType.MEAN_SQUARED_ERROR: mean_squared_error,
        LossType.MEAN_SQUARED_ERROR_AVG_REDUCE: mean_squared_error,
        LossType.MEAN_SQUARED_ERROR_SUM_REDUCE: lambda p, l: jnp.sum(
            jnp.square(p.astype(jnp.float32) - l.astype(jnp.float32))
        ),
        LossType.IDENTITY: identity_loss,
    }[loss_type]
