"""BERT-Large as the configuration file states it, in plain float32.

A pre-LN bidirectional encoder over a token embedding alone: per block
LayerNorm, 16 heads of 64 with a full softmax scaled by 1/sqrt(64),
output projection, residual; LayerNorm, 4096-wide GELU (erf form)
feed-forward with biases, residual; a final LayerNorm, one dense layer
to the vocabulary, and the mean cross-entropy over every position.

``params`` is ``{"embedding" [V, E], "layers": [{"ln1": (scale, bias),
"wq"/"wk"/"wv" [E, H, D], "wo" [H, D, E], "ln2": (scale, bias), "ff1":
(kernel, bias), "ff2": (kernel, bias)}], "final_ln": (scale, bias),
"head": (kernel, bias)}``: the trainer's values (bf16) upcast, found by
the driver in ``executor.params``; a missing bias is ``None``.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5


def _layer_norm(x, scale_bias):
    scale, bias = scale_bias
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * scale + bias


def _dense(x, kernel_bias):
    kernel, bias = kernel_bias
    y = x @ kernel
    return y if bias is None else y + bias


def _gelu_erf(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / np.sqrt(2.0)))


def _block(x, layer):
    h = _layer_norm(x, layer["ln1"])
    q = jnp.einsum("nse,ehd->nhsd", h, layer["wq"])
    k = jnp.einsum("nse,ehd->nhsd", h, layer["wk"])
    v = jnp.einsum("nse,ehd->nhsd", h, layer["wv"])
    scores = jnp.einsum("nhqd,nhkd->nhqk", q, k) / np.sqrt(q.shape[-1])
    ctx = jnp.einsum("nhqk,nhkd->nqhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + jnp.einsum("nqhd,hde->nqe", ctx, layer["wo"])
    h = _gelu_erf(_dense(_layer_norm(x, layer["ln2"]), layer["ff1"]))
    return x + _dense(h, layer["ff2"])


def _chunk_loss_sum(params, stacked, tokens, labels):
    x = params["embedding"][tokens]
    x, _ = jax.lax.scan(lambda x, layer: (_block(x, layer), None), x, stacked)
    logits = _dense(_layer_norm(x, params["final_ln"]), params["head"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).sum()


@jax.jit
def _mean_loss(params, tokens, labels):
    f32 = lambda t: jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), t)
    params = f32(params)
    stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *params["layers"])
    with jax.default_matmul_precision("highest"):
        sums = jax.lax.map(
            lambda tl: _chunk_loss_sum(params, stacked, tl[0], tl[1]), (tokens, labels)
        )
    return sums.sum() / labels.size


def mean_loss(params: Dict, tokens: np.ndarray, labels: np.ndarray, chunk: int = 2) -> float:
    """Mean cross-entropy over every position of ``tokens`` [N, S], taken
    ``chunk`` sequences at a time so that the [chunk, S, V] float32
    logits are all that is ever held."""
    n, s = tokens.shape
    if n % chunk:
        raise ValueError(f"{n} sequences do not divide into chunks of {chunk}")
    shape = (n // chunk, chunk, s)
    return float(_mean_loss(params, jnp.asarray(tokens.reshape(shape)), jnp.asarray(labels.reshape(shape))))
