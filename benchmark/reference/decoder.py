"""GPT-2-medium as the configuration file states it, in plain float32.

A pre-LN causal decoder: token + learned position embedding; per block
LayerNorm, 16 heads of 64 with a causal softmax scaled by 1/sqrt(64),
output projection, residual; LayerNorm, 4096-wide GELU (tanh form,
``gelu_new``) feed-forward with biases, residual; a final LayerNorm and
an output matrix. Departures from the source are the configuration's:
no bias on q/k/v/o, ``lm_head`` not tied to the embedding.

The parameters are read in the layout the served engine holds them
(``tok_embed`` [V, E], ``pos_embed`` [P, E], ``final_ln_g/b``,
``lm_head`` [E, V], ``layers``: a list of ``ln1_g/b``, ``wq/wk/wv``
[E, H, D], ``wo`` [H, D, E], ``ln2_g/b``, ``ff1`` [E, F], ``ff1_b``,
``ff2`` [F, E], ``ff2_b``); the values are the engine's, the arithmetic
is this file's. Layers are stacked and scanned so that the 24-layer
program compiles as one block.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5


def _layer_norm(x, g, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def _block(x, layer):
    n, s, _ = x.shape
    h = _layer_norm(x, layer["ln1_g"], layer["ln1_b"])
    q = jnp.einsum("nse,ehd->nhsd", h, layer["wq"])
    k = jnp.einsum("nse,ehd->nhsd", h, layer["wk"])
    v = jnp.einsum("nse,ehd->nhsd", h, layer["wv"])
    scores = jnp.einsum("nhqd,nhkd->nhqk", q, k) / np.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    ctx = jnp.einsum("nhqk,nhkd->nqhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + jnp.einsum("nqhd,hde->nqe", ctx, layer["wo"])
    h = _layer_norm(x, layer["ln2_g"], layer["ln2_b"])
    h = _gelu_new(h @ layer["ff1"] + layer["ff1_b"])
    return x + h @ layer["ff2"] + layer["ff2_b"]


def hidden_states(params: Dict, tokens: jax.Array) -> jax.Array:
    """[N, S] tokens -> final-LayerNorm hidden states [N, S, E]."""
    f32 = lambda t: jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), t)
    stacked = f32(jax.tree.map(lambda *ls: jnp.stack(ls), *params["layers"]))
    s = tokens.shape[1]
    x = f32(params["tok_embed"])[tokens] + f32(params["pos_embed"])[jnp.arange(s)][None]
    x, _ = jax.lax.scan(lambda x, layer: (_block(x, layer), None), x, stacked)
    return _layer_norm(x, f32(params["final_ln_g"]), f32(params["final_ln_b"]))


@jax.jit
def _gaps(params, tokens, at, produced):
    with jax.default_matmul_precision("highest"):
        h = hidden_states(params, tokens)
        # the logits at position p predict the token at p + 1
        sel = jnp.take_along_axis(h, at[:, :, None], axis=1)  # [N, T, E]
        logits = sel @ jnp.asarray(params["lm_head"], jnp.float32)  # [N, T, V]
    got = jnp.take_along_axis(logits, produced[:, :, None], axis=2)[..., 0]
    return jnp.argmax(logits, -1), jnp.max(logits, -1) - got, jnp.all(jnp.isfinite(logits))


def teacher_force(
    params: Dict, prompts: Sequence[Sequence[int]], streams: Sequence[Sequence[int]],
    pad_to: int, max_new: int,
) -> Tuple[int, int, float]:
    """Push ``prompt + produced`` of every request through the reference
    and look at each produced token's logit: returns (tokens checked,
    tokens that are not the reference's argmax, the worst distance in
    logits below the reference's best). Shapes are fixed by ``pad_to``
    and ``max_new`` so that one compiled program serves every run; a
    causal model's earlier positions do not see the padding."""
    n = len(prompts)
    tokens = np.zeros((n, pad_to), np.int32)
    at = np.zeros((n, max_new), np.int32)
    produced = np.zeros((n, max_new), np.int32)
    valid = np.zeros((n, max_new), bool)
    for i, (p, s) in enumerate(zip(prompts, streams)):
        tokens[i, : len(p)] = p
        tokens[i, len(p) : len(p) + len(s)] = s
        at[i, : len(s)] = len(p) - 1 + np.arange(len(s))
        produced[i, : len(s)] = s
        valid[i, : len(s)] = True
    argmax, gap, finite = _gaps(params, jnp.asarray(tokens), jnp.asarray(at), jnp.asarray(produced))
    if not bool(finite):
        raise FloatingPointError("the reference decoder produced non-finite logits")
    argmax, gap = np.asarray(argmax), np.asarray(gap)
    off = int(np.sum((argmax != produced) & valid))
    return int(valid.sum()), off, float(np.where(valid, gap, 0.0).max())
