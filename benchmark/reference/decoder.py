"""GPT-2-medium as the configuration file states it: plain float32 at
XLA's default matmul precision (``matmul_precision`` in the file: on
the TPU a float32 matmul is one bfloat16 pass with float32
accumulation, and float32 everywhere between the matmuls).

A pre-LN causal decoder: token + learned position embedding; per block
LayerNorm, 16 heads of 64 with a causal softmax scaled by 1/sqrt(64),
output projection, residual; LayerNorm, 4096-wide GELU (tanh form,
``gelu_new``) feed-forward with biases, residual; a final LayerNorm and
an output matrix. Departures from the source are the configuration's:
no bias on q/k/v/o, ``lm_head`` not tied to the embedding.

The weights are the BENCHMARK's, made here from ``--seed``
(``init_params``) and handed to the program as a checkpoint would be:
``tok_embed`` [V, E], ``pos_embed`` [P, E], ``final_ln_g/b``,
``lm_head`` [E, V], ``layers``: a list of ``ln1_g/b``, ``wq/wk/wv``
[E, H, D], ``wo`` [H, D, E], ``ln2_g/b``, ``ff1`` [E, F], ``ff1_b``,
``ff2`` [F, E], ``ff2_b``. The reference reads nothing the program has
made. Layers are stacked and scanned so that the 24-layer program
compiles as one block. Every equation runs in the type of the weights
it is given (no constant promotes): float32 for the reference, a lower
type for its control (``control.py``).
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5
ROWS = 16  # requests per call of the reference: its [rows, H, S, S] scores stay at 1 GiB
NEAR_TIE = 0.01  # logits: the reference's two best closer than this are a near-tie


def _uniform(key, shape, fan_in, fan_out):
    lim = (6.0 / (fan_in + fan_out)) ** 0.5
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def _init(key, v, p, e, h, f, n_layers):
    d = e // h
    keys = iter(jax.random.split(key, 4 + 6 * n_layers))
    ones, zeros = jnp.ones((e,), jnp.float32), jnp.zeros((e,), jnp.float32)
    params = {
        "tok_embed": _uniform(next(keys), (v, e), v, e),
        "pos_embed": 0.02 * jax.random.normal(next(keys), (p, e), jnp.float32),
        "final_ln_g": ones, "final_ln_b": zeros,
        "lm_head": _uniform(next(keys), (e, v), e, v),
    }
    params["layers"] = [
        {
            "ln1_g": ones, "ln1_b": zeros,
            "wq": _uniform(next(keys), (e, h, d), e, e),
            "wk": _uniform(next(keys), (e, h, d), e, e),
            "wv": _uniform(next(keys), (e, h, d), e, e),
            "wo": _uniform(next(keys), (h, d, e), e, e),
            "ln2_g": ones, "ln2_b": zeros,
            "ff1": _uniform(next(keys), (e, f), e, f), "ff1_b": jnp.zeros((f,), jnp.float32),
            "ff2": _uniform(next(keys), (f, e), f, e), "ff2_b": zeros,
        }
        for _ in range(n_layers)
    ]
    return params


def init_params(seed: int, config: Dict) -> Dict:
    """The configuration's float32 weights from the seed, on the device,
    in one jitted call: Glorot-uniform matrices, 0.02-normal positions,
    unit LayerNorms, zero biases (the values PR 22-25's runs served:
    the same draws in the same order as the program's own initialiser,
    which the benchmark no longer calls)."""
    c = config
    return _init(jax.random.key(seed), c["vocab_size"], c["n_positions"], c["n_embd"], c["n_head"],
                 c["n_inner"], c["n_layer"])


def _layer_norm(x, g, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(float(np.sqrt(2.0 / np.pi)) * (x + 0.044715 * x ** 3)))


def _block(x, layer):
    n, s, _ = x.shape
    h = _layer_norm(x, layer["ln1_g"], layer["ln1_b"])
    q = jnp.einsum("nse,ehd->nhsd", h, layer["wq"])
    k = jnp.einsum("nse,ehd->nhsd", h, layer["wk"])
    v = jnp.einsum("nse,ehd->nhsd", h, layer["wv"])
    scores = jnp.einsum("nhqd,nhkd->nhqk", q, k) / float(np.sqrt(q.shape[-1]))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    ctx = jnp.einsum("nhqk,nhkd->nqhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + jnp.einsum("nqhd,hde->nqe", ctx, layer["wo"])
    h = _layer_norm(x, layer["ln2_g"], layer["ln2_b"])
    h = _gelu_new(h @ layer["ff1"] + layer["ff1_b"])
    return x + h @ layer["ff2"] + layer["ff2_b"]


def logits_at(params: Dict, tokens: jax.Array, at: jax.Array) -> jax.Array:
    """[N, S] tokens, [N, T] positions -> the logits [N, T, V] that
    predict the token after each position, in the weights' type."""
    stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *params["layers"])
    x = params["tok_embed"][tokens] + params["pos_embed"][jnp.arange(tokens.shape[1])][None]
    x, _ = jax.lax.scan(lambda x, layer: (_block(x, layer), None), x, stacked)
    x = _layer_norm(x, params["final_ln_g"], params["final_ln_b"])
    return jnp.take_along_axis(x, at[:, :, None], axis=1) @ params["lm_head"]


@jax.jit
def _judge(params, tokens, at, chosen):
    logits = logits_at(params, tokens, at)
    top2 = jax.lax.top_k(logits, 2)[0]
    got = jnp.take_along_axis(logits, chosen[:, :, None], axis=2)[..., 0]
    return top2[..., 0] - got, top2[..., 0] - top2[..., 1], jnp.all(jnp.isfinite(logits))


def layout(prompts: Sequence[Sequence[int]], streams: Sequence[Sequence[int]], pad_to: int, max_new: int) -> Dict:
    """``prompt + produced`` of every request as arrays of one shape
    (fixed by ``pad_to`` and ``max_new``, so that one compiled program
    serves every run; a causal model's earlier positions do not see the
    padding): ``tokens`` [N, pad_to], ``at`` [N, max_new] the position
    whose logits chose each produced token, ``chosen`` those tokens,
    ``valid`` which of them exist."""
    n = len(prompts)
    out = {"tokens": np.zeros((n, pad_to), np.int32), "at": np.zeros((n, max_new), np.int32),
           "chosen": np.zeros((n, max_new), np.int32), "valid": np.zeros((n, max_new), bool)}
    for i, (p, s) in enumerate(zip(prompts, streams)):
        out["tokens"][i, : len(p)] = p
        out["tokens"][i, len(p) : len(p) + len(s)] = s
        out["at"][i, : len(s)] = len(p) - 1 + np.arange(len(s))
        out["chosen"][i, : len(s)] = s
        out["valid"][i, : len(s)] = True
    return out


def judge(params: Dict, tokens, at, chosen, valid) -> Dict:
    """Each token ``chosen`` after position ``at`` of ``tokens``, as the
    reference sees it: ``gap``, how far its logit lies below the
    reference's best (0 where it is the argmax), and ``margin``, how far
    the reference's second logit lies below its best, both as flat
    arrays over the ``valid`` tokens. ``ROWS`` requests a call."""
    gaps, margins = [], []
    for lo in range(0, len(tokens), ROWS):
        rows = [np.resize(a[lo : lo + ROWS], (ROWS,) + a.shape[1:]) for a in (tokens, at, chosen)]
        gap, margin, finite = _judge(params, *map(jnp.asarray, rows))
        if not bool(finite):
            raise FloatingPointError("the reference decoder produced non-finite logits")
        keep = valid[lo : lo + ROWS]
        gaps.append(np.asarray(gap)[: len(keep)][keep])
        margins.append(np.asarray(margin)[: len(keep)][keep])
    return {"gap": np.concatenate(gaps), "margin": np.concatenate(margins)}


def reading(judged: Dict) -> Dict:
    """The number ``correct`` compares, and what it is made of.

    Coarser arithmetic shows in greedy tokens only where the reference's
    two best logits are nearly tied: there it picks the second, a little
    below the best. How many such positions a seed's random weights
    offer moves eight-fold from seed to seed (6 to 28 % of tokens within
    ``NEAR_TIE``; chip runs, PR 26), and any count of wrong picks with
    it. So ``near_tie_gap`` is the SUM of all tokens' gaps below the
    reference's best logit per NEAR-TIED position: it grows with the
    square of the arithmetic's error, it is steady from seed to seed
    (2.1e-5 to 3.3e-5 over twelve), and one plainly wrong token (0.1 or
    more below) in thousands lifts it past any limit set here."""
    gap, margin = judged["gap"], judged["margin"]
    near = int((margin < NEAR_TIE).sum())
    return {
        "tokens": int(gap.size), "near_ties": near, "off_argmax": int((gap > 0).sum()),
        "worst_gap": float(gap.max()), "near_tie_gap": float(gap.sum() / max(near, 1)),
    }
