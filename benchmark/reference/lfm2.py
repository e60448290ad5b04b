"""LFM2-8B-A1B as its configuration file states it, in plain float32.

Equations (``u = RMSNorm(x)``: ``x / sqrt(mean(x^2) + eps) * g``, eps
1e-5; no bias anywhere), from the source's ``config.json``
(``model_type: lfm2_moe``) and its published description:

* ``x0 = E[tokens]`` — no position term at the embedding. After the
  last layer ``RMSNorm`` (the config's ``embedding_norm``), then
  ``logits = x E^T``.
* every layer ``l``: ``h = x + Op_l(RMSNorm_op(x))``,
  ``y = h + FFN_l(RMSNorm_ffn(h))``.
* ``Op`` = gated short convolution (``layer_types[l] == "conv"``):
  ``[B, C, X] = split3(W_in u)``; ``z_t = B_t * X_t``; ``c_t = sum_j
  w[:, j] * z_{t-2+j}`` (depthwise, causal, kernel ``conv_L_cache`` = 3,
  zeros before the sequence); ``Op = W_out (C_t * c_t)``.
* ``Op`` = attention (``"full_attention"``): ``q = W_q u`` (32 heads of
  64), ``k = W_k u``, ``v = W_v u`` (8 heads of 64); q and k each
  RMSNorm over the 64 of a head (learned weight); rotary (theta 1e6,
  rotate-half, all 64 dimensions) on q and k; causal ``softmax(q k^T /
  8) v``, query head ``i`` reading K/V head ``i // 4``; ``W_o``.
* ``FFN``, ``l < num_dense_layers``: ``W2 (silu(W1 v) * W3 v)``, width
  7168.
* ``FFN`` after them: ``s = sigmoid(W_g v)`` over the 32 experts;
  ``I = top4(s + b)`` (``b`` the expert bias: the choice only);
  ``g_i = s_i / (sum_{j in I} s_j + 1e-6) * routed_scaling_factor``;
  ``FFN = sum_{i in I} g_i W2_i (silu(W1_i v) * W3_i v)``, width 1792.
  No shared expert, no capacity, no dropped token.

Departures from the published model, each the configuration's
(``assumed`` / ``reduced`` in its file):

* the first ``num_hidden_layers`` (16) of the 24 published layers;
* the output matrix is the embedding, transposed (the catalog's row does
  not say; the family ties them);
* the router's arithmetic is float32 (the served dtype is bfloat16);
* the expert bias is drawn from the seed (normal, sd 0.02) and not left
  at zero, so that a program that adds it to the gate fails;
* random weights from ``--seed`` stand for the checkpoint.

Everything here is ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no cache, no kernel, no
sorted or grouped product (every expert multiplies every row and the
gate, zero off the top 4, weighs it), one layer at a time, and within an
expert layer one expert at a time, so that a float32 copy of one expert
(44 MB), not of the model, is what the reference adds beside the weights
the program serves from.

The WEIGHTS are the benchmark's: made here from the seed
(:func:`init_params`), in bfloat16 as the configuration serves them
(router matrix and bias float32), in the pytree the program takes as a
checkpoint (``flexflow_tpu/generation/decoder.py``): ``tok_embed`` [V,
E], ``final_ln_g``, ``layers``: a list of ``ln1_g``; ``wq`` [E, 32, 64],
``wk`` / ``wv`` [E, 8, 64], ``wo`` [32, 64, E], ``q_norm_g`` /
``k_norm_g`` [64] or ``conv_in`` [E, 3E], ``conv_w`` [E, 3],
``conv_out`` [E, E]; ``ln2_g``; ``w1`` / ``w3`` [E, F], ``w2`` [F, E] or
``router`` [E, N], ``router_bias`` [N], ``ew1`` / ``ew3`` [N, E, Fe],
``ew2`` [N, Fe, E]. The reference reads nothing the program has made.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .decoder import layout, reading  # noqa: F401  (the decoder cells' layout of a sample, and their reading of a judged one)

ROWS = 4  # requests per call: [rows, 32, S, S] float32 scores stay at 0.5 GiB


def sizes(config: Dict) -> Dict:
    """The numbers the equations need, from the configuration file's
    keys (the source's own names)."""
    c = config
    n = int(c["num_hidden_layers"])
    return {
        "layers": n, "e": int(c["hidden_size"]), "heads": int(c["num_attention_heads"]),
        "kv_heads": int(c["num_key_value_heads"]),
        "head_dim": int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]),
        "f": int(c["intermediate_size"]), "fe": int(c["moe_intermediate_size"]),
        "experts": int(c["num_experts"]), "top_k": int(c["num_experts_per_tok"]),
        "dense": int(c["num_dense_layers"]), "kernel": int(c["conv_L_cache"]), "vocab": int(c["vocab_size"]),
        "theta": float(c["rope_theta"]), "eps": float(c["norm_eps"]),
        "scaling": float(c["routed_scaling_factor"]),
        # the first n of the published pattern
        "types": tuple("attention" if t == "full_attention" else "conv" for t in c["layer_types"][:n]),
    }


# ------------------------------------------------------------------ weights
def _uniform(key, shape, fan_in, fan_out, dtype=jnp.bfloat16):
    lim = (6.0 / (fan_in + fan_out)) ** 0.5
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim).astype(dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _init_layer(key, kind, ffn, dims):
    e, h, hk, d, f, fe, n, kernel = dims
    keys = iter(jax.random.split(key, 10))
    ones = jnp.ones((e,), jnp.bfloat16)
    layer = {"ln1_g": ones, "ln2_g": ones}
    if kind == "attention":
        layer.update(
            wq=_uniform(next(keys), (e, h, d), e, h * d), wk=_uniform(next(keys), (e, hk, d), e, hk * d),
            wv=_uniform(next(keys), (e, hk, d), e, hk * d), wo=_uniform(next(keys), (h, d, e), h * d, e),
            q_norm_g=jnp.ones((d,), jnp.bfloat16), k_norm_g=jnp.ones((d,), jnp.bfloat16),
        )
    else:
        layer.update(
            conv_in=_uniform(next(keys), (e, 3 * e), e, 3 * e),
            # a kernel of 3 taps: uniform over +-sqrt(3 / 3), unit gain
            conv_w=_uniform(next(keys), (e, kernel), kernel, kernel),
            conv_out=_uniform(next(keys), (e, e), e, e),
        )
    if ffn == "swiglu":
        layer.update(w1=_uniform(next(keys), (e, f), e, f), w3=_uniform(next(keys), (e, f), e, f),
                     w2=_uniform(next(keys), (f, e), f, e))
    else:
        layer.update(
            router=_uniform(next(keys), (e, n), e, n, jnp.float32),
            router_bias=0.02 * jax.random.normal(next(keys), (n,), jnp.float32),
            ew1=_uniform(next(keys), (n, e, fe), e, fe), ew3=_uniform(next(keys), (n, e, fe), e, fe),
            ew2=_uniform(next(keys), (n, fe, e), fe, e),
        )
    return layer


def init_params(seed: int, config: Dict) -> Dict:
    """The configuration's weights from the seed, on the device, one
    jitted call per layer (a float32 draw of one tensor, 0.5 GB for an
    expert stack, is the most that lives beside the bfloat16 result):
    Glorot-uniform matrices, unit norms, a 0.02-normal expert bias."""
    s = sizes(config)
    keys = jax.random.split(jax.random.key(seed), s["layers"] + 1)
    dims = (s["e"], s["heads"], s["kv_heads"], s["head_dim"], s["f"], s["fe"], s["experts"], s["kernel"])
    embed = jax.jit(lambda k: _uniform(k, (s["vocab"], s["e"]), s["vocab"], s["e"]))(keys[0])
    return {
        "tok_embed": embed, "final_ln_g": jnp.ones((s["e"],), jnp.bfloat16),
        "layers": [
            _init_layer(keys[1 + l], s["types"][l], "swiglu" if l < s["dense"] else "experts", dims)
            for l in range(s["layers"])
        ],
    }


# ---------------------------------------------------------------- equations
def _c(a, s):
    """``a`` in the type the equations are computed in: float32 for the
    reference; for its control the served bfloat16."""
    return a.astype(s["dtype"])


def _rms(x, g, s):
    xf = x.astype(jnp.float32)
    return _c(xf / jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + s["eps"]) * g.astype(jnp.float32), s)


CHUNK = 128  # the control's partial sums: one MXU tile of the contraction


def _mm(a, w, s):
    """``a [..., K] @ w [K, N]`` in the equations' type. The control
    ``bfloat16_sums`` (one step coarser than the configuration states,
    which accumulates in float32) keeps its running sum in bfloat16: the
    contraction in chunks of ``CHUNK``, each partial product and each
    sum of two rounded to bfloat16."""
    w = _c(w, s)
    if not s.get("bf16_sums"):
        return a @ w
    acc = jnp.zeros(a.shape[:-1] + w.shape[1:], jnp.bfloat16)
    for lo in range(0, w.shape[0], CHUNK):
        acc = acc + jnp.matmul(a[..., lo : lo + CHUNK], w[lo : lo + CHUNK], preferred_element_type=jnp.bfloat16)
    return acc


def _heads(u, w, s):
    """``u [N, S, E]`` through ``w [E, H, D]`` -> ``[N, S, H, D]``."""
    return _mm(u, w.reshape(w.shape[0], -1), s).reshape(u.shape[:-1] + w.shape[1:])


def _rotary(x, theta):
    """Rotate-half over all of the head's dimensions: x [N, S, H, D]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]  # [S, D/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return (x * cos + rotated * sin).astype(x.dtype)


def _attention(u, layer, s):
    n, t, _ = u.shape
    q, k, v = _heads(u, layer["wq"], s), _heads(u, layer["wk"], s), _heads(u, layer["wv"], s)
    q = _rotary(_rms(q, layer["q_norm_g"], s), s["theta"])
    k = _rotary(_rms(k, layer["k_norm_g"], s), s["theta"])
    group = s["heads"] // s["kv_heads"]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)  # query head i reads K/V head i // group
    scores = jnp.einsum("nqhd,nkhd->nhqk", q, k) / float(np.sqrt(s["head_dim"]))
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    probs = _c(jax.nn.softmax(scores.astype(jnp.float32), axis=-1), s)
    ctx = jnp.einsum("nhqk,nkhd->nqhd", probs, v)
    return _mm(ctx.reshape(n, t, -1), layer["wo"].reshape(-1, layer["wo"].shape[-1]), s)


def _short_conv(u, layer, s):
    b_, c_, x_ = jnp.split(_mm(u, layer["conv_in"], s), 3, axis=-1)
    z = b_ * x_
    k = s["kernel"]
    zpad = jnp.pad(z, ((0, 0), (k - 1, 0), (0, 0)))  # zeros before the sequence
    w = layer["conv_w"].astype(jnp.float32)
    c = _c(sum(w[:, j] * zpad[:, j : j + z.shape[1]].astype(jnp.float32) for j in range(k)), s)
    return _mm(c_ * c, layer["conv_out"], s)


def routing(v, layer, s):
    """Gates [..., N] (float32, whatever the equations' type: the
    configuration's router is), zero off a token's top k: the bias moves
    the choice and never the gate."""
    score = jax.nn.sigmoid(jnp.matmul(v.astype(jnp.float32), layer["router"], precision="highest"))
    _, chosen = jax.lax.top_k(score + layer["router_bias"], s["top_k"])
    picked = jnp.take_along_axis(score, chosen, axis=-1)
    gate = picked / (picked.sum(-1, keepdims=True) + 1e-6) * s["scaling"]
    return jnp.sum(jax.nn.one_hot(chosen, s["experts"], dtype=jnp.float32) * gate[..., None], axis=-2)


def _experts(v, layer, s):
    gates = routing(v, layer, s)

    def one(acc, expert):
        w1, w3, w2, g = expert
        y = _mm(jax.nn.silu(_mm(v, w1, s)) * _mm(v, w3, s), w2, s)
        return (acc + g[..., None] * y.astype(jnp.float32)).astype(acc.dtype), None

    # (the sum over a token's experts: float32, or the control's bfloat16)
    zeros = jnp.zeros(v.shape, jnp.bfloat16 if s.get("bf16_sums") else jnp.float32)
    out, _ = jax.lax.scan(one, zeros, (layer["ew1"], layer["ew3"], layer["ew2"], jnp.moveaxis(gates, -1, 0)))
    return _c(out, s)


def _swiglu(v, layer, s):
    return _mm(jax.nn.silu(_mm(v, layer["w1"], s)) * _mm(v, layer["w3"], s), layer["w2"], s)


def block(x, layer, kind: str, s: Dict):
    """One layer: ``h = x + Op(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``."""
    u = _rms(x, layer["ln1_g"], s)
    h = x + (_attention(u, layer, s) if kind == "attention" else _short_conv(u, layer, s))
    v = _rms(h, layer["ln2_g"], s)
    return h + (_experts(v, layer, s) if "router" in layer else _swiglu(v, layer, s))


@functools.lru_cache(maxsize=None)
def _programs(frozen_sizes, dtype_name: str = "float32", coarser: str = ""):
    """The jitted pieces: embedding, one layer of each kind, head.
    ``coarser``, a control's step below the stated arithmetic: ``int8``
    (every matrix rounded to int8 levels INSIDE the piece that reads it,
    so that the rounded copy of one layer is all that ever lives beside
    the weights) or ``bfloat16_sums`` (:func:`_mm`)."""
    s = dict(frozen_sizes, dtype=jnp.dtype(dtype_name), bf16_sums=coarser == "bfloat16_sums")
    rounded = (lambda tree: {k: _int8(k, a) for k, a in tree.items()}) if coarser == "int8" else (lambda tree: tree)
    # float32 is float32: on a TPU a float32 matmul at the default
    # precision is one bfloat16 pass. (The control computes in the
    # served bfloat16, where the default is what the program has.)
    highest = jax.default_matmul_precision("highest" if dtype_name == "float32" else "default")

    def embed(table, tokens):
        return _c(rounded({"tok_embed": table})["tok_embed"][tokens], s)

    def layer_fn(kind):
        def run(x, layer):
            with highest:
                return block(x, rounded(layer), kind, s)
        return jax.jit(run)

    def head(x, g, table, at):
        with highest:
            x = _rms(jnp.take_along_axis(x, at[:, :, None], axis=1), g, s)
            table = rounded({"tok_embed": table})["tok_embed"]
            if s["bf16_sums"]:
                return _mm(x, table.T, s).astype(jnp.float32)
            return jnp.matmul(x, _c(table, s).T, preferred_element_type=jnp.float32)

    return jax.jit(embed), {k: layer_fn(k) for k in ("attention", "conv")}, jax.jit(head)


def hidden(params: Dict, tokens, config: Dict, dtype: str = "float32", coarser: str = ""):
    """[N, S] tokens -> the last layer's output [N, S, E], layer by
    layer, in float32 (``dtype``: the control's is the served one)."""
    s = sizes(config)
    embed, layer_fns, _ = _programs(tuple(sorted(s.items())), dtype, coarser)
    x = embed(params["tok_embed"], tokens)
    for kind, layer in zip(s["types"], params["layers"]):
        x = layer_fns[kind](x, layer)
    return x


def logits_at(params: Dict, tokens, at, config: Dict, dtype: str = "float32", coarser: str = ""):
    """[N, S] tokens, [N, T] positions -> the logits [N, T, V] that
    predict the token after each position."""
    s = sizes(config)
    head = _programs(tuple(sorted(s.items())), dtype, coarser)[2]
    return head(hidden(params, tokens, config, dtype, coarser), params["final_ln_g"], params["tok_embed"], at)


@jax.jit
def _gaps(logits, chosen):
    top2 = jax.lax.top_k(logits, 2)[0]
    got = jnp.take_along_axis(logits, chosen[:, :, None], axis=2)[..., 0]
    return top2[..., 0] - got, top2[..., 0] - top2[..., 1]


def judge(params: Dict, config: Dict, tokens, at, arms: Dict[str, np.ndarray], valid, rows: int = ROWS) -> Dict[str, Dict]:
    """Each arm's tokens (``arms[name]`` [N, T]: the tokens chosen after
    positions ``at`` of ``tokens``) as the float32 reference sees them,
    ``decoder.judge``'s contract per arm: ``gap``, how far the token's
    logit lies below the reference's best, and ``margin``, how far the
    reference's second lies below its best, as flat arrays over the
    ``valid`` tokens. The reference's logits are computed once for all
    arms, ``rows`` requests a call."""
    out = {name: {"gap": [], "margin": []} for name in arms}
    for lo in range(0, len(tokens), rows):
        part = [np.resize(a[lo : lo + rows], (rows,) + a.shape[1:]) for a in (tokens, at)]
        logits = logits_at(params, jnp.asarray(part[0]), jnp.asarray(part[1]), config)
        if not bool(jnp.all(jnp.isfinite(logits))):
            raise FloatingPointError("the reference produced non-finite logits")
        keep = valid[lo : lo + rows]
        for name, chosen in arms.items():
            gap, margin = _gaps(logits, jnp.asarray(np.resize(chosen[lo : lo + rows], (rows,) + chosen.shape[1:])))
            out[name]["gap"].append(np.asarray(gap)[: len(keep)][keep])
            out[name]["margin"].append(np.asarray(margin)[: len(keep)][keep])
    return {name: {k: np.concatenate(v) for k, v in arm.items()} for name, arm in out.items()}


def choices(params: Dict, config: Dict, tokens, at, arithmetic: str, rows: int = ROWS) -> np.ndarray:
    """[N, T] greedy tokens after each position ``at`` of ``tokens`` of
    the same equations in a coarser ``arithmetic``, put in the program's
    place (after the same prefixes) and judged as served tokens are:

    * ``bfloat16`` — the arithmetic the configuration STATES
      (``matmul_precision`` in its file): bfloat16 weights and
      activations, float32 accumulation, norms, softmax and the router
      in float32. Not a control: the yardstick. How far this lies from
      the float32 reference is how far honest bfloat16 lies from it;
    * ``int8`` — the CONTROL, one step coarser than the configuration
      states: every matrix rounded to 255 levels per output channel,
      under that same arithmetic;
    * ``bfloat16_sums`` — the other step below it: the stated arithmetic
      with every matrix product's running sum, and the sum over a
      token's experts, kept in bfloat16 (:func:`_mm`). A second reading,
      not the one the limits are set against."""
    if arithmetic not in ("bfloat16", "int8", "bfloat16_sums"):
        raise ValueError(f"arithmetic {arithmetic!r}: 'bfloat16', 'int8' or 'bfloat16_sums'")
    out = []
    for lo in range(0, len(tokens), rows):
        part = [np.resize(a[lo : lo + rows], (rows,) + a.shape[1:]) for a in (tokens, at)]
        logits = logits_at(params, jnp.asarray(part[0]), jnp.asarray(part[1]), config, "bfloat16",
                           "" if arithmetic == "bfloat16" else arithmetic)
        out.append(np.asarray(jnp.argmax(logits, -1))[: len(tokens) - lo])
    return np.concatenate(out)


def gap_ratio(judged: Dict, stated: Dict) -> float:
    """THE number the cell's ``correct`` compares: the summed distance
    of an arm's tokens below the float32 reference's best logit, over
    the same sum for the choices of the reference computed in the
    arithmetic the configuration states, after the same prefixes.

    Why a ratio and not ``decoder.reading``'s ``near_tie_gap`` alone.
    In this model a token's logits do not move smoothly with the
    arithmetic's error: wherever two experts' router scores are nearly
    tied (one layer in fourteen for a token, with random weights), any
    rounding flips the choice and the logits jump by whole tenths. A
    sound bfloat16 program therefore sits four fifths of its tokens off
    the float32 reference's argmax (my chip runs, PR 27), far from any
    limit a GPT-2 cell could use and by an amount that moves with the
    seed's weights. What a sound program cannot do is sit FARTHER from
    the float32 reference than the plain equations do when they are
    computed in the very arithmetic the configuration states: that
    distance is measured in the same run, on the same prefixes, by the
    same judge, and the ratio of the two is steady from seed to seed.
    A program a step coarser (the int8 control), or one that computes
    another model, reads well over 1."""
    return float(judged["gap"].sum() / max(float(stated["gap"].sum()), 1e-30))


def worst_request_ratio(judged: Dict, stated: Dict, valid) -> float:
    """:func:`gap_ratio` request by request (``valid`` [N, T]: which
    tokens of which request the flat arrays hold), the largest. The
    pooled ratio is a mean over ~20,000 tokens and moves by 5 % when one
    token in twenty is wrong; a fault that garbles ONE stream (a slot's
    state, a block, a hand-over at one request's bucket boundary) sits
    in one request of the sample, whose own ratio it multiplies."""
    of = np.nonzero(np.asarray(valid))[0]
    own = np.bincount(of, weights=judged["gap"], minlength=len(valid))
    ref = np.bincount(of, weights=stated["gap"], minlength=len(valid))
    return float(np.max(own[ref > 0] / ref[ref > 0]))


def _int8(name: str, a):
    """The weight ``name`` as int8 storage would hold it: one symmetric
    scale per output channel (per row of the embedding, per expert and
    output channel of an expert stack), 255 levels; vectors, the
    convolution's 3 taps and the float32 router are left alone."""
    if a.ndim < 2 or name in ("router", "conv_w"):
        return a
    axis = -1 if name == "tok_embed" else (1 if name in ("ew1", "ew3", "ew2") else 0)
    af = a.astype(jnp.float32)
    scale = jnp.max(jnp.abs(af), axis=axis, keepdims=True) / 127.0
    return (jnp.round(af / scale) * scale).astype(a.dtype)


def engine_config(config: Dict, max_positions: int):
    """The configuration as the program takes it
    (``flexflow_tpu.generation.decoder.DecoderConfig``)."""
    from flexflow_tpu.core.types import DataType
    from flexflow_tpu.generation.decoder import DecoderConfig

    s = sizes(config)
    dtype = {"bfloat16": DataType.BFLOAT16, "float32": DataType.FLOAT}[config.get("serving_dtype", "bfloat16")]
    return DecoderConfig(
        num_layers=s["layers"], hidden_size=s["e"], num_heads=s["heads"], ff_size=s["f"],
        seq_length=max_positions, vocab_size=s["vocab"], causal=True, dtype=dtype,
        norm="rmsnorm", norm_eps=s["eps"], positions="rotary", rope_theta=s["theta"], qk_norm=True,
        num_kv_heads=s["kv_heads"], head_dim=s["head_dim"], layer_types=s["types"], conv_kernel=s["kernel"],
        ffn="swiglu", num_dense_layers=s["dense"], num_experts=s["experts"], experts_per_token=s["top_k"],
        moe_ff_size=s["fe"], routed_scaling_factor=s["scaling"], tied_head=True,
    )


def cast_params(params: Dict, dtype) -> Dict:
    """The weights in another type (the CPU tests serve float32), the
    float32 router left as it is."""
    return jax.tree.map(lambda a: a if a.dtype == jnp.float32 else a.astype(dtype), params)


def expert_tokens(params: Dict, config: Dict, sequences: Sequence[Sequence[int]]) -> List[List[int]]:
    """Tokens each expert of each expert layer is handed when every
    sequence is run whole: the count the program's counters are held to
    (one row per expert layer, over all positions of all sequences)."""
    s = sizes(config)
    embed, layer_fns, _ = _programs(tuple(sorted(s.items())))
    counts = [np.zeros(s["experts"], np.int64) for l in range(s["layers"]) if l >= s["dense"]]
    for seq in sequences:
        x = embed(params["tok_embed"], jnp.asarray([list(seq)], jnp.int32))
        row = 0
        for kind, layer in zip(s["types"], params["layers"]):
            if "router" in layer:
                with jax.default_matmul_precision("highest"):
                    s32 = dict(s, dtype=jnp.dtype("float32"))
                    u = _rms(x, layer["ln1_g"], s32)
                    h = x + (_attention(u, layer, s32) if kind == "attention" else _short_conv(u, layer, s32))
                    gates = routing(_rms(h, layer["ln2_g"], s32), layer, s32)
                counts[row] += np.asarray((gates > 0).sum(axis=(0, 1)))
                row += 1
            x = layer_fns[kind](x, layer)
    return [c.tolist() for c in counts]
