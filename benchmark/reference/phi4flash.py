"""Phi-4-mini-flash-reasoning (``model_type: phi4flash``; arXiv:2507.06607, the
decoder-hybrid-decoder "SambaY") as its configuration file states it, in plain
float32: the WHOLE published model, every layer and the whole vocabulary.

Equations (``LN(x; g, b) = (x - mean) / sqrt(var + eps) * g + b``, eps 1e-5).
``x0 = E[tokens]``: nothing is added at the embedding and nothing is rotated;
the model has no positional signal but the causal mask and the recurrences.
Every layer ``l`` of the 32 is ``u = x + Op_l(LN1(x))``, ``y = u +
SwiGLU(LN2(u))``, ``SwiGLU(v) = W_down(silu(W_gate v) * W_up v)`` (width
10,240, no bias); after the last a final LayerNorm, then ``logits = x E^T``
(tied, no bias). ``Op_l`` by the layer's index (``h`` its normed input):

* **Mamba-1 mixer**, layers 0, 2, ..., 16 (``d_inner`` D = 2 E = 5,120; N = 16;
  R = ceil(E / 16) = 160; kernel 4): ``[x, z] = W_in h`` (no bias); ``x <-
  silu(conv1d_causal(x) + b_conv)`` (depthwise, zeros before the sequence);
  ``[delta (R), B (N), C (N)] = W_x x``; ``dt = softplus(W_dt delta + b_dt)``
  [D]; ``A = -exp(A_log)`` [D, N]; in float32, from ``S = 0``::

      S_t[d, n] = exp(dt_t[d] A[d, n]) S_{t-1}[d, n] + dt_t[d] B_t[n] x_t[d]
      y_t[d]    = sum_n C_t[n] S_t[d, n] + D[d] x_t[d]

  ``out = W_out (y_t * silu(z_t))``. Layer 16 also hands on ``m_t = y_t``
  (before the gate): the MEMORY.
* **Gated Memory Unit**, layers 18, 20, ..., 30: ``out = W_2 (m_t * silu(W_1
  h))``, ``m_t`` layer 16's at the same row; no bias.
* **Differential attention**: window layers 1, 3, ..., 15 (mask: own position
  and the 511 before it), layer 17 (causal), cross layers 19, 21, ..., 31
  (causal, over LAYER 17's K/V: a cross layer has ``W_q`` and ``W_o`` alone).
  ``q = W_q h + b_q`` as 40 heads of 64, ``k``, ``v = W_{k,v} h + b`` as 20 heads
  of 64, ``W_o`` with bias. Query pair ``i`` of 20 is ``q1_i = q[2i]``, ``q2_i =
  q[2i + 1]``; K/V pair ``j`` of 10 is ``k1_j = k[2j]``, ``k2_j = k[2j + 1]``,
  ``V_j = [v[2j] ‖ v[2j + 1]]`` (128 wide); query pair ``i`` reads K/V pair ``i
  // 2``. ``a1_i = softmax(q1_i k1_j^T / 8 + mask) V_j``, ``a2_i`` likewise of
  ``q2_i``, ``k2_j``; ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``,
  ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``; ``o_i = (1 - lambda_init)
  RMSNorm_128(a1_i - lambda a2_i; g)``; the 20 x 128 read as 40 x 64 into
  ``W_o``. Softmax, lambda and the norm in float32.

:func:`logits_at` computes that: EVERY layer over EVERY row (the program runs
layers 18-31 of a prompt on its last row alone). Beside it, for ``gap_ratio``,
the same equations "in the arithmetic the configuration states" (``dtype=
"bfloat16"``): bfloat16 weights and activations with float32 accumulation;
norms, softmax, lambda, ``dt``, the decay, the recurrence, the stored state,
the memory, the skip and the gates in float32. The controls are that
arithmetic with one thing changed (:data:`CONTROLS`).

Everything here is ``jax.numpy`` under ``jax.default_matmul_precision("highest")``
for float32: no cache, no kernel, no chunk (the recurrence is a plain scan over
positions), no batching beyond ``ROWS`` requests a call, one layer at a time,
attention one K/V pair at a time. The WEIGHTS are the benchmark's: made here
from the seed (:func:`init_params`), bfloat16 (``A_log``, ``D``, the step's
bias and the four lambda vectors float32), in the pytree the program takes as a
checkpoint: ``tok_embed`` [V, E], ``final_ln_g`` / ``final_ln_b``, ``layers``: a
list of ``ln1_g`` / ``ln1_b`` / ``ln2_g`` / ``ln2_b``, ``w1`` (gate) / ``w3`` (up)
[E, F] / ``w2`` [F, E], and by kind ``ssm_in`` [E, 2 D], ``ssm_conv_w`` [D, 4],
``ssm_conv_b``, ``ssm_x`` [D, R + 2 N], ``ssm_dt_w`` [R, D], ``ssm_dt_bias`` [D],
``ssm_a_log`` [D, N], ``ssm_d`` [D], ``ssm_out`` [D, E]; ``gmu_in`` [E, D],
``gmu_out`` [D, E]; ``wq`` [E, 40, 64], ``wk`` / ``wv`` [E, 20, 64], ``wo`` [40,
64, E], ``bq`` / ``bk`` / ``bv`` / ``bo``, ``lambda_q1`` / ``k1`` / ``q2`` / ``k2``
[64], ``subln_g`` [128]. The reference reads nothing the program has made.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from .decoder import layout, reading  # noqa: F401  (the decoder cells' layout of a sample and reading of a judged one)
from .lfm2 import _c, _gaps, _uniform, cast_params, gap_ratio, worst_request_ratio  # noqa: F401
from .mellum2 import worst_request_excess  # noqa: F401  (the request-by-request comparison, as code-gen has it)

ROWS = 2  # requests per call
HEAD_ROWS = 256  # positions a call of the head (200,064 logits a position)
# what a control changes, beside the stated arithmetic it is computed in
CONTROLS = ("bfloat16_state", "no_lambda", "memory_from_other", "window_as_full", "cross_own_kv")


def kinds(layers: int) -> tuple:
    """A layer's kind by its index: the published ``layer_types`` rule
    (``mb_per_layer`` 2: every second layer a Mamba mixer in the first
    half; the split at ``layers // 2``: memory from that layer, the one
    full K/V from the next, then GMU and cross layers in turn)."""
    half = layers // 2
    out = []
    for l in range(layers):
        if l <= half:
            out.append("mamba" if l % 2 == 0 else "window")
        elif l == half + 1:
            out.append("attention")
        else:
            out.append("gmu" if l % 2 == 0 else "cross")
    return tuple(out)


def sizes(config: Dict) -> Dict:
    """The numbers the equations need, from the configuration file's keys
    (the source's own names; what the source does not hold is the file's
    ``assumed``, by key)."""
    c, a = config, config["assumed"]
    if c["mb_per_layer"] != 2 or c["hidden_act"] != "silu" or not c["tie_word_embeddings"] or c["mlp_bias"] or c["lm_head_bias"]:
        raise ValueError("a Mamba layer every second, silu, a tied head and bias-free SwiGLU are what is written down")
    e, heads, layers = int(c["hidden_size"]), int(c["num_attention_heads"]), int(c["num_hidden_layers"])
    if layers % 2 or layers < 6 or (layers // 2) % 2:
        raise ValueError(f"{layers} layers: the split at layers // 2 has to fall on a Mamba layer")
    return {
        "layers": layers, "kinds": kinds(layers), "e": e, "heads": heads, "kv_heads": int(c["num_key_value_heads"]),
        "head_dim": int(a["head_dim"]["value"]), "f": int(c["intermediate_size"]), "vocab": int(c["vocab_size"]),
        "eps": float(c["layer_norm_eps"]), "window": int(c["sliding_window"]),
        "inner": int(a["mamba_expand"]["value"]) * e, "state": int(a["mamba_d_state"]["value"]),
        "kernel": int(a["mamba_d_conv"]["value"]), "dt_rank": int(a["mamba_dt_rank"]["value"]),
        "dt_min": float(a["ssm_init"]["dt_min"]), "dt_max": float(a["ssm_init"]["dt_max"]), "dt_floor": float(a["ssm_init"]["dt_floor"]),
        "memory_layer": layers // 2, "kv_layer": layers // 2 + 1,
    }


def parameter_counts(config: Dict) -> Dict[str, float]:
    """Parameters by the file's own sizes: a layer of each kind (its
    SwiGLU and two norms included), the tied embedding, and the ``whole``
    model, which is also what this chip holds (nothing is cut)."""
    s = sizes(config)
    e, d, n, r, k = s["e"], s["inner"], s["state"], s["dt_rank"], s["kernel"]
    q, kv, hd = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"], s["head_dim"]
    ffn = 3 * e * s["f"] + 4 * e  # the SwiGLU and the two LayerNorms
    mamba = e * 2 * d + d * k + d + d * (r + 2 * n) + r * d + d + d * n + d + d * e + ffn
    diff = 4 * hd + 2 * hd  # lambda's four vectors and the pair norm's weight
    attention = e * q + q + 2 * (e * kv + kv) + q * e + e + diff + ffn
    cross = e * q + q + q * e + e + diff + ffn
    gmu = 2 * e * d + ffn
    per = {"mamba": mamba, "window": attention, "attention": attention, "gmu": gmu, "cross": cross}
    embedding = s["vocab"] * e
    whole = sum(per[kind] for kind in s["kinds"]) + embedding + 2 * e
    return {"mamba_layer": mamba, "attention_layer": attention, "cross_layer": cross, "gmu_layer": gmu,
            "embedding": embedding, "layers": whole - embedding - 2 * e, "whole": whole, "held": whole}


# ------------------------------------------------------------------ weights
@functools.partial(jax.jit, static_argnums=(1, 2))
def _init_layer(key, kind: str, dims):
    e, h, hk, hd, f, d, n, r, k, dt_min, dt_max, dt_floor = dims
    keys = iter(jax.random.split(key, 20))
    bf = jnp.bfloat16
    small = lambda shape: (0.02 * jax.random.normal(next(keys), shape, jnp.float32)).astype(bf)  # noqa: E731
    layer = {"ln1_g": jnp.ones((e,), bf), "ln1_b": small((e,)), "ln2_g": jnp.ones((e,), bf), "ln2_b": small((e,)),
             "w1": _uniform(next(keys), (e, f), e, f), "w3": _uniform(next(keys), (e, f), e, f), "w2": _uniform(next(keys), (f, e), f, e)}
    if kind == "mamba":
        # the published initialisation of the state-space parameters (assumed.ssm_init): dt log-uniform in
        # [dt_min, dt_max], floored, through the inverse softplus; A_log = log(1..N) a channel; D = 1
        step = jnp.exp(jax.random.uniform(next(keys), (d,), jnp.float32) * (math.log(dt_max) - math.log(dt_min)) + math.log(dt_min))
        step = jnp.maximum(step, dt_floor)
        layer.update(
            ssm_in=_uniform(next(keys), (e, 2 * d), e, 2 * d), ssm_conv_w=_uniform(next(keys), (d, k), k, 1), ssm_conv_b=small((d,)),
            ssm_x=_uniform(next(keys), (d, r + 2 * n), d, r + 2 * n), ssm_dt_w=_uniform(next(keys), (r, d), r, d),
            ssm_dt_bias=step + jnp.log(-jnp.expm1(-step)),
            ssm_a_log=jnp.log(jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (d, n))),
            ssm_d=jnp.ones((d,), jnp.float32), ssm_out=_uniform(next(keys), (d, e), d, e),
        )
    elif kind == "gmu":
        layer.update(gmu_in=_uniform(next(keys), (e, d), e, d), gmu_out=_uniform(next(keys), (d, e), d, e))
    else:
        layer.update(wq=_uniform(next(keys), (e, h, hd), e, h * hd), wo=_uniform(next(keys), (h, hd, e), h * hd, e),
                     bq=small((h, hd)), bo=small((e,)))
        if kind != "cross":
            layer.update(wk=_uniform(next(keys), (e, hk, hd), e, hk * hd), wv=_uniform(next(keys), (e, hk, hd), e, hk * hd),
                         bk=small((hk, hd)), bv=small((hk, hd)))
        layer.update({f"lambda_{name}": 0.1 * jax.random.normal(next(keys), (hd,), jnp.float32) for name in ("q1", "k1", "q2", "k2")})
        layer.update(subln_g=jnp.ones((2 * hd,), bf))
    return layer


def init_params(seed: int, config: Dict) -> Dict:
    """The configuration's weights from the seed, on the device, one jitted
    call per layer: Glorot-uniform matrices, unit norm weights, 0.02-normal
    biases, the state-space parameters as the published initialisation
    draws them and lambda's four vectors 0.1-normal (``assumed``)."""
    s = sizes(config)
    keys = jax.random.split(jax.random.key(seed), s["layers"] + 2)
    dims = (s["e"], s["heads"], s["kv_heads"], s["head_dim"], s["f"], s["inner"], s["state"], s["dt_rank"], s["kernel"],
            s["dt_min"], s["dt_max"], s["dt_floor"])
    v, e = s["vocab"], s["e"]
    return {
        "tok_embed": jax.jit(lambda k: _uniform(k, (v, e), v, e))(keys[0]),
        "final_ln_g": jnp.ones((e,), jnp.bfloat16),
        "final_ln_b": jax.jit(lambda k: (0.02 * jax.random.normal(k, (e,), jnp.float32)).astype(jnp.bfloat16))(keys[1]),
        "layers": [_init_layer(keys[2 + l], s["kinds"][l], dims) for l in range(s["layers"])],
    }


# ---------------------------------------------------------------- equations
def _ln(x, g, b, s):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return _c((xf - mu) / jnp.sqrt(var + s["eps"]) * g.astype(jnp.float32) + b.astype(jnp.float32), s)


def _sum32(eq: str, a, b):
    """An einsum whose sum is float32 whatever the operands' type: the
    operands widened (exactly) and multiplied at the precision in force."""
    return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32))


def _proj(a, w, s, bias=None):
    """``a [..., K] @ w [K, ...]`` summed in float32, a bias added there,
    handed on in the equations' type."""
    out = _sum32("...k,kn->...n", _c(a, s), _c(w, s).reshape(w.shape[0], -1))
    return _c(out if bias is None else out + bias.astype(jnp.float32).reshape(-1), s)


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _mamba(u, layer, s, lengths):
    """The Mamba-1 mixer of ``u`` [N, S, E]: its output, the memory ``m``
    [N, S, D] float32 (``y`` before the gate) and the state [N, D, N']
    after each row's first ``lengths`` positions (past them ``dt = 0``:
    the state stands still)."""
    n_rows, t = u.shape[:2]
    d, n, r, f32 = s["inner"], s["state"], s["dt_rank"], jnp.float32
    xz = _proj(u, layer["ssm_in"], s)
    x, z = xz[..., :d], xz[..., d:]
    pad = jnp.concatenate([jnp.zeros((n_rows, s["kernel"] - 1, d), x.dtype), x], axis=1).astype(f32)
    w = layer["ssm_conv_w"].astype(f32)
    x = _c(jax.nn.silu(sum(w[:, j] * pad[:, j : j + t] for j in range(s["kernel"])) + layer["ssm_conv_b"].astype(f32)), s)
    dbc = _proj(x, layer["ssm_x"], s)
    delta, b, c = dbc[..., :r], dbc[..., r : r + n], dbc[..., r + n :]
    dt = jax.nn.softplus(_sum32("...r,rd->...d", delta, _c(layer["ssm_dt_w"], s)) + layer["ssm_dt_bias"])
    dt = jnp.where(jnp.arange(t)[None, :, None] < lengths[:, None, None], dt, 0.0)
    a = -jnp.exp(layer["ssm_a_log"])  # [D, N']
    coarse = s.get("bf16_state")

    def step(state, row):
        xt, dtt, bt, ct = row  # [N, D], [N, D], [N, N'] x 2
        state = jnp.exp(dtt[..., None] * a) * state + (dtt * xt)[..., None] * bt[:, None, :]
        if coarse:  # the control: the stored state holds bfloat16 (reduce_precision: a cast there and back is elided on a TPU)
            state = jax.lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)
        return state, jnp.sum(state * ct[:, None, :], axis=-1)

    rows = tuple(jnp.moveaxis(v.astype(f32), 1, 0) for v in (x, dt, b, c))
    final, ys = jax.lax.scan(step, jnp.zeros((n_rows, d, n), f32), rows)
    y = jnp.moveaxis(ys, 0, 1) + layer["ssm_d"] * x.astype(f32)
    return _proj(y * jax.nn.silu(z.astype(f32)), layer["ssm_out"], s), y, final


def _gmu(u, layer, s, memory):
    gate = _sum32("...e,ed->...d", u, _c(layer["gmu_in"], s))
    return _proj(memory * jax.nn.silu(gate), layer["gmu_out"], s)


def _attention(u, layer, s, init, window: int, kv=None):
    """Differential attention of a layer whose ``lambda_init`` is ``init``
    over its own K/V, or (``kv``: a cross layer) over the K/V another layer
    produced; ``window`` > 0: the query's own position and the ``window -
    1`` before it. Returns the output and the K/V it attended ([N, S, 20,
    64] each)."""
    n_rows, t = u.shape[:2]
    h, hk, hd = s["heads"], s["kv_heads"], s["head_dim"]
    q = _proj(u, layer["wq"], s, layer["bq"]).reshape(n_rows, t, hk // 2, h // hk, 2, hd)  # [.., K/V pair, query pair of it, 1|2, 64]
    if kv is None:
        kv = tuple(_proj(u, layer[w], s, layer[b]).reshape(n_rows, t, hk, hd) for w, b in (("wk", "bk"), ("wv", "bv")))
    k, v = kv
    kp, vp = k.reshape(n_rows, t, hk // 2, 2, hd), v.reshape(n_rows, t, hk // 2, 2 * hd)
    pos = jnp.arange(t)
    seen = pos[None, :] <= pos[:, None]
    if window:
        seen = seen & (pos[None, :] > pos[:, None] - window)
    lam = jnp.exp(jnp.sum(layer["lambda_q1"] * layer["lambda_k1"])) - jnp.exp(jnp.sum(layer["lambda_q2"] * layer["lambda_k2"])) + init
    if s.get("no_lambda"):  # the control: plain attention, the second softmax never subtracted
        lam = 0.0

    def one_pair(args):
        qj, kj, vj = args  # [N, S, 2 query pairs, 2, 64], [N, S, 2, 64], [N, S, 128]
        scores = _sum32("nqghd,nkhd->nghqk", qj, kj) / math.sqrt(hd)
        probs = _c(jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1), s)
        return _c(_sum32("nghqk,nkv->nqghv", probs, vj), s)  # [N, S, 2, 2, 128]: a1 and a2 of each query pair

    ctx = jax.lax.map(one_pair, (jnp.moveaxis(q, 2, 0), jnp.moveaxis(kp, 2, 0), jnp.moveaxis(vp, 2, 0)))
    ctx = jnp.moveaxis(ctx, 0, 2).astype(jnp.float32)  # [N, S, 10, 2, 2, 128]
    diff = ctx[..., 0, :] - lam * ctx[..., 1, :]
    normed = diff / jnp.sqrt(jnp.mean(diff * diff, axis=-1, keepdims=True) + s["eps"]) * layer["subln_g"].astype(jnp.float32)
    o = _c((1.0 - init) * normed, s).reshape(n_rows, t, h * hd)
    return _proj(o, layer["wo"].reshape(h * hd, -1), s, layer["bo"]), (k, v)


def kind_of(layer: Dict) -> str:
    """A layer's kind by the weights it holds (a window layer and the K/V
    layer hold the same: ``attention``)."""
    return "mamba" if "ssm_in" in layer else "gmu" if "gmu_in" in layer else "attention" if "wk" in layer else "cross"


def block(x, layer, s: Dict, init, window: int, lengths, carried: Dict):
    """One layer: ``u = x + Op(LN1(x))``, ``y = u + SwiGLU(LN2(u))``, the
    operator by the weights the layer holds. ``init``: its ``lambda_init``;
    ``window``: an attention layer's (0: causal over everything);
    ``carried``: what it reads of earlier layers (``memory``: a Mamba
    layer's ``m``; ``kv`` and ``kv_weights``: the K/V layer's). Returns the
    output and what the layer leaves for later ones and for :func:`probe`:
    a Mamba layer's ``memory`` and final ``state``, an attention layer's
    ``kv``."""
    kind, u, left = kind_of(layer), _ln(x, layer["ln1_g"], layer["ln1_b"], s), {}
    if kind == "mamba":
        out, left["memory"], left["state"] = _mamba(u, layer, s, lengths)
    elif kind == "gmu":
        out = _gmu(u, layer, s, carried["memory"])
    elif kind == "cross":
        kv, weights = carried["kv"], carried["kv_weights"]
        if s.get("cross_own_kv"):  # the control: K/V of the layer's OWN input, through the producer's projections
            kv = tuple(_proj(u, weights[w], s, weights[b]).reshape(kv[0].shape) for w, b in (("wk", "bk"), ("wv", "bv")))
        out, _ = _attention(u, layer, s, init, 0, kv)
    else:
        out, left["kv"] = _attention(u, layer, s, init, window)
    x = x + out
    v = _ln(x, layer["ln2_g"], layer["ln2_b"], s)
    gate, up = _sum32("...e,ef->...f", v, _c(layer["w1"], s)), _sum32("...e,ef->...f", v, _c(layer["w3"], s))
    return x + _proj(jax.nn.silu(gate) * up, layer["w2"], s), left


@functools.lru_cache(maxsize=None)
def _programs(frozen_sizes, dtype_name: str = "float32", control: str = ""):
    """The jitted pieces: embedding, one layer (of whichever kind its
    weights say: five programs), head. ``control``: one of :data:`CONTROLS`
    upon the bfloat16 arithmetic."""
    s = dict(frozen_sizes, dtype=jnp.dtype(dtype_name), **{c: control == c for c in CONTROLS if c != "bfloat16_state"},
             bf16_state=control == "bfloat16_state")
    # float32 is float32: on a TPU a float32 matmul at the default precision is one bfloat16 pass
    highest = jax.default_matmul_precision("highest" if dtype_name == "float32" else "default")

    def embed(table, tokens):
        return _c(table[tokens], s)

    @functools.partial(jax.jit, static_argnums=(3,))
    def layer_fn(x, layer, init, window, lengths, carried):
        with highest:
            return block(x, layer, s, init, window, lengths, carried)

    def head(x, g, b, table, at):
        with highest:
            x = _ln(jnp.take_along_axis(x, at[:, :, None], axis=1), g, b, s)
            return _sum32("nte,ve->ntv", x, _c(table, s))

    return jax.jit(embed), layer_fn, jax.jit(head)


def hidden(params: Dict, tokens, config: Dict, dtype: str = "float32", control: str = "", lengths=None, seen=None):
    """[N, S] tokens -> the last layer's output [N, S, E], layer by layer,
    every layer over every row. ``seen``: a list that takes every Mamba
    layer's state after each row's first ``lengths`` positions."""
    s = sizes(config)
    embed, layer_fn, _ = _programs(tuple(sorted(s.items())), dtype, control)
    x = embed(params["tok_embed"], tokens)
    lengths = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32) if lengths is None else lengths
    # (the control: the GMUs read the Mamba layer before the memory layer)
    memory_layer = s["memory_layer"] - 2 if control == "memory_from_other" else s["memory_layer"]
    carried: Dict = {}
    for index, layer in enumerate(params["layers"]):
        kind = s["kinds"][index]
        window = s["window"] if kind == "window" and control != "window_as_full" else 0
        needs = {"gmu": ("memory",), "cross": ("kv", "kv_weights")}.get(kind, ())
        x, left = layer_fn(x, layer, jnp.float32(lambda_init(index)), window, lengths, {k: carried[k] for k in needs})
        if index == memory_layer:
            carried["memory"] = left["memory"]
        if index == s["kv_layer"]:
            carried.update(kv=left["kv"], kv_weights={name: layer[name] for name in ("wk", "bk", "wv", "bv")})
        if seen is not None and "state" in left:
            seen.append(left["state"])
    return x


def head_at(params: Dict, x, at, config: Dict, dtype: str = "float32", control: str = ""):
    """The logits [N, T, V] at positions ``at`` [N, T] of the last layer's
    output ``x``."""
    head = _programs(tuple(sorted(sizes(config).items())), dtype, control)[2]
    return head(x, params["final_ln_g"], params["final_ln_b"], params["tok_embed"], at)


def logits_at(params: Dict, tokens, at, config: Dict, dtype: str = "float32", control: str = ""):
    """[N, S] tokens, [N, T] positions -> the logits [N, T, V] that predict
    the token after each position."""
    return head_at(params, hidden(params, tokens, config, dtype, control), at, config, dtype, control)


def _blocks(tokens, at, rows: int):
    for lo in range(0, len(tokens), rows):
        yield lo, [np.resize(a[lo : lo + rows], (rows,) + a.shape[1:]) for a in (tokens, at)]


def judge(params: Dict, config: Dict, tokens, at, arms: Dict[str, np.ndarray], valid, rows: int = ROWS) -> Dict[str, Dict]:
    """Each arm's tokens (``arms[name]`` [N, T]: the tokens chosen after
    positions ``at`` of ``tokens``) as the float32 reference sees them
    (``lfm2.judge``'s contract): per arm ``gap``, how far the token's logit
    lies below the reference's best, and ``margin``, how far the
    reference's second lies below its best, flat over the ``valid`` tokens.
    The reference's hidden rows are computed once for all arms, ``rows``
    requests a call, its logits ``HEAD_ROWS`` positions a call."""
    out = {name: {"gap": [], "margin": []} for name in arms}
    for lo, (toks, ats) in _blocks(tokens, at, rows):
        x = hidden(params, jnp.asarray(toks), config)
        keep = valid[lo : lo + rows]
        parts = {name: ([], []) for name in arms}
        for t0 in range(0, ats.shape[1], HEAD_ROWS):
            logits = head_at(params, x, jnp.asarray(ats[:, t0 : t0 + HEAD_ROWS]), config)
            if not bool(jnp.all(jnp.isfinite(logits))):
                raise FloatingPointError("the reference produced non-finite logits")
            for name, chosen in arms.items():
                picked = np.resize(chosen[lo : lo + rows], (rows,) + chosen.shape[1:])[:, t0 : t0 + HEAD_ROWS]
                gap, margin = _gaps(logits, jnp.asarray(picked))
                parts[name][0].append(np.asarray(gap))
                parts[name][1].append(np.asarray(margin))
        for name, (gaps, margins) in parts.items():
            out[name]["gap"].append(np.concatenate(gaps, axis=1)[: len(keep)][keep])
            out[name]["margin"].append(np.concatenate(margins, axis=1)[: len(keep)][keep])
    return {name: {k: np.concatenate(v) for k, v in arm.items()} for name, arm in out.items()}


def choices(params: Dict, config: Dict, tokens, at, arithmetic: str, rows: int = ROWS) -> np.ndarray:
    """[N, T] greedy tokens after each position ``at`` of ``tokens`` of the
    equations computed otherwise, put in the program's place (after the
    same prefixes) and judged as served tokens are:

    * ``bfloat16`` — the arithmetic the configuration STATES (module
      docstring). Not a control: the yardstick (``lfm2.gap_ratio``);
    * ``bfloat16_state`` — the recurrent state rounded to bfloat16 after
      every position: what a bfloat16 state cache holds;
    * ``no_lambda`` — ``lambda a2`` left out: plain attention under the
      pair norm;
    * ``memory_from_other`` — the GMUs read the scan output of the Mamba
      layer BEFORE the memory layer (14, not 16);
    * ``window_as_full`` — the window layers attend their whole history;
    * ``cross_own_kv`` — a cross layer attends K/V projected from its OWN
      input (through the K/V layer's matrices), not the K/V layer's."""
    if arithmetic != "bfloat16" and arithmetic not in CONTROLS:
        raise ValueError(f"arithmetic {arithmetic!r}: 'bfloat16' or one of {CONTROLS}")
    control = "" if arithmetic == "bfloat16" else arithmetic
    out = []
    for lo, (toks, ats) in _blocks(tokens, at, rows):
        x = hidden(params, jnp.asarray(toks), config, "bfloat16", control)
        picked = [np.asarray(jnp.argmax(head_at(params, x, jnp.asarray(ats[:, t0 : t0 + HEAD_ROWS]), config, "bfloat16", control), -1))
                  for t0 in range(0, ats.shape[1], HEAD_ROWS)]
        out.append(np.concatenate(picked, axis=1)[: len(tokens) - lo])
    return np.concatenate(out)


def probe(params: Dict, config: Dict, tokens, lengths, arithmetic: str = "bfloat16", rows: int = ROWS) -> Dict:
    """What the program's stored state is held to (the driver's
    ``probe_engine``): the equations over each row's first ``lengths``
    positions of ``tokens`` [N, S] in the ``arithmetic`` the configuration
    states (``bfloat16``), in a control's (:func:`choices`) or in
    ``float32``. ``state`` [9 layers, N, D, N'] float32: every Mamba
    layer's state after a row's last position, on the device."""
    if arithmetic not in ("bfloat16", "float32") and arithmetic not in CONTROLS:
        raise ValueError(f"arithmetic {arithmetic!r}: 'bfloat16', 'float32' or one of {CONTROLS}")
    states = []
    for lo, (toks, lens) in _blocks(np.asarray(tokens), np.asarray(lengths, np.int32), rows):
        seen, keep = [], min(rows, len(tokens) - lo)
        hidden(params, jnp.asarray(toks), config, "float32" if arithmetic == "float32" else "bfloat16",
               arithmetic if arithmetic in CONTROLS else "", jnp.asarray(lens), seen)
        states.append(jnp.stack([v[:keep] for v in seen]))
    return {"state": jnp.concatenate(states, axis=1)}


def state_distances(ours, theirs) -> np.ndarray:
    """[layers, N, D]: how far each channel's ``[N']`` state of ``ours``
    lies from ``theirs``' (both [layers, N, D, N']), as a share of
    ``theirs``' norm of that channel."""
    ours, theirs = (jnp.asarray(v, jnp.float32) for v in (ours, theirs))
    return np.asarray(jnp.sqrt(jnp.sum(jnp.square(ours - theirs), axis=-1) / jnp.maximum(jnp.sum(jnp.square(theirs), axis=-1), 1e-30)))


def state_error(ours, theirs, share: float = 0.9) -> np.ndarray:
    """[layers]: of the (row, channel) distances (:func:`state_distances`)
    the one that ``share`` of them lie under. By channel and not pooled: a
    few channels of slow decay hold most of a state's norm
    (``reference/nemotron_h.py::state_error``, PR 55's lesson)."""
    off = state_distances(ours, theirs)
    return np.quantile(off.reshape(off.shape[0], -1), share, axis=1)


def engine_config(config: Dict, max_positions: int):
    """The configuration as the program takes it
    (``flexflow_tpu.generation.decoder.DecoderConfig``)."""
    from flexflow_tpu.core.types import DataType
    from flexflow_tpu.generation.decoder import DecoderConfig

    s = sizes(config)
    dtype = {"bfloat16": DataType.BFLOAT16, "float32": DataType.FLOAT}[config.get("serving_dtype", "bfloat16")]
    return DecoderConfig(
        num_layers=s["layers"], hidden_size=s["e"], num_heads=s["heads"], ff_size=s["f"], seq_length=max_positions,
        vocab_size=s["vocab"], causal=True, dtype=dtype, norm="layernorm", norm_eps=s["eps"], block="sequential",
        positions="rotary", rope_parameters={"attention": {"positions": "none"}, "window": {"positions": "none"}},
        num_kv_heads=s["kv_heads"], head_dim=s["head_dim"], layer_types=s["kinds"], window=s["window"], ffn="swiglu",
        tied_head=True, differential=True, attention_bias=True, kv_source=s["kv_layer"], memory_source=s["memory_layer"],
        mamba_expand=s["inner"] // s["e"], mamba_dt_rank=s["dt_rank"], ssm_state_size=s["state"], ssm_conv_kernel=s["kernel"],
        ssm_chunk=int(config["assumed"]["scan_chunk"]["value"]), ssm_dt_range=(s["dt_min"], s["dt_max"], s["dt_floor"]),
    )
