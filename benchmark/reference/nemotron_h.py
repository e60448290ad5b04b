"""NVIDIA-Nemotron-3-Super-120B-A12B (``model_type: nemotron_h``) as its
configuration file states it, in plain float32: ONE chip's share of the
published model.

Equations (``RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g``, eps 1e-5; no
bias but the convolution's). ``x0 = E[tokens]``; every published layer is
ONE residual branch under ONE norm, ``x <- x + Mixer_l(RMSNorm(x; g_l))``,
the mixer by the layer's letter in ``hybrid_override_pattern``; after the
last a final RMSNorm, then ``logits = x W_head`` (untied).

* ``M``, Mamba-2 (``u`` the normed input; H = 128 heads of P = 64, so
  ``d_inner`` = 8,192; G = 8 groups (``n_groups``) of N = 128
  (``ssm_state_size``); kernel 4): ``[z (8,192), xBC (10,240), dt (128)] =
  u W_in``; ``xBC <- silu(conv1d_causal(xBC; w[10,240, 4]) + b)``,
  depthwise, zeros before the sequence; split into ``x`` [H, P], ``B`` and
  ``C`` [G, N] (the 16 heads of a group share its B and C); ``dt <-
  softplus(dt + dt_bias)`` [H], ``A = -exp(A_log)`` [H]. The recurrence, in
  float32, head ``h`` of group ``g``, position by position from ``S = 0``::

      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T          (S is [P, N])
      y_t = S_t C_t + D x_t

  then the gated group norm: ``y <- y * silu(z)``, RMS-normalised over
  each of the G groups of 1,024 values apart, times a weight [8,192];
  ``out = y W_out``.
* ``*``, attention: 32 query heads over 2 K/V heads of 128 (query head
  ``i`` reads K/V head ``i // 16``), causal softmax in float32 over ``j <=
  t``, scores over sqrt(128), NO positional signal (``assumed.positions``
  in the file), ``out = concat_i(o_i) W_O``.
* ``E``, LatentMoE: ``s = sigmoid(u W_g)`` over ALL 512 published experts
  in float32, ``I = top_22(s + b)``, ``g_i = 5 s_i / (sum_{j in I} s_j +
  1e-6)``; ``v = u W_down`` (4,096 -> 1,024); each routed expert ungated,
  ``e_i(v) = relu(v W1_i)^2 W2_i`` (1,024 -> 2,688 -> 1,024); ``r =
  (sum_{i in I, i held} g_i e_i(v)) W_up`` (1,024 -> 4,096); one shared
  expert on ``u`` itself, ungated relu^2 of width 5,376; ``out = r +
  shared(u)``. The sum runs over the experts THIS chip holds
  (``expert_share`` in the file: 128 of the 512, those of chip 0); what
  the other chips' experts would add is left out, as the program leaves
  it out. ``W_up`` is linear and bias-free, so the four shares' routed
  parts add up before it or behind it.

:func:`logits_at` computes that. Beside it, for ``gap_ratio``, the same
equations "in the arithmetic the configuration states" (``dtype=
"bfloat16"``): bfloat16 weights and activations with float32 accumulation;
norms, softmax, router, ``dt``, ``exp(dt A)``, the recurrence, the stored
state ``S``, the skip, the gate and the group norm in float32. The
controls are that arithmetic with one thing changed (:data:`CONTROLS`).

Departures from the published model, each the configuration's (``assumed``
/ ``reduced`` / ``not_served`` in its file): the first ``num_hidden_layers``
letters of the 88-letter pattern; 128 of each ``E`` layer's 512 routed
experts; a quarter of the vocabulary; the gate's 1e-6; router, softmax and
the state in float32; random weights from ``--seed``; no multi-token-
prediction module.

Everything here is ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` for float32: no cache, no
kernel, no chunks (the recurrence is a plain scan over positions), no
batching beyond ``ROWS`` requests a call, one layer at a time, within an
expert layer one expert at a time. The WEIGHTS are the benchmark's: made
here from the seed (:func:`init_params`), bfloat16 (router, selection bias,
``dt_bias``, ``A_log`` and ``D`` float32), in the pytree the program takes
as a checkpoint: ``tok_embed`` [V, E], ``lm_head`` [E, V], ``final_ln_g``,
``layers``: a list of ``ln1_g`` and, by letter, ``ssm_in`` [E, 18,560],
``ssm_conv_w`` [10,240, 4], ``ssm_conv_b``, ``ssm_dt_bias`` / ``ssm_a_log``
/ ``ssm_d`` [128], ``ssm_norm_g`` [8,192], ``ssm_out`` [8,192, E]; ``wq``
[E, 32, 128], ``wk`` / ``wv`` [E, 2, 128], ``wo`` [32, 128, E]; ``router``
[E, 512], ``router_bias`` [512], ``lat_down`` [E, 1,024], ``lat_up``
[1,024, E], ``ew1`` [held, 1,024, 2,688], ``ew2`` [held, 2,688, 1,024],
``sw1`` [E, 5,376], ``sw2`` [5,376, E]. The reference reads nothing the
program has made.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .decoder import layout, reading  # noqa: F401  (the decoder cells' layout of a sample and reading of a judged one)
from .lfm2 import _c, _gaps, _mm, _rms, _uniform, cast_params, gap_ratio, worst_request_ratio  # noqa: F401
from .mellum2 import worst_request_excess  # noqa: F401  (the request-by-request comparison, as code-gen has it)

ROWS = 2  # requests per call
# what a control changes, beside the stated arithmetic it is computed in
CONTROLS = ("bfloat16_state", "bfloat16_router", "state_skipped")
LETTERS = {"M": "ssm", "*": "attention", "E": "ffn"}


def sizes(config: Dict) -> Dict:
    """The numbers the equations need, from the configuration file's keys
    (the source's own names). ``experts`` is what the router scores: the
    PUBLISHED count; ``held`` the indices of those whose weights exist here
    (``n_routed_experts`` of them, the share of ``expert_share.chip``);
    ``pattern`` the first ``num_hidden_layers`` letters of the published
    one."""
    c = config
    if c["n_group"] != 1 or c["topk_group"] != 1 or not c["norm_topk_prob"] or c["mlp_hidden_act"] != "relu2":
        raise ValueError("a sigmoid router without a group limit, renormalised gates and relu^2 experts are what is written down")
    n_held, share = int(c["n_routed_experts"]), c["expert_share"]
    experts = int(c["published"]["n_routed_experts"])
    first = int(share["chip"]) * n_held
    if int(share["chips"]) * n_held != experts or not 0 <= first < experts:
        raise ValueError(f"{share['chips']} chips of {n_held} experts are not the published {experts}")
    pattern = c["hybrid_override_pattern"][: int(c["num_hidden_layers"])]
    if len(pattern) != int(c["num_hidden_layers"]) or set(pattern) - set(LETTERS):
        raise ValueError(f"pattern {pattern!r} for {c['num_hidden_layers']} layers")
    heads, p = int(c["mamba_num_heads"]), int(c["mamba_head_dim"])
    if heads * p != int(c["expand"]) * int(c["hidden_size"]):
        raise ValueError("mamba_num_heads x mamba_head_dim is not expand x hidden_size")
    return {
        "pattern": pattern, "layers": len(pattern), "e": int(c["hidden_size"]), "heads": int(c["num_attention_heads"]),
        "kv_heads": int(c["num_key_value_heads"]), "head_dim": int(c["head_dim"]),
        "m_heads": heads, "m_dim": p, "groups": int(c["n_groups"]), "state": int(c["ssm_state_size"]),
        "kernel": int(c["conv_kernel"]), "chunk": int(c["chunk_size"]),
        "fe": int(c["moe_intermediate_size"]), "latent": int(c["moe_latent_size"]),
        "shared_f": int(c["moe_shared_expert_intermediate_size"]) * int(c["n_shared_experts"]),
        "experts": experts, "held": tuple(range(first, first + n_held)), "top_k": int(c["num_experts_per_tok"]),
        "vocab": int(c["vocab_size"]), "eps": float(c["norm_eps"]), "scaling": float(c["routed_scaling_factor"]),
        "dt_min": float(c["time_step_min"]), "dt_max": float(c["time_step_max"]), "dt_floor": float(c["time_step_floor"]),
    }


def parameter_counts(config: Dict) -> Dict[str, float]:
    """Parameters by the file's own sizes: a layer of each letter (``E``:
    outside its routed experts, and one routed expert), the published
    model ``whole`` and ``active`` a token (88 layers, 512 experts, top-22,
    the whole vocabulary), and what this chip ``held``s (the cut)."""
    s, pub = sizes(config), config["published"]
    e, di = s["e"], s["m_heads"] * s["m_dim"]
    cw = di + 2 * s["groups"] * s["state"]
    m = e + e * (di + cw + s["m_heads"]) + cw * s["kernel"] + cw + 3 * s["m_heads"] + di + di * e
    a = e + e * (s["heads"] + 2 * s["kv_heads"]) * s["head_dim"] + s["heads"] * s["head_dim"] * e
    expert = 2 * s["latent"] * s["fe"]
    outside = e + e * s["experts"] + s["experts"] + 2 * e * s["latent"] + 2 * e * s["shared_f"]
    full = config["hybrid_override_pattern"]
    count = lambda pattern, n_experts, vocab: (  # noqa: E731
        pattern.count("M") * m + pattern.count("*") * a + pattern.count("E") * (outside + n_experts * expert) + 2 * vocab * e + e)
    return {
        "ssm_layer": m, "attention_layer": a, "expert_layer_outside": outside, "routed_expert": expert,
        "whole": count(full, s["experts"], int(pub["vocab_size"])),
        "active": count(full, s["top_k"], int(pub["vocab_size"])),
        "held": count(s["pattern"], len(s["held"]), s["vocab"]),
    }


# ------------------------------------------------------------------ weights
@functools.partial(jax.jit, static_argnums=(1, 2))
def _init_layer(key, letter: str, dims):
    e, h, hk, d, mh, mp, g, n, kernel, fe, lat, fs, experts, held, dt_min, dt_max, dt_floor = dims
    keys = iter(jax.random.split(key, 10))
    ones = lambda k: jnp.ones((k,), jnp.bfloat16)  # noqa: E731
    layer = {"ln1_g": ones(e)}
    if letter == "M":
        di = mh * mp
        cw = di + 2 * g * n
        # the published modelling code's initialisation of the state-space parameters (assumed.ssm_init):
        # dt log-uniform in [time_step_min, time_step_max], floored, through the inverse softplus
        step = jnp.exp(jax.random.uniform(next(keys), (mh,), jnp.float32) * (math.log(dt_max) - math.log(dt_min)) + math.log(dt_min))
        step = jnp.maximum(step, dt_floor)
        layer.update(
            ssm_in=_uniform(next(keys), (e, di + cw + mh), e, di + cw + mh),
            ssm_conv_w=_uniform(next(keys), (cw, kernel), kernel, 1), ssm_conv_b=jnp.zeros((cw,), jnp.bfloat16),
            ssm_dt_bias=step + jnp.log(-jnp.expm1(-step)), ssm_a_log=jnp.log(jnp.arange(1, mh + 1, dtype=jnp.float32)),
            ssm_d=jnp.ones((mh,), jnp.float32), ssm_norm_g=ones(di), ssm_out=_uniform(next(keys), (di, e), di, e),
        )
    elif letter == "*":
        layer.update(
            wq=_uniform(next(keys), (e, h, d), e, h * d), wk=_uniform(next(keys), (e, hk, d), e, hk * d),
            wv=_uniform(next(keys), (e, hk, d), e, hk * d), wo=_uniform(next(keys), (h, d, e), h * d, e),
        )
    else:
        layer.update(
            router=_uniform(next(keys), (e, experts), e, experts, jnp.float32),
            router_bias=0.02 * jax.random.normal(next(keys), (experts,), jnp.float32),
            lat_down=_uniform(next(keys), (e, lat), e, lat), lat_up=_uniform(next(keys), (lat, e), lat, e),
            ew1=_uniform(next(keys), (held, lat, fe), lat, fe), ew2=_uniform(next(keys), (held, fe, lat), fe, lat),
            sw1=_uniform(next(keys), (e, fs), e, fs), sw2=_uniform(next(keys), (fs, e), fs, e),
        )
    return layer


def init_params(seed: int, config: Dict) -> Dict:
    """The configuration's weights from the seed, on the device, one jitted
    call per layer: Glorot-uniform matrices, unit norms, a 0.02-normal
    selection bias, the state-space parameters as the published modelling
    code draws them (``A_log = log(1..H)``, ``dt_bias`` the inverse softplus
    of a log-uniform ``dt``, ``D = 1``); of a layer's routed experts the
    held ones alone."""
    s = sizes(config)
    keys = jax.random.split(jax.random.key(seed), s["layers"] + 2)
    dims = (s["e"], s["heads"], s["kv_heads"], s["head_dim"], s["m_heads"], s["m_dim"], s["groups"], s["state"], s["kernel"],
            s["fe"], s["latent"], s["shared_f"], s["experts"], len(s["held"]), s["dt_min"], s["dt_max"], s["dt_floor"])
    v, e = s["vocab"], s["e"]
    return {
        "tok_embed": jax.jit(lambda k: _uniform(k, (v, e), v, e))(keys[0]),
        "lm_head": jax.jit(lambda k: _uniform(k, (e, v), e, v))(keys[1]),
        "final_ln_g": jnp.ones((e,), jnp.bfloat16),
        "layers": [_init_layer(keys[2 + l], s["pattern"][l], dims) for l in range(s["layers"])],
    }


# ---------------------------------------------------------------- equations
def _mamba(u, layer, s, frozen_from):
    """The ``M`` mixer of ``u`` [N, S, E], and the state [N, H, P, N]
    after the last position. ``frozen_from`` [N]: the position from which
    a row's state is NOT updated any more (the control ``state_skipped``;
    a row's own length: the state after it; S: never)."""
    n_rows, t = u.shape[:2]
    h, p, g, n = s["m_heads"], s["m_dim"], s["groups"], s["state"]
    di, f32 = h * p, jnp.float32
    proj = _mm(u, layer["ssm_in"], s)
    z, xbc, dt = proj[..., :di], proj[..., di : di + di + 2 * g * n], proj[..., di + di + 2 * g * n :]
    pad = jnp.concatenate([jnp.zeros((n_rows, s["kernel"] - 1, xbc.shape[-1]), xbc.dtype), xbc], axis=1).astype(f32)
    w = layer["ssm_conv_w"].astype(f32)
    mixed = sum(w[:, j] * pad[:, j : j + t] for j in range(s["kernel"])) + layer["ssm_conv_b"].astype(f32)
    xbc = _c(jax.nn.silu(mixed), s)
    x = xbc[..., :di].reshape(n_rows, t, h, p)
    b = xbc[..., di : di + g * n].reshape(n_rows, t, g, n)
    c = xbc[..., di + g * n :].reshape(n_rows, t, g, n)
    dt = jax.nn.softplus(dt.astype(f32) + layer["ssm_dt_bias"])
    dt = jnp.where(jnp.arange(t)[None, :, None] < frozen_from[:, None, None], dt, 0.0)  # (a skipped update: S_t = S_{t-1})
    a = -jnp.exp(layer["ssm_a_log"])
    coarse = s.get("bf16_state")

    def step(state, row):
        xt, dtt, bt, ct = row  # [N, H, P], [N, H], [N, G, N] x 2
        bh, ch = jnp.repeat(bt, h // g, axis=1), jnp.repeat(ct, h // g, axis=1)
        state = jnp.exp(dtt * a)[..., None, None] * state + (dtt[..., None] * xt)[..., None] * bh[:, :, None, :]
        if coarse:  # the control: the stored state holds bfloat16 (reduce_precision: the TPU compiler elides a
            # float32 -> bfloat16 -> float32 round trip as excess precision, and the control then read exactly 1)
            state = jax.lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)
        return state, jnp.sum(state * ch[:, :, None, :], axis=-1)

    rows = tuple(jnp.moveaxis(v.astype(f32), 1, 0) for v in (x, dt, b, c))
    final, ys = jax.lax.scan(step, jnp.zeros((n_rows, h, p, n), f32), rows)
    y = jnp.moveaxis(ys, 0, 1) + layer["ssm_d"][:, None] * x.astype(f32)
    y = y.reshape(n_rows, t, di) * jax.nn.silu(z.astype(f32))
    y = y.reshape(n_rows, t, g, di // g)
    y = (y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + s["eps"])).reshape(n_rows, t, di) * layer["ssm_norm_g"].astype(f32)
    return _mm(_c(y, s), layer["ssm_out"], s), final


def _sum32(eq: str, a, b):
    """An einsum whose sum is float32 whatever the operands' type: the
    operands widened (exactly) and multiplied at the precision in force,
    which for bfloat16 values under the default precision is the MXU's
    bfloat16 product with float32 accumulation."""
    return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32))


def _attention(u, layer, s):
    """Grouped-query causal attention without any positional signal."""
    n_rows, t = u.shape[:2]
    h, hk, d = s["heads"], s["kv_heads"], s["head_dim"]
    q = _mm(u, layer["wq"].reshape(s["e"], -1), s).reshape(n_rows, t, hk, h // hk, d)
    k = _mm(u, layer["wk"].reshape(s["e"], -1), s).reshape(n_rows, t, hk, d)
    v = _mm(u, layer["wv"].reshape(s["e"], -1), s).reshape(n_rows, t, hk, d)
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    def one_kv_head(args):
        qh, kh, vh = args  # [N, S, group, D], [N, S, D] x 2
        scores = _sum32("nqgd,nkd->ngqk", qh, kh) / math.sqrt(d)
        probs = _c(jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1), s)
        return _c(_sum32("ngqk,nkd->nqgd", probs, vh), s)

    ctx = jax.lax.map(one_kv_head, (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(n_rows, t, h * d)
    return _mm(ctx, layer["wo"].reshape(-1, s["e"]), s)


def routing(u, layer, s):
    """Gates [..., 512] over ALL the published experts (float32 whatever
    the equations' type; the control ``bfloat16_router`` multiplies
    bfloat16 operands), zero off a token's top k: the bias moves the
    choice and never the gate."""
    if s.get("bf16_router"):
        product = _sum32("...e,en->...n", u.astype(jnp.bfloat16), layer["router"].astype(jnp.bfloat16))
    else:
        product = jnp.matmul(u.astype(jnp.float32), layer["router"], precision="highest")
    score = jax.nn.sigmoid(product)
    _, chosen = jax.lax.top_k(score + layer["router_bias"], s["top_k"])
    picked = jnp.take_along_axis(score, chosen, axis=-1)
    gate = picked / (picked.sum(-1, keepdims=True) + 1e-6) * s["scaling"]
    return jnp.sum(jax.nn.one_hot(chosen, s["experts"], dtype=jnp.float32) * gate[..., None], axis=-2)


def _relu2(v, w1, w2, s):
    return _mm(_c(jnp.square(jax.nn.relu(_mm(v, w1, s).astype(jnp.float32))), s), w2, s)


def _experts(u, layer, s, held=None, shared: bool = True, lengths=None):
    """The shared expert plus the routed sum, through the latent, over the
    experts ``held`` (indices into the published experts, in the order the
    stacks hold them; default: the configuration's share); and the picks
    [N, held]: for how many of a row's first ``lengths`` positions (None:
    all) the router chose each held expert."""
    held = s["held"] if held is None else held
    gates = routing(u, layer, s)[..., jnp.asarray(held, jnp.int32)]
    counted = True if lengths is None else (jnp.arange(u.shape[1])[None, :] < lengths[:, None])[..., None]
    picks = jnp.sum((gates > 0) & counted, axis=1, dtype=jnp.int32)
    v = _mm(u, layer["lat_down"], s)

    def one(acc, expert):
        w1, w2, g = expert
        return acc + g[..., None] * _relu2(v, w1, w2, s).astype(jnp.float32), None

    total, _ = jax.lax.scan(one, jnp.zeros(v.shape, jnp.float32), (layer["ew1"], layer["ew2"], jnp.moveaxis(gates, -1, 0)))
    out = _mm(_c(total, s), layer["lat_up"], s)
    if not shared:
        return out, picks
    return _c(out.astype(jnp.float32) + _relu2(u, layer["sw1"], layer["sw2"], s).astype(jnp.float32), s), picks


def block(x, layer, s: Dict, frozen_from, lengths):
    """One layer: ``x + Mixer(RMSNorm(x))``, the mixer by what the layer
    holds; and what :func:`probe` reads of it: an ``M`` layer's state
    after the last position, an ``E`` layer's picks by held expert."""
    u = _rms(x, layer["ln1_g"], s)
    if "wq" in layer:
        return x + _attention(u, layer, s), None
    out, seen = _mamba(u, layer, s, frozen_from) if "ssm_in" in layer else _experts(u, layer, s, lengths=lengths)
    return x + out, seen


@functools.lru_cache(maxsize=None)
def _programs(frozen_sizes, dtype_name: str = "float32", control: str = ""):
    """The jitted pieces: embedding, one layer (of whichever letter its
    weights say), head. ``control``: one of :data:`CONTROLS` upon the
    bfloat16 arithmetic."""
    s = dict(frozen_sizes, dtype=jnp.dtype(dtype_name), bf16_state=control == "bfloat16_state",
             bf16_router=control == "bfloat16_router")
    # float32 is float32: on a TPU a float32 matmul at the default precision is one bfloat16 pass
    highest = jax.default_matmul_precision("highest" if dtype_name == "float32" else "default")

    def embed(table, tokens):
        return _c(table[tokens], s)

    def layer_fn(x, layer, frozen_from, lengths):
        with highest:
            return block(x, layer, s, frozen_from, lengths)

    def head(x, g, w, at):
        with highest:
            x = _rms(jnp.take_along_axis(x, at[:, :, None], axis=1), g, s)
            return _sum32("nte,ev->ntv", x, _c(w, s))

    return jax.jit(embed), jax.jit(layer_fn), jax.jit(head)


def hidden(params: Dict, tokens, config: Dict, dtype: str = "float32", control: str = "", frozen_from=None, seen=None, lengths=None):
    """[N, S] tokens -> the last layer's output [N, S, E], layer by layer.
    ``seen``: a list that takes what :func:`block` reads of every layer
    over each row's first ``lengths`` positions."""
    embed, layer_fn, _ = _programs(tuple(sorted(sizes(config).items())), dtype, control)
    x = embed(params["tok_embed"], tokens)
    whole = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    frozen_from, lengths = (whole if v is None else v for v in (frozen_from, lengths))
    for layer in params["layers"]:
        x, read = layer_fn(x, layer, frozen_from, lengths)
        if seen is not None:
            seen.append(read)
    return x


def logits_at(params: Dict, tokens, at, config: Dict, dtype: str = "float32", control: str = "", frozen_from=None):
    """[N, S] tokens, [N, T] positions -> the logits [N, T, V] that predict
    the token after each position."""
    head = _programs(tuple(sorted(sizes(config).items())), dtype, control)[2]
    return head(hidden(params, tokens, config, dtype, control, frozen_from), params["final_ln_g"], params["lm_head"], at)


def judge(params: Dict, config: Dict, tokens, at, arms: Dict[str, np.ndarray], valid, rows: int = ROWS) -> Dict[str, Dict]:
    """Each arm's tokens (``arms[name]`` [N, T]: the tokens chosen after
    positions ``at`` of ``tokens``) as the float32 reference sees them
    (``lfm2.judge``'s contract): per arm ``gap``, how far the token's logit
    lies below the reference's best, and ``margin``, how far the
    reference's second lies below its best, flat over the ``valid`` tokens.
    The reference's logits are computed once for all arms, in blocks of
    ``rows`` requests."""
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
    """[N, T] greedy tokens after each position ``at`` of ``tokens`` of the
    equations computed otherwise, put in the program's place (after the
    same prefixes) and judged as served tokens are:

    * ``bfloat16`` — the arithmetic the configuration STATES (module
      docstring). Not a control: the yardstick (``lfm2.gap_ratio``);
    * ``bfloat16_state`` — the stated arithmetic with the recurrent state
      rounded to bfloat16 after every position: what a bfloat16 state
      cache holds;
    * ``bfloat16_router`` — the stated arithmetic with the router's
      product over bfloat16 operands;
    * ``state_skipped`` — the stated arithmetic with the FIRST request's
      state frozen where its prompt ends: every later update of that one
      sequence skipped (a slot the update call never visits), the other
      requests sound."""
    if arithmetic != "bfloat16" and arithmetic not in CONTROLS:
        raise ValueError(f"arithmetic {arithmetic!r}: 'bfloat16' or one of {CONTROLS}")
    out = []
    for lo in range(0, len(tokens), rows):
        part = [np.resize(a[lo : lo + rows], (rows,) + a.shape[1:]) for a in (tokens, at)]
        frozen = np.full((rows,), part[0].shape[1], np.int32)
        if arithmetic == "state_skipped" and lo == 0:
            frozen[0] = int(part[1][0, 0]) + 1  # (the position of the request's first reply token)
        logits = logits_at(params, jnp.asarray(part[0]), jnp.asarray(part[1]), config, "bfloat16",
                           arithmetic if arithmetic in ("bfloat16_state", "bfloat16_router") else "", jnp.asarray(frozen))
        out.append(np.asarray(jnp.argmax(logits, -1))[: len(tokens) - lo])
    return np.concatenate(out)


def probe(params: Dict, config: Dict, tokens, lengths, arithmetic: str = "bfloat16", frozen_from=None, rows: int = ROWS) -> Dict:
    """What the program's stored state and router counters are held to
    (the driver's ``probe_engine``): the equations over each row's first
    ``lengths`` positions of ``tokens`` [N, S] in the ``arithmetic`` the
    configuration states (``bfloat16``), in a control's (:func:`choices`;
    ``state_skipped`` freezes the first row's state from ``frozen_from``
    on) or in ``float32``. ``state`` [M layers, N, H, P, N] float32: every
    ``M`` layer's state after a row's last position, on the device;
    ``picks`` [E layers, held]: for how many positions, all rows
    together, the router chose each held expert."""
    if arithmetic not in ("bfloat16", "float32") and arithmetic not in CONTROLS:
        raise ValueError(f"arithmetic {arithmetic!r}: 'bfloat16', 'float32' or one of {CONTROLS}")
    states, picks = [], 0
    for lo in range(0, len(tokens), rows):
        part = [np.resize(a[lo : lo + rows], (rows,) + a.shape[1:]) for a in (np.asarray(tokens), np.asarray(lengths, np.int32))]
        frozen = part[1].copy()  # (past its length a row's state stands still: what comes out is the state AT it)
        if arithmetic == "state_skipped" and lo == 0:
            frozen[0] = int(frozen_from)
        seen, keep = [], min(rows, len(tokens) - lo)
        hidden(params, jnp.asarray(part[0]), config, "float32" if arithmetic == "float32" else "bfloat16",
               arithmetic if arithmetic in ("bfloat16_state", "bfloat16_router") else "", jnp.asarray(frozen), seen, jnp.asarray(part[1]))
        states.append(jnp.stack([v[:keep] for v in seen if v is not None and v.ndim == 4]))
        picks = picks + np.stack([np.asarray(v)[:keep].sum(0) for v in seen if v is not None and v.ndim == 2]).astype(np.int64)
    return {"state": jnp.concatenate(states, axis=1), "picks": picks}


def round_router(params: Dict) -> Dict:
    """The weights with every router matrix rounded to the values
    bfloat16 holds (float32 still): to a router that multiplies bfloat16
    operands they are the same weights, to a float32 one they are not."""
    rounded = jax.jit(lambda w: jax.lax.reduce_precision(w, exponent_bits=8, mantissa_bits=7))
    return dict(params, layers=[dict(layer, router=rounded(layer["router"])) if "router" in layer else layer for layer in params["layers"]])


STATE_SHARE = 0.9  # of the (row, head) pairs lie under what :func:`state_error` gives, unless it is asked for another share


def state_distances(ours, theirs) -> np.ndarray:
    """[M layers, N, H]: how far each head's [P, N] state of ``ours`` lies
    from ``theirs``' (both [M layers, N, H, P, N]), as a share of
    ``theirs``' norm of that head."""
    ours, theirs = (jnp.asarray(v, jnp.float32) for v in (ours, theirs))
    return np.asarray(jnp.sqrt(jnp.sum(jnp.square(ours - theirs), axis=(-2, -1)) / jnp.maximum(jnp.sum(jnp.square(theirs), axis=(-2, -1)), 1e-30)))


def state_error(ours, theirs, share: float = STATE_SHARE) -> np.ndarray:
    """[M layers]: how far the states ``ours`` lie from ``theirs`` BY
    (row, head) (:func:`state_distances`): of the N x H distances the one
    that ``share`` of them lie under. By head and not
    pooled (:func:`state_error_pooled`): a few heads of slow decay hold
    half of a state's norm, and ONE bfloat16 rounding of ONE head's ``dt``
    at a row's last positions that fell the other way on the two sides
    moves the pooled number a hundredfold (6.0e-4 on seed 4289300017 where
    thirteen runs read 6.7e-7 to 7.1e-5), and its own pair alone. A state
    stored coarser moves EVERY pair, where a sound program leaves half of
    them bit-equal: that is the MEDIAN's to catch. A slot the update never
    visits moves every head of its row far, an eighth of the pairs of a
    probe of 8 rows, and the median sees none of it: that is the 0.9
    share's. And a sound program's own rounding at one of a row's last
    positions, fallen the other way on the two sides, moves every head of
    that ONE row a little (at most 3.2e-3 in twenty runs), the same eighth:
    the 0.9 share alone, held under what a rounded state reads, called one
    sound run in five not correct (PR 55). Hence both shares, each under a
    limit of its own (``drivers/serve_nemotron.py::verdict``)."""
    off = state_distances(ours, theirs)
    return np.quantile(off.reshape(off.shape[0], -1), share, axis=1)


def state_error_pooled(ours, theirs) -> np.ndarray:
    """[M layers]: the same distance with all rows and heads pooled, as a
    share of ``theirs``' norm (logged beside :func:`state_error`)."""
    ours, theirs = (jnp.asarray(v, jnp.float32).reshape(v.shape[0], -1) for v in (ours, theirs))
    return np.asarray(jnp.sqrt(jnp.sum(jnp.square(ours - theirs), axis=1) / jnp.sum(jnp.square(theirs), axis=1)))


def pick_error(ours, theirs) -> np.ndarray:
    """[E layers]: the picks by held expert that ``ours`` and ``theirs``
    ([E layers, held] counts over the same positions) do not share, as a
    share of ``theirs``: a token whose last pick falls on another expert
    moves two counts by one."""
    ours, theirs = np.asarray(ours, np.int64), np.asarray(theirs, np.int64)
    return np.abs(ours - theirs).sum(axis=1) / np.maximum(theirs.sum(axis=1), 1)


def engine_config(config: Dict, max_positions: int):
    """The configuration as the program takes it
    (``flexflow_tpu.generation.decoder.DecoderConfig``)."""
    from flexflow_tpu.core.types import DataType
    from flexflow_tpu.generation.decoder import DecoderConfig

    s = sizes(config)
    dtype = {"bfloat16": DataType.BFLOAT16, "float32": DataType.FLOAT}[config.get("serving_dtype", "bfloat16")]
    return DecoderConfig(
        num_layers=s["layers"], hidden_size=s["e"], num_heads=s["heads"], ff_size=0,
        seq_length=max_positions, vocab_size=s["vocab"], causal=True, dtype=dtype,
        norm="rmsnorm", norm_eps=s["eps"], block="single", positions="rotary",
        rope_parameters={"attention": {"positions": "none"}}, num_kv_heads=s["kv_heads"], head_dim=s["head_dim"],
        layer_types=tuple(LETTERS[c] for c in s["pattern"]), num_dense_layers=0, num_experts=s["experts"],
        experts_per_token=s["top_k"], moe_ff_size=s["fe"], routed_scaling_factor=s["scaling"], router="sigmoid",
        num_shared_experts=1, shared_ff_size=s["shared_f"], experts_held=s["held"], tied_head=False,
        expert_activation="relu2", moe_latent_size=s["latent"], ssm_heads=s["m_heads"], ssm_head_dim=s["m_dim"],
        ssm_groups=s["groups"], ssm_state_size=s["state"], ssm_conv_kernel=s["kernel"], ssm_chunk=s["chunk"],
        ssm_dt_range=(s["dt_min"], s["dt_max"], s["dt_floor"]),
    )


def expert_layer(u, layer, config: Dict, held: Sequence[int], shared: bool = True):
    """One ``E`` layer's mixer of its normed input ``u`` [N, S, E] in
    float32 over the routed experts ``held`` (``layer``'s stacks hold them
    in that order), with or without the shared expert: what the shares of
    a layer are added up from (tests/test_nemotron.py)."""
    s = dict(sizes(config), dtype=jnp.dtype("float32"))
    with jax.default_matmul_precision("highest"):
        return _experts(u.astype(jnp.float32), layer, s, held=tuple(held), shared=shared)[0]


def mamba_layer(u, layer, config: Dict, frozen_from: Optional[jax.Array] = None):
    """One ``M`` layer's mixer of ``u`` [N, S, E] in float32."""
    s = dict(sizes(config), dtype=jnp.dtype("float32"))
    frozen = jnp.full((u.shape[0],), u.shape[1], jnp.int32) if frozen_from is None else frozen_from
    with jax.default_matmul_precision("highest"):
        return _mamba(u.astype(jnp.float32), layer, s, frozen)[0]
