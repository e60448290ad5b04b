"""JoyAI-LLM-Flash (48B-A2.7B) as its configuration file states it, in
plain float32: ONE chip's share of the published model.

Equations (``RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g``, eps 1e-6;
no bias anywhere), from the source's ``config.json`` (``model_type:
joyai_llm_flash``), for positions ``t`` of a sequence:

* ``x0 = E[tokens]``; every layer ``h = x + Attn(RMSNorm(x; g1))``,
  ``y = h + FFN_l(RMSNorm(h; g2))``; after the last a final RMSNorm,
  then ``logits = x W_head`` (``tie_word_embeddings: false``).
* Attention (every layer; ``u`` its normed input): ``c_q = RMSNorm(u
  W_DQ)`` (2048 -> 1536); ``q = c_q W_UQ`` -> 32 heads of 192 = ``[q_nope
  (128), q_rope (64)]``; ``[c_kv (512), k_r (64)] = u W_DKV``; ``c =
  RMSNorm(c_kv)``; ``q_rope`` and ``k_r`` (ONE vector, all heads') rotated
  over interleaved pairs ``(2i, 2i+1)``, ``inv_i = theta^(-2i/64)``,
  theta 32,000,000, angle ``t inv_i`` in float32: ``(a, b) -> (a cos - b
  sin, b cos + a sin)``; ``[k_nope_i (128), v_i (128)] = c W_UKV`` per
  head; ``s_i(t, j) = (q_nope_i(t) . k_nope_i(j) + q_rope_i(t) . k_r(j))
  / sqrt(192)``, softmax in float32 over ``j <= t``; ``o_i = sum_j p_i(t,
  j) v_i(j)``; ``out = concat_i(o_i) W_O``.
* ``FFN_0`` a dense SwiGLU of 7168 (``first_k_dense_replace: 1``);
  ``FFN_l``, l >= 1: ``S(v) + sum_{i in I, i held} g_i E_i(v)``, ``S``
  and each ``E_i`` a SwiGLU of 768; ``s = sigmoid(v W_g)`` over ALL the
  published experts in float32, ``I = top_8(s + b)`` (``noaux_tc``;
  ``n_group = topk_group = 1``: no group limit), ``g_i = 2.5 s_i /
  (sum_{j in I} s_j + 1e-6)``. The sum runs over the experts THIS chip
  holds (``expert_share`` in the file: 16 of the 256, those of chip 0);
  what the other chips' experts would add is left out, as the program
  leaves it out: nothing stands in for the absent chips.

:func:`logits_at` computes the EXPANDED form only. Beside it, for
``gap_ratio``, the same equations "in the arithmetic the configuration
states" (``dtype="bfloat16"``): bfloat16 weights and activations with
float32 accumulation, norms, softmax, rotary angles and router in
float32 — in the ABSORBED form, which is what the program's decode steps
(all served tokens but a request's first) compute: ``q~_i = q_nope_i
W_UK_i^T`` rounded to bfloat16, scores ``(q~_i . c(j) + q_rope_i .
k_r(j)) / sqrt(192)`` over the rounded rows, the probabilities rounded to
bfloat16 before they weigh the rows, ``o_i = (sum_j p c(j)) W_UV_i`` with
the attended row rounded first. The controls are that arithmetic with
one thing changed (:data:`CONTROLS`).

Departures from the published model, each the configuration's
(``assumed`` / ``reduced`` / ``not_served`` in its file): the first
``num_hidden_layers`` of the 40 layers; 16 of each layer's 256 routed
experts; the gate's 1e-6; router and softmax in float32; random weights
from ``--seed``; no multi-token-prediction module.

Everything here is ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` for float32: no cache, no
kernel, no batching beyond ``ROWS`` requests a call, one layer at a
time, within attention one head at a time and within an expert layer one
expert at a time. The WEIGHTS are the benchmark's: made here from the
seed (:func:`init_params`), bfloat16 (router float32), in the pytree the
program takes as a checkpoint: ``tok_embed`` [V, E], ``lm_head`` [E, V],
``final_ln_g``, ``layers``: a list of ``ln1_g``, ``w_dq`` [E, 1536],
``q_lora_g`` [1536], ``w_uq`` [1536, 32, 192], ``w_dkv`` [E, 576],
``kv_lora_g`` [512], ``w_ukv`` [512, 32, 256], ``wo`` [32, 128, E],
``ln2_g`` and either ``w1`` / ``w3`` [E, 7168], ``w2`` [7168, E] or
``router`` [E, 256], ``router_bias`` [256], ``ew1`` / ``ew3`` [held, E,
768], ``ew2`` [held, 768, E], ``sw1`` / ``sw3`` [E, 768], ``sw2`` [768,
E]. The reference reads nothing the program has made.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .decoder import layout, reading  # noqa: F401  (the decoder cells' layout of a sample and reading of a judged one)
from .lfm2 import _c, _gaps, _int8, _mm, _rms, _uniform, cast_params, gap_ratio, worst_request_ratio  # noqa: F401
from .mellum2 import worst_request_excess  # noqa: F401  (the request-by-request comparison, as code-gen has it)

ROWS = 2  # requests per call
# what a control changes, beside the stated arithmetic it is computed in
CONTROLS = ("int8", "bfloat16_sums", "absorbed_scale", "no_shared_expert")


def sizes(config: Dict) -> Dict:
    """The numbers the equations need, from the configuration file's
    keys (the source's own names). ``experts`` is what the router
    scores: the PUBLISHED count; ``held`` the indices of those whose
    weights exist here (``n_routed_experts`` of them, the share of
    ``expert_share.chip``)."""
    c = config
    if c["scoring_func"] != "sigmoid" or c["topk_method"] != "noaux_tc" or c["n_group"] != 1 or c["topk_group"] != 1:
        raise ValueError("a sigmoid router with a selection bias and no group limit is what is written down")
    if c.get("rope_scaling") is not None or not c["rope_interleave"]:
        raise ValueError("plain interleaved rotary embedding is what is written down")
    n_held, share = int(c["n_routed_experts"]), c["expert_share"]
    experts = int(c["published"]["n_routed_experts"])
    first = int(share["chip"]) * n_held
    if int(share["chips"]) * n_held != experts or not 0 <= first < experts:
        raise ValueError(f"{share['chips']} chips of {n_held} experts are not the published {experts}")
    return {
        "layers": int(c["num_hidden_layers"]), "e": int(c["hidden_size"]), "heads": int(c["num_attention_heads"]),
        "q_rank": int(c["q_lora_rank"]), "kv_rank": int(c["kv_lora_rank"]), "nope": int(c["qk_nope_head_dim"]),
        "rope": int(c["qk_rope_head_dim"]), "v_dim": int(c["v_head_dim"]), "f": int(c["intermediate_size"]),
        "fe": int(c["moe_intermediate_size"]), "experts": experts, "held": tuple(range(first, first + n_held)),
        "shared": int(c["n_shared_experts"]), "top_k": int(c["num_experts_per_tok"]),
        "dense": int(c["first_k_dense_replace"]), "vocab": int(c["vocab_size"]), "eps": float(c["rms_norm_eps"]),
        "theta": float(c["rope_theta"]), "scaling": float(c["routed_scaling_factor"]),
    }


# ------------------------------------------------------------------ weights
@functools.partial(jax.jit, static_argnums=(1, 2))
def _init_layer(key, dense: bool, dims):
    e, h, rq, rkv, dn, dr, dv, f, fe, n, held, shared = dims
    keys = iter(jax.random.split(key, 14))
    ones = lambda n: jnp.ones((n,), jnp.bfloat16)  # noqa: E731
    layer = {
        "ln1_g": ones(e), "ln2_g": ones(e), "q_lora_g": ones(rq), "kv_lora_g": ones(rkv),
        "w_dq": _uniform(next(keys), (e, rq), e, rq), "w_uq": _uniform(next(keys), (rq, h, dn + dr), rq, h * (dn + dr)),
        "w_dkv": _uniform(next(keys), (e, rkv + dr), e, rkv + dr),
        "w_ukv": _uniform(next(keys), (rkv, h, dn + dv), rkv, h * (dn + dv)),
        "wo": _uniform(next(keys), (h, dv, e), h * dv, e),
    }
    if dense:
        layer.update(w1=_uniform(next(keys), (e, f), e, f), w3=_uniform(next(keys), (e, f), e, f),
                     w2=_uniform(next(keys), (f, e), f, e))
        return layer
    layer.update(
        router=_uniform(next(keys), (e, n), e, n, jnp.float32),
        router_bias=0.02 * jax.random.normal(next(keys), (n,), jnp.float32),
        ew1=_uniform(next(keys), (held, e, fe), e, fe), ew3=_uniform(next(keys), (held, e, fe), e, fe),
        ew2=_uniform(next(keys), (held, fe, e), fe, e),
        sw1=_uniform(next(keys), (e, shared * fe), e, shared * fe), sw3=_uniform(next(keys), (e, shared * fe), e, shared * fe),
        sw2=_uniform(next(keys), (shared * fe, e), shared * fe, e),
    )
    return layer


def init_params(seed: int, config: Dict) -> Dict:
    """The configuration's weights from the seed, on the device, one
    jitted call per layer: Glorot-uniform matrices, unit norms, a
    0.02-normal selection bias; of a layer's routed experts the held
    ones alone."""
    s = sizes(config)
    keys = jax.random.split(jax.random.key(seed), s["layers"] + 2)
    dims = (s["e"], s["heads"], s["q_rank"], s["kv_rank"], s["nope"], s["rope"], s["v_dim"], s["f"], s["fe"],
            s["experts"], len(s["held"]), s["shared"])
    v, e = s["vocab"], s["e"]
    return {
        "tok_embed": jax.jit(lambda k: _uniform(k, (v, e), v, e))(keys[0]),
        "lm_head": jax.jit(lambda k: _uniform(k, (e, v), e, v))(keys[1]),
        "final_ln_g": jnp.ones((e,), jnp.bfloat16),
        "layers": [_init_layer(keys[2 + l], l < s["dense"], dims) for l in range(s["layers"])],
    }


# ---------------------------------------------------------------- equations
def _rotary_pairs(x, theta: float):
    """Interleaved-pair rotary embedding over the last axis: x [N, S,
    ..., D], position = index along axis 1, angles in float32."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]  # [S, D/2]
    ang = ang.reshape((1, x.shape[1]) + (1,) * (x.ndim - 3) + (d // 2,))
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang), b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _projections(u, layer, s):
    """q_nope [N, S, H, 128], q_rope [N, S, H, 64] (rotated), c [N, S,
    512] (normed), k_r [N, S, 64] (rotated)."""
    c_q = _rms(_mm(u, layer["w_dq"], s), layer["q_lora_g"], s)
    q = _mm(c_q, layer["w_uq"].reshape(s["q_rank"], -1), s).reshape(u.shape[:2] + (s["heads"], s["nope"] + s["rope"]))
    kv = _mm(u, layer["w_dkv"], s)
    c = _rms(kv[..., : s["kv_rank"]], layer["kv_lora_g"], s)
    return q[..., : s["nope"]], _rotary_pairs(q[..., s["nope"]:], s["theta"]), c, _rotary_pairs(kv[..., s["kv_rank"]:], s["theta"])


def _softmax_rows(scores, s):
    """Causal softmax in float32 over [N, Sq, Sk] scores, handed on in
    the equations' type."""
    t = scores.shape[-1]
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    return _c(jax.nn.softmax(jnp.where(seen, scores, -jnp.inf).astype(jnp.float32), axis=-1), s)


def _attention(u, layer, s):
    """The EXPANDED form: K and V per head out of ``c``."""
    q_nope, q_rope, c, k_r = _projections(u, layer, s)
    scale = float(np.sqrt(s["nope"] + s["rope"]))

    def one_head(args):
        qn, qr, w = args  # [N, S, 128], [N, S, 64], [512, 256]
        kv = _mm(c, w, s)
        k_nope, v = kv[..., : s["nope"]], kv[..., s["nope"]:]
        scores = (jnp.einsum("nqd,nkd->nqk", qn, k_nope) + jnp.einsum("nqd,nkd->nqk", qr, k_r)) / scale
        return jnp.einsum("nqk,nkd->nqd", _softmax_rows(scores, s), v)

    ctx = jax.lax.map(one_head, (jnp.moveaxis(q_nope, 2, 0), jnp.moveaxis(q_rope, 2, 0), jnp.moveaxis(layer["w_ukv"], 1, 0)))
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(u.shape[:2] + (-1,))
    return _mm(ctx, layer["wo"].reshape(-1, layer["wo"].shape[-1]), s)


def _attention_absorbed(u, layer, s):
    """The ABSORBED form, with the roundings where the stated arithmetic
    puts them (module docstring). The control ``absorbed_scale`` divides
    by the root of the ROW's width, 576: what a program that takes the
    scale off the operand it scores computes."""
    q_nope, q_rope, c, k_r = _projections(u, layer, s)
    width = s["kv_rank"] + s["rope"] if s.get("absorbed_scale") else s["nope"] + s["rope"]
    scale = float(np.sqrt(width))

    def one_head(args):
        qn, qr, w = args
        w = _c(w, s)
        q_abs = _c(jnp.einsum("nqd,cd->nqc", qn, w[:, : s["nope"]], preferred_element_type=jnp.float32), s)
        scores = (jnp.einsum("nqc,nkc->nqk", q_abs, c, preferred_element_type=jnp.float32)
                  + jnp.einsum("nqd,nkd->nqk", qr, k_r, preferred_element_type=jnp.float32)) / scale
        attended = _c(jnp.einsum("nqk,nkc->nqc", _softmax_rows(scores, s), c, preferred_element_type=jnp.float32), s)
        return _mm(attended, w[:, s["nope"]:], s)

    ctx = jax.lax.map(one_head, (jnp.moveaxis(q_nope, 2, 0), jnp.moveaxis(q_rope, 2, 0), jnp.moveaxis(layer["w_ukv"], 1, 0)))
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(u.shape[:2] + (-1,))
    return _mm(ctx, layer["wo"].reshape(-1, layer["wo"].shape[-1]), s)


def routing(v, layer, s):
    """Gates [..., N] over ALL the published experts (float32 whatever
    the equations' type), zero off a token's top k: the bias moves the
    choice and never the gate."""
    score = jax.nn.sigmoid(jnp.matmul(v.astype(jnp.float32), layer["router"], precision="highest"))
    _, chosen = jax.lax.top_k(score + layer["router_bias"], s["top_k"])
    picked = jnp.take_along_axis(score, chosen, axis=-1)
    gate = picked / (picked.sum(-1, keepdims=True) + 1e-6) * s["scaling"]
    return jnp.sum(jax.nn.one_hot(chosen, s["experts"], dtype=jnp.float32) * gate[..., None], axis=-2)


def _swiglu(v, w1, w3, w2, s):
    return _mm(jax.nn.silu(_mm(v, w1, s)) * _mm(v, w3, s), w2, s)


def _experts(v, layer, s, held=None):
    """The shared expert plus the routed sum over the experts ``held``
    (indices into the published experts, in the order the stacks hold
    them; default: the configuration's share)."""
    held = s["held"] if held is None else held
    gates = routing(v, layer, s)[..., jnp.asarray(held)]

    def one(acc, expert):
        w1, w3, w2, g = expert
        return (acc + g[..., None] * _swiglu(v, w1, w3, w2, s).astype(jnp.float32)).astype(acc.dtype), None

    zeros = jnp.zeros(v.shape, jnp.bfloat16 if s.get("bf16_sums") else jnp.float32)
    out, _ = jax.lax.scan(one, zeros, (layer["ew1"], layer["ew3"], layer["ew2"], jnp.moveaxis(gates, -1, 0)))
    if s.get("no_shared_expert"):
        return _c(out, s)
    return _c(out.astype(jnp.float32) + _swiglu(v, layer["sw1"], layer["sw3"], layer["sw2"], s).astype(jnp.float32), s)


def block(x, layer, s: Dict):
    """One layer: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``."""
    attention = _attention_absorbed if s.get("absorbed") else _attention
    h = x + attention(_rms(x, layer["ln1_g"], s), layer, s)
    v = _rms(h, layer["ln2_g"], s)
    return h + (_experts(v, layer, s) if "router" in layer else _swiglu(v, layer["w1"], layer["w3"], layer["w2"], s))


@functools.lru_cache(maxsize=None)
def _programs(frozen_sizes, dtype_name: str = "float32", control: str = ""):
    """The jitted pieces: embedding, one layer of each kind (dense,
    experts), head. float32: the expanded form. bfloat16: the stated
    arithmetic, absorbed, and ``control`` one of :data:`CONTROLS` upon
    it (``int8`` rounds every matrix INSIDE the piece that reads it)."""
    s = dict(frozen_sizes, dtype=jnp.dtype(dtype_name), absorbed=dtype_name != "float32",
             bf16_sums=control == "bfloat16_sums", absorbed_scale=control == "absorbed_scale",
             no_shared_expert=control == "no_shared_expert")
    rounded = (lambda tree: {k: _int8(k, a) for k, a in tree.items()}) if control == "int8" else (lambda tree: tree)
    # float32 is float32: on a TPU a float32 matmul at the default
    # precision is one bfloat16 pass
    highest = jax.default_matmul_precision("highest" if dtype_name == "float32" else "default")

    def embed(table, tokens):
        return _c(rounded({"tok_embed": table})["tok_embed"][tokens], s)

    def layer_fn(x, layer):
        with highest:
            return block(x, rounded(layer), s)

    def head(x, g, w, at):
        with highest:
            x = _rms(jnp.take_along_axis(x, at[:, :, None], axis=1), g, s)
            w = rounded({"lm_head": w})["lm_head"]
            if s["bf16_sums"]:
                return _mm(x, w, s).astype(jnp.float32)
            return jnp.matmul(x, _c(w, s), preferred_element_type=jnp.float32)

    return jax.jit(embed), jax.jit(layer_fn), jax.jit(head)


def hidden(params: Dict, tokens, config: Dict, dtype: str = "float32", control: str = ""):
    """[N, S] tokens -> the last layer's output [N, S, E], layer by layer."""
    embed, layer_fn, _ = _programs(tuple(sorted(sizes(config).items())), dtype, control)
    x = embed(params["tok_embed"], tokens)
    for layer in params["layers"]:
        x = layer_fn(x, layer)
    return x


def logits_at(params: Dict, tokens, at, config: Dict, dtype: str = "float32", control: str = ""):
    """[N, S] tokens, [N, T] positions -> the logits [N, T, V] that
    predict the token after each position."""
    head = _programs(tuple(sorted(sizes(config).items())), dtype, control)[2]
    return head(hidden(params, tokens, config, dtype, control), params["final_ln_g"], params["lm_head"], at)


def judge(params: Dict, config: Dict, tokens, at, arms: Dict[str, np.ndarray], valid, rows: int = ROWS) -> Dict[str, Dict]:
    """Each arm's tokens (``arms[name]`` [N, T]: the tokens chosen after
    positions ``at`` of ``tokens``) as the float32 reference sees them
    (``lfm2.judge``'s contract): per arm ``gap``, how far the token's
    logit lies below the reference's best, and ``margin``, how far the
    reference's second lies below its best, flat over the ``valid``
    tokens. The reference's logits are computed once for all arms, in
    blocks of ``rows`` requests."""
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
    the equations computed otherwise, put in the program's place (after
    the same prefixes) and judged as served tokens are:

    * ``bfloat16`` — the arithmetic the configuration STATES, in the
      absorbed form (module docstring). Not a control: the yardstick
      (``lfm2.gap_ratio``);
    * ``int8`` / ``bfloat16_sums`` — a step coarser than stated, as
      ``reference/lfm2.py`` defines them;
    * ``absorbed_scale`` — the stated arithmetic with the absorbed
      scores divided by sqrt(576), the cached row's width, and not by
      sqrt(192), the expanded score's;
    * ``no_shared_expert`` — the stated arithmetic with the shared
      expert left out of every expert layer."""
    if arithmetic != "bfloat16" and arithmetic not in CONTROLS:
        raise ValueError(f"arithmetic {arithmetic!r}: 'bfloat16' or one of {CONTROLS}")
    out = []
    for lo in range(0, len(tokens), rows):
        part = [np.resize(a[lo : lo + rows], (rows,) + a.shape[1:]) for a in (tokens, at)]
        logits = logits_at(params, jnp.asarray(part[0]), jnp.asarray(part[1]), config, "bfloat16",
                           "" if arithmetic == "bfloat16" else arithmetic)
        out.append(np.asarray(jnp.argmax(logits, -1))[: len(tokens) - lo])
    return np.concatenate(out)


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
        norm="rmsnorm", norm_eps=s["eps"], positions="rotary", rope_theta=s["theta"],
        layer_types=("latent",) * s["layers"], q_lora_rank=s["q_rank"], kv_lora_rank=s["kv_rank"],
        qk_nope_head_dim=s["nope"], qk_rope_head_dim=s["rope"], v_head_dim=s["v_dim"], rope_interleave=True,
        ffn="swiglu", num_dense_layers=s["dense"], num_experts=s["experts"], experts_per_token=s["top_k"],
        moe_ff_size=s["fe"], routed_scaling_factor=s["scaling"], router="sigmoid",
        num_shared_experts=s["shared"], experts_held=s["held"], tied_head=False,
    )


def expert_layer(v, layer, config: Dict, held: Sequence[int]):
    """One expert layer's feed-forward of rows ``v`` [N, S, E] in
    float32 over the routed experts ``held`` (``layer``'s stacks hold
    them in that order), the shared expert included: what the shares of
    a layer are added up from (tests/test_joyai.py)."""
    s = dict(sizes(config), dtype=jnp.dtype("float32"))
    with jax.default_matmul_precision("highest"):
        return _experts(v.astype(jnp.float32), layer, s, held=tuple(held))


def expert_tokens(params: Dict, config: Dict, sequences: Sequence[Sequence[int]]):
    """Tokens each published expert of each expert layer is chosen for
    when every sequence is run whole (``[layer][expert]``): the count the
    program's counters are held to, over the held experts."""
    s = sizes(config)
    embed, layer_fn, _ = _programs(tuple(sorted(s.items())))
    s32 = dict(s, dtype=jnp.dtype("float32"))
    counts = [np.zeros(s["experts"], np.int64) for _ in range(s["layers"] - s["dense"])]
    for seq in sequences:
        x = embed(params["tok_embed"], jnp.asarray([list(seq)], jnp.int32))
        for l, layer in enumerate(params["layers"]):
            if "router" in layer:
                with jax.default_matmul_precision("highest"):
                    h = x + _attention(_rms(x, layer["ln1_g"], s32), layer, s32)
                    gates = routing(_rms(h, layer["ln2_g"], s32), layer, s32)
                counts[l - s["dense"]] += np.asarray((gates > 0).sum(axis=(0, 1)))
            x = layer_fn(x, layer)
    return [c.tolist() for c in counts]
