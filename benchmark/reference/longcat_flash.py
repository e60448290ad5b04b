"""LongCat-Flash-Chat (560B, 18.6-31.3B active a token) as its
configuration file states it, in plain float32: ONE chip's share of the
published model.

Equations (``RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g``, eps 1e-5;
no bias anywhere), from the source's ``config.json``, for positions ``t``
of a sequence. ``x0 = E[tokens]``. One published layer is TWO sequential
sub-layers ``i = 0, 1``, each with norms and weights of its own, and one
routed branch on a shortcut around the second:

* ``h = RMSNorm_a[i](x)``; latent attention ``A(h)``: ``c_q = RMSNorm(h
  W_DQ)`` (6144 -> 1536); ``q = sqrt(6144 / 1536) x (c_q W_UQ)`` -> 64
  heads of 192 = ``[q_nope (128), q_rope (64)]`` (``mla_scale_q_lora``);
  ``[c_kv (512), k_r (64)] = h W_DKV``; ``c = sqrt(6144 / 512) x
  RMSNorm(c_kv)`` (``mla_scale_kv_lora``; ``k_r`` is NOT scaled);
  ``q_rope`` and ``k_r`` (ONE vector, all heads') rotated over interleaved
  pairs ``(2i, 2i+1)``, ``inv_i = theta^(-2i/64)``, theta 10,000,000,
  angle ``t inv_i`` in float32; ``[k_nope_h (128), v_h (128)] = c W_UKV``
  per head; ``s_h(t, j) = (q_nope_h(t) . k_nope_h(j) + q_rope_h(t) .
  k_r(j)) / sqrt(192)``, softmax in float32 over ``j <= t``; ``a_h = sum_j
  p_h(t, j) v_h(j)``; ``A = concat_h(a_h) W_O``.
* ``x = x + A``; ``u = RMSNorm_f[i](x)``.
* ONLY for ``i = 0``: ``p = softmax(u W_r)`` over all 768 outputs of the
  router in float32 (512 published experts, then 256 identity
  "zero-computation" experts); ``I = top_12(p + b)`` (``b`` the selection
  bias: it moves the choice, never the gate); ``g_j = 6 p_j`` for ``j in
  I`` (no renormalisation); ``R = sum_{j in I, j < 512, j held} g_j E_j(u)
  + (sum_{j in I, j >= 512} g_j) u``, ``E(u) = W2 (silu(W1 u) * W3 u)`` of
  width 2,048. ``R`` is kept.
* ``x = x + D_i(u)``, ``D_i`` a dense SwiGLU of width 12,288.
* ONLY after ``i = 1``: ``x = x + R``.

After the last layer a final RMSNorm, then ``logits = x W_head`` (untied).
The first sum of ``R`` runs over the experts THIS chip holds
(``expert_share`` in the file: 16 of the 512, those of chip 0); the
identity experts' term is whole (it needs no weights and no exchange:
every chip of the deployment computes it for its own tokens); what the
other chips' experts would add is left out, as the program leaves it out.

:func:`logits_at` computes the EXPANDED form only. Beside it, for
``gap_ratio``, the same equations "in the arithmetic the configuration
states" (``dtype="bfloat16"``): bfloat16 weights and activations with
float32 accumulation, norms, softmax, rotary angles and router in float32
— in the ABSORBED form, which the program's decode steps compute
(``reference/joyai.py`` says where its roundings lie; the two scales are
applied in float32 before the cast, as the equations place them). The
controls are that arithmetic with one thing changed (:data:`CONTROLS`).

Departures from the published model, each the configuration's
(``assumed`` / ``reduced`` / ``not_served`` in its file): the first
``num_layers`` of the 28 layers; 16 of each layer's 512 routed experts; an
eighth of the vocabulary; the two scales' values and places; gates not
renormalised; a seeded selection bias; router and softmax in float32;
random weights from ``--seed``.

Everything here is ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` for float32: no cache, no
kernel, no batching beyond ``ROWS`` requests a call, one published layer
at a time, within attention one head at a time and within the routed
branch one expert at a time. The WEIGHTS are the benchmark's: made here
from the seed (:func:`init_params`), bfloat16 (router and bias float32),
in the pytree the program takes as a checkpoint: ``tok_embed`` [V, E],
``lm_head`` [E, V], ``final_ln_g``, ``layers``: the ``2 x num_layers``
SUB-layers in order, each ``ln1_g``, ``w_dq`` [E, 1536], ``q_lora_g``,
``w_uq`` [1536, 64, 192], ``w_dkv`` [E, 576], ``kv_lora_g``, ``w_ukv``
[512, 64, 256], ``wo`` [64, 128, E], ``ln2_g``, ``w1`` / ``w3`` [E,
12288], ``w2`` [12288, E], and the even ones (``i = 0``) also ``router``
[E, 768], ``router_bias`` [768], ``ew1`` / ``ew3`` [held, E, 2048],
``ew2`` [held, 2048, E]. The reference reads nothing the program has made.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .decoder import layout  # noqa: F401  (the decoder cells' layout of a judged sample: the driver's)
from .joyai import _rotary_pairs, _softmax_rows
from .lfm2 import _c, _gaps, _int8, _mm, _rms, _uniform, cast_params  # noqa: F401  (cast_params: the rehearsal's and the tests')

ROWS = 1  # requests per call
# what a control changes, beside the stated arithmetic it is computed in: a
# step coarser (the first two), and the two mechanisms a program could get
# wrong and still emit fluent tokens
CONTROLS = ("int8", "bfloat16_sums", "no_zero_experts", "renormalised_gates")
BIAS_DEVIATION = 0.1  # of a uniform pick's probability, 1 / outputs (the configuration's `assumed.selection_bias`)


def sizes(config: Dict) -> Dict:
    """The numbers the equations need, from the configuration file's keys
    (the source's own names). ``experts`` is the PUBLISHED count of real
    experts and ``zero`` that of identity experts: the router scores
    ``experts + zero`` outputs; ``held`` the indices of the real experts
    whose weights exist here; ``vocab`` the rows of the embedding held."""
    c = config
    if c["attention_method"] != "MLA" or c["zero_expert_type"] != "identity" or c["attention_bias"] or c.get("rope_scaling"):
        raise ValueError("latent attention without bias or frequency scaling beside identity zero experts is what is written down")
    if not (c["mla_scale_q_lora"] and c["mla_scale_kv_lora"]):
        raise ValueError("both LoRA scales are what is written down")
    n_held, share = int(c["n_routed_experts"]), c["expert_share"]
    experts = int(c["published"]["n_routed_experts"])
    first = int(share["chip"]) * n_held
    if int(share["chips"]) * n_held != experts or not 0 <= first < experts:
        raise ValueError(f"{share['chips']} chips of {n_held} experts are not the published {experts}")
    if int(c["vocab_share"]["chips"]) * int(c["vocab_size"]) != int(c["published"]["vocab_size"]):
        raise ValueError(f"{c['vocab_share']['chips']} slices of {c['vocab_size']} rows are not the published vocabulary")
    e, rq, rkv = int(c["hidden_size"]), int(c["q_lora_rank"]), int(c["kv_lora_rank"])
    return {
        "layers": int(c["num_layers"]), "e": e, "heads": int(c["num_attention_heads"]), "q_rank": rq, "kv_rank": rkv,
        "nope": int(c["qk_nope_head_dim"]), "rope": int(c["qk_rope_head_dim"]), "v_dim": int(c["v_head_dim"]),
        "f": int(c["ffn_hidden_size"]), "fe": int(c["expert_ffn_hidden_size"]), "experts": experts,
        "zero": int(c["zero_expert_num"]), "held": tuple(range(first, first + n_held)), "top_k": int(c["moe_topk"]),
        "vocab": int(c["vocab_size"]), "eps": float(c["rms_norm_eps"]), "theta": float(c["rope_theta"]),
        "scaling": float(c["routed_scaling_factor"]), "q_scale": math.sqrt(e / rq), "kv_scale": math.sqrt(e / rkv),
    }


# ------------------------------------------------------------------ weights
@functools.partial(jax.jit, static_argnums=(1, 2))
def _init_sub(key, routed: bool, dims):
    e, h, rq, rkv, dn, dr, dv, f, fe, outputs, held = dims
    keys = iter(jax.random.split(key, 13))
    ones = lambda n: jnp.ones((n,), jnp.bfloat16)  # noqa: E731
    sub = {
        "ln1_g": ones(e), "ln2_g": ones(e), "q_lora_g": ones(rq), "kv_lora_g": ones(rkv),
        "w_dq": _uniform(next(keys), (e, rq), e, rq), "w_uq": _uniform(next(keys), (rq, h, dn + dr), rq, h * (dn + dr)),
        "w_dkv": _uniform(next(keys), (e, rkv + dr), e, rkv + dr),
        "w_ukv": _uniform(next(keys), (rkv, h, dn + dv), rkv, h * (dn + dv)),
        "wo": _uniform(next(keys), (h, dv, e), h * dv, e),
        "w1": _uniform(next(keys), (e, f), e, f), "w3": _uniform(next(keys), (e, f), e, f), "w2": _uniform(next(keys), (f, e), f, e),
    }
    if routed:
        sub.update(
            router=_uniform(next(keys), (e, outputs), e, outputs, jnp.float32),
            router_bias=BIAS_DEVIATION / outputs * jax.random.normal(next(keys), (outputs,), jnp.float32),
            ew1=_uniform(next(keys), (held, e, fe), e, fe), ew3=_uniform(next(keys), (held, e, fe), e, fe),
            ew2=_uniform(next(keys), (held, fe, e), fe, e),
        )
    return sub


def init_params(seed: int, config: Dict, held: Optional[int] = None) -> Dict:
    """The configuration's weights from the seed, on the device, one
    jitted call per sub-layer: Glorot-uniform matrices, unit norms, a
    selection bias of deviation ``0.1 / 768``; of a layer's routed experts
    the held ones alone (``held``: another count of them, for the test
    that adds the shares up)."""
    s = sizes(config)
    keys = jax.random.split(jax.random.key(seed), 2 * s["layers"] + 2)
    dims = (s["e"], s["heads"], s["q_rank"], s["kv_rank"], s["nope"], s["rope"], s["v_dim"], s["f"], s["fe"],
            s["experts"] + s["zero"], len(s["held"]) if held is None else held)
    v, e = s["vocab"], s["e"]
    return {
        "tok_embed": jax.jit(lambda k: _uniform(k, (v, e), v, e))(keys[0]),
        "lm_head": jax.jit(lambda k: _uniform(k, (e, v), e, v))(keys[1]),
        "final_ln_g": jnp.ones((e,), jnp.bfloat16),
        "layers": [_init_sub(keys[2 + i], i % 2 == 0, dims) for i in range(2 * s["layers"])],
    }


# ---------------------------------------------------------------- equations
def _projections(h, sub, s):
    """q_nope [N, S, H, 128], q_rope [N, S, H, 64] (scaled, rotated), c
    [N, S, 512] (normed, scaled), k_r [N, S, 64] (rotated, not scaled):
    the scales in float32, before the cast to the equations' type."""
    c_q = _rms(_mm(h, sub["w_dq"], s), sub["q_lora_g"], s)
    q = _mm(c_q, sub["w_uq"].reshape(s["q_rank"], -1), s).reshape(h.shape[:2] + (s["heads"], s["nope"] + s["rope"]))
    q = _c(q.astype(jnp.float32) * s["q_scale"], s)
    kv = _mm(h, sub["w_dkv"], s)
    c = _c(_rms(kv[..., : s["kv_rank"]], sub["kv_lora_g"], dict(s, dtype=jnp.float32)) * s["kv_scale"], s)
    return q[..., : s["nope"]], _rotary_pairs(q[..., s["nope"]:], s["theta"]), c, _rotary_pairs(kv[..., s["kv_rank"]:], s["theta"])


def cache_rows(h, sub, config: Dict):
    """What a position caches, ``[c, k_r]`` [N, S, 576] in float32: the
    row the program's cache is held to (the scaled ``c``, the rotated and
    unscaled ``k_r``)."""
    s = dict(sizes(config), dtype=jnp.dtype("float32"))
    with jax.default_matmul_precision("highest"):
        _, _, c, k_r = _projections(h.astype(jnp.float32), sub, s)
    return jnp.concatenate([c, k_r], axis=-1)


def _attention(h, sub, s):
    """The EXPANDED form: K and V per head out of ``c``."""
    q_nope, q_rope, c, k_r = _projections(h, sub, s)
    scale = float(np.sqrt(s["nope"] + s["rope"]))

    def one_head(args):
        qn, qr, w = args  # [N, S, 128], [N, S, 64], [512, 256]
        kv = _mm(c, w, s)
        k_nope, v = kv[..., : s["nope"]], kv[..., s["nope"]:]
        scores = (jnp.einsum("nqd,nkd->nqk", qn, k_nope) + jnp.einsum("nqd,nkd->nqk", qr, k_r)) / scale
        return jnp.einsum("nqk,nkd->nqd", _softmax_rows(scores, s), v)

    ctx = jax.lax.map(one_head, (jnp.moveaxis(q_nope, 2, 0), jnp.moveaxis(q_rope, 2, 0), jnp.moveaxis(sub["w_ukv"], 1, 0)))
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(h.shape[:2] + (-1,))
    return _mm(ctx, sub["wo"].reshape(-1, sub["wo"].shape[-1]), s)


def _attention_absorbed(h, sub, s):
    """The ABSORBED form, with the roundings where the stated arithmetic
    puts them (``reference/joyai.py``)."""
    q_nope, q_rope, c, k_r = _projections(h, sub, s)
    scale = float(np.sqrt(s["nope"] + s["rope"]))

    def one_head(args):
        qn, qr, w = args
        w = _c(w, s)
        q_abs = _c(jnp.einsum("nqd,cd->nqc", qn, w[:, : s["nope"]], preferred_element_type=jnp.float32), s)
        scores = (jnp.einsum("nqc,nkc->nqk", q_abs, c, preferred_element_type=jnp.float32)
                  + jnp.einsum("nqd,nkd->nqk", qr, k_r, preferred_element_type=jnp.float32)) / scale
        attended = _c(jnp.einsum("nqk,nkc->nqc", _softmax_rows(scores, s), c, preferred_element_type=jnp.float32), s)
        return _mm(attended, w[:, s["nope"]:], s)

    ctx = jax.lax.map(one_head, (jnp.moveaxis(q_nope, 2, 0), jnp.moveaxis(q_rope, 2, 0), jnp.moveaxis(sub["w_ukv"], 1, 0)))
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(h.shape[:2] + (-1,))
    return _mm(ctx, sub["wo"].reshape(-1, sub["wo"].shape[-1]), s)


def routing(u, sub, s):
    """Gates [..., 768] over ALL the router's outputs (float32 whatever
    the equations' type), zero off a token's top 12: ``6 p_j``, not
    renormalised; the bias moves the choice and never the gate. The
    control ``renormalised_gates`` divides by the picked sum (the reading
    not taken: ``norm_topk_prob``)."""
    p = jax.nn.softmax(jnp.matmul(u.astype(jnp.float32), sub["router"], precision="highest"), axis=-1)
    _, chosen = jax.lax.top_k(p + sub["router_bias"], s["top_k"])
    picked = jnp.take_along_axis(p, chosen, axis=-1)
    if s.get("renormalised_gates"):
        picked = picked / picked.sum(-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(chosen, s["experts"] + s["zero"], dtype=jnp.float32) * (picked * s["scaling"])[..., None], axis=-2)


def _swiglu(u, w1, w3, w2, s):
    return _mm(jax.nn.silu(_mm(u, w1, s)) * _mm(u, w3, s), w2, s)


def _shortcut(u, sub, s, held=None, parts: bool = False):
    """The routed branch ``R`` of ``u``: the sum over the real experts
    ``held`` (indices into the published experts, in the order the stacks
    hold them; default: the configuration's share) and the identity
    experts' term, added in float32. ``parts``: the two, apart."""
    held = s["held"] if held is None else held
    gates = routing(u, sub, s)

    def one(acc, expert):
        w1, w3, w2, g = expert
        return (acc + g[..., None] * _swiglu(u, w1, w3, w2, s).astype(jnp.float32)).astype(acc.dtype), None

    zeros = jnp.zeros(u.shape, jnp.bfloat16 if s.get("bf16_sums") else jnp.float32)
    mine = jnp.moveaxis(gates[..., jnp.asarray(held)], -1, 0)
    routed, _ = jax.lax.scan(one, zeros, (sub["ew1"], sub["ew3"], sub["ew2"], mine))
    zero = jnp.sum(gates[..., s["experts"]:], axis=-1)[..., None] * u.astype(jnp.float32)
    if parts:
        return {"routed": routed.astype(jnp.float32), "zero": zero}
    return _c(routed if s.get("no_zero_experts") else routed.astype(jnp.float32) + zero, s)


def block(x, first, second, s: Dict):
    """One published layer: two sub-layers, the routed branch of the
    first one's feed-forward input added behind the second."""
    attention = _attention_absorbed if s.get("absorbed") else _attention
    x = x + attention(_rms(x, first["ln1_g"], s), first, s)
    u = _rms(x, first["ln2_g"], s)
    kept = _shortcut(u, first, s)
    x = x + _swiglu(u, first["w1"], first["w3"], first["w2"], s)
    x = x + attention(_rms(x, second["ln1_g"], s), second, s)
    u = _rms(x, second["ln2_g"], s)
    x = x + _swiglu(u, second["w1"], second["w3"], second["w2"], s)
    return x + kept


@functools.lru_cache(maxsize=None)
def _programs(frozen_sizes, dtype_name: str = "float32", control: str = ""):
    """The jitted pieces: embedding, one published layer, head. float32:
    the expanded form. bfloat16: the stated arithmetic, absorbed, and
    ``control`` one of :data:`CONTROLS` upon it (``int8`` rounds every
    matrix INSIDE the piece that reads it)."""
    s = dict(frozen_sizes, dtype=jnp.dtype(dtype_name), absorbed=dtype_name != "float32",
             bf16_sums=control == "bfloat16_sums", no_zero_experts=control == "no_zero_experts",
             renormalised_gates=control == "renormalised_gates")
    rounded = (lambda tree: {k: _int8(k, a) for k, a in tree.items()}) if control == "int8" else (lambda tree: tree)
    # float32 is float32: on a TPU a float32 matmul at the default
    # precision is one bfloat16 pass
    highest = jax.default_matmul_precision("highest" if dtype_name == "float32" else "default")

    def embed(table, tokens):
        return _c(rounded({"tok_embed": table})["tok_embed"][tokens], s)

    def layer_fn(x, first, second):
        with highest:
            return block(x, rounded(first), rounded(second), s)

    def head(x, g, w, at):
        with highest:
            x = _rms(jnp.take_along_axis(x, at[:, :, None], axis=1), g, s)
            w = rounded({"lm_head": w})["lm_head"]
            if s["bf16_sums"]:
                return _mm(x, w, s).astype(jnp.float32)
            return jnp.matmul(x, _c(w, s), preferred_element_type=jnp.float32)

    return jax.jit(embed), jax.jit(layer_fn), jax.jit(head)


def hidden(params: Dict, tokens, config: Dict, dtype: str = "float32", control: str = ""):
    """[N, S] tokens -> the last layer's output [N, S, E], one published
    layer (two of ``params["layers"]``) at a time."""
    embed, layer_fn, _ = _programs(tuple(sorted(sizes(config).items())), dtype, control)
    x = embed(params["tok_embed"], tokens)
    subs = params["layers"]
    for first, second in zip(subs[0::2], subs[1::2]):
        x = layer_fn(x, first, second)
    return x


def logits_at(params: Dict, tokens, at, config: Dict, dtype: str = "float32", control: str = ""):
    """[N, S] tokens, [N, T] positions -> the logits [N, T, V] that
    predict the token after each position."""
    head = _programs(tuple(sorted(sizes(config).items())), dtype, control)[2]
    return head(hidden(params, tokens, config, dtype, control), params["final_ln_g"], params["lm_head"], at)


def judge(params: Dict, config: Dict, tokens, at, arms: Dict[str, np.ndarray], valid, rows: int = ROWS) -> Dict[str, Dict]:
    """Each arm's tokens as the float32 reference sees them
    (``lfm2.judge``'s contract): per arm ``gap`` and ``margin``, flat over
    the ``valid`` tokens; the reference's logits computed once for all
    arms, in blocks of ``rows`` requests."""
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
      absorbed form. Not a control: the yardstick (``lfm2.gap_ratio``);
    * ``int8`` / ``bfloat16_sums`` — a step coarser than stated, as
      ``reference/lfm2.py`` defines them;
    * ``no_zero_experts`` — the stated arithmetic with the identity
      experts' term left out of every routed branch;
    * ``renormalised_gates`` — the stated arithmetic with a token's gates
      divided by their sum (``6 p_j / sum_I p``)."""
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
    (``flexflow_tpu.generation.decoder.DecoderConfig``): the stack is the
    ``2 x num_layers`` sub-layers, every second one carrying the routed
    branch on its shortcut."""
    from flexflow_tpu.core.types import DataType
    from flexflow_tpu.generation.decoder import DecoderConfig

    s = sizes(config)
    dtype = {"bfloat16": DataType.BFLOAT16, "float32": DataType.FLOAT}[config.get("serving_dtype", "bfloat16")]
    return DecoderConfig(
        num_layers=2 * s["layers"], hidden_size=s["e"], num_heads=s["heads"], ff_size=s["f"],
        seq_length=max_positions, vocab_size=s["vocab"], causal=True, dtype=dtype,
        norm="rmsnorm", norm_eps=s["eps"], positions="rotary", rope_theta=s["theta"],
        layer_types=("latent",) * (2 * s["layers"]), q_lora_rank=s["q_rank"], kv_lora_rank=s["kv_rank"],
        qk_nope_head_dim=s["nope"], qk_rope_head_dim=s["rope"], v_head_dim=s["v_dim"], rope_interleave=True,
        latent_q_scale=s["q_scale"], latent_kv_scale=s["kv_scale"],
        ffn="swiglu", shortcut_experts=2, num_experts=s["experts"], zero_experts=s["zero"],
        experts_per_token=s["top_k"], moe_ff_size=s["fe"], routed_scaling_factor=s["scaling"], router="softmax",
        router_softmax_bias=True, router_renormalise=False, experts_held=s["held"], tied_head=False,
    )


def shortcut_parts(u, sub, config: Dict, held: Sequence[int]) -> Dict:
    """The routed branch of rows ``u`` [N, S, E] in float32, its two parts
    apart: ``routed`` over the real experts ``held`` (``sub``'s stacks
    hold them in that order) and ``zero``, the identity experts' term:
    what the shares of a layer are added up from (tests/test_longcat.py)."""
    s = dict(sizes(config), dtype=jnp.dtype("float32"))
    with jax.default_matmul_precision("highest"):
        return _shortcut(u.astype(jnp.float32), sub, s, held=tuple(held), parts=True)


def router_picks(params: Dict, config: Dict, sequences: Sequence[Sequence[int]]):
    """Tokens each of the router's outputs is chosen for in each published
    layer when every sequence is run whole (``[layer][output]``), and the
    tokens by how many REAL experts they picked (``[layer][0 .. k]``):
    what the program's counters are held to."""
    s = sizes(config)
    embed, layer_fn, _ = _programs(tuple(sorted(s.items())))
    s32 = dict(s, dtype=jnp.dtype("float32"))
    picks = [np.zeros(s["experts"] + s["zero"], np.int64) for _ in range(s["layers"])]
    real = [np.zeros(s["top_k"] + 1, np.int64) for _ in range(s["layers"])]
    subs = params["layers"]
    for seq in sequences:
        x = embed(params["tok_embed"], jnp.asarray([list(seq)], jnp.int32))
        for l, (first, second) in enumerate(zip(subs[0::2], subs[1::2])):
            with jax.default_matmul_precision("highest"):
                h = x + _attention(_rms(x, first["ln1_g"], s32), first, s32)
                chosen = np.asarray(routing(_rms(h, first["ln2_g"], s32), first, s32) > 0)[0]
            picks[l] += chosen.sum(axis=0)
            real[l] += np.bincount(chosen[:, : s["experts"]].sum(axis=1), minlength=s["top_k"] + 1)
            x = layer_fn(x, first, second)
    return [p.tolist() for p in picks], [r.tolist() for r in real]
