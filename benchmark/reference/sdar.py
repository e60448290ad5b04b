"""SDAR-30B-A3B-Chat as its configuration file states it, and the
block-diffusion loop it is generated by, in plain float32.

Equations (``RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g``, eps 1e-6;
no bias anywhere), from the source's ``config.json`` (``model_type:
sdar_moe``), for rows ``t`` at positions ``p(t)`` and block length ``B``:

* ``x0 = E[tokens]``, a row that is still masked embedded as the mask
  token ``M``; every layer ``h = x + Attn(RMSNorm(x; g1))``, ``y = h +
  MoE(RMSNorm(h; g2))``; after the last a final RMSNorm, then ``logits =
  x W_head`` (``tie_word_embeddings: false``). **The logits at position
  i are the distribution of the token AT position i**: nothing is shifted.
* ``q = x Wq`` (32 heads of 128), ``k = x Wk``, ``v = x Wv`` (4 heads of
  128); q and k each RMSNorm over the 128 of a head (learned weight),
  then rotate-half rotary at the row's own position, theta 1,000,000,
  no scaling; query head ``i`` reads K/V head ``i // 8``.
* ``s(t, j) = q(t) . k(j) / sqrt(128)``, admitted iff ``p(j) // B <=
  p(t) // B`` (every earlier block, and the whole of the row's own,
  later rows included); float32 softmax; ``out = (P v) Wo``.
* ``MoE(u)``: ``p = softmax(u Wr)`` over the 128 experts in float32, ``I
  = top_8(p)``, ``g_i = p_i / sum_{j in I} p_j``, ``sum_{i in I} g_i
  W2_i (silu(W1_i u) * W3_i u)``, experts of 768; no shared expert, no
  capacity, no dropped token.

Generation of ``N`` tokens behind a prompt of ``P`` (:func:`generate`,
the plain loop): positions in blocks of ``B`` from 0; block by block
from ``P // B``, a block starts as the prompt's remaining tokens
(fixed) then ``M`` at every other row; a denoising forward runs the
WHOLE sequence so far with the block's rows (no cache here: the K/V a
later block reads is whatever the forward of the final tokens gives, so
the program's commit forward is implicit) and reads the logits at the
rows still masked; a row's candidate is its best token other than ``M``,
its confidence that token's softmax probability over the whole
vocabulary; ``low_confidence_static`` fixes the ``B / S`` masked rows of
highest confidence (ties to the lower position), ``low_confidence_dynamic``
every masked row whose confidence passes ``threshold`` and the static
rule's rows if fewer pass. A fixed row stays fixed; tokens past ``N`` in
the last block are dropped.

Under this mask the K/V of a block does not depend on any later block,
so a block's rows can be scored against the K/V that ONE forward of the
final sequence gave the earlier blocks (:func:`block_logits`: exact, and
tests/test_sdar.py says so). That is how a served request is judged: all
its denoising states as one batch of ``B``-row forwards behind one whole
forward, two whole forwards of work and not one a state.

Departures from the published model, each the configuration's
(``assumed`` / ``departures`` in its file): the first ``num_hidden_layers``
of 48; per-head q/k RMSNorm; float32 router, softmax and confidence;
block length, steps and the mask token (the config has none of the
three); the mask token is never a candidate; a row is masked by a flag,
not by its id; weights from ``--seed``.

Everything is ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no paged cache, no kernel,
one layer at a time, one expert at a time. The WEIGHTS are the
benchmark's (:func:`init_params`, bfloat16, the router float32), in the
pytree the program takes: ``tok_embed``, ``lm_head``, ``final_ln_g``,
``layers``: ``ln1_g``, ``wq`` [E, 32, 128], ``wk`` / ``wv`` [E, 4, 128],
``wo`` [32, 128, E], ``q_norm_g`` / ``k_norm_g``, ``ln2_g``, ``router``
[E, 128], ``ew1`` / ``ew3`` [128, E, 768], ``ew2`` [128, 768, E].
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .lfm2 import _c, _heads, _int8, _mm, _rms, _uniform, cast_params, gap_ratio  # noqa: F401
from .mellum2 import _experts, _init_layer, routing  # the same expert layer (softmax top-8, renormalised) and the same weights' shapes

NEG = -1e30
STATES = 64  # denoising states a call of block_logits: [64, 4, V] float32 logits are 155 MB
CONTROLS = ("bfloat16_sums", "int8", "causal_mask")


def sizes(config: Dict) -> Dict:
    c, g = config, config["generation"]
    if c.get("rope_scaling") is not None or int(c["decoder_sparse_step"]) != 1 or c["mlp_only_layers"]:
        raise ValueError("written down: plain rotary, every layer an expert layer")
    return {
        "layers": int(c["num_hidden_layers"]), "e": int(c["hidden_size"]), "heads": int(c["num_attention_heads"]),
        "kv_heads": int(c["num_key_value_heads"]), "head_dim": int(c["head_dim"]),
        "fe": int(c["moe_intermediate_size"]), "experts": int(c["num_experts"]),
        "top_k": int(c["num_experts_per_tok"]), "vocab": int(c["vocab_size"]),
        "eps": float(c["rms_norm_eps"]), "theta": float(c["rope_theta"]),
        "block": int(g["block_length"]), "mask": int(g["mask_token_id"]),
    }


def parameter_count(config: Dict, layers: Optional[int] = None) -> Dict[str, int]:
    """Parameters of ``layers`` layers (None: the file's own) with the
    embedding and the head, from the file's sizes: ``total``, and
    ``active`` a token (8 of the 128 experts)."""
    s = sizes(config)
    n = s["layers"] if layers is None else layers
    attention = 2 * s["e"] * s["heads"] * s["head_dim"] + 2 * s["e"] * s["kv_heads"] * s["head_dim"]
    norms = 2 * s["e"] + 2 * s["head_dim"]
    outside = attention + s["e"] * s["experts"] + norms
    expert = 3 * s["e"] * s["fe"]
    ends = 2 * s["vocab"] * s["e"] + s["e"]
    return {
        "layer": outside + s["experts"] * expert, "outside_experts": outside, "expert": expert, "ends": ends,
        "total": n * (outside + s["experts"] * expert) + ends, "active": n * (outside + s["top_k"] * expert) + ends,
    }


# ------------------------------------------------------------------ weights
def init_params(seed: int, config: Dict) -> Dict:
    """The configuration's weights from the seed, on the device, one
    jitted call per layer: Glorot-uniform matrices, unit norms."""
    s = sizes(config)
    keys = jax.random.split(jax.random.key(seed), s["layers"] + 2)
    dims = (s["e"], s["heads"], s["kv_heads"], s["head_dim"], s["fe"], s["experts"])
    v, e = s["vocab"], s["e"]
    return {
        "tok_embed": jax.jit(lambda k: _uniform(k, (v, e), v, e))(keys[0]),
        "lm_head": jax.jit(lambda k: _uniform(k, (e, v), e, v))(keys[1]),
        "final_ln_g": jnp.ones((e,), jnp.bfloat16),
        "layers": [_init_layer(keys[2 + l], dims) for l in range(s["layers"])],
    }


# ---------------------------------------------------------------- equations
def _rotary_at(x, pos, theta: float):
    """Rotate-half over all of a head's dimensions: x [N, T, H, D] at
    positions ``pos`` [N, T], angles in float32."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[..., None] * inv  # [N, T, D/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, :, None, :]
    xf = x.astype(jnp.float32)
    rotated = jnp.concatenate([-xf[..., d // 2:], xf[..., : d // 2]], -1)
    return (xf * cos + rotated * sin).astype(x.dtype)


def _attention(u, layer, pos, s: Dict, past=None):
    """Rows ``u`` [N, T, E] at positions ``pos`` [N, T] attend each other
    under the block mask and, given ``past`` = (k [S, Hkv, D], v, their
    positions [S]) — ONE sequence's K/V, shared by all N — also every
    past key of an earlier block. Returns the layer's output and the
    rows' own (k, v) [N, T, Hkv, D]."""
    n, t, _ = u.shape
    b, group = s["block"], s["heads"] // s["kv_heads"]
    q = _rotary_at(_rms(_heads(u, layer["wq"], s), layer["q_norm_g"], s), pos, s["theta"])
    k = _rotary_at(_rms(_heads(u, layer["wk"], s), layer["k_norm_g"], s), pos, s["theta"])
    v = _heads(u, layer["wv"], s)
    scale = float(np.sqrt(s["head_dim"]))
    if s.get("causal_mask"):  # the control: a program that keeps the causal mask
        seen = pos[:, None, :] <= pos[:, :, None]
    else:
        seen = pos[:, None, :] // b <= pos[:, :, None] // b  # [N, query, key]
    qg = q.reshape(n, t, s["kv_heads"], group, s["head_dim"])
    own = jnp.where(seen[:, None, None], jnp.einsum("nqhgd,nkhd->nhgqk", qg, k) / scale, -jnp.inf)
    if past is not None:
        pk, pv, ppos = past
        before = ppos[None, None, :] // b < pos[:, :, None] // b  # [N, query, past key]: an EARLIER block's
        old = jnp.where(before[:, None, None], jnp.einsum("nqhgd,khd->nhgqk", qg, _c(pk, s)) / scale, -jnp.inf)
        own = jnp.concatenate([old, own], axis=-1)
    probs = _c(jax.nn.softmax(own.astype(jnp.float32), axis=-1), s)
    split = 0 if past is None else past[0].shape[0]
    ctx = jnp.einsum("nhgqk,nkhd->nqhgd", probs[..., split:], v)
    if past is not None:
        ctx = ctx + jnp.einsum("nhgqk,khd->nqhgd", probs[..., :split], _c(pv, s))
    out = _mm(ctx.reshape(n, t, -1), layer["wo"].reshape(-1, layer["wo"].shape[-1]), s)
    return out, (k, v)


def block(x, layer, pos, s: Dict, past=None):
    """One layer: ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``;
    and the rows' K/V."""
    a, kv = _attention(_rms(x, layer["ln1_g"], s), layer, pos, s, past)
    h = x + a
    return h + _experts(_rms(h, layer["ln2_g"], s), layer, s), kv


@functools.lru_cache(maxsize=None)
def _programs(frozen_sizes, dtype_name: str = "float32", control: str = ""):
    """The jitted pieces: embedding, one layer (with and without a
    past), head. ``control`` (:data:`CONTROLS`): ``bfloat16_sums`` and
    ``int8`` as ``reference/lfm2.py`` defines them, ``causal_mask`` the
    stated arithmetic under the causal mask."""
    s = dict(frozen_sizes, dtype=jnp.dtype(dtype_name), bf16_sums=control == "bfloat16_sums",
             causal_mask=control == "causal_mask")
    rounded = (lambda tree: {k: _int8(k, a) for k, a in tree.items()}) if control == "int8" else (lambda tree: tree)
    highest = jax.default_matmul_precision("highest" if dtype_name == "float32" else "default")

    def embed(table, tokens, masked):
        return _c(rounded({"tok_embed": table})["tok_embed"][jnp.where(masked, s["mask"], tokens)], s)

    def layer_fn(x, layer, pos):
        with highest:
            return block(x, rounded(layer), pos, s)

    def layer_past(x, layer, pos, pk, pv, ppos):
        with highest:
            return block(x, rounded(layer), pos, s, (pk, pv, ppos))[0]

    def head(x, g, w):
        with highest:
            x = _rms(x, g, s)
            w = rounded({"lm_head": w})["lm_head"]
            if s["bf16_sums"]:
                return _mm(x, w, s).astype(jnp.float32)
            return jnp.matmul(x, _c(w, s), preferred_element_type=jnp.float32)

    return jax.jit(embed), jax.jit(layer_fn), jax.jit(layer_past), jax.jit(head)


def _pieces(config: Dict, dtype: str, control: str):
    return _programs(tuple(sorted(sizes(config).items())), dtype, control)


def hidden_kv(params: Dict, tokens, masked, config: Dict, dtype: str = "float32", control: str = ""):
    """[N, T] tokens (``masked`` [N, T] bool: rows embedded as the mask
    token) from position 0 -> the last layer's output [N, T, E] and every
    layer's (k, v) [N, T, Hkv, D]: the whole forward under the block
    mask, no cache."""
    embed, layer_fn, _, _ = _pieces(config, dtype, control)
    tokens = jnp.asarray(tokens)
    pos = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None], tokens.shape)
    x, kvs = embed(params["tok_embed"], tokens, jnp.asarray(masked)), []
    for layer in params["layers"]:
        x, kv = layer_fn(x, layer, pos)
        kvs.append(kv)
    return x, kvs


def forward(params: Dict, tokens, masked, config: Dict, dtype: str = "float32", control: str = ""):
    """The oracle: logits [N, T, V] of the whole sequence under the
    block mask (at a size whose [T, V] logits fit: the tests')."""
    x, _ = hidden_kv(params, tokens, masked, config, dtype, control)
    return _pieces(config, dtype, control)[3](x, params["final_ln_g"], params["lm_head"])


def block_logits(params: Dict, config: Dict, kvs, blocks, masked, bases, dtype: str = "float32", control: str = ""):
    """Logits [M, B, V] of ``M`` block states (``blocks`` / ``masked``
    [M, B], the block at position ``bases[m]``) of ONE sequence, each
    against the K/V ``kvs`` (:func:`hidden_kv` of that sequence's final
    tokens, its row 0) gave the blocks before it."""
    embed, _, layer_past, head = _pieces(config, dtype, control)
    blocks, bases = jnp.asarray(blocks), jnp.asarray(bases)
    pos = bases[:, None] + jnp.arange(blocks.shape[1])[None, :]
    x = embed(params["tok_embed"], blocks, jnp.asarray(masked))
    for layer, (k, v) in zip(params["layers"], kvs):
        x = layer_past(x, layer, pos, k[0], v[0], jnp.arange(k.shape[1]))
    return head(x, params["final_ln_g"], params["lm_head"])


# -------------------------------------------------------------------- rule
@functools.partial(jax.jit, static_argnums=(4,))
def rule(logits, masked, n_fix, threshold, mask_token: int):
    """The generation rule on float32 ``logits`` [M, B, V] of blocks whose
    ``masked`` [M, B] rows are open: every row's candidate (its best
    token other than the mask token), its log confidence (that token's
    log softmax over the whole vocabulary), and the rows fixed: every
    masked row whose confidence passes ``threshold`` if ``n_fix`` or more
    do, else the ``n_fix`` masked rows of highest confidence, ties to the
    lower position (the static rule: a threshold no confidence passes)."""
    candidates = jnp.where(jnp.arange(logits.shape[-1]) == mask_token, NEG, logits)
    token = jnp.argmax(candidates, axis=-1).astype(jnp.int32)
    best = jnp.take_along_axis(logits, token[..., None], axis=-1)[..., 0]
    log_conf = best - jax.scipy.special.logsumexp(logits, axis=-1)
    order = jnp.argsort(-jnp.where(masked, jnp.exp(log_conf), -1.0), axis=1, stable=True)
    top = jnp.argsort(order, axis=1, stable=True) < n_fix
    passes = jnp.logical_and(masked, jnp.exp(log_conf) > threshold)
    chosen = jnp.logical_and(masked, jnp.where((passes.sum(axis=1) >= n_fix)[:, None], passes, top))
    return token, log_conf, chosen, best


def generate(params: Dict, config: Dict, prompt: Sequence[int], max_new: int, steps: Optional[int] = None,
             remasking: Optional[str] = None, threshold: Optional[float] = None, dtype: str = "float32",
             eos_id: Optional[int] = None) -> Tuple[List[int], List[int]]:
    """The plain loop (module docstring): the reply's ``max_new`` tokens
    and, for each, the index within its block of the denoising forward
    that fixed it. Every forward is a whole one of the sequence so far."""
    s, g = sizes(config), config["generation"]
    b = s["block"]
    n_fix = b // int(g["denoising_steps"] if steps is None else steps)
    static = (g["remasking"] if remasking is None else remasking) == "low_confidence_static"
    limit = 2.0 if static else float(g.get("threshold", 0.9) if threshold is None else threshold)
    seq, fixed_at = list(prompt), []
    base = len(prompt) // b * b
    reply: List[int] = []
    while len(reply) < max_new:
        given = seq[base:]
        tokens = np.array(given + [0] * (b - len(given)), np.int32)
        open_ = np.arange(b) >= len(given)
        at, forwards = [-1] * b, 0
        while open_.any():
            whole = np.concatenate([np.asarray(seq[:base], np.int32), tokens])[None]
            hidden = np.concatenate([np.zeros((base,), bool), open_])[None]
            logits = forward(params, whole, hidden, config, dtype)[:, base:]
            token, _, chosen, _ = rule(logits, jnp.asarray(open_[None]), n_fix, limit, s["mask"])
            for j in np.nonzero(np.asarray(chosen[0]))[0]:
                tokens[j], open_[j], at[j] = int(token[0, j]), False, forwards
            forwards += 1
        for j in range(len(given), b):
            if len(reply) < max_new:
                reply.append(int(tokens[j]))
                fixed_at.append(at[j])
                if eos_id is not None and reply[-1] == eos_id:
                    return reply, fixed_at
        seq = seq[:base] + [int(t) for t in tokens]
        base += b
    return reply, fixed_at


# ------------------------------------------------------------------- judge
def trajectory(prompt: Sequence[int], reply: Sequence[int], fixed_at: Sequence[int], block: int, pad_to: int,
               states: int) -> Dict:
    """One served request as arrays of fixed shape: ``tokens`` [pad_to],
    its final sequence cut to whole blocks (later blocks never reach an
    earlier one, so what lies behind is padding), and every denoising
    state of every WHOLE block of the reply (the last block, where the
    budget cuts it, is not judged: its dropped rows are not known):
    ``blocks`` / ``masked`` [states, B] the block as that forward saw it,
    ``bases`` [states], ``chosen`` [states, B] the rows the program fixed
    at it and ``picked`` their tokens, ``valid`` [states]."""
    p, seq = len(prompt), list(prompt) + list(reply)
    whole = len(seq) // block * block
    out = {
        "tokens": np.zeros((pad_to,), np.int32), "blocks": np.zeros((states, block), np.int32),
        "masked": np.zeros((states, block), bool), "bases": np.zeros((states,), np.int32),
        "chosen": np.zeros((states, block), bool), "picked": np.zeros((states, block), np.int32),
        "valid": np.zeros((states,), bool),
    }
    out["tokens"][:whole] = seq[:whole]
    m = 0
    for base in range(p // block * block, whole, block):
        rows = np.arange(base, base + block)
        at = np.array([-1 if r < p else fixed_at[r - p] for r in rows])
        final = np.asarray(seq[base : base + block], np.int32)
        for f in range(at.max() + 1):
            open_ = at >= f
            out["blocks"][m], out["masked"][m], out["bases"][m] = np.where(open_, 0, final), open_, base
            out["chosen"][m], out["picked"][m], out["valid"][m] = at == f, final, True
            m += 1
    if m > states:
        raise ValueError(f"{m} denoising states, room for {states}")
    return out


def choices(params: Dict, config: Dict, lay: Dict, n_fix: int, threshold: float, arithmetic: str) -> Dict:
    """What the equations computed in ``arithmetic`` (``bfloat16``: the
    arithmetic the configuration STATES, the yardstick; or one of
    :data:`CONTROLS`) fix after the same states: ``chosen`` [states, B]
    and ``picked``, put in the program's place and judged as it is."""
    dtype, control = ("bfloat16", "" if arithmetic == "bfloat16" else arithmetic)
    if control and control not in CONTROLS:
        raise ValueError(f"arithmetic {arithmetic!r}: 'bfloat16' or one of {CONTROLS}")
    mask_token = sizes(config)["mask"]
    _, kvs = hidden_kv(params, lay["tokens"][None], np.zeros((1, len(lay["tokens"])), bool), config, dtype, control)
    chosen, picked = [], []
    for lo in range(0, len(lay["valid"]), STATES):
        part = slice(lo, lo + STATES)
        logits = block_logits(params, config, kvs, lay["blocks"][part], lay["masked"][part], lay["bases"][part], dtype, control)
        token, _, rows, _ = rule(logits, jnp.asarray(lay["masked"][part]), n_fix, threshold, mask_token)
        chosen.append(np.asarray(rows))
        picked.append(np.asarray(token))
    return {"chosen": np.concatenate(chosen), "picked": np.concatenate(picked)}


def judge(params: Dict, config: Dict, lay: Dict, arms: Dict[str, Dict], n_fix: int, threshold: float) -> Dict[str, Dict]:
    """Each arm's fixed rows (``arms[name]``: ``chosen`` / ``picked``
    [states, B]) as the float32 reference sees them at the same states,
    in logits, flat over the fixed rows of the valid states: ``gap``,
    how far the fixed token's logit lies below the reference's best
    candidate at its row, and ``row_gap``, how far the row's log
    confidence (the reference's, of its own candidate) lies below the
    least one among the rows the reference's own choice would have
    fixed. The reference's logits are computed once for all arms."""
    mask_token = sizes(config)["mask"]
    _, kvs = hidden_kv(params, lay["tokens"][None], np.zeros((1, len(lay["tokens"])), bool), config)
    out = {name: {"gap": [], "row_gap": []} for name in arms}
    for lo in range(0, len(lay["valid"]), STATES):
        part = slice(lo, lo + STATES)
        if not lay["valid"][part].any():
            continue
        logits = block_logits(params, config, kvs, lay["blocks"][part], lay["masked"][part], lay["bases"][part])
        if not bool(jnp.all(jnp.isfinite(logits))):
            raise FloatingPointError("the reference produced non-finite logits")
        _, log_conf, own, best = rule(logits, jnp.asarray(lay["masked"][part]), n_fix, threshold, mask_token)
        log_conf, own, best = np.asarray(log_conf), np.asarray(own), np.asarray(best)
        least = np.where(own, log_conf, np.inf).min(axis=1, keepdims=True)  # of the reference's own choice of rows
        for name, arm in arms.items():
            rows = arm["chosen"][part] & lay["valid"][part][:, None]
            got = np.asarray(jnp.take_along_axis(logits, jnp.asarray(arm["picked"][part])[..., None], axis=-1)[..., 0])
            out[name]["gap"].append((best - got)[rows])
            out[name]["row_gap"].append(np.maximum(least - log_conf, 0.0)[rows])
    return {name: {k: np.concatenate(v) if v else np.zeros((0,)) for k, v in arm.items()} for name, arm in out.items()}


def pooled_gap_ratio(judged: Dict, stated: Dict) -> float:
    """THE number the cell's ``correct`` compares: both distances of an
    arm's fixed rows from the float32 reference, in logits, summed (how
    far each fixed token lies below the reference's best candidate at its
    row; how far each fixed row's log confidence lies below the least the
    reference's own choice of rows would have fixed), over the same sum
    for what the stated arithmetic fixes after the same states: ``lfm2.
    gap_ratio``'s reasoning, over two kinds of distance. Why pooled: the
    tokens' ratio alone has ``lfm2.gap_ratio``'s small-denominator tail
    (a sound program read 1.42 on the one seed of twelve whose stated
    arithmetic tipped half its usual number of near-ties, and a cap on a
    token's gap does not cure it: the count moved, not a few large gaps);
    the rows' shortfalls are several hundred small terms a request on
    every seed, and the sum of both read 0.92-1.13 for the sound program
    where the controls read 1.73-2.23 (the cell's file, ``correct``)."""
    own = float(judged["gap"].sum() + judged["row_gap"].sum())
    return own / max(float(stated["gap"].sum() + stated["row_gap"].sum()), 1e-30)


def row_gap_ratio(judged: Dict, stated: Dict) -> float:
    """The rows' part of :func:`pooled_gap_ratio` alone (logged, not
    judged): the summed shortfall of log confidence below the
    reference's own least, over the stated arithmetic's."""
    return float(judged["row_gap"].sum() / max(float(stated["row_gap"].sum()), 1e-30))


def reading(judged: Dict) -> Dict:
    gap = judged["gap"]
    return {"rows": int(gap.size), "off_argmax": int((gap > 0).sum()), "mean_gap": float(gap.mean()) if gap.size else 0.0,
            "off_rows": int((judged["row_gap"] > 0).sum()),
            "mean_row_gap": float(judged["row_gap"].mean()) if gap.size else 0.0}


# ----------------------------------------------------------------- program
def engine_config(config: Dict, max_positions: int):
    """The configuration as the program takes it
    (``flexflow_tpu.generation.decoder.DecoderConfig``)."""
    from flexflow_tpu.core.types import DataType
    from flexflow_tpu.generation.decoder import DecoderConfig

    s = sizes(config)
    dtype = {"bfloat16": DataType.BFLOAT16, "float32": DataType.FLOAT}[config.get("serving_dtype", "bfloat16")]
    return DecoderConfig(
        num_layers=s["layers"], hidden_size=s["e"], num_heads=s["heads"], ff_size=s["fe"],
        seq_length=max_positions, vocab_size=s["vocab"], causal=True, dtype=dtype,
        norm="rmsnorm", norm_eps=s["eps"], positions="rotary", rope_theta=s["theta"], qk_norm=True,
        num_kv_heads=s["kv_heads"], head_dim=s["head_dim"], num_dense_layers=0, num_experts=s["experts"],
        experts_per_token=s["top_k"], moe_ff_size=s["fe"], router="softmax", tied_head=False,
        block_mask=s["block"],
    )


def diffusion_rule(config: Dict, **over):
    """The generation rule the engine is built with
    (``flexflow_tpu.generation.engine.BlockDiffusion``): the file's
    ``generation`` group, ``over`` over it."""
    from flexflow_tpu.generation.engine import BlockDiffusion

    g = {k: config["generation"][k] for k in ("block_length", "denoising_steps", "remasking", "threshold", "mask_token_id")
         if k in config["generation"]}
    return BlockDiffusion(**{**g, **over})


def expert_tokens(params: Dict, config: Dict, sequences: Sequence[Sequence[int]]):
    """Tokens each expert of each layer is handed when every sequence is
    run whole (no row masked): the count the program's prefill counters
    are held to."""
    s = sizes(config)
    embed, layer_fn, _, _ = _pieces(config, "float32", "")
    s32 = dict(s, dtype=jnp.dtype("float32"))
    counts = [np.zeros(s["experts"], np.int64) for _ in range(s["layers"])]
    for seq in sequences:
        tokens = jnp.asarray([list(seq)], jnp.int32)
        pos = jnp.arange(tokens.shape[1])[None]
        x = embed(params["tok_embed"], tokens, jnp.zeros(tokens.shape, bool))
        for l, layer in enumerate(params["layers"]):
            with jax.default_matmul_precision("highest"):
                h = x + _attention(_rms(x, layer["ln1_g"], s32), layer, pos, s32)[0]
                gates = routing(_rms(h, layer["ln2_g"], s32), layer, s32)
            counts[l] += np.asarray((gates > 0).sum(axis=(0, 1)))
            x = layer_fn(x, layer, pos)[0]
    return [c.tolist() for c in counts]
