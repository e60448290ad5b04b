"""Decoder-only transformer for the generation engine: a pure-JAX
params pytree + three forward modes that provably agree.

The graph-built transformers (models/transformer.py) lower to one-shot
jitted programs with no state; generation needs a forward that can split
into prefill (write the prompt's K/V into the cache) and decode (one
token against cached K/V). This module keeps the same layer recipe as
``attention_encoder_layer`` with ``causal=True`` — pre-LN residual
blocks, GELU FFN, the ops/attention.py weight layouts ([E, H, D]
projections, [H, D, E] output) — plus a learned absolute position
embedding (cache positions index it directly) and a token-embedding
front end with an LM head.

Three forwards over one params pytree:

* :func:`forward_full` — full-context causal forward, [B, S] -> logits
  [B, S, V]. The parity oracle.
* :func:`prefill` — forward_full that also returns every layer's K/V
  ([L, B, S, H, D]) for the engine to scatter into the block cache,
  with per-sequence length masking so padded prompt buckets match the
  unpadded forward.
* :func:`decode_step` — one token per sequence against the cache
  (writes the token's K/V, then decode-mode attention), [B] -> logits
  [B, V].
* :func:`verify_step` — a W-token append window per sequence against
  the cache (writes all W tokens' K/V, then chunked-append attention
  with causal-within-window masking), [B, W] -> logits [B, W, V]. The
  speculative-decoding verification forward: W sequential decode_steps
  in ONE call, with identical logits.

``forward_full(tokens)[b, i] == decode logits after caching tokens[:i]``
within fp32 tolerance — asserted by tests/test_generation.py;
``verify_step`` agrees with ``decode_step`` token-for-token — asserted
by tests/test_speculative.py.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models.transformer import TransformerConfig
from ..ops.attention import append_attention_core, decode_attention_core, masked_attention
from .cache import slot_mapping

# a decoder is a plain pytree: jit-friendly, checkpoint-friendly
DecoderParams = Dict[str, Any]


def _glorot(rng, shape):
    fan_in, fan_out = shape[0], shape[-1]
    if len(shape) == 3:  # [E, H, D] / [H, D, E] projections
        fan_in = shape[0] if shape[0] > shape[2] else shape[0] * shape[1]
        fan_out = shape[1] * shape[2] if shape[0] > shape[2] else shape[2]
    lim = (6.0 / (fan_in + fan_out)) ** 0.5
    return jax.random.uniform(rng, shape, jnp.float32, -lim, lim)


def init_decoder_params(
    rng: jax.Array, cfg: TransformerConfig, max_positions: Optional[int] = None
) -> DecoderParams:
    """Initialize the decoder pytree for ``cfg`` (``vocab_size`` > 0)."""
    if cfg.vocab_size <= 0:
        raise ValueError("generation decoder needs cfg.vocab_size > 0")
    e, h = cfg.hidden_size, cfg.num_heads
    d = e // h
    f, v = cfg.ff_size, cfg.vocab_size
    p = max_positions or cfg.seq_length
    keys = iter(jax.random.split(rng, 4 + 6 * cfg.num_layers))
    params: DecoderParams = {
        "tok_embed": _glorot(next(keys), (v, e)),
        "pos_embed": 0.02 * jax.random.normal(next(keys), (p, e), jnp.float32),
        "final_ln_g": jnp.ones((e,), jnp.float32),
        "final_ln_b": jnp.zeros((e,), jnp.float32),
        "lm_head": _glorot(next(keys), (e, v)),
        "layers": [],
    }
    for _ in range(cfg.num_layers):
        params["layers"].append(
            {
                "ln1_g": jnp.ones((e,), jnp.float32),
                "ln1_b": jnp.zeros((e,), jnp.float32),
                "wq": _glorot(next(keys), (e, h, d)),
                "wk": _glorot(next(keys), (e, h, d)),
                "wv": _glorot(next(keys), (e, h, d)),
                "wo": _glorot(next(keys), (h, d, e)),
                "ln2_g": jnp.ones((e,), jnp.float32),
                "ln2_b": jnp.zeros((e,), jnp.float32),
                "ff1": _glorot(next(keys), (e, f)),
                "ff1_b": jnp.zeros((f,), jnp.float32),
                "ff2": _glorot(next(keys), (f, e)),
                "ff2_b": jnp.zeros((e,), jnp.float32),
            }
        )
    return params


def _ln(x, g, b, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _embed(params, tokens, positions):
    return params["tok_embed"][tokens] + params["pos_embed"][positions]


def _ffn(layer, x):
    h = _ln(x, layer["ln2_g"], layer["ln2_b"])
    h = jax.nn.gelu(h @ layer["ff1"] + layer["ff1_b"])
    return x + h @ layer["ff2"] + layer["ff2_b"]


def forward_full(
    params: DecoderParams,
    tokens: jax.Array,
    lengths: Optional[jax.Array] = None,
) -> jax.Array:
    """Full-context causal forward: [B, S] int32 -> logits [B, S, V].
    ``lengths`` masks padded key positions (bucketed prompts)."""
    b, s = tokens.shape
    x = _embed(params, tokens, jnp.arange(s)[None, :])
    lens = lengths if lengths is not None else jnp.full((b,), s, jnp.int32)
    for layer in params["layers"]:
        h = _ln(x, layer["ln1_g"], layer["ln1_b"])
        q = jnp.einsum("bse,ehd->bshd", h, layer["wq"])
        k = jnp.einsum("bse,ehd->bshd", h, layer["wk"])
        v = jnp.einsum("bse,ehd->bshd", h, layer["wv"])
        ctx = masked_attention(q, k, v, lens, causal=True)
        x = x + jnp.einsum("bshd,hde->bse", ctx, layer["wo"])
        x = _ffn(layer, x)
    x = _ln(x, params["final_ln_g"], params["final_ln_b"])
    return x @ params["lm_head"]


def prefill(
    params: DecoderParams,
    tokens: jax.Array,
    lengths: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Prefill forward: logits [B, S, V] plus every layer's K/V
    ([L, B, S, H, D] each) for the engine to write into the cache."""
    b, s = tokens.shape
    with jax.named_scope("embed"):
        x = _embed(params, tokens, jnp.arange(s)[None, :])
    ks, vs = [], []
    for li, layer in enumerate(params["layers"]):
        with jax.named_scope(f"layer{li}"):
            with jax.named_scope("attention"):
                h = _ln(x, layer["ln1_g"], layer["ln1_b"])
                q = jnp.einsum("bse,ehd->bshd", h, layer["wq"])
                k = jnp.einsum("bse,ehd->bshd", h, layer["wk"])
                v = jnp.einsum("bse,ehd->bshd", h, layer["wv"])
                ks.append(k)
                vs.append(v)
                ctx = masked_attention(q, k, v, lengths, causal=True)
                x = x + jnp.einsum("bshd,hde->bse", ctx, layer["wo"])
            with jax.named_scope("mlp"):
                x = _ffn(layer, x)
    with jax.named_scope("head"):
        x = _ln(x, params["final_ln_g"], params["final_ln_b"])
        return x @ params["lm_head"], jnp.stack(ks), jnp.stack(vs)


def write_rows(cache, layer: int, block, offset, rows):
    """Write a step's rows ([n, H, D]) of static ``layer`` at
    ``(block[n], offset[n])`` with ONE scatter on the whole [L,
    num_blocks, block_size, R, LW] operand (a position's H x D values
    stored row-major as R x LW: generation/cache.py). With the operand
    donated the scatter runs in place: the step touches n rows, not a
    layer. Taking ``cache[layer]`` out, or writing a rebuilt layer back,
    would make every step copy layer-sized values (ISSUE 24)."""
    rows = rows.reshape(rows.shape[0], *cache.shape[3:]).astype(cache.dtype)
    return cache.at[layer, block, offset].set(rows)


def decode_step(
    params: DecoderParams,
    tokens: jax.Array,
    positions: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    block_tables: jax.Array,
    context_lens: jax.Array,
    backend: str = "cpu",
    mesh=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step for every batch slot.

    tokens/positions: [B] int32 (the token being decoded and its cache
    position); cache_k/cache_v: [L, num_blocks, block_size, R, LW] (the
    stored form of [..., H, D]); block_tables: [B, max_blocks];
    context_lens: [B] — valid cache positions INCLUDING this token
    (``positions + 1`` for live slots, 0 for inactive ones, whose writes
    land in scratch block 0).
    Returns (logits [B, V], cache_k, cache_v) with the K/V written:
    the two arrays pass through whole (rows scattered in place when the
    caller donates them, the kernel reading blocks of the same arrays).
    """
    bs = cache_k.shape[2]
    with jax.named_scope("embed"):
        x = _embed(params, tokens, positions)  # [B, E]
        block, offset = jax.vmap(lambda bt, p: slot_mapping(bt, p, bs))(block_tables, positions)
    for li, layer in enumerate(params["layers"]):
        # scope names land in the instructions' op_name: a device trace
        # can be grouped by them (layer<i>/attention | cache_write | mlp)
        with jax.named_scope(f"layer{li}"):
            with jax.named_scope("attention"):
                h = _ln(x, layer["ln1_g"], layer["ln1_b"])
                q = jnp.einsum("be,ehd->bhd", h, layer["wq"])
                k = jnp.einsum("be,ehd->bhd", h, layer["wk"])
                v = jnp.einsum("be,ehd->bhd", h, layer["wv"])
            # write this token's K/V, then attend over the updated cache
            # so the token sees itself (context_lens includes it)
            with jax.named_scope("cache_write"):
                cache_k = write_rows(cache_k, li, block, offset, k)
                cache_v = write_rows(cache_v, li, block, offset, v)
            with jax.named_scope("attention"):
                ctx = decode_attention_core(
                    q, cache_k, cache_v, li, block_tables, context_lens,
                    backend=backend, mesh=mesh,
                )
                x = x + jnp.einsum("bhd,hde->be", ctx, layer["wo"])
            with jax.named_scope("mlp"):
                x = _ffn(layer, x)
    with jax.named_scope("head"):
        x = _ln(x, params["final_ln_g"], params["final_ln_b"])
        return x @ params["lm_head"], cache_k, cache_v


def verify_step(
    params: DecoderParams,
    tokens: jax.Array,
    positions: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    block_tables: jax.Array,
    backend: str = "cpu",
    mesh=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One chunked-append (speculative verification) step for every
    batch slot.

    tokens/positions: [B, W] int32 — the window being scored (the last
    committed token followed by up to W-1 drafted tokens) and each
    window token's cache position. ``positions < 0`` marks padding
    window slots (fixed-shape windows with fewer real drafts): their
    K/V scatter to scratch block 0 and their attention/logits rows are
    meaningless (the caller's acceptance logic never reads them).
    cache_k/cache_v: [L, num_blocks, block_size, R, LW]; block_tables:
    [B, max_blocks]. Returns (logits [B, W, V], cache_k, cache_v) with
    all W tokens' K/V written — accepted positions hold exactly the K/V
    sequential decode would have written (a window token's K/V depends
    only on its prefix, which is valid up to the first rejection);
    rejected/later positions hold garbage that the next window
    overwrites before any masked read can see it.
    """
    bs = cache_k.shape[2]
    with jax.named_scope("embed"):
        safe_pos = jnp.maximum(positions, 0)
        x = _embed(params, tokens, safe_pos)  # [B, W, E]
        block, offset = jax.vmap(lambda bt, p: slot_mapping(bt, p, bs))(block_tables, safe_pos)
        # padding -> scratch block 0, offset 0
        block = jnp.where(positions >= 0, block, 0).reshape(-1)
        offset = jnp.where(positions >= 0, offset, 0).reshape(-1)
    for li, layer in enumerate(params["layers"]):
        with jax.named_scope(f"layer{li}"):
            with jax.named_scope("attention"):
                h = _ln(x, layer["ln1_g"], layer["ln1_b"])
                q = jnp.einsum("bwe,ehd->bwhd", h, layer["wq"])
                k = jnp.einsum("bwe,ehd->bwhd", h, layer["wk"])
                v = jnp.einsum("bwe,ehd->bwhd", h, layer["wv"])
            # write the whole window's K/V, then attend over the updated
            # cache with per-query position masks (each token sees itself
            # and everything before it, nothing after)
            with jax.named_scope("cache_write"):
                cache_k = write_rows(cache_k, li, block, offset, k.reshape(-1, *k.shape[2:]))
                cache_v = write_rows(cache_v, li, block, offset, v.reshape(-1, *v.shape[2:]))
            with jax.named_scope("attention"):
                ctx = append_attention_core(
                    q, cache_k, cache_v, li, block_tables, positions,
                    backend=backend, mesh=mesh,
                )
                x = x + jnp.einsum("bwhd,hde->bwe", ctx, layer["wo"])
            with jax.named_scope("mlp"):
                x = _ffn(layer, x)
    with jax.named_scope("head"):
        x = _ln(x, params["final_ln_g"], params["final_ln_b"])
        return x @ params["lm_head"], cache_k, cache_v
