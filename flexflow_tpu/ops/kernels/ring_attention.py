"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

Green-field capability (SURVEY §2.2 / §5: the reference has NO sequence
parallelism — only seq_length iteration plumbing, config.h:165-170). Two
TPU-native schemes over the ICI torus:

  * **Ring attention**: Q stays put; K/V chunks rotate around the "seq"
    mesh axis via ``jax.lax.ppermute`` (neighbor hops on the ICI ring),
    merging per-chunk partial attention with the online-softmax rule.
    HBM footprint per chip is O(S/n); comm overlaps compute on the torus.
  * **Ulysses**: all-to-all swaps sequence sharding for head sharding,
    runs full-sequence attention on 1/n of the heads locally, and swaps
    back. One all-to-all each way; good when heads >= mesh axis size.

Both are pure-JAX (differentiable through scan/ppermute); the per-chunk
core uses the same blockwise algebra as the Pallas flash kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _chunk_attend(q, k, v, scale, mask):
    """Blockwise partial attention: returns (m, l, o_unnormalized).

    q: [B, Sq, H, D]; k, v: [B, Sc, H, D]; mask: [Sq, Sc] bool or None.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)  # [B,H,Sq]
    p = jnp.exp(s - m[..., None])
    # fully-masked rows: make their contribution exactly zero
    p = jnp.where(m[..., None] <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1)  # [B,H,Sq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return m, l, o


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Ring attention over an SPMD axis (call inside shard_map).

    q, k, v: local shards [B, S_local, H, D]; every device holds one
    sequence chunk. K/V rotate ``n`` times around the ring.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    sk_local = k.shape[1]  # may differ from s_local for cross-attention
    qf = q.astype(jnp.float32)
    q_pos = my * s_local + jnp.arange(s_local)  # global positions of local q

    perm = [(i, (i + 1) % n) for i in range(n)]

    def attend(m, l, acc, kc, vc, t):
        src_chunk = (my - t) % n  # which global chunk we currently hold
        if causal:
            k_pos = src_chunk * sk_local + jnp.arange(sk_local)
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = None
        mc, lc, oc = _chunk_attend(qf, kc.astype(jnp.float32), vc, scale, mask)
        m_new = jnp.maximum(m, mc)
        # guard -inf - -inf when a row has seen nothing yet
        c_old = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - m_new))
        c_new = jnp.where(mc <= NEG_INF / 2, 0.0, jnp.exp(mc - m_new))
        l_out = l * c_old + lc * c_new
        acc_out = acc * jnp.swapaxes(c_old, 1, 2)[..., None] + oc * jnp.swapaxes(c_new, 1, 2)[..., None]
        return m_new, l_out, acc_out

    def step(carry, t):
        kc, vc, m, l, acc = carry
        m, l, acc = attend(m, l, acc, kc, vc, t)
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return (kc, vc, m, l, acc), None

    # derive the scan inits from q (x*0 keeps the value exact for finite
    # x) so they inherit q's varying manual axes — fresh zeros would be
    # invarying and reject the scan carry under nested shard_map vma
    # tracking (the pp x cp composition runs this inside the pipeline's
    # shard_map)
    zero_bhs = jnp.swapaxes(qf, 1, 2)[..., 0] * 0.0
    m0 = zero_bhs + NEG_INF
    l0 = zero_bhs
    acc0 = qf * 0.0
    # n-1 rotating steps, then attend to the last-held chunk without the
    # final ppermute pair (whose result would be discarded)
    (kc, vc, m, l, acc), _ = jax.lax.scan(step, (k, v, m0, l0, acc0), jnp.arange(n - 1))
    m, l, acc = attend(m, l, acc, kc, vc, n - 1)
    l = jnp.maximum(l, 1e-30)
    out = acc / jnp.swapaxes(l, 1, 2)[..., None]
    return out.astype(q.dtype)


def ring_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    seq_axis: str = "seq",
    batch_axis: Optional[str] = "data",
    causal: bool = False,
    scale: Optional[float] = None,
    head_axis: Optional[str] = None,
) -> jax.Array:
    """shard_map wrapper: [B, S, H, D] globally, S sharded on ``seq_axis``.

    ``head_axis``: keep the head dim sharded through the kernel (cp x tp
    composition — head-sharded projections from Megatron weights would
    otherwise be all-gathered at this boundary)."""
    ba = batch_axis if batch_axis in mesh.axis_names else None
    ha = head_axis if head_axis in mesh.axis_names else None
    spec = P(ba, seq_axis, ha, None)
    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name=seq_axis, causal=causal, scale=scale),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = False,
    scale: Optional[float] = None,
    attn_fn=None,
) -> jax.Array:
    """Ulysses (all-to-all) sequence parallelism (call inside shard_map).

    Local shards [B, S/n, H, D] -> all_to_all -> [B, S, H/n, D] -> local
    full-sequence attention -> all_to_all back. ``attn_fn(q, k, v)`` runs
    the local attention (defaults to the blockwise core; on TPU the Pallas
    flash kernel slots in).
    """
    n = jax.lax.psum(1, axis_name)

    def seq_to_heads(x):
        # [B, S/n, H, D] -> [B, S, H/n, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    if attn_fn is None:
        from ..attention import reference_attention

        attn_fn = functools.partial(reference_attention, causal=causal, scale=scale)
    out = attn_fn(qh, kh, vh)
    return heads_to_seq(out)


def ulysses_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    seq_axis: str = "seq",
    batch_axis: Optional[str] = "data",
    causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    ba = batch_axis if batch_axis in mesh.axis_names else None
    spec = P(ba, seq_axis, None, None)
    fn = jax.shard_map(
        functools.partial(ulysses_attention, axis_name=seq_axis, causal=causal, scale=scale),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
