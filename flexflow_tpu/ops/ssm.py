"""A state-space layer's recurrence, in its three forms: Mamba-2's first,
and below it Mamba-1's (``selective_*``), which has no heads and a decay
for every (channel, state) pair.

For a head ``h`` of group ``g`` (``H`` heads of width ``P``; ``G`` groups
whose ``B_t``, ``C_t`` [N] the group's ``H / G`` heads share), in float32::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        (S is [P, N])
    y_t = S_t C_t

``dt_t`` > 0 a head's step and ``A`` < 0 its rate; a row with ``dt = 0``
and ``x = 0`` (padding behind a prompt's length, a slot that is not live)
passes the state on unchanged, bit for bit: ``1 x S + 0``. The skip term
``D x_t`` and the gate are the layer's (generation/decoder.py).

* :func:`recurrence` — the equations as written, a scan over positions:
  what the other two are tested against.
* :func:`chunk_scan` — a prefill's form (the SSD form): inside a chunk of
  ``Q`` positions a ``[Q, Q]`` matrix of decays times ``C B^T`` applied to
  the chunk's inputs, between chunks the state carried by a scan over the
  chunks. Its products are einsums the MXU takes as they stand, at
  ``HIGHEST`` precision: the recurrence is float32, and a single bfloat16
  pass would round the decays and the state it hands over.
* :func:`update` — a decode step's form: one position for every slot
  against the slots' stored state, read once, written once in place, ``y``
  out of the same pass: on a TPU the Pallas call ``ssm_state_update``
  (kernels/ssm_update.py), elsewhere the XLA composition
  :func:`update_reference` of the same arithmetic.

**Stored state.** A layer's state is ``[slots, H / pack, N, pack x P]``
float32 (:func:`state_shape`): ``pack = 128 // P`` heads of one group side
by side on the 128 lanes, ``N`` on the sublanes. Everything a step brings
for a (head, p) pair — ``exp(dt A)``, ``dt x``, and what it takes away,
``y`` — is then a lane-dense row in its natural ``[slots, H, P]`` layout,
and ``B`` / ``C`` are broadcast over the lanes once a group. :func:`pack_state`
/ :func:`unpack_state` turn ``[..., H, P, N]`` into it and back.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..device import on_tpu
from .kernels.ssm_update import selective_state_update, ssm_state_update

_HIGHEST = jax.lax.Precision.HIGHEST
LANES = 128


def heads_packed(heads: int, head_dim: int, groups: int) -> int:
    """Heads that share a stored row of lanes: as many heads of one
    group as fill 128 lanes, or 1 where they do not divide."""
    pack = max(1, min(LANES // head_dim, heads // groups))
    return pack if (heads // groups) % pack == 0 else 1


def state_shape(heads: int, head_dim: int, groups: int, state: int) -> Tuple[int, int, int]:
    """One sequence's stored state of one layer (module docstring)."""
    pack = heads_packed(heads, head_dim, groups)
    return heads // pack, state, pack * head_dim


def pack_state(s, groups: int):
    """``[..., H, P, N]`` -> the stored ``[..., H / pack, N, pack x P]``."""
    *lead, h, p, n = s.shape
    pack = heads_packed(h, p, groups)
    s = s.reshape(*lead, h // pack, pack, p, n)
    return jnp.moveaxis(s, -1, -3).reshape(*lead, h // pack, n, pack * p)


def unpack_state(s, head_dim: int):
    """The stored form back to ``[..., H, P, N]``."""
    *lead, hp, n, lanes = s.shape
    pack = lanes // head_dim
    s = s.reshape(*lead, hp, n, pack, head_dim)
    return jnp.moveaxis(s, -3, -1).reshape(*lead, hp * pack, head_dim, n)


def recurrence(x, dt, a, b, c, state=None):
    """The equations, position by position. ``x`` [B, S, H, P], ``dt``
    [B, S, H] float32, ``a`` [H] float32 (negative), ``b`` / ``c`` [B, S,
    G, N]; ``state`` [B, H, P, N] float32 (None: zeros). Returns ``y``
    [B, S, H, P] float32 and the state after the last position."""
    bsz, _, h, p = x.shape
    g, n = b.shape[2:]
    per = h // g
    s0 = jnp.zeros((bsz, h, p, n), jnp.float32) if state is None else state

    def step(s, row):
        xt, dtt, bt, ct = row  # [B, H, P], [B, H], [B, G, N] x 2
        bh, ch = jnp.repeat(bt, per, axis=1), jnp.repeat(ct, per, axis=1)  # [B, H, N]
        s = jnp.exp(dtt * a)[..., None, None] * s + (dtt[..., None] * xt)[..., None] * bh[:, :, None, :]
        return s, jnp.sum(s * ch[:, :, None, :], axis=-1)

    rows = tuple(jnp.moveaxis(v.astype(jnp.float32), 1, 0) for v in (x, dt, b, c))
    final, ys = jax.lax.scan(step, s0, rows)
    return jnp.moveaxis(ys, 0, 1), final


def chunk_scan(x, dt, a, b, c, chunk: int):
    """A prefill's form, from a zero state: the operands and results of
    :func:`recurrence`, the sequence cut into chunks of ``chunk``
    positions (padded behind with rows of ``dt = 0``, which pass the
    state on)."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    per = h // g
    pad = -s % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)) for v in (x, dt, b, c))
    nc = (s + pad) // chunk
    f32 = jnp.float32
    dt = dt.astype(f32)
    xd = (x.astype(f32) * dt[..., None]).reshape(bsz, nc, chunk, g, per, p)  # dt_s x_s
    bq, cq = (v.astype(f32).reshape(bsz, nc, chunk, g, n) for v in (b, c))
    cum = jnp.cumsum((dt * a).reshape(bsz, nc, chunk, g, per), axis=2)  # [B, nc, Q, G, per]: log of the decay from the chunk's start
    # inside a chunk: y_q += sum_{s <= q} exp(cum_q - cum_s) (C_q . B_s) dt_s x_s
    cb = jnp.einsum("bcqgn,bcsgn->bcgqs", cq, bq, precision=_HIGHEST)
    seen = jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :]
    diff = cum[:, :, :, None] - cum[:, :, None, :]  # [B, nc, Q, S, G, per]
    decay = jnp.exp(jnp.where(seen[None, None, :, :, None, None], diff, -jnp.inf))
    m = cb[..., None].transpose(0, 1, 3, 4, 2, 5) * decay  # [B, nc, Q, S, G, per]
    y = jnp.einsum("bcqsgk,bcsgkp->bcqgkp", m, xd, precision=_HIGHEST)
    # a chunk's own contribution to the state at its end, and its whole decay
    to_end = jnp.exp(cum[:, :, -1:] - cum)  # [B, nc, Q, G, per]
    own = jnp.einsum("bcsgkp,bcsgn->bcgkpn", xd * to_end[..., None], bq, precision=_HIGHEST)
    whole = jnp.exp(cum[:, :, -1])  # [B, nc, G, per]

    def carry(state, part):
        mine, through = part
        return through[..., None, None] * state + mine, state  # (the state BEFORE the chunk goes out)

    final, before = jax.lax.scan(
        carry, jnp.zeros((bsz, g, per, p, n), f32), (jnp.moveaxis(own, 1, 0), jnp.moveaxis(whole, 1, 0))
    )
    # what came before the chunk: y_q += exp(cum_q) C_q . S_before
    y = y + jnp.einsum("bcqgn,cbgkpn->bcqgkp", cq, before, precision=_HIGHEST) * jnp.exp(cum)[..., None]
    return y.reshape(bsz, nc * chunk, h, p)[:, :s], final.reshape(bsz, h, p, n)


def _step_rows(x, dt, a, groups: int):
    """What a step brings for each (head, p) pair, as the stored state's
    lanes hold them: ``exp(dt A)`` and ``dt x`` [slots, H / pack, pack x
    P] float32."""
    slots, h, p = x.shape
    pack = heads_packed(h, p, groups)
    decay = jnp.broadcast_to(jnp.exp(dt * a)[..., None], (slots, h, p))
    return decay.reshape(slots, h // pack, pack * p), (dt[..., None] * x.astype(jnp.float32)).reshape(slots, h // pack, pack * p)


def update_reference(state, layer: int, x, dt, a, b, c):
    """:func:`update` as an XLA composition: the same arithmetic on the
    stored layout, two passes over the state where the kernel makes one."""
    slots, h, p = x.shape
    g = b.shape[1]
    decay, xd = _step_rows(x, dt, a, g)
    rows = decay.shape[1] // g  # stored rows of heads a group
    bt, ct = (jnp.repeat(v.astype(jnp.float32), rows, axis=1)[..., None] for v in (b, c))  # [slots, H / pack, N, 1]
    new = decay[:, :, None, :] * state[layer] + bt * xd[:, :, None, :]
    y = jnp.sum(new * ct, axis=2)
    return y.reshape(slots, h, p), state.at[layer].set(new)


def update(state, layer: int, x, dt, a, b, c, backend: Optional[str] = None, interpret: bool = False):
    """One decode step of static ``layer`` of the WHOLE stored state
    ``[n_layers, slots, H / pack, N, pack x P]`` float32 (never a
    sliced-out layer: the caller donates the array and the update runs
    in place). ``x`` [slots, H, P], ``dt`` [slots, H] float32 (0 in a
    slot that is not live, whose ``x`` is 0 too), ``a`` [H], ``b`` / ``c``
    [slots, G, N]. Returns ``y`` [slots, H, P] float32 and the state."""
    kernel = interpret or (on_tpu() if backend is None else backend == "tpu")
    if kernel and state.shape[-1] == LANES and state.shape[-2] % 8 == 0:
        g = b.shape[1]
        decay, xd = _step_rows(x, dt, a, g)
        y, state = ssm_state_update(state, layer, decay, xd, b.astype(jnp.float32), c.astype(jnp.float32), interpret=interpret)
        return y.reshape(x.shape), state
    return update_reference(state, layer, x, dt, a, b, c)


# ------------------------------------------------------------- Mamba-1
# A Mamba-1 (selective) recurrence has a decay for every (channel, state)
# pair and no heads: for channel ``d`` of ``D`` and state index ``n`` of
# ``N``, in float32::
#
#     S_t[d, n] = exp(dt_t[d] A[d, n]) S_{t-1}[d, n] + dt_t[d] B_t[n] x_t[d]
#     y_t[d]    = sum_n C_t[n] S_t[d, n]
#
# so there is no ``[Q, Q]`` matrix of decays a chunk that an MXU could take
# (:func:`chunk_scan` rests on ONE decay a head): the three forms below are
# the oracle, a scan in chunks and a decode step's update. A row with ``dt =
# 0`` and ``x = 0`` passes the state on bit for bit, as above. The stored
# state is ``[slots, N, D]`` float32, ``N`` on the sublanes and the channels
# on the lanes (:func:`selective_state_shape`).


def selective_state_shape(inner: int, state: int) -> Tuple[int, int]:
    """One sequence's stored state of one Mamba-1 layer: ``[N, D]``."""
    return state, inner


def selective_recurrence(x, dt, a, b, c, state=None):
    """The equations, position by position: ``x`` [B, S, D], ``dt`` [B, S,
    D] float32, ``a`` [D, N] float32 (negative), ``b`` / ``c`` [B, S, N];
    ``state`` [B, D, N] float32 (None: zeros). Returns ``y`` [B, S, D]
    float32 and the state after the last position."""
    s0 = jnp.zeros((x.shape[0], x.shape[2], b.shape[2]), jnp.float32) if state is None else state

    def step(s, row):
        xt, dtt, bt, ct = row  # [B, D], [B, D], [B, N] x 2
        s = jnp.exp(dtt[..., None] * a) * s + (dtt * xt)[..., None] * bt[:, None, :]
        return s, jnp.sum(s * ct[:, None, :], axis=-1)

    rows = tuple(jnp.moveaxis(v.astype(jnp.float32), 1, 0) for v in (x, dt, b, c))
    final, ys = jax.lax.scan(step, s0, rows)
    return jnp.moveaxis(ys, 0, 1), final


def selective_scan(x, dt, a, b, c, chunk: int):
    """A prefill's form, from a zero state: the operands and results of
    :func:`selective_recurrence`, the state kept as it is stored (``[B, N,
    D]``: the channels on the lanes) and carried by a scan over chunks of
    ``chunk`` positions whose inside is unrolled (the sequence padded
    behind with rows of ``dt = 0``, which pass the state on): the same
    float32 arithmetic position by position as the oracle's, a loop
    iteration a chunk and not a position."""
    bsz, s, d = x.shape
    pad = -s % chunk
    rows = [v.astype(jnp.float32) for v in (x, dt, b, c)]
    if pad:
        rows = [jnp.pad(v, ((0, 0), (0, pad), (0, 0))) for v in rows]
    at = a.T  # [N, D]

    def step(state, row):
        xt, dtt, bt, ct = row
        state = jnp.exp(dtt[:, None, :] * at) * state + bt[:, :, None] * (dtt * xt)[:, None, :]
        return state, jnp.sum(state * ct[:, :, None], axis=1)

    final, ys = jax.lax.scan(
        step, jnp.zeros((bsz, b.shape[2], d), jnp.float32), tuple(jnp.moveaxis(v, 1, 0) for v in rows), unroll=chunk
    )
    return jnp.moveaxis(ys, 0, 1)[:, :s], jnp.swapaxes(final, 1, 2)


def selective_update_reference(state, layer: int, x, dt, a, b, c):
    """:func:`selective_update` as an XLA composition on the stored layout."""
    dt = dt.astype(jnp.float32)
    new = jnp.exp(dt[:, None, :] * a.T) * state[layer] + b.astype(jnp.float32)[:, :, None] * (dt * x.astype(jnp.float32))[:, None, :]
    return jnp.sum(new * c.astype(jnp.float32)[:, :, None], axis=1), state.at[layer].set(new)


def selective_update(state, layer: int, x, dt, a, b, c, backend: Optional[str] = None, interpret: bool = False):
    """One decode step of static ``layer`` of the WHOLE stored state ``[n_layers,
    slots, N, D]`` float32 (never a sliced-out layer: the caller donates
    the array and the update runs in place). ``x`` / ``dt`` [slots, D] (``dt``
    float32; both 0 in a slot that is not live), ``a`` [D, N], ``b`` / ``c``
    [slots, N]. Returns ``y`` [slots, D] float32 and the state: on a TPU
    the Pallas call ``selective_state_update`` (the decay formed inside),
    elsewhere the XLA composition of the same arithmetic."""
    kernel = interpret or (on_tpu() if backend is None else backend == "tpu")
    if kernel and state.shape[-1] % LANES == 0 and state.shape[-2] % 8 == 0:
        f32 = jnp.float32
        return selective_state_update(
            state, layer, dt.astype(f32), x.astype(f32), a.T, b.astype(f32), c.astype(f32), interpret=interpret
        )
    return selective_update_reference(state, layer, x, dt, a, b, c)
