"""Pipeline parallelism: GPipe-style microbatch pipelining over a
``pipe`` mesh axis.

Reference status (SURVEY §2.2): OP_PIPELINE is an enum placeholder with
NO implementation (ffconst.h:160; only stray references in
ffconst_utils.cc:171 and substitution.cc:1448) — the reference's
"pipeline" is just inter-op device placement from the DP search's graph
splits. This module is the real thing, TPU-native:

  * stage parameters carry a leading [S] stage axis sharded over "pipe";
  * inside shard_map every device applies its own stage to its current
    microbatch each tick, then the activations rotate one hop along the
    pipe axis with lax.ppermute (a neighbor transfer on the ICI torus);
  * a lax.scan over M + S - 1 ticks runs the classic GPipe schedule
    (fill, steady state, drain; bubble fraction (S-1)/(M+S-1));
  * reverse-mode AD through scan + ppermute yields the backward
    pipeline automatically (ppermute's transpose is the reverse hop).

Works for homogeneous stage stacks (each stage runs the same program
with its own weights) — the transformer-block case; heterogeneous
prologue/epilogue (embeddings, heads) run outside the pipelined region
under the usual dp/tp shardings.

Tensor parallelism composes INSIDE stages (dp x pp x tp, 3-D
parallelism): pipeline_strategy(tp=...) shards stage weights on "model"
per the Megatron layout and ops psum row-parallel partials themselves
(LowerCtx.weight_sharded_dim) — GSPMD cannot see through shard_map.

The rotating boundary is a PYTREE carry: one or more activation streams
flowing block to block (two-stream boundaries), plus per-microbatch
"shared" tensors that every block reads but passes through unchanged (a
fixed encoder output feeding cross-attention) — each microbatch's shared
context rotates along with its activations so it is present at whatever
stage currently holds that microbatch. boundary_structure() classifies a
PCG's repeat boundary into rotating streams and shared values. Blocks
must still be stateless (batchnorm state stays outside the stack; MoE
aux losses ARE supported via with_aux).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .mesh import PIPE_AXIS


def shard_stage_params(mesh: Mesh, stacked_params):
    """Place stacked stage params [S, ...] with the stage axis on "pipe"
    (per-leaf rank-aware; biases and matrices differ in rank)."""
    return jax.tree.map(
        lambda p: jax.device_put(
            p, NamedSharding(mesh, PartitionSpec(PIPE_AXIS, *([None] * (p.ndim - 1))))
        ),
        stacked_params,
    )


def gpipe(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    n_microbatches: int,
    mesh: Mesh,
    axis: str = PIPE_AXIS,
    with_aux: bool = False,
    param_specs: Any = None,
    carry_specs: Any = None,
    shared_specs: Any = None,
) -> Callable[[Any, jax.Array], jax.Array]:
    """Build a pipelined apply: (stacked_params, x[, shared]) -> y.

    stage_fn(params_for_one_stage, carry) -> carry, where carry is any
    pytree of arrays with the same structure and shapes in and out (a
    single hidden-state array for residual-block stacks; a tuple for
    two-stream boundaries).
    stacked_params: pytree whose leaves have a leading stage axis [S, ...]
    sharded over ``axis``. x: pytree of [B, ...] leaves with B divisible
    by n_microbatches.

    ``shared``: optional pytree of per-microbatch tensors every block
    READS but never writes (a fixed encoder output for cross-attention).
    They rotate along the pipe with their microbatch — so the stage
    currently holding microbatch m sees m's shared context — but are
    never banked or psum-broadcast at the exit, and stage_fn receives
    them as a third argument: stage_fn(params, carry, shared) -> carry.

    ``carry_specs``/``shared_specs``: optional per-leaf PartitionSpecs
    for the MICROBATCHED layout [M, mb, ...] — pp x cp composition
    shards the carry's sequence dim on "seq" (P(None, data, "seq",
    None)) so each stage runs ring attention over its sequence shard.
    Default: batch dim on "data", everything else replicated.

    with_aux=True: stage_fn returns (activation, aux_scalar) and the
    pipelined apply returns (y, aux) where aux sums each stage's scalar
    over its VALID (stage, microbatch) ticks — fill/drain garbage ticks
    are masked out — averaged over microbatches and the data axis, so
    MoE load-balance losses (aggregate.cc lambda_bal) survive inside the
    pipelined stack instead of being rejected.

    The returned function must be called under jit with ``mesh`` active
    (shard_map handles the collectives).
    """
    n_stages = mesh.shape[axis]

    def pipelined(stacked_params, x, shared=None):
        has_shared = shared is not None and len(jax.tree.leaves(shared)) > 0
        if not has_shared:
            shared = ()
        leaves = jax.tree.leaves(x) + jax.tree.leaves(shared)
        b = leaves[0].shape[0]
        assert all(l.shape[0] == b for l in leaves), [l.shape for l in leaves]
        assert b % n_microbatches == 0, (b, n_microbatches)
        mb = b // n_microbatches

        def to_mb(tree):
            # [M, mb, ...] microbatch schedule, per leaf. Split B with mb
            # MAJOR, then transpose: a batch dim sharded on "data"
            # propagates onto the mb dim through this reshape (contiguous
            # shards stay aligned) and the transpose carries it to dim 1
            # for free. The M-major split instead lands the sharding on
            # the microbatch-INDEX dim, and moving it off again at the
            # xs_spec constraint costs an involuntary full
            # rematerialization under pp x cp (XLA spmd_partitioner
            # warning; ADVICE/VERDICT r4). unmb below is its exact
            # inverse, so per-sample outputs stay aligned with inputs.
            return jax.tree.map(
                lambda a: a.reshape((mb, n_microbatches) + a.shape[1:]).swapaxes(0, 1),
                tree,
            )

        xs, ss = to_mb(x), to_mb(shared)

        # shard specs for the microbatched layout, needed both by the
        # shard_map boundary and by the per-leaf variance setup inside
        from .mesh import DATA_AXIS

        data = DATA_AXIS if DATA_AXIS in mesh.axis_names and mesh.shape[DATA_AXIS] > 1 else None
        mb_spec = lambda t: jax.tree.map(lambda _: PartitionSpec(None, data), t)
        xs_spec = carry_specs if carry_specs is not None else mb_spec(xs)
        ss_spec = shared_specs if shared_specs is not None else mb_spec(ss)

        def _spec_axes(spec):
            out = ()
            for entry in spec:
                for a in (entry if isinstance(entry, tuple) else (entry,)):
                    if a and a != axis and a not in out:
                        out = out + (a,)
            return out

        all_axes = ()
        for _sp in jax.tree.leaves(
            (xs_spec, ss_spec), is_leaf=lambda s: isinstance(s, PartitionSpec)
        ):
            for _a in _spec_axes(_sp):
                if _a not in all_axes:
                    all_axes = all_axes + (_a,)

        def per_device(params, xs_local, ss_local):
            # params: this stage's slice, leading axis of size 1
            params = jax.tree.map(lambda p: p[0], params)
            stage = jax.lax.axis_index(axis)
            ticks = n_microbatches + n_stages - 1
            # local microbatch shapes (the batch dim may be data-sharded)
            zeros_mb = lambda t: jax.tree.map(lambda a: jnp.zeros(a.shape[1:], a.dtype), t)
            act0, shr0 = zeros_mb(xs_local), zeros_mb(ss_local)
            # only the ROTATING streams get an output bank: shared
            # tensors are read-only context the caller already holds —
            # banking them would buy an [M, mb, ...] buffer + an
            # all-stage psum per shared leaf for values we then discard.
            # FRESH zeros (not zeros_like) so the bank starts invarying
            # and the pcast below can set its full variance explicitly.
            outs0 = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), xs_local)
            # rank-1, not scalar: a 0-d aux residual crossing the
            # shard_map fwd/bwd partial-eval split trips _check_names
            # (residual out_names {0: axes} is invalid for ndim-0), which
            # surfaced as a _SpecError under jax.grad of pipelined MoE
            aux0 = jnp.zeros((1,), jnp.float32)
            # shard_map tracks varying manual axes: each carry leaf must
            # enter the scan with the variance it will have after a tick
            # — {pipe} ∪ the axes ITS spec shards over (data for the
            # batch dim; seq in pp x cp). The banked outs pick up the
            # same per-leaf axes (they hold copies of the rotating
            # values) plus pipe.
            vary_leaf = lambda a, sp: jax.lax.pcast(
                a, (axis,) + _spec_axes(sp), to="varying"
            )
            act0 = jax.tree.map(vary_leaf, act0, xs_spec)
            shr0 = jax.tree.map(vary_leaf, shr0, ss_spec)
            outs0 = jax.tree.map(vary_leaf, outs0, xs_spec)
            aux0 = jax.lax.pcast(aux0, (axis,) + all_axes, to="varying")

            def tick(carry, t):
                act, shr, outs, aux_acc = carry
                # stage 0 injects microbatch t; others use the arriving act
                inject = jnp.where(t < n_microbatches, t, 0)
                fresh_of = lambda tree: jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, inject, keepdims=False),
                    tree,
                )
                pick = lambda fresh, arriving: jax.tree.map(
                    lambda f, a: jnp.where(stage == 0, f, a), fresh, arriving
                )
                inp = pick(fresh_of(xs_local), act)
                sinp = pick(fresh_of(ss_local), shr)
                args = (params, inp, sinp) if has_shared else (params, inp)
                if with_aux:
                    out, aux_t = stage_fn(*args)
                    # this stage holds microbatch t - stage; real ones only
                    mb = t - stage
                    live = jnp.logical_and(mb >= 0, mb < n_microbatches)
                    aux_acc = aux_acc + jnp.where(live, aux_t.astype(jnp.float32), 0.0)
                else:
                    out = stage_fn(*args)
                # last stage banks microbatch t - (S-1)
                done_idx = t - (n_stages - 1)
                is_last = stage == n_stages - 1
                valid = jnp.logical_and(is_last, done_idx >= 0)

                def bank(bank_arr, o):
                    updated = jax.lax.dynamic_update_index_in_dim(
                        bank_arr, o.astype(bank_arr.dtype), jnp.maximum(done_idx, 0), 0
                    )
                    return jnp.where(valid, updated, bank_arr)

                outs = jax.tree.map(bank, outs, out)
                # rotate the carry (and each microbatch's shared context)
                # one hop down the pipe
                perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
                rot = lambda t_: jax.tree.map(
                    lambda o: jax.lax.ppermute(o, axis, perm), t_
                )
                return (rot(out), rot(sinp), outs, aux_acc), None

            (act, shr, outs, aux_acc), _ = jax.lax.scan(
                tick, (act0, shr0, outs0, aux0), jnp.arange(ticks)
            )
            # outs is populated only on the last stage; psum broadcasts it
            # (every other stage holds zeros)
            mask = stage == n_stages - 1
            y_out = jax.tree.map(
                lambda o: jax.lax.psum(o * mask.astype(o.dtype), axis), outs
            )
            if not with_aux:
                return y_out
            # sum stages (each stage = distinct blocks), average over
            # microbatches; the mean over every carry-sharded axis (data,
            # and seq under pp x cp) matches how a non-pipelined GSPMD
            # run reduces a sharded-batch aux loss — and leaves the
            # scalar invariant, as the PartitionSpec() out_spec requires
            aux = jax.lax.psum(aux_acc, axis) / n_microbatches
            for a in all_axes:
                aux = jax.lax.pmean(aux, a)
            return y_out, aux

        # param_specs carries tp-sharded stacked specs (dp x pp x tp);
        # default: stage axis only
        specs_params = (
            param_specs
            if param_specs is not None
            else jax.tree.map(lambda _: PartitionSpec(axis), stacked_params)
        )
        out_specs = (xs_spec, PartitionSpec()) if with_aux else xs_spec
        result = jax.shard_map(
            per_device,
            mesh=mesh,
            in_specs=(specs_params, xs_spec, ss_spec),
            out_specs=out_specs,
        )(stacked_params, xs, ss)
        unmb = lambda t: jax.tree.map(
            lambda a: a.swapaxes(0, 1).reshape((b,) + a.shape[2:]), t
        )
        if with_aux:
            y, aux = result
            return unmb(y), aux.reshape(())  # callers see the scalar aux
        return unmb(result)

    return pipelined


# ---------------------------------------------------------------------------
# stage discovery: find the repeated block structure of a PCG
# ---------------------------------------------------------------------------


def _node_signatures(graph, order):
    """Cheap per-position prefilter signature: (op_type, params, in-edge
    (dst_idx, src_idx) shape). Edge wiring is checked exactly by
    _blocks_equal — the signature alone would either break on shared
    externals (a fixed encoder output read by every block sits at a
    different relative offset from each) or over-match."""
    sigs = []
    for n in order:
        edges = tuple(sorted((e.dst_idx, e.src_idx) for e in graph.in_edges(n)))
        sigs.append((n.op_type, n.params, edges))
    return sigs


def _blocks_equal(graph, order, pos, a1, a2, p):
    """Are order[a1:a1+p] and order[a2:a2+p] isomorphic blocks? Each
    in-edge pair must be INTERNAL with the same relative producer offset,
    or EXTERNAL in both blocks (producer before the block start) — the
    entry value of block 0 may sit far away in topo order (the tgt input
    behind a whole encoder) while later blocks read their predecessor.
    Which external wiring shapes are actually pipelinable is validated
    downstream by boundary_structure's rotating/shared contract."""
    for off in range(p):
        x, y = order[a1 + off], order[a2 + off]
        ex = sorted(graph.in_edges(x), key=lambda e: (e.dst_idx, e.src_idx))
        ey = sorted(graph.in_edges(y), key=lambda e: (e.dst_idx, e.src_idx))
        for e1, e2 in zip(ex, ey):
            int1 = pos[e1.src] >= a1
            int2 = pos[e2.src] >= a2
            if int1 != int2:
                return False
            if int1 and (a1 + off) - pos[e1.src] != (a2 + off) - pos[e2.src]:
                return False
    return True


def detect_repeats(graph):
    """Split the PCG into (pre, repeats, post) where ``repeats`` is the
    maximal run of structurally-isomorphic contiguous blocks (a
    transformer's encoder stack, or a decoder stack whose blocks all read
    one shared encoder output). Block isomorphism is what lets the
    executor stack per-block params [S, r, ...] and run them as ONE SPMD
    stage program under the GPipe schedule.

    Returns (pre: List[Node], repeats: List[List[Node]], post: List[Node]);
    repeats == [] when no periodic region of >= 2 blocks exists.
    """
    order = list(graph.topo_order())
    pos = {n.guid: i for i, n in enumerate(order)}
    sigs = _node_signatures(graph, order)
    n = len(order)
    # maximize covered nodes; tie-break earliest start, then SMALLEST
    # period (k repeats of one block beat k/2 repeats of a double block:
    # more repeats = more stage-count flexibility)
    best = None  # (coverage, -a, -p, a, p, k)
    for a in range(n - 1):
        if best is not None and best[0] >= n - a:
            break
        for p in range(1, (n - a) // 2 + 1):
            if sigs[a : a + p] != sigs[a + p : a + 2 * p]:
                continue  # prefilter
            if not _blocks_equal(graph, order, pos, a, a + p, p):
                continue
            k = 2
            while (
                a + (k + 1) * p <= n
                and sigs[a + k * p : a + (k + 1) * p] == sigs[a : a + p]
                and _blocks_equal(graph, order, pos, a, a + k * p, p)
            ):
                k += 1
            cand = (k * p, -a, -p, a, p, k)
            if best is None or cand > best:
                best = cand
    if best is None:
        return order, [], []
    _, _, _, a, p, k = best
    repeats = [order[a + j * p : a + (j + 1) * p] for j in range(k)]
    return order[:a], repeats, order[a + k * p :]


def boundary_values(graph, repeats):
    """Single-stream view of the boundary: ((in_guid, in_idx),
    (out_guid, out_idx)). Thin wrapper over boundary_structure — raises
    ValueError when the region needs the full tuple carry (several
    rotating streams or shared values), so single-stream callers keep
    their historical contract without a second validator to maintain."""
    rotating_in, shared, streams = boundary_structure(graph, repeats)
    if shared or len(rotating_in) != 1:
        raise ValueError(
            f"pipeline boundary carries {len(rotating_in)} rotating streams "
            f"+ {len(shared)} shared values (single-stream caller needs "
            "exactly 1 + 0); use boundary_structure for the tuple carry"
        )
    p, i = streams[0]
    return rotating_in[0], (repeats[-1][p].guid, i)


def boundary_structure(graph, repeats):
    """Classify the pipelined region's boundary for a TUPLE carry.

    Every external input slot of a repeat — identified structurally as
    (consumer's template position, dst_idx) — must be one of:
      * SHARED: every repeat reads the SAME (guid, idx) produced outside
        the region (a fixed encoder output feeding cross-attention);
      * ROTATING: repeat j reads what repeat j-1 produced at a fixed
        template-local position (the activation streams; one for
        residual stacks, several for two-stream boundaries).

    Returns (rotating_in, shared, out_streams):
      rotating_in: [(src_guid, src_idx)] values entering repeat 0 from
        the pre-region, one per distinct rotating stream, canonical order;
      shared: [(src_guid, src_idx)] produced outside the region;
      out_streams: [(template_pos, out_idx)] aligned with rotating_in —
        where each stream leaves a block, template-locally.
    Raises ValueError for boundary shapes outside this contract (e.g. a
    skip connection reaching across two blocks).
    """
    region_guids = {n.guid for rep in repeats for n in rep}
    # per-repeat guid sets and guid->position maps, hoisted once — the
    # slot/stream/escape checks below all index into them
    rep_guids = [{n.guid for n in rep} for rep in repeats]
    rep_pos = [{n.guid: i for i, n in enumerate(rep)} for rep in repeats]

    def slots(j):
        guids, pos, out = rep_guids[j], rep_pos[j], {}
        for node in repeats[j]:
            for e in graph.in_edges(node):
                if e.src not in guids:
                    out[(pos[node.guid], e.dst_idx)] = (e.src, e.src_idx)
        return out

    per_rep = [slots(j) for j in range(len(repeats))]
    slot_keys = sorted(per_rep[0])
    for j, s in enumerate(per_rep[1:], 1):
        if sorted(s) != slot_keys:
            raise ValueError(
                f"repeat {j} external-input slots {sorted(s)} differ from "
                f"template slots {slot_keys}"
            )

    shared: List[Tuple[int, int]] = []
    # stream key (template_pos, out_idx) -> entry value for repeat 0
    stream_entry: Dict[Tuple[int, int], Tuple[int, int]] = {}
    stream_order: List[Tuple[int, int]] = []
    for key in slot_keys:
        vals = [s[key] for s in per_rep]
        if all(v == vals[0] for v in vals) and vals[0][0] not in region_guids:
            if vals[0] not in shared:
                shared.append(vals[0])
            continue
        # rotating: repeat j's producer must sit in repeat j-1 at one
        # fixed template position
        stream = None
        for j in range(1, len(repeats)):
            src, idx = per_rep[j][key]
            prev_pos = rep_pos[j - 1]
            if src not in prev_pos:
                raise ValueError(
                    f"slot {key}: repeat {j} reads {(src, idx)} which is neither "
                    "shared nor produced by the previous repeat"
                )
            this = (prev_pos[src], idx)
            if stream is None:
                stream = this
            elif stream != this:
                raise ValueError(
                    f"slot {key}: producer position varies across repeats "
                    f"({stream} vs {this})"
                )
        entry = per_rep[0][key]
        if entry[0] in region_guids:
            raise ValueError(f"slot {key}: repeat 0 reads from inside the region")
        if stream in stream_entry:
            if stream_entry[stream] != entry:
                raise ValueError(
                    f"stream {stream}: inconsistent entry values "
                    f"({stream_entry[stream]} vs {entry})"
                )
        else:
            stream_entry[stream] = entry
            stream_order.append(stream)

    if not stream_order:
        raise ValueError("pipelined region has no rotating stream")
    rotating_in = [stream_entry[s] for s in stream_order]
    # the executor seeds the template's inputs by entry (guid, idx): the
    # keys must be pairwise distinct or two carry positions would collide
    # on one key and blocks would silently read the wrong tensor (e.g. a
    # decoder whose initial hidden state IS the shared encoder output)
    all_keys = rotating_in + shared
    if len(set(all_keys)) != len(all_keys):
        raise ValueError(
            f"boundary entry values collide (rotating {rotating_in}, "
            f"shared {shared}): inexpressible as a tuple carry"
        )

    # region outputs: whatever escapes the LAST repeat must be a rotating
    # stream position (those are banked by the schedule); the sink case
    # (no escapes) exposes all streams. A MIDDLE repeat's value escaping
    # the region (deep supervision off an intermediate block) is
    # unrecoverable — the schedule banks only the final carry — and must
    # fail HERE with ValueError so the search falls back to dp/tp, not
    # later with a KeyError in the executor.
    last_pos = rep_pos[-1]
    streams = set(stream_order)
    for j, rep in enumerate(repeats):
        is_last = j == len(repeats) - 1
        ok_dsts = rep_guids[j] if is_last else rep_guids[j] | rep_guids[j + 1]
        for node in rep:
            for e in graph.out_edges(node):
                if e.dst in ok_dsts:
                    continue
                if is_last:
                    if (last_pos[node.guid], e.src_idx) not in streams:
                        raise ValueError(
                            f"last repeat exposes {(node.guid, e.src_idx)} at "
                            f"position {(last_pos[node.guid], e.src_idx)}, "
                            "which is not a rotating stream"
                        )
                else:
                    raise ValueError(
                        f"repeat {j} value {(node.guid, e.src_idx)} escapes the "
                        "pipelined region mid-stack (only the final carry is "
                        "banked)"
                    )
    return rotating_in, shared, stream_order


def balanced_stages(costs, n_stages: int):
    """Split op costs into contiguous stages minimizing the max stage cost
    (the placement half of pipeline parallelism; reference analog: the DP
    search's sequential graph splits, graph.cc:206-231). Returns stage
    boundary indices: ops [b[i], b[i+1]) form stage i."""
    n = len(costs)
    if n_stages <= 1 or n <= n_stages:
        bounds = list(range(n + 1))
        while len(bounds) < n_stages + 1:
            bounds.append(n)
        return bounds[: n_stages + 1]
    prefix = [0.0]
    for c in costs:
        prefix.append(prefix[-1] + c)

    def stage_cost(i, j):
        return prefix[j] - prefix[i]

    # binary search the max stage cost, greedy feasibility
    lo, hi = max(costs), prefix[-1]
    for _ in range(40):
        mid = (lo + hi) / 2
        stages, start = 1, 0
        for i in range(1, n + 1):
            if stage_cost(start, i) > mid:
                stages += 1
                start = i - 1
        if stages <= n_stages:
            hi = mid
        else:
            lo = mid
    # materialize bounds at threshold hi
    bounds = [0]
    start = 0
    for i in range(1, n + 1):
        if stage_cost(start, i) > hi and len(bounds) < n_stages:
            bounds.append(i - 1)
            start = i - 1
    bounds.append(n)
    while len(bounds) < n_stages + 1:
        bounds.insert(-1, bounds[-2])
    return bounds
