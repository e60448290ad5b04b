"""PCG → XLA executor.

This module replaces the reference's entire task-execution machinery:
FFModel::forward/backward/update (src/runtime/model.cc:2423-2501), the
per-op Legion IndexLaunchers (e.g. Linear::forward src/ops/linear.cc:328),
the FFMapper fan-out (src/mapper/mapper.cc:381-485), and the NCCL
gradient-sync tasks (src/runtime/optimizer.cc:261).

TPU-native design: the whole training iteration — forward, loss,
backward (autodiff), gradient all-reduce (GSPMD-inserted psum over the
mesh's data axes), and the optimizer update — is ONE jitted function,
traced once and compiled by XLA. Legion tracing (begin_trace/end_trace)
is subsumed: every iteration replays the compiled executable. Horizontal
fusion (FusedOp, model.cc:2503) is subsumed by XLA fusion.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core.graph import PCGraph, Node
from ..core.types import CompMode, LossType, MetricsType, OpType
from ..obs.capacity import GLOBAL_PROGRAMS
from ..obs.steptrace import phase
from ..obs.truth import GLOBAL_LEDGER
from ..ops.base import LowerCtx, get_op_def
from ..parallel.propagation import infer_all_specs
from ..parallel.strategy import ParallelStrategy, to_partition_spec
from . import faults, initializers, losses, metrics as metrics_mod
from .optimizers import Optimizer


def _node_key(node: Node) -> str:
    return f"{node.op_type.value}_{node.guid}"


# Per-executor program namespace in GLOBAL_PROGRAMS ("executor[N].forward"):
# distinct executors legitimately trace distinct programs, which must not
# read as retraces of one another in /v2/debug/programs.
_EXECUTOR_IDS = itertools.count()


_PIPE_KEY = "__pipe_stages__"


@dataclasses.dataclass
class _PipelinePlan:
    """Executable stage partition derived from strategy.pipeline."""

    pre: List[Node]
    repeats: List[List[Node]]  # stage-major contiguous blocks
    post: List[Node]
    n_stages: int
    n_microbatches: int
    # tuple carry (parallel/pipeline.py boundary_structure): the values
    # entering repeat 0 per rotating stream, the per-microbatch shared
    # values every block reads, and each stream's template-local exit
    rotating_in: List[Tuple[int, int]]  # [(guid, idx)]
    shared: List[Tuple[int, int]]  # [(guid, idx)], produced in pre
    out_streams: List[Tuple[int, int]]  # [(template_pos, out_idx)]
    # global shapes of the carry entries (rotating then shared), for
    # building pp x cp sequence-sharded carry specs
    entry_shapes: List[Tuple[int, ...]] = dataclasses.field(default_factory=list)


def _build_pipeline_plan(graph: PCGraph, strategy) -> Optional[_PipelinePlan]:
    if strategy is None or strategy.pipeline is None or strategy.pipeline.n_stages <= 1:
        return None
    from ..parallel.pipeline import boundary_structure, detect_repeats

    pa = strategy.pipeline
    pre, repeats, post = detect_repeats(graph)
    staged = {g for g in pa.stage_of}
    rep_guids = {n.guid for rep in repeats for n in rep}
    if staged != rep_guids:
        raise ValueError(
            "strategy.pipeline.stage_of does not match the graph's detected "
            f"repeated blocks ({len(staged)} staged vs {len(rep_guids)} detected)"
        )
    if len(repeats) % pa.n_stages != 0:
        raise ValueError(
            f"{len(repeats)} blocks not divisible into {pa.n_stages} stages"
        )
    # verify the assignment is contiguous stage-major (stackable [S, r, ...])
    r = len(repeats) // pa.n_stages
    for j, rep in enumerate(repeats):
        want = j // r
        for node in rep:
            if pa.stage_of.get(node.guid) != want:
                raise ValueError(
                    f"block {j} node {node} assigned stage "
                    f"{pa.stage_of.get(node.guid)}, need contiguous stage {want}"
                )
    rotating_in, shared, out_streams = boundary_structure(graph, repeats)
    # every carry entry is microbatched along dim 0 by the schedule: a
    # batch-less shared tensor (e.g. an (S, E) bias broadcast into every
    # block) would be silently row-sliced per microbatch — reject with
    # the same ValueError contract as the structural checks
    from ..parallel.propagation import infer_all_specs

    specs = infer_all_specs(graph)
    lead = {(g, i): (specs[g][i].shape[:1] or (1,))[0] for g, i in rotating_in + shared}
    if len(set(lead.values())) > 1:
        raise ValueError(
            f"pipeline carry entries disagree on the leading (batch) dim: {lead} "
            "— batch-less shared tensors cannot ride the microbatch schedule"
        )
    entry_shapes = [tuple(specs[g][i].shape) for g, i in rotating_in + shared]
    return _PipelinePlan(
        pre=pre,
        repeats=repeats,
        post=post,
        n_stages=pa.n_stages,
        n_microbatches=pa.n_microbatches,
        rotating_in=rotating_in,
        shared=shared,
        out_streams=out_streams,
        entry_shapes=entry_shapes,
    )


@dataclasses.dataclass
class CompiledExecutor:
    """A compiled training/inference program for one PCG + strategy."""

    graph: PCGraph
    strategy: Optional[ParallelStrategy]
    mesh: Optional[Any]  # jax.sharding.Mesh
    loss_type: Optional[LossType]
    metric_types: Tuple[MetricsType, ...]
    optimizer: Optional[Optimizer]
    outputs: List[Tuple[int, int]]  # (node guid, output idx), order = user's outputs
    backend: str = "tpu"
    comp_mode: CompMode = CompMode.TRAINING
    # iteration-level sequence truncation (reference: FFIterationConfig
    # seq_length, config.h:165-170; forward(seq_length) model.cc:2423).
    # Changing it retraces the step with the new static shapes.
    seq_length: Optional[int] = None

    # activation rematerialization: recompute each repeated block in the
    # backward pass (jax.checkpoint per block) instead of storing its
    # activations — HBM/FLOPs trade (FFConfig.remat_blocks)
    remat_blocks: bool = False
    # ZeRO-1: shard optimizer moments over the data axis (beyond-parity;
    # the reference replicates optimizer state on every device —
    # ParameterSyncType only picks HOW gradients sync, optimizer.cc:261).
    # GSPMD keeps the moments distributed between steps and gathers only
    # inside the update, cutting per-device optimizer memory ~1/dp.
    zero_optimizer: bool = False
    _zero_specs: Any = None
    # gradient accumulation: each train step splits the batch into this
    # many grad microbatches, averages their gradients via a lax.scan
    # (one microbatch's activations live at a time) and applies ONE
    # optimizer update — large effective batches without the activation
    # memory (beyond-parity; no reference analog)
    grad_accum_steps: int = 1
    # which chain the train step's loss runs, decided from the graph in
    # _build_steps: "fused_softmax_ce" (the loss taken from the last
    # softmax's input) or "composed" (the loss of the model's output)
    loss_form: str = "composed"

    params: Any = None
    opt_state: Any = None
    state: Any = None  # non-trainable (batchnorm stats, ...)
    _train_step: Optional[Callable] = None
    _eval_step: Optional[Callable] = None
    _forward: Optional[Callable] = None
    _truth_counts: Any = None  # program -> window calls (truth-ledger sampling)
    _pipeline_plan: Any = None  # _PipelinePlan when the strategy pipelines
    _remat_plan: Any = None  # (pre, repeats, post) when remat_blocks engaged

    # ----------------------------------------------------------- building
    def initialize(self, rng: jax.Array):
        """Materialize params/state (reference: FFModel::init_operators +
        initializer tasks) and build the jitted step functions."""
        import zlib

        self._pipeline_plan = _build_pipeline_plan(self.graph, self.strategy)
        if self.remat_blocks and self._pipeline_plan is None:
            from ..parallel.pipeline import detect_repeats

            pre, repeats, post = detect_repeats(self.graph)
            # GPipe's scan already recomputes per-tick, so remat only
            # applies to the plain interpreter; need >= 2 blocks to win
            self._remat_plan = (pre, repeats, post) if len(repeats) >= 2 else None
        specs = infer_all_specs(self.graph)
        params: Dict[str, Dict[str, jax.Array]] = {}
        state: Dict[str, Dict[str, jax.Array]] = {}
        # deterministic init independent of process-global guids and
        # PYTHONHASHSEED: key on canonical topo index + crc32(weight name)
        canon = {n.guid: i for i, n in enumerate(self.graph.topo_order())}
        for node in self.graph.topo_order():
            op_def = get_op_def(node.op_type)
            in_specs = [specs[e.src][e.src_idx] for e in self.graph.in_edges(node)]
            wspecs = op_def.weight_specs(node.params, in_specs)
            if not wspecs:
                continue
            nkey = _node_key(node)
            for w in wspecs:
                key = jax.random.fold_in(jax.random.fold_in(rng, canon[node.guid]), zlib.crc32(w.name.encode()))
                init = initializers.get_initializer(w.initializer)
                arr = init(key, w.spec)
                arr = self._place_weight(node.guid, w.name, arr)
                if w.trainable:
                    params.setdefault(nkey, {})[w.name] = arr
                else:
                    state.setdefault(nkey, {})[w.name] = arr
        if self._pipeline_plan is not None:
            params = self._stack_pipeline_params(params, state)
        self.params = params
        self.state = state
        if self.optimizer is not None:
            self._zero_specs = self._zero1_spec_tree()
            if self._zero_specs is None:
                self.opt_state = self.optimizer.init_state(params)
            else:
                # allocate the moments DIRECTLY into their data-axis
                # shards (jit + out_shardings): replicate-then-reshard
                # would spike init-time HBM by the full moment size —
                # the very memory ZeRO exists to save
                from jax.sharding import NamedSharding, PartitionSpec

                proto = jax.eval_shape(self.optimizer.init_state, params)
                repl = NamedSharding(self.mesh, PartitionSpec())
                shardings = {
                    k: (
                        jax.tree.map(lambda s: NamedSharding(self.mesh, s), self._zero_specs)
                        if k in ("m", "v") and sub is not None
                        else jax.tree.map(lambda _: repl, sub)
                    )
                    for k, sub in proto.items()
                }
                self.opt_state = jax.jit(
                    self.optimizer.init_state, out_shardings=shardings
                )(params)
        self._build_steps()
        return self

    def _map_moments(self, opt_state, fn):
        """Apply ``fn(leaf, zero_spec)`` over the optimizer moment trees
        ("m"/"v"), leaving scalars and absent moments untouched."""
        for k in ("m", "v"):
            if opt_state.get(k) is not None:
                opt_state[k] = jax.tree.map(fn, opt_state[k], self._zero_specs)
        return opt_state

    def _zero1_spec_tree(self):
        """Per-param-leaf PartitionSpec for ZeRO-1 moment sharding: the
        param's own sharding plus the first unsharded, evenly-divisible
        dim moved onto "data". None when ZeRO is off or there is no
        data-parallel axis to shard over."""
        from ..parallel.mesh import DATA_AXIS

        if (
            not self.zero_optimizer
            or self.mesh is None
            or DATA_AXIS not in self.mesh.axis_names
            or self.mesh.shape[DATA_AXIS] < 2
        ):
            return None
        from jax.sharding import PartitionSpec

        dp = self.mesh.shape[DATA_AXIS]

        def leaf_spec(p):
            base = list(p.sharding.spec) + [None] * (p.ndim - len(p.sharding.spec))
            for i in range(p.ndim):
                if base[i] is None and p.shape[i] % dp == 0:
                    base[i] = DATA_AXIS
                    break
            return PartitionSpec(*base)

        return jax.tree.map(leaf_spec, self.params)

    def _stack_pipeline_params(self, params, state):
        """Restructure repeat-node params into stacked leaves [S, r, ...]
        with the stage axis sharded over "pipe" (+ any tp axes from the
        strategy); records the specs in self._pipe_param_specs so the
        gpipe in_specs use the very same layout."""
        import numpy as np

        plan = self._pipeline_plan
        self._pipe_param_specs: Dict[str, Dict[str, Any]] = {}
        for rep in plan.repeats:
            for node in rep:
                if _node_key(node) in state and state[_node_key(node)]:
                    raise NotImplementedError(
                        f"pipelined op {node} has non-trainable state; "
                        "keep stateful ops (batchnorm) outside the block stack"
                    )
        S, r = plan.n_stages, len(plan.repeats) // plan.n_stages
        stacked: Dict[str, Dict[str, jax.Array]] = {}
        for t, tnode in enumerate(plan.repeats[0]):
            tkey = _node_key(tnode)
            names = params.get(tkey, {})
            if not names:
                continue
            stacked[tkey] = {}
            self._pipe_param_specs[tkey] = {}
            for wname in names:
                rows = [
                    np.asarray(params[_node_key(rep[t])][wname])
                    for rep in plan.repeats
                ]
                arr = jnp.asarray(np.stack(rows).reshape((S, r) + rows[0].shape))
                spec = self._stacked_weight_spec(tnode.guid, wname, arr.ndim)
                self._pipe_param_specs[tkey][wname] = spec
                if self.mesh is not None:
                    from jax.sharding import NamedSharding

                    arr = jax.device_put(arr, NamedSharding(self.mesh, spec))
                stacked[tkey][wname] = arr
        for rep in plan.repeats:
            for node in rep:
                params.pop(_node_key(node), None)
        params[_PIPE_KEY] = stacked
        return params

    def _stacked_weight_spec(self, guid: int, wname: str, ndim: int):
        """PartitionSpec for a stacked pipeline weight [S, r, *w.shape]:
        stage axis on "pipe", plus whatever tp axes the strategy assigned
        to the underlying weight dims (dp x pp x tp composition)."""
        from jax.sharding import PartitionSpec

        from ..parallel.mesh import PIPE_AXIS
        from ..parallel.strategy import to_partition_spec

        wspec = self.strategy.weight_spec(guid, wname) if self.strategy else None
        tail = list(to_partition_spec(wspec)) if wspec else []
        tail += [None] * (ndim - 2 - len(tail))
        return PartitionSpec(PIPE_AXIS, None, *tail)

    def _place_weight(self, guid: int, name: str, arr: jax.Array) -> jax.Array:
        if self.mesh is None:
            return arr
        from jax.sharding import NamedSharding

        spec = self.strategy.weight_spec(guid, name) if self.strategy else None
        return _put_global(arr, NamedSharding(self.mesh, to_partition_spec(spec)), full=True)

    # ----------------------------------------------------------- forward
    def _forward_impl(self, params, state, inputs: Sequence[jax.Array], rng, training: bool,
                      also: Sequence[Tuple[int, int]] = ()):
        """Interpret the PCG in topological order (the reference's
        FFModel::forward op loop, model.cc:2423 — but traced, not
        dispatched per iteration). ``also`` names further values
        (node guid, output idx) to return behind the model's outputs."""
        if self._pipeline_plan is not None:
            return self._forward_pipelined(params, state, inputs, rng, training, also)
        if self._remat_plan is not None and training:
            return self._forward_remat(params, state, inputs, rng, also)
        values: Dict[Tuple[int, int], jax.Array] = {}
        ctx = LowerCtx(
            training=training,
            rng=rng,
            backend=self.backend,
            mesh=self.mesh,
            seq_length=self.seq_length,
        )
        for node in self.graph.topo_order():
            op_def = get_op_def(node.op_type)
            nkey = _node_key(node)
            if node.op_type == OpType.INPUT:
                values[(node.guid, 0)] = inputs[node.params.input_index]
                values[(node.guid, 0)] = self._constrain_output(node.guid, 0, values[(node.guid, 0)])
                continue
            node_inputs = [values[(e.src, e.src_idx)] for e in self.graph.in_edges(node)]
            weights = {}
            weights.update(params.get(nkey, {}))
            weights.update(state.get(nkey, {}))
            ctx.node_guid = node.guid
            # the layer's name lands in its instructions' op_name, so a
            # device trace can be grouped by layer
            with jax.named_scope(node.name or nkey):
                outs = op_def.lower(node.params, node_inputs, weights, ctx)
            for i, o in enumerate(outs):
                values[(node.guid, i)] = self._constrain_output(node.guid, i, o)
        new_state = _apply_state_updates(state, ctx.state_updates, self.graph)
        outputs = [values[k] for k in (*self.outputs, *also)]
        return outputs, new_state, ctx.aux_losses

    def _interpret_nodes(self, nodes, values, params, state, rng, training, constrain=True):
        """Interpret a node subset given pre-seeded boundary values."""
        ctx = LowerCtx(
            training=training,
            rng=rng,
            backend=self.backend,
            mesh=self.mesh if constrain else None,
            seq_length=self.seq_length,
        )
        for node in nodes:
            op_def = get_op_def(node.op_type)
            nkey = _node_key(node)
            node_inputs = [values[(e.src, e.src_idx)] for e in self.graph.in_edges(node)]
            weights = {}
            weights.update(params.get(nkey, {}))
            weights.update(state.get(nkey, {}))
            ctx.node_guid = node.guid
            with jax.named_scope(node.name or nkey):
                outs = op_def.lower(node.params, node_inputs, weights, ctx)
            for i, o in enumerate(outs):
                values[(node.guid, i)] = (
                    self._constrain_output(node.guid, i, o) if constrain else o
                )
        return ctx

    def _forward_pipelined(self, params, state, inputs, rng, training, also=()):
        """GPipe execution of the repeated block stack (reference has no
        pipeline implementation — OP_PIPELINE is a placeholder,
        ffconst.h:160; this is the TPU-native schedule from
        parallel/pipeline.py): pre-nodes run under plain GSPMD shardings,
        the stacked stage params [S, r, ...] rotate activations along the
        "pipe" mesh axis, post-nodes consume the pipeline output."""
        from ..parallel.pipeline import gpipe

        plan = self._pipeline_plan
        values: Dict[Tuple[int, int], jax.Array] = {}
        for node in plan.pre:
            if node.op_type == OpType.INPUT:
                v = inputs[node.params.input_index]
                values[(node.guid, 0)] = self._constrain_output(node.guid, 0, v)
        pre_ctx = self._interpret_nodes(
            [n for n in plan.pre if n.op_type != OpType.INPUT],
            values, params, state, rng, training,
        )
        # tuple carry: rotating streams (banked at the exit) and
        # per-microbatch shared values (read-only context the schedule
        # rotates but never banks) — all produced by the pre region
        x = tuple(values[v] for v in plan.rotating_in)
        x_shared = tuple(values[v] for v in plan.shared)

        template = plan.repeats[0]

        r = len(plan.repeats) // plan.n_stages
        # blocks that can emit aux losses (MoE load balance) engage the
        # schedule's masked aux accumulation; otherwise the plain path
        # keeps zero overhead
        with_aux = any(
            node.op_type in (OpType.AGGREGATE, OpType.AGGREGATE_SPEC)
            and getattr(node.params, "lambda_bal", 0.0) > 0.0
            for node in template
        )

        # manual tensor parallelism inside the stage program (dp x pp x tp):
        # GSPMD cannot see through shard_map, so ops get the strategy's
        # weight SpecTuples and psum row-parallel partials themselves
        from ..parallel.mesh import MODEL_AXIS, SEQ_AXIS

        tp_axis = (
            MODEL_AXIS
            if (
                self.strategy is not None
                and self.strategy.axis_sizes.get(MODEL_AXIS, 1) > 1
                and MODEL_AXIS in self.mesh.axis_names
            )
            else None
        )
        # pp x cp: the carry's sequence dim shards over "seq" inside the
        # stage shard_map; attention lowers to ring attention over it
        cp_axis = (
            SEQ_AXIS
            if (
                self.strategy is not None
                and self.strategy.axis_sizes.get(SEQ_AXIS, 1) > 1
                and SEQ_AXIS in self.mesh.axis_names
            )
            else None
        )
        cp_size = self.mesh.shape[SEQ_AXIS] if cp_axis else 1
        from ..parallel.mesh import DATA_AXIS as _DATA_AXIS

        # single source of truth for the manual data axis: shared by the
        # LowerCtx (shard_rng decorrelation) and the carry entry_spec
        dp_axis = (
            _DATA_AXIS
            if _DATA_AXIS in self.mesh.axis_names and self.mesh.shape[_DATA_AXIS] > 1
            else None
        )
        tpl_wspecs = {
            node.guid: (
                self.strategy.node_shardings[node.guid].weights
                if self.strategy and node.guid in self.strategy.node_shardings
                else None
            )
            for node in template
        }

        def stage_fn(stage_params, act, shr=()):
            # stage_params leaves [r, ...]: scan the stage's blocks.
            # RNG folds the GLOBAL block index (stage*r + ridx): folding
            # only ridx would give corresponding blocks of every stage
            # identical dropout masks
            from ..parallel.mesh import PIPE_AXIS

            stage_idx = jax.lax.axis_index(PIPE_AXIS)

            def body(carry, rep):
                rep_params, ridx = rep
                act_in, aux_in = carry
                # seed the template's external inputs: rotating streams by
                # their repeat-0 entry keys, shared values by their own
                local = {k: act_in[i] for i, k in enumerate(plan.rotating_in)}
                local.update({k: shr[i] for i, k in enumerate(plan.shared)})
                # pp x cp: static bookkeeping of which values carry a
                # cp-REPLICATED (full-length) seq dim — shared entries
                # whose seq didn't divide cp stay unsharded (entry_spec
                # below), and cross-attention over them must lower dense,
                # not ring (ADVICE r4). Propagated like the values: an
                # op's outputs follow its first input (attention output
                # follows q; elementwise follows its operand).
                repl = {}
                if cp_axis is not None:
                    repl = {k: False for k in plan.rotating_in}
                    n_rot_ = len(plan.rotating_in)
                    for i, k in enumerate(plan.shared):
                        shp = plan.entry_shapes[n_rot_ + i]
                        repl[k] = len(shp) >= 3 and shp[1] % cp_size != 0
                ctx = LowerCtx(
                    training=training,
                    rng=jax.random.fold_in(rng, stage_idx * r + ridx),
                    backend=self.backend,
                    mesh=None,  # inside shard_map: manual, no GSPMD constraints
                    seq_length=self.seq_length,
                    tp_axis=tp_axis,
                    cp_axis=cp_axis,
                    dp_axis=dp_axis,
                )
                for node in template:
                    op_def = get_op_def(node.op_type)
                    in_keys = [(e.src, e.src_idx) for e in self.graph.in_edges(node)]
                    ins = [local[k] for k in in_keys]
                    ctx.node_guid = node.guid
                    ctx.weight_specs = tpl_wspecs[node.guid]
                    ins_repl = [repl.get(k, False) for k in in_keys]
                    ctx.kv_seq_replicated = len(ins_repl) >= 2 and bool(ins_repl[1])
                    outs = op_def.lower(node.params, ins, rep_params.get(_node_key(node), {}), ctx)
                    out_repl = bool(ins_repl[0]) if ins_repl else False
                    for i, o in enumerate(outs):
                        local[(node.guid, i)] = o
                        repl[(node.guid, i)] = out_repl
                aux_out = aux_in
                for a in ctx.aux_losses:
                    aux_out = aux_out + a.astype(jnp.float32)
                # next block's carry: each stream's exit value (shared
                # values are closed over, not threaded)
                act_out = tuple(
                    local[(template[p].guid, i)] for p, i in plan.out_streams
                )
                return (act_out, aux_out), None

            # rank-1 like gpipe's accumulator: scalar scan-carry residuals
            # crossing the shard_map partial-eval split hit the
            # _check_names scalar-residual hole (see parallel/pipeline.py)
            aux0 = jnp.zeros((1,), jnp.float32)
            # shard_map tracks varying manual axes: the aux accumulator
            # picks up pipe (per-stage weights), data (per-shard
            # tokens), and seq (per-sequence-shard partials under
            # pp x cp) variance inside the scan
            from ..parallel.mesh import DATA_AXIS, PIPE_AXIS

            vaxes = (PIPE_AXIS,)
            if DATA_AXIS in self.mesh.axis_names and self.mesh.shape[DATA_AXIS] > 1:
                vaxes = vaxes + (DATA_AXIS,)
            if cp_axis is not None:
                vaxes = vaxes + (cp_axis,)
            aux0 = jax.lax.pcast(aux0, vaxes, to="varying")
            (act, aux_sum), _ = jax.lax.scan(
                body, (act, aux0), (stage_params, jnp.arange(r))
            )
            if with_aux:
                return act, aux_sum
            return act

        # specs recorded at stacking time — the device_put sharding and
        # the shard_map in_specs are structurally the same objects
        param_specs = self._pipe_param_specs
        carry_specs = shared_specs = None
        if cp_axis is not None:
            # microbatched layout [M, mb, S, ...]: shard the sequence dim
            # (index 2) on "seq" for every rank>=3 entry whose S divides
            from jax.sharding import PartitionSpec as _P

            d_ax = dp_axis

            def entry_spec(shape):
                # only rank>=3 [B, S, ...] entries carry a sequence dim;
                # a rank-2 [B, F] stream's dim 1 is FEATURES, never shard
                # it over "seq"
                if len(shape) >= 3 and shape[1] % cp_size == 0:
                    return _P(None, d_ax, cp_axis, *([None] * (len(shape) - 2)))
                return _P(None, d_ax, *([None] * max(0, len(shape) - 1)))

            # ring attention treats every local array as a sequence
            # shard: a rotating stream whose seq dim cannot shard would
            # silently attend over wrong positions — reject instead
            for s in plan.entry_shapes[: len(plan.rotating_in)]:
                if len(s) >= 3 and s[1] % cp_size != 0:
                    raise ValueError(
                        f"pp x cp: rotating stream seq dim {s[1]} not divisible "
                        f"by cp={cp_size}"
                    )
            n_rot = len(plan.rotating_in)
            carry_specs = tuple(entry_spec(s) for s in plan.entry_shapes[:n_rot])
            shared_specs = tuple(entry_spec(s) for s in plan.entry_shapes[n_rot:])
        pipelined = gpipe(
            stage_fn,
            n_microbatches=plan.n_microbatches,
            mesh=self.mesh,
            with_aux=with_aux,
            param_specs=param_specs,
            carry_specs=carry_specs,
            shared_specs=shared_specs,
        )
        if with_aux:
            y, pipe_aux = pipelined(params[_PIPE_KEY], x, x_shared)
        else:
            y = pipelined(params[_PIPE_KEY], x, x_shared)
            pipe_aux = None
        # bank each rotating stream at its LAST-repeat producer so the
        # post region can consume any of them
        last = plan.repeats[-1]
        for i, (p, idx) in enumerate(plan.out_streams):
            values[(last[p].guid, idx)] = y[i]
        post_ctx = self._interpret_nodes(plan.post, values, params, state, rng, training)
        aux = pre_ctx.aux_losses + post_ctx.aux_losses
        if pipe_aux is not None:
            aux = aux + [pipe_aux]
        updates = dict(pre_ctx.state_updates)
        updates.update(post_ctx.state_updates)
        new_state = _apply_state_updates(state, updates, self.graph)
        outputs = [values[k] for k in (*self.outputs, *also)]
        return outputs, new_state, aux

    def _forward_remat(self, params, state, inputs, rng, also=()):
        """Plain interpretation with each repeated block wrapped in
        jax.checkpoint: the backward pass recomputes block activations
        instead of keeping them live — the TPU-native HBM/FLOPs trade
        ("use remat to trade FLOPs for memory"); numerically identical
        to the plain path."""
        pre, repeats, post = self._remat_plan
        values: Dict[Tuple[int, int], jax.Array] = {}
        for node in pre:
            if node.op_type == OpType.INPUT:
                v = inputs[node.params.input_index]
                values[(node.guid, 0)] = self._constrain_output(node.guid, 0, v)
        pre_ctx = self._interpret_nodes(
            [n for n in pre if n.op_type != OpType.INPUT],
            values, params, state, rng, training=True,
        )
        aux = list(pre_ctx.aux_losses)
        updates = dict(pre_ctx.state_updates)
        wanted = set(self.outputs)
        for rep in repeats:
            guids = {n.guid for n in rep}
            ext_in = sorted(
                {
                    (e.src, e.src_idx)
                    for n in rep
                    for e in self.graph.in_edges(n)
                    if e.src not in guids
                }
            )
            ext_out = sorted(
                {
                    (e.src, e.src_idx)
                    for n in rep
                    for e in self.graph.out_edges(n)
                    if e.dst not in guids
                }
                | {(g, i) for (g, i) in wanted if g in guids}
            )
            rep_params = {_node_key(n): params.get(_node_key(n), {}) for n in rep}
            rep_state = {_node_key(n): state.get(_node_key(n), {}) for n in rep}

            def block_fn(rep_params, rep_state, ext_vals, *, _rep=rep, _in=ext_in, _out=ext_out):
                local = dict(zip(_in, ext_vals))
                ctx = self._interpret_nodes(
                    _rep, local, rep_params, rep_state, rng, training=True
                )
                upd = {f"{g}\x00{name}": v for (g, name), v in ctx.state_updates.items()}
                return (
                    tuple(local[k] for k in _out),
                    tuple(ctx.aux_losses),
                    upd,
                )

            outs, aux_j, upd_j = jax.checkpoint(block_fn)(
                rep_params, rep_state, tuple(values[k] for k in ext_in)
            )
            for k, v in zip(ext_out, outs):
                values[k] = v
            aux.extend(aux_j)
            for key, v in upd_j.items():
                g, name = key.split("\x00", 1)
                updates[(int(g), name)] = v
        post_ctx = self._interpret_nodes(post, values, params, state, rng, training=True)
        aux.extend(post_ctx.aux_losses)
        updates.update(post_ctx.state_updates)
        new_state = _apply_state_updates(state, updates, self.graph)
        outputs = [values[k] for k in (*self.outputs, *also)]
        return outputs, new_state, aux

    def _constrain_output(self, guid: int, idx: int, x: jax.Array) -> jax.Array:
        if self.mesh is None or self.strategy is None:
            return x
        spec = self.strategy.output_spec(guid, idx)
        if spec is None:
            return x
        # on a TRIVIAL mesh (one device total) no constraint can shard
        # or anti-propagate anything, yet each one still lands in the
        # HLO as a fusion boundary — the searched path measured ~2-4%
        # slower than dp on a single chip purely from these no-op
        # markers. Multi-device meshes keep every constraint: even a
        # fully-replicated spec is a deliberate barrier against GSPMD
        # propagating a neighbor's sharding onto the tensor.
        if self.mesh.size == 1:
            return x
        from jax.sharding import NamedSharding

        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, to_partition_spec(spec)))

    # -------------------------------------------------------------- steps
    def _softmax_loss_logits(self) -> Optional[Tuple[int, int]]:
        """The value (node guid, output idx) that a cross-entropy loss
        may be taken from in place of the model's last output: the input
        of the node that produces it, when that node is a softmax over
        the last axis whose output nothing else reads. None otherwise."""
        if self.loss_type not in (
            LossType.SPARSE_CATEGORICAL_CROSSENTROPY, LossType.CATEGORICAL_CROSSENTROPY
        ):
            return None
        guid, _ = self.outputs[-1]
        node = self.graph.nodes[guid]
        if node.op_type != OpType.SOFTMAX or self.graph.out_edges(node):
            return None
        rank = len(infer_all_specs(self.graph)[guid][0].shape)
        if node.params.axis not in (-1, rank - 1):
            return None
        # under a block plan only the nodes behind the blocks leave
        # their inputs among the top-level values
        if self._pipeline_plan is not None and node not in self._pipeline_plan.post:
            return None
        if self._remat_plan is not None and node not in self._remat_plan[2]:
            return None
        (edge,) = self.graph.in_edges(node)
        return edge.src, edge.src_idx

    def _build_steps(self):
        loss_fn = losses.get_loss_fn(self.loss_type) if self.loss_type else None
        metric_types = self.metric_types
        logits_at = self._softmax_loss_logits()
        loss_logits = () if logits_at is None else (logits_at,)
        self.loss_form = "fused_softmax_ce" if loss_logits else "composed"
        sparse_labels = self.loss_type == LossType.SPARSE_CATEGORICAL_CROSSENTROPY

        def forward(params, state, inputs, rng):
            outs, _, _ = self._forward_impl(params, state, inputs, rng, training=False)
            return outs

        accum = int(self.grad_accum_steps)
        if accum < 1:
            raise ValueError(f"grad_accum_steps must be >= 1, got {accum}")

        def train_step(params, opt_state, state, inputs, label, rng):
            def objective(p, st, ins, lab, r):
                outs, new_state, aux = self._forward_impl(
                    p, st, ins, r, training=True, also=loss_logits
                )
                final = outs[len(self.outputs) - 1]
                with jax.named_scope("loss"):
                    # a graph that ends in a softmax only the loss reads:
                    # the loss takes its INPUT, and the model's output
                    # stays the probabilities for the metrics
                    if loss_logits:
                        loss = losses.softmax_crossentropy(outs[-1], lab, sparse_labels)
                    else:
                        loss = loss_fn(final, lab)
                # aux is a Python LIST of scalar aux losses — pytree
                # structure iteration at trace time, not a traced array
                for a in aux:  # flexlint: disable=jit-discipline
                    loss = loss + a
                mets = metrics_mod.compute_metrics(metric_types, final, lab)
                mets["loss"] = loss
                return loss, (mets, new_state)

            if accum == 1:
                grads, (mets, new_state) = jax.grad(objective, has_aux=True)(
                    params, state, inputs, label, rng
                )
            else:
                # gradient accumulation: scan grad microbatches so only
                # one microbatch's activations are live; mean-of-means
                # equals the full-batch gradient for mean losses
                b = inputs[0].shape[0]
                if b % accum:
                    raise ValueError(
                        f"batch {b} not divisible by grad_accum_steps={accum}"
                    )
                mb = b // accum

                def strided(x):
                    # microbatch i = rows {i, i+accum, ...}: a contiguous
                    # split would concentrate each microbatch on a subset
                    # of the dp devices and force per-step resharding
                    return x.reshape((mb, accum) + x.shape[1:]).swapaxes(0, 1)

                mb_inputs = tuple(strided(x) for x in inputs)
                mb_label = strided(label)

                def body(carry, xs):
                    gsum, st = carry
                    ins, lab, r = xs
                    g, (mets, st2) = jax.grad(objective, has_aux=True)(
                        params, st, ins, lab, r
                    )
                    return (jax.tree.map(jnp.add, gsum, g), st2), mets

                (gsum, new_state), mets_all = jax.lax.scan(
                    body,
                    (jax.tree.map(jnp.zeros_like, params), state),
                    (mb_inputs, mb_label, jax.random.split(rng, accum)),
                )
                grads = jax.tree.map(lambda g: g / accum, gsum)

                # "loss" is a per-batch mean; rmse is nonlinear (sqrt of a
                # mean, metrics.py:69) so summing per-microbatch values
                # would change its semantics — invert to per-microbatch
                # MSE, average (microbatches are equal-sized), re-apply;
                # every other metric key is a per-batch SUM
                # (count/correct/*_loss, metrics.py:48-69)
                def merge(k, v):
                    # k is a static metrics-dict KEY (a Python str at
                    # trace time), not a traced value
                    if k == "loss":  # flexlint: disable=jit-discipline
                        return jnp.mean(v)
                    if k == "rmse_loss":  # flexlint: disable=jit-discipline
                        return jnp.sqrt(jnp.mean(jnp.square(v / mb))) * b
                    return jnp.sum(v)

                mets = {k: merge(k, v) for k, v in mets_all.items()}
            with jax.named_scope("optimizer"):
                new_params, new_opt_state = self.optimizer.apply(params, grads, opt_state)
            if self._zero_specs is not None:
                # ZeRO-1: pin the updated moments back onto their
                # data-axis shards so GSPMD keeps them distributed
                # between steps (donated buffers preserve the layout)
                from jax.sharding import NamedSharding

                new_opt_state = self._map_moments(
                    new_opt_state,
                    lambda x, s: jax.lax.with_sharding_constraint(
                        x, NamedSharding(self.mesh, s)
                    ),
                )
            return new_params, new_opt_state, new_state, mets

        def eval_step(params, state, inputs, label, rng):
            outs, _, _ = self._forward_impl(params, state, inputs, rng, training=False)
            final = outs[-1]
            mets = metrics_mod.compute_metrics(metric_types, final, label)
            if loss_fn is not None:
                mets["loss"] = loss_fn(final, label)
            return mets

        # GLOBAL_PROGRAMS.instrument: every trace self-registers in the
        # process-wide jit registry (obs/capacity.py) with its argument
        # signature, so GET /v2/debug/programs and retrace blame cover
        # the executor's programs too (the wrapper body runs at trace
        # time only — zero steady-state cost). Each executor gets its
        # own namespace: a second executor's first compile of "forward"
        # is a new program, not a phantom retrace of the first one's.
        self._prog_ns = f"executor[{next(_EXECUTOR_IDS)}]"
        # evict this executor's registry namespace when it is collected:
        # rebuilding executors in a loop must not grow GLOBAL_PROGRAMS
        weakref.finalize(self, GLOBAL_PROGRAMS.remove_namespace, self._prog_ns)
        weakref.finalize(self, GLOBAL_LEDGER.remove_namespace, self._prog_ns)
        # predict side of the truth ledger: the strategy simulator's
        # whole-step estimate for THIS executor's train program, keyed so
        # the measured train windows below join it (obs/truth.py)
        if self.optimizer is not None:
            self._register_step_prediction()
        self._forward = jax.jit(
            GLOBAL_PROGRAMS.instrument(f"{self._prog_ns}.forward", forward)
        )
        self._eval_step = jax.jit(
            GLOBAL_PROGRAMS.instrument(f"{self._prog_ns}.eval_step", eval_step)
        )
        self._eval_step_fn = eval_step
        self._eval_window_cache = {}
        if self.optimizer is not None:
            self._train_step_fn = train_step
            self._train_step = jax.jit(
                GLOBAL_PROGRAMS.instrument(
                    f"{self._prog_ns}.train_step", train_step, loss_form=self.loss_form
                ),
                donate_argnums=(0, 1, 2),
            )
            self._multi_step_cache = {}
            self._window_cache = {}

    def _register_step_prediction(self) -> None:
        """Register the strategy-level simulated step time for this
        executor's train program in the truth ledger. Telemetry only: a
        graph the strategy predictor cannot walk (exotic pipeline
        layouts, missing shardings) must never break compile."""
        try:
            from ..parallel.machine import MachineSpec
            from ..search.calibration import (
                CPU_FITTED_CONTENTION,
                chip_spec_for,
                detected_device_kind,
                load_or_calibrate,
            )
            from ..search.simulator import predict_strategy_time

            devs = jax.devices()
            chip = chip_spec_for(detected_device_kind())
            if jax.default_backend() == "cpu":
                # the bench's virtual-device convention: N virtual CPU
                # devices share one host, so per-device peaks divide by
                # N x the fitted contention factor
                scale = max(1, len(devs)) * CPU_FITTED_CONTENTION
                chip = dataclasses.replace(
                    chip,
                    bf16_flops=chip.bf16_flops / scale,
                    f32_flops=chip.f32_flops / scale,
                    hbm_bandwidth=chip.hbm_bandwidth / scale,
                )
            machine = MachineSpec(
                num_nodes=1, devices_per_node=max(1, len(devs)), chip=chip
            )
            predict_strategy_time(
                self.graph,
                self.strategy,
                machine=machine,
                calibration=load_or_calibrate(machine),
                ledger_key=f"{self._prog_ns}.train_step",
            )
        except Exception:
            pass

    def _truth_sample(self, program: str) -> bool:
        """Whether to measure THIS window call for the truth ledger.
        Measuring requires a device sync, which serializes the host/
        device overlap a training loop otherwise enjoys — so sample:
        the first few calls per program (warm statistics quickly, and
        cover short benches like _bench_one entirely), then every 8th."""
        if self._truth_counts is None:
            self._truth_counts = {}
        n = self._truth_counts.get(program, 0)
        self._truth_counts[program] = n + 1
        return n < 4 or n % 8 == 0

    def _measure_window_step(self, program: str, traces_before: int,
                             elapsed: float, num_steps: int) -> None:
        """Measure side of the truth ledger: per-optimizer-step wall
        seconds from one traced multi-step window. Compile calls
        (the window program traced during this call) are excluded —
        their wall time is compile cost, not step time."""
        if GLOBAL_PROGRAMS.trace_count(program) > traces_before:
            return
        GLOBAL_LEDGER.measure(
            f"{self._prog_ns}.train_step", elapsed / max(1, num_steps)
        )

    # ---------------------------------------------------------------- API
    def set_learning_rate(self, lr: float) -> None:
        """Adjust lr in-place (it lives in opt_state as a traced scalar, so
        this does not invalidate the jit cache — reference:
        flexflow_c.cc set_learning_rate / keras LearningRateScheduler)."""
        if self.opt_state is not None and "lr" in self.opt_state:
            self.opt_state["lr"] = jnp.asarray(lr, jnp.float32)

    def _run_train_program(self, program: str, jitted, inputs, label, rng,
                           num_steps: int) -> Dict[str, Any]:
        """Dispatch one train program (``ff.train.dispatch``) and, on
        the sampled calls (see _truth_sample), measure it for the truth
        ledger: the timing includes a metrics sync — telemetry, not
        billing. Both drains of a measured call are ``ff.train.truth_sync``
        spans, so a trace shows which device idle the ledger bought."""
        measure = self._truth_sample(program)
        traces_before = GLOBAL_PROGRAMS.trace_count(program) if measure else 0
        if measure:
            # drain async dispatch backlog BEFORE the timer starts: the
            # unmeasured calls between samples never sync, so the device
            # may still be running earlier steps — timing them into this
            # window would over-report step time and false-alarm drift
            with phase("train.truth_sync"):
                jax.block_until_ready(self.params)
        with phase("train.dispatch") as dispatch:
            self.params, self.opt_state, self.state, mets = jitted(
                self.params, self.opt_state, self.state, tuple(inputs), label, rng
            )
        if measure:
            with phase("train.truth_sync") as sync:
                jax.block_until_ready(mets)
            self._measure_window_step(
                program, traces_before, sync.t1 - dispatch.t0, num_steps
            )
        if program in GLOBAL_PROGRAMS.unstamped:
            # this call compiled: its wall (host seconds of the dispatch,
            # and the drain where this call was a measured one) is the
            # program's lump, beside the parts JAX's events gave it
            GLOBAL_PROGRAMS.set_compile_time(program, (sync.t1 if measure else dispatch.t1) - dispatch.t0)
        return mets

    def train_batch(self, inputs: Sequence[jax.Array], label: jax.Array, rng: jax.Array) -> Dict[str, Any]:
        # chaos hook (no-op unless a FaultPlan is installed): rules can
        # raise a device error, stall, or NaN-poison the batch
        inputs = faults.inject(faults.EXECUTOR_TRAIN_BATCH, inputs)
        with phase("train.shard_inputs"):
            inputs = self._shard_inputs(inputs)
            if jax.process_count() > 1:
                label = self.shard_label(label)
        # the default fit loop (trace_window=1) runs THIS program, so
        # the simulator's step prediction must pair here too, not only
        # on the traced multi-step windows below
        return self._run_train_program(
            f"{self._prog_ns}.train_step", self._train_step, inputs, label, rng, 1
        )

    def _scan_train_steps(self, w: int, per_step_xs: bool):
        """Get-or-build the jitted program running ``w`` train steps as
        one lax.scan (the Legion begin_trace/end_trace analog,
        flexflow_cffi.py:2079-2086 — per-step host dispatch and runtime
        analysis are paid once per window).

        per_step_xs=True: inputs/labels carry a leading [w] axis, one
        slice and one split rng key per step (train_window). False: the
        same batch every step with a folded key (train_batch_repeated).
        Returns stacked metrics (leaves [w]).
        """
        cache = self._window_cache if per_step_xs else self._multi_step_cache
        jitted = cache.get(w)
        if jitted is not None:
            return jitted
        step = self._train_step_fn

        def program(params, opt_state, state, inputs, label, rng):
            if per_step_xs:
                xs = (tuple(inputs), label, jax.random.split(rng, w))

                def body(carry, x):
                    ins, lab, r = x
                    p, o, s, mets = step(*carry, ins, lab, r)
                    return (p, o, s), mets
            else:
                xs = jnp.arange(w)

                def body(carry, i):
                    p, o, s, mets = step(*carry, inputs, label, jax.random.fold_in(rng, i))
                    return (p, o, s), mets

            (params, opt_state, state), mets = jax.lax.scan(
                body, (params, opt_state, state), xs
            )
            return params, opt_state, state, mets

        name = (f"{self._prog_ns}.train_window[{w}]" if per_step_xs
                else f"{self._prog_ns}.train_repeat[{w}]")
        jitted = jax.jit(
            GLOBAL_PROGRAMS.instrument(name, program, loss_form=self.loss_form),
            donate_argnums=(0, 1, 2),
        )
        cache[w] = jitted
        return jitted

    def train_batch_repeated(
        self, inputs: Sequence[jax.Array], label: jax.Array, rng: jax.Array, num_steps: int
    ) -> Dict[str, Any]:
        """Run ``num_steps`` optimizer steps on ONE batch inside a single
        XLA program (steady-state step timing without per-step dispatch).
        Returns the final step's metrics."""
        if self.optimizer is None:
            raise RuntimeError("train_batch_repeated requires a compiled optimizer")
        jitted = self._scan_train_steps(num_steps, per_step_xs=False)
        with phase("train.shard_inputs"):
            inputs = self._shard_inputs(inputs)
            if jax.process_count() > 1:
                label = self.shard_label(label)
        mets = self._run_train_program(
            f"{self._prog_ns}.train_repeat[{num_steps}]", jitted, inputs, label,
            rng, num_steps,
        )
        return jax.tree.map(lambda m: m[-1], mets)

    def train_window(
        self, inputs: Sequence[jax.Array], labels: jax.Array, rng: jax.Array
    ) -> Dict[str, Any]:
        """Run one optimizer step per stacked batch inside a single XLA
        program: ``inputs``/``labels`` carry a leading ``[steps, ...]``
        axis and lax.scan consumes one slice (and one split rng key) per
        step. Returns the metrics of every step (leaves shaped [steps])."""
        if self.optimizer is None:
            raise RuntimeError("train_window requires a compiled optimizer")
        w = int(inputs[0].shape[0])
        jitted = self._scan_train_steps(w, per_step_xs=True)
        with phase("train.shard_inputs"):
            inputs = self._shard_inputs(inputs, leading_axis=True)
            labels = self.shard_label(labels, leading_axis=True)
        return self._run_train_program(
            f"{self._prog_ns}.train_window[{w}]", jitted, inputs, labels, rng, w
        )

    def eval_window(
        self, inputs: Sequence[jax.Array], labels: jax.Array, rng: Optional[jax.Array] = None
    ) -> Dict[str, Any]:
        """Evaluate one batch per leading-axis slice inside a single XLA
        program (the eval half of the iteration-tracing story). Returns
        per-step metrics (leaves shaped [steps])."""
        w = int(inputs[0].shape[0])
        jitted = self._eval_window_cache.get(w)
        if jitted is None:
            step = self._eval_step_fn

            def window(params, state, inputs, labels, rng):
                def body(carry, xs):
                    ins, lab, r = xs
                    return carry, step(params, state, ins, lab, r)

                _, mets = jax.lax.scan(
                    body, 0, (tuple(inputs), labels, jax.random.split(rng, w))
                )
                return mets

            jitted = jax.jit(
                GLOBAL_PROGRAMS.instrument(f"{self._prog_ns}.eval_window[{w}]", window)
            )
            self._eval_window_cache[w] = jitted
        if rng is None:
            rng = jax.random.key(0)
        inputs = self._shard_inputs(inputs, leading_axis=True)
        labels = self.shard_label(labels, leading_axis=True)
        return jitted(self.params, self.state, tuple(inputs), labels, rng)

    def eval_batch(self, inputs: Sequence[jax.Array], label: jax.Array, rng: Optional[jax.Array] = None) -> Dict[str, Any]:
        inputs = self._shard_inputs(inputs)
        if jax.process_count() > 1:
            label = self.shard_label(label)
        if rng is None:
            rng = jax.random.key(0)
        return self._eval_step(self.params, self.state, tuple(inputs), label, rng)

    def predict(self, inputs: Sequence[jax.Array], rng: Optional[jax.Array] = None) -> List[jax.Array]:
        inputs = self._shard_inputs(inputs)
        if rng is None:
            rng = jax.random.key(0)
        outs = self._forward(self.params, self.state, tuple(inputs), rng)
        # chaos hook: error / stall / NaN-poisoned outputs
        return faults.inject(faults.EXECUTOR_PREDICT, outs)

    def input_shardings(self):
        """(per-input NamedShardings, label sharding). Labels share the
        first input's batch-axis sharding. None when there is no mesh."""
        if self.mesh is None:
            return None, None
        from jax.sharding import NamedSharding, PartitionSpec

        input_nodes = sorted(
            (n for n in self.graph.nodes.values() if n.op_type == OpType.INPUT),
            key=lambda n: n.params.input_index,
        )
        shardings = []
        for node in input_nodes:
            spec = self.strategy.output_spec(node.guid, 0) if self.strategy else None
            shardings.append(NamedSharding(self.mesh, to_partition_spec(spec)))
        label = None
        if shardings:
            pspec = shardings[0].spec
            label = NamedSharding(self.mesh, PartitionSpec(pspec[0] if len(pspec) else None))
        return shardings, label

    def _shard_inputs(self, inputs: Sequence[jax.Array], leading_axis: bool = False) -> List[jax.Array]:
        """``leading_axis``: inputs carry an extra unsharded [steps] axis
        in front of the batch sharding (train_window's stacked batches)."""
        if self.mesh is None:
            return [jnp.asarray(x) for x in inputs]
        shardings, _ = self.input_shardings()
        if leading_axis:
            shardings = [_prepend_axis(s, self.mesh) for s in shardings]
        return [_put_global(jnp.asarray(x), s, full=False) for x, s in zip(inputs, shardings)]

    def shard_label(self, label, leading_axis: bool = False):
        """Place a label batch on the mesh (multi-host: ``label`` is this
        process's shard of the global batch)."""
        if self.mesh is None:
            return jnp.asarray(label)
        _, ls = self.input_shardings()
        if ls is None:
            return jnp.asarray(label)
        if leading_axis:
            ls = _prepend_axis(ls, self.mesh)
        return _put_global(jnp.asarray(label), ls, full=False)


def _prepend_axis(sharding, mesh):
    """The same batch sharding with an extra unsharded leading axis."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec(None, *sharding.spec))


def _put_global(x, sharding, full: bool):
    """Place host data on a (possibly multi-host) sharding. Single
    process: plain device_put. Multi-process, ``full=True``: ``x`` is the
    complete global array on every process (weights — deterministic init
    computes them identically everywhere), and each process slices its
    addressable shards from it, which stays correct whichever mesh axis
    rides DCN. ``full=False``: ``x`` is this process's slice of the
    global batch (the TPU-native analog of the reference's per-node
    dataloader partitions, flexflow_dataloader.cc)."""
    if jax.process_count() == 1:
        return jax.device_put(x, sharding)
    import numpy as np

    arr = np.asarray(x)
    if full:
        return jax.make_array_from_callback(arr.shape, sharding, lambda idx: arr[idx])
    return jax.make_array_from_process_local_data(sharding, arr)


def _apply_state_updates(state, updates: Dict, graph: PCGraph):
    if not updates:
        return state
    new_state = {k: dict(v) for k, v in state.items()}
    for (guid, name), val in updates.items():
        node = graph.nodes[guid]
        new_state[_node_key(node)][name] = val
    return new_state
