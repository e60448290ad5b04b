"""FFModel: the central model-building + training API.

Reference: FFModel (include/flexflow/model.h:328-554 — ~60 layer
builders; src/runtime/model.cc:5195 LoC). API names and argument orders
mirror the reference so FlexFlow programs port mechanically; semantics
are TPU-native: building a layer records a PCG node (the reference's
lazy Layer graph, src/runtime/layer.cc), and ``compile`` lowers the PCG
through the Unity search to a single jitted, mesh-sharded train step
instead of Legion task launches.
"""
from __future__ import annotations

import time
from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .config import FFConfig, FFIterationConfig
from .core.graph import Node, PCGraph
from .core.tensor import TensorSpec
from .core.types import (
    ActiMode,
    AggrMode,
    CompMode,
    DataType,
    LossType,
    MetricsType,
    OpType,
    PoolType,
)
from .obs.steptrace import GLOBAL_STARTUP
from .ops import io_ops, linear as linear_mod, conv as conv_mod
from .ops.attention import MultiHeadAttentionParams
from .ops.batch_matmul import BatchMatmulParams
from .ops.elementwise import ElementBinaryParams, ElementUnaryParams
from .ops.embedding import EmbeddingParams
from .ops.moe_ops import (
    AggregateParams,
    AggregateSpecParams,
    CacheParams,
    GroupByParams,
    TopKParams,
)
from .ops.norm import BatchNormParams, LayerNormParams
from .ops.reduction_ops import GatherParams, MeanParams, ReduceSumParams
from .ops.shape_ops import (
    CastParams,
    ConcatParams,
    FlatParams,
    ReshapeParams,
    ReverseParams,
    SplitParams,
    TransposeParams,
)
from .ops.softmax import DropoutParams, SoftmaxParams
from .parallel.propagation import infer_all_specs
from .runtime.executor import CompiledExecutor
from .runtime.metrics import PerfMetrics
from .runtime.optimizers import Optimizer, SGDOptimizer


class Tensor:
    """Frontend tensor handle: (graph node, output index) + logical spec.

    Reference: the Tensor/TensorBase frontend objects (tensor.h) created
    eagerly by layer calls and resolved at compile.
    """

    def __init__(self, model: "FFModel", node: Node, idx: int, spec: TensorSpec):
        self._model = model
        self.node = node
        self.idx = idx
        self.spec = spec

    @property
    def shape(self) -> Tuple[int, ...]:
        return self.spec.shape

    @property
    def dtype(self) -> DataType:
        return self.spec.dtype

    @property
    def ndim(self) -> int:
        return self.spec.ndim

    def __repr__(self):
        return f"Tensor(shape={self.shape}, dtype={self.dtype.value}, node={self.node.guid})"

    # numpy-ish sugar
    def __add__(self, other):
        return self._model.add(self, other)

    def __sub__(self, other):
        return self._model.subtract(self, other)

    def __mul__(self, other):
        return self._model.multiply(self, other)


class FFModel:
    """Model builder + trainer (reference: model.h:328)."""

    def __init__(self, config: Optional[FFConfig] = None, seed: int = 0):
        self.config = config or FFConfig()
        self.graph = PCGraph()
        self._num_inputs = 0
        self._seed = seed
        self.iter_config = FFIterationConfig()
        self.executor: Optional[CompiledExecutor] = None
        self.strategy = None
        self.mesh = None
        self.label_spec: Optional[TensorSpec] = None
        self._outputs: List[Tensor] = []
        self._search_result = None

    # ------------------------------------------------------------ helpers
    def _add(self, op_type: OpType, params, inputs: Sequence[Tensor], name: str = "") -> List[Tensor]:
        node = self.graph.new_node(op_type, params, name)
        for i, t in enumerate(inputs):
            self.graph.add_edge(t.node, node, t.idx, i)
        from .ops.base import get_op_def

        out_specs = get_op_def(op_type).infer_output_specs(params, [t.spec for t in inputs])
        return [Tensor(self, node, i, s) for i, s in enumerate(out_specs)]

    def _one(self, *args, **kw) -> Tensor:
        return self._add(*args, **kw)[0]

    # ----------------------------------------------------- tensor creation
    def create_tensor(self, shape: Sequence[int], dtype: DataType = DataType.FLOAT, name: str = "") -> Tensor:
        """An input placeholder (reference: FFModel::create_tensor)."""
        params = io_ops.InputParams(tuple(int(s) for s in shape), dtype, self._num_inputs)
        self._num_inputs += 1
        return self._one(OpType.INPUT, params, [], name=name or f"input{params.input_index}")

    def create_weight(self, shape: Sequence[int], dtype: DataType = DataType.FLOAT, initializer: str = "glorot_uniform", name: str = "") -> Tensor:
        params = io_ops.WeightParams(tuple(int(s) for s in shape), dtype, initializer)
        return self._one(OpType.WEIGHT, params, [], name=name)

    # ------------------------------------------------------------- layers
    @staticmethod
    def _acti(activation) -> ActiMode:
        """Accept ActiMode, its string value ("relu"), or None."""
        if activation is None:
            return ActiMode.NONE
        if isinstance(activation, ActiMode):
            return activation
        return ActiMode(activation)

    def dense(
        self,
        input: Tensor,
        out_dim: int,
        activation: ActiMode = ActiMode.NONE,
        use_bias: bool = True,
        datatype: Optional[DataType] = None,
        kernel_initializer: str = "glorot_uniform",
        bias_initializer: str = "zeros",
        name: str = "",
    ) -> Tensor:
        # datatype None inherits the input dtype (the reference's DT_NONE
        # default, model.h dense) — a bf16 model's dense layers must not
        # silently compute and store f32 because the caller omitted it
        p = linear_mod.LinearParams(
            out_dim, use_bias, self._acti(activation), datatype or input.dtype,
            kernel_initializer, bias_initializer,
        )
        return self._one(OpType.LINEAR, p, [input], name=name)

    def conv2d(
        self,
        input: Tensor,
        out_channels: int,
        kernel_h: int,
        kernel_w: int,
        stride_h: int,
        stride_w: int,
        padding_h: int,
        padding_w: int,
        activation: ActiMode = ActiMode.NONE,
        groups: int = 1,
        use_bias: bool = True,
        name: str = "",
    ) -> Tensor:
        p = conv_mod.Conv2DParams(
            out_channels,
            (kernel_h, kernel_w),
            (stride_h, stride_w),
            (padding_h, padding_w),
            groups,
            use_bias,
            self._acti(activation),
            input.dtype,
        )
        return self._one(OpType.CONV2D, p, [input], name=name)

    def pool2d(
        self,
        input: Tensor,
        kernel_h: int,
        kernel_w: int,
        stride_h: int,
        stride_w: int,
        padding_h: int,
        padding_w: int,
        pool_type: PoolType = PoolType.MAX,
        activation: ActiMode = ActiMode.NONE,
        name: str = "",
    ) -> Tensor:
        p = conv_mod.Pool2DParams((kernel_h, kernel_w), (stride_h, stride_w), (padding_h, padding_w), pool_type, self._acti(activation))
        return self._one(OpType.POOL2D, p, [input], name=name)

    def embedding(
        self,
        input: Tensor,
        num_entries: int,
        out_dim: int,
        aggr: AggrMode = AggrMode.NONE,
        datatype: DataType = DataType.FLOAT,
        kernel_initializer: str = "glorot_uniform",
        name: str = "",
    ) -> Tensor:
        p = EmbeddingParams(num_entries, out_dim, aggr, datatype, kernel_initializer)
        return self._one(OpType.EMBEDDING, p, [input], name=name)

    def multihead_attention(
        self,
        query: Tensor,
        key: Tensor,
        value: Tensor,
        embed_dim: int,
        num_heads: int,
        kdim: int = 0,
        vdim: int = 0,
        dropout: float = 0.0,
        bias: bool = False,
        add_bias_kv: bool = False,
        add_zero_attn: bool = False,
        causal: bool = False,
        name: str = "",
    ) -> Tensor:
        if add_bias_kv or add_zero_attn:
            raise NotImplementedError("add_bias_kv / add_zero_attn are not supported")
        p = MultiHeadAttentionParams(embed_dim, num_heads, kdim, vdim, dropout, bias, causal, query.dtype)
        return self._one(OpType.MULTIHEAD_ATTENTION, p, [query, key, value], name=name)

    def rnn(
        self,
        input: Tensor,
        hidden_size: int,
        initial_state: Optional[Tensor] = None,
        activation: ActiMode = ActiMode.TANH,
        name: str = "",
    ) -> Tuple[Tensor, Tensor]:
        """Elman RNN over [B, T, D] -> (sequence [B, T, H], final_h [B, H]).
        Reference: nmt/ RNN mode."""
        from .ops.recurrent import RecurrentParams

        p = RecurrentParams(hidden_size, input.dtype, self._acti(activation))
        ins = [input] + ([initial_state] if initial_state is not None else [])
        outs = self._add(OpType.RNN, p, ins, name=name)
        return outs[0], outs[1]

    def lstm(
        self,
        input: Tensor,
        hidden_size: int,
        initial_h: Optional[Tensor] = None,
        initial_c: Optional[Tensor] = None,
        name: str = "",
    ) -> Tuple[Tensor, Tensor, Tensor]:
        """LSTM over [B, T, D] -> (sequence, final_h, final_c).
        Reference: nmt/lstm.cc (cudnnRNN LSTM mode)."""
        from .ops.recurrent import RecurrentParams

        p = RecurrentParams(hidden_size, input.dtype)
        if initial_c is not None and initial_h is None:
            raise ValueError("lstm: initial_c requires initial_h (pass zeros for h explicitly)")
        ins = [input]
        if initial_h is not None:
            ins.append(initial_h)
            if initial_c is not None:
                ins.append(initial_c)
        outs = self._add(OpType.LSTM, p, ins, name=name)
        return outs[0], outs[1], outs[2]

    def layer_norm(
        self,
        input: Tensor,
        axes: Optional[Sequence[int]] = None,
        elementwise_affine: bool = True,
        eps: float = 1e-5,
        name: str = "",
    ) -> Tensor:
        if axes is None:
            axes = [input.ndim - 1]
        p = LayerNormParams(tuple(axes), elementwise_affine, eps, input.dtype)
        return self._one(OpType.LAYERNORM, p, [input], name=name)

    def batch_norm(self, input: Tensor, relu: bool = True, eps: float = 1e-5, name: str = "") -> Tensor:
        p = BatchNormParams(relu=relu, eps=eps, dtype=input.dtype)
        return self._one(OpType.BATCHNORM, p, [input], name=name)

    def batch_matmul(
        self,
        A: Tensor,
        B: Tensor,
        a_seq_length_dim: int = -1,
        b_seq_length_dim: int = -1,
        name: str = "",
    ) -> Tensor:
        p = BatchMatmulParams(a_seq_length_dim, b_seq_length_dim)
        return self._one(OpType.BATCH_MATMUL, p, [A, B], name=name)

    # --------------------------------------------------------- elementwise
    def _binary(self, op: OpType, x: Tensor, y: Tensor, inplace_a: bool = False, name: str = "") -> Tensor:
        return self._one(op, ElementBinaryParams(op, inplace_a), [x, y], name=name)

    def add(self, x, y, inplace_a=False, name=""):
        return self._binary(OpType.EW_ADD, x, y, inplace_a, name)

    def subtract(self, x, y, inplace_a=False, name=""):
        return self._binary(OpType.EW_SUB, x, y, inplace_a, name)

    def multiply(self, x, y, inplace_a=False, name=""):
        return self._binary(OpType.EW_MUL, x, y, inplace_a, name)

    def divide(self, x, y, inplace_a=False, name=""):
        return self._binary(OpType.EW_DIV, x, y, inplace_a, name)

    def max(self, x, y, inplace_a=False, name=""):
        return self._binary(OpType.EW_MAX, x, y, inplace_a, name)

    def min(self, x, y, inplace_a=False, name=""):
        return self._binary(OpType.EW_MIN, x, y, inplace_a, name)

    def _unary(self, op: OpType, x: Tensor, scalar: float = 0.0, inplace: bool = False, name: str = "") -> Tensor:
        return self._one(op, ElementUnaryParams(op, scalar, inplace), [x], name=name)

    def relu(self, x, inplace=True, name=""):
        return self._unary(OpType.RELU, x, inplace=inplace, name=name)

    def sigmoid(self, x, name=""):
        return self._unary(OpType.SIGMOID, x, name=name)

    def tanh(self, x, name=""):
        return self._unary(OpType.TANH, x, name=name)

    def elu(self, x, inplace=True, name=""):
        return self._unary(OpType.ELU, x, inplace=inplace, name=name)

    def gelu(self, x, name=""):
        return self._unary(OpType.GELU, x, name=name)

    def identity(self, x, name=""):
        return self._unary(OpType.IDENTITY, x, name=name)

    def exp(self, x, name=""):
        return self._unary(OpType.EXP, x, name=name)

    def sin(self, x, name=""):
        return self._unary(OpType.SIN, x, name=name)

    def cos(self, x, name=""):
        return self._unary(OpType.COS, x, name=name)

    def rsqrt(self, x, name=""):
        return self._unary(OpType.RSQRT, x, name=name)

    def pow(self, x, exponent: float, name=""):
        return self._unary(OpType.POW, x, scalar=exponent, name=name)

    def scalar_add(self, x, scalar: float, inplace=True, name=""):
        return self._unary(OpType.SCALAR_ADD, x, scalar=scalar, inplace=inplace, name=name)

    def scalar_sub(self, x, scalar: float, inplace=True, name=""):
        return self._unary(OpType.SCALAR_SUB, x, scalar=scalar, inplace=inplace, name=name)

    def scalar_multiply(self, x, scalar: float, inplace=True, name=""):
        return self._unary(OpType.SCALAR_MUL, x, scalar=scalar, inplace=inplace, name=name)

    def scalar_true_divide(self, x, scalar: float, inplace=True, name=""):
        return self._unary(OpType.SCALAR_TRUE_DIV, x, scalar=scalar, inplace=inplace, name=name)

    # ----------------------------------------------------------- shape ops
    def reshape(self, input: Tensor, shape: Sequence[int], name: str = "") -> Tensor:
        return self._one(OpType.RESHAPE, ReshapeParams(tuple(shape)), [input], name=name)

    def transpose(self, input: Tensor, perm: Sequence[int], name: str = "") -> Tensor:
        return self._one(OpType.TRANSPOSE, TransposeParams(tuple(perm)), [input], name=name)

    def reverse(self, input: Tensor, axis: int, name: str = "") -> Tensor:
        return self._one(OpType.REVERSE, ReverseParams(axis), [input], name=name)

    def flat(self, input: Tensor, name: str = "") -> Tensor:
        return self._one(OpType.FLAT, FlatParams(), [input], name=name)

    def concat(self, tensors: Sequence[Tensor], axis: int, name: str = "") -> Tensor:
        return self._one(OpType.CONCAT, ConcatParams(axis, len(tensors)), list(tensors), name=name)

    def split(self, input: Tensor, sizes: Union[int, Sequence[int]], axis: int, name: str = "") -> List[Tensor]:
        if isinstance(sizes, int):
            total = input.shape[axis]
            if total % sizes != 0:
                raise ValueError(f"split: dim {axis} of size {total} not divisible into {sizes} chunks")
            sizes = [total // sizes] * sizes
        if sum(sizes) != input.shape[axis]:
            raise ValueError(f"split sizes {sizes} do not sum to dim size {input.shape[axis]}")
        return self._add(OpType.SPLIT, SplitParams(tuple(sizes), axis), [input], name=name)

    def cast(self, input: Tensor, dtype: DataType, name: str = "") -> Tensor:
        return self._one(OpType.CAST, CastParams(dtype), [input], name=name)

    # ---------------------------------------------------------------- misc
    def softmax(self, input: Tensor, axis: int = -1, name: str = "") -> Tensor:
        return self._one(OpType.SOFTMAX, SoftmaxParams(axis), [input], name=name)

    def dropout(self, input: Tensor, rate: float, seed: int = 0, name: str = "") -> Tensor:
        return self._one(OpType.DROPOUT, DropoutParams(rate, seed), [input], name=name)

    def gather(self, input: Tensor, index: Tensor, axis: int, name: str = "") -> Tensor:
        return self._one(OpType.GATHER, GatherParams(axis), [input, index], name=name)

    def reduce_sum(self, input: Tensor, axes: Sequence[int], keepdims: bool = False, name: str = "") -> Tensor:
        return self._one(OpType.REDUCE_SUM, ReduceSumParams(tuple(axes), keepdims), [input], name=name)

    def mean(self, input: Tensor, dims: Sequence[int], keepdims: bool = False, name: str = "") -> Tensor:
        return self._one(OpType.MEAN, MeanParams(tuple(dims), keepdims), [input], name=name)

    # ----------------------------------------------------------- MoE layers
    def top_k(self, input: Tensor, k: int, sorted: bool = True, name: str = "") -> Tuple[Tensor, Tensor]:
        outs = self._add(OpType.TOPK, TopKParams(k, sorted), [input], name=name)
        return outs[0], outs[1]

    def group_by(
        self, input: Tensor, assign: Tensor, n: int, alpha: float, stacked: bool = False, name: str = ""
    ) -> Union[List[Tensor], Tensor]:
        outs = self._add(OpType.GROUP_BY, GroupByParams(n, alpha, stacked), [input, assign], name=name)
        return outs[0] if stacked else outs

    def experts(
        self,
        grouped: Tensor,
        num_exp: int,
        hidden_size: int,
        out_dim: int,
        activation: ActiMode = ActiMode.RELU,
        name: str = "",
    ) -> Tensor:
        """Batched expert FFN over stacked [n, cap, D] (TPU-native: the
        expert dim shards over the mesh for real expert parallelism)."""
        from .ops.moe_ops import ExpertsParams

        p = ExpertsParams(num_exp, hidden_size, out_dim, activation, grouped.dtype)
        return self._one(OpType.EXPERTS, p, [grouped], name=name)

    def aggregate(
        self, gate_preds: Tensor, gate_assign: Tensor, exp_preds: Sequence[Tensor], n: int, lambda_bal: float, name: str = ""
    ) -> Tensor:
        p = AggregateParams(n, lambda_bal)
        return self._one(OpType.AGGREGATE, p, [gate_preds, gate_assign] + list(exp_preds), name=name)

    def aggregate_spec(
        self, gate_preds: Tensor, gate_assign: Tensor, exp_preds: Sequence[Tensor], n: int, lambda_bal: float, name: str = ""
    ) -> Tensor:
        p = AggregateSpecParams(n, lambda_bal)
        return self._one(OpType.AGGREGATE_SPEC, p, [gate_preds, gate_assign] + list(exp_preds), name=name)

    def cache(self, input: Tensor, num_batches: int = 1, trigger_threshold: float = 0.0, name: str = "") -> Tensor:
        return self._one(OpType.CACHE, CacheParams(num_batches, trigger_threshold), [input], name=name)

    def moe(
        self,
        input: Tensor,
        num_exp: int,
        num_select: int,
        expert_hidden_size: int,
        alpha: float = 2.0,
        lambda_bal: float = 0.04,
        batched: bool = True,
        name: str = "",
    ) -> Tensor:
        """Composite MoE layer (reference: FFModel::moe, src/ops/moe.cc:20):
        dense gate -> topk -> group_by -> experts -> aggregate.

        batched=True (default, TPU-native): ONE stacked dispatch + ONE
        batched Experts op — constant HLO size at any expert count, and
        the expert dim shards over the mesh (real expert parallelism).
        batched=False reproduces the reference's n separate per-expert
        Dense ops."""
        gate = self.dense(input, num_exp, ActiMode.NONE, name=f"{name}_gate")
        gate = self.softmax(gate, name=f"{name}_gate_sm")
        topk_vals, topk_idx = self.top_k(gate, num_select, name=f"{name}_topk")
        if batched:
            grouped = self.group_by(input, topk_idx, num_exp, alpha, stacked=True, name=f"{name}_groupby")
            expert_out = self.experts(
                grouped, num_exp, expert_hidden_size, input.shape[-1], name=f"{name}_experts"
            )
            return self.aggregate(topk_vals, topk_idx, [expert_out], num_exp, lambda_bal, name=f"{name}_agg")
        grouped = self.group_by(input, topk_idx, num_exp, alpha, name=f"{name}_groupby")
        expert_outs = []
        for e, g in enumerate(grouped):
            h = self.dense(g, expert_hidden_size, ActiMode.RELU, name=f"{name}_exp{e}")
            h = self.dense(h, input.shape[-1], ActiMode.NONE, name=f"{name}_exp{e}_out")
            expert_outs.append(h)
        return self.aggregate(topk_vals, topk_idx, expert_outs, num_exp, lambda_bal, name=f"{name}_agg")

    def residual(self, x: Tensor, fx: Tensor, name: str = "") -> Tensor:
        return self.add(x, fx, name=name)

    # -------------------------------------------------------------- compile
    def compile(
        self,
        optimizer: Optional[Optimizer] = None,
        loss_type: Optional[LossType] = None,
        metrics: Sequence[MetricsType] = (),
        comp_mode: CompMode = CompMode.TRAINING,
        outputs: Optional[Sequence[Tensor]] = None,
        strategy=None,
    ):
        """Search for a parallelization strategy and build the compiled
        executable (reference: FFModel::compile, model.cc:2811 — search
        task, convert_graph_to_operators, NCCL init all collapse into
        strategy selection + one jit)."""
        if optimizer is None:
            optimizer = SGDOptimizer(lr=self.config.learning_rate, weight_decay=self.config.weight_decay)
        self.optimizer = optimizer
        self.loss_type = loss_type
        self.metrics = list(metrics)
        self.comp_mode = comp_mode
        self._outputs = list(outputs) if outputs else [self._default_output()]
        from .parallel.distributed import maybe_initialize_from_env
        from .parallel.mesh import build_mesh
        from .parallel.strategy import data_parallel_strategy

        # multi-host entry (reference: GASNet multi-node; here one process
        # per host joins via jax.distributed when the env declares a job).
        # Must run BEFORE anything touches the backend — config.num_devices
        # may call jax.devices(), and jax.distributed.initialize refuses
        # to run after backend init.
        maybe_initialize_from_env()
        num_devices = self.config.num_devices

        if strategy is not None:
            self.strategy = strategy
        elif self.config.import_strategy_file:
            from .parallel.strategy import ParallelStrategy

            with open(self.config.import_strategy_file) as f:
                self.strategy = ParallelStrategy.from_json(f.read())
        elif self.config.pipeline_stages > 1:
            from .parallel.strategy import pipeline_strategy

            pp = self.config.pipeline_stages
            if num_devices % pp != 0:
                raise ValueError(f"{num_devices} devices not divisible by pipeline_stages={pp}")
            self.strategy = pipeline_strategy(
                self.graph,
                pp=pp,
                dp=num_devices // pp,
                n_microbatches=self.config.pipeline_microbatches,
            )
        elif self.config.only_data_parallel or self.config.search_budget <= 0:
            self.strategy = data_parallel_strategy(self.graph, num_devices)
        else:
            from .search.unity import unity_optimize

            with GLOBAL_STARTUP.span("search"):
                self.strategy, self._search_result = unity_optimize(self.graph, self.config)
            # adopt the rewritten PCG (reference: convert_graph_to_operators
            # model.cc:2856-2858); compute-node guids survive rewrites, so
            # frontend Tensor handles remain valid
            if self._search_result.graph is not None:
                self.graph = self._search_result.graph
        # a strategy built for (or exported from) a DIFFERENT graph has
        # guids matching nothing here; the GSPMD path would silently run
        # fully replicated (every sharding lookup misses) — the bench's
        # tp/hybrid measurements did exactly that until this guard; only
        # the pipeline path's stage_of validation caught its own case.
        # Strategies carry layer names (the reference's strategy files
        # are name-keyed, triton strategy.cc), so a structurally
        # identical rebuild remaps cleanly; anything else is an error.
        remapped = self.strategy.remap_to(self.graph)
        if remapped is None:
            raise ValueError(
                "strategy was built for a different graph: its node guids "
                "match nothing here and name-based remapping failed "
                "(missing or ambiguous layer names); rebuild or re-export "
                "the strategy against THIS model's graph"
            )
        self.strategy = remapped
        if self.config.export_strategy_file:
            with open(self.config.export_strategy_file, "w") as f:
                f.write(self.strategy.to_json())
        if self.config.export_strategy_computation_graph_file:
            with open(self.config.export_strategy_computation_graph_file, "w") as f:
                f.write(self.graph.to_dot())
        with GLOBAL_STARTUP.span("mesh"):
            self.mesh = build_mesh(self.strategy.axis_sizes)
        with GLOBAL_STARTUP.span("executor"):
            self.executor = CompiledExecutor(
                graph=self.graph,
                strategy=self.strategy,
                mesh=self.mesh,
                loss_type=loss_type,
                metric_types=tuple(metrics),
                optimizer=optimizer if comp_mode == CompMode.TRAINING else None,
                outputs=[(t.node.guid, t.idx) for t in self._outputs],
                backend=jax.default_backend(),
                comp_mode=comp_mode,
                remat_blocks=self.config.remat_blocks,
                zero_optimizer=self.config.zero_optimizer,
                grad_accum_steps=self.config.grad_accum_steps,
            )
        # host seconds: the initialisers' programs compile (or load) and
        # are dispatched here; the device may still be filling the
        # parameters when the span closes
        with GLOBAL_STARTUP.span("param_init"):
            self.executor.initialize(jax.random.key(self._seed))
        print(
            f"compiled: mesh {dict(zip(self.mesh.axis_names, self.mesh.devices.shape))}, "
            f"loss {loss_type.value if loss_type else None} ({self.executor.loss_form}); "
            f"start-up seconds so far: {GLOBAL_STARTUP.summary()}"
        )
        return self

    def _default_output(self) -> Tensor:
        sinks = self.graph.sink_nodes()
        if len(sinks) != 1:
            raise ValueError(f"model has {len(sinks)} sink nodes; pass outputs= to compile()")
        specs = infer_all_specs(self.graph)
        n = sinks[0]
        return Tensor(self, n, 0, specs[n.guid][0])

    # ----------------------------------------------------------------- fit
    @staticmethod
    def _as_batches(x, y):
        """Normalize dataset inputs: keep real (np/jnp) arrays as-is —
        device-resident data must not bounce through the host — and
        materialize anything else (lists, tuples) as numpy so the
        windowed slicing/reshape paths work on every accepted input."""

        def arr(a):
            return a if isinstance(a, (np.ndarray, jnp.ndarray)) else np.asarray(a)

        xs = [x] if isinstance(x, (np.ndarray, jnp.ndarray)) else list(x)
        return [arr(xx) for xx in xs], arr(y)

    @staticmethod
    def _iter_windows(xs, y, bs: int, steps: int, tw: int):
        """Yield (step, k, window_xs, window_y): full tw-step windows as
        stacked [k, bs, ...] arrays, tail steps (k == 1) as plain
        batches for the already-compiled eager program."""
        step = 0
        while step < steps:
            k = tw if steps - step >= tw else 1
            lo = step * bs
            if k > 1:
                hi = lo + k * bs
                yield step, k, [
                    xx[lo:hi].reshape((k, bs) + xx.shape[1:]) for xx in xs
                ], y[lo:hi].reshape((k, bs) + y.shape[1:])
            else:
                yield step, 1, [jnp.asarray(xx[lo:lo + bs]) for xx in xs], jnp.asarray(y[lo:lo + bs])
            step += k

    def fit(
        self,
        x: Union[np.ndarray, Sequence[np.ndarray]],
        y: np.ndarray,
        epochs: Optional[int] = None,
        batch_size: Optional[int] = None,
        verbose: bool = True,
        trace_window: Optional[int] = None,
    ) -> PerfMetrics:
        """Training loop (reference: FFModel.fit flexflow_cffi.py:2044).

        ``trace_window`` > 1 is the analog of the reference's Legion
        iteration tracing (begin_trace/end_trace, flexflow_cffi.py:
        2079-2086): that many steps run as ONE XLA program (lax.scan
        over stacked batches, executor.train_window), paying host
        dispatch once per window. Defaults to FFConfig.trace_window.
        Note: the windowed path derives per-step rng keys by splitting
        one per-window key, so models with rng-dependent training ops
        (dropout) follow a different — equally valid — randomness stream
        than the eager loop; deterministic models train identically.
        """
        assert self.executor is not None, "call compile() first"
        xs, y = self._as_batches(x, y)
        epochs = epochs or self.config.epochs
        bs = batch_size or self.config.batch_size
        tw = max(1, trace_window or self.config.trace_window)
        n = xs[0].shape[0]
        steps = n // bs
        rng = jax.random.key(self._seed + 1)
        perf = PerfMetrics()
        if self.config.profiling:  # reference: --profiling per-op timings
            self.profile(x=[xx[:bs] for xx in xs])
        interval = max(1, self.config.printing_interval)
        # fit()'s ELAPSED TIME report mirrors the reference CLI's wall
        # time; the training loop is not scheduler-plane code and has
        # no injectable clock to honor
        t0 = time.time()  # flexlint: disable=clock-discipline
        for epoch in range(epochs):
            # full windows run traced; tail steps (k == 1) run eagerly on
            # the already-compiled single-step program rather than paying
            # a whole extra XLA compile for a once-per-epoch window size
            for step, k, batch_x, batch_y in self._iter_windows(xs, y, bs, steps, tw):
                rng, sub = jax.random.split(rng)
                # one profiler step per dispatch (a window of k steps is
                # one program): xprof's step analysis then lines up with
                # the executor's ff.train.* spans inside it
                with jax.profiler.StepTraceAnnotation(
                    "ff.train.step", step_num=epoch * steps + step
                ):
                    run = self.executor.train_window if k > 1 else self.executor.train_batch
                    mets = run(batch_x, batch_y, sub)
                if k > 1:
                    host = {kk: np.asarray(v) for kk, v in mets.items()}
                    for i in range(k):
                        perf.update({kk: float(v[i]) for kk, v in host.items() if kk != "loss"})
                        if verbose and (step + i) % interval == 0:
                            print(
                                f"epoch {epoch} step {step + i}/{steps} "
                                f"loss {float(host.get('loss', np.zeros(k))[i]):.4f} acc {perf.accuracy:.4f}"
                            )
                else:
                    perf.update({kk: float(v) for kk, v in mets.items() if kk != "loss"})
                    if verbose and step % interval == 0:
                        loss = float(mets.get("loss", 0.0))
                        print(f"epoch {epoch} step {step}/{steps} loss {loss:.4f} acc {perf.accuracy:.4f}")
        elapsed = time.time() - t0  # flexlint: disable=clock-discipline
        thru = epochs * steps * bs / max(1e-9, elapsed)
        if verbose:
            print(f"ELAPSED TIME = {elapsed:.4f}s THROUGHPUT = {thru:.2f} samples/s")
        self.last_elapsed = elapsed
        self.last_throughput = thru
        return perf

    def evaluate(
        self, x, y, batch_size: Optional[int] = None, trace_window: Optional[int] = None
    ) -> PerfMetrics:
        assert self.executor is not None
        xs, y = self._as_batches(x, y)
        bs = batch_size or self.config.batch_size
        tw = max(1, trace_window or self.config.trace_window)
        steps = xs[0].shape[0] // bs
        perf = PerfMetrics()
        for _, k, batch_x, batch_y in self._iter_windows(xs, y, bs, steps, tw):
            if k > 1:
                wmets = self.executor.eval_window(batch_x, batch_y)
                host = {kk: np.asarray(v) for kk, v in wmets.items()}
                for i in range(k):
                    perf.update({kk: float(v[i]) for kk, v in host.items() if kk != "loss"})
            else:
                mets = self.executor.eval_batch(batch_x, batch_y)
                perf.update({kk: float(v) for kk, v in mets.items() if kk != "loss"})
        return perf

    def predict(self, x) -> jax.Array:
        xs = [x] if isinstance(x, (np.ndarray, jnp.ndarray)) else list(x)
        return self.executor.predict([jnp.asarray(xx) for xx in xs])[0]

    # --------------------------------------------- checkpoint / dataloader
    def save_checkpoint(self, path: str, step: int = 0) -> None:
        """Save weights + optimizer state + strategy (new capability vs the
        reference, which only had weight get/set — SURVEY.md §5)."""
        from .runtime.checkpoint import save_checkpoint

        assert self.executor is not None, "compile() first"
        save_checkpoint(path, self.executor, step=step, strategy=self.strategy)

    def load_checkpoint(self, path: str) -> int:
        from .runtime.checkpoint import restore_checkpoint

        assert self.executor is not None, "compile() first"
        return restore_checkpoint(path, self.executor)

    def create_data_loader(self, x, y, batch_size: Optional[int] = None, shuffle: bool = True):
        """Reference: FFModel.create_data_loader (flexflow_cffi.py:2178).
        Batches land pre-sharded per the compiled strategy when available."""
        from .runtime.dataloader import DataLoader

        xs = [x] if isinstance(x, (np.ndarray, jnp.ndarray)) else list(x)
        shardings = label_sharding = None
        if self.executor is not None:
            shardings, label_sharding = self.executor.input_shardings()
        return DataLoader(
            xs,
            y,
            batch_size or self.config.batch_size,
            shuffle=shuffle,
            shardings=shardings,
            label_sharding=label_sharding,
        )

    def profile(self, x=None, verbose: bool = True):
        """Per-op forward timing table (reference: --profiling cudaEvent
        brackets in every kernel, e.g. linear_kernels.cu:95-118)."""
        from .runtime.profiling import format_profiles, profile_step

        assert self.executor is not None, "call compile() first"
        if x is None:
            specs = infer_all_specs(self.graph)
            ins = sorted(
                (n for n in self.graph.nodes.values() if n.op_type == OpType.INPUT),
                key=lambda n: n.params.input_index,
            )
            rs = np.random.RandomState(0)
            x = []
            for n in ins:
                s = specs[n.guid][0]
                if s.dtype.jnp in (jnp.int32, jnp.int64):
                    x.append(rs.randint(0, 2, s.shape).astype(np.int32))
                else:
                    x.append(rs.randn(*s.shape).astype(np.float32))
        profiles = profile_step(self.executor, x)
        if verbose:
            print(format_profiles(profiles))
        return profiles

    def recompile_on_condition(self, trigger, alter):
        """Reference: FFModel::recompile_on_condition (model.cc:2430)."""
        from .runtime.recompile import RecompileState

        assert self.executor is not None, "call compile() first"
        return RecompileState(trigger, alter, self)

    # ------------------------------------------------------- introspection
    def parallel_tensor(self, tensor: Tensor):
        """How ``tensor`` is sharded under the compiled strategy
        (reference: ParallelTensorBase's per-dim degree / replica dims,
        parallel_tensor.h:36-71 — here surfaced from the strategy's
        PartitionSpecs instead of Legion partitions)."""
        from .core.parallel_tensor import view_from_spec

        assert self.strategy is not None, "compile() first"
        sh = self.strategy.node_shardings.get(tensor.node.guid)
        spec = self.strategy.output_spec(tensor.node.guid, tensor.idx)
        return view_from_spec(
            tensor.spec,
            spec,
            self.strategy.axis_sizes,
            machine_view_hash=sh.machine_view_hash if sh else 0,
        )

    def parallel_weight(self, tensor: Tensor, name: str):
        """Sharding view of one of ``tensor``'s op's weights."""
        from .core.parallel_tensor import view_from_spec
        from .ops.base import get_op_def

        assert self.strategy is not None, "compile() first"
        node = tensor.node
        specs = infer_all_specs(self.graph)
        in_specs = [specs[e.src][e.src_idx] for e in self.graph.in_edges(node)]
        wspecs = {w.name: w for w in get_op_def(node.op_type).weight_specs(node.params, in_specs)}
        if name not in wspecs:
            raise KeyError(f"op {node} has no weight {name!r}; has {sorted(wspecs)}")
        sh = self.strategy.node_shardings.get(node.guid)
        return view_from_spec(
            wspecs[name].spec,
            self.strategy.weight_spec(node.guid, name),
            self.strategy.axis_sizes,
            machine_view_hash=sh.machine_view_hash if sh else 0,
        )

    def get_weight(self, tensor: Tensor, name: str) -> np.ndarray:
        """Gather one weight to host (reference:
        ParallelTensorBase::get_tensor, parallel_tensor.h:165-169, the
        cffi get-weights path)."""
        assert self.executor is not None, "compile() first"
        key = f"{tensor.node.op_type.value}_{tensor.node.guid}"
        have = []
        for store in (self.executor.params, self.executor.state):
            group = store.get(key) or {}
            if name in group:
                return np.asarray(jax.device_get(group[name]))
            have.extend(group)
        raise KeyError(f"no weight {name!r} on {key}; has {sorted(have)}")

    def set_weight(self, tensor: Tensor, name: str, value) -> None:
        """Write one weight from host data, preserving its sharding
        (reference: ParallelTensorBase::set_tensor)."""
        assert self.executor is not None, "compile() first"
        key = f"{tensor.node.op_type.value}_{tensor.node.guid}"
        for store in (self.executor.params, self.executor.state):
            group = store.get(key)
            if group is not None and name in group:
                cur = group[name]
                arr = np.asarray(value, dtype=np.asarray(cur).dtype)
                if arr.shape != cur.shape:
                    raise ValueError(f"shape {arr.shape} != {cur.shape} for {key}.{name}")
                group[name] = jax.device_put(arr, cur.sharding)
                return
        raise KeyError(f"no weight {name!r} on {key}")

    def get_output(self) -> Tensor:
        return self._outputs[0] if self._outputs else self._default_output()

    def num_layers(self) -> int:
        return sum(1 for n in self.graph.nodes.values() if n.op_type != OpType.INPUT)
