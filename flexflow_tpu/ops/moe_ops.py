"""Mixture-of-Experts operator family: TopK, GroupBy, Aggregate,
AggregateSpec, Cache.

Reference: src/ops/topk.cc (437), group_by.cc (534), aggregate.cc (569,
with the lambda_bal load-balancing gradient), aggregate_spec.cc (519),
cache.cc (291, score-triggered recompile). The reference moves tokens
with CUDA scatter kernels into per-expert buffers of capacity
``alpha * k * B / n``. TPU-native: identical static-capacity semantics,
implemented with one-hot matmuls, cumsum position assignment and
scatter — all static shapes so XLA can compile them; expert parallelism
lays experts on a mesh axis and XLA's all_to_all moves the tokens.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List

import jax
import jax.numpy as jnp

from ..core.tensor import TensorSpec
from ..core.types import ActiMode, DataType, OpType
from .base import LowerCtx, OpCost, OpDef, io_cost, register_op


@dataclasses.dataclass(frozen=True)
class TopKParams:
    k: int
    sorted: bool = True


@register_op
class TopKOp(OpDef):
    op_type = OpType.TOPK
    params_cls = TopKParams

    @staticmethod
    def infer_output_specs(params: TopKParams, input_specs: List[TensorSpec]):
        (x,) = input_specs
        shape = x.shape[:-1] + (params.k,)
        return [TensorSpec(shape, x.dtype), TensorSpec(shape, DataType.INT32)]

    @staticmethod
    def lower(params: TopKParams, inputs, weights, ctx):
        values, indices = jax.lax.top_k(inputs[0], params.k)
        return [values, indices.astype(jnp.int32)]

    @staticmethod
    def cost(params, input_specs, output_specs):
        n = input_specs[0].num_elements
        return io_cost(input_specs, output_specs, flops=float(n) * math.log2(max(2, input_specs[0].shape[-1])))


def expert_capacity(batch: int, k: int, n_experts: int, alpha: float) -> int:
    """Per-expert token capacity (reference: group_by.cc capacity calc)."""
    return max(1, int(math.ceil(alpha * k * batch / n_experts)))


def _dispatch_positions(assign: jax.Array, n: int):
    """(flat_assign [B*K], pos_in_expert [B*K]): each (token, slot)'s
    0-based position within its expert's buffer, via masked cumsum."""
    flat_assign = assign.reshape(-1).astype(jnp.int32)
    onehot = jax.nn.one_hot(flat_assign, n, dtype=jnp.int32)  # [B*K, n]
    pos = jnp.cumsum(onehot, axis=0) * onehot  # 1-based position per expert
    return flat_assign, jnp.sum(pos, axis=-1) - 1


def _dispatch_stacked(data, assign, n: int, cap: int) -> jax.Array:
    """ONE dense-capacity scatter of tokens into [n, cap, D] (round-2 fix:
    the per-expert Python scatter loop was O(n_experts) HLO for the
    reference's 64-expert configs, examples/cpp/mixture_of_experts)."""
    b, d = data.shape
    k = assign.shape[-1]
    flat_assign, pos_in_expert = _dispatch_positions(assign, n)
    token_idx = jnp.repeat(jnp.arange(b), k)
    valid = pos_in_expert < cap
    dst = jnp.where(valid, flat_assign * cap + pos_in_expert, n * cap)  # row n*cap = dropped
    buf = jnp.zeros((n * cap + 1, d), data.dtype).at[dst].set(data[token_idx])
    return buf[: n * cap].reshape(n, cap, d)


@dataclasses.dataclass(frozen=True)
class GroupByParams:
    n_experts: int
    alpha: float = 1.0  # capacity factor
    stacked: bool = False  # True -> single [n, cap, D] output (feeds ExpertsOp)


@register_op
class GroupByOp(OpDef):
    """Scatter tokens into per-expert buffers.

    Inputs: data [B, D], assignments [B, K] (int expert ids).
    Outputs: n_experts tensors [capacity, D] — or, with stacked=True, ONE
    [n_experts, capacity, D] tensor whose leading dim shards over the
    expert mesh axis (token routing becomes a GSPMD all_to_all).
    Overflowing tokens are dropped (same drop semantics as the
    reference's fixed-size buffers, group_by.cc).
    """

    op_type = OpType.GROUP_BY
    params_cls = GroupByParams

    @staticmethod
    def infer_output_specs(params: GroupByParams, input_specs: List[TensorSpec]):
        data, assign = input_specs
        b, d = data.shape
        cap = expert_capacity(b, assign.shape[-1], params.n_experts, params.alpha)
        if params.stacked:
            return [TensorSpec((params.n_experts, cap, d), data.dtype)]
        return [TensorSpec((cap, d), data.dtype) for _ in range(params.n_experts)]

    @staticmethod
    def lower(params: GroupByParams, inputs, weights, ctx: LowerCtx):
        data, assign = inputs
        b, d = data.shape
        n = params.n_experts
        cap = expert_capacity(b, assign.shape[-1], n, params.alpha)
        buf = _dispatch_stacked(data, assign, n, cap)
        if params.stacked:
            return [buf]
        return [buf[e] for e in range(n)]

    @staticmethod
    def cost(params: GroupByParams, input_specs, output_specs):
        return io_cost(input_specs, output_specs, flops=2.0 * input_specs[0].num_elements)


@dataclasses.dataclass(frozen=True)
class ExpertsParams:
    """Batched two-layer expert FFN (reference: the n per-expert Dense
    pairs of FFModel::moe, src/ops/moe.cc:20; here ONE op whose weights
    carry a leading expert dim, so expert parallelism is just sharding
    that dim over the mesh's expert/model axis)."""

    n_experts: int
    hidden_size: int
    out_dim: int
    activation: ActiMode = ActiMode.RELU
    dtype: DataType = DataType.FLOAT


@register_op
class ExpertsOp(OpDef):
    """[n, cap, D] -> [n, cap, out_dim] batched expert MLP.

    When the mesh has an expert-bearing axis ("expert", else "model")
    that divides n_experts, compute runs under shard_map with each device
    applying only its local experts — weights never move; tokens ride the
    GSPMD all_to_all at the shard_map boundary (the TPU-native form of
    the reference's per-expert machine views, moe.cc:180-204).
    """

    op_type = OpType.EXPERTS
    params_cls = ExpertsParams

    @staticmethod
    def infer_output_specs(params: ExpertsParams, input_specs: List[TensorSpec]):
        x = input_specs[0]
        return [TensorSpec((x.shape[0], x.shape[1], params.out_dim), params.dtype)]

    @staticmethod
    def weight_specs(params: ExpertsParams, input_specs: List[TensorSpec]):
        from .base import WeightSpec

        d = input_specs[0].shape[-1]
        n, h, o = params.n_experts, params.hidden_size, params.out_dim
        dt = params.dtype
        return [
            WeightSpec("w1", TensorSpec((n, d, h), dt), "glorot_uniform"),
            WeightSpec("b1", TensorSpec((n, h), dt), "zeros"),
            WeightSpec("w2", TensorSpec((n, h, o), dt), "glorot_uniform"),
            WeightSpec("b2", TensorSpec((n, o), dt), "zeros"),
        ]

    @staticmethod
    def _apply(x, w1, b1, w2, b2, activation):
        from .elementwise import apply_activation

        h = jnp.einsum("ncd,ndh->nch", x, w1) + b1[:, None, :]
        h = apply_activation(activation, h)
        return jnp.einsum("nch,nho->nco", h, w2) + b2[:, None, :]

    @staticmethod
    def lower(params: ExpertsParams, inputs, weights, ctx: LowerCtx):
        x = inputs[0]
        w1, b1, w2, b2 = weights["w1"], weights["b1"], weights["w2"], weights["b2"]
        mesh = getattr(ctx, "mesh", None)
        axis = None
        if mesh is not None:
            for cand in ("expert", "model"):
                if cand in mesh.axis_names and mesh.shape[cand] > 1 and params.n_experts % mesh.shape[cand] == 0:
                    axis = cand
                    break
        if axis is not None:
            from jax.sharding import PartitionSpec as P

            def local(x, w1, b1, w2, b2):
                # each device: only its n/E experts; tokens arrived via
                # the boundary all_to_all
                return ExpertsOp._apply(x, w1, b1, w2, b2, params.activation)

            ep = P(axis, None, None)
            e2 = P(axis, None)
            y = jax.shard_map(
                local,
                mesh=mesh,
                in_specs=(ep, ep, e2, ep, e2),
                out_specs=ep,
            )(x, w1, b1, w2, b2)
        else:
            y = ExpertsOp._apply(x, w1, b1, w2, b2, params.activation)
        return [y.astype(params.dtype.jnp)]

    @staticmethod
    def cost(params: ExpertsParams, input_specs, output_specs):
        n, cap, d = input_specs[0].shape
        flops = 2.0 * n * cap * d * params.hidden_size + 2.0 * n * cap * params.hidden_size * params.out_dim
        w_bytes = (n * d * params.hidden_size + n * params.hidden_size * params.out_dim) * params.dtype.size_bytes
        c = io_cost(input_specs, output_specs, flops=flops, extra_mem=w_bytes)
        c.bytes_accessed += w_bytes
        return c


@dataclasses.dataclass(frozen=True)
class AggregateParams:
    n_experts: int
    lambda_bal: float = 0.0  # load-balance aux loss weight (aggregate.cc)
    alpha: float = 1.0


@register_op
class AggregateOp(OpDef):
    """Gather expert outputs back to token order, weighted by gate scores.

    Inputs: gate_preds [B, K], gate_assign [B, K], then n_experts tensors
    [capacity, D] (reference aggregate.cc input layout, minus the
    backward-only full_gate_grads which autodiff makes unnecessary).
    Output: [B, D].
    """

    op_type = OpType.AGGREGATE
    params_cls = AggregateParams

    @staticmethod
    def infer_output_specs(params: AggregateParams, input_specs: List[TensorSpec]):
        gate = input_specs[0]
        d = input_specs[2].shape[-1]
        return [TensorSpec((gate.shape[0], d), input_specs[2].dtype)]

    @staticmethod
    def _gather_rows(gate_assign, experts, n: int):
        """Expert rows in (token, slot) order: [B*K, D]. ``experts`` is
        either a single stacked [n, cap, D] tensor or n [cap, D] tensors."""
        stacked = experts[0] if len(experts) == 1 and experts[0].ndim == 3 else jnp.stack(experts)
        cap = stacked.shape[1]
        flat_assign, pos_in_expert = _dispatch_positions(gate_assign, n)
        valid = pos_in_expert < cap
        rows = stacked[flat_assign, jnp.clip(pos_in_expert, 0, cap - 1)]  # [B*K, D]
        return jnp.where(valid[:, None], rows, 0.0), flat_assign

    @staticmethod
    def lower(params: AggregateParams, inputs, weights, ctx: LowerCtx):
        gate_preds, gate_assign = inputs[0], inputs[1]
        b, k = gate_preds.shape
        n = params.n_experts
        rows, flat_assign = AggregateOp._gather_rows(gate_assign, inputs[2:], n)
        d = rows.shape[-1]
        w = gate_preds.reshape(-1)[:, None].astype(rows.dtype)
        out = jnp.sum((rows * w).reshape(b, k, d), axis=1)
        if params.lambda_bal > 0.0:
            # load-balance aux loss (reference: aggregate.cc lambda_bal):
            # penalize squared per-expert token fractions (Shazeer-style)
            frac = jnp.mean(jax.nn.one_hot(flat_assign, n, dtype=jnp.float32), axis=0)
            imp = jnp.mean(
                jax.nn.one_hot(flat_assign, n, dtype=jnp.float32)
                * gate_preds.reshape(-1, 1).astype(jnp.float32),
                axis=0,
            )
            ctx.aux_losses.append(params.lambda_bal * n * jnp.sum(frac * imp))
        return [out]

    @staticmethod
    def cost(params, input_specs, output_specs):
        return io_cost(input_specs, output_specs, flops=3.0 * output_specs[0].num_elements)


@dataclasses.dataclass(frozen=True)
class AggregateSpecParams:
    n_experts: int
    lambda_bal: float = 0.0
    alpha: float = 1.0


@register_op
class AggregateSpecOp(OpDef):
    """Speculative-assignment variant (reference: aggregate_spec.cc/.cu).

    Forward (aggspec_forward_kernel, aggregate_spec.cu:21-63): outputs
    every chosen expert's prediction SEPARATELY, [B*K, D] — NOT the
    gate-weighted sum — so the loss evaluates each speculative routing.
    Backward to the gate (aggspec_backward_kernel_gate, :64-127) is a
    hand-crafted rule, not the forward's transpose: each selected gate
    score's gradient is its normalized share of the squared output error
    minus (1 - gate_pred), plus the lambda_bal balance term, mean-centered
    across experts. Implemented with jax.custom_vjp; expert gradients use
    the standard scatter transpose.
    """

    op_type = OpType.AGGREGATE_SPEC
    params_cls = AggregateSpecParams

    @staticmethod
    def infer_output_specs(params: AggregateSpecParams, input_specs: List[TensorSpec]):
        gate = input_specs[0]
        d = input_specs[2].shape[-1]
        return [TensorSpec((gate.shape[0] * gate.shape[1], d), input_specs[2].dtype)]

    @staticmethod
    def lower(params: AggregateSpecParams, inputs, weights, ctx: LowerCtx):
        gate_preds, gate_assign = inputs[0], inputs[1]
        experts = tuple(inputs[2:])
        n = params.n_experts
        lambda_bal = params.lambda_bal

        @jax.custom_vjp
        def agg_spec(gate_preds, experts):
            rows, _ = AggregateOp._gather_rows(gate_assign, experts, n)
            return rows  # [B*K, D]

        def fwd(gate_preds, experts):
            rows, flat_assign = AggregateOp._gather_rows(gate_assign, experts, n)
            return rows, (gate_preds, experts, flat_assign)

        def bwd(res, g):
            gate_preds, experts, flat_assign = res
            b, k = gate_preds.shape
            # expert grads: standard transpose of the gather (linear part)
            def gather_only(experts):
                rows, _ = AggregateOp._gather_rows(gate_assign, experts, n)
                return rows

            _, exp_vjp = jax.vjp(gather_only, experts)
            (experts_grad,) = exp_vjp(g)
            # gate grads: reference rule (aggregate_spec.cu:87-126)
            err = jnp.sum(jnp.square(g.astype(jnp.float32)), axis=-1) * b  # [B*K]
            full = jnp.zeros((b, n), jnp.float32)
            bi = jnp.repeat(jnp.arange(b), k)
            full = full.at[bi, flat_assign].add(err)
            err_sum = jnp.sum(err.reshape(b, k), axis=-1, keepdims=False)  # [B]
            full = full / jnp.maximum(err_sum, 1e-20)[:, None]
            # -(1 - gate_pred) on each selected entry
            full = full.at[bi, flat_assign].add(-(1.0 - gate_preds.reshape(-1).astype(jnp.float32)))
            if lambda_bal > 0.0:
                counts = jnp.sum(jax.nn.one_hot(flat_assign, n, dtype=jnp.float32), axis=0)
                full = full + lambda_bal * counts[None, :]
            full = full - jnp.mean(full, axis=-1, keepdims=True)  # zero-mean over experts
            gate_grad = full[bi, flat_assign].reshape(b, k).astype(gate_preds.dtype)
            return gate_grad, experts_grad

        agg_spec.defvjp(fwd, bwd)
        return [agg_spec(gate_preds, experts)]

    @staticmethod
    def cost(params, input_specs, output_specs):
        return io_cost(input_specs, output_specs, flops=3.0 * output_specs[0].num_elements)


@dataclasses.dataclass(frozen=True)
class CacheParams:
    num_batches: int = 1
    trigger_threshold: float = 0.0


@register_op
class CacheOp(OpDef):
    """Input-caching op (reference: cache.cc): stores recent batches and
    scores drift to trigger recompilation. Forward is identity; the
    scoring/trigger logic lives in runtime/recompile.py on host."""

    op_type = OpType.CACHE
    params_cls = CacheParams

    @staticmethod
    def infer_output_specs(params, input_specs: List[TensorSpec]):
        return [input_specs[0]]

    @staticmethod
    def lower(params, inputs, weights, ctx):
        return [inputs[0]]

    @staticmethod
    def cost(params, input_specs, output_specs):
        return OpCost()
