"""Serving layout: how the generation engine's state maps onto a device
mesh (ISSUE 15 — multi-chip sharded generation).

The layout is Megatron/Pope-style intra-layer tensor parallelism over
the ``"model"`` mesh axis (parallel/mesh.py ``serving_mesh``), chosen
for DECODE: the KV cache — the thing that actually outgrows one chip in
serving — shards along its head axis, each shard's attention runs over
its LOCAL KV heads only, and the single cross-shard boundary is the
partial-sum reduction at the attention output projection (GSPMD lowers
it to a psum on ICI, exactly the collective ops/parallel_ops.py's
``ReductionOp`` annotates in the training path).

Per-leaf placement of the decoder pytree (decoder.py):

  wq/wk/wv  [E, H, D]   head axis sharded      P(None, "model", None)
  wo        [H, D, E]   head axis sharded      P("model", None, None)
                        (row-parallel: contraction over the sharded H
                        produces partials -> ONE psum per layer at the
                        attention output)
  ff1       [E, F]      column-parallel        P(None, "model")
  ff2       [F, E]      row-parallel           P("model", None)
                        (only when tp divides F; otherwise replicated —
                        the layout degrades, it never errors)
  everything else       replicated             P()

and of the engine's runtime state:

  KV cache k/v [L, num_blocks, block_size, R, LW]  P(None, None, None,
                                                    "model", None)
                  (a position's H x D values stored as R rows, packed
                  shard by shard — cache.py — so the row axis shards
                  exactly as the head axis does)
  block tables / positions / sampling params / tokens   replicated

Block tables and the host-side allocator are therefore device-count-
agnostic: a block id means the same (block, offset) slot on every
shard, only the head slice living there differs. A 1-device mesh makes
every spec a no-op — the engine is bit-for-bit the single-device
engine, which is the exactness anchor the multi-device tests
(tests/test_mesh_generation.py) compare against.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..parallel.mesh import MODEL_AXIS, serving_mesh


def validate_kv_shards(num_kv_heads: int, tp_degree: int) -> None:
    """KV heads divide across shards — a non-dividing degree would need
    uneven head slices the fixed-shape jits cannot express."""
    if tp_degree < 1:
        raise ValueError(f"tp_degree must be >= 1, got {tp_degree}")
    if num_kv_heads % tp_degree != 0:
        raise ValueError(
            f"num_kv_heads % tp_degree != 0: {num_kv_heads} KV heads do "
            f"not divide across {tp_degree} shards; pick a tp_degree "
            f"that divides the head count"
        )


@dataclasses.dataclass(frozen=True)
class ServingLayout:
    """One engine's mesh + the NamedShardings its jits are built with."""

    mesh: Mesh
    tp_degree: int
    num_heads: int

    @classmethod
    def build(
        cls,
        num_heads: int,
        tp_degree: int = 1,
        mesh: Optional[Mesh] = None,
        devices=None,
    ) -> "ServingLayout":
        validate_kv_shards(num_heads, tp_degree)
        if mesh is None:
            mesh = serving_mesh(tp_degree, devices)
        elif MODEL_AXIS not in mesh.axis_names:
            raise ValueError(
                f"serving mesh must carry a '{MODEL_AXIS}' axis, got "
                f"{mesh.axis_names}"
            )
        return cls(mesh=mesh, tp_degree=tp_degree, num_heads=num_heads)

    # ------------------------------------------------------------ shardings
    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    @property
    def replicated(self) -> NamedSharding:
        return self.sharding()

    @property
    def cache_sharding(self) -> NamedSharding:
        """KV cache [L, num_blocks, block_size, R, LW]: rows, that is
        heads, sharded."""
        return self.sharding(None, None, None, MODEL_AXIS, None)

    def param_shardings(self, params: Dict[str, Any]) -> Dict[str, Any]:
        """Per-leaf NamedSharding pytree matching the decoder params."""
        repl = self.replicated
        head_in = self.sharding(None, MODEL_AXIS, None)  # wq/wk/wv [E,H,D]
        head_out = self.sharding(MODEL_AXIS, None, None)  # wo [H,D,E]

        def layer_shardings(layer: Dict[str, Any]) -> Dict[str, Any]:
            out = {k: repl for k in layer}
            if "wq" in layer:  # an attention layer (decoder.py: layer_types)
                out["wq"] = out["wk"] = out["wv"] = head_in
                out["wo"] = head_out
            # Megatron MLP: column-parallel up, row-parallel down — only
            # when the mesh degree divides the ff width; an odd width
            # degrades to replicated FFN compute instead of failing the
            # build
            if "ff1" in layer and layer["ff1"].shape[1] % self.tp_degree == 0:
                out["ff1"] = self.sharding(None, MODEL_AXIS)
                out["ff2"] = self.sharding(MODEL_AXIS, None)
            return out

        return {
            **{k: repl for k in params if k != "layers"},
            "layers": [layer_shardings(l) for l in params["layers"]],
        }

    def shard_params(self, params: Dict[str, Any]) -> Dict[str, Any]:
        """Commit the decoder pytree onto the mesh per the layout."""
        return jax.tree_util.tree_map(
            jax.device_put, params, self.param_shardings(params)
        )

    def put_replicated(self, x):
        """Commit a host array onto the mesh, replicated. Every
        non-sharded jit input goes through here so input shardings are
        identical call to call — a drifting placement would recompile
        the fixed-shape programs (the zero-steady-state-retrace
        contract)."""
        return jax.device_put(x, self.replicated)

    def describe(self) -> Dict[str, Any]:
        """Metadata block: mesh geometry + the per-tensor specs."""
        return {
            "tp_degree": self.tp_degree,
            "mesh_devices": self.mesh.size,
            "mesh_axes": {
                name: int(size) for name, size in self.mesh.shape.items()
            },
            "kv_heads_per_shard": self.num_heads // self.tp_degree,
            "specs": {
                "cache_kv": f"[L, blocks, block, H/{self.tp_degree}, D]",
                "wq/wk/wv": f"[E, H/{self.tp_degree}, D]",
                "wo": f"[H/{self.tp_degree}, D, E]",
                "block_tables": "replicated",
                "sampling_state": "replicated",
            },
        }
