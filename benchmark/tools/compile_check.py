#!/usr/bin/env python3
"""Deviceless v5e compiles at real widths: what fits, before any chip
time is spent. Run in the sandbox (``JAX_PLATFORMS=cpu``):

    python benchmark/tools/compile_check.py serve --slots 8 12 16
    python benchmark/tools/compile_check.py train --batch 8 16 32
    python benchmark/tools/compile_check.py train4 --batch 32

libtpu compiles for a chip that is described and not attached
(``jax.experimental.topologies``); ``memory_analysis()`` of the compiled
program gives its arguments, outputs, temporaries and what donation
aliases. The rule the cells' files quote: a setting fits if every
program of the cell leaves 1 GiB of the chip's 15.75 GiB to spare,
counting what the process holds beside the program (weights, cache,
optimizer state). Nothing here runs a program or reports a time.

``serve`` builds the cell's engine with abstract weights, points its
kernel dispatch at the TPU path, and compiles the decode program and
``prefill[1024]``; ``train`` compiles the one-chip train step; ``train4``
runs the Unity search as the v5e would see it (the chip's own cost
table, four devices) and compiles the step it chooses over a 2x2 mesh.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

USABLE = 15.75 * 2**30
SPARE = 1.0 * 2**30
GIB = 2.0**30


def report(label: str, compiled) -> bool:
    m = compiled.memory_analysis()
    args_, out, tmp, alias = (m.argument_size_in_bytes, m.output_size_in_bytes,
                              m.temp_size_in_bytes, m.alias_size_in_bytes)
    peak = args_ + out + tmp - alias
    fits = peak <= USABLE - SPARE
    print(f"{label}: arguments {args_ / GIB:.2f} + outputs {out / GIB:.2f} + temporaries "
          f"{tmp / GIB:.2f} - aliased {alias / GIB:.2f} "
          f"= {peak / GIB:.2f} GiB -> {'fits' if fits else 'DOES NOT FIT'} with 1 GiB to spare "
          f"of {USABLE / GIB:.2f}", flush=True)
    return fits


def topology():
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


def take_tpu_paths():
    """Code that asks ``on_tpu()`` sees the CPU here; steer the kernel
    dispatch to the path the chip takes (the verify skill's recipe)."""
    import flexflow_tpu.ops.attention as attention

    attention.on_tpu = lambda: True


def serve(slots_list) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import spec
    from flexflow_tpu.generation import GenerationEngine, init_decoder_params
    from flexflow_tpu.models.transformer import TransformerConfig

    take_tpu_paths()
    one = SingleDeviceSharding(topology().devices[0])
    cell = spec.load_cell("gpt2-medium.prompt-batch")
    c, d = cell.config, cell.workload["deployment"]
    cfg = TransformerConfig(
        num_layers=c["n_layer"], hidden_size=c["n_embd"], num_heads=c["n_head"],
        ff_size=c["n_inner"], seq_length=c["n_positions"], vocab_size=c["vocab_size"], causal=True,
    )
    shapes = jax.eval_shape(lambda k: init_decoder_params(k, cfg), jax.random.key(0))
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), shapes)
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    key = jax.eval_shape(lambda: jax.random.key(0))
    for slots in slots_list:
        engine = GenerationEngine(
            shapes, cfg, max_batch_slots=slots, block_size=int(d["block_size"]),
            prompt_buckets=[1024], max_seq_len=int(d["max_seq_len"]),
        )
        engine.backend = "tpu"
        b, mb, v = slots, engine.max_blocks_per_seq, cfg.vocab_size
        ck = sds(engine.cache.k.shape, engine.cache.k.dtype)
        cache_bytes = 2 * engine.cache.k.size * engine.cache.k.dtype.itemsize
        i32, f32 = jnp.int32, jnp.float32
        t0 = time.time()
        dec = jax.jit(engine._decode_impl, donate_argnums=(3, 4)).lower(
            params, sds((b,), i32), sds((b,), i32), ck, ck, sds((b, mb), i32), sds((b,), i32),
            sds((b,), f32), sds((b,), i32), sds((b,), f32), sds((b,), jnp.uint32), sds((b,), i32),
            sds((b, v), f32),
        ).compile()
        ok_d = report(f"serve slots={slots} decode (cache {cache_bytes / GIB:.2f} GiB, weights "
                      f"{weights / GIB:.2f} GiB; {time.time() - t0:.0f}s)", dec)
        t0 = time.time()
        pre = jax.jit(engine._prefill_impl).lower(
            params, sds((1, 1024), i32), sds((), i32), ck, ck, sds((mb,), i32), sds((), f32),
            sds((), i32), jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one), sds((v,), f32),
        ).compile()
        # prefill does not donate: the old cache lives beside the new one
        ok_p = report(f"serve slots={slots} prefill[1024] ({time.time() - t0:.0f}s)", pre)
        print(f"serve slots={slots}: {'FITS' if ok_d and ok_p else 'does not fit'}", flush=True)
        del engine


def _train_model(cell_name: str, batch: int):
    from benchmark import spec
    from benchmark.drivers import train as driver

    cell = spec.load_cell(cell_name)
    model, _ = driver.build_model(cell, 0, batch, int(cell.traffic["params"]["seq"]))
    return model


def _compile_train_step(model, mesh, label: str) -> bool:
    """Lower the executor's own train step for the described chips: the
    executor reads ``self.mesh`` and ``self.backend`` when it traces."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    ex = model.executor
    ex.mesh, ex.backend = mesh, "tpu"

    def on_mesh(a):
        spec_ = getattr(a.sharding, "spec", PartitionSpec())
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=NamedSharding(mesh, spec_))

    params, opt, state = (jax.tree.map(on_mesh, t) for t in (ex.params, ex.opt_state, ex.state))
    in_sh, lab_sh = ex.input_shardings()
    b, s = model.config.batch_size, model._outputs[0].shape[1]
    tokens = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=in_sh[0])
    labels = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=lab_sh)
    key = jax.eval_shape(lambda: jax.random.key(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=NamedSharding(mesh, PartitionSpec()))
    t0 = time.time()
    compiled = jax.jit(ex._train_step_fn, donate_argnums=(0, 1, 2)).lower(
        params, opt, state, (tokens,), labels, key
    ).compile()
    hlo = compiled.as_text()
    print(f"{label}: compiled in {time.time() - t0:.0f}s; Mosaic calls {hlo.count('tpu_custom_call')}, "
          f"all-reduce {hlo.count(' all-reduce(') + hlo.count(' all-reduce-start(')}, "
          f"all-gather {hlo.count(' all-gather(') + hlo.count(' all-gather-start(')}, "
          f"reduce-scatter {hlo.count(' reduce-scatter(')}", flush=True)
    return report(label + " (per chip)", compiled)


def train(batches) -> None:
    import numpy as np
    from jax.sharding import Mesh

    from flexflow_tpu import AdamOptimizer, LossType

    take_tpu_paths()
    topo = topology()
    for batch in batches:
        model = _train_model("bert-large.mlm-s512", batch)
        model.compile(optimizer=AdamOptimizer(alpha=1e-4),
                      loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
        mesh = Mesh(np.array(topo.devices[:1]), model.mesh.axis_names)
        ok = _compile_train_step(model, mesh, f"train batch={batch}")
        print(f"train batch={batch}: {'FITS' if ok else 'does not fit'}", flush=True)
        del model


def train4(batches) -> None:
    import numpy as np
    from jax.sharding import Mesh

    import flexflow_tpu.search.unity as unity
    from flexflow_tpu import AdamOptimizer, LossType
    from flexflow_tpu.search.calibration import chip_spec_for

    take_tpu_paths()
    # the search as the v5e host sees it: its own chip spec (and through
    # it the committed cost table), not this sandbox's CPU
    unity._detected_chip = lambda honest_cpu=False: chip_spec_for("TPU v5 lite")
    topo = topology()
    for batch in batches:
        model = _train_model("bert-large.mlm-s512-x4", batch)
        model.compile(optimizer=AdamOptimizer(alpha=1e-4),
                      loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
        axes = dict(zip(model.mesh.axis_names, model.mesh.devices.shape))
        r = model._search_result
        print(f"train4 batch={batch}: the search chose mesh {axes}, predicted step "
              f"{r.best_cost * 1e3:.1f} ms, {r.candidates_explored} candidates", flush=True)
        mesh = Mesh(np.array(topo.devices).reshape(model.mesh.devices.shape), model.mesh.axis_names)
        ok = _compile_train_step(model, mesh, f"train4 batch={batch}")
        print(f"train4 batch={batch}: {'FITS' if ok else 'does not fit'}", flush=True)
        del model


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    sub.add_parser("serve").add_argument("--slots", type=int, nargs="+", default=[8, 12, 16])
    sub.add_parser("train").add_argument("--batch", type=int, nargs="+", default=[8, 16, 32])
    sub.add_parser("train4").add_argument("--batch", type=int, nargs="+", default=[32])
    args = ap.parse_args()
    if args.what == "train4":
        os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    {"serve": lambda: serve(args.slots), "train": lambda: train(args.batch),
     "train4": lambda: train4(args.batch)}[args.what]()
    return 0


if __name__ == "__main__":
    sys.exit(main())
