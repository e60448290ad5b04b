"""Training throughput on the TPU: searched strategy vs data-parallel vs
tensor-parallel.

One process on the chip, no probe and no fallback: without a TPU, with a
device kind that has no peak in the table below, or when any leg fails,
the run raises and exits non-zero. Prints ONE JSON line: {"metric",
"value", "unit", "vs_baseline", "extra"}. The reference's headline is
searched-strategy vs data-parallel on identical hardware
(scripts/osdi22ae/bert.sh); we report MFU for each strategy plus
simulator-validation ratios (predicted/measured) and the rank agreement
between simulated and measured strategy ordering.

These are the measurements the benchmark proper (ROADMAP Speed 1: cells,
the ledger, the trace reduction) starts from; this file is not it.
"""
from __future__ import annotations

import json
import time

import numpy as np

# (device_kind substring, peak bf16 FLOP/s per jax device), most specific first.
# v2/v3 expose one core per jax device; v4+ one (mega)chip per device.
_PEAK_BF16 = [
    ("v6e", 918e12),
    ("v6 lite", 918e12),
    ("v6", 918e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v5litepod", 197e12),
    ("v5p", 459e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 61.25e12),
    ("v2", 22.5e12),
]


def train_flops_per_token(n_params: int, num_layers: int, seq_length: int, hidden_size: int) -> float:
    """6N (fwd+bwd matmul FLOPs per token) + attention score/value
    matmuls 12*L*S*H — the PaLM-appendix-style accounting; 6N alone
    undercounts the work (``benchmark/stats.py`` holds the same)."""
    return 6.0 * n_params + 12.0 * num_layers * seq_length * hidden_size


def peak_flops_per_device(device_kind: str) -> float:
    kind = device_kind.lower()
    for sub, peak in _PEAK_BF16:
        if sub in kind:
            return peak
    raise ValueError(
        f"no bf16 peak for device kind {device_kind!r}: add it to _PEAK_BF16 "
        f"(an unknown device is an error, not a default)"
    )


def _bench_one(ex, batch, cfg, iters):
    """Measure steady-state step time of a compiled executor.

    The timed unit is a traced multi-step window (train_batch_repeated:
    lax.scan over the train step inside ONE XLA program — the analog of
    the reference's Legion iteration tracing), so per-step host dispatch
    is excluded from the step time, exactly as it is in a real fit loop
    that runs traced. Every flush is a scalar readback (float(loss)).
    """
    import jax
    import jax.numpy as jnp

    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(batch, cfg.seq_length, cfg.hidden_size), cfg.dtype.jnp)
    y = jnp.asarray(rs.randn(batch, cfg.seq_length, cfg.hidden_size), cfg.dtype.jnp)
    rng = jax.random.key(0)
    # warmup = compile + first run of the SAME traced-window program the
    # timed loop uses (a train_batch warmup would compile the single-step
    # program too — an unused, expensive extra XLA compile)
    mets = ex.train_batch_repeated([x], y, rng, num_steps=iters)
    float(mets["loss"])
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        mets = ex.train_batch_repeated([x], y, rng, num_steps=iters)
        float(mets["loss"])  # device->host readback flushes the window
        best = min(best, time.perf_counter() - t0)
    return best / iters


def main():
    import jax

    from flexflow_tpu import DataType, FFConfig, LossType, SGDOptimizer
    from flexflow_tpu.device import enable_compile_cache, require_tpu
    from flexflow_tpu.models import TransformerConfig, build_transformer

    dev = require_tpu()
    enable_compile_cache()
    backend = jax.default_backend()
    n_dev = len(jax.devices())
    kind = dev.device_kind
    peak = peak_flops_per_device(kind) * n_dev

    # BERT-Base-shaped encoder, bf16 activations (flash attention on TPU)
    cfg = TransformerConfig(
        num_layers=12,
        hidden_size=768,
        num_heads=12,
        ff_size=3072,
        seq_length=128,
        dtype=DataType.BFLOAT16,
    )
    batch = 32 * n_dev
    iters = 40
    metric = "bert_base_seq128_train_throughput"

    def build(only_dp: bool, budget: int, strategy_fn=None):
        config = FFConfig(
            batch_size=batch,
            workers_per_node=n_dev,
            num_nodes=1,
            only_data_parallel=only_dp,
            search_budget=budget,
        )
        model = build_transformer(config, cfg)
        # strategies must be built from THIS model's graph: guids are
        # process-unique per build, and a foreign strategy's shardings
        # would silently never apply (the model now rejects that)
        model.compile(
            optimizer=SGDOptimizer(lr=0.01),
            loss_type=LossType.MEAN_SQUARED_ERROR,
            strategy=strategy_fn(model.graph) if strategy_fn else None,
        )
        return model

    model_dp = build(only_dp=True, budget=0)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(model_dp.executor.params))
    flops_per_token = train_flops_per_token(
        n_params, cfg.num_layers, cfg.seq_length, cfg.hidden_size
    )
    step_dp = _bench_one(model_dp.executor, batch, cfg, iters)
    graph = model_dp.graph
    del model_dp

    # ---- simulator validation: predicted step time of every strategy
    # family against its measured step time, on this chip's own spec
    # and committed calibration table
    from flexflow_tpu.parallel.machine import MachineSpec
    from flexflow_tpu.parallel.strategy import (
        context_parallel_strategy,
        data_parallel_strategy,
        megatron_strategy,
        pipeline_strategy,
    )
    from flexflow_tpu.search.calibration import chip_spec_for, load_or_calibrate
    from flexflow_tpu.search.simulator import predict_strategy_time
    from flexflow_tpu.search.unity import predict_cp_time, predict_pipeline_time

    machine = MachineSpec(num_nodes=1, devices_per_node=n_dev, chip=chip_spec_for(kind))
    calibration = load_or_calibrate(machine, allow_measure=True, device_kind=kind)

    # FACTORIES, not instances: each measured model rebuilds the
    # strategy from its OWN graph (guids are process-unique per build)
    factories = {"dp": lambda g: data_parallel_strategy(g, n_dev)}
    # tp and hybrid candidates (skip shapes that don't divide)
    if n_dev >= 2 and cfg.num_heads % 2 == 0:
        factories["tp"] = lambda g: megatron_strategy(g, dp=1, tp=min(n_dev, cfg.num_heads))
        if n_dev >= 4:
            factories["hybrid"] = lambda g: megatron_strategy(g, dp=n_dev // 2, tp=2)
    # pipeline candidate: a strategy family whose constants were NOT
    # fitted (fit set = dp/tp/hybrid), so its predicted/measured ratio
    # is a TRANSFER check of the cost model
    pp_layout = None
    if n_dev >= 4 and cfg.num_layers % 2 == 0:
        factories["pp"] = lambda g: pipeline_strategy(g, pp=2, dp=n_dev // 2)
        pp_layout = (2, 1, 1)
    # cp: the second held-out family (ring-attention comm model)
    cp_layout = None
    if n_dev >= 4 and cfg.seq_length % 2 == 0:
        factories["cp"] = lambda g: context_parallel_strategy(g, dp=n_dev // 2, cp=2)
        cp_layout = (2, 1)
    pred = {}
    for name, fn in factories.items():
        if name == "pp":
            p = predict_pipeline_time(
                graph, n_dev, batch, *pp_layout,
                machine=machine, calibration=calibration,
            )
        elif name == "cp":
            p = predict_cp_time(
                graph, n_dev, batch, *cp_layout,
                machine=machine, calibration=calibration,
            )
        else:
            p = predict_strategy_time(graph, fn(graph), machine, calibration=calibration)
        if p is not None:  # the proposers return None for a layout they reject
            pred[name] = p
    sim_dp_ratio = round(pred["dp"] / step_dp, 3)

    # ---- measure tp / hybrid / pp / cp so simulated vs measured rank
    # order is a reported fact, not an assumption
    measured = {"dp": step_dp}
    for name in ("tp", "hybrid", "pp", "cp"):
        if name not in pred:
            continue
        m = build(only_dp=True, budget=0, strategy_fn=factories[name])
        measured[name] = _bench_one(m.executor, batch, cfg, iters)
        del m
    rank_agreement = best_agreement = fitted_rank_agreement = None
    sim_ratios = {}
    if len(measured) >= 2:
        sim_rank = sorted(measured, key=lambda n: pred[n])
        meas_rank = sorted(measured, key=lambda n: measured[n])
        rank_agreement = sim_rank == meas_rank
        best_agreement = sim_rank[0] == meas_rank[0]
        sim_ratios = {n: round(pred[n] / measured[n], 3) for n in measured}
        # the regression guard ranks the FITTED families only; the full
        # rank over the held-out pp/cp transfer families can break on
        # near-ties (the per-strategy step_ms fields show the margins)
        fitted = [n for n in measured if n in ("dp", "tp", "hybrid")]
        if len(fitted) >= 2:  # one family alone ranks vacuously
            fitted_rank_agreement = sorted(fitted, key=lambda n: pred[n]) == sorted(
                fitted, key=lambda n: measured[n]
            )

    t_search = time.perf_counter()
    model_s = build(only_dp=False, budget=5)
    search_s = time.perf_counter() - t_search
    step_s = _bench_one(model_s.executor, batch, cfg, iters)
    # predict the searched strategy with the SAME machine/calibration
    # as the other ratios
    pred_s = predict_strategy_time(
        model_s.graph, model_s.strategy, machine, calibration=calibration
    )
    sim_s_ratio = round(pred_s / step_s, 3)
    del model_s

    # ---- secondary: BERT-Large (the BASELINE.json north-star config,
    # scripts/osdi22ae/bert.sh) measured dp on this chip, same traced
    # window
    lcfg = TransformerConfig(
        num_layers=24, hidden_size=1024, num_heads=16, ff_size=4096,
        seq_length=128, dtype=DataType.BFLOAT16,
    )
    lbatch = 16 * n_dev
    lconfig = FFConfig(
        batch_size=lbatch, workers_per_node=n_dev, num_nodes=1,
        only_data_parallel=True, search_budget=0,
    )
    lmodel = build_transformer(lconfig, lcfg)
    lmodel.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.MEAN_SQUARED_ERROR,
    )
    lparams = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(lmodel.executor.params))
    lstep = _bench_one(lmodel.executor, lbatch, lcfg, 12)
    ltok = lbatch * lcfg.seq_length / lstep
    lf = train_flops_per_token(lparams, lcfg.num_layers, lcfg.seq_length, lcfg.hidden_size)
    del lmodel

    def mfu(step):
        toks = batch * cfg.seq_length / step
        return round(toks * flops_per_token / peak, 4)

    # headline value and MFU describe the SAME configuration: the
    # searched strategy
    result = {
        "metric": metric,
        "value": round(batch / step_s, 2),
        "unit": "samples/s",
        "vs_baseline": round(mfu(step_s) / 0.45, 4),
        "extra": {
            "backend": backend,
            "device_kind": kind,
            "devices": n_dev,
            "batch": batch,
            "params": n_params,
            "peak_flops": peak,
            "dp_step_ms": round(step_dp * 1e3, 2),
            "searched_step_ms": round(step_s * 1e3, 2),
            **{
                f"{n}_step_ms": round(measured[n] * 1e3, 2)
                for n in ("tp", "hybrid", "pp", "cp") if n in measured
            },
            "dp_mfu": mfu(step_dp),
            "searched_mfu": mfu(step_s),
            "mfu": mfu(step_s),
            "search_s": round(search_s, 1),
            "sim_pred_over_measured_dp": sim_dp_ratio,
            "sim_pred_over_measured_searched": sim_s_ratio,
            "sim_pred_over_measured": sim_ratios or None,
            "sim_rank_agreement": rank_agreement,
            "sim_rank_agreement_fitted": fitted_rank_agreement,
            "sim_best_strategy_agreement": best_agreement,
            "calibration_kind": calibration.device_kind,
            "calibration_source": calibration.source,
            "bert_large_step_ms": round(lstep * 1e3, 2),
            "bert_large_mfu": round(ltok * lf / peak, 4),
            "bert_large_params": lparams,
            "bert_large_batch": lbatch,
        },
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
