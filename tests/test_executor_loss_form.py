"""Which loss chain a train step runs is read from the graph
(``CompiledExecutor.loss_form``): fused where the model ends in a softmax
over the last axis that only the loss reads, composed everywhere else;
both train to the same losses, and the fused step's program holds what
the composed one's does not."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, LossType, MetricsType, SGDOptimizer
from flexflow_tpu.models import TransformerConfig, build_nmt, build_transformer
from flexflow_tpu.obs.capacity import GLOBAL_PROGRAMS
from flexflow_tpu.parallel.strategy import data_parallel_strategy, megatron_strategy

B, S, V = 8, 8, 48
SPARSE = LossType.SPARSE_CATEGORICAL_CROSSENTROPY


def transformer(tail=False, accum=1):
    """A tiny ``build_transformer``; ``tail`` puts an identity behind its
    softmax, so that something else reads it and the chain is composed."""
    cfg = TransformerConfig(num_layers=2, hidden_size=32, num_heads=4, ff_size=64, seq_length=S, vocab_size=V)
    model = build_transformer(FFConfig(batch_size=B, grad_accum_steps=accum), cfg)
    if tail:
        model.identity(model.get_output(), name="tail")
    return model


def head(axis=-1, softmax=True):
    model = FFModel(FFConfig(batch_size=B))
    t = model.dense(model.create_tensor((B, S, 16), name="x"), V, name="head")
    if softmax:
        model.softmax(t, axis=axis)
    return model


def compiled(model, loss=SPARSE, strategy=None, **kw):
    model.compile(
        optimizer=SGDOptimizer(lr=0.1), loss_type=loss,
        strategy=strategy or data_parallel_strategy(model.graph, 1), **kw,
    )
    return model.executor


@pytest.mark.parametrize(
    "build,loss,form",
    [
        (transformer, SPARSE, "fused_softmax_ce"),
        (transformer, LossType.CATEGORICAL_CROSSENTROPY, "fused_softmax_ce"),
        (head, SPARSE, "fused_softmax_ce"),
        (lambda: head(axis=2), SPARSE, "fused_softmax_ce"),
        (lambda: head(axis=1), SPARSE, "composed"),
        (lambda: transformer(tail=True), SPARSE, "composed"),
        (lambda: head(softmax=False), SPARSE, "composed"),
        (transformer, LossType.MEAN_SQUARED_ERROR, "composed"),
        (transformer, LossType.IDENTITY, "composed"),
    ],
    ids=["transformer", "dense_labels", "head", "last_axis_by_index", "other_axis", "softmax_read_twice",
         "ends_in_dense", "mse", "identity_loss"],
)
def test_loss_form_from_the_graph(build, loss, form, capsys):
    ex = compiled(build(), loss)
    assert ex.loss_form == form
    assert f"({form})" in capsys.readouterr().out  # FFModel.compile says which


def test_nmt_takes_the_last_softmax_only():
    """``attn_weights`` is a softmax that ``attn_context`` reads: it keeps
    autodiff's gradient; ``tgt_probs`` is last and is taken."""
    model = build_nmt(FFConfig(batch_size=4), src_vocab=40, tgt_vocab=V, embed_dim=16, hidden_size=16,
                      num_layers=1, src_len=6, tgt_len=5)
    ex = compiled(model)
    assert ex.loss_form == "fused_softmax_ce"
    names = {n.guid: n.name for n in model.graph.topo_order()}
    assert names[ex._softmax_loss_logits()[0]] == "tgt_proj"
    rs = np.random.RandomState(0)
    src, tgt, lab = (jnp.asarray(rs.randint(0, 40, (4, n)), jnp.int32) for n in (6, 5, 5))
    losses = [float(ex.train_batch([src, tgt], lab, jax.random.key(i))["loss"]) for i in range(4)]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]


def test_registry_carries_the_form():
    ex = compiled(transformer())
    x, y = batch()
    ex.train_batch([x], y, jax.random.key(0))
    entry = next(e for e in GLOBAL_PROGRAMS.snapshot() if e["name"] == f"{ex._prog_ns}.train_step")
    assert entry["signature"]["loss_form"] == "fused_softmax_ce"


def batch(seed=0):
    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randint(0, V, (B, S)), jnp.int32), jnp.asarray(rs.randint(0, V, (B, S)), jnp.int32))


LAYOUTS = {
    "one_device": lambda g: data_parallel_strategy(g, 1),
    "data4": lambda g: data_parallel_strategy(g, 4),
    # tp shards lm_head's kernel over the vocabulary: the log-sum-exp
    # reduces over a sharded axis
    "dp2_tp2_vocab": lambda g: megatron_strategy(g, dp=2, tp=2),
}


@pytest.mark.parametrize("accum", [1, 4])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_ten_steps_match_the_composed_chain(layout, accum):
    runs = {}
    for tail in (False, True):
        model = transformer(tail=tail, accum=accum)
        model.compile(optimizer=AdamOptimizer(alpha=1e-2), loss_type=SPARSE, strategy=LAYOUTS[layout](model.graph))
        ex = model.executor
        assert ex.loss_form == ("composed" if tail else "fused_softmax_ce")
        if layout == "dp2_tp2_vocab":
            kernel = next(v for k, v in ex.params.items() if k.startswith("linear_") and v["kernel"].shape[-1] == V)["kernel"]
            assert "model" in str(kernel.sharding.spec[1])
        losses = []
        for i in range(10):
            x, y = batch(i % 3)
            losses.append(float(ex.train_batch([x], y, jax.random.key(i))["loss"]))
        runs[tail] = losses
    assert runs[False][-1] < runs[False][0]
    np.testing.assert_allclose(runs[False], runs[True], rtol=1e-5)


@pytest.mark.parametrize(
    "plan,config",
    [("_remat_plan", dict(remat_blocks=True)), ("_pipeline_plan", dict(pipeline_stages=2, pipeline_microbatches=2))],
    ids=["remat", "pipeline"],
)
def test_block_plans_hand_the_logits_on(plan, config):
    """Under a block plan the forward runs by region; the softmax sits
    behind the blocks, so its input is still among the values at hand."""
    runs = {}
    for tail in (False, True):
        cfg = TransformerConfig(num_layers=4, hidden_size=32, num_heads=4, ff_size=64, seq_length=S, vocab_size=V)
        model = build_transformer(FFConfig(batch_size=B, only_data_parallel=True, **config), cfg)
        if tail:
            model.identity(model.get_output(), name="tail")
        model.compile(optimizer=SGDOptimizer(lr=0.1), loss_type=SPARSE)
        ex = model.executor
        assert getattr(ex, plan) is not None
        assert ex.loss_form == ("composed" if tail else "fused_softmax_ce")
        x, y = batch()
        runs[tail] = [float(ex.train_batch([x], y, jax.random.key(i))["loss"]) for i in range(4)]
    np.testing.assert_allclose(runs[False], runs[True], rtol=1e-5)


def test_outputs_stay_probabilities():
    ex = compiled(transformer(), metrics=[MetricsType.ACCURACY, MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY])
    x, y = batch()
    mets = ex.train_batch([x], y, jax.random.key(0))
    # the metric reads the probabilities, the loss the logits: one number
    np.testing.assert_allclose(mets["sparse_cce_loss"] / (B * S), mets["loss"], rtol=1e-5)
    (probs,) = ex.predict([x])
    np.testing.assert_allclose(np.asarray(probs).sum(-1), 1.0, rtol=1e-5)
    assert probs.shape == (B, S, V) and float(probs.min()) >= 0.0
    ev = ex.eval_batch([x], y)
    np.testing.assert_allclose(ev["sparse_cce_loss"] / (B * S), ev["loss"], rtol=1e-5)


# ---------------------------------------------------------- the program


def entry_results(hlo: str):
    """(shape, opcode) of every instruction of the entry computation."""
    entry = hlo[hlo.index("ENTRY "):]
    entry = entry[: entry.index("\n}")]
    return re.findall(r"^\s+(?:ROOT )?%?[\w.\-]+ = (\S+) ([\w\-]+)\(", entry, flags=re.M)


def train_step_programs(tail):
    ex = compiled(transformer(tail=tail))
    x, y = batch()
    lowered = jax.jit(ex._train_step_fn).lower(ex.params, ex.opt_state, ex.state, (x,), y, jax.random.key(0))
    return lowered.as_text(), lowered.compile().as_text()


def test_fused_step_scatters_nothing_into_the_logits_shape():
    """The composed chain's gradient scatters one value a row into a
    float32 array of the logits' shape, and its program carries more
    float32 ``[tokens, vocabulary]`` results than the fused one's, which
    compares with an iota. (What the TPU's compiler leaves of either is
    held by tests/test_generation.py, beside the other compiles for a
    described chip: the CPU's fuses nothing into a reduction.)"""
    logits = f"f32[{B},{S},{V}]"

    def count(compiled_hlo):
        return sum(shape.startswith(logits) for shape, _ in entry_results(compiled_hlo))

    def scatters(stablehlo):  # result types; the embedding's gradient scatters too, into its table
        return re.findall(r"stablehlo\.scatter.*?-> tensor<([^>]*)>", stablehlo, flags=re.S)

    stable, hlo = train_step_programs(tail=False)
    stable0, hlo0 = train_step_programs(tail=True)
    assert f"{B}x{S}x{V}xf32" not in scatters(stable)
    assert f"{B}x{S}x{V}xf32" in scatters(stable0)
    assert count(hlo) < count(hlo0)
