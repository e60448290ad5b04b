"""The fused softmax + cross-entropy (``losses.softmax_crossentropy``)
against the composed chain it stands for: the loss of ``softmax(logits)``
under autodiff, in value and in gradient."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.runtime import losses

CLASSES = 250  # no multiple of 128, as 30,522 is none


def composed(sparse):
    loss = losses.sparse_categorical_crossentropy if sparse else losses.categorical_crossentropy
    return lambda x, y: loss(jax.nn.softmax(x, axis=-1), y)


def fused(sparse):
    return lambda x, y: losses.softmax_crossentropy(x, y, sparse)


def make(shape, label_shape, seed=0, dtype=jnp.float32):
    rs = np.random.RandomState(seed)
    logits = jnp.asarray(rs.randn(*shape, CLASSES) * 3.0, dtype)
    labels = jnp.asarray(rs.randint(0, CLASSES, label_shape), jnp.int32)
    return logits, labels


@pytest.mark.parametrize(
    "shape,label_shape",
    [((6,), (6,)), ((6,), (6, 1)), ((3, 5), (3, 5)), ((3, 5), (3, 5, 1))],
    ids=["B", "B1", "BS", "BS1"],
)
def test_sparse_matches_composed(shape, label_shape):
    logits, labels = make(shape, label_shape)
    v, g = jax.value_and_grad(fused(True))(logits, labels)
    v0, g0 = jax.value_and_grad(composed(True))(logits, labels)
    np.testing.assert_allclose(v, v0, rtol=1e-6)
    np.testing.assert_allclose(g, g0, atol=1e-6)
    assert g.dtype == logits.dtype and g.shape == logits.shape


@pytest.mark.parametrize("shape", [(6,), (3, 5)], ids=["B", "BS"])
def test_dense_matches_composed(shape):
    logits, _ = make(shape, shape)
    rs = np.random.RandomState(1)
    labels = jax.nn.softmax(jnp.asarray(rs.randn(*shape, CLASSES), jnp.float32))
    v, g = jax.value_and_grad(fused(False))(logits, labels)
    v0, g0 = jax.value_and_grad(composed(False))(logits, labels)
    np.testing.assert_allclose(v, v0, rtol=1e-6)
    np.testing.assert_allclose(g, g0, atol=1e-6)


def test_dense_one_hot_is_sparse():
    logits, labels = make((8,), (8,))
    v, g = jax.value_and_grad(fused(False))(logits, jax.nn.one_hot(labels, CLASSES))
    v0, g0 = jax.value_and_grad(fused(True))(logits, labels)
    np.testing.assert_allclose(v, v0, rtol=1e-6)
    np.testing.assert_allclose(g, g0, atol=1e-7)


@pytest.mark.parametrize("sparse", [True, False], ids=["sparse", "dense"])
def test_label_under_the_floor(sparse):
    """A label whose probability is under 1e-8 reads -log(1e-8) and its
    row passes no gradient, as the composed chain's clip has it."""
    logits, labels = make((4,), (4,))
    logits = logits.at[0, int(labels[0])].set(-60.0)
    y = labels if sparse else jax.nn.one_hot(labels, CLASSES)
    v, g = jax.value_and_grad(fused(sparse))(logits, y)
    v0, g0 = jax.value_and_grad(composed(sparse))(logits, y)
    np.testing.assert_allclose(v, v0, rtol=1e-6)
    np.testing.assert_allclose(g, g0, atol=1e-6)
    assert not np.any(np.asarray(g[0]))
    rest = jax.value_and_grad(fused(sparse))(logits[1:], y[1:])[0]
    np.testing.assert_allclose(v, (-math.log(1e-8) + 3 * rest) / 4, rtol=1e-6)


def test_masked_logits_stay_finite():
    logits, labels = make((4,), (4,))
    logits = logits.at[:, 7].set(-jnp.inf)
    labels = jnp.where(labels == 7, 8, labels)
    v, g = jax.value_and_grad(fused(True))(logits, labels)
    assert np.isfinite(v) and np.all(np.isfinite(np.asarray(g)))
    np.testing.assert_allclose(v, composed(True)(logits, labels), rtol=1e-6)


def test_bfloat16_logits():
    """From bfloat16 logits the fused value is float32 arithmetic on them
    (the composed chain rounds the probabilities to bfloat16 first), and
    the gradient is bfloat16, the float32 one within a rounding."""
    logits, labels = make((64,), (64,), dtype=jnp.bfloat16)
    ref_v, ref_g = jax.value_and_grad(composed(True))(logits.astype(jnp.float32), labels)
    v, g = jax.value_and_grad(fused(True))(logits, labels)
    v0, g0 = jax.value_and_grad(composed(True))(logits, labels)
    assert g.dtype == jnp.bfloat16
    assert abs(v - ref_v) <= abs(v0 - ref_v)
    np.testing.assert_allclose(v, ref_v, rtol=1e-6)
    step = 2.0 ** -8  # bfloat16 keeps 8 bits: a rounding is half of this, relative
    np.testing.assert_allclose(g.astype(jnp.float32), ref_g, rtol=step, atol=1e-9)
    np.testing.assert_allclose(g.astype(jnp.float32), g0.astype(jnp.float32), rtol=4 * step, atol=2e-6)


def test_under_jit_and_scaled_cotangent():
    logits, labels = make((3, 5), (3, 5))
    g = jax.jit(jax.grad(lambda x: 3.0 * fused(True)(x, labels)))(logits)
    g0 = jax.grad(lambda x: 3.0 * composed(True)(x, labels))(logits)
    np.testing.assert_allclose(g, g0, atol=3e-6)
