"""`losses.compute_loss`, the one loss path of the training step, against a
float64 NumPy reference.

The step hands it `logits.astype(float32)` (compiler/compile.py, under
`ff.loss`), so a bfloat16 case here rounds the logits to bfloat16 first and
then does what the step does; the reference reads the same rounded values.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.losses import LossType, compute_loss

# GPT-2's published vocabulary: not a multiple of 128, so nothing lane-aligned
# is being flattered
SHAPE = (2, 8, 50257)
DTYPES = [jnp.float32, jnp.bfloat16]


def _logits(dtype, shape=SHAPE, seed=0):
    x = 3.0 * np.random.default_rng(seed).standard_normal(shape)
    return jnp.asarray(x, jnp.float32).astype(dtype)


def _labels(shape=SHAPE, seed=1):
    return np.random.default_rng(seed).integers(
        0, shape[-1], shape[:-1]).astype(np.int32)


def _f64(x):
    return np.asarray(x.astype(jnp.float32), np.float64)


def _log_softmax64(x):
    x = x - x.max(axis=-1, keepdims=True)
    return x - np.log(np.exp(x).sum(axis=-1, keepdims=True))


def _picked(a, labels):
    return np.take_along_axis(a, labels[..., None], axis=-1)[..., 0]


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_sparse_from_logits_is_the_mean_over_all_leading_dims(dtype):
    """[batch, seq, vocab] logits: the mean is over batch * seq rows, not
    over the batch alone."""
    logits, labels = _logits(dtype), _labels()
    want = -_picked(_log_softmax64(_f64(logits)), labels).mean()
    got = compute_loss("sparse_categorical_crossentropy",
                       logits.astype(jnp.float32), jnp.asarray(labels))
    assert got.dtype == jnp.float32 and got.shape == ()
    np.testing.assert_allclose(float(got), want, rtol=2e-6)
    # labels as they come from a loader: a trailing axis of one, any int
    again = compute_loss(LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                         logits.astype(jnp.float32),
                         jnp.asarray(labels[..., None].astype(np.int64)))
    assert float(again) == float(got)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_sparse_gradient_is_softmax_minus_onehot_over_the_rows(dtype):
    """d loss / d logits through the step's own cast, in the logits' dtype."""
    logits, labels = _logits(dtype), _labels()
    grad = jax.grad(lambda x: compute_loss(
        "sparse_categorical_crossentropy", x.astype(jnp.float32),
        jnp.asarray(labels)))(logits)
    assert grad.dtype == dtype and grad.shape == SHAPE
    want = np.exp(_log_softmax64(_f64(logits)))
    np.put_along_axis(want, labels[..., None],
                      _picked(want, labels)[..., None] - 1.0, axis=-1)
    want /= labels.size
    # float32: rounding of the softmax; bfloat16: half an ulp of the result
    rtol = 2e-5 if dtype == jnp.float32 else 2.0 ** -8
    np.testing.assert_allclose(_f64(grad), want, rtol=rtol,
                               atol=rtol * np.abs(want).max() * 1e-3)


def _probabilities(shape=(4, 6, 33), seed=2):
    p = np.exp(_log_softmax64(
        2.0 * np.random.default_rng(seed).standard_normal(shape)))
    return p.astype(np.float32)


def test_sparse_from_probabilities_clips_at_1e_12():
    p = _probabilities()
    labels = _labels(p.shape)
    p[0, 0, labels[0, 0]] = 0.0          # log(0) is clipped, not -inf
    want = -np.log(np.clip(_picked(p.astype(np.float64), labels), 1e-12,
                           None)).mean()
    got = compute_loss("sparse_categorical_crossentropy", jnp.asarray(p),
                       jnp.asarray(labels), from_logits=False)
    assert np.isfinite(float(got))
    np.testing.assert_allclose(float(got), want, rtol=2e-6)


def test_categorical_from_logits_takes_dense_targets():
    shape = (4, 6, 33)
    logits, targets = _logits(jnp.float32, shape), _probabilities(shape, seed=3)
    want = -(targets.astype(np.float64)
             * _log_softmax64(_f64(logits))).sum(axis=-1).mean()
    got = compute_loss("categorical_crossentropy", logits,
                       jnp.asarray(targets))
    np.testing.assert_allclose(float(got), want, rtol=2e-6)
    # one-hot targets are the sparse loss
    labels = _labels(shape)
    onehot = np.eye(shape[-1], dtype=np.float32)[labels]
    np.testing.assert_allclose(
        float(compute_loss("categorical_crossentropy", logits,
                           jnp.asarray(onehot))),
        float(compute_loss("sparse_categorical_crossentropy", logits,
                           jnp.asarray(labels))), rtol=2e-6)


def test_categorical_from_probabilities():
    shape = (4, 6, 33)
    p, targets = _probabilities(shape), _probabilities(shape, seed=3)
    want = -(targets.astype(np.float64)
             * np.log(np.clip(p.astype(np.float64), 1e-12, None))
             ).sum(axis=-1).mean()
    got = compute_loss("categorical_crossentropy", jnp.asarray(p),
                       jnp.asarray(targets), from_logits=False)
    np.testing.assert_allclose(float(got), want, rtol=2e-6)


@pytest.mark.parametrize("name", ["mean_squared_error",
                                  "mean_squared_error_avg_reduce"])
def test_mean_squared_error_is_the_mean_over_every_element(name):
    shape = (4, 6, 33)
    out = _logits(jnp.float32, shape)
    targets = np.random.default_rng(4).integers(-3, 4, shape)  # ints: cast
    want = np.square(_f64(out) - targets).mean()
    got = compute_loss(name, out, jnp.asarray(targets))
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(float(got), want, rtol=2e-6)


def test_identity_is_the_mean_of_the_output_and_reads_no_label():
    out = _logits(jnp.float32, (4, 6, 33))
    got = compute_loss(LossType.IDENTITY, out, None)
    np.testing.assert_allclose(float(got), _f64(out).mean(), rtol=1e-5,
                               atol=1e-7)


def test_an_unknown_loss_name_is_refused():
    with pytest.raises(ValueError, match="hinge"):
        compute_loss("hinge", _logits(jnp.float32, (2, 3)), None)
