"""``LlamaPretrainingCriterion`` under differentiation computes the logits'
gradient in its forward pass and keeps that alone (``causal_lm_loss``, a
``jax.custom_vjp``): loss and gradient against autodiff of the expression
the criterion had before, which is still what an undifferentiated call
runs."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.llama import (LlamaPretrainingCriterion,
                                     causal_lm_loss)

IGNORE = -100
VOCAB = 37


def _autodiff_loss(lg, lb, ign=IGNORE):
    lg = lg.astype(jnp.float32)
    logp = lg - jax.nn.logsumexp(lg, axis=-1, keepdims=True)
    valid = lb != ign
    lb_safe = jnp.where(valid, lb, 0)
    tok = jnp.take_along_axis(logp, lb_safe[..., None], axis=-1)[..., 0]
    tok = jnp.where(valid, tok, 0.0)
    return -tok.sum() / jnp.maximum(valid.sum(), 1)


def _case(name, dtype):
    rng = np.random.default_rng(7)
    logits = jnp.asarray(3.0 * rng.normal(size=(2, 9, VOCAB)), dtype)
    labels = rng.integers(0, VOCAB, (2, 9))
    if name == "some_ignored":
        labels[0, :4] = IGNORE
        labels[1, -1] = IGNORE
    if name == "all_ignored":
        labels[:] = IGNORE
    scale = 0.125 if name == "scaled_cotangent" else 1.0
    return logits, jnp.asarray(labels, jnp.int32), scale


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", ["all_valid", "some_ignored", "all_ignored",
                                  "scaled_cotangent"])
def test_loss_and_logits_gradient_against_autodiff(name, dtype):
    logits, labels, scale = _case(name, dtype)
    want, want_grad = jax.value_and_grad(
        lambda lg: scale * _autodiff_loss(lg, labels))(logits)
    got, grad = jax.jit(jax.value_and_grad(
        lambda lg: scale * causal_lm_loss(lg, labels, IGNORE)))(logits)
    assert grad.dtype == logits.dtype and got.dtype == jnp.float32
    assert float(got) == pytest.approx(float(want), rel=1e-6, abs=1e-7)
    # one rounding to the logits' dtype, as autodiff's own cotangent has
    tol = 1e-5 if dtype == "float32" else 2.0 ** -8
    np.testing.assert_allclose(np.asarray(grad, np.float32),
                               np.asarray(want_grad, np.float32),
                               rtol=tol, atol=tol * scale / 18)
    assert np.isfinite(np.asarray(grad, np.float32)).all()
    if name == "all_ignored":
        assert float(got) == 0.0 and not np.asarray(grad, np.float32).any()
    # undifferentiated: the old expression itself
    assert float(causal_lm_loss(logits, labels, IGNORE)) == pytest.approx(
        float(_autodiff_loss(logits, labels)), rel=1e-6, abs=1e-7)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_eager_tape_gives_the_same_gradient(dtype):
    logits, labels, _ = _case("some_ignored", dtype)
    want = jax.grad(lambda lg: causal_lm_loss(lg, labels, IGNORE))(logits)
    t = paddle.Tensor(logits)
    t.stop_gradient = False
    loss = LlamaPretrainingCriterion(ignore_index=IGNORE)(
        t, paddle.Tensor(labels))
    loss.backward()
    np.testing.assert_array_equal(np.asarray(t.grad._data, np.float32),
                                  np.asarray(want, np.float32))
