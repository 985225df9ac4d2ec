"""Custom-op extension API (VERDICT.md round-1 item 8; reference:
``paddle/phi/api/ext/`` PD_BUILD_OP + ``python/paddle/utils/cpp_extension``,
exercised upstream by ``test/custom_op/``)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.utils import register_op, get_op, cpp_extension
from paddle_tpu.utils.custom_op import REGISTRY


def _leaf(a):
    t = paddle.to_tensor(np.asarray(a, np.float32))
    t.stop_gradient = False
    return t


def test_register_plain_op_autodiff():
    @register_op(name="t_sq3", override=True)
    def sq3(x):
        return x * x * x

    x = _leaf([1.0, 2.0])
    y = sq3(x)
    np.testing.assert_allclose(y.numpy(), [1, 8])
    y.sum().backward()
    np.testing.assert_allclose(x.grad.numpy(), [3, 12])   # jax autodiff
    assert "t_sq3" in REGISTRY and get_op("t_sq3") is sq3.raw


def test_register_custom_vjp():
    calls = {"bwd": 0}

    def fwd(x):
        return jnp.tanh(x), (x,)

    def vjp(res, cot):
        calls["bwd"] += 1
        (x,) = res
        return (cot * (1 - jnp.tanh(x) ** 2) * 2.0,)   # deliberately 2x

    mytanh = register_op(fwd, name="t_tanh2", vjp=vjp, override=True)
    x = _leaf([0.3])
    y = mytanh(x)
    np.testing.assert_allclose(y.numpy(), np.tanh([0.3]), rtol=1e-6)
    y.backward()
    # custom rule (2x the true grad) proves the vjp was used
    np.testing.assert_allclose(x.grad.numpy(),
                               2 * (1 - np.tanh(0.3) ** 2), rtol=1e-5)
    assert calls["bwd"] == 1


def test_custom_op_under_to_static_and_double_grad():
    def fwd(x):
        return x * x, (x,)

    def vjp(res, cot):
        (x,) = res
        return (cot * 2 * x,)

    sq = register_op(fwd, name="t_sq_vjp", vjp=vjp, override=True)

    @paddle.jit.to_static
    def f(x):
        return sq(x).sum()

    x = _leaf([2.0, 3.0])
    np.testing.assert_allclose(float(f(x).numpy()), 13.0)

    # double grad through the custom vjp (jax.custom_vjp composes)
    x2 = _leaf([2.0])
    y = sq(x2).sum()
    (g1,) = paddle.grad(y, x2, create_graph=True)
    np.testing.assert_allclose(g1.numpy(), [4.0])
    (g2,) = paddle.grad(g1, x2)
    np.testing.assert_allclose(g2.numpy(), [2.0])


def test_register_pallas_kernel_op():
    """A user Pallas kernel as a first-class op (the TPU-native custom
    device kernel; interpret mode on CPU)."""
    from jax.experimental import pallas as pl

    def scale_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.5

    def _call(x):
        return pl.pallas_call(
            scale_kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=jax.default_backend() != "tpu",
        )(x)

    # inference-only kernel: fine on non-diff inputs
    pallas_scale = register_op(_call, name="t_pallas_scale", override=True)
    x = paddle.to_tensor(np.arange(8, dtype=np.float32).reshape(2, 4))
    y = pallas_scale(x)
    np.testing.assert_allclose(y.numpy(), np.arange(8).reshape(2, 4) * 2.5)

    # training kernel: pair the pallas fwd with a custom vjp
    pallas_scale_t = register_op(
        lambda x: (_call(x), ()), name="t_pallas_scale_t",
        vjp=lambda res, cot: (cot * 2.5,), override=True)
    xl = _leaf(np.ones((2, 4)))
    out = pallas_scale_t(xl)
    out.sum().backward()
    np.testing.assert_allclose(xl.grad.numpy(), np.full((2, 4), 2.5))


def test_vjp_op_with_static_kwargs():
    def fwd(x, scale=1.0):
        return x * scale, (scale,)

    def vjp(res, cot):
        (scale,) = res
        return (cot * scale,)

    op = register_op(fwd, name="t_scale_kw", vjp=vjp, override=True)
    x = _leaf([2.0])
    y = op(x, scale=3.0)
    np.testing.assert_allclose(y.numpy(), [6.0])
    y.backward()
    np.testing.assert_allclose(x.grad.numpy(), [3.0])


def test_duplicate_registration_rejected():
    register_op(lambda x: x, name="t_dup", override=True)
    with pytest.raises(ValueError, match="already registered"):
        register_op(lambda x: x, name="t_dup")


def test_fused_swiglu_ported_through_api():
    """The in-tree worked example: fused_swiglu runs through register_op
    with a hand-written VJP matching jax autodiff."""
    from paddle_tpu.ops import fused

    rng = np.random.RandomState(0)
    a, g = rng.randn(4, 8).astype(np.float32), rng.randn(4, 8).astype(np.float32)
    x, gate = _leaf(a), _leaf(g)
    out = fused.fused_swiglu(x, gate)
    silu = a * (1 / (1 + np.exp(-a)))
    np.testing.assert_allclose(out.numpy(), silu * g, rtol=1e-5)
    out.sum().backward()
    # numeric grad check of the hand-written vjp
    eps = 1e-3
    num = (fused._swiglu_fwd(jnp.asarray(a + eps), jnp.asarray(g))[0].sum()
           - fused._swiglu_fwd(jnp.asarray(a - eps), jnp.asarray(g))[0].sum()) / (2 * eps)
    np.testing.assert_allclose(float(x.grad.numpy().sum()), float(num),
                               rtol=1e-2)
    assert "fused_swiglu" in REGISTRY


def test_primal_is_what_an_undifferentiated_call_runs():
    """``primal=``: the op's own expression for calls that are not being
    differentiated; ``fwd`` / ``vjp`` run under differentiation alone."""
    calls = []

    def fwd(x):
        calls.append("fwd")
        return 2.0 * x, ()

    def primal(x):
        calls.append("primal")
        return x + x

    op = register_op(fwd, name="t_primal", vjp=lambda res, ct: (2.0 * ct,),
                     primal=primal, override=True)
    x = _leaf([1.0, 2.0])
    with paddle.no_grad():
        np.testing.assert_allclose(op(x).numpy(), [2.0, 4.0])
    assert calls == ["primal"]
    del calls[:]
    op(x).sum().backward()
    np.testing.assert_allclose(x.grad.numpy(), [2.0, 2.0])
    assert "fwd" in calls
    with pytest.raises(ValueError, match="primal="):
        register_op(lambda x: x, name="t_primal_alone", primal=primal)


def _plain_swiglu(a, g):
    a, g = a.astype(jnp.float32), g.astype(jnp.float32)
    return a * jax.nn.sigmoid(a) * g


@pytest.mark.parametrize("split", [False, True], ids=["two_inputs", "split"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fused_swiglu_rules_against_autodiff_of_the_plain_formula(dtype,
                                                                   split):
    """Forward and both gradients of the rules ``fused_swiglu`` runs under
    differentiation, against ``jax.vjp`` of the plain formula in float32,
    within the dtype's rounding; the undifferentiated call too; and the
    eager tape gives what ``jax.value_and_grad`` through
    ``FunctionalModule`` gives."""
    from paddle_tpu.framework.functional import FunctionalModule
    from paddle_tpu.ops import fused

    rng = np.random.RandomState(3)
    tol = 1e-5 if dtype == "float32" else 2e-2
    a, g, ct = (jnp.asarray(rng.randn(6, 16), dtype) for _ in range(3))
    want, pull = jax.vjp(_plain_swiglu, a, g)
    want_da, want_dg = pull(ct.astype(jnp.float32))
    raw = fused._swiglu_registered().raw
    got, pull = jax.vjp(raw, a, g)
    da, dg = pull(ct)
    assert got.dtype == da.dtype == dg.dtype == jnp.dtype(dtype)
    for have, ref in ((got, want), (da, want_da), (dg, want_dg),
                      (raw(a, g), want)):
        np.testing.assert_allclose(np.asarray(have, np.float32),
                                   np.asarray(ref), rtol=tol, atol=tol)

    class Gate(paddle.nn.Layer):
        def __init__(self):
            super().__init__()
            self.w = self.create_parameter([6, 32 if split else 16],
                                           dtype=dtype)

        def forward(self, x):
            h = self.w * x
            if split:
                return fused.fused_swiglu(h).astype("float32").sum()
            return fused.fused_swiglu(h, x).astype("float32").sum()

    layer = Gate()
    x = paddle.to_tensor(np.asarray(
        rng.randn(6, 32 if split else 16), np.float32)).astype(dtype)
    loss = layer(x)
    loss.backward()
    fm = FunctionalModule(layer, training=True)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda ps: fm(ps, [], jax.random.key(0), x._data)[0])(
            fm.param_arrays())
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(layer.w.grad.numpy(), np.float32),
        np.asarray(ref_grads[0], np.float32), rtol=1e-6, atol=1e-6)


CPP_SRC = r"""
extern "C" void double_plus_one(const float* in, float* out, long n) {
    for (long i = 0; i < n; ++i) out[i] = 2.0f * in[i] + 1.0f;
}
"""


def test_cpp_extension_host_op():
    """Host tier: C++ source -> g++ shared lib -> ctypes -> pure_callback
    op that stays jit-compatible (reference: cpp_extension.load custom op)."""
    import ctypes

    lib = cpp_extension.load("t_host_ext", [CPP_SRC])
    lib.double_plus_one.argtypes = [
        ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
        ctypes.c_long]

    def host_fn(x):
        x = np.ascontiguousarray(np.asarray(x), np.float32)
        out = np.empty_like(x)
        lib.double_plus_one(
            x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), x.size)
        return out

    op = register_op(host_fn, name="t_double_plus_one", host_callback=True,
                     out_shape=lambda x: jax.ShapeDtypeStruct(x.shape,
                                                              jnp.float32),
                     override=True)
    x = paddle.to_tensor(np.array([1.0, 2.0, 3.0], np.float32))
    np.testing.assert_allclose(op(x).numpy(), [3, 5, 7])

    # under jit (pure_callback path)
    @paddle.jit.to_static
    def f(x):
        return op(x) + 1.0

    np.testing.assert_allclose(f(x).numpy(), [4, 6, 8])
