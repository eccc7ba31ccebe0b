"""Custom-op extension point (reference: paddle/phi/api/ext/op_meta_info.h
PD_BUILD_OP / PD_BUILD_GRAD_OP, python/paddle/utils/cpp_extension/).

Everything here goes through the PUBLIC API only:
paddle_tpu.utils.register_custom_op."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.ops import api
from paddle_tpu.utils import register_custom_op


def _unique(name):
    return f"{name}_{np.random.randint(1 << 30)}"


class TestRegisterCustomOp:
    def test_autodiff_backward(self):
        """No backward given -> jax.vjp of the forward."""
        opname = _unique("swish_custom")

        @register_custom_op(name=opname)
        def swish(x, *, beta=1.0):
            return x * jax.nn.sigmoid(beta * x)

        x = paddle.to_tensor(np.linspace(-2, 2, 12).astype(np.float32),
                             stop_gradient=False)
        y = getattr(api, opname)(x, beta=2.0)
        y.sum().backward()
        xf = np.asarray(x._value)
        sig = 1 / (1 + np.exp(-2.0 * xf))
        np.testing.assert_allclose(np.asarray(y._value), xf * sig, rtol=1e-5)
        ref_grad = sig + xf * 2.0 * sig * (1 - sig)
        np.testing.assert_allclose(np.asarray(x.grad._value), ref_grad,
                                   rtol=1e-4)

    def test_custom_backward_rule(self):
        """backward sees (inputs, outputs, grad_outputs) + attrs — the
        PD_BUILD_GRAD_OP contract."""
        opname = _unique("scaled_sq")
        calls = []

        def bwd(x, out, g, *, alpha):
            calls.append(True)
            return 2.0 * alpha * x * g

        @register_custom_op(name=opname, backward=bwd)
        def scaled_sq(x, *, alpha=1.0):
            return alpha * x * x

        x = paddle.to_tensor(np.arange(1.0, 5.0, dtype=np.float32),
                             stop_gradient=False)
        y = getattr(api, opname)(x, alpha=3.0)
        y.sum().backward()
        np.testing.assert_allclose(np.asarray(y._value),
                                   3.0 * np.arange(1.0, 5.0) ** 2, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(x.grad._value),
                                   6.0 * np.arange(1.0, 5.0), rtol=1e-6)
        assert calls  # the custom rule actually ran

    def test_none_grad_for_nondiff_input(self):
        opname = _unique("gather_rows")

        def bwd(x, idx, out, g):
            gx = jnp.zeros_like(x).at[idx].add(g)
            return gx, None  # no grad for integer indices

        @register_custom_op(name=opname, backward=bwd)
        def gather_rows(x, idx):
            return x[idx]

        x = paddle.to_tensor(np.random.randn(5, 3).astype(np.float32),
                             stop_gradient=False)
        idx = paddle.to_tensor(np.array([0, 2, 2], np.int32))
        out = getattr(api, opname)(x, idx)
        out.sum().backward()
        g = np.asarray(x.grad._value)
        np.testing.assert_allclose(g[0], 1.0)
        np.testing.assert_allclose(g[2], 2.0)
        np.testing.assert_allclose(g[1], 0.0)

    def test_pallas_backed_op(self):
        """A Pallas kernel registered through the public API only (interpret
        mode: tests run on CPU; the repo's own kernels are held to the TPU
        compiler by tests/test_tpu_compile.py)."""
        from jax.experimental import pallas as pl

        opname = _unique("pallas_axpy")

        def _kernel(x_ref, y_ref, o_ref, *, a):
            o_ref[:] = a * x_ref[:] + y_ref[:]

        def axpy_bwd(x, y, out, g, *, a=2.0):
            return a * g, g

        @register_custom_op(name=opname, backward=axpy_bwd)
        def pallas_axpy(x, y, *, a=2.0):
            import functools as ft

            return pl.pallas_call(
                ft.partial(_kernel, a=a),
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                interpret=True,
            )(x, y)

        x = paddle.to_tensor(np.random.randn(8, 16).astype(np.float32),
                             stop_gradient=False)
        y = paddle.to_tensor(np.random.randn(8, 16).astype(np.float32),
                             stop_gradient=False)
        out = getattr(api, opname)(x, y, a=3.0)
        out.sum().backward()
        np.testing.assert_allclose(
            np.asarray(out._value),
            3.0 * np.asarray(x._value) + np.asarray(y._value), rtol=1e-5,
            atol=1e-6)
        np.testing.assert_allclose(np.asarray(x.grad._value), 3.0, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(y.grad._value), 1.0, rtol=1e-6)

    def test_jit_to_static_integration(self):
        opname = _unique("cube_op")

        @register_custom_op(name=opname)
        def cube(x):
            return x ** 3

        from paddle_tpu import jit

        @jit.to_static
        def f(x):
            return getattr(api, opname)(x) + 1.0

        x = paddle.to_tensor(np.array([1.0, 2.0], np.float32))
        np.testing.assert_allclose(np.asarray(f(x)._value), [2.0, 9.0],
                                   rtol=1e-6)

    def test_infer_meta(self):
        opname = _unique("pad_double")

        @register_custom_op(name=opname)
        def pad_double(x):
            return jnp.concatenate([x, x], axis=0)

        from paddle_tpu.ops.registry import get_op

        aval = get_op(opname).infer_meta(
            Tensor(jnp.zeros((3, 4), jnp.float32)))
        assert tuple(aval.shape) == (6, 4)

    def test_unhashable_attr_raises(self):
        opname = _unique("bad_attr")

        def bwd(x, out, g, *, w):
            return g

        @register_custom_op(name=opname, backward=bwd)
        def bad(x, *, w=None):
            return x

        x = paddle.to_tensor(np.zeros(3, np.float32), stop_gradient=False)
        with pytest.raises(TypeError, match="hashable"):
            getattr(api, opname)(x, w=[1, 2])
