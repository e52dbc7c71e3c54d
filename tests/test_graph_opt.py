"""Graph-optimization passes: conv-bias->BN elision, and the pointwise
convolutions against numpy.

The fold pass (executor._plan_conv_bias_bn_fold) removes the mathematically
-zero-gradient bias of a conv feeding a BatchNorm (the Gluon zoo's
BottleneckV1 pattern, reference gluon/model_zoo/vision/resnet.py:107,113)
and must be numerically invisible to users. A 1x1 convolution is a matrix
product over the channels: value and both gradients are held to that form
written in numpy, strided or not, in both layouts.
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.test_utils import assert_almost_equal


def _bind_conv_bn(x, w, b, gamma, beta, mm, mv, layout="NCHW",
                  stride=(1, 1)):
    """conv(+bias)->BN->sum graph bound with grads (env read at bind)."""
    data = mx.sym.var("data")
    weight = mx.sym.var("weight")
    bias = mx.sym.var("bias")
    axis = 1 if layout == "NCHW" else 3
    conv = mx.sym.Convolution(data, weight, bias, kernel=(1, 1),
                              stride=stride, num_filter=w.shape[0],
                              layout=layout)
    bn = mx.sym.BatchNorm(conv, mx.sym.var("gamma"), mx.sym.var("beta"),
                          mx.sym.var("mm"), mx.sym.var("mv"),
                          fix_gamma=False, axis=axis, momentum=0.9)
    # nonlinear head — sum(bn) alone is constant in w AND b (normalized
    # outputs sum to N*H*W*beta), which would make every grad trivially 0
    out = mx.sym.sum(mx.sym.Activation(bn, act_type="relu"))
    return out.bind(
        mx.cpu(),
        args={"data": mx.nd.array(x), "weight": mx.nd.array(w),
              "bias": mx.nd.array(b), "gamma": mx.nd.array(gamma),
              "beta": mx.nd.array(beta)},
        args_grad={"data": mx.nd.zeros(x.shape),
                   "weight": mx.nd.zeros(w.shape),
                   "bias": mx.nd.zeros(b.shape),
                   "gamma": mx.nd.zeros(gamma.shape),
                   "beta": mx.nd.zeros(beta.shape)},
        aux_states={"mm": mx.nd.array(mm), "mv": mx.nd.array(mv)})


def _run_fold(monkeypatch, disabled, train=True):
    rng = np.random.RandomState(7)
    x = rng.uniform(-1, 1, (4, 3, 6, 6)).astype(np.float32)
    w = rng.uniform(-1, 1, (5, 3, 1, 1)).astype(np.float32)
    b = rng.uniform(-1, 1, (5,)).astype(np.float32)
    gamma = rng.uniform(0.5, 1.5, (5,)).astype(np.float32)
    beta = rng.uniform(-1, 1, (5,)).astype(np.float32)
    mm = rng.uniform(-0.5, 0.5, (5,)).astype(np.float32)
    mv = rng.uniform(0.5, 1.5, (5,)).astype(np.float32)
    if disabled:
        monkeypatch.setenv("MXNET_FOLD_CONV_BIAS_BN", "0")
    else:
        monkeypatch.delenv("MXNET_FOLD_CONV_BIAS_BN", raising=False)
    exe = _bind_conv_bn(x, w, b, gamma, beta, mm, mv)
    if train:
        exe.forward(is_train=True)
        exe.backward()
    else:
        exe.forward(is_train=False)
    return exe


@pytest.mark.parametrize("train", [True, False])
def test_conv_bias_bn_fold_matches_unfolded(monkeypatch, train):
    ref = _run_fold(monkeypatch, disabled=True, train=train)
    opt = _run_fold(monkeypatch, disabled=False, train=train)
    assert_almost_equal(opt.outputs[0], ref.outputs[0].asnumpy(),
                        rtol=1e-5, atol=1e-5)
    # running stats must track the x+b domain exactly like the reference
    for a, r in zip(opt.aux_arrays, ref.aux_arrays):
        assert_almost_equal(a, r.asnumpy(), rtol=1e-5, atol=1e-5)
    if train:
        names = opt._symbol.list_arguments()
        for name, ga, gr in zip(names, opt.grad_arrays, ref.grad_arrays):
            if name == "bias":
                # both are "mathematically zero + rounding": the unfolded
                # graph computes the zero through a full reduce (fp32 fuzz
                # ~1e-4), the folded graph short-circuits it
                assert np.all(np.abs(ga.asnumpy()) < 1e-3)
                assert np.all(np.abs(gr.asnumpy()) < 1e-3)
            else:
                # rounding order differs (stats of x vs x+b): fp32 noise
                assert_almost_equal(ga, gr.asnumpy(), rtol=1e-3, atol=1e-4)


def test_conv_bias_bn_fold_bias_grad_zero(monkeypatch):
    monkeypatch.delenv("MXNET_FOLD_CONV_BIAS_BN", raising=False)
    exe = _run_fold(monkeypatch, disabled=False, train=True)
    names = exe._symbol.list_arguments()
    gbias = exe.grad_arrays[names.index("bias")].asnumpy()
    assert np.all(gbias == 0.0)


def test_conv_bias_bn_fold_skips_shared_conv_output(monkeypatch):
    """Conv output consumed by BOTH a BN and a plain add: fold must not
    fire (the second consumer sees the biased activation)."""
    monkeypatch.delenv("MXNET_FOLD_CONV_BIAS_BN", raising=False)
    rng = np.random.RandomState(3)
    x = rng.uniform(-1, 1, (2, 3, 4, 4)).astype(np.float32)
    w = rng.uniform(-1, 1, (3, 3, 1, 1)).astype(np.float32)
    b = rng.uniform(-1, 1, (3,)).astype(np.float32)
    ones = np.ones((3,), np.float32)
    zeros = np.zeros((3,), np.float32)
    data = mx.sym.var("data")
    conv = mx.sym.Convolution(data, mx.sym.var("weight"), mx.sym.var("bias"),
                              kernel=(1, 1), num_filter=3)
    bn = mx.sym.BatchNorm(conv, mx.sym.var("gamma"), mx.sym.var("beta"),
                          mx.sym.var("mm"), mx.sym.var("mv"),
                          fix_gamma=False)
    out = mx.sym.sum(bn + conv)
    exe = out.bind(mx.cpu(),
                   args={"data": mx.nd.array(x), "weight": mx.nd.array(w),
                         "bias": mx.nd.array(b), "gamma": mx.nd.array(ones),
                         "beta": mx.nd.array(zeros)},
                   args_grad={n: mx.nd.zeros(s) for n, s in
                              [("data", x.shape), ("weight", w.shape),
                               ("bias", b.shape), ("gamma", (3,)),
                               ("beta", (3,))]},
                   aux_states={"mm": mx.nd.array(zeros),
                               "mv": mx.nd.array(ones)})
    exe.forward(is_train=True)
    exe.backward()
    names = exe._symbol.list_arguments()
    gbias = exe.grad_arrays[names.index("bias")].asnumpy()
    # the add branch gives the bias a REAL gradient: sum over N,H,W = 2*4*4
    assert_almost_equal(gbias, np.full((3,), 32.0), rtol=1e-4)


@pytest.mark.parametrize("train", [True, False])
def test_relu_pool_fold_matches_unfolded(monkeypatch, train):
    """relu folded into its sole-consumer maxpool: outputs and grads must
    match the explicit relu->maxpool graph."""
    rng = np.random.RandomState(21)
    x = rng.uniform(-2, 2, (2, 3, 10, 10)).astype(np.float32)
    head = rng.uniform(-1, 1, (2, 3, 5, 5)).astype(np.float32)
    data = mx.sym.var("data")
    net = mx.sym.Activation(data, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                         pool_type="max")

    def run():
        exe = net.bind(mx.cpu(), args={"data": mx.nd.array(x)},
                       args_grad={"data": mx.nd.zeros(x.shape)})
        exe.forward(is_train=train)
        if train:
            exe.backward(mx.nd.array(head))
        return (exe.outputs[0].asnumpy(),
                exe.grad_arrays[0].asnumpy() if train else None)

    monkeypatch.setenv("MXNET_FOLD_RELU_POOL", "0")
    out_ref, g_ref = run()
    monkeypatch.delenv("MXNET_FOLD_RELU_POOL", raising=False)
    out_opt, g_opt = run()
    assert_almost_equal(out_opt, out_ref, rtol=1e-6, atol=1e-7)
    assert (out_opt >= 0).all()
    if train:
        assert_almost_equal(g_opt, g_ref, rtol=1e-5, atol=1e-6)


def test_relu_pool_fold_skips_shared_relu():
    """relu consumed by maxpool AND another op must not fold."""
    rng = np.random.RandomState(4)
    x = rng.uniform(-2, 2, (2, 3, 8, 8)).astype(np.float32)
    data = mx.sym.var("data")
    act = mx.sym.Activation(data, act_type="relu")
    pool = mx.sym.Pooling(act, kernel=(2, 2), stride=(2, 2), pool_type="max")
    out = mx.sym.Group([pool, mx.sym.sum(act)])
    exe = out.bind(mx.cpu(), args={"data": mx.nd.array(x)})
    exe.forward(is_train=False)
    # the second output must see the REAL relu (nonnegative, elementwise)
    relu_sum = exe.outputs[1].asnumpy()
    assert_almost_equal(relu_sum, np.maximum(x, 0).sum(), rtol=1e-5)


def _kept_pixels(x, stride, layout):
    return x[:, :, ::stride[0], ::stride[1]] if layout == "NCHW" \
        else x[:, ::stride[0], ::stride[1], :]


def _conv1x1_numpy(x, w, stride, layout):
    """1x1 convolution written from its definition: a matrix product over
    the channels at every kept pixel."""
    return np.einsum(
        "nchw,oc->nohw" if layout == "NCHW" else "nhwc,oc->nhwo",
        _kept_pixels(x, stride, layout), w.reshape(w.shape[0], -1))


def _conv1x1_grads_numpy(dy, x, w, stride, layout):
    """Its gradients in closed form: dx is dy @ W written into the kept
    pixels of a zero array, dw is dy^T @ x[kept]."""
    forms = ("nohw,oc->nchw", "nohw,nchw->oc") if layout == "NCHW" \
        else ("nhwo,oc->nhwc", "nhwo,nhwc->oc")
    dx = np.zeros_like(x)
    _kept_pixels(dx, stride, layout)[...] = np.einsum(
        forms[0], dy, w.reshape(w.shape[0], -1))
    dw = np.einsum(forms[1], dy, _kept_pixels(x, stride, layout))
    return dx, dw.reshape(w.shape)


@pytest.mark.parametrize("layout,stride", [
    ("NCHW", (1, 1)), ("NCHW", (2, 2)), ("NHWC", (1, 1)), ("NHWC", (2, 2)),
])
def test_conv1x1_matches_einsum(layout, stride):
    rng = np.random.RandomState(11)
    if layout == "NCHW":
        x = rng.uniform(-1, 1, (2, 6, 8, 8)).astype(np.float32)
        w = rng.uniform(-1, 1, (4, 6, 1, 1)).astype(np.float32)
    else:
        x = rng.uniform(-1, 1, (2, 8, 8, 6)).astype(np.float32)
        w = rng.uniform(-1, 1, (4, 1, 1, 6)).astype(np.float32)
    out = mx.nd.Convolution(mx.nd.array(x), mx.nd.array(w),
                            kernel=(1, 1), stride=stride, num_filter=4,
                            no_bias=True, layout=layout).asnumpy()
    ref = _conv1x1_numpy(x, w, stride, layout)
    assert out.shape == ref.shape
    assert_almost_equal(out, ref, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("layout", ["NCHW", "NHWC"])
def test_conv1x1_strided_grads(layout):
    """Strided 1x1 conv: the input gradient is dy @ W written into the
    kept pixels of a zero array, the weight gradient dy^T @ x[::2, ::2]."""
    from mxnet_tpu.test_utils import check_numeric_gradient
    rng = np.random.RandomState(13)
    if layout == "NCHW":
        x = rng.uniform(-1, 1, (2, 3, 7, 7)).astype(np.float32)
        w = rng.uniform(-1, 1, (4, 3, 1, 1)).astype(np.float32)
    else:
        x = rng.uniform(-1, 1, (2, 7, 7, 3)).astype(np.float32)
        w = rng.uniform(-1, 1, (4, 1, 1, 3)).astype(np.float32)
    conv = mx.sym.Convolution(mx.sym.var("data"), mx.sym.var("weight"),
                              kernel=(1, 1), stride=(2, 2), num_filter=4,
                              no_bias=True, layout=layout)
    head_np = rng.uniform(-1, 1, (2, 4, 4, 4)).astype(np.float32)
    exe = conv.bind(mx.cpu(),
                    args={"data": mx.nd.array(x), "weight": mx.nd.array(w)},
                    args_grad={"data": mx.nd.zeros(x.shape),
                               "weight": mx.nd.zeros(w.shape)})
    exe.forward(is_train=True)
    exe.backward(mx.nd.array(head_np))
    out_ref = _conv1x1_numpy(x, w, (2, 2), layout)
    dx_ref, dw_ref = _conv1x1_grads_numpy(head_np, x, w, (2, 2), layout)
    assert_almost_equal(exe.outputs[0].asnumpy(), out_ref,
                        rtol=1e-5, atol=1e-6)
    assert_almost_equal(exe.grad_dict["data"].asnumpy(), dx_ref,
                        rtol=1e-4, atol=1e-5)
    assert_almost_equal(exe.grad_dict["weight"].asnumpy(), dw_ref,
                        rtol=1e-4, atol=1e-5)
    check_numeric_gradient(conv, {"data": x, "weight": w},
                           numeric_eps=1e-2, rtol=5e-2, atol=1e-3)


def test_conv1x1_numeric_gradients():
    from mxnet_tpu.test_utils import check_numeric_gradient
    rng = np.random.RandomState(5)
    x = rng.uniform(-1, 1, (2, 3, 6, 6)).astype(np.float32)
    w = rng.uniform(-1, 1, (4, 3, 1, 1)).astype(np.float32)
    conv = mx.sym.Convolution(mx.sym.var("data"), mx.sym.var("weight"),
                              kernel=(1, 1), stride=(2, 2), num_filter=4,
                              no_bias=True)
    check_numeric_gradient(conv, {"data": x, "weight": w},
                           numeric_eps=1e-2, rtol=5e-2, atol=1e-3)


@pytest.mark.parametrize("shape", [
    (4, 4, 4, 256, 128),      # (N, Ho, Wo, K, C): tiny c3-entry-like
    (2, 7, 7, 256, 128),      # odd spatial extents, c5-downsample-like
    (8, 2, 2, 128, 256),
])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_strided_1x1_dgrad_matches_matmul_interleave(shape, dtype):
    """The input gradient of the stride-2 NHWC 1x1 conv at ResNet's
    stage-entry widths: dy @ W in the even rows and columns, and exactly
    zero in the odd ones."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import nn as nn_ops
    N, Ho, Wo, K, C = shape
    rng = np.random.RandomState(0)
    dy = jnp.asarray(rng.randn(N, Ho, Wo, K), dtype)
    w = jnp.asarray(rng.randn(K, 1, 1, C), dtype)
    params = {"kernel": (1, 1), "stride": (2, 2), "no_bias": True,
              "layout": "NHWC", "num_filter": K}
    x = jnp.zeros((N, 2 * Ho, 2 * Wo, C), dtype)
    _, vjp = jax.vjp(lambda d: nn_ops._convolution(params, d, w)[0], x)
    got = np.asarray(vjp(dy)[0], np.float32)
    want = np.zeros(x.shape, np.float32)
    want[:, ::2, ::2, :] = np.einsum(
        "nhwo,oc->nhwc", np.asarray(dy, np.float32),
        np.asarray(w, np.float32).reshape(K, C))
    tol = 1e-4 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    assert np.all(got[:, 1::2, :, :] == 0) and np.all(got[:, :, 1::2, :] == 0)


@pytest.mark.parametrize("stride,shapes", [
    ((2, 2), (2, 8, 8, 128, 64)),
    ((1, 1), (2, 8, 8, 128, 64)),
    ((2, 2), (2, 8, 8, 96, 64)),      # channels not lane-aligned
])
def test_conv1x1_nhwc_value_and_grads_match_einsum(stride, shapes):
    """Forward and both gradients of sum(conv(x, w)^2) against the einsum
    forms, at widths where the channels fill (or miss) whole lanes."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import nn as nn_ops
    N, H, W, C, K = shapes
    rng = np.random.RandomState(1)
    x = rng.randn(N, H, W, C).astype(np.float32)
    w = rng.randn(K, 1, 1, C).astype(np.float32)
    params = {"kernel": (1, 1), "stride": stride, "no_bias": True,
              "layout": "NHWC", "num_filter": K}

    def conv(x, w):
        return nn_ops._convolution(params, x, w)[0]

    got_y = conv(jnp.asarray(x), jnp.asarray(w))
    got_dx, got_dw = jax.grad(lambda x, w: jnp.sum(conv(x, w) ** 2),
                              argnums=(0, 1))(jnp.asarray(x), jnp.asarray(w))
    want_y = _conv1x1_numpy(x, w, stride, "NHWC")
    want_dx, want_dw = _conv1x1_grads_numpy(2 * want_y, x, w, stride, "NHWC")
    for got, want in ((got_y, want_y), (got_dx, want_dx), (got_dw, want_dw)):
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=2e-3, atol=2e-3)
