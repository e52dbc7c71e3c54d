"""Operator tests (modeled on reference tests/python/unittest/test_operator.py):
NumPy-oracle forward checks + numeric-gradient backward checks."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.test_utils import (assert_almost_equal, check_numeric_gradient,
                                  check_symbolic_forward)


def test_fully_connected():
    x = np.random.uniform(size=(4, 10)).astype(np.float32)
    w = np.random.uniform(size=(5, 10)).astype(np.float32)
    b = np.random.uniform(size=(5,)).astype(np.float32)
    out = mx.nd.FullyConnected(mx.nd.array(x), mx.nd.array(w), mx.nd.array(b),
                               num_hidden=5)
    assert_almost_equal(out, x @ w.T + b, rtol=1e-4)
    data = mx.sym.var("data")
    weight = mx.sym.var("weight")
    bias = mx.sym.var("bias")
    fc = mx.sym.FullyConnected(data, weight, bias, num_hidden=5)
    check_symbolic_forward(fc, {"data": x, "weight": w, "bias": b},
                           [x @ w.T + b], rtol=1e-4)
    check_numeric_gradient(fc, {"data": x, "weight": w, "bias": b},
                           numeric_eps=1e-2, rtol=5e-2, atol=1e-3)


def test_convolution_forward():
    # oracle: direct conv computed via numpy
    x = np.random.uniform(-1, 1, (2, 3, 7, 7)).astype(np.float32)
    w = np.random.uniform(-1, 1, (4, 3, 3, 3)).astype(np.float32)
    b = np.zeros(4, np.float32)
    out = mx.nd.Convolution(mx.nd.array(x), mx.nd.array(w), mx.nd.array(b),
                            kernel=(3, 3), num_filter=4).asnumpy()
    assert out.shape == (2, 4, 5, 5)
    ref = np.zeros_like(out)
    for n in range(2):
        for f in range(4):
            for i in range(5):
                for j in range(5):
                    ref[n, f, i, j] = (x[n, :, i:i + 3, j:j + 3] * w[f]).sum()
    assert_almost_equal(out, ref, rtol=1e-3, atol=1e-4)


def test_convolution_options():
    x = np.random.uniform(-1, 1, (2, 4, 8, 8)).astype(np.float32)
    # stride + pad
    out = mx.nd.Convolution(mx.nd.array(x),
                            mx.nd.array(np.random.uniform(-1, 1, (6, 4, 3, 3)).astype(np.float32)),
                            kernel=(3, 3), num_filter=6, stride=(2, 2),
                            pad=(1, 1), no_bias=True)
    assert out.shape == (2, 6, 4, 4)
    # dilate
    out = mx.nd.Convolution(mx.nd.array(x),
                            mx.nd.array(np.random.uniform(-1, 1, (6, 4, 3, 3)).astype(np.float32)),
                            kernel=(3, 3), num_filter=6, dilate=(2, 2), no_bias=True)
    assert out.shape == (2, 6, 4, 4)
    # grouped
    out = mx.nd.Convolution(mx.nd.array(x),
                            mx.nd.array(np.random.uniform(-1, 1, (4, 2, 3, 3)).astype(np.float32)),
                            kernel=(3, 3), num_filter=4, num_group=2, no_bias=True)
    assert out.shape == (2, 4, 6, 6)
    # 1D and 3D
    out = mx.nd.Convolution(mx.nd.ones((2, 3, 10)),
                            mx.nd.ones((4, 3, 3)), kernel=(3,), num_filter=4,
                            no_bias=True)
    assert out.shape == (2, 4, 8)
    out = mx.nd.Convolution(mx.nd.ones((1, 2, 5, 5, 5)),
                            mx.nd.ones((3, 2, 2, 2, 2)), kernel=(2, 2, 2),
                            num_filter=3, no_bias=True)
    assert out.shape == (1, 3, 4, 4, 4)


def test_deconvolution():
    x = mx.nd.ones((1, 2, 4, 4))
    w = mx.nd.ones((2, 3, 3, 3))
    out = mx.nd.Deconvolution(x, w, kernel=(3, 3), num_filter=3, no_bias=True)
    assert out.shape == (1, 3, 6, 6)
    out2 = mx.nd.Deconvolution(x, w, kernel=(3, 3), num_filter=3,
                               stride=(2, 2), pad=(1, 1), adj=(1, 1),
                               no_bias=True)
    assert out2.shape == (1, 3, 8, 8)
    # deconv(conv) roundtrip shape: (i-1)*s - 2p + k + adj
    data = mx.sym.var("data")
    dec = mx.sym.Deconvolution(data, mx.sym.var("w"), kernel=(3, 3),
                               num_filter=3, no_bias=True)
    x_np = np.random.uniform(size=(1, 2, 4, 4)).astype(np.float32)
    w_np = np.random.uniform(size=(2, 3, 3, 3)).astype(np.float32)
    check_numeric_gradient(dec, {"data": x_np, "w": w_np}, numeric_eps=1e-2,
                           rtol=5e-2, atol=1e-3)


def test_pooling():
    x_np = np.random.uniform(size=(2, 3, 6, 6)).astype(np.float32)
    x = mx.nd.array(x_np)
    out = mx.nd.Pooling(x, kernel=(2, 2), stride=(2, 2), pool_type="max")
    ref = x_np.reshape(2, 3, 3, 2, 3, 2).max(axis=(3, 5))
    assert_almost_equal(out, ref)
    out = mx.nd.Pooling(x, kernel=(2, 2), stride=(2, 2), pool_type="avg")
    ref = x_np.reshape(2, 3, 3, 2, 3, 2).mean(axis=(3, 5))
    assert_almost_equal(out, ref, rtol=1e-5)
    gout = mx.nd.Pooling(x, global_pool=True, pool_type="max", kernel=(1, 1))
    assert_almost_equal(gout.squeeze(), x_np.max(axis=(2, 3)), rtol=1e-5)
    gavg = mx.nd.Pooling(x, global_pool=True, pool_type="avg", kernel=(1, 1))
    assert_almost_equal(gavg.squeeze(), x_np.mean(axis=(2, 3)), rtol=1e-5)


def test_activation_ops():
    x_np = np.random.uniform(-2, 2, (3, 4)).astype(np.float32)
    x = mx.nd.array(x_np)
    assert_almost_equal(mx.nd.Activation(x, act_type="relu"),
                        np.maximum(x_np, 0))
    assert_almost_equal(mx.nd.Activation(x, act_type="tanh"), np.tanh(x_np),
                        rtol=1e-4)
    assert_almost_equal(mx.nd.Activation(x, act_type="sigmoid"),
                        1 / (1 + np.exp(-x_np)), rtol=1e-4)
    assert_almost_equal(mx.nd.Activation(x, act_type="softrelu"),
                        np.log1p(np.exp(x_np)), rtol=1e-4)
    assert_almost_equal(mx.nd.LeakyReLU(x, act_type="leaky", slope=0.1),
                        np.where(x_np >= 0, x_np, 0.1 * x_np), rtol=1e-5)
    assert_almost_equal(mx.nd.LeakyReLU(x, act_type="elu", slope=1.0),
                        np.where(x_np >= 0, x_np, np.expm1(x_np)), rtol=1e-4)


def test_softmax_ops():
    x_np = np.random.uniform(-2, 2, (3, 5)).astype(np.float32)
    x = mx.nd.array(x_np)
    e = np.exp(x_np - x_np.max(1, keepdims=True))
    ref = e / e.sum(1, keepdims=True)
    assert_almost_equal(mx.nd.softmax(x), ref, rtol=1e-4)
    assert_almost_equal(mx.nd.log_softmax(x), np.log(ref), rtol=1e-3, atol=1e-5)
    assert_almost_equal(mx.nd.softmax(x, temperature=2.0),
                        np.exp(x_np / 2) / np.exp(x_np / 2).sum(1, keepdims=True),
                        rtol=1e-4)


def test_batchnorm():
    x_np = np.random.uniform(-1, 1, (4, 3, 5, 5)).astype(np.float32)
    gamma = np.random.uniform(0.5, 1.5, (3,)).astype(np.float32)
    beta = np.random.uniform(-0.5, 0.5, (3,)).astype(np.float32)
    mm = np.zeros(3, np.float32)
    mv = np.ones(3, np.float32)
    arrs = [mx.nd.array(v) for v in (x_np, gamma, beta, mm, mv)]
    with mx.autograd.train_mode():
        out = mx.nd.BatchNorm(*arrs, fix_gamma=False, eps=1e-5, momentum=0.9)
    out = out[0] if isinstance(out, list) else out
    mean = x_np.mean(axis=(0, 2, 3))
    var = x_np.var(axis=(0, 2, 3))
    ref = (x_np - mean.reshape(1, 3, 1, 1)) / np.sqrt(var.reshape(1, 3, 1, 1) + 1e-5)
    ref = ref * gamma.reshape(1, 3, 1, 1) + beta.reshape(1, 3, 1, 1)
    assert_almost_equal(out, ref, rtol=1e-3, atol=1e-4)
    # moving stats updated in place
    assert_almost_equal(arrs[3], 0.9 * mm + 0.1 * mean, rtol=1e-4)
    assert_almost_equal(arrs[4], 0.9 * mv + 0.1 * var, rtol=1e-4)
    # inference mode uses the moving stats
    out2 = mx.nd.BatchNorm(*arrs, fix_gamma=False, eps=1e-5)
    out2 = out2[0] if isinstance(out2, list) else out2
    cur_mm, cur_mv = arrs[3].asnumpy(), arrs[4].asnumpy()
    ref2 = (x_np - cur_mm.reshape(1, 3, 1, 1)) / np.sqrt(cur_mv.reshape(1, 3, 1, 1) + 1e-5)
    ref2 = ref2 * gamma.reshape(1, 3, 1, 1) + beta.reshape(1, 3, 1, 1)
    assert_almost_equal(out2, ref2, rtol=1e-3, atol=1e-4)


def test_layernorm():
    x_np = np.random.uniform(-1, 1, (4, 6)).astype(np.float32)
    g = np.random.uniform(0.5, 1.5, (6,)).astype(np.float32)
    b = np.random.uniform(-0.5, 0.5, (6,)).astype(np.float32)
    out = mx.nd.LayerNorm(mx.nd.array(x_np), mx.nd.array(g), mx.nd.array(b))
    out = out[0] if isinstance(out, list) else out
    mean = x_np.mean(-1, keepdims=True)
    std = x_np.std(-1, keepdims=True)
    ref = (x_np - mean) / np.sqrt(std ** 2 + 1e-5) * g + b
    assert_almost_equal(out, ref, rtol=1e-3, atol=1e-4)


def test_dropout():
    x = mx.nd.ones((100, 100))
    with mx.autograd.train_mode():
        out = mx.nd.Dropout(x, p=0.5)
    out = out[0] if isinstance(out, list) else out
    arr = out.asnumpy()
    frac = (arr == 0).mean()
    assert 0.35 < frac < 0.65
    nz = arr[arr != 0]
    assert_almost_equal(nz, np.full_like(nz, 2.0))
    # eval mode = identity
    out = mx.nd.Dropout(x, p=0.5)
    out = out[0] if isinstance(out, list) else out
    assert (out.asnumpy() == 1).all()


def test_embedding_op():
    w = np.random.uniform(size=(10, 4)).astype(np.float32)
    idx = np.array([1, 3, 5], np.float32)
    out = mx.nd.Embedding(mx.nd.array(idx), mx.nd.array(w), input_dim=10,
                          output_dim=4)
    assert_almost_equal(out, w[[1, 3, 5]])


def test_softmax_output_grad():
    x_np = np.random.uniform(-1, 1, (4, 5)).astype(np.float32)
    label_np = np.array([0, 2, 4, 1], np.float32)
    data = mx.sym.var("data")
    label = mx.sym.var("label")
    sym = mx.sym.SoftmaxOutput(data, label, name="softmax")
    exe = sym.bind(mx.cpu(), {"data": mx.nd.array(x_np), "label": mx.nd.array(label_np)},
                   args_grad={"data": mx.nd.zeros((4, 5))},
                   grad_req={"data": "write"})
    exe.forward(is_train=True)
    e = np.exp(x_np - x_np.max(1, keepdims=True))
    p = e / e.sum(1, keepdims=True)
    assert_almost_equal(exe.outputs[0], p, rtol=1e-4)
    exe.backward()
    oh = np.eye(5, dtype=np.float32)[label_np.astype(int)]
    assert_almost_equal(exe.grad_dict["data"], p - oh, rtol=1e-4)


def test_regression_outputs():
    x_np = np.random.uniform(-1, 1, (4, 3)).astype(np.float32)
    y_np = np.random.uniform(-1, 1, (4, 3)).astype(np.float32)
    data, label = mx.sym.var("data"), mx.sym.var("label")
    lin = mx.sym.LinearRegressionOutput(data, label)
    exe = lin.bind(mx.cpu(), {"data": mx.nd.array(x_np), "label": mx.nd.array(y_np)},
                   args_grad={"data": mx.nd.zeros((4, 3))},
                   grad_req={"data": "write"})
    exe.forward(is_train=True)
    assert_almost_equal(exe.outputs[0], x_np)
    exe.backward()
    assert_almost_equal(exe.grad_dict["data"], x_np - y_np, rtol=1e-5)
    log = mx.sym.LogisticRegressionOutput(data, label)
    exe = log.bind(mx.cpu(), {"data": mx.nd.array(x_np), "label": mx.nd.array(y_np)},
                   args_grad={"data": mx.nd.zeros((4, 3))},
                   grad_req={"data": "write"})
    exe.forward(is_train=True)
    sig = 1 / (1 + np.exp(-x_np))
    assert_almost_equal(exe.outputs[0], sig, rtol=1e-4)
    exe.backward()
    assert_almost_equal(exe.grad_dict["data"], sig - y_np, rtol=1e-4)


def test_sequence_ops():
    data = np.arange(24, dtype=np.float32).reshape(4, 3, 2)  # (T,B,C)
    seqlen = np.array([2, 3, 1], np.float32)
    out = mx.nd.SequenceMask(mx.nd.array(data), mx.nd.array(seqlen),
                             use_sequence_length=True, value=-1.0)
    ref = data.copy()
    for b, l in enumerate(seqlen.astype(int)):
        ref[l:, b, :] = -1
    assert_almost_equal(out, ref)
    last = mx.nd.SequenceLast(mx.nd.array(data), mx.nd.array(seqlen),
                              use_sequence_length=True)
    ref_last = np.stack([data[int(l) - 1, b] for b, l in enumerate(seqlen)])
    assert_almost_equal(last, ref_last)
    rev = mx.nd.SequenceReverse(mx.nd.array(data), mx.nd.array(seqlen),
                                use_sequence_length=True)
    ref_rev = data.copy()
    for b, l in enumerate(seqlen.astype(int)):
        ref_rev[:l, b] = data[:l, b][::-1]
    assert_almost_equal(rev, ref_rev)


def test_rnn_op_shapes():
    T, B, I, H = 5, 3, 4, 6
    from mxnet_tpu.ops.nn import rnn_param_size
    for mode, nstate in [("rnn_tanh", 1), ("gru", 1), ("lstm", 2)]:
        psize = rnn_param_size(2, I, H, False, mode)
        data = mx.nd.random.normal(shape=(T, B, I))
        params = mx.nd.random.normal(shape=(psize,)) * 0.1
        state = mx.nd.zeros((2, B, H))
        args = [data, params, state]
        if mode == "lstm":
            args.append(mx.nd.zeros((2, B, H)))
        outs = mx.nd.RNN(*args, state_size=H, num_layers=2, mode=mode,
                         state_outputs=True)
        assert outs[0].shape == (T, B, H)
        assert outs[1].shape == (2, B, H)
        if mode == "lstm":
            assert outs[2].shape == (2, B, H)
    # bidirectional
    psize = rnn_param_size(1, I, H, True, "lstm")
    outs = mx.nd.RNN(mx.nd.random.normal(shape=(T, B, I)),
                     mx.nd.random.normal(shape=(psize,)) * 0.1,
                     mx.nd.zeros((2, B, H)), mx.nd.zeros((2, B, H)),
                     state_size=H, num_layers=1, bidirectional=True,
                     mode="lstm", state_outputs=True)
    assert outs[0].shape == (T, B, 2 * H)


def test_lstm_vs_manual():
    """Fused RNN(lstm) matches a hand-rolled cell."""
    T, B, I, H = 3, 2, 4, 5
    from mxnet_tpu.ops.nn import rnn_param_size
    psize = rnn_param_size(1, I, H, False, "lstm")
    params = np.random.uniform(-0.5, 0.5, (psize,)).astype(np.float32)
    data = np.random.uniform(-1, 1, (T, B, I)).astype(np.float32)
    out = mx.nd.RNN(mx.nd.array(data), mx.nd.array(params),
                    mx.nd.zeros((1, B, H)), mx.nd.zeros((1, B, H)),
                    state_size=H, num_layers=1, mode="lstm",
                    state_outputs=False)
    w_i2h = params[:4 * H * I].reshape(4 * H, I)
    w_h2h = params[4 * H * I:4 * H * I + 4 * H * H].reshape(4 * H, H)
    b = params[4 * H * I + 4 * H * H:]
    b_i2h, b_h2h = b[:4 * H], b[4 * H:]

    def sig(v):
        return 1 / (1 + np.exp(-v))

    h = np.zeros((B, H), np.float32)
    c = np.zeros((B, H), np.float32)
    ys = []
    for t in range(T):
        g = data[t] @ w_i2h.T + b_i2h + h @ w_h2h.T + b_h2h
        i, f, gg, o = np.split(g, 4, axis=1)
        c = sig(f) * c + sig(i) * np.tanh(gg)
        h = sig(o) * np.tanh(c)
        ys.append(h)
    assert_almost_equal(out, np.stack(ys), rtol=1e-4, atol=1e-5)


def test_random_ops():
    mx.random.seed(42)
    u = mx.nd.random.uniform(0, 1, shape=(1000,))
    arr = u.asnumpy()
    assert 0 <= arr.min() and arr.max() <= 1
    assert abs(arr.mean() - 0.5) < 0.05
    n = mx.nd.random.normal(2.0, 3.0, shape=(2000,))
    assert abs(n.asnumpy().mean() - 2.0) < 0.3
    assert abs(n.asnumpy().std() - 3.0) < 0.3
    # seeding is reproducible
    mx.random.seed(7)
    a = mx.nd.random.uniform(shape=(5,)).asnumpy()
    mx.random.seed(7)
    b = mx.nd.random.uniform(shape=(5,)).asnumpy()
    assert_almost_equal(a, b)
    p = mx.nd.random.poisson(lam=4.0, shape=(2000,))
    assert abs(p.asnumpy().mean() - 4.0) < 0.3
    g = mx.nd.random.gamma(alpha=2.0, beta=2.0, shape=(2000,))
    assert abs(g.asnumpy().mean() - 4.0) < 0.5
    m = mx.nd.random.multinomial(mx.nd.array([0.0, 0.0, 1.0]), shape=8)
    assert (m.asnumpy() == 2).all()


def test_optimizer_update_ops():
    w = mx.nd.ones((4,))
    g = mx.nd.ones((4,)) * 0.5
    mx.nd.sgd_update(w, g, lr=0.1, out=w)
    assert_almost_equal(w, np.full(4, 0.95, np.float32), rtol=1e-5)
    mom = mx.nd.zeros((4,))
    mx.nd.sgd_mom_update(w, g, mom, lr=0.1, momentum=0.9, out=w)
    assert_almost_equal(w, np.full(4, 0.90, np.float32), rtol=1e-5)
    assert_almost_equal(mom, np.full(4, -0.05, np.float32), rtol=1e-4)
    mean, var = mx.nd.zeros((4,)), mx.nd.zeros((4,))
    w2 = mx.nd.ones((4,))
    mx.nd.adam_update(w2, g, mean, var, lr=0.01, out=w2)
    assert (w2.asnumpy() < 1).all()


def test_linalg_ops():
    a = np.random.uniform(size=(4, 4)).astype(np.float32)
    spd = a @ a.T + 4 * np.eye(4, dtype=np.float32)
    L = mx.nd.linalg_potrf(mx.nd.array(spd))
    assert_almost_equal(L.asnumpy() @ L.asnumpy().T, spd, rtol=1e-3, atol=1e-4)
    g = mx.nd.linalg_gemm2(mx.nd.array(a), mx.nd.array(a), transpose_b=True)
    assert_almost_equal(g, a @ a.T, rtol=1e-4)
    sld = mx.nd.linalg_sumlogdiag(mx.nd.array(spd))
    assert_almost_equal(sld, np.log(np.diag(spd)).sum(), rtol=1e-4)


def test_lrn():
    x = np.random.uniform(size=(2, 8, 4, 4)).astype(np.float32)
    out = mx.nd.LRN(mx.nd.array(x), nsize=5, alpha=1e-4, beta=0.75, knorm=2.0)
    half = 2
    ref = np.zeros_like(x)
    for c in range(8):
        lo, hi = max(0, c - half), min(8, c + half + 1)
        ssum = (x[:, lo:hi] ** 2).sum(axis=1)
        ref[:, c] = x[:, c] / np.power(2.0 + 1e-4 / 5 * ssum, 0.75)
    assert_almost_equal(out, ref, rtol=1e-4)


def test_box_ops():
    a = mx.nd.array([[0, 0, 2, 2], [1, 1, 3, 3]])
    b = mx.nd.array([[0, 0, 2, 2]])
    iou = mx.nd.box_iou(a, b)
    assert_almost_equal(iou, np.array([[1.0], [1.0 / 7.0]], np.float32), rtol=1e-4)
    dets = mx.nd.array([[[0, 0.9, 0, 0, 2, 2], [0, 0.8, 0.1, 0.1, 2, 2],
                         [1, 0.7, 5, 5, 7, 7]]])
    out = mx.nd.box_nms(dets, overlap_thresh=0.5)
    arr = out.asnumpy()[0]
    assert arr[0, 1] == pytest.approx(0.9)
    assert (arr[1] == -1).all()          # suppressed
    assert arr[2, 1] == pytest.approx(0.7)


def test_smooth_l1_where():
    x = np.array([-2.0, -0.5, 0.5, 2.0], np.float32)
    out = mx.nd.smooth_l1(mx.nd.array(x), scalar=1.0)
    ref = np.where(np.abs(x) < 1, 0.5 * x * x, np.abs(x) - 0.5)
    assert_almost_equal(out, ref)


def test_linalg_namespaces():
    import numpy as np
    A = mx.nd.array(np.array([[2.0, 1.0], [1.0, 2.0]], "f"))
    L = mx.nd.linalg.potrf(A)
    np.testing.assert_allclose(L.asnumpy() @ L.asnumpy().T, A.asnumpy(),
                               rtol=1e-5)
    out = mx.nd.linalg.gemm2(A, A)
    np.testing.assert_allclose(out.asnumpy(), A.asnumpy() @ A.asnumpy(),
                               rtol=1e-5)
    s = mx.sym.linalg.sumlogdiag(mx.sym.Variable("a"))
    _, o, _ = s.infer_shape(a=(3, 3))
    # deliberate delta vs reference: scalar () instead of (1,) — the
    # jnp.sum over the diagonal drops the axis (la_op.h keeps a 1-dim)
    assert o == [()]


def test_space_to_depth_conv_rewrite_matches_direct():
    """The TPU stem rewrite (_space_to_depth_conv) must be the EXACT same
    function as the stride-2 conv it replaces, gradients included."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from mxnet_tpu.ops.nn import _space_to_depth_conv

    rng = np.random.RandomState(0)
    for (C, k, pad, H) in [(3, 7, 3, 32), (1, 3, 1, 28), (4, 5, 2, 63),
                           (3, 8, 3, 64)]:
        x = jnp.asarray(rng.randn(2, C, H, H).astype(np.float32))
        w = jnp.asarray(rng.randn(8, C, k, k).astype(np.float32))
        dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                        ("NCHW", "OIHW", "NCHW"))

        def f_ref(x, w):
            return lax.conv_general_dilated(
                x, w, (2, 2), [(pad, pad), (pad, pad)],
                dimension_numbers=dn).sum()

        def f_got(x, w):
            return _space_to_depth_conv(x, w, (pad, pad)).sum()

        ref = lax.conv_general_dilated(x, w, (2, 2), [(pad, pad), (pad, pad)],
                                       dimension_numbers=dn)
        got = _space_to_depth_conv(x, w, (pad, pad))
        assert ref.shape == got.shape
        assert float(jnp.abs(ref - got).max()) < 1e-4
        for a, b in zip(jax.grad(f_ref, (0, 1))(x, w),
                        jax.grad(f_got, (0, 1))(x, w)):
            assert float(jnp.abs(a - b).max()) < 1e-3


def test_batchnorm_backward_oracle():
    """BN training-mode backward against the analytic batch-norm gradient
    (reference batch_norm-inl.h BatchNormBackward). The custom-VJP fused
    backward (ops/nn.py _bn_train_core) must match for both NCHW and NHWC
    axes and with fix_gamma on/off."""
    rng = np.random.RandomState(7)
    N, C, H, W = 4, 5, 3, 6
    eps = 1e-3

    def oracle(x, g, dy, axis):
        red = tuple(i for i in range(x.ndim) if i != axis)
        bs = tuple(-1 if i == axis else 1 for i in range(x.ndim))
        n = np.prod([x.shape[i] for i in red]).astype(np.float64)
        m = x.mean(axis=red).reshape(bs)
        v = ((x - m) ** 2).mean(axis=red).reshape(bs)
        inv = 1.0 / np.sqrt(v + eps)
        xhat = (x - m) * inv
        sdy = dy.sum(axis=red).reshape(bs)
        sdyx = (dy * xhat).sum(axis=red).reshape(bs)
        dx = (g.reshape(bs) * inv) * (dy - sdy / n - xhat * sdyx / n)
        return dx, np.squeeze(sdyx), np.squeeze(sdy)

    for axis, shape in ((1, (N, C, H, W)), (3, (N, H, W, C))):
        for fix_gamma in (False, True):
            x_np = (rng.randn(*shape) * 2 + 1).astype(np.float64)
            g_np = (rng.rand(C) + 0.5).astype(np.float64)
            b_np = rng.randn(C).astype(np.float64)
            dy_np = rng.randn(*shape).astype(np.float64)

            x = mx.nd.array(x_np, dtype="float64")
            g = mx.nd.array(g_np, dtype="float64")
            b = mx.nd.array(b_np, dtype="float64")
            mm = mx.nd.zeros((C,), dtype="float64")
            mv = mx.nd.ones((C,), dtype="float64")
            for t in (x, g, b):
                t.attach_grad()
            with mx.autograd.record():
                y = mx.nd.BatchNorm(x, g, b, mm, mv, eps=eps, axis=axis,
                                    fix_gamma=fix_gamma)
                y = y[0] if isinstance(y, list) else y
                head = mx.nd.array(dy_np, dtype="float64")
                loss = (y * head).sum()
            loss.backward()

            g_eff = np.ones_like(g_np) if fix_gamma else g_np
            dx_o, dg_o, db_o = oracle(x_np, g_eff, dy_np, axis)
            # internal statistics accumulate in f32 -> f32-level tolerance
            np.testing.assert_allclose(x.grad.asnumpy(), dx_o,
                                       rtol=2e-4, atol=2e-4)
            np.testing.assert_allclose(b.grad.asnumpy(), db_o,
                                       rtol=2e-4, atol=2e-4)
            if fix_gamma:
                np.testing.assert_allclose(g.grad.asnumpy(), 0.0, atol=1e-7)
            else:
                np.testing.assert_allclose(g.grad.asnumpy(), dg_o,
                                           rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("layout", ["NCHW", "NHWC"])
def test_maxpool_3x3_s2_value_and_grad(layout):
    """ResNet's stem pool (3x3, stride 2, pad 1: overlapping windows)
    against numpy: each output is its window's maximum, and the gradient
    of sum(out^2) reaches each input through the windows it wins."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import nn as nn_ops

    rng = np.random.RandomState(0)
    # distinct values => no ties, so the gradient's routing is unique
    x = rng.permutation(2 * 8 * 13 * 13).reshape(2, 8, 13, 13) \
        .astype(np.float32)
    xp = np.pad(x, ((0, 0), (0, 0), (1, 1), (1, 1)),
                constant_values=-np.inf)
    want = np.empty((2, 8, 7, 7), np.float32)
    want_g = np.zeros(xp.shape, np.float32)
    for i in range(7):
        for j in range(7):
            win = xp[:, :, 2 * i:2 * i + 3, 2 * j:2 * j + 3].reshape(2, 8, 9)
            top = win.argmax(axis=2)
            want[:, :, i, j] = win.max(axis=2)
            for n in range(2):
                for c in range(8):
                    a, b = divmod(top[n, c], 3)
                    want_g[n, c, 2 * i + a, 2 * j + b] += 2 * want[n, c, i, j]
    want_g = want_g[:, :, 1:-1, 1:-1]
    params = {"kernel": (3, 3), "stride": (2, 2), "pad": (1, 1),
              "pool_type": "max", "layout": layout}
    to, back = ((0, 2, 3, 1), (0, 3, 1, 2)) if layout == "NHWC" \
        else ((0, 1, 2, 3),) * 2

    def run(v):
        return nn_ops._pooling(params, v)[0]

    xin = jnp.asarray(x.transpose(to))
    got = np.asarray(run(xin)).transpose(back)
    got_g = np.asarray(jax.grad(lambda v: jnp.sum(run(v) ** 2))(xin)) \
        .transpose(back)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(got_g, want_g, rtol=1e-6)


def test_nhwc_stem_conv_matches_im2col():
    """ResNet's NHWC stem (7x7, stride 2, pad 3, 3 channels), which lowers
    as a plain convolution: value and both gradients against an im2col
    matrix product written in numpy."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import nn as nn_ops

    rng = np.random.RandomState(0)
    N, H, C, O, k, pad = 2, 32, 3, 8, 7, 3
    x = rng.randn(N, H, H, C).astype(np.float32)
    w = rng.randn(O, k, k, C).astype(np.float32)
    Ho = (H + 2 * pad - k) // 2 + 1
    head = rng.randn(N, Ho, Ho, O).astype(np.float32)
    xp = np.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    cols = np.stack([xp[:, 2 * i:2 * i + k, 2 * j:2 * j + k, :]
                     .reshape(N, k * k * C)
                     for i in range(Ho) for j in range(Ho)], axis=1)
    w2 = w.reshape(O, k * k * C)
    want = (cols @ w2.T).reshape(N, Ho, Ho, O)
    head2 = head.reshape(N, Ho * Ho, O)
    want_dw = np.einsum("npo,npk->ok", head2, cols).reshape(w.shape)
    dcols = (head2 @ w2).reshape(N, Ho, Ho, k, k, C)
    want_dx = np.zeros_like(xp)
    for i in range(Ho):
        for j in range(Ho):
            want_dx[:, 2 * i:2 * i + k, 2 * j:2 * j + k, :] += dcols[:, i, j]
    want_dx = want_dx[:, pad:-pad, pad:-pad, :]
    params = {"kernel": (k, k), "stride": (2, 2), "pad": (pad, pad),
              "no_bias": True, "layout": "NHWC", "num_filter": O}

    def conv(x, w):
        return nn_ops._convolution(params, x, w)[0]

    got = conv(jnp.asarray(x), jnp.asarray(w))
    got_dx, got_dw = jax.grad(
        lambda x, w: jnp.sum(conv(x, w) * head), (0, 1))(
            jnp.asarray(x), jnp.asarray(w))
    assert got.shape == want.shape
    assert np.abs(np.asarray(got) - want).max() < 1e-4
    assert np.abs(np.asarray(got_dx) - want_dx).max() < 1e-3
    assert np.abs(np.asarray(got_dw) - want_dw).max() < 1e-3
