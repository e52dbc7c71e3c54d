"""Module API tests (modeled on reference test_module.py + tests/python/train).

Includes the end-to-end slice: Module.fit on a synthetic separable problem
must reach high accuracy (reference tests/python/train/test_mlp.py pattern).
"""
import logging

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.test_utils import assert_almost_equal


def _mlp_sym(num_hidden=32, num_classes=4):
    data = mx.sym.var("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=num_hidden, name="fc1")
    act1 = mx.sym.Activation(fc1, act_type="relu", name="relu1")
    fc2 = mx.sym.FullyConnected(act1, num_hidden=num_classes, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _synthetic_data(n=400, dim=10, classes=4, seed=0):
    rng = np.random.RandomState(seed)
    centers = rng.uniform(-3, 3, (classes, dim)).astype(np.float32)
    labels = rng.randint(0, classes, n)
    x = centers[labels] + rng.normal(0, 0.3, (n, dim)).astype(np.float32)
    return x.astype(np.float32), labels.astype(np.float32)


def test_module_bind_forward():
    net = _mlp_sym()
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, 10))], label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    batch = mx.io.DataBatch(data=[mx.nd.ones((8, 10))],
                            label=[mx.nd.zeros((8,))])
    mod.forward(batch, is_train=False)
    out = mod.get_outputs()[0]
    assert out.shape == (8, 4)
    assert_almost_equal(out.asnumpy().sum(1), np.ones(8), rtol=1e-4)


def test_module_fit_converges():
    x, y = _synthetic_data()
    train_iter = mx.io.NDArrayIter(x, y, batch_size=32, shuffle=True)
    val_iter = mx.io.NDArrayIter(x, y, batch_size=32)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.fit(train_iter, eval_data=val_iter, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5}, num_epoch=6,
            eval_metric="acc")
    score = mod.score(val_iter, "acc")
    assert score[0][1] > 0.95, "accuracy %f too low" % score[0][1]


def test_module_fit_adam_kvstore_device():
    x, y = _synthetic_data(seed=1)
    train_iter = mx.io.NDArrayIter(x, y, batch_size=25)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.fit(train_iter, optimizer="adam",
            optimizer_params={"learning_rate": 0.05}, num_epoch=5,
            kvstore="device")
    score = mod.score(mx.io.NDArrayIter(x, y, batch_size=25), "acc")
    assert score[0][1] > 0.9


def test_module_predict_and_outputs():
    x, y = _synthetic_data(n=64)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    it = mx.io.NDArrayIter(x, y, batch_size=16)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    pred = mod.predict(it)
    assert pred.shape == (64, 4)


def test_module_save_load_checkpoint(tmp_path):
    x, y = _synthetic_data(n=64)
    it = mx.io.NDArrayIter(x, y, batch_size=16)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    prefix = str(tmp_path / "model")
    mod.save_checkpoint(prefix, 3)
    mod2 = mx.mod.Module.load(prefix, 3)
    mod2.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    a1, _ = mod.get_params()
    a2, _ = mod2.get_params()
    for k in a1:
        assert_almost_equal(a1[k], a2[k].asnumpy())
    # predictions identical
    p1 = mod.predict(it).asnumpy()
    p2 = mod2.predict(it).asnumpy()
    assert_almost_equal(p1, p2, rtol=1e-5)


def test_module_get_set_params():
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 10))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params()
    args, auxs = mod.get_params()
    args = {k: v.copy() for k, v in args.items()}
    args["fc1_bias"][:] = 7
    mod.set_params(args, auxs)
    new_args, _ = mod.get_params()
    assert (new_args["fc1_bias"].asnumpy() == 7).all()


def test_module_input_grads():
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 10))],
             label_shapes=[("softmax_label", (4,))],
             inputs_need_grad=True)
    mod.init_params()
    batch = mx.io.DataBatch(data=[mx.nd.ones((4, 10))],
                            label=[mx.nd.zeros((4,))])
    mod.forward_backward(batch)
    ig = mod.get_input_grads()[0]
    assert ig.shape == (4, 10)
    assert np.abs(ig.asnumpy()).sum() > 0


def test_bucketing_module():
    def sym_gen(seq_len):
        data = mx.sym.var("data")
        fc = mx.sym.FullyConnected(data, num_hidden=8, name="fc")
        out = mx.sym.SoftmaxOutput(fc, name="softmax")
        return out, ("data",), ("softmax_label",)

    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=10,
                                 context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 10))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd")
    for key, dim in [(10, 10), (5, 5), (10, 10)]:
        batch = mx.io.DataBatch(
            data=[mx.nd.ones((4, dim))], label=[mx.nd.zeros((4,))],
            bucket_key=key,
            provide_data=[mx.io.DataDesc("data", (4, dim))],
            provide_label=[mx.io.DataDesc("softmax_label", (4,))])
        mod.forward_backward(batch)
        mod.update()
    assert len(mod._buckets) == 2
    # parameters shared across buckets
    m10 = mod._buckets[10]
    m5 = mod._buckets[5]
    assert m10._exec.arg_dict["fc_bias"] is m5._exec.arg_dict["fc_bias"]


def test_sequential_module():
    net1 = mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=8, name="fc1")
    net2 = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(mx.sym.var("data"),
                                                      num_hidden=4, name="fc2"),
                                name="softmax")
    mod = mx.mod.SequentialModule()
    mod.add(mx.mod.Module(net1, label_names=None, context=mx.cpu()))
    mod.add(mx.mod.Module(net2, context=mx.cpu()), take_labels=True,
            auto_wiring=True)
    mod.bind(data_shapes=[("data", (4, 10))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params()
    batch = mx.io.DataBatch(data=[mx.nd.ones((4, 10))],
                            label=[mx.nd.zeros((4,))])
    mod.forward(batch, is_train=False)
    assert mod.get_outputs()[0].shape == (4, 4)


def test_module_fixed_params():
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu(),
                        fixed_param_names=["fc1_weight"])
    mod.bind(data_shapes=[("data", (4, 10))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params()
    mod.init_optimizer()
    w_before = mod._exec.arg_dict["fc1_weight"].asnumpy().copy()
    w2_before = mod._exec.arg_dict["fc2_weight"].asnumpy().copy()
    batch = mx.io.DataBatch(data=[mx.nd.ones((4, 10))],
                            label=[mx.nd.zeros((4,))])
    mod.forward_backward(batch)
    mod.update()
    assert_almost_equal(mod._exec.arg_dict["fc1_weight"], w_before)
    assert not np.allclose(mod._exec.arg_dict["fc2_weight"].asnumpy(), w2_before)


def test_feedforward_legacy():
    x, y = _synthetic_data(n=128)
    model = mx.FeedForward(_mlp_sym(), ctx=mx.cpu(), num_epoch=3,
                           numpy_batch_size=32,
                           optimizer_params={"learning_rate": 0.5})
    model.fit(x, y)
    pred = model.predict(x)
    assert pred.shape == (128, 4)


def test_python_loss_module():
    """PythonLossModule spliced after a Module inside SequentialModule
    (reference python_module.py pattern): custom python loss gradient
    drives the network."""
    import numpy as np

    x, y = _synthetic_data(n=300, dim=10, classes=4, seed=3)
    data = mx.sym.var("data")
    net = mx.sym.FullyConnected(data, num_hidden=4, name="fcout")

    def ce_grad(scores, labels):
        s = scores.asnumpy()
        p = np.exp(s - s.max(1, keepdims=True))
        p /= p.sum(1, keepdims=True)
        lab = labels.asnumpy().astype(int)
        p[np.arange(len(lab)), lab] -= 1.0
        return p / len(lab)

    seq = mx.mod.SequentialModule()
    seq.add(mx.mod.Module(net, label_names=[]))
    seq.add(mx.mod.PythonLossModule(grad_func=ce_grad), take_labels=True,
            auto_wiring=True)
    train = mx.io.NDArrayIter(x, y, batch_size=50, shuffle=True,
                              label_name="softmax_label")
    seq.bind(data_shapes=train.provide_data,
             label_shapes=train.provide_label, inputs_need_grad=False)
    seq.init_params(mx.init.Xavier())
    seq.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.5})
    for _ in range(25):
        train.reset()
        for batch in train:
            seq.forward(batch, is_train=True)
            seq.backward()
            seq.update()
    # accuracy via the first module's outputs
    train.reset()
    correct = total = 0
    for batch in train:
        seq.forward(batch, is_train=False)
        out = seq.get_outputs()[0].asnumpy()
        n = out.shape[0] - batch.pad
        correct += (out[:n].argmax(1) == batch.label[0].asnumpy()[:n]).sum()
        total += n
    assert correct / total > 0.9, correct / total


def test_step_scan_carries_batchnorm_state():
    """One `_step_scan` over 4 batches against four `_step`s on a network
    with a BatchNorm under momentum SGD: the scan's carry holds the moving
    statistics and the momenta beside the parameters, so all of them, and
    every step's outputs, must come out the same."""
    import numpy as np

    def build():
        data = mx.sym.var("data")
        net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
        net = mx.sym.BatchNorm(net, fix_gamma=False, name="bn")
        net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
        net = mx.sym.SoftmaxOutput(net, name="softmax")
        mod = mx.mod.Module(net, context=mx.cpu())
        mod.bind(data_shapes=[("data", (6, 4))],
                 label_shapes=[("softmax_label", (6,))])
        mod.init_params(initializer=mx.init.Xavier(rnd_type="uniform"))
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1,
                                             "momentum": 0.9})
        return mod

    def momenta(mod):
        return {name: mod._updater.states[i].asnumpy()
                for i, name in enumerate(mod._param_names)
                if mod._updater.states.get(i) is not None}

    rng = np.random.RandomState(5)
    batches = [mx.io.DataBatch(
        data=[mx.nd.array(rng.randn(6, 4).astype(np.float32))],
        label=[mx.nd.array((rng.rand(6) * 3).astype(np.float32))])
        for _ in range(4)]

    ref = build()
    scanned = build()
    a0, x0 = ref.get_params()  # same initial weights for both
    scanned.set_params(a0, x0)
    x0 = {name: v.asnumpy() for name, v in x0.items()}
    out_ref = []
    for batch in batches:
        ref._step(batch)
        out_ref.append(ref.get_outputs()[0].asnumpy())
    out_scan = scanned._step_scan(batches)
    assert out_scan is not False and len(out_scan) == 1
    assert np.allclose(out_scan[0].asnumpy(), np.stack(out_ref),
                       rtol=1e-5, atol=1e-6)
    a_ref, aux_ref = ref.get_params()
    a_scan, aux_scan = scanned.get_params()
    assert set(aux_ref) == {"bn_moving_mean", "bn_moving_var"}
    for name in aux_ref:
        # the statistics moved off their initial 0 / 1, four times over
        assert not np.allclose(aux_ref[name].asnumpy(), x0[name])
    m_ref, m_scan = momenta(ref), momenta(scanned)
    assert set(m_ref) == set(a_ref) and set(m_scan) == set(a_ref)
    for have, want in ((a_scan, a_ref), (aux_scan, aux_ref)):
        for name in want:
            assert np.allclose(have[name].asnumpy(), want[name].asnumpy(),
                               rtol=1e-5, atol=1e-6), name
    for name in m_ref:
        assert np.abs(m_ref[name]).max() > 0, name
        assert np.allclose(m_scan[name], m_ref[name],
                           rtol=1e-5, atol=1e-6), name
