"""Single-process multi-device data parallelism through Module.

Reference `python/mxnet/module/executor_group.py:129,289,330`:
`Module(context=[gpu(0),gpu(1),...])` slices every batch across the bound
devices and reduces gradients. Here the same API binds ONE SPMD executor
over a 'dp' mesh (inputs batch-sharded, params replicated, gradient psum
in-program), so an N-device run must reproduce the 1-device loss/parameter
trajectory exactly (same global batch, same reductions, same RNG stream).

Runs on the 8 virtual CPU devices the conftest forces."""
import numpy as np
import pytest

import mxnet_tpu as mx


def _make_data(n=256, d=20, k=3, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    W = rng.randn(d, k).astype(np.float32)
    y = X.dot(W).argmax(axis=1).astype(np.float32)
    return X, y


def _mlp(with_bn=False):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    if with_bn:
        net = mx.sym.BatchNorm(net, name="bn1", fix_gamma=False)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _train(contexts, with_bn=False, optimizer="sgd",
           opt_params=(("learning_rate", 0.5), ("momentum", 0.9)),
           epochs=6):
    X, y = _make_data()
    it = mx.io.NDArrayIter(X, y, batch_size=32, shuffle=False,
                           label_name="softmax_label")
    mod = mx.mod.Module(_mlp(with_bn), context=contexts)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mx.random.seed(7)
    np.random.seed(7)  # initializers draw from numpy's global RNG
    mod.init_params(initializer=mx.init.Xavier(rnd_type="uniform",
                                               factor_type="avg",
                                               magnitude=2))
    mod.init_optimizer(optimizer=optimizer, optimizer_params=opt_params)
    metric = mx.metric.Accuracy()
    accs = []
    for _ in range(epochs):
        it.reset()
        metric.reset()
        for batch in it:
            mod._step(batch)
            mod.update_metric(metric, batch.label)
        accs.append(metric.get()[1])
    args, auxs = mod.get_params()
    return accs, {n: a.asnumpy() for n, a in args.items()}, \
        {n: a.asnumpy() for n, a in auxs.items()}


def test_dp_module_matches_single_device_trajectory():
    accs1, args1, _ = _train([mx.cpu(0)])
    ctxs = [mx.cpu(i) for i in range(8)]
    accs8, args8, _ = _train(ctxs)
    assert accs8 == pytest.approx(accs1, abs=1e-3)
    for name in args1:
        np.testing.assert_allclose(args8[name], args1[name],
                                   rtol=2e-4, atol=2e-5, err_msg=name)
    assert accs8[-1] > 0.8  # it actually learns (>0.9 covered by the
    # longer-horizon score test below; this lr/momentum setting oscillates)


def test_dp_module_batchnorm_cross_replica_stats():
    """BN over a dp-sharded batch must use GLOBAL batch statistics (the
    mean reduce spans the sharded axis), matching the single-device run —
    stronger than the reference's per-device BN."""
    accs1, args1, aux1 = _train([mx.cpu(0)], with_bn=True)
    accs8, args8, aux8 = _train([mx.cpu(i) for i in range(8)], with_bn=True)
    assert accs8 == pytest.approx(accs1, abs=1e-3)
    for name in aux1:  # moving_mean / moving_var match => global stats
        np.testing.assert_allclose(aux8[name], aux1[name],
                                   rtol=2e-4, atol=2e-5, err_msg=name)


def test_dp_module_adam_states_sharded_consistently():
    accs8, _, _ = _train([mx.cpu(i) for i in range(8)], optimizer="adam",
                         opt_params=(("learning_rate", 0.01),))
    assert accs8[-1] > 0.8


def test_dp_module_forward_outputs_global_batch():
    X, y = _make_data(n=64)
    it = mx.io.NDArrayIter(X, y, batch_size=64, label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(8)])
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    batch = next(it)
    mod.forward(batch, is_train=False)
    out = mod.get_outputs()[0]
    assert out.shape == (64, 3)
    probs = out.asnumpy()
    np.testing.assert_allclose(probs.sum(axis=1), 1.0, rtol=1e-5)


def test_dp_module_takes_given_params_onto_the_mesh():
    """`fit(arg_params=...)` copies the caller's host arrays INTO the
    replicated buffers (NDArray.copyto keeps the destination's
    placement): the params stay on all 8 devices and the run matches
    the one-device run from the same params."""
    X, y = _make_data()
    rng = np.random.RandomState(3)
    given = {"fc1_weight": rng.randn(16, 20) * 0.1, "fc1_bias": np.zeros(16),
             "fc2_weight": rng.randn(3, 16) * 0.1, "fc2_bias": np.zeros(3)}

    def fit(contexts):
        it = mx.io.NDArrayIter(X, y, batch_size=32,
                               label_name="softmax_label")
        mod = mx.mod.Module(_mlp(), context=contexts)
        mod.fit(it, num_epoch=2, optimizer_params=(("learning_rate", 0.5),),
                arg_params={k: mx.nd.array(v) for k, v in given.items()})
        return mod

    dp = fit([mx.cpu(i) for i in range(8)])
    for name in given:
        w = dp._exec.arg_dict[name]._data
        assert len(w.sharding.device_set) == 8, name
    one = fit([mx.cpu(0)])
    for name in given:
        np.testing.assert_allclose(dp._exec.arg_dict[name].asnumpy(),
                                   one._exec.arg_dict[name].asnumpy(),
                                   rtol=2e-4, atol=2e-5, err_msg=name)


def test_dp_module_rejects_indivisible_batch():
    X, y = _make_data(n=60)
    it = mx.io.NDArrayIter(X, y, batch_size=30, label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(8)])
    with pytest.raises(Exception, match="divisible"):
        mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)


def test_dp_module_score_and_predict():
    X, y = _make_data()
    it = mx.io.NDArrayIter(X, y, batch_size=32, label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(8)])
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    np.random.seed(3)
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.5),))
    for _ in range(5):
        it.reset()
        for batch in it:
            mod._step(batch)
    it.reset()
    metric = mx.metric.Accuracy()
    mod.score(it, metric)
    assert metric.get()[1] > 0.9


def _train_fit(ctxs, batches_per_dispatch, epochs=4):
    X, y = _make_data()
    it = mx.io.NDArrayIter(X, y, batch_size=32, shuffle=False,
                           label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=ctxs)
    mx.random.seed(5)
    np.random.seed(5)
    import logging
    mod.fit(it, num_epoch=epochs, optimizer="sgd",
            initializer=mx.init.Xavier(),
            optimizer_params=(("learning_rate", 0.3), ("momentum", 0.9)),
            batches_per_dispatch=batches_per_dispatch)
    args, _ = mod.get_params()
    it.reset()
    metric = mx.metric.Accuracy()
    mod.score(it, metric)
    return metric.get()[1], {n: a.asnumpy() for n, a in args.items()}


def test_step_scan_matches_per_step():
    """fit(batches_per_dispatch=K) — K fused steps in one lax.scan dispatch
    — must reproduce the per-batch _step trajectory exactly."""
    acc1, p1 = _train_fit([mx.cpu(0)], 1)
    accK, pK = _train_fit([mx.cpu(0)], 4)
    assert accK == pytest.approx(acc1, abs=1e-3)
    for name in p1:
        np.testing.assert_allclose(pK[name], p1[name], rtol=1e-4,
                                   atol=1e-5, err_msg=name)


def test_step_scan_on_dp_mesh():
    """scan-of-steps composes with SPMD dp sharding."""
    acc1, p1 = _train_fit([mx.cpu(0)], 4)
    acc8, p8 = _train_fit([mx.cpu(i) for i in range(8)], 4)
    assert acc8 == pytest.approx(acc1, abs=2e-2)
    for name in p1:
        np.testing.assert_allclose(p8[name], p1[name], rtol=2e-3,
                                   atol=2e-4, err_msg=name)


def test_step_scan_metric_counts_every_batch():
    X, y = _make_data(n=96)
    it = mx.io.NDArrayIter(X, y, batch_size=32, label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(0)])
    seen = []
    mod.fit(it, num_epoch=1, optimizer="sgd",
            initializer=mx.init.Xavier(),
            batches_per_dispatch=2,
            batch_end_callback=lambda p: seen.append(p.nbatch))
    assert seen == [0, 1, 2]  # 3 batches -> one scan(2) + one plain step


def test_dp_with_bf16_type_dict():
    """SPMD dp composes with bf16 binding (type_dict)."""
    X, y = _make_data()
    it = mx.io.NDArrayIter(X, y, batch_size=32, label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(8)])
    td = {"data": "bfloat16"}
    td.update({p_: "bfloat16" for p_ in mod._param_names})
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             type_dict=td)
    np.random.seed(0)
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.5),))
    for _ in range(3):
        it.reset()
        for batch in it:
            mod._step(batch)
    w = mod._exec.arg_dict["fc1_weight"]
    assert str(w.dtype) == "bfloat16"
    assert len(w._data.sharding.device_set) == 8
    out = mod.get_outputs()[0].asnumpy().astype(np.float32)
    assert np.isfinite(out).all()


def test_dp_with_bucketing_module():
    """BucketingModule shares the dp-sharded parameter arrays across
    bucket executors (shared_exec carries the shardings)."""
    ctxs = [mx.cpu(i) for i in range(8)]

    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(
            mx.sym.Reshape(data, shape=(-1, 4)), num_hidden=8, name="fc1")
        net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
        return mx.sym.SoftmaxOutput(net, name="softmax"), ("data",), \
            ("softmax_label",)

    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=8,
                                 context=ctxs)
    mod.bind(data_shapes=[("data", (16, 8, 4))],
             label_shapes=[("softmax_label", (16 * 8,))])
    np.random.seed(0)
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.1),))
    rng = np.random.RandomState(0)
    for key in (8, 4, 8):
        batch = mx.io.DataBatch(
            data=[mx.nd.array(rng.rand(16, key, 4).astype(np.float32))],
            label=[mx.nd.array((rng.rand(16 * key) * 3)
                               .astype(np.float32))],
            bucket_key=key,
            provide_data=[("data", (16, key, 4))],
            provide_label=[("softmax_label", (16 * key,))], pad=0)
        mod.forward_backward(batch)
        mod.update()
    w = mod._curr_module._exec.arg_dict["fc1_weight"]
    assert len(w._data.sharding.device_set) == 8  # stayed on the mesh
