"""A scanned step's outputs (`Module._step_scan`): the last batch's rows are
the module's outputs, sliced when they are first read and not behind the
dispatch, where the slice's launch can be the one the runtime holds until
the program before has ended (`Executor.last_outputs_of`)."""
import numpy as np

import mxnet_tpu as mx


def _module():
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


def _batches(n, seed=5):
    rng = np.random.RandomState(seed)
    return [mx.io.DataBatch(
        data=[mx.nd.array(rng.randn(6, 4).astype(np.float32))],
        label=[mx.nd.array((rng.rand(6) * 3).astype(np.float32))])
        for _ in range(n)]


def test_scan_outputs_are_the_last_batch_sliced_when_read():
    mod = _module()
    stacked = mod._step_scan(_batches(4))
    assert mod._exec._stacked_outputs is not None   # nothing sliced yet
    outs = mod.get_outputs()
    assert mod._exec._stacked_outputs is None
    assert len(outs) == 1 and outs[0].shape == (6, 3)
    np.testing.assert_array_equal(outs[0].asnumpy(), stacked[0].asnumpy()[3])
    assert mod.get_outputs() is outs   # sliced once


def test_a_later_program_replaces_scan_outputs_not_read():
    mod = _module()
    mod._step_scan(_batches(4))
    batch = _batches(1, seed=6)[0]
    mod._step(batch)
    assert mod._exec._stacked_outputs is None
    step_outs = mod.get_outputs()[0].asnumpy()
    mod.forward(batch, is_train=False)
    assert mod.get_outputs()[0].shape == step_outs.shape == (6, 3)


def test_fit_leaves_the_last_batch_outputs():
    """`fit` reads the stacked outputs itself; what it leaves as the
    module's outputs after a scanned epoch is the last batch's."""
    mod = _module()
    batches = _batches(4)
    it = mx.io.NDArrayIter(
        np.concatenate([b.data[0].asnumpy() for b in batches]),
        np.concatenate([b.label[0].asnumpy() for b in batches]),
        batch_size=6, label_name="softmax_label")
    seen = []

    def keep(param):
        if "outs" in param.locals and param.locals["outs"]:
            seen.append(param.locals["outs"]["softmax_output"].asnumpy())

    mod.fit(it, num_epoch=1, batches_per_dispatch=4,
            batch_end_callback=keep)
    assert len(seen) == 4
    np.testing.assert_array_equal(mod.get_outputs()[0].asnumpy(), seen[-1])
