"""Gluon block suite (reference tests/python/unittest/test_gluon.py):
Parameter/ParameterDict, SymbolBlock, HybridBlock export/import,
save/load params, Trainer with lr scheduling, losses."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, autograd
from mxnet_tpu.gluon import nn


def test_parameter_basic():
    # name must match an initializer pattern (reference raises
    # "Unknown initialization pattern" for unmatched bare names too)
    p = gluon.Parameter("dense0_weight", shape=(3, 4))
    p.initialize(init=mx.init.Xavier())
    assert p.data().shape == (3, 4)
    assert p.grad() is not None or True
    p.set_data(mx.nd.ones((3, 4)))
    np.testing.assert_allclose(p.data().asnumpy(), 1.0)


def test_dense_and_sequential():
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(3))
    net.initialize()
    x = mx.nd.random.uniform(shape=(4, 6))
    out = net(x)
    assert out.shape == (4, 3)
    net.hybridize()
    out2 = net(x)
    np.testing.assert_allclose(out.asnumpy(), out2.asnumpy(), rtol=1e-5,
                               atol=1e-6)


def test_save_load_params(tmp_path):
    net = nn.HybridSequential(prefix="slp_")
    with net.name_scope():
        net.add(nn.Dense(5), nn.Dense(2))
    net.initialize()
    x = mx.nd.random.uniform(shape=(2, 3))
    want = net(x).asnumpy()
    path = str(tmp_path / "p.params")
    net.save_params(path)

    net2 = nn.HybridSequential(prefix="slp_")
    with net2.name_scope():
        net2.add(nn.Dense(5), nn.Dense(2))
    net2.load_params(path)
    np.testing.assert_allclose(net2(x).asnumpy(), want, rtol=1e-6)


def test_hybrid_export_symbolblock(tmp_path):
    net = nn.HybridSequential(prefix="exp_")
    with net.name_scope():
        net.add(nn.Dense(4, activation="tanh"), nn.Dense(2))
    net.initialize()
    net.hybridize()
    x = mx.nd.random.uniform(shape=(3, 5))
    want = net(x).asnumpy()
    prefix = str(tmp_path / "model")
    net.export(prefix)
    assert os.path.exists(prefix + "-symbol.json")
    assert os.path.exists(prefix + "-0000.params")

    sb = gluon.SymbolBlock.imports(prefix + "-symbol.json", ["data"],
                                   prefix + "-0000.params")
    got = sb(x).asnumpy()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_trainer_with_scheduler():
    net = nn.Dense(1)
    net.initialize()
    sched = mx.lr_scheduler.FactorScheduler(step=2, factor=0.5)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 1.0, "lr_scheduler": sched})
    x = mx.nd.ones((2, 3))
    for i in range(4):
        with autograd.record():
            loss = net(x).sum()
        loss.backward()
        trainer.step(2)
    assert trainer.learning_rate < 1.0


def test_losses():
    pred = mx.nd.array(np.random.RandomState(0).randn(4, 3).astype("f"))
    label = mx.nd.array(np.array([0, 1, 2, 1], "f"))
    l = gluon.loss.SoftmaxCrossEntropyLoss()(pred, label)
    assert l.shape == (4,)
    l1 = gluon.loss.L1Loss()(pred, mx.nd.zeros((4, 3)))
    np.testing.assert_allclose(l1.asnumpy(),
                               np.abs(pred.asnumpy()).mean(axis=1),
                               rtol=1e-5)
    l2 = gluon.loss.L2Loss()(pred, mx.nd.zeros((4, 3)))
    np.testing.assert_allclose(l2.asnumpy(),
                               (pred.asnumpy() ** 2).mean(axis=1) / 2,
                               rtol=1e-5)
    sig = gluon.loss.SigmoidBinaryCrossEntropyLoss()
    lb = sig(pred, mx.nd.ones((4, 3)))
    assert (lb.asnumpy() > 0).all()


def test_block_grad_flow_and_collect():
    net = nn.HybridSequential()
    net.add(nn.Dense(4), nn.Dense(1))
    net.initialize()
    params = net.collect_params()
    assert len(params) == 4  # 2 weights + 2 biases
    x = mx.nd.ones((2, 3))
    with autograd.record():
        y = net(x).sum()
    y.backward()
    for p in params.values():
        assert np.isfinite(p.grad().asnumpy()).all()


def test_constant_and_embedding():
    emb = nn.Embedding(10, 4)
    emb.initialize()
    idx = mx.nd.array(np.array([1, 3], "f"))
    out = emb(idx)
    assert out.shape == (2, 4)


def test_hybridize_remat_matches_plain():
    """hybridize(remat=True) rematerializes activations (jax.checkpoint,
    the MXNET_BACKWARD_DO_MIRROR analog) without changing results."""
    rng = np.random.RandomState(7)
    x = mx.nd.array(rng.randn(4, 6).astype("f"))

    results = []
    for remat in (False, True):
        net = nn.HybridSequential()
        net.add(nn.Dense(8, activation="tanh", in_units=6),
                nn.Dense(3, in_units=8))
        net.initialize(mx.init.Xavier(rnd_type="gaussian"))
        # identical weights across both nets
        if not results:
            saved = {k: v.data().asnumpy()
                     for k, v in net.collect_params().items()}
            order = list(net.collect_params().keys())
        else:
            for k, v in zip(order, net.collect_params().values()):
                v.set_data(mx.nd.array(saved[k]))
        net.hybridize(remat=remat)
        xc = x.copy()
        xc.attach_grad()
        with autograd.record():
            y = net(xc).sum()
        y.backward()
        results.append((float(y.asnumpy()), xc.grad.asnumpy()))
    np.testing.assert_allclose(results[0][0], results[1][0], rtol=1e-5)
    np.testing.assert_allclose(results[0][1], results[1][1], rtol=1e-5)


def test_contrib_concurrent():
    from mxnet_tpu.gluon import contrib as gc
    c = gc.nn.Concurrent(axis=1)
    c.add(nn.Dense(3), nn.Dense(4))
    c.initialize()
    out = c(mx.nd.ones((2, 5)))
    assert out.shape == (2, 7)


def test_contrib_interval_sampler_and_wikitext(tmp_path):
    from mxnet_tpu.gluon import contrib as gc
    assert list(gc.data.IntervalSampler(13, interval=3)) == \
        [0, 3, 6, 9, 12, 1, 4, 7, 10, 2, 5, 8, 11]
    assert list(gc.data.IntervalSampler(13, interval=3, rollover=False)) \
        == [0, 3, 6, 9, 12]
    # WikiText from a local file
    (tmp_path / "wiki.train.tokens").write_text(
        " hello world foo \n bar hello baz qux \n" * 20)
    ds = gc.data.WikiText2(root=str(tmp_path), segment="train", seq_len=5)
    assert len(ds) > 10
    data, label = ds[0]
    assert data.shape == (5,) and label.shape == (5,)
    # label is data shifted by one in the token stream
    np.testing.assert_allclose(label.asnumpy()[:-1], data.asnumpy()[1:])
    with pytest.raises(IOError):
        gc.data.WikiText103(root=str(tmp_path / "nope"))


def test_hybridized_batchnorm_updates_moving_stats():
    """Round-3 fix: under hybridize() the BN moving-stats updates happen on
    tracers; the cached program must surface them as aux outputs and commit
    them back, or eval (global stats) silently uses the INITIAL stats."""
    np.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.BatchNorm())
    net.initialize()
    net.hybridize()
    x = mx.nd.array(np.random.rand(16, 4).astype(np.float32) * 5 + 10)
    with mx.autograd.record():
        net(x)
    bn = list(net._children.values())[0]
    mean = bn.running_mean.data().asnumpy()
    var = bn.running_var.data().asnumpy()
    # one momentum-0.9 update from (0, 1) toward the batch stats
    assert np.abs(mean).max() > 0.5, mean   # moved off the init value
    assert np.abs(var - 1.0).max() > 0.1, var
    # eager reference produces the same stats
    net2 = nn.HybridSequential()
    net2.add(nn.BatchNorm())
    net2.initialize()
    with mx.autograd.record():
        net2(x)
    bn2 = list(net2._children.values())[0]
    np.testing.assert_allclose(mean, bn2.running_mean.data().asnumpy(),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(var, bn2.running_var.data().asnumpy(),
                               rtol=1e-5, atol=1e-6)


def test_hybridized_nested_deferred_bn_updates_stats():
    """Review r3: a deferred-init BN CHILD called via __call__ inside a
    parent's hybrid_forward must still commit moving stats — the parent's
    warmup aux-suppression must not leak into the child's jit trace."""

    class Wrapper(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.bn = nn.BatchNorm()  # in_channels deferred

        def hybrid_forward(self, F, x):
            return self.bn(x)

    np.random.seed(1)
    net = Wrapper()
    net.initialize()
    net.hybridize()
    x = mx.nd.array(np.random.rand(16, 4).astype(np.float32) * 5 + 10)
    with mx.autograd.record():
        net(x)
        net(x)
    mean = net.bn.running_mean.data().asnumpy()
    assert np.abs(mean).max() > 0.5, mean  # stats moved off init


def test_hybridized_trace_keeps_the_callers_context():
    """Ops inside a hybridized block see the context of the call as
    `_ctx` (a tracer has no device of its own): the device-gated
    lowerings (`_s2d_eligible`, `_fused_lstm_ok`) read it. Found on the
    chip: the trace wrapped its tracers in the default cpu(0), so a net
    hybridized on mx.tpu() never got the space-to-depth stem."""
    seen = []

    class Probe(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.dense = nn.Dense(2, in_units=3)

        def hybrid_forward(self, F, x):
            seen.append(x.ctx)
            out = self.dense(x)
            seen.append(out.ctx)
            return out

    for ctx in (mx.tpu(1), mx.cpu(0)):   # tpu(1): host device 1 in CPU mode
        net = Probe()
        net.initialize(ctx=ctx)
        net.hybridize()
        del seen[:]
        out = net(mx.nd.ones((4, 3), ctx=ctx))
        assert seen and all(c == ctx for c in seen), (ctx, seen)
        assert out.ctx == ctx


def test_hybridized_net_moved_to_another_context_is_traced_again():
    """The context is part of the compiled program's key: ONE hybridized
    net called on a second context at the same shape is traced for that
    context, and does not replay the lowering chosen for the first (a
    net first run on cpu() and then moved with reset_ctx(mx.tpu()) would
    lose the space-to-depth stem and the fused LSTM again; the other way
    round a Pallas lowering would run on host buffers)."""
    traced_for = []

    class Probe(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.dense = nn.Dense(2, in_units=3)

        def hybrid_forward(self, F, x):
            traced_for.append(x.ctx)
            return self.dense(x)

    net = Probe()
    net.initialize(ctx=mx.cpu(0))
    net.hybridize()
    x = np.arange(12, dtype=np.float32).reshape(4, 3)
    first = net(mx.nd.array(x, ctx=mx.cpu(0)))
    assert traced_for == [mx.cpu(0)]
    net.collect_params().reset_ctx(mx.tpu(1))
    moved = net(mx.nd.array(x, ctx=mx.tpu(1)))
    assert traced_for == [mx.cpu(0), mx.tpu(1)]      # traced again
    assert moved.ctx == mx.tpu(1)
    assert moved._data.devices() == {mx.tpu(1).jax_device()}
    np.testing.assert_allclose(moved.asnumpy(), first.asnumpy(), rtol=1e-6)
    # each context keeps its program: going back and forth traces no more
    net(mx.nd.array(x, ctx=mx.tpu(1)))
    net.collect_params().reset_ctx(mx.cpu(0))
    back = net(mx.nd.array(x, ctx=mx.cpu(0)))
    assert traced_for == [mx.cpu(0), mx.tpu(1)]
    assert back.ctx == mx.cpu(0)
