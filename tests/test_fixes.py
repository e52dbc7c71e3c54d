"""Regression tests for review findings: CTC loss math, positional attr
args, NDArrayIter roll_over, F1 averaging, PrefetchingIter depth."""
import itertools

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon


def _brute_ctc(probs, label, blank):
    """-log p(label) by enumerating all alignment paths (probs: (T, C))."""
    T, C = probs.shape
    total = 0.0
    for path in itertools.product(range(C), repeat=T):
        # collapse: remove repeats, then blanks
        collapsed = [k for k, _ in itertools.groupby(path) if k != blank]
        if collapsed == list(label):
            p = 1.0
            for t, k in enumerate(path):
                p *= probs[t, k]
            total += p
    return -np.log(total)


@pytest.mark.parametrize("blank_label", ["first", "last"])
def test_ctc_loss_against_brute_force(blank_label):
    rng = np.random.RandomState(3)
    T, B, C = 4, 2, 3
    logits = rng.randn(T, B, C).astype("float32")
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    blank = 0 if blank_label == "first" else C - 1
    if blank_label == "first":
        labels = np.array([[1, 2], [2, 0]], "float32")  # 0 pads
        label_seqs = [[1, 2], [2]]
    else:
        labels = np.array([[0, 1], [1, -1]], "float32")  # -1 pads
        label_seqs = [[0, 1], [1]]
    out = mx.nd.CTCLoss(mx.nd.array(logits), mx.nd.array(labels),
                           blank_label=blank_label).asnumpy()
    for b in range(B):
        want = _brute_ctc(probs[:, b], label_seqs[b], blank)
        assert abs(out[b] - want) < 1e-4, (b, out[b], want)


def test_ctc_loss_data_and_label_lengths():
    rng = np.random.RandomState(0)
    T, B, C = 5, 2, 4
    logits = rng.randn(T, B, C).astype("float32")
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    labels = np.array([[1, 2, 3], [2, 2, 0]], "float32")
    data_len = np.array([4, 5], "float32")
    label_len = np.array([2, 2], "float32")
    out = mx.nd.CTCLoss(
        mx.nd.array(logits), mx.nd.array(labels),
        mx.nd.array(data_len), mx.nd.array(label_len),
        use_data_lengths=True, use_label_lengths=True).asnumpy()
    want0 = _brute_ctc(probs[:4, 0], [1, 2], 0)
    want1 = _brute_ctc(probs[:5, 1], [2, 2], 0)
    assert abs(out[0] - want0) < 1e-4
    assert abs(out[1] - want1) < 1e-4


def test_gluon_ctc_loss_ntc_layout():
    loss_fn = gluon.loss.CTCLoss()  # default NTC
    pred = mx.nd.random.uniform(shape=(2, 6, 5))
    label = mx.nd.array([[1, 2, -1, -1], [0, 1, 2, 3]])
    out = loss_fn(pred, label).asnumpy()
    assert out.shape == (2,)
    assert np.all(np.isfinite(out)) and np.all(out > 0)


def test_gluon_ctc_loss_label_lengths_used():
    loss_fn = gluon.loss.CTCLoss()
    pred = mx.nd.random.uniform(shape=(1, 6, 4))
    # label padded with 0 — a REAL class when blank is last; only
    # label_lengths distinguishes [1] from [1, 0, 0]
    label = mx.nd.array([[1, 0, 0]])
    short = loss_fn(pred, label, None, mx.nd.array([1])).asnumpy()
    full = loss_fn(pred, label).asnumpy()
    assert not np.allclose(short, full)


def test_swapaxes_positional():
    x = mx.nd.arange(6).reshape((2, 3))
    y = mx.nd.swapaxes(x, 0, 1)
    assert y.shape == (3, 2)


def test_ndarray_iter_roll_over():
    data = np.arange(10, dtype="float32").reshape(10, 1)
    it = mx.io.NDArrayIter(data, np.arange(10, dtype="float32"),
                           batch_size=4, last_batch_handle="roll_over")
    epoch1 = [b.data[0].asnumpy().ravel() for b in it]
    assert [len(b) for b in epoch1] == [4, 4]  # 2 leftover held back
    it.reset()
    # 2 held-back + 10 fresh = 12 samples -> 3 full batches
    epoch2 = [(b.data[0].asnumpy().ravel(), b.label[0].asnumpy()) for b in it]
    assert [len(d) for d, _ in epoch2] == [4, 4, 4]
    # first batch of epoch 2 starts with the held-back samples 8, 9,
    # and the labels roll with the data
    assert epoch2[0][0][0] == 8.0 and epoch2[0][0][1] == 9.0
    assert epoch2[0][1][0] == 8.0


def test_f1_macro_vs_micro():
    macro = mx.metric.F1(average="macro")
    micro = mx.metric.F1(average="micro")
    batches = [
        (np.array([1, 1, 1, 1]), np.array([1, 1, 1, 0])),
        (np.array([0, 1]), np.array([0, 0])),
    ]
    for label, pred in batches:
        pred_scores = np.eye(2)[pred]
        for m in (macro, micro):
            m.update([mx.nd.array(label)], [mx.nd.array(pred_scores)])
    # micro pools counts: tp=3, fp=0, fn=2 -> f1 = 6/8
    assert abs(micro.get()[1] - 2 * 3 / (2 * 3 + 0 + 2)) < 1e-6
    # macro averages per-batch f1: (6/7 + 0) / 2
    assert abs(macro.get()[1] - ((2 * 3 / (2 * 3 + 0 + 1)) + 0.0) / 2) < 1e-6
    assert macro.get()[1] != micro.get()[1]


def test_prefetching_iter_depth_survives_reset():
    base = mx.io.NDArrayIter(np.zeros((8, 2), "float32"), batch_size=2)
    it = mx.io.PrefetchingIter(base, depth=5)
    list(it)
    it.reset()
    assert it._queue.maxsize == 5
    assert len(list(it)) == 4


def test_topk_mask():
    x = mx.nd.array([[1.0, 3.0, 2.0]])
    mask = mx.nd.topk(x, k=2, ret_typ="mask").asnumpy()
    assert np.array_equal(mask, [[0, 1, 1]])


def test_topk_mask_axis0():
    x = mx.nd.array([[1.0, 3.0, 2.0], [5.0, 0.0, 4.0]])
    mask = mx.nd.topk(x, axis=0, k=1, ret_typ="mask").asnumpy()
    assert np.array_equal(mask, [[0, 1, 0], [1, 0, 1]])


def test_ctc_loss_empty_label():
    logits = np.zeros((3, 1, 2), "float32")  # uniform: p(blank)=0.5 per step
    out = mx.nd.CTCLoss(mx.nd.array(logits), mx.nd.array([[1.0]]),
                        mx.nd.array([3.0]), mx.nd.array([0.0]),
                        use_data_lengths=True, use_label_lengths=True).asnumpy()
    assert abs(out[0] - (-np.log(0.5 ** 3))) < 1e-4


def test_symbol_swapaxes_positional():
    s = mx.sym.var("x")
    y = mx.sym.swapaxes(s, 0, 1)
    ex = y.bind(mx.cpu(), {"x": mx.nd.ones((2, 3))})
    assert ex.forward()[0].shape == (3, 2)


def test_hard_reset_drops_roll_over_cache():
    data = np.arange(10, dtype="float32").reshape(10, 1)
    it = mx.io.NDArrayIter(data, batch_size=4, last_batch_handle="roll_over")
    list(it)  # leaves a 2-sample cache
    it.hard_reset()
    it.reset()
    first = next(it)
    assert first.data[0].asnumpy()[0, 0] == 0.0


def test_small_parity_modules():
    """kvstore_server/log/registry/libinfo exist with reference APIs."""
    import warnings
    import mxnet_tpu as mx
    assert mx.libinfo.find_lib_path(), "native lib should be discoverable"
    lg = mx.log.get_logger("parity_test", level=mx.log.INFO)
    lg.info("hello")

    class Base:
        pass

    class Impl(Base):
        pass
    reg = mx.registry.get_register_func(Base, "thing")
    reg(Impl)
    create = mx.registry.get_create_func(Base, "thing")
    assert isinstance(create("impl"), Impl)
    assert isinstance(create(Impl()), Impl)
    alias = mx.registry.get_alias_func(Base, "thing")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        alias("impl2")(Impl)
    assert isinstance(create("impl2"), Impl)
    srv = mx.kvstore_server.KVStoreServer(mx.kv.create("local"))
    assert callable(srv._controller())


def test_batchnorm_variance_large_mean_stable():
    """ADVICE r2: E[x^2]-E[x]^2 cancels catastrophically for large-mean
    activations (first BN over 0-255 images); the centered two-pass form
    must match numpy's variance."""
    rng = np.random.RandomState(7)
    x = (rng.rand(4, 3, 8, 8) * 255.0).astype(np.float32) + 1e4
    data = mx.nd.array(x)
    gamma = mx.nd.ones((3,))
    beta = mx.nd.zeros((3,))
    mm = mx.nd.zeros((3,))
    mv = mx.nd.ones((3,))
    with mx.autograd.record(train_mode=True):
        out = mx.nd.BatchNorm(data, gamma, beta, mm, mv, fix_gamma=False,
                              eps=1e-5)
    got = out[0].asnumpy() if isinstance(out, list) else out.asnumpy()
    ref_mean = x.mean(axis=(0, 2, 3), keepdims=True)
    ref_var = x.var(axis=(0, 2, 3), keepdims=True)
    want = (x - ref_mean) / np.sqrt(ref_var + 1e-5)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_create_graph_replay_uses_recorded_inputs():
    """ADVICE r2: grad(create_graph=True) must replay the forward on the
    RECORDED input buffers, not the current ones after in-place mutation."""
    x = mx.nd.array([2.0])
    x.attach_grad()
    with mx.autograd.record():
        y = x * x * x          # y = x^3, dy/dx = 3x^2 = 12 at x=2
    x[:] = 100.0               # mutate AFTER recording, BEFORE the replay
    gx = mx.autograd.grad(y, x, create_graph=True)
    np.testing.assert_allclose(gx.asnumpy(), [12.0], rtol=1e-6)


def test_legacy_misc_scheduler():
    """Deprecated mx.misc scheduler API (reference misc.py) keeps
    working for old user code."""
    import mxnet_tpu as mx
    s = mx.misc.FactorScheduler(step=10, factor=0.5)
    s.base_lr = 0.8
    assert abs(s(0) - 0.8) < 1e-9
    assert abs(s(10) - 0.4) < 1e-9
    assert abs(s(25) - 0.2) < 1e-9
    import pytest
    with pytest.raises(ValueError):
        mx.misc.FactorScheduler(step=0)


def test_get_logger_root_gets_formatter_and_replaces_handlers(tmp_path):
    """Satellite (PR 2): the root logger (name=None) gets the colored
    formatter like any named logger, and re-calling with a different
    filename REPLACES the old handler instead of stacking a second."""
    import logging
    from mxnet_tpu.log import _Formatter

    root = logging.getLogger()
    saved = list(root.handlers)
    try:
        root.handlers = []
        lg = mx.log.get_logger(level=mx.log.INFO)
        ours = [h for h in lg.handlers
                if isinstance(h.formatter, _Formatter)]
        assert len(ours) == 1  # root got the framework formatter
        assert mx.log.get_logger(level=mx.log.INFO) is lg
        assert len([h for h in lg.handlers
                    if isinstance(h.formatter, _Formatter)]) == 1
    finally:
        for h in list(root.handlers):
            root.removeHandler(h)
            h.close()
        root.handlers = saved
        root._mx_log_dest = ()

    f1, f2 = str(tmp_path / "a.log"), str(tmp_path / "b.log")
    lg = mx.log.get_logger("telemetry_fix_test", filename=f1,
                           level=mx.log.INFO)
    lg.info("to-a")
    # same destination: no new handler stacked
    mx.log.get_logger("telemetry_fix_test", filename=f1, level=mx.log.INFO)
    assert len(lg.handlers) == 1
    # NEW destination: handler replaced, old file stops receiving
    mx.log.get_logger("telemetry_fix_test", filename=f2, level=mx.log.INFO)
    assert len(lg.handlers) == 1
    lg.info("to-b")
    a, b = open(f1).read(), open(f2).read()
    assert "to-a" in a and "to-b" not in a
    assert "to-b" in b


def test_profiler_resume_without_config_is_a_noop(monkeypatch):
    """Satellite (PR 2): a bare resume() used to silently start a trace
    into the default directory; now it warns and starts nothing."""
    import warnings
    from mxnet_tpu import profiler

    started = []
    import jax
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: started.append(d))
    monkeypatch.setitem(profiler._state, "configured", False)
    monkeypatch.setitem(profiler._state, "paused", False)
    monkeypatch.setitem(profiler._state, "running", False)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        profiler.resume()
    assert started == []
    assert any("set_config" in str(x.message) for x in w)
    assert not profiler._state["running"]
    # after set_config, resume() is a legitimate start again
    profiler.set_config(filename=str("/tmp/_prof_fix_test"))
    profiler.resume()
    assert started and profiler._state["running"]
    monkeypatch.setitem(profiler._state, "running", False)
    monkeypatch.setitem(profiler._state, "configured", False)


def test_profiler_autostart_honors_aggregate_env(tmp_path):
    """Satellite (PR 2): MXNET_PROFILER_AUTOSTART=1 +
    MXNET_PROFILER_AGGREGATE=1 collects the aggregate table."""
    import os
    import subprocess
    import sys
    code = (
        "import mxnet_tpu as mx\n"
        "a = mx.nd.ones((8, 8))\n"
        "(a + a).asnumpy()\n"
        "mx.profiler.set_state('stop')\n"
        "t = mx.profiler.dumps()\n"
        "assert 'Profile Statistics.' in t, repr(t[:80])\n"
        "print('AGG_OK')\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(mx.__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_PROFILER_AUTOSTART="1", MXNET_PROFILER_AGGREGATE="1",
               PYTHONPATH=repo)
    r = subprocess.run([sys.executable, "-c", code], cwd=str(tmp_path),
                       capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "AGG_OK" in r.stdout
