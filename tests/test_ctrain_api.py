"""MXT* train C ABI: a C++ host process trains a model end-to-end and
its loss curve matches the Python Module path exactly.

Reference parity: cpp-package/example/lenet.cpp trains over the C API
(include/mxnet/c_api.h); here cpp-package/example/mlp_train.cpp drives
src/c_train_api.cc, which delegates to the SAME Module._step program
Python uses — so parity is byte-marshalling plus determinism, verified
against a same-seed Python run.
"""
import os
import re
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
BIN = os.path.join(REPO, "cpp-package", "example", "mlp_train")

N, D, CLASSES, EPOCHS, BATCH = 512, 16, 10, 8, 64


def _symbol_json():
    import mxnet_tpu as mx
    d = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(d, num_hidden=64, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=CLASSES, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _data():
    rng = np.random.RandomState(3)
    centers = rng.randn(CLASSES, D) * 3.0
    y = rng.randint(0, CLASSES, N)
    x = centers[y] + rng.randn(N, D) * 0.6
    return x.astype(np.float32), y.astype(np.float32)


def _python_curve(sym, x, y):
    """Same training loop through the Python Module path, same seed."""
    import mxnet_tpu as mx
    mod = mx.mod.Module(sym)
    mod.bind(data_shapes=[("data", (BATCH, D))],
             label_shapes=[("softmax_label", (BATCH,))])
    mx.random.seed(7)  # same point CTrainer.init_params seeds
    np.random.seed(7)  # initializers draw from the numpy global RNG
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    losses = []
    from mxnet_tpu.io import DataBatch
    for _ in range(EPOCHS):
        total = 0.0
        for b in range(N // BATCH):
            xb = x[b * BATCH:(b + 1) * BATCH]
            yb = y[b * BATCH:(b + 1) * BATCH]
            mod._step(DataBatch(data=[mx.nd.array(xb)],
                                label=[mx.nd.array(yb)]))
            probs = mod.get_outputs()[0].asnumpy()
            p = probs[np.arange(BATCH), yb.astype(int)]
            total += float(-np.log(np.maximum(p, 1e-12)).sum())
        losses.append(total / N)
    return losses


def test_cpp_trains_to_95pct_and_matches_python(tmp_path):
    build = subprocess.run(["make", "-C", SRC, "cpp_example"],
                           capture_output=True, text=True)
    assert build.returncode == 0, build.stderr

    import mxnet_tpu as mx
    sym = _symbol_json()
    x, y = _data()
    sym_path = str(tmp_path / "mlp-symbol.json")
    sym.save(sym_path)
    data_path = str(tmp_path / "data.bin")
    with open(data_path, "wb") as f:
        f.write(x.tobytes())
        f.write(y.tobytes())

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([REPO] + sys.path)
    env.setdefault("JAX_PLATFORMS", "cpu")
    run = subprocess.run(
        [BIN, sym_path, data_path, str(N), str(D), str(CLASSES),
         str(EPOCHS), str(BATCH), "1"],
        capture_output=True, text=True, env=env, timeout=600)
    assert run.returncode == 0, run.stdout + run.stderr[-2000:]
    assert "FINAL acc" in run.stdout
    final = float(re.search(r"FINAL acc ([\d.]+)", run.stdout).group(1))
    assert final > 0.95, run.stdout

    cpp_losses = [float(m) for m in
                  re.findall(r"epoch \d+ loss ([\d.]+)", run.stdout)]
    assert len(cpp_losses) == EPOCHS
    # loss must actually go down (training happened)
    assert cpp_losses[-1] < cpp_losses[0] * 0.5

    py_losses = _python_curve(sym, x, y)
    np.testing.assert_allclose(cpp_losses, py_losses, rtol=1e-4,
                               atol=1e-5)


def test_cpp_checkpoint_roundtrip(tmp_path):
    """SaveCheckpoint from the C ABI writes a Python-loadable .params."""
    pytest.importorskip("mxnet_tpu")
    import mxnet_tpu as mx
    from mxnet_tpu.ctrain import CTrainer

    sym = _symbol_json()
    x, y = _data()
    tr = CTrainer(sym.tojson(), 1, 0, ["data"], ["softmax_label"])
    tr.bind(["data", "softmax_label"], [(BATCH, D), (BATCH,)])
    tr.init_params("xavier", 7)
    tr.init_optimizer("sgd", {"learning_rate": "0.1"})
    tr.step(["data", "softmax_label"],
            [x[:BATCH].tobytes(), y[:BATCH].tobytes()])
    prefix = str(tmp_path / "model")
    tr.save_checkpoint(prefix, 1)
    params = mx.nd.load(prefix + "-0001.params")
    assert any(k.endswith("fc1_weight") for k in params)

    # and load back through the C-ABI helper path
    tr2 = CTrainer(sym.tojson(), 1, 0, ["data"], ["softmax_label"])
    tr2.bind(["data", "softmax_label"], [(BATCH, D), (BATCH,)])
    tr2.init_params("zeros", 0)
    tr2.load_params(prefix + "-0001.params")
    tr2.forward(["data"], [x[:BATCH].tobytes()])
    tr.forward(["data"], [x[:BATCH].tobytes()])
    np.testing.assert_allclose(
        np.frombuffer(tr2.output_bytes(0), np.float32),
        np.frombuffer(tr.output_bytes(0), np.float32), rtol=1e-5)
