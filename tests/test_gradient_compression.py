"""2-bit gradient compression tests.

Mirrors the semantics exercised by the reference's
`tests/nightly/dist_sync_kvstore.py` compressed push-pull checks and
`docs/faq/gradient_compression.md`: thresholding, error feedback
accumulation, wire-size ratio.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gradient_compression import GradientCompression


def test_quantize_dequantize_mapping():
    import jax.numpy as jnp
    gc = GradientCompression({"type": "2bit", "threshold": 0.5})
    g = jnp.asarray([0.6, -0.7, 0.1, -0.1, 0.0, 2.0, -2.0], jnp.float32)
    res = jnp.zeros_like(g)
    packed, new_res = gc.quantize(g, res)
    out = np.asarray(gc.dequantize(packed, g.shape, jnp.float32))
    # elements past +/-threshold send one threshold step; small ones send 0
    np.testing.assert_allclose(out, [0.5, -0.5, 0, 0, 0, 0.5, -0.5])
    # residual keeps what was not sent
    np.testing.assert_allclose(
        np.asarray(new_res), [0.1, -0.2, 0.1, -0.1, 0.0, 1.5, -1.5],
        rtol=1e-6, atol=1e-6)


def test_wire_size_is_16x_smaller():
    import jax.numpy as jnp
    gc = GradientCompression({"type": "2bit", "threshold": 0.5})
    g = jnp.zeros((1024,), jnp.float32)
    packed, _ = gc.quantize(g, g)
    assert packed.dtype == jnp.uint8
    assert packed.size == 1024 // 4  # 2 bits/elem: 16x vs float32 bytes


def test_error_feedback_accumulates():
    """Pushing a constant sub-threshold gradient must eventually deliver
    threshold steps at the right average rate (error feedback)."""
    import jax.numpy as jnp
    gc = GradientCompression({"type": "2bit", "threshold": 1.0})
    g = jnp.full((4,), 0.3, jnp.float32)
    res = jnp.zeros_like(g)
    delivered = np.zeros(4, np.float32)
    for _ in range(10):
        packed, res = gc.quantize(g, res)
        delivered += np.asarray(gc.dequantize(packed, g.shape, jnp.float32))
    # 10 pushes of 0.3 = 3.0 total; with threshold 1.0 exactly 3 steps sent
    np.testing.assert_allclose(delivered, 3.0)
    np.testing.assert_allclose(np.asarray(res), 0.0, atol=1e-5)


def test_kvstore_compressed_push_pull():
    kv = mx.kv.create("local")
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    shape = (8, 4)
    kv.init("w", mx.nd.zeros(shape))
    big = mx.nd.ones(shape) * 0.9
    kv.push("w", big)
    out = mx.nd.zeros(shape)
    kv.pull("w", out=out)
    # one step of +0.5 lands; 0.4 stays in the residual
    np.testing.assert_allclose(out.asnumpy(), 0.5)
    kv.push("w", big)
    kv.pull("w", out=out)
    # 2-bit codes saturate at one threshold step per push; residual grows
    np.testing.assert_allclose(out.asnumpy(), 0.5)
    np.testing.assert_allclose(
        np.asarray(kv._gc._residuals["w"]), 0.8, rtol=1e-6)


def test_kvstore_compression_params_recorded():
    kv = mx.kv.create("local")
    kv.set_gradient_compression({"type": "2bit", "threshold": 2.0})
    assert kv._gc.threshold == 2.0
    with pytest.raises(ValueError):
        kv.set_gradient_compression({"type": "1bit"})


def test_compressed_wire_bytes_two_process(tmp_path):
    """2-process dist_sync with 2-bit compression: only the packed uint8
    codes cross the collective — transferred bytes ~= dense/16 (reference
    kvstore_dist.h:379 Quantize-before-ZPush) — and training semantics
    survive (error feedback keeps the sum drifting toward the true
    gradient)."""
    import os
    import re
    import subprocess
    import sys
    TOOLS = os.path.join(os.path.dirname(__file__), os.pardir, "tools")
    worker = tmp_path / "worker.py"
    worker.write_text(
        "import numpy as np\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu.parallel import dist\n"
        "dist.init()\n"
        "kv = mx.kv.create('dist_sync')\n"
        "kv.set_gradient_compression({'type': '2bit', 'threshold': 0.5})\n"
        "rank = kv.rank\n"
        "kv.init('w', mx.nd.zeros((64, 64)))\n"
        "g = mx.nd.ones((64, 64)) * (0.6 if rank == 0 else -0.6)\n"
        "kv.push('w', g)\n"
        "out = mx.nd.zeros((64, 64))\n"
        "kv.pull('w', out=out)\n"
        "# +0.5 (rank0, code 01) + -0.5 (rank1, code 10) = 0.0 stored\n"
        "np.testing.assert_allclose(out.asnumpy(), 0.0, atol=1e-6)\n"
        "wire = kv._last_wire_bytes\n"
        "dense = kv._last_dense_bytes\n"
        "assert wire * 15 <= dense, (wire, dense)\n"
        "print('WIRE %d DENSE %d RATIO %.1f OK' % (wire, dense,\n"
        "      dense / wire))\n"
        "# error feedback: residual 0.1 accumulates across pushes\n"
        "for _ in range(4):\n"
        "    kv.push('w', mx.nd.ones((64, 64)) * 0.3)\n"
        "kv.pull('w', out=out)\n"
        "assert abs(out.asnumpy().mean()) > 0.1\n"
        "print('GC DIST', rank, 'OK')\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(TOOLS, os.pardir))
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "launch.py"), "-n", "2",
         "--port", "9447", "--", sys.executable, str(worker)],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr + r.stdout
    assert r.stdout.count("OK") == 4
    m = re.search(r"RATIO ([\d.]+)", r.stdout)
    assert float(m.group(1)) >= 15.0


def test_training_accuracy_with_compression():
    """Accuracy smoke (reference docs/faq/gradient_compression.md): a
    separable problem still trains to high accuracy through the
    quantized gradient path with a sane threshold."""
    rng = np.random.RandomState(0)
    protos = rng.rand(4, 16).astype("f") * 2
    y = rng.randint(0, 4, 600)
    X = protos[y] + rng.randn(600, 16).astype("f") * 0.1
    it = mx.io.NDArrayIter(X, y.astype("f"), 50, shuffle=True)
    data = mx.sym.var("data")
    net = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    kv = mx.kv.create("device")
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.05})
    mod = mx.mod.Module(net)
    mod.fit(it, optimizer="sgd", initializer=mx.init.Xavier(),
            optimizer_params={"learning_rate": 0.5}, num_epoch=12,
            kvstore=kv)
    acc = dict(mod.score(mx.io.NDArrayIter(X, y.astype("f"), 50),
                         "acc"))["accuracy"]
    assert acc > 0.9, acc


def test_tpu_kvstore_roundtrip_error_bound_and_bytes_counter():
    """The `tpu` kvstore's compressed push path: (a) error feedback
    bounds the round-trip error — with per-push gradients bounded by the
    threshold, every element's residual (cumulative pushed minus
    cumulative delivered) stays within ONE threshold — and (b)
    `kvstore_compressed_bytes_total` counts the packed code bytes each
    push produced."""
    from mxnet_tpu import telemetry
    kv = mx.kv.create("tpu")
    thresh = 0.5
    kv.set_gradient_compression({"type": "2bit", "threshold": thresh})
    shape = (16, 8)
    rng = np.random.RandomState(3)
    kv.init(0, mx.nd.zeros(shape))
    kv._set_updater(lambda key, grad, stored: None)  # keep store inert

    c0 = telemetry.counter("kvstore_compressed_bytes_total").value
    pushed_total = np.zeros(shape, np.float32)
    pushes = 12
    for _ in range(pushes):
        # |g| <= threshold: the regime where the error-feedback residual
        # provably stays within one threshold step per element
        g = rng.uniform(-thresh, thresh, shape).astype(np.float32)
        pushed_total += g
        kv.push(0, mx.nd.array(g))
    # delivered = pushed - residual; the residual is the ONLY loss, and
    # error feedback keeps it within one threshold per element
    residual = np.asarray(kv._gc._residuals[0])
    np.testing.assert_array_less(np.abs(residual), thresh + 1e-6)
    c1 = telemetry.counter("kvstore_compressed_bytes_total").value
    packed_per_push = int(np.ceil(shape[0] * shape[1] / 4))  # 2-bit codes
    assert c1 - c0 == pushes * packed_per_push
    # the counted wire bytes are 16x smaller than the dense payload
    dense_per_push = shape[0] * shape[1] * 4
    assert (c1 - c0) * 16 == pushes * dense_per_push
