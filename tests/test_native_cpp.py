"""Build and run the native C++ unit tests (the reference's tests/cpp
suite analog — tests/cpp/{engine,storage,operator} there run under
googletest; src/tests/native_tests.cc is a self-contained CHECK harness
over the libmxtpu C API)."""
import os
import subprocess

import pytest

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")


@pytest.mark.skipif(not os.path.exists(os.path.join(SRC, "Makefile")),
                    reason="native sources not present")
def test_native_cpp_suite():
    build = subprocess.run(["make", "-C", SRC, "tests/native_tests"],
                           capture_output=True, text=True)
    assert build.returncode == 0, build.stderr
    run = subprocess.run([os.path.join(SRC, "tests", "native_tests")],
                         capture_output=True, text=True)
    assert run.returncode == 0, run.stdout + run.stderr
    assert "checks passed" in run.stdout


def test_ndlist_cross_language_roundtrip(tmp_path):
    """The native NDList reader/writer is byte-compatible with the Python
    .params serializer in BOTH directions (reference c_predict_api
    MXNDListCreate over NDArray::Save files)."""
    import ctypes
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu._native import lib as _lib_fn
    lib = _lib_fn()
    if lib is None:
        import pytest
        pytest.skip("native library not built")

    # Python writes -> C reads
    f = str(tmp_path / "py.params")
    w = np.arange(12, dtype=np.float32).reshape(3, 4) * 0.5
    ids = np.array([3, 1, 4], np.int64)
    mx.nd.save(f, {"arg:w": mx.nd.array(w),
                   "ids": mx.nd.array(ids, dtype=np.int64)})
    h = ctypes.c_void_p()
    count = ctypes.c_size_t()
    assert lib.MXTNDListCreateFromFile(
        f.encode(), ctypes.byref(h), ctypes.byref(count)) == 0
    assert count.value == 2
    name = ctypes.c_char_p()
    data = ctypes.c_void_p()
    shape = ctypes.POINTER(ctypes.c_int64)()
    ndim = ctypes.c_uint32()
    flag = ctypes.c_int()
    got = {}
    for i in range(2):
        assert lib.MXTNDListGet(h, i, ctypes.byref(name),
                                ctypes.byref(data), ctypes.byref(shape),
                                ctypes.byref(ndim),
                                ctypes.byref(flag)) == 0
        shp = tuple(shape[d] for d in range(ndim.value))
        nbytes = int(np.prod(shp)) * (4 if flag.value == 0 else 8)
        raw = ctypes.string_at(data, nbytes)
        got[name.value.decode()] = (shp, flag.value, raw)
    assert got["arg:w"][0] == (3, 4) and got["arg:w"][1] == 0
    np.testing.assert_array_equal(
        np.frombuffer(got["arg:w"][2], np.float32).reshape(3, 4), w)
    assert got["ids"][1] == 6
    np.testing.assert_array_equal(
        np.frombuffer(got["ids"][2], np.int64), ids)
    assert lib.MXTNDListFree(h) == 0

    # C writes -> Python loads
    f2 = str(tmp_path / "c.params")
    names = (ctypes.c_char_p * 1)(b"bias")
    arr = np.array([1.0, -2.5], np.float32)
    datas = (ctypes.c_void_p * 1)(arr.ctypes.data)
    shp_arr = (ctypes.c_int64 * 1)(2)
    shapes = (ctypes.POINTER(ctypes.c_int64) * 1)(shp_arr)
    ndims = (ctypes.c_uint32 * 1)(1)
    flags = (ctypes.c_int * 1)(0)
    assert lib.MXTNDListSave(f2.encode(), 1, names, datas, shapes, ndims,
                             flags) == 0
    loaded = mx.nd.load(f2)
    np.testing.assert_array_equal(loaded["bias"].asnumpy(), arr)


def test_ndlist_rejects_corrupt_files(tmp_path):
    """Crafted corruption must produce clean errors, not out-of-bounds
    reads: huge name length, huge ndim, negative dims (review r3)."""
    import ctypes
    import struct
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu._native import lib as _lib_fn
    lib = _lib_fn()
    if lib is None:
        import pytest
        pytest.skip("native library not built")

    f = str(tmp_path / "ok.params")
    mx.nd.save(f, {"w": mx.nd.array(np.ones((2, 2), np.float32))})
    good = open(f, "rb").read()

    def parse(buf):
        h = ctypes.c_void_p()
        count = ctypes.c_size_t()
        rc = lib.MXTNDListCreate(buf, len(buf), ctypes.byref(h),
                                 ctypes.byref(count))
        if rc == 0:
            lib.MXTNDListFree(h)
        return rc

    assert parse(good) == 0
    # name length field is the last 12..4 bytes region: set to huge
    corrupt = bytearray(good)
    corrupt[-9:-1] = struct.pack("<Q", 2 ** 63)[0:8]
    assert parse(bytes(corrupt)) != 0
    # huge ndim in the record header (offset: 24 list hdr + 4 magic + 4
    # stype)
    corrupt = bytearray(good)
    corrupt[32:36] = struct.pack("<I", 0xFFFFFFF0)
    assert parse(bytes(corrupt)) != 0
    # negative dim
    corrupt = bytearray(good)
    corrupt[36:44] = struct.pack("<q", -2)
    assert parse(bytes(corrupt)) != 0
    # truncated payload
    assert parse(good[:-6]) != 0


def test_ndlist_bf16_roundtrip(tmp_path):
    """bf16 .params (dtype flag 12, this framework's serializer extension)
    must round-trip through the native C API (advisor r3: DTypeSize
    rejected flag 12, so native code couldn't read checkpoints the Python
    side writes for bf16 models)."""
    import ctypes
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu._native import lib as _lib_fn
    lib = _lib_fn()
    if lib is None:
        import pytest
        pytest.skip("native library not built")

    f = str(tmp_path / "bf16.params")
    w = mx.nd.array(np.arange(6, dtype=np.float32).reshape(2, 3),
                    dtype="bfloat16")
    mx.nd.save(f, {"w": w})

    h = ctypes.c_void_p()
    count = ctypes.c_size_t()
    assert lib.MXTNDListCreateFromFile(
        f.encode(), ctypes.byref(h), ctypes.byref(count)) == 0
    assert count.value == 1
    name = ctypes.c_char_p()
    data = ctypes.c_void_p()
    shape = ctypes.POINTER(ctypes.c_int64)()
    ndim = ctypes.c_uint32()
    flag = ctypes.c_int()
    assert lib.MXTNDListGet(h, 0, ctypes.byref(name), ctypes.byref(data),
                            ctypes.byref(shape), ctypes.byref(ndim),
                            ctypes.byref(flag)) == 0
    assert flag.value == 12
    raw = ctypes.string_at(data, 2 * 3 * 2)
    assert lib.MXTNDListFree(h) == 0

    # C writes the same bf16 payload back; Python must load it as bf16
    f2 = str(tmp_path / "c_bf16.params")
    names = (ctypes.c_char_p * 1)(b"w")
    buf = ctypes.create_string_buffer(raw, len(raw))
    datas = (ctypes.c_void_p * 1)(ctypes.addressof(buf))
    shp_arr = (ctypes.c_int64 * 2)(2, 3)
    shapes = (ctypes.POINTER(ctypes.c_int64) * 1)(shp_arr)
    ndims = (ctypes.c_uint32 * 1)(2)
    flags = (ctypes.c_int * 1)(12)
    assert lib.MXTNDListSave(f2.encode(), 1, names, datas, shapes, ndims,
                             flags) == 0
    loaded = mx.nd.load(f2)["w"]
    assert str(loaded.dtype) == "bfloat16"
    np.testing.assert_array_equal(loaded.asnumpy().astype(np.float32),
                                  np.arange(6, dtype=np.float32).reshape(2, 3))


def test_c_predict_api_end_to_end(tmp_path):
    """Reference-style C deployment: export a trained symbol+params from
    Python, run the compiled MXPred* client (src/tests/predict_demo.c)
    against them, and check its outputs equal the Python Predictor's
    (reference include/mxnet/c_predict_api.h flow)."""
    import struct
    import sys
    import numpy as np
    import mxnet_tpu as mx

    build = subprocess.run(["make", "-C", SRC, "tests/predict_demo"],
                           capture_output=True, text=True)
    assert build.returncode == 0, build.stderr

    # tiny model: 2-layer MLP, deterministic params
    rng = np.random.RandomState(0)
    data = mx.sym.var("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    params = {
        "arg:fc1_weight": mx.nd.array(rng.randn(8, 5).astype(np.float32)),
        "arg:fc1_bias": mx.nd.array(rng.randn(8).astype(np.float32)),
        "arg:fc2_weight": mx.nd.array(rng.randn(3, 8).astype(np.float32)),
        "arg:fc2_bias": mx.nd.array(rng.randn(3).astype(np.float32)),
    }
    sym_path = str(tmp_path / "model-symbol.json")
    param_path = str(tmp_path / "model.params")
    net.save(sym_path)
    mx.nd.save(param_path, params)

    x = rng.randn(4, 5).astype(np.float32)

    from mxnet_tpu.predict import Predictor
    with Predictor(open(sym_path).read(), param_path,
                   input_shapes={"data": (4, 5)}) as pred:
        pred.forward(data=x)
        expect = pred.get_output(0)

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(SRC, os.pardir)] + sys.path)
    env.setdefault("JAX_PLATFORMS", "cpu")
    run = subprocess.run(
        [os.path.join(SRC, "tests", "predict_demo"), sym_path, param_path,
         "data", "4", "5"],
        input=x.tobytes(), capture_output=True, env=env, timeout=420)
    assert run.returncode == 0, run.stderr.decode()[-2000:]
    got = np.array([[float(v) for v in line.split()]
                    for line in run.stdout.decode().strip().splitlines()])
    assert got.shape == expect.shape
    assert np.allclose(got, expect, rtol=1e-4, atol=1e-5), (got, expect)

    # ADVICE r4: a weight name must NOT be settable through set_input —
    # the reference c_predict_api rejects keys that aren't declared
    # inputs (a typo would otherwise silently overwrite the weight)
    import pytest
    with Predictor(open(sym_path).read(), param_path,
                   input_shapes={"data": (4, 5)}) as pred:
        with pytest.raises(mx.base.MXNetError, match="no input named"):
            pred.set_input("fc1_weight", np.zeros((8, 5), np.float32))

    # bad CLI arguments must error out, not crash (ADVICE r4)
    bad = subprocess.run(
        [os.path.join(SRC, "tests", "predict_demo"), sym_path, param_path,
         "data", "0", "xyz"],
        input=b"", capture_output=True, env=env, timeout=60)
    assert bad.returncode == 2
    assert b"bad batch/dim" in bad.stderr
