"""Pretrained-zoo converter (tools/convert_zoo_params.py): reference-style
.params files load through vision.<model>(pretrained=True).

No egress exists to fetch the real zoo blobs (reference
model_store.py:70-105 downloads them), so the tests synthesize a
reference-FORMAT file — same byte container, same gluon naming, same
arg:/aux: prefixes a checkpoint-saved file carries — and assert the
converted model reproduces the source net's outputs exactly.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo import vision

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(REPO, "tools", "convert_zoo_params.py")


def _make_reference_style_file(tmp_path, prefixed=True):
    """Init a resnet18_v1 and save it the way reference checkpoints look:
    arg:/aux: key prefixes, NCHW OIHW weights, gluon-prefixed names."""
    net = vision.resnet18_v1()
    net.initialize(mx.init.Xavier())
    x = mx.nd.array(np.random.RandomState(0).rand(1, 3, 224, 224)
                    .astype(np.float32))
    want = net(x).asnumpy()
    blob = {}
    for name, p in net.collect_params().items():
        tag = "aux:" if "running" in name else "arg:"
        blob[(tag + name) if prefixed else name] = p.data()
    path = str(tmp_path / "resnet18_v1-0000.params")
    mx.nd.save(path, blob)
    return path, x, want


def _run_tool(src, out_dir, *extra):
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    r = subprocess.run(
        [sys.executable, TOOL, src, "--model", "resnet18_v1",
         "--out-dir", out_dir] + list(extra),
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    return r.stdout


def test_convert_and_pretrained_load(tmp_path):
    src, x, want = _make_reference_style_file(tmp_path)
    out_dir = str(tmp_path / "zoo")
    out = _run_tool(src, out_dir)
    assert "matched" in out
    net = vision.resnet18_v1(pretrained=True, root=out_dir)
    got = net(x).asnumpy()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_convert_nhwc_layout(tmp_path):
    src, x, want = _make_reference_style_file(tmp_path)
    out_dir = str(tmp_path / "zoo_nhwc")
    _run_tool(src, out_dir, "--layout", "NHWC")
    net = vision.resnet18_v1(pretrained=True, root=out_dir, layout="NHWC")
    x_nhwc = mx.nd.array(x.asnumpy().transpose(0, 2, 3, 1))
    got = net(x_nhwc).asnumpy()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_pretrained_without_file_raises(tmp_path):
    with pytest.raises(mx.base.MXNetError, match="not found"):
        vision.resnet18_v1(pretrained=True, root=str(tmp_path / "empty"))
