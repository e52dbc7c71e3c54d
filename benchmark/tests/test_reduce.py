"""The yardstick's own arithmetic, checked by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_reduce.py -q

The trace reduction runs on ``data/small.xplane.pb``, recorded on the chip
(TPU v5 lite; my chip run, PR 23) from a `fit` of the zoo's vgg16 at 32x32,
batch 8, with the harness's own profiler options; `flops.py` is held to the
papers' published counts."""
import importlib
import json
import os

import pytest

from benchmark import flops, reduce

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "data", "small.xplane.pb")
STEP = r"^jit_step\("


def test_union_and_gaps():
    spans = [(0, 10), (5, 20), (30, 40), (35, 38), (90, 120)]
    assert reduce.union_ns(spans, 0, 100) == 20 + 10 + 10
    assert reduce.union_ns(spans, 8, 33) == 12 + 3
    assert reduce.gaps_ns(spans, 0, 100) == [(20, 30), (40, 90)]
    assert reduce.gaps_ns([], 0, 7) == [(0, 7)]
    assert reduce.union_ns([(3, 3)], 0, 10) == 0


def test_short_name_and_category():
    name = "%fusion.12 = bf16[8,8]{1,0} fusion(bf16[8,8] %p), kind=kLoop"
    assert reduce.short_name(name) == "fusion.12"
    assert reduce.category({"name": "copy-done.3", "stats": {}}) == "copy-done"
    assert reduce.category({"name": "x", "stats": {"hlo_category": "Conv"}}) \
        == "conv"


@pytest.fixture(scope="module")
def trace():
    return reduce.reduce(TRACE, STEP)


def test_window_is_whole_dispatches(trace):
    assert trace["dispatches"] == 3
    chip = trace["chips"][0]
    steps = [m for m in chip["modules"] if m["name"].startswith("jit_step(")]
    # the last execution starts where the window ends, so it is clipped away
    assert len(steps) == 3
    assert trace["lo"] == min(m["start"] for m in steps)
    assert all(trace["lo"] <= e["start"] <= e["end"] <= trace["hi"]
               for e in chip["ops"])


def test_busy_idle_and_step_time(trace):
    assert trace["window_s"] == pytest.approx(0.024749769, rel=1e-6)
    assert trace["busy_s"] == pytest.approx(0.002738309, rel=1e-6)
    chip = trace["chips"][0]
    idle = sum(b - a for a, b in chip["gaps"]) / 1e9
    assert idle + trace["busy_s"] == pytest.approx(trace["window_s"],
                                                   rel=1e-9)
    seconds, runs = reduce.module_time(trace, STEP)
    assert runs == 3 and seconds == pytest.approx(0.002735142, rel=1e-6)
    # the ops of a step lie inside its program's event
    assert trace["busy_s"] <= seconds * 1.01 + 3e-5


def test_breakdown(trace):
    doc = reduce.breakdown(trace)
    assert len(doc["device_ops"]) <= 10 and len(doc["idle_gaps"]) <= 10
    gaps = dict(doc["idle_gaps"])
    assert gaps["between_step_programs"] > 100 * gaps["inside_step_program"]
    assert sum(gaps.values()) + trace["busy_s"] == pytest.approx(
        trace["window_s"], rel=1e-9)
    json.dumps(doc)


def test_no_step_program_is_nothing_to_read():
    assert reduce.reduce(TRACE, r"^jit_no_such_program\(") is None


@pytest.mark.parametrize("config, published_gmacs", [
    ("resnet50_v1_bf16_nhwc", 3.8),    # He et al., Table 1: 3.8e9 FLOPs
    ("vgg16_bf16_nchw", 15.5),         # 15.5 G multiply-adds, column D
])
def test_flops_against_the_papers(config, published_gmacs):
    with open(os.path.join(HERE, "..", "configs", config + ".json")) as f:
        cfg = json.load(f)
    layers = importlib.import_module(
        "benchmark.refs." + cfg["reference"]).layers(cfg["reference_args"])
    size = cfg["image"]
    shape = (2, size, size, 3) if cfg["layout"] == "NHWC" \
        else (2, 3, size, size)
    macs = flops.forward_macs_per_sample(layers, shape, cfg["layout"])
    assert macs / 1e9 == pytest.approx(published_gmacs, rel=0.05)
    mats = flops.matmul_layers(layers, shape, cfg["layout"])
    # forward + two gradients, less the image's gradient
    assert flops.train_flops(mats) == 2 * (
        3 * sum(m["macs"] for m in mats) - mats[0]["macs"])
