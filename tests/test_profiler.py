"""Profiler aggregate statistics + memory profiling (reference
src/profiler/aggregate_stats.cc, storage_profiler.h) and the per-op perf
harness (reference test_utils.py:1133 check_speed,
tests/cpp/operator/coreop_perf.cc)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def agg():
    profiler.set_config(aggregate_stats=True, profile_memory=True)
    profiler.reset_stats()
    yield
    profiler.set_config(aggregate_stats=False, profile_memory=False)
    profiler.reset_stats()


def test_aggregate_stats_table(agg):
    a = mx.nd.ones((32, 32))
    for _ in range(3):
        b = mx.nd.dot(a, a)
    (b + 1).asnumpy()
    table = profiler.dumps()
    assert "Profile Statistics." in table
    assert "dot" in table
    # per-op count column is real
    line = [l for l in table.splitlines() if l.startswith("dot")][0]
    assert int(line.split()[1]) == 3
    # memory section present with positive byte counts
    assert "Memory allocations" in table
    mline = [l for l in table.splitlines()
             if l.startswith("dot") and l in table.split(
                 "Memory allocations")[1]]
    assert mline and int(mline[0].split()[2]) >= 3 * 32 * 32 * 4


def test_dumps_reset(agg):
    mx.nd.ones((4,)).asnumpy()
    (mx.nd.ones((4,)) * 2).asnumpy()
    assert profiler.dumps(reset=True) != ""
    assert profiler.dumps() == ""


def test_dumps_empty_when_disabled():
    profiler.set_config(aggregate_stats=False)
    profiler.reset_stats()
    mx.nd.ones((4,)).asnumpy()
    assert profiler.dumps() == ""


def test_executor_calls_aggregated(agg):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc")
    exe = net.simple_bind(mx.cpu(), data=(4, 16))
    exe.forward(is_train=False)
    exe.forward_backward()
    table = profiler.dumps()
    assert "_executor_forward" in table
    assert "_executor_forward_backward" in table


def test_check_speed_returns_time():
    from mxnet_tpu.test_utils import check_speed
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc")
    sec = check_speed(net, ctx=mx.cpu(), N=3, data=(4, 16))
    assert 0 < sec < 10


def test_op_bench_harness_tiny():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "perf", "op_bench.py"),
         "--preset", "tiny", "-N", "2"],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "Convolution3x3" in out.stdout
    assert "FAIL" not in out.stdout, out.stdout
    # one JSON line per op for regression diffing
    import json
    json_lines = [l for l in out.stdout.splitlines()
                  if l.startswith('{"metric": "op_us"')]
    assert len(json_lines) >= 10
    assert all(json.loads(l)["us_per_iter"] > 0 for l in json_lines)


# ---------------------------------------------------------------------------
# XPlane device-time attribution (reference engine-instrumented aggregate
# stats, src/profiler/aggregate_stats.cc + src/engine/threaded_engine.h:80)
# ---------------------------------------------------------------------------

def test_xplane_device_time_table(tmp_path):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import xplane

    logdir = str(tmp_path / "trace")
    jax.profiler.start_trace(logdir)
    f = jax.jit(lambda x, w: jnp.tanh(x @ w) @ w.T)
    w = jnp.ones((256, 256), jnp.float32)
    x = jnp.ones((128, 256), jnp.float32)
    for _ in range(4):
        x = f(x, w)
    jax.block_until_ready(x)
    jax.profiler.stop_trace()

    files = xplane.find_xplane_files(logdir)
    assert files, "trace capture produced no .xplane.pb"

    # the HLO execution line must show the matmul with nonzero device time
    table = xplane.op_table(logdir, line_filter="PjRtCpuClient")
    dots = [k for k in table if "dot" in k or "fusion" in k]
    assert dots, f"no dot/fusion op in table: {sorted(table)[:20]}"
    assert all(table[k]["total_ps"] > 0 for k in dots)

    # rendered table is non-empty and carries the share column
    txt = xplane.dumps(logdir, line_filter="PjRtCpuClient", top=10)
    assert "Total (ms)" in txt and "%" in txt

    # profiler front door
    out = profiler.device_dumps(logdir, line_filter="PjRtCpuClient")
    assert out == txt


def test_xplane_cli(tmp_path):
    import jax
    import jax.numpy as jnp

    logdir = str(tmp_path / "trace")
    jax.profiler.start_trace(logdir)
    jax.block_until_ready(jax.jit(lambda a: a @ a)(jnp.ones((64, 64))))
    jax.profiler.stop_trace()

    out = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.xplane", logdir, "--top", "5",
         "--json", str(tmp_path / "t.json")],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "TOTAL" in out.stdout
    assert (tmp_path / "t.json").exists()
