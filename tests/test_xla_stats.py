"""Device & compiler observability (`mxnet_tpu/xla_stats.py`): compile
accounting with the retrace explainer, the memory ledger /
`profiler._device_memory_lines` zeros-on-CPU contract, MFU goodput, the
bench regression gate, and the crash flight recorder (including the
launched chaos-kill acceptance test)."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry, xla_stats

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import launchutil  # noqa: E402
import bench_gate  # noqa: E402


@pytest.fixture
def fresh(tmp_path):
    telemetry.reset()
    xla_stats.reset()
    telemetry.configure(str(tmp_path / "telemetry"), snapshot_interval=0)
    yield str(tmp_path / "telemetry")
    telemetry.configure(None)
    telemetry.reset()
    xla_stats.reset()


def _fc_module(batch=4, for_training=False):
    data = mx.sym.var("data")
    fc = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    mod = mx.mod.Module(fc, label_names=None, context=mx.cpu())
    mod.bind(data_shapes=[("data", (batch, 10))],
             for_training=for_training)
    mod.init_params()
    return mod


# ---------------------------------------------------------------------------
# Compile accounting (tentpole 1)
# ---------------------------------------------------------------------------

def test_one_compile_then_cache_hits(fresh):
    """Repeated Module.forward with a FIXED shape is exactly one XLA
    compile; every later call is a cache hit and no retrace fires."""
    mod = _fc_module()
    batch = mx.io.DataBatch(data=[mx.nd.ones((4, 10))], label=None)
    for _ in range(4):
        mod.forward(batch, is_train=False)
    site = dict(site="executor.forward")
    assert telemetry.get_metric("jit_compiles_total", **site).value == 1
    assert telemetry.get_metric("jit_cache_hits_total", **site).value == 3
    retr = telemetry.get_metric("jit_retraces_total", **site)
    assert retr is None or retr.value == 0
    # compile wall time landed in the per-site histogram
    h = telemetry.get_metric("jit_compile_seconds", **site)
    assert h is not None and h.count == 1 and h.sum > 0


def test_retrace_explainer_names_changed_dimension(fresh):
    """A batch-shape change retraces, and the explainer names the input
    and the exact dimension that changed."""
    mod = _fc_module()
    mod.forward(mx.io.DataBatch(data=[mx.nd.ones((4, 10))], label=None),
                is_train=False)
    mod.forward(mx.io.DataBatch(data=[mx.nd.ones((8, 10))], label=None),
                is_train=False)
    site = dict(site="executor.forward")
    assert telemetry.get_metric("jit_retraces_total", **site).value == 1
    assert telemetry.get_metric("jit_compiles_total", **site).value == 2
    info = xla_stats.last_retrace()
    assert info is not None and info["site"] == "executor.forward"
    assert "'data'" in info["reason"]
    assert "dim 0" in info["reason"] and "4 -> 8" in info["reason"]
    # the unlabeled totals advanced too (what the Prometheus snapshot
    # acceptance reads)
    assert telemetry.counter("jit_retraces_total").value >= 1
    assert telemetry.counter("jit_compiles_total").value >= 2


def test_unrelated_models_do_not_cross_retrace(fresh):
    """Two independent models hitting the same jit site are separate
    lineages: the second model's first compile is a compile, NOT a
    retrace diffed against the first model's signature."""
    _fc_module().forward(
        mx.io.DataBatch(data=[mx.nd.ones((4, 10))], label=None),
        is_train=False)
    data = mx.sym.var("data")
    other = mx.sym.FullyConnected(data, num_hidden=7, name="other_fc")
    mod2 = mx.mod.Module(other, label_names=None, context=mx.cpu())
    mod2.bind(data_shapes=[("data", (2, 6))], for_training=False)
    mod2.init_params()
    mod2.forward(mx.io.DataBatch(data=[mx.nd.ones((2, 6))], label=None),
                 is_train=False)
    site = dict(site="executor.forward")
    assert telemetry.get_metric("jit_compiles_total", **site).value == 2
    retr = telemetry.get_metric("jit_retraces_total", **site)
    assert retr is None or retr.value == 0
    assert xla_stats.last_retrace() is None


def test_static_arg_and_dtype_changes_explained(fresh):
    """The explainer covers static-arg flips and dtype changes, not just
    shapes (executor.forward's is_train flag is static)."""
    mod = _fc_module(for_training=True)
    batch = mx.io.DataBatch(data=[mx.nd.ones((4, 10))], label=None)
    mod.forward(batch, is_train=False)
    mod.forward(batch, is_train=True)
    info = xla_stats.last_retrace()
    assert info["site"] == "executor.forward"
    assert "static" in info["reason"]
    assert "False" in info["reason"] and "True" in info["reason"]


def test_tracked_jit_inside_trace_falls_through(fresh):
    """A tracked function called under an outer trace (tracer inputs)
    must not try to AOT-dispatch — gluon's vjp path depends on this."""
    import jax
    import jax.numpy as jnp
    tj = xla_stats.tracked_jit(lambda x: x * 2, "test.site")
    out = jax.jit(lambda x: tj(x) + 1)(jnp.ones(3))
    np.testing.assert_allclose(np.asarray(out), 3.0)
    # the outer jit traced through: no tracked compile happened
    assert telemetry.get_metric("jit_compiles_total",
                                site="test.site") is None
    np.testing.assert_allclose(np.asarray(tj(jnp.ones(3))), 2.0)
    assert telemetry.get_metric("jit_compiles_total",
                                site="test.site").value == 1


def test_gluon_hybridize_compile_accounting(fresh):
    from mxnet_tpu.gluon import nn
    net = nn.Dense(3, in_units=5)
    net.initialize()
    net.hybridize()
    x = mx.nd.ones((2, 5))
    for _ in range(3):
        net(x)
    site = dict(site="gluon.hybrid_forward")
    assert telemetry.get_metric("jit_compiles_total", **site).value == 1
    assert telemetry.get_metric("jit_cache_hits_total", **site).value == 2


# ---------------------------------------------------------------------------
# Memory ledger (tentpole 2) + profiler satellite
# ---------------------------------------------------------------------------

def test_memory_ledger_params_and_activations(fresh):
    mod = _fc_module(for_training=True)
    led = xla_stats.ledger()
    # bind recorded the module's parameter and gradient bytes
    assert led[("fc", "params")] == (10 * 4 + 4) * 4
    assert led[("fc", "grads")] == (10 * 4 + 4) * 4
    # a compile records the executable's temp/output bytes under its site
    mod.forward(mx.io.DataBatch(data=[mx.nd.ones((4, 10))], label=None),
                is_train=False)
    led = xla_stats.ledger()
    assert ("executor.forward", "xla_output") in led
    # gauges exist for Prometheus
    assert telemetry.get_metric("memory_ledger_bytes", scope="fc",
                                section="params").value > 0
    report = xla_stats.memory_report()
    assert "params" in report and "fc" in report
    assert "Live device buffers" in report


def test_device_memory_estimated_from_live_buffers_on_cpu(fresh):
    """CPU backends have no memory_stats(): the ledger reports, per
    device, the bytes of the live buffers there (``estimated: True``),
    never skipping a device or raising, so the Prometheus series stay
    continuous and memprof sees real numbers on the CPU mesh."""
    import jax
    held = mx.nd.ones((256, 256))          # 256 KiB on cpu(0)
    held.wait_to_read()
    dev0 = str(mx.cpu(0).jax_device())
    recs = xla_stats.device_memory()
    assert len(recs) == len(jax.devices())
    assert all(r["estimated"] for r in recs)
    by_dev = {r["device"]: r for r in recs}
    assert by_dev[dev0]["bytes_in_use"] >= held._data.nbytes
    for r in recs:
        assert r["peak_bytes_in_use"] >= r["bytes_in_use"] >= 0
        g = telemetry.get_metric("hbm_bytes_in_use", device=r["device"])
        assert g is not None and g.value == r["bytes_in_use"]
    from mxnet_tpu import profiler
    lines = profiler._device_memory_lines()
    assert len(lines) == min(8, len(recs))
    assert all("bytes_in_use=" in l for l in lines)


def test_profiler_memory_section_includes_device_lines(fresh):
    from mxnet_tpu import profiler
    profiler.set_config(aggregate_stats=True, profile_memory=True)
    profiler.reset_stats()
    try:
        (mx.nd.ones((8, 8)) + 1).asnumpy()
        table = profiler.dumps()
        assert "Backend allocator (PJRT memory_stats)." in table
        assert "bytes_in_use=" in table
    finally:
        profiler.set_config(aggregate_stats=False, profile_memory=False)
        profiler.reset_stats()


def test_optimizer_bytes_ledgered_after_update(fresh):
    data = mx.sym.var("data")
    fc = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    net = mx.sym.SoftmaxOutput(fc, name="softmax")
    x = np.random.RandomState(0).uniform(size=(32, 10)).astype(np.float32)
    y = np.zeros(32, dtype=np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=16)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=1, eval_metric="acc",
            optimizer_params=(("learning_rate", 0.01),
                              ("momentum", 0.9)))
    led = xla_stats.ledger()
    key = (mod._ledger_scope(), "optimizer")
    assert key in led and led[key] > 0  # momentum buffers


# ---------------------------------------------------------------------------
# Goodput / MFU (tentpole 3)
# ---------------------------------------------------------------------------

def test_mfu_gauges_from_fit(fresh, monkeypatch):
    monkeypatch.setenv("MXNET_PEAK_FLOPS", "1e12")
    data = mx.sym.var("data")
    fc = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    net = mx.sym.SoftmaxOutput(fc, name="softmax")
    x = np.random.RandomState(0).uniform(size=(64, 10)).astype(np.float32)
    y = np.zeros(64, dtype=np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=16)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=1, eval_metric="acc")
    assert xla_stats.flops_per_batch() > 0
    g = xla_stats.goodput(batches=8, elapsed=0.5)
    assert g is not None and g["model_flops_per_second"] > 0
    assert g["mfu"] == pytest.approx(
        g["model_flops_per_second"] / xla_stats.peak_flops_total())
    text = telemetry.dumps()
    assert "\nmfu " in text or "\nmfu{" in text
    assert "model_flops_per_second" in text
    assert telemetry.counter("model_flops_total").value > 0


def test_peak_flops_env_override_and_table(monkeypatch):
    monkeypatch.setenv("MXNET_PEAK_FLOPS", "2.5e13")
    assert xla_stats.peak_flops_per_device() == 2.5e13
    monkeypatch.delenv("MXNET_PEAK_FLOPS")
    # unknown device kind (cpu) -> 0, and mfu_of degrades to 0
    assert xla_stats.peak_flops_per_device() == 0.0
    assert xla_stats.mfu_of(1e12) == 0.0


def test_speedometer_goodput_suffix(fresh, monkeypatch):
    monkeypatch.setenv("MXNET_PEAK_FLOPS", "1e9")
    xla_stats.note_train_step(1000.0, batches=1)
    sp = mx.callback.Speedometer(batch_size=16, frequent=4)
    sp._mark()
    telemetry.counter("fit_batches_total").inc(100)
    telemetry.counter("fit_samples_total").inc(1600)
    time.sleep(0.02)
    suffix = sp._goodput_suffix()
    assert "mfu" in suffix and "model FLOP/s" in suffix
    # no FLOPs figure -> empty suffix, reference log format untouched
    xla_stats.reset()
    assert sp._goodput_suffix() == ""


# ---------------------------------------------------------------------------
# Monitor satellite
# ---------------------------------------------------------------------------

def test_monitor_install_dedupes_and_counts(fresh):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc")
    exe = net.simple_bind(mx.cpu(), data=(4, 16))
    mon = mx.monitor.Monitor(interval=1, pattern=".*fc.*")
    for _ in range(3):   # repeated fit calls re-install the monitor
        mon.install(exe)
    assert len(mon.exes) == 1
    mon.tic()
    exe.forward()
    stats = mon.toc()
    assert stats
    c = telemetry.get_metric("monitor_stats_total")
    assert c is not None and c.value == len(stats)


# ---------------------------------------------------------------------------
# Bench gate satellite
# ---------------------------------------------------------------------------

def _write_history(d, value=100.0):
    rec = {"metric": bench_gate.TRAIN_METRIC, "value": value,
           "unit": "img/s"}
    with open(os.path.join(d, "BENCH_r01.json"), "w") as fh:
        json.dump({"n": 1, "parsed": rec,
                   "tail": json.dumps(rec) + "\n"}, fh)


def test_bench_gate_pass_and_fail(tmp_path):
    d = str(tmp_path)
    _write_history(d, 100.0)
    ok = [{"metric": bench_gate.TRAIN_METRIC, "value": 95.0}]
    bad = [{"metric": bench_gate.TRAIN_METRIC, "value": 80.0}]
    assert bench_gate.gate_records(ok, history_dir=d) == 0
    assert bench_gate.gate_records(bad, history_dir=d) == 1
    # threshold is honored
    assert bench_gate.gate_records(bad, history_dir=d,
                                   threshold=0.25) == 0
    # a cpu-platform run regressing vs accelerator history skips...
    cpu = [{"metric": bench_gate.TRAIN_METRIC, "value": 8.0,
            "platform": "cpu"}]
    assert bench_gate.gate_records(cpu, history_dir=d) == 0
    # ...unless strict
    assert bench_gate.gate_records(cpu, history_dir=d, strict=True) == 1


def test_bench_gate_missing_metric_or_history(tmp_path):
    d = str(tmp_path)
    # no history at all -> nothing to gate -> pass (strict fails)
    recs = [{"metric": bench_gate.TRAIN_METRIC, "value": 50.0}]
    assert bench_gate.gate_records(recs, history_dir=d) == 0
    assert bench_gate.gate_records(recs, history_dir=d, strict=True) == 1
    _write_history(d, 100.0)
    assert bench_gate.gate_records([], history_dir=d) == 0
    # infer-only runs gate the inference headline instead
    _write_history(d, 100.0)
    infer_hist = {"metric": bench_gate.INFER_METRIC, "value": 200.0}
    with open(os.path.join(d, "BENCH_r02.json"), "w") as fh:
        json.dump({"parsed": infer_hist}, fh)
    assert bench_gate.gate_records(
        [{"metric": bench_gate.INFER_METRIC, "value": 195.0}],
        history_dir=d) == 0
    assert bench_gate.gate_records(
        [{"metric": bench_gate.INFER_METRIC, "value": 100.0}],
        history_dir=d) == 1


def test_bench_gate_cli_reads_repo_history(tmp_path):
    """The CLI form the acceptance criterion runs: a fresh-run file at
    the recorded best passes against the repo's checked-in BENCH_*.json
    history."""
    hist = bench_gate.load_history(REPO)
    assert bench_gate.TRAIN_METRIC in hist  # real rounds are parseable
    best = hist[bench_gate.TRAIN_METRIC][0][0]
    run = tmp_path / "run.jsonl"
    run.write_text("noise line\n" + json.dumps(
        {"metric": bench_gate.TRAIN_METRIC, "value": best, "unit": "img/s"})
        + "\n")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_gate.py"),
         str(run)], capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    assert '"status": "pass"' in r.stdout


# ---------------------------------------------------------------------------
# Flight recorder (tentpole 4)
# ---------------------------------------------------------------------------

def test_flight_recorder_ring_and_dump(fresh):
    telemetry.event("alpha", k=1)
    with telemetry.span("beta"):
        pass
    path = xla_stats.flight_recorder.dump(reason="unit")
    assert path and os.path.basename(path).startswith(
        "flightrecorder-host")
    doc = json.load(open(path))
    assert doc["reason"] == "unit" and doc["pid"] == os.getpid()
    names = [e["name"] for e in doc["events"]]
    assert "alpha" in names and "beta" in names
    assert isinstance(doc["metrics"], dict)
    assert telemetry.counter("flightrecorder_dumps_total").value == 1


def test_flight_recorder_ring_is_bounded(fresh):
    rec = xla_stats.FlightRecorder(maxlen=16)
    for i in range(100):
        rec.record({"name": "e%d" % i})
    evs = rec.events()
    assert len(evs) == 16 and evs[-1]["name"] == "e99"


def test_flight_recorder_records_without_telemetry_dir():
    telemetry.configure(None)
    telemetry.reset()
    xla_stats.reset()
    try:
        telemetry.event("quiet.crash.context")
        names = [e["name"] for e in xla_stats.flight_recorder.events()]
        assert "quiet.crash.context" in names
        # but with no dir configured a dump has nowhere to go
        env_dir = os.environ.pop("MXNET_TELEMETRY_DIR", None)
        try:
            assert xla_stats.flight_recorder.dump(reason="x") is None
        finally:
            if env_dir is not None:
                os.environ["MXNET_TELEMETRY_DIR"] = env_dir
    finally:
        xla_stats.reset()
        telemetry.reset()


def test_fit_exception_dumps_flight_recorder(fresh):
    data = mx.sym.var("data")
    fc = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    net = mx.sym.SoftmaxOutput(fc, name="softmax")
    x = np.zeros((32, 10), dtype=np.float32)
    y = np.zeros(32, dtype=np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=16)
    mod = mx.mod.Module(net, context=mx.cpu())

    boom = mx.callback.Speedometer(16, frequent=1)

    def exploding_callback(param):
        raise RuntimeError("injected callback failure")

    with pytest.raises(RuntimeError, match="injected callback failure"):
        mod.fit(it, num_epoch=1, eval_metric="acc",
                batch_end_callback=[boom, exploding_callback])
    path = os.path.join(fresh, "flightrecorder-host%d.json"
                        % telemetry.host_id())
    doc = json.load(open(path))
    assert doc["reason"] == "fit_exception"
    assert "injected callback failure" in doc["error"]


# ---------------------------------------------------------------------------
# Acceptance: launched chaos-kill run leaves a parseable flight record
# whose last event precedes (is) the injected fault
# ---------------------------------------------------------------------------

FLIGHT_WORKER = r"""
import sys
import jax.numpy as jnp
from mxnet_tpu import telemetry
from mxnet_tpu.parallel import elastic

def step_fn(state, step):
    telemetry.event("worker.step", i=step)
    return {"w": state["w"] + 1.0}

t = elastic.ElasticTrainer(step_fn, {"w": jnp.zeros(2)},
                           dead_node_timeout=None)
t.run(10)   # chaos worker.death@3 fires at the 4th step boundary
print("UNREACHABLE", flush=True)
"""


@pytest.mark.launched
@pytest.mark.timeout(120)
def test_launched_chaos_kill_leaves_flight_record(tmp_path):
    from mxnet_tpu import chaos
    worker = tmp_path / "worker.py"
    worker.write_text(FLIGHT_WORKER)
    teldir = str(tmp_path / "telemetry")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO, MXNET_TELEMETRY_DIR=teldir,
               MXNET_CHAOS="worker.death@3")
    p = subprocess.Popen([sys.executable, str(worker)], env=env,
                         stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
    out, _ = launchutil.communicate(p)
    assert p.returncode == chaos.DEAD_EXIT_CODE, out[-4000:]
    assert "UNREACHABLE" not in out

    path = os.path.join(teldir, "flightrecorder-host0.json")
    assert os.path.exists(path), os.listdir(teldir)
    doc = json.load(open(path))
    assert doc["reason"] == "chaos.worker.death"
    events = doc["events"]
    assert events, "flight record carries no events"
    # the ring's last entry IS the injected fault; everything else
    # precedes it, and only steps 0..2 ran before the step-4 boundary
    last = events[-1]
    assert last["name"] == "chaos.injection"
    assert last["args"]["site"] == "worker.death"
    steps = [e["args"]["i"] for e in events if e["name"] == "worker.step"]
    assert steps == [0, 1, 2]
    assert all(e["mono"] <= last["mono"] for e in events)
    assert doc["dumped_mono"] >= last["mono"]
    # the post-mortem carries the registry too
    assert "chaos_injections_total" in doc["metrics"]
