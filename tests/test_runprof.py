"""Run anatomy (`mxnet_tpu/runprof.py`): the goodput/badput ledger
(taxonomy tiles the run wall), training-health sentinels (non-finite
values, step-time spikes, loss plateau/divergence) with flight-recorder
dumps, lost-work accounting across restarts, the report CLI with
per-host goodput skew, the bench_gate goodput gate with its state-
seconds delta line, zero-compile instrumentation proof, and a launched
chaos kill-and-resume run whose ledger shows measured recovery +
checkpoint_restore + lost-work badput.
"""
import io as _io
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import runprof, stepprof, telemetry, xla_stats

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import launchutil  # noqa: E402


@pytest.fixture
def fresh():
    """Clean registry + reset run ledger and step profiler."""
    telemetry.reset()
    stepprof.reset()
    runprof.reset()
    yield
    runprof.reset()
    stepprof.reset()
    telemetry.reset()


# ---------------------------------------------------------------------------
# Ledger: taxonomy tiles the run wall
# ---------------------------------------------------------------------------

def test_taxonomy_tiles_run_wall(fresh):
    led = runprof.RunLedger(window=32)
    time.sleep(0.03)                       # -> init
    led.note_state("compile", 0.0)         # zero-cost note is fine
    for _ in range(6):
        t0 = time.perf_counter()
        time.sleep(0.008)
        led.note_step({"data_wait": 0.002},
                      time.perf_counter() - t0)
    time.sleep(0.02)                       # -> idle
    snap = led.snapshot()
    assert set(snap["states"]) == set(runprof.RUN_STATES)
    total = sum(snap["states"].values())
    wall = snap["run_wall_seconds"]
    assert total == pytest.approx(wall, rel=0.10)
    assert snap["states"]["init"] >= 0.02
    assert snap["states"]["idle"] >= 0.01
    assert snap["states"]["train_productive"] > 0
    assert snap["states"]["input_stall"] > 0
    assert 0 < snap["goodput_fraction"] < 1


def test_first_step_compile_does_not_deflate_init(fresh):
    """Compile paid INSIDE the first train step happens after the
    step's front edge: it must not be subtracted from the derived init
    residual (a minutes-long first compile would otherwise misfile the
    whole startup period as idle and flip the verdict)."""
    led = runprof.RunLedger(window=32)
    time.sleep(0.05)                  # true init
    t0 = time.perf_counter()
    time.sleep(0.03)                  # "compile inside the first step"
    dur = time.perf_counter() - t0
    led.note_state("compile", dur)
    led.note_step({}, dur)            # the step wall covers the compile
    snap = led.snapshot()
    assert snap["states"]["init"] >= 0.04
    assert snap["states"]["idle"] <= 0.02


def test_explicit_state_validation(fresh):
    led = runprof.RunLedger()
    with pytest.raises(ValueError, match="derived"):
        led.note_state("idle", 1.0)
    with pytest.raises(ValueError, match="taxonomy"):
        led.note_state("bogus", 1.0)


def test_state_counters_and_goodput_gauge(fresh):
    runprof.note_state("checkpoint_save", 0.001)
    c = telemetry.get_metric("run_state_seconds", state="checkpoint_save")
    assert c is not None and c.value == pytest.approx(0.001)
    time.sleep(0.02)   # un-tiled wall -> derived init grows
    snap = runprof.snapshot()
    g = telemetry.get_metric("run_goodput_fraction")
    assert g is not None
    assert g.read() == pytest.approx(snap["goodput_fraction"], abs=0.05)
    # derived counters published monotonically by snapshot()
    init_c = telemetry.get_metric("run_state_seconds", state="init")
    assert init_c is not None and init_c.value > 0
    v1 = init_c.value
    time.sleep(0.01)
    runprof.snapshot()
    assert init_c.value > v1


def test_run_state_spans_land_in_event_log(fresh, tmp_path):
    telemetry.configure(str(tmp_path))
    try:
        runprof.note_state("checkpoint_save", 0.05, step=3)
        path = os.path.join(
            str(tmp_path),
            "events_host%d_pid%d.jsonl" % (telemetry.host_id(),
                                           os.getpid()))
        events = telemetry.read_events(path)
    finally:
        telemetry.configure(None)
    spans = [e for e in events if e.get("name") == "run.checkpoint_save"]
    assert spans and spans[0]["ph"] == "X"
    assert spans[0]["dur"] == pytest.approx(0.05)
    assert spans[0]["args"]["step"] == 3


def test_disabled_is_noop(fresh, monkeypatch):
    monkeypatch.setenv("MXNET_RUNPROF", "0")
    runprof.note_state("compile", 1.0)
    runprof.note_step({}, 1.0)
    runprof.observe_metric("loss", float("nan"))
    assert runprof.state_seconds("compile") == 0.0
    assert not runprof.should_check()
    assert telemetry.get_metric("run_anomalies_total",
                                kind="nonfinite_loss") is None


# ---------------------------------------------------------------------------
# Sentinels
# ---------------------------------------------------------------------------

def test_nonfinite_loss_sentinel_dumps_flight_recorder(fresh, tmp_path):
    telemetry.configure(str(tmp_path))
    try:
        runprof.observe_metric("cross-entropy-loss", float("nan"))
    finally:
        telemetry.configure(None)
    c = telemetry.get_metric("run_anomalies_total", kind="nonfinite_loss")
    assert c is not None and c.value == 1
    dump = os.path.join(str(tmp_path),
                        "flightrecorder-host%d.json" % telemetry.host_id())
    assert os.path.exists(dump)
    doc = json.load(open(dump))
    assert doc["reason"] == "runprof.nonfinite_loss"
    snap = runprof.snapshot()
    assert snap["anomaly_counts"] == {"nonfinite_loss": 1}
    assert snap["anomalies"][-1]["kind"] == "nonfinite_loss"


def test_nonfinite_metric_vs_loss_kinds(fresh):
    runprof.observe_metric("accuracy", float("inf"))
    runprof.observe_metric("perplexity", float("nan"))
    assert telemetry.get_metric("run_anomalies_total",
                                kind="nonfinite_metric").value == 1
    assert telemetry.get_metric("run_anomalies_total",
                                kind="nonfinite_loss").value == 1


def test_halt_env_raises_after_counting(fresh, monkeypatch):
    monkeypatch.setenv("MXNET_RUNPROF_HALT", "1")
    with pytest.raises(runprof.RunHealthError, match="nonfinite_loss"):
        runprof.observe_metric("loss", float("nan"))
    c = telemetry.get_metric("run_anomalies_total", kind="nonfinite_loss")
    assert c is not None and c.value == 1   # counted before the halt


def test_halt_inside_step_fn_propagates_not_recovers(fresh, monkeypatch):
    """A sentinel halt raised INSIDE an elastic step_fn is a verdict,
    not a worker failure: it must escape the recover/exit machinery
    instead of burning the restart budget re-tripping itself."""
    monkeypatch.setenv("MXNET_RUNPROF_HALT", "1")
    import jax.numpy as jnp
    from mxnet_tpu.parallel import elastic

    def step_fn(state, step):
        if step == 1:
            runprof.note_anomaly("test_halt", dump=False)
        return state

    t = elastic.ElasticTrainer(step_fn, {"w": jnp.zeros(2)},
                               dead_node_timeout=None,
                               on_failure="recover")
    with pytest.raises(runprof.RunHealthError):
        t.run(3)
    assert t.restarts_used == 0   # no recovery cycle was entered


def test_step_time_spike_sentinel(fresh):
    led = runprof.RunLedger(window=32)
    for _ in range(10):
        led.note_step({}, 0.01)
    led.note_step({}, 0.5)   # > 4x the 0.01 median
    snap = led.snapshot()
    assert snap["anomaly_counts"].get("step_time_spike") == 1
    # steady steps never accuse anyone
    led2 = runprof.RunLedger(window=32)
    for _ in range(20):
        led2.note_step({}, 0.01)
    assert "step_time_spike" not in led2.snapshot()["anomaly_counts"]


def test_loss_divergence_sentinel(fresh):
    led = runprof.RunLedger(window=16)
    for v in [1.0, 0.8, 0.6, 0.5, 0.5, 0.5, 0.5, 0.5]:
        led.observe_metric("loss", v)
    for v in [1.2, 1.5, 2.0, 2.5, 3.0, 3.5, 4.0, 4.5]:
        led.observe_metric("loss", v)
    assert led.snapshot()["anomaly_counts"].get("loss_divergence") == 1


def test_loss_windows_are_per_metric(fresh):
    """Two healthy loss-like metrics at different scales must not read
    their interleaving as a divergence."""
    led = runprof.RunLedger(window=16)
    for i in range(16):
        led.observe_metric("nll-loss", 2.3 - 0.01 * i)
        led.observe_metric("perplexity", 10.0 - 0.05 * i)
    assert led.snapshot()["anomaly_counts"] == {}


def test_loss_plateau_sentinel(fresh):
    led = runprof.RunLedger(window=16)
    for _ in range(16):
        led.observe_metric("loss", 0.7)
    assert led.snapshot()["anomaly_counts"].get("loss_plateau") == 1
    # a healthily-declining loss trips neither heuristic
    led2 = runprof.RunLedger(window=16)
    for i in range(16):
        led2.observe_metric("loss", 1.0 - 0.05 * i)
    assert led2.snapshot()["anomaly_counts"] == {}


def test_clip_global_norm_counts_nonfinite(fresh):
    from mxnet_tpu.gluon.utils import clip_global_norm
    a = mx.nd.array(np.array([np.inf, 1.0], dtype=np.float32))
    with pytest.warns(UserWarning, match="nan or inf"):
        clip_global_norm([a], 1.0)
    assert telemetry.get_metric("grad_nonfinite_total").value == 1
    assert telemetry.get_metric("run_anomalies_total",
                                kind="nonfinite_grad_norm").value == 1
    # a finite norm counts nothing
    b = mx.nd.array(np.ones(4, dtype=np.float32))
    clip_global_norm([b], 1.0)
    assert telemetry.get_metric("grad_nonfinite_total").value == 1


def test_monitor_nan_count_stat_and_routing(fresh):
    from mxnet_tpu import monitor as monitor_mod
    bad = mx.nd.array(np.array([np.nan, 1.0, np.inf], dtype=np.float32))
    assert float(monitor_mod.nan_count(bad).asscalar()) == 2.0
    ok = mx.nd.array(np.ones(3, dtype=np.float32))
    assert float(monitor_mod.nan_count(ok).asscalar()) == 0.0
    # a Monitor using nan_count routes nonzero counts into the sentinel
    m = monitor_mod.Monitor(1, stat_func=monitor_mod.nan_count)
    m.activated = True
    m.queue = [(0, "fc_weight", monitor_mod.nan_count(bad))]
    res = m.toc()
    assert len(res) == 1
    assert telemetry.get_metric("run_anomalies_total",
                                kind="nonfinite_tensor").value == 1
    # the default value stat routes a non-finite result the same way
    m2 = monitor_mod.Monitor(1)
    m2.activated = True
    m2.queue = [(0, "fc_weight", m2.stat_func(bad))]
    m2.toc()
    assert telemetry.get_metric("run_anomalies_total",
                                kind="nonfinite_tensor").value == 2


def test_fit_loop_sampled_health_check(fresh, monkeypatch):
    monkeypatch.setenv("MXNET_RUNPROF_CHECK_EVERY", "2")
    data = mx.sym.var("data")
    fc = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    net = mx.sym.SoftmaxOutput(fc, name="softmax")
    x = np.random.RandomState(0).uniform(size=(64, 10)).astype(np.float32)
    y = np.zeros(64, dtype=np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=16)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=2, eval_metric="acc")
    snap = runprof.snapshot()
    # the fit trained: productive seconds recorded, goodput sane, and a
    # healthy accuracy metric tripped nothing
    assert snap["states"]["train_productive"] > 0
    assert 0 < snap["goodput_fraction"] <= 1
    assert snap["anomaly_counts"] == {}
    assert snap["steps"] == 8


# ---------------------------------------------------------------------------
# Compile / checkpoint / recovery states + zero-compile instrumentation
# ---------------------------------------------------------------------------

def test_compile_feeds_ledger_and_instrumentation_is_free(fresh):
    import jax.numpy as jnp
    from mxnet_tpu import compiled
    compiled.reset()
    prog = compiled.tracked_jit(lambda v: v + 1, "runprof.test")
    prog(jnp.ones((4,), jnp.float32))
    assert runprof.state_seconds("compile") > 0
    c = telemetry.get_metric("run_state_seconds", state="compile")
    assert c is not None and c.value > 0
    # exercising the whole runprof surface compiles NOTHING
    before = xla_stats.compile_counts()
    for _ in range(16):
        runprof.note_step({"data_wait": 0.001}, 0.01)
    runprof.note_state("checkpoint_save", 0.01)
    runprof.observe_metric("loss", 0.5)
    runprof.snapshot()
    buf = _io.StringIO()
    runprof.report(out=buf)
    assert xla_stats.compile_counts() == before


def test_checkpointer_feeds_save_restore_states(fresh, tmp_path):
    import jax.numpy as jnp
    from mxnet_tpu.parallel.checkpoint import abstract_like
    from mxnet_tpu.parallel.elastic import ElasticCheckpointer
    tree = {"w": jnp.zeros((4,), jnp.float32)}
    ck = ElasticCheckpointer(str(tmp_path / "ck"))
    ck.save(1, tree)
    assert runprof.state_seconds("checkpoint_save") > 0
    step, _ = ck.restore(abstract_like(tree))
    assert step == 1
    assert runprof.state_seconds("checkpoint_restore") > 0


def test_elastic_trainer_feeds_productive_and_recovery(fresh, tmp_path):
    import jax.numpy as jnp
    from mxnet_tpu.parallel import elastic
    from mxnet_tpu.parallel.retry import RetryPolicy
    failed = {"done": False}

    def step_fn(state, step):
        if step == 2 and not failed["done"]:
            failed["done"] = True
            raise RuntimeError("boom")
        time.sleep(0.005)
        return {"w": state["w"] + 1.0}

    t = elastic.ElasticTrainer(
        step_fn, {"w": jnp.zeros(2)}, ckpt_dir=str(tmp_path / "ck"),
        ckpt_every=2, on_failure="recover", dead_node_timeout=None,
        retry_policy=RetryPolicy(max_attempts=3, base_delay=0.01,
                                 max_delay=0.05))
    out = t.run(4)
    assert float(np.asarray(out["w"])[0]) == 4.0
    assert runprof.state_seconds("train_productive") >= 4 * 0.005
    assert runprof.state_seconds("checkpoint_save") > 0
    assert runprof.state_seconds("recovery") > 0
    # the recover cycle restored from step 2: restore booked separately
    assert runprof.state_seconds("checkpoint_restore") > 0
    snap = runprof.snapshot()
    assert snap["goodput_fraction"] < 1


# ---------------------------------------------------------------------------
# Lost work across restarts
# ---------------------------------------------------------------------------

def _write_progress(dir, host, pid, step, avg, scope=None):
    path = os.path.join(str(dir),
                        "runprof_progress_host%d_pid%d.json" % (host, pid))
    with open(path, "w") as fh:
        json.dump({"step": step, "avg_step_seconds": avg,
                   "scope": scope, "updated": time.time()}, fh)


def test_note_resume_books_lost_work(fresh, tmp_path):
    _write_progress(tmp_path, telemetry.host_id(), 99991, 12, 0.5)
    _write_progress(tmp_path, telemetry.host_id(), 99992, 9, 0.5)
    lost = runprof.note_resume(7, dir=str(tmp_path))
    assert lost == 5    # highest marker (12) minus the checkpoint (7)
    assert telemetry.get_metric("run_lost_steps_total").value == 5
    assert telemetry.get_metric("run_lost_work_seconds").value == \
        pytest.approx(2.5)
    snap = runprof.snapshot()
    assert snap["lost_steps"] == 5
    assert snap["lost_work_seconds"] == pytest.approx(2.5)
    assert snap["resumed_from"] == 7
    # the in-memory high-water clamps to the resumed step: the dead
    # crash point must not be re-persisted and re-booked next recovery
    assert snap["progress_step"] == 7
    # the markers were consumed at the resume that booked them: a
    # second resume from the same checkpoint cannot double-book
    assert runprof.note_resume(7, dir=str(tmp_path)) == 0
    assert telemetry.get_metric("run_lost_steps_total").value == 5


def test_note_progress_persists_marker(fresh, tmp_path):
    telemetry.configure(str(tmp_path))
    try:
        runprof.note_progress(3, step_seconds=0.1)
        # throttled: rapid-fire progress inside the 0.2s window lags...
        for s in range(4, 9):
            runprof.note_progress(s, step_seconds=0.1)
        # ...until the exit-path flush writes the high-water mark NOW
        runprof.flush_progress()
    finally:
        telemetry.configure(None)
    fns = [fn for fn in os.listdir(str(tmp_path))
           if fn.startswith("runprof_progress_host")]
    assert len(fns) == 1
    doc = json.load(open(os.path.join(str(tmp_path), fns[0])))
    assert doc["step"] == 8
    assert doc["avg_step_seconds"] == pytest.approx(0.1)
    # a marker without a mean prices lost steps at zero, not wrongly
    _write_progress(tmp_path, telemetry.host_id(), 77001, 20, None)
    assert runprof.note_resume(15, dir=str(tmp_path)) == 5
    assert telemetry.get_metric("run_lost_steps_total").value == 5
    assert telemetry.get_metric("run_lost_work_seconds") is None
    # an OTHER run's marker (different scope) in the same telemetry dir
    # is invisible to this run's resume — and left on disk for its owner
    _write_progress(tmp_path, telemetry.host_id(), 77002, 40, 0.5,
                    scope="/ck/other-run")
    assert runprof.note_resume(15, dir=str(tmp_path),
                               scope="/ck/this-run") == 0
    assert telemetry.get_metric("run_lost_steps_total").value == 5
    assert len(os.listdir(str(tmp_path))) == 1   # other marker survives


# ---------------------------------------------------------------------------
# Verdicts
# ---------------------------------------------------------------------------

def _states(**kv):
    st = {s: 0.0 for s in runprof.RUN_STATES}
    st.update(kv)
    return st


@pytest.mark.parametrize("states,expect", [
    (_states(train_productive=9.5, idle=0.5), "healthy"),
    (_states(train_productive=2.0, compile=6.0), "compile-heavy"),
    (_states(train_productive=2.0, checkpoint_save=5.0),
     "checkpoint-heavy"),
    (_states(train_productive=2.0, checkpoint_restore=5.0),
     "checkpoint-heavy"),
    (_states(train_productive=2.0, recovery=5.0), "recovery-heavy"),
    (_states(train_productive=2.0, input_stall=5.0), "input-bound"),
    (_states(train_productive=2.0, idle=5.0), "idle-heavy"),
    (_states(train_productive=1.0, init=5.0), "init-heavy"),
])
def test_verdict_classes(states, expect):
    verdict, hint = runprof.classify(states)
    assert verdict == expect
    assert hint == runprof.HINTS[expect]


def test_verdict_unknown_and_anomaly_hint():
    assert runprof.classify({})[0] == "unknown"
    v, hint = runprof.classify(_states(train_productive=10.0),
                               anomaly_counts={"nonfinite_loss": 2})
    assert v == "healthy"
    assert "nonfinite_loss x2" in hint and "flight-recorder" in hint


# ---------------------------------------------------------------------------
# Snapshots, merge, skew, report
# ---------------------------------------------------------------------------

def _host_snapshot(dir, host, pid, productive, wall, lost=0,
                   anomalies=None, incarnation=0):
    doc = {"host": host, "pid": pid, "updated": time.time(),
           "incarnation": incarnation,
           "run_wall_seconds": wall, "steps": 10,
           "lost_steps": lost, "lost_work_seconds": lost * 0.2,
           "anomaly_counts": anomalies or {}, "anomalies": [],
           "states": _states(train_productive=productive,
                             idle=wall - productive),
           "goodput_fraction": productive / wall}
    with open(os.path.join(str(dir), "runprof_i%d_host%d_pid%d.json"
                           % (incarnation, host, pid)), "w") as fh:
        json.dump(doc, fh)


def test_merge_keeps_every_incarnation_and_skew(fresh, tmp_path):
    # host 0: a crashed incarnation and its replacement REUSING the pid
    # (the k8s pid-1 case) — the incarnation in filename + key keeps
    # both snapshots
    _host_snapshot(tmp_path, 0, 100, productive=4.0, wall=5.0)
    _host_snapshot(tmp_path, 0, 100, productive=4.0, wall=5.0, lost=2,
                   incarnation=1)
    # host 1: one slow incarnation
    _host_snapshot(tmp_path, 1, 200, productive=2.0, wall=5.0,
                   anomalies={"step_time_spike": 1})
    # torn file from a killed writer is skipped, not fatal
    with open(os.path.join(str(tmp_path),
                           "runprof_host9_pid9.json"), "w") as fh:
        fh.write("{torn")
    # a non-training snapshot (the supervise() launcher) contributes
    # its recovery badput but NOT its wall/init — a launcher that sat
    # idle all run must not deflate merged goodput into init-heavy
    sup = {"host": 0, "pid": 999, "updated": time.time(),
           "incarnation": 0, "run_wall_seconds": 60.0, "steps": 0,
           "lost_steps": 0, "lost_work_seconds": 0.0,
           "anomaly_counts": {}, "anomalies": [],
           "states": _states(recovery=1.5, init=58.5),
           "goodput_fraction": 0.0}
    with open(os.path.join(str(tmp_path),
                           "runprof_i0_host0_pid999.json"), "w") as fh:
        json.dump(sup, fh)
    merged = runprof.merge_host_snapshots(str(tmp_path))
    assert set(merged) == {(0, 100, 0), (0, 100, 1), (1, 200, 0),
                           (0, 999, 0)}
    agg = runprof.aggregate(merged.values())
    assert agg["lost_steps"] == 2
    assert agg["run_wall_seconds"] == pytest.approx(15.0)
    assert agg["goodput_fraction"] == pytest.approx(10.0 / 15.0)
    assert agg["states"]["recovery"] == pytest.approx(1.5)
    assert agg["states"]["init"] == pytest.approx(0.0)
    skew = runprof.goodput_by_host(merged)
    assert skew["slowest"] == 1
    assert skew["skew"] == pytest.approx(0.8 - 0.4)
    g = telemetry.get_metric("run_goodput_skew")
    assert g is not None and g.read() == pytest.approx(0.4)


def test_report_renders_waterfall_lost_work_and_skew(fresh, tmp_path):
    _host_snapshot(tmp_path, 0, 100, productive=4.0, wall=5.0, lost=3,
                   anomalies={"nonfinite_loss": 1})
    _host_snapshot(tmp_path, 1, 200, productive=2.0, wall=5.0)
    buf = _io.StringIO()
    rc = runprof.report(str(tmp_path), out=buf)
    text = buf.getvalue()
    assert rc == 0
    assert "train_productive" in text and "lost work: 3 step(s)" in text
    assert "nonfinite_loss x1" in text
    assert "hosts: 2" in text and "slowest host 1" in text
    rec = json.loads(text.strip().splitlines()[-1])
    assert rec["metric"] == "runprof_report"
    assert rec["lost_steps"] == 3
    assert rec["goodput_fraction"] == pytest.approx(0.6)
    assert rec["goodput_skew"] == pytest.approx(0.4)
    assert rec["slowest_host"] == 1


def test_report_single_snapshot_file_and_empty_dir(fresh, tmp_path):
    _host_snapshot(tmp_path, 0, 100, productive=1.0, wall=10.0)
    path = os.path.join(str(tmp_path), "runprof_i0_host0_pid100.json")
    buf = _io.StringIO()
    assert runprof.report(path, out=buf) == 0
    rec = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert rec["verdict"] == "idle-heavy"
    empty = tmp_path / "empty"
    empty.mkdir()
    buf = _io.StringIO()
    assert runprof.report(str(empty), out=buf) == 1


def test_report_cli_subprocess(tmp_path):
    _host_snapshot(tmp_path, 0, 100, productive=9.0, wall=10.0)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.Popen(
        [sys.executable, "-m", "mxnet_tpu.runprof", "report",
         str(tmp_path), "--json"],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    out, err = launchutil.communicate(proc)
    assert proc.returncode == 0, out + err
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["metric"] == "runprof_report"
    assert rec["verdict"] == "healthy"


# ---------------------------------------------------------------------------
# Speedometer goodput suffix (gated by MXNET_STEPPROF)
# ---------------------------------------------------------------------------

def test_speedometer_goodput_suffix_gated(fresh):
    sp = mx.callback.Speedometer(batch_size=16, frequent=4)
    sp._mark()
    t0 = time.perf_counter()
    time.sleep(0.02)
    runprof.note_step({}, time.perf_counter() - t0)
    assert sp._runprof_suffix() == ""     # disabled: no suffix
    stepprof.enable()
    try:
        suffix = sp._runprof_suffix()
        assert suffix.startswith("\tgoodput ") and suffix.endswith("%")
        sp._mark()
        assert sp._runprof_suffix() == ""  # nothing advanced since mark
    finally:
        stepprof.disable()


# ---------------------------------------------------------------------------
# bench_gate: the goodput gate + state-seconds delta line
# ---------------------------------------------------------------------------

def test_bench_gate_goodput_regression_prints_state_deltas(fresh,
                                                           tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import bench_gate
    hist = {"parsed": {
        "metric": bench_gate.TRAIN_METRIC, "value": 2800.0,
        "goodput_fraction": 0.95,
        "run_states": {"train_productive": 9.5, "compile": 0.2}}}
    with open(str(tmp_path / "BENCH_r01.json"), "w") as fh:
        json.dump(hist, fh)
    run = [{"metric": bench_gate.TRAIN_METRIC, "value": 2800.0,
            "goodput_fraction": 0.6,
            "run_states": {"train_productive": 6.0, "compile": 0.2,
                           "checkpoint_save": 3.5}}]
    buf = _io.StringIO()
    rc = bench_gate.gate_records(run, history_dir=str(tmp_path),
                                 metric=bench_gate.GOODPUT_METRIC,
                                 out=buf)
    assert rc == 1
    lines = [json.loads(l) for l in buf.getvalue().splitlines()]
    assert lines[0]["status"] == "fail"
    states = [l for l in lines if l["metric"] == "bench_gate_states"]
    assert states and "checkpoint_save +3.500s" in states[0]["detail"]
    # a non-regressed run passes
    ok = [{"metric": bench_gate.TRAIN_METRIC, "value": 2800.0,
           "goodput_fraction": 0.93}]
    buf = _io.StringIO()
    assert bench_gate.gate_records(ok, history_dir=str(tmp_path),
                                   metric=bench_gate.GOODPUT_METRIC,
                                   out=buf) == 0


def test_repo_gate_picks_up_goodput_records(fresh, tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import bench_gate
    # no history for the goodput metric -> lenient skip, exit 0
    run = [{"metric": bench_gate.TRAIN_METRIC, "value": 2800.0,
            "goodput_fraction": 0.9}]
    buf = _io.StringIO()
    rc = bench_gate.gate_records(run, history_dir=str(tmp_path),
                                 metric=bench_gate.GOODPUT_METRIC,
                                 out=buf)
    assert rc == 0
    assert json.loads(buf.getvalue().splitlines()[0])["status"] == "skip"


# ---------------------------------------------------------------------------
# launched: chaos kill-and-resume leaves a priced badput ledger
# ---------------------------------------------------------------------------

RUNPROF_WORKER = r"""
import json, os, sys, time
coord, rank, ckdir, tdir = sys.argv[1], int(sys.argv[2]), sys.argv[3], \
    sys.argv[4]
os.environ["MXNET_TELEMETRY_DIR"] = tdir
restart = int(os.environ.get("MXNET_ELASTIC_RESTART", "0"))
if restart == 0 and rank == 1:
    # incarnation 0 only: rank 1 dies mid-run, strictly after the
    # step-5 checkpoint committed (chaos armed via env before import)
    os.environ["MXNET_CHAOS"] = "worker.death@8"
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import runprof
from mxnet_tpu.parallel import dist, elastic
import jax.numpy as jnp

dist.init(coord, 2, rank, recoverable=True)
dist.stop_heartbeat(); dist.start_heartbeat(interval=0.1)

def step_fn(state, step):
    time.sleep(0.25)
    return {"w": state["w"] + 1.0}

t = elastic.ElasticTrainer(step_fn, {"w": jnp.zeros(4)}, ckpt_dir=ckdir,
                           ckpt_every=5, on_failure="exit",
                           dead_node_timeout=1.0, watchdog_interval=0.25)
out = t.run(12)
print("RESUMED_FROM", t.resumed_from, flush=True)
print("FINAL", float(np.asarray(out["w"])[0]), flush=True)
runprof.write_host_snapshot(force=True)
print("RUNPROF", json.dumps(runprof.snapshot()), flush=True)
dist.stop_heartbeat()
os._exit(0)  # skip jax's shutdown barrier (peer histories differ)
"""


@pytest.mark.launched
@pytest.mark.timeout(180)
def test_launched_chaos_kill_and_resume_prices_badput(fresh, tmp_path):
    """Acceptance: a launched 2-process elastic run loses a worker to
    chaos, the supervisor relaunches the pod, and the run-anatomy
    ledger prices it: nonzero checkpoint_restore badput and lost-work
    steps in the worker snapshots, recovery badput in the supervisor's
    ledger, goodput < 1, all consistent with the merged waterfall."""
    from mxnet_tpu.parallel import elastic
    from mxnet_tpu.parallel.retry import RetryPolicy
    worker = tmp_path / "worker.py"
    worker.write_text(RUNPROF_WORKER)
    ckdir = str(tmp_path / "ck")
    tdir = str(tmp_path / "telemetry")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO)
    restarts, log_dir = elastic.supervise(
        lambda rank, restart, coord: [sys.executable, str(worker), coord,
                                      str(rank), ckdir, tdir],
        nprocs=2, max_restarts=2, env=env,
        log_dir=str(tmp_path / "logs"), round_timeout=120,
        policy=RetryPolicy(max_attempts=3, base_delay=0.2, max_delay=1.0))
    assert restarts >= 1   # incarnation 0 really did lose the worker

    # the supervisor's own ledger booked the relaunch backoff
    assert runprof.state_seconds("recovery") > 0

    for r in range(2):
        out = open(os.path.join(log_dir,
                                "r%d_rank%d.log" % (restarts, r))).read()
        assert "RESUMED_FROM 5" in out, out
        assert "FINAL 12.0" in out, out
        line = [l for l in out.splitlines()
                if l.startswith("RUNPROF ")][-1]
        snap = json.loads(line[len("RUNPROF "):])
        # the resumed incarnation restored a checkpoint and re-executed
        # the steps the dead incarnation had already trained past it
        assert snap["states"]["checkpoint_restore"] > 0, snap
        assert snap["lost_steps"] >= 1, snap
        assert snap["lost_work_seconds"] > 0, snap
        assert snap["states"]["train_productive"] > 0, snap
        assert 0 < snap["goodput_fraction"] < 1, snap

    # merged report over the telemetry dir: both hosts' snapshots (plus
    # the supervisor's, written here so its recovery badput is in the
    # same waterfall), consistent with the per-worker ledgers
    runprof.write_host_snapshot(dir=tdir, force=True)
    merged = runprof.merge_host_snapshots(tdir)
    assert len(merged) >= 3
    buf = _io.StringIO()
    rc = runprof.report(tdir, out=buf)
    text = buf.getvalue()
    assert rc == 0, text
    rec = json.loads(text.strip().splitlines()[-1])
    assert rec["lost_steps"] >= 2          # both ranks re-did work
    assert rec["states"]["checkpoint_restore"] > 0
    assert rec["states"]["recovery"] > 0
    assert rec["goodput_fraction"] < 1
    assert "hosts: " in text               # goodput skew line rendered
