"""Step-time anatomy profiler (`mxnet_tpu/stepprof.py`): taxonomy
completeness (shares sum to 1), the overlap estimator on a synthetic
async workload, verdict classification fixtures for every bottleneck
class, prefetch queue telemetry, the Speedometer phase summary, the
report CLI, bench_gate's pre-diagnosed phase deltas, a chrome-trace
round-trip of the phase spans through ``tools/merge_traces.py``, and a
launched 2-process straggler run where ``MXNET_CHAOS heartbeat.delay``
makes one host provably slow.
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
from mxnet_tpu import stepprof, telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import launchutil  # noqa: E402


@pytest.fixture
def fresh():
    """Clean registry + a reset process profiler; verbose layer off."""
    telemetry.reset()
    stepprof.reset()
    stepprof.disable()
    yield
    stepprof.disable()
    stepprof.reset()
    telemetry.reset()


# ---------------------------------------------------------------------------
# Taxonomy completeness
# ---------------------------------------------------------------------------

def test_phase_taxonomy_shares_sum_to_one(fresh):
    prof = stepprof.StepProfiler(window=64)
    # a step where every taxonomy phase appears, plus untiled residual
    prof.record_step({"data_wait": 0.010, "h2d": 0.005, "dispatch": 0.020,
                      "device_compute": 0.050, "sync": 0.008,
                      "opt_update": 0.004}, wall=0.100)
    for basis in ("p50", "total"):
        sh = prof.shares(basis=basis)
        assert set(sh) == set(stepprof.PHASES) | {stepprof.PHASE_OTHER}
        assert sum(sh.values()) == pytest.approx(1.0, abs=1e-9)
    # the residual bucket is wall minus the tiled phases
    tot = prof.totals()
    assert tot[stepprof.PHASE_OTHER] == pytest.approx(0.003)
    # an unknown phase name is a programming error, not a new bucket
    with pytest.raises(ValueError, match="unknown phase"):
        prof.record_step({"gpu_stuff": 1.0}, wall=1.0)
    with pytest.raises(ValueError, match="unknown phase"):
        prof.phase("not_a_phase")


def test_step_and_phase_ctx_feed_histograms_and_records(fresh):
    with stepprof.step(batches=3) as sp:
        with stepprof.phase("data_wait"):
            time.sleep(0.002)
        with stepprof.phase("dispatch") as ph:
            time.sleep(0.001)
        sp["note"] = "x"
    assert ph.seconds >= 0.001
    st = stepprof.profiler.step_stats()
    assert st["steps"] == 1 and st["batches"] == 3
    assert st["mean_step_seconds"] >= 0.003
    tot = stepprof.totals()
    assert tot["data_wait"] >= 0.002 and tot["dispatch"] >= 0.001
    # telemetry histograms exist under the step_* naming
    for name in ("step_seconds", "step_data_wait_seconds",
                 "step_dispatch_seconds"):
        h = telemetry.get_metric(name)
        assert h is not None and h.count == 1, name
    # phases outside an open step still feed histograms, not records
    with stepprof.phase("sync"):
        pass
    assert telemetry.get_metric("step_sync_seconds").count == 1
    assert stepprof.profiler.step_stats()["steps"] == 1


# ---------------------------------------------------------------------------
# Overlap estimator (synthetic async workload)
# ---------------------------------------------------------------------------

def test_overlap_estimator_synthetic_async(fresh):
    prof = stepprof.StepProfiler(window=64)
    # sampled-sync steps measure TRUE device time: 100 ms per step
    for _ in range(4):
        prof.record_step({"dispatch": 0.005, "device_compute": 0.100},
                         wall=0.108, synced=True)
    # async steady state: the host blocks 40 ms on the readback while
    # 60 ms of device time hid under data_wait — the estimator must
    # surface those hidden 60 ms
    for _ in range(8):
        prof.record_step({"data_wait": 0.060, "dispatch": 0.010,
                          "device_compute": 0.040}, wall=0.115)
    ov = prof.overlap()
    assert ov["steps"] == 8   # synced steps are the estimate, not the view
    assert ov["device_busy_est"] == pytest.approx(0.100, rel=0.01)
    assert ov["device_visible"] == pytest.approx(0.040, rel=0.01)
    assert ov["overlap_seconds"] == pytest.approx(0.060, rel=0.05)
    assert ov["hidden_fraction"] == pytest.approx(0.60, rel=0.05)


def test_overlap_without_samples_is_none(fresh):
    prof = stepprof.StepProfiler(window=8)
    prof.record_step({"data_wait": 0.01, "device_compute": 0.02},
                     wall=0.04)
    ov = prof.overlap()
    assert ov["device_busy_est"] is None
    assert ov["hidden_fraction"] is None
    assert ov["host_busy"] is not None


def test_note_device_sample_marks_step_and_gauges(fresh):
    with stepprof.step():
        with stepprof.phase("device_compute", synced=True):
            pass
        stepprof.note_device_sample(0.05, batches=5)
    ov = stepprof.overlap()
    # 0.05 s over 5 batches -> 0.01 s/batch entered the estimator
    assert ov["device_busy_est"] == pytest.approx(0.01)
    assert stepprof.timeline()[-1]["synced"] is True


# ---------------------------------------------------------------------------
# Verdict classification fixtures
# ---------------------------------------------------------------------------

def _shares(**kv):
    base = {p: 0.0 for p in stepprof.PHASES + (stepprof.PHASE_OTHER,)}
    base.update(kv)
    return base


@pytest.mark.parametrize("shares,expect", [
    (_shares(data_wait=0.5, h2d=0.2, device_compute=0.3), "input-bound"),
    (_shares(dispatch=0.45, other=0.15, device_compute=0.4),
     "dispatch-bound"),
    (_shares(sync=0.6, device_compute=0.3, data_wait=0.1), "sync-bound"),
    (_shares(device_compute=0.7, opt_update=0.1, dispatch=0.2),
     "compute-bound"),
])
def test_verdict_classes(shares, expect):
    verdict, hint = stepprof.classify(shares)
    assert verdict == expect
    assert hint and "unknown" not in verdict


def test_verdict_unknown_on_empty():
    assert stepprof.classify({})[0] == "unknown"
    assert stepprof.classify(_shares())[0] == "unknown"
    assert stepprof.verdict()[0] in (
        "unknown", "input-bound", "dispatch-bound", "sync-bound",
        "compute-bound")


def test_verdict_hints_refined_by_extras():
    disp = _shares(dispatch=0.8, device_compute=0.2)
    v, hint = stepprof.classify(disp, retraces=7)
    assert v == "dispatch-bound" and "retraces" in hint \
        and "bucket" in hint
    v, hint = stepprof.classify(disp, fused=False)
    assert "not fused" in hint
    comp = _shares(device_compute=0.9, dispatch=0.1)
    v, hint = stepprof.classify(comp, donated=False)
    assert v == "compute-bound" and "donation is OFF" in hint


# ---------------------------------------------------------------------------
# Module.fit wiring: shares from a real (CPU) fit loop
# ---------------------------------------------------------------------------

def _tiny_fit(epochs=2, **fit_kw):
    data = mx.sym.var("data")
    fc = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    net = mx.sym.SoftmaxOutput(fc, name="softmax")
    x = np.random.RandomState(0).uniform(size=(64, 10)).astype(np.float32)
    y = np.zeros(64, dtype=np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=16)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=epochs, eval_metric="acc", **fit_kw)
    return mod


def test_fit_records_taxonomy_and_consistent_verdict(fresh):
    _tiny_fit()
    st = stepprof.profiler.step_stats()
    assert st["steps"] == 8 and st["batches"] == 8
    sh = stepprof.shares()
    assert sum(sh.values()) == pytest.approx(1.0, abs=0.05)
    verdict, _ = stepprof.verdict()
    assert verdict != "unknown"
    # the verdict names the dominant phase group
    groups = {v: sum(sh.get(p, 0.0) for p in g)
              for v, g in stepprof.VERDICT_GROUPS.items()}
    assert verdict == max(groups, key=lambda v: groups[v])


def test_fit_sampled_sync_feeds_overlap(fresh):
    stepprof.enable(sync_every=2)
    try:
        _tiny_fit(epochs=1)
    finally:
        stepprof.disable()
    ov = stepprof.overlap()
    assert ov["device_busy_est"] is not None  # samples were taken
    h = telemetry.get_metric("step_device_compute_seconds")
    assert h is not None and h.count >= 4


def test_gluon_trainer_loop_populates_steps(fresh):
    """The gluon path has no fit loop, so `Trainer.step` itself must
    record steps (ImplicitStepper): shares/verdict work, and the step
    wall reaches back over the user's fwd/bwd between calls."""
    from mxnet_tpu import gluon, autograd
    net = gluon.nn.Dense(3)
    net.initialize(ctx=mx.cpu())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    x = mx.nd.ones((8, 4))
    for _ in range(4):
        with autograd.record():
            out = net(x)
            loss = (out * out).sum()
        loss.backward()
        time.sleep(0.002)   # "user fwd/bwd time" between step() calls
        trainer.step(8)
    st = stepprof.profiler.step_stats()
    assert st["steps"] == 4
    # steps 2..4 stretch back over the 2 ms of user work
    assert st["wall_total_seconds"] >= 3 * 0.002
    sh = stepprof.shares()
    assert sum(sh.values()) == pytest.approx(1.0, abs=1e-6)
    assert sh["opt_update"] > 0
    assert stepprof.verdict()[0] != "unknown"


def test_implicit_stepper_noop_inside_explicit_step(fresh):
    stepper = stepprof.ImplicitStepper()
    with stepprof.step():
        with stepper.bracket():
            with stepprof.phase("opt_update"):
                pass
    assert stepprof.profiler.step_stats()["steps"] == 1  # no double count


def test_implicit_stepper_failed_step_not_recorded(fresh):
    stepper = stepprof.ImplicitStepper()
    with pytest.raises(RuntimeError, match="boom"):
        with stepper.bracket():
            raise RuntimeError("boom")
    # matching an explicit step: an aborted step leaves no record to
    # skew shares / mean_step_seconds / straggler snapshots
    assert stepprof.profiler.step_stats()["steps"] == 0
    with stepper.bracket():
        pass
    assert stepprof.profiler.step_stats()["steps"] == 1


def test_implicit_stepper_carries_prestep_phases(fresh):
    stepper = stepprof.ImplicitStepper()
    stepper.carry_phase("h2d", 0.5)
    with pytest.raises(ValueError):
        stepper.carry_phase("nope", 1.0)
    with stepper.bracket():
        pass
    tot = stepprof.totals()
    assert tot["h2d"] == pytest.approx(0.5)  # reached the step record


# ---------------------------------------------------------------------------
# Prefetch telemetry (ROADMAP item 4 satellite)
# ---------------------------------------------------------------------------

def test_prefetch_queue_depth_and_wait_series(fresh):
    x = np.arange(80, dtype=np.float32).reshape(20, 4)
    base = mx.io.NDArrayIter(x, np.zeros(20, np.float32), batch_size=4)
    it = mx.io.PrefetchingIter(base)
    # give the producer a beat to fill the queue, then read the gauge
    time.sleep(0.1)
    g = telemetry.get_metric("prefetch_queue_depth")
    assert g is not None and 0 <= g.read() <= 2
    n = sum(1 for _ in it)
    assert n == 5
    cons = telemetry.get_metric("prefetch_wait_seconds", side="consumer")
    prod = telemetry.get_metric("prefetch_wait_seconds", side="producer")
    assert cons is not None and cons.count >= 5
    assert prod is not None and prod.count >= 5
    # the gauge holds a weakref: a dropped iterator degrades the scrape
    # to the pushed value instead of keeping the queue alive
    del it, base
    import gc
    gc.collect()
    assert g.read() == 0.0


# ---------------------------------------------------------------------------
# Speedometer phase summary (gated by MXNET_STEPPROF)
# ---------------------------------------------------------------------------

def test_speedometer_phase_suffix_gated(fresh):
    sp = mx.callback.Speedometer(batch_size=16, frequent=4)
    sp._mark()
    with stepprof.step():
        with stepprof.phase("data_wait"):
            time.sleep(0.002)
        with stepprof.phase("device_compute"):
            time.sleep(0.004)
    assert sp._phase_suffix() == ""     # disabled: no suffix
    stepprof.enable()
    try:
        suffix = sp._phase_suffix()
        assert "data" in suffix and "compute" in suffix and "%" in suffix
        sp._mark()
        assert sp._phase_suffix() == ""  # nothing advanced since mark
    finally:
        stepprof.disable()


# ---------------------------------------------------------------------------
# Report: sources, CLI, bench_gate phase deltas
# ---------------------------------------------------------------------------

def test_report_from_bench_json_and_prom(fresh, tmp_path):
    doc = {"metric": "train_phase_breakdown",
           "phases": {"data_wait": 0.55, "h2d": 0.1, "dispatch": 0.1,
                      "device_compute": 0.2, "sync": 0.05},
           "verdict": "input-bound"}
    p = tmp_path / "bench_stepprof.json"
    p.write_text(json.dumps(doc))
    out = _io.StringIO()
    rc = stepprof.report(str(p), out=out)
    text = out.getvalue()
    assert rc == 0
    assert "verdict: input-bound" in text and "PrefetchingIter" in text
    rec = json.loads(text.strip().splitlines()[-1])
    assert rec["metric"] == "stepprof_report"
    assert rec["verdict"] == "input-bound"
    # .prom round trip: feed histograms, snapshot, report from the file
    prof = stepprof.profiler
    for _ in range(3):
        prof.record_step({"sync": 0.08, "device_compute": 0.01,
                          "dispatch": 0.01}, wall=0.11)
    prom = str(tmp_path / "metrics.prom")
    telemetry.write_snapshot(prom)
    out = _io.StringIO()
    assert stepprof.report(prom, out=out) == 0
    assert "verdict: sync-bound" in out.getvalue()


def test_report_cli_subprocess(tmp_path):
    doc = {"phases": {"dispatch": 0.7, "device_compute": 0.2,
                      "other": 0.1}}
    p = tmp_path / "run.json"
    p.write_text(json.dumps(doc))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("MXNET_TELEMETRY_DIR", None)
    r = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.stepprof", "report", str(p),
         "--json"],
        capture_output=True, text=True, timeout=launchutil.LAUNCH_TIMEOUT,
        env=env, cwd=str(tmp_path))
    assert r.returncode == 0, r.stdout + r.stderr
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["verdict"] == "dispatch-bound"


def test_bench_gate_prints_phase_deltas_on_regression(fresh, tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import bench_gate
    good_phases = {"data_wait": 0.05, "dispatch": 0.1,
                   "device_compute": 0.85}
    bad_phases = {"data_wait": 0.45, "dispatch": 0.1,
                  "device_compute": 0.45}
    (tmp_path / "BENCH_r01.json").write_text(json.dumps({
        "parsed": {"metric": bench_gate.TRAIN_METRIC, "value": 100.0,
                   "phases": good_phases}}))
    run = [{"metric": bench_gate.TRAIN_METRIC, "value": 70.0,
            "phases": bad_phases, "verdict": "input-bound"}]
    out = _io.StringIO()
    rc = bench_gate.gate_records(run, history_dir=str(tmp_path), out=out)
    assert rc == 1
    lines = [json.loads(l) for l in out.getvalue().splitlines()]
    gate = [l for l in lines if l["metric"] == "bench_gate"][0]
    assert gate["status"] == "fail"
    ph = [l for l in lines if l["metric"] == "bench_gate_phases"][0]
    assert ph["delta"]["data_wait"] == pytest.approx(0.40)
    assert "data_wait +40%" in ph["detail"]
    # a pass prints no phase line
    out = _io.StringIO()
    assert bench_gate.gate_records(
        [{"metric": bench_gate.TRAIN_METRIC, "value": 99.0}],
        history_dir=str(tmp_path), out=out) == 0
    assert "bench_gate_phases" not in out.getvalue()


# ---------------------------------------------------------------------------
# Cross-host merge + straggler detection (in-process)
# ---------------------------------------------------------------------------

def _host_snapshot(tmp_path, host, step_seconds, steps=20):
    prof = stepprof.StepProfiler(window=64)
    for _ in range(steps):
        prof.record_step({"dispatch": step_seconds}, wall=step_seconds)
    telemetry.set_host_id(host)
    try:
        path = prof.write_host_snapshot(dir=str(tmp_path), force=True)
    finally:
        telemetry.set_host_id(0)
    assert path and os.path.exists(path)
    return path


def test_straggler_detection_and_unskewed(fresh, tmp_path):
    _host_snapshot(tmp_path, 0, 0.010)
    _host_snapshot(tmp_path, 1, 0.050)
    res = stepprof.detect_stragglers(str(tmp_path))
    assert set(res["hosts"]) == {0, 1}
    assert res["straggler_host"] == 1
    assert res["skew_seconds"] == pytest.approx(0.040, rel=0.01)
    assert telemetry.get_metric("step_skew_seconds").value == \
        pytest.approx(0.040, rel=0.01)
    assert telemetry.get_metric("straggler_host").value == 1
    # unskewed: equal hosts accuse nobody
    for f in os.listdir(tmp_path):
        os.remove(os.path.join(tmp_path, f))
    _host_snapshot(tmp_path, 0, 0.020)
    _host_snapshot(tmp_path, 1, 0.0201)
    res = stepprof.detect_stragglers(str(tmp_path))
    assert res["straggler_host"] == -1
    assert abs(res["skew_seconds"]) < 0.001


def test_merge_keeps_freshest_per_host_and_skips_garbage(fresh, tmp_path):
    _host_snapshot(tmp_path, 0, 0.010)
    (tmp_path / "stepprof_host9_pid1.json").write_text("{torn")
    hosts = stepprof.merge_host_snapshots(str(tmp_path))
    assert set(hosts) == {0}
    assert stepprof.merge_host_snapshots(str(tmp_path / "missing")) == {}


# ---------------------------------------------------------------------------
# Chrome-trace round trip through tools/merge_traces.py
# ---------------------------------------------------------------------------

def test_phase_spans_round_trip_chrome_trace(fresh, tmp_path):
    teldir = str(tmp_path / "telemetry")
    telemetry.configure(teldir, snapshot_interval=0)
    try:
        with stepprof.step():
            with stepprof.phase("data_wait"):
                pass
            with stepprof.phase("dispatch"):
                pass
            with stepprof.phase("device_compute", via="update_metric"):
                pass
    finally:
        telemetry.configure(None)
    out = str(tmp_path / "trace.json")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "merge_traces.py"),
         teldir, "-o", out],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    names = [e["name"] for e in json.load(open(out))["traceEvents"]]
    for needle in ("step", "step.data_wait", "step.dispatch",
                   "step.device_compute"):
        assert needle in names, (needle, names)
    # phase slices are complete ("X") events with real durations
    evs = [e for e in json.load(open(out))["traceEvents"]
           if e["name"].startswith("step.")]
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in evs)


# ---------------------------------------------------------------------------
# Timeline: step records with their clocks and ordered phases
# ---------------------------------------------------------------------------

def _check_spans_tile(rec):
    """Spans lie inside the step, in order, without overlap, and with
    ``other`` sum to ``wall``."""
    edge = 0.0
    for name, start, dur, attrs in rec["spans"]:
        assert name in stepprof.PHASES and isinstance(attrs, dict)
        assert start >= edge - 1e-9 and dur >= 0.0, rec["spans"]
        edge = start + dur
    assert edge <= rec["wall"] + 1e-9
    assert sum(d for _, _, d, _ in rec["spans"]) + rec["other"] == \
        pytest.approx(rec["wall"], abs=1e-9)
    summed = {}
    for name, _, dur, _ in rec["spans"]:
        summed[name] = summed.get(name, 0.0) + dur
    assert summed == pytest.approx(rec["phases"])


def test_timeline_spans_in_order_and_tile_the_step(fresh):
    with stepprof.step(batches=2):
        with stepprof.phase("h2d") as ph:
            time.sleep(0.002)
            ph["bytes"] = 77
        with stepprof.phase("dispatch", site="x"):
            time.sleep(0.001)
        time.sleep(0.001)   # nobody's: lands in `other`
        for _ in range(2):  # one entry per occurrence, not a sum
            with stepprof.phase("device_compute", via="update_metric"):
                time.sleep(0.001)
    (rec,) = stepprof.timeline()
    assert [sp[0] for sp in rec["spans"]] == \
        ["h2d", "dispatch", "device_compute", "device_compute"]
    assert rec["spans"][0][3] == {"bytes": 77}
    assert rec["spans"][1][3] == {"site": "x"}
    assert rec["spans"][2][3] == {"via": "update_metric"}
    assert rec["batches"] == 2 and rec["other"] >= 0.001
    assert rec["phases"]["device_compute"] >= 0.002
    _check_spans_tile(rec)
    json.dumps(stepprof.timeline())   # plain lists and dicts


def test_timeline_seq_rises_by_one_and_ring_stays_bounded(fresh,
                                                          monkeypatch):
    monkeypatch.setenv("MXNET_STEPPROF_WINDOW", "16")
    prof = stepprof.StepProfiler()
    for _ in range(40):
        with prof.step():
            with prof.phase("dispatch"):
                pass
    recs = prof.timeline()
    assert len(recs) == 16
    assert [r["seq"] for r in recs] == list(range(24, 40))
    entries = [r["clock"][1] for r in recs]
    assert entries == sorted(entries)
    # a failed step leaves no record and takes no number
    with pytest.raises(RuntimeError):
        with prof.step():
            raise RuntimeError("boom")
    with prof.step():
        pass
    assert prof.timeline()[-1]["seq"] == 40


def test_timeline_clock_pair_places_a_phase_on_the_wall_clock(fresh):
    with stepprof.step():
        time.sleep(0.003)
        beside = time.time_ns()
        with stepprof.phase("dispatch"):
            time.sleep(0.001)
    (rec,) = stepprof.timeline()
    wall_ns, perf = rec["clock"]
    assert isinstance(wall_ns, int) and isinstance(perf, float)
    (_, start, _, _), = rec["spans"]
    assert start >= 0.003
    assert abs(wall_ns + start * 1e9 - beside) < 1e6   # within 1 ms


def test_timeline_of_fed_records_has_no_clock(fresh):
    prof = stepprof.StepProfiler(window=8)
    prof.record_step({"dispatch": 0.01}, wall=0.02)
    (rec,) = prof.timeline()
    assert rec["clock"] is None and rec["spans"] == []
    assert rec["seq"] == 0 and rec["phases"] == {"dispatch": 0.01}


def test_timeline_implicit_stepper_stretch_moves_both_clocks(fresh):
    stepper = stepprof.ImplicitStepper()
    with stepper.bracket():
        pass
    time.sleep(0.004)            # the user's forward/backward
    with stepprof.phase("h2d") as ph:
        time.sleep(0.002)        # staging before the step call
    stepper.carry_phase("h2d", ph.seconds)
    beside = time.time_ns()
    with stepper.bracket():
        pass
    first, second = stepprof.timeline()
    # the second step reaches back to where the first ended (its record
    # was booked in between), on both clocks of its pair
    seam = second["clock"][1] - (first["clock"][1] + first["wall"])
    assert 0.0 <= seam < 3e-3
    assert second["wall"] >= 0.006
    (name, start, dur, _), = second["spans"]
    assert name == "h2d" and dur == pytest.approx(ph.seconds)
    assert 0.004 <= start and start + dur <= second["wall"]
    assert abs(second["clock"][0] + (start + dur) * 1e9 - beside) < 1e6


@pytest.mark.parametrize("per_dispatch", [1, 4])
def test_fit_timeline_tiles_the_loop_and_h2d_carries_bytes(fresh,
                                                           per_dispatch):
    from mxnet_tpu import xla_stats
    data = mx.sym.var("data")
    fc = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    net = mx.sym.SoftmaxOutput(fc, name="softmax")
    x = np.random.RandomState(0).uniform(size=(256, 10)).astype(np.float32)
    it = mx.io.NDArrayIter(x, np.zeros(256, np.float32), batch_size=16)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=1, eval_metric="acc",
            batches_per_dispatch=per_dispatch,
            batch_end_callback=lambda param: time.sleep(0.003))
    before = xla_stats.compile_counts()
    recs = stepprof.timeline()
    assert xla_stats.compile_counts() == before   # reading compiles nothing
    assert len(recs) == 16 // per_dispatch
    assert [r["seq"] for r in recs] == list(range(len(recs)))
    batch_bytes = 16 * 10 * 4 + 16 * 4     # float32 data and labels
    for rec in recs:
        _check_spans_tile(rec)
        assert rec["batches"] == per_dispatch
        # `prepare` stages the next batch (group) in h2d phases of its
        # own; the step's own h2d binds (stacks) what it finds
        names = [sp[0] for sp in rec["spans"]
                 if sp[3].get("via") != "prepare"]
        assert names.count("h2d") == 1 and names.count("dispatch") == 1
        assert names.index("h2d") < names.index("dispatch")
        # one read-back wait a batch, each after the dispatch
        assert names.count("device_compute") == per_dispatch
        assert names.index("dispatch") < names.index("device_compute")
        (h2d,) = [sp for sp in rec["spans"] if sp[0] == "h2d"
                  and sp[3].get("via") != "prepare"]
        assert h2d[3]["bytes"] == batch_bytes * per_dispatch
        # the callbacks run inside the step: their time is its `other`
        assert rec["other"] >= 0.003 * per_dispatch
    # the loop tiles: a step begins where the one before it ended
    seams = [b["clock"][1] - (a["clock"][1] + a["wall"])
             for a, b in zip(recs, recs[1:])]
    assert all(seam >= 0.0 for seam in seams)
    assert sorted(seams)[len(seams) // 2] < 1e-3, seams
    covered = sum(r["wall"] for r in recs) + sum(seams)
    assert covered == pytest.approx(
        recs[-1]["clock"][1] + recs[-1]["wall"] - recs[0]["clock"][1])
    assert sum(seams) < 0.1 * covered


def test_straggler_needs_an_absolute_skew_too(fresh, tmp_path):
    # 29 % apart, but 0.8 ms: what six test workers on one host do to a
    # 2 ms step (ROADMAP D10); the ratio alone would accuse host 1
    _host_snapshot(tmp_path, 0, 0.0020)
    _host_snapshot(tmp_path, 1, 0.0028)
    res = stepprof.detect_stragglers(str(tmp_path))
    assert res["skew_seconds"] == pytest.approx(0.0008, rel=0.01)
    assert res["straggler_host"] == -1
    assert telemetry.get_metric("straggler_host").value == -1
    # the same ratio with a skew a scheduler does not explain
    _host_snapshot(tmp_path, 1, 0.0280)
    _host_snapshot(tmp_path, 0, 0.0200)
    assert stepprof.detect_stragglers(str(tmp_path))["straggler_host"] == 1


# ---------------------------------------------------------------------------
# Launched acceptance: a chaos-slowed host is named straggler
# ---------------------------------------------------------------------------

STRAGGLER_WORKER = r"""
import os, sys, time
rank, steps = int(sys.argv[1]), int(sys.argv[2])
from mxnet_tpu import stepprof, chaos, telemetry
assert telemetry.host_id() == rank
for i in range(steps):
    with stepprof.step():
        with stepprof.phase("dispatch"):
            time.sleep(0.002)
        extra = chaos.heartbeat_extra_delay()
        if extra:
            time.sleep(extra)   # the injected straggler stall
path = stepprof.write_host_snapshot(force=True)
assert path, "no telemetry dir configured?"
print("WORKER_OK", rank, flush=True)
"""


def _run_straggler_pair(tmp_path, tag, chaos_spec):
    teldir = str(tmp_path / ("telemetry_" + tag))
    os.makedirs(teldir)
    worker = tmp_path / "worker.py"
    worker.write_text(STRAGGLER_WORKER)
    procs = []
    for rank in range(2):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=REPO, MXNET_TELEMETRY_DIR=teldir,
                   MXNET_TELEMETRY_HOST=str(rank))
        env.pop("MXNET_CHAOS", None)
        if rank == 1 and chaos_spec:
            env["MXNET_CHAOS"] = chaos_spec
        procs.append(subprocess.Popen(
            [sys.executable, str(worker), str(rank), "20"], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    for rank, (p, (out, _)) in enumerate(
            zip(procs, launchutil.communicate_all(procs))):
        assert p.returncode == 0, out[-3000:]
        assert "WORKER_OK %d" % rank in out, out[-3000:]
    return stepprof.detect_stragglers(teldir)


@pytest.mark.launched
@pytest.mark.timeout(180)
def test_launched_straggler_named_and_unskewed_clean(fresh, tmp_path):
    """Acceptance (ISSUE 6): a 2-process run where MXNET_CHAOS
    `heartbeat.delay` stalls every step of host 1 reports
    step_skew_seconds > 0 and names host 1 in straggler_host; the same
    pair without chaos reports skew ~= 0 and accuses nobody."""
    skewed = _run_straggler_pair(
        tmp_path, "skewed", "heartbeat.delay@0x100=0.05")
    assert skewed["straggler_host"] == 1, skewed
    assert skewed["skew_seconds"] > 0.02, skewed
    clean = _run_straggler_pair(tmp_path, "clean", None)
    assert clean["straggler_host"] == -1, clean
    assert abs(clean["skew_seconds"]) < 0.01, clean
