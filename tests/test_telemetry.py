"""Unified runtime telemetry (`mxnet_tpu/telemetry.py`): registry
semantics, histogram quantiles, JSONL event log + chrome-trace export,
Prometheus exposition, multi-host merge, and the hot-path wire-ins
(kvstore, retry, elastic checkpoints, Module.fit phases, Speedometer).

The launched acceptance test at the bottom runs a 2-process elastic run
with chaos enabled and asserts — not demonstrates — that per-host JSONL
logs merge into one chrome trace and that `telemetry.dumps()` carries
nonzero kvstore/retry/checkpoint/chaos series on every host.

Also here: the `xplane.dumps` unit test on a synthetic hand-encoded
.xplane.pb, so the protobuf parser is no longer exercised only
end-to-end through a live jax trace.
"""
import json
import os
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import launchutil  # noqa: E402


@pytest.fixture
def fresh(tmp_path):
    """Clean registry + event log routed to a tmp dir (no snapshot
    thread); always unconfigured afterwards."""
    telemetry.reset()
    d = str(tmp_path / "telemetry")
    telemetry.configure(d, snapshot_interval=0)
    yield d
    telemetry.configure(None)
    telemetry.reset()


# ---------------------------------------------------------------------------
# Registry semantics
# ---------------------------------------------------------------------------

def test_counter_gauge_identity_and_labels(fresh):
    c = telemetry.counter("reqs_total", "requests", route="a")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    # same (name, labels) -> same object; different labels -> new series
    assert telemetry.counter("reqs_total", route="a") is c
    other = telemetry.counter("reqs_total", route="b")
    assert other is not c and other.value == 0
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1)
    g = telemetry.gauge("depth")
    g.set(7)
    g.inc()
    g.dec(3)
    assert g.value == 5
    # a name cannot change kind
    with pytest.raises(ValueError, match="already registered"):
        telemetry.gauge("reqs_total")
    # lookup without creation
    assert telemetry.get_metric("reqs_total", route="a") is c
    assert telemetry.get_metric("reqs_total", route="zzz") is None


def test_counter_thread_safety(fresh):
    c = telemetry.counter("mt_total")

    def worker():
        for _ in range(1000):
            c.inc()

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000


def test_histogram_quantiles_and_bounded_reservoir(fresh):
    h = telemetry.histogram("lat_seconds")
    for v in range(1, 1001):
        h.observe(float(v))
    assert h.count == 1000 and h.sum == 500500.0
    assert h.min == 1.0 and h.max == 1000.0
    assert abs(h.quantile(0.5) - 500) < 30
    assert abs(h.quantile(0.95) - 950) < 30
    assert abs(h.quantile(0.99) - 990) < 30
    # bounded: a small reservoir keeps exact count/sum but caps samples
    small = telemetry.histogram("small_seconds", reservoir=64)
    for v in range(10000):
        small.observe(float(v))
    assert small.count == 10000
    assert len(small._samples) == 64
    assert 2000 < small.quantile(0.5) < 8000  # unbiased-ish median
    assert telemetry.histogram("lat_seconds") is h  # identity
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_prometheus_dumps_format(fresh):
    telemetry.counter("a_total", "things done", kind='we"ird\nlabel').inc(3)
    telemetry.gauge("b").set(1.5)
    telemetry.histogram("c_seconds").observe(0.25)
    text = telemetry.dumps()
    assert "# HELP a_total things done" in text
    assert "# TYPE a_total counter" in text
    # label value escaped: quote and newline must not break the line
    assert 'a_total{kind="we\\"ird\\nlabel"} 3' in text
    assert "# TYPE b gauge" in text and "\nb 1.5" in text
    assert "# TYPE c_seconds summary" in text
    assert 'c_seconds{quantile="0.5"} 0.25' in text
    assert "c_seconds_sum 0.25" in text
    assert "c_seconds_count 1" in text
    snap = telemetry.snapshot()
    assert snap["c_seconds"]["series"][0]["p99"] == 0.25


# ---------------------------------------------------------------------------
# Spans, JSONL event log, chrome-trace export
# ---------------------------------------------------------------------------

def test_span_feeds_histogram_without_event_log():
    telemetry.reset()
    try:
        assert telemetry.configured_dir() is None
        with telemetry.span("quiet.region"):
            pass
        h = telemetry.get_metric("quiet_region_seconds")
        assert h is not None and h.count == 1
    finally:
        telemetry.reset()


def test_jsonl_chrome_trace_round_trip(fresh):
    with telemetry.span("outer", step=3) as sp:
        sp["extra"] = "yes"
        time.sleep(0.01)
    telemetry.event("marker", reason="because")
    telemetry.flush()
    files = [f for f in os.listdir(fresh) if f.endswith(".jsonl")]
    assert len(files) == 1
    events = telemetry.read_events(os.path.join(fresh, files[0]))
    span_ev = [e for e in events if e["name"] == "outer"][0]
    assert span_ev["ph"] == "X"
    assert span_ev["dur"] >= 0.01
    assert span_ev["args"] == {"step": 3, "extra": "yes"}
    for key in ("ts", "mono", "pid", "host", "tid"):
        assert key in span_ev
    inst = [e for e in events if e["name"] == "marker"][0]
    assert inst["ph"] == "i" and inst["args"]["reason"] == "because"
    # registry side: the span duration landed in a histogram
    assert telemetry.get_metric("outer_seconds").count == 1

    out = os.path.join(fresh, "trace.json")
    trace = telemetry.merge(fresh, out=out)
    with open(out) as fh:
        assert json.load(fh) == trace
    tev = trace["traceEvents"]
    x = [e for e in tev if e.get("ph") == "X"][0]
    assert x["name"] == "outer" and x["dur"] >= 0.01 * 1e6
    assert x["ts"] == span_ev["ts"] * 1e6
    assert any(e.get("ph") == "M" and e["name"] == "process_name"
               for e in tev)
    # a torn trailing line (killed writer) is skipped, not fatal
    with open(os.path.join(fresh, files[0]), "a") as fh:
        fh.write('{"name": "torn')
    assert len(telemetry.read_events(os.path.join(fresh, files[0]))) \
        == len(events)


def test_span_records_error_attr(fresh):
    with pytest.raises(RuntimeError):
        with telemetry.span("failing"):
            raise RuntimeError("boom")
    telemetry.flush()
    files = [f for f in os.listdir(fresh) if f.endswith(".jsonl")]
    ev = [e for e in telemetry.read_events(os.path.join(fresh, files[0]))
          if e["name"] == "failing"][0]
    assert "RuntimeError: boom" in ev["args"]["error"]


def test_multi_host_merge_one_timeline(fresh, tmp_path):
    """Events from different hosts land on distinct trace-process rows
    of ONE wall-clock-ordered timeline (the multi-host story)."""
    d = str(tmp_path / "multihost")
    os.makedirs(d)
    t0 = 1000.0
    for host, offs in ((0, 0.0), (1, 0.005)):
        with open(os.path.join(d, "events_host%d_pid%d.jsonl"
                               % (host, 100 + host)), "w") as fh:
            for i in range(3):
                fh.write(json.dumps({
                    "name": "step", "ph": "X", "ts": t0 + offs + i * 0.1,
                    "dur": 0.05, "pid": 100 + host, "host": host,
                    "tid": 1, "args": {"i": i}}) + "\n")
    trace = telemetry.merge(d)
    tev = trace["traceEvents"]
    metas = [e for e in tev if e.get("ph") == "M"]
    assert sorted(e["args"]["name"] for e in metas) == \
        ["host0/pid100", "host1/pid101"]
    xs = [e for e in tev if e.get("ph") == "X"]
    assert len(xs) == 6 and len({e["pid"] for e in xs}) == 2
    # one timeline: globally sorted by wall clock
    assert [e["ts"] for e in xs] == sorted(e["ts"] for e in xs)


def test_snapshot_file_and_periodic_writer(tmp_path):
    telemetry.reset()
    d = str(tmp_path / "snap")
    try:
        telemetry.configure(d, snapshot_interval=0.05)
        telemetry.counter("snap_total").inc(5)
        deadline = time.time() + 5
        path = os.path.join(
            d, "metrics_host%d_pid%d.prom"
            % (telemetry.host_id(), os.getpid()))
        while time.time() < deadline:
            if os.path.exists(path) and "snap_total 5" in open(path).read():
                break
            time.sleep(0.02)
        assert "snap_total 5" in open(path).read()
    finally:
        telemetry.configure(None)
        telemetry.reset()


# ---------------------------------------------------------------------------
# Hot-path wire-ins
# ---------------------------------------------------------------------------

def test_kvstore_push_pull_series(fresh):
    kv = mx.kv.create("local")
    kv.init("w", mx.nd.zeros((16, 16)))
    for _ in range(3):
        kv.push("w", mx.nd.ones((16, 16)))
    out = mx.nd.zeros((16, 16))
    kv.pull("w", out=out)
    assert telemetry.counter("kvstore_push_total").value == 3
    assert telemetry.counter("kvstore_pull_total").value == 1
    nbytes = 16 * 16 * 4
    assert telemetry.counter("kvstore_push_bytes_total").value == 3 * nbytes
    assert telemetry.counter("kvstore_pull_bytes_total").value == nbytes
    h = telemetry.get_metric("kvstore_push_seconds")
    assert h.count == 3 and h.sum > 0
    # spans landed in the event log too
    telemetry.flush()
    files = [f for f in os.listdir(fresh) if f.endswith(".jsonl")]
    names = [e["name"] for e in
             telemetry.read_events(os.path.join(fresh, files[0]))]
    assert names.count("kvstore.push") == 3


def test_retry_attempts_counted(fresh):
    from mxnet_tpu.parallel import retry

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TimeoutError("transient")
        return "ok"

    policy = retry.RetryPolicy(max_attempts=5, base_delay=0.0,
                               max_delay=0.0)
    assert retry.retry_call(flaky, policy=policy,
                            describe="flaky thing") == "ok"
    c = telemetry.get_metric("retry_attempts_total", call="flaky thing")
    assert c is not None and c.value == 2
    with pytest.raises(retry.RetryError):
        retry.retry_call(lambda: (_ for _ in ()).throw(TimeoutError("x")),
                         policy=retry.RetryPolicy(max_attempts=2,
                                                  base_delay=0.0),
                         describe="doomed thing")
    assert telemetry.get_metric("retry_exhausted_total",
                                call="doomed thing").value == 1


def test_elastic_checkpoint_durations(fresh, tmp_path):
    from mxnet_tpu.parallel import elastic

    ck = elastic.ElasticCheckpointer(str(tmp_path / "ck"), keep_last=2)
    tree = {"w": np.arange(8, dtype=np.float32)}
    ck.save(1, tree)
    ck.save(2, tree)
    from mxnet_tpu.parallel.checkpoint import abstract_like
    step, out = ck.restore(abstract_like(tree))
    assert step == 2
    np.testing.assert_allclose(np.asarray(out["w"]), tree["w"])
    assert telemetry.get_metric("elastic_checkpoint_save_seconds").count == 2
    assert telemetry.get_metric(
        "elastic_checkpoint_restore_seconds").count == 1


def test_fit_phase_series_and_speedometer(fresh):
    data = mx.sym.var("data")
    fc = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    net = mx.sym.SoftmaxOutput(fc, name="softmax")
    x = np.random.RandomState(0).uniform(size=(64, 10)).astype(np.float32)
    y = np.zeros(64, dtype=np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=16)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=2, eval_metric="acc")
    assert telemetry.counter("fit_batches_total").value == 8
    assert telemetry.counter("fit_samples_total").value == 128
    # PR 6: the ad-hoc fit.* spans became the stepprof taxonomy
    for phase in ("data_wait", "h2d", "dispatch", "device_compute"):
        h = telemetry.get_metric("step_%s_seconds" % phase)
        assert h is not None and h.count >= 8, phase
    assert telemetry.get_metric("step_seconds").count >= 8
    # Speedometer reads samples/sec from the registry, not local math
    sp = mx.callback.Speedometer(batch_size=16, frequent=4)
    sp._mark()
    telemetry.counter("fit_samples_total").inc(1000)
    time.sleep(0.05)
    speed = sp._speed()
    assert 1000 / 0.05 * 0.2 < speed < 1000 / 0.05 * 1.2
    # outside an instrumented loop the reference arithmetic kicks in
    sp2 = mx.callback.Speedometer(batch_size=16, frequent=4)
    sp2._mark()
    time.sleep(0.01)
    assert sp2._speed() == pytest.approx(
        4 * 16 / (time.time() - sp2.tic), rel=0.8)


def test_op_dispatch_series_via_profiler_hook(fresh):
    from mxnet_tpu import profiler
    profiler.set_config(aggregate_stats=True, profile_memory=False)
    profiler.reset_stats()
    try:
        a = mx.nd.ones((8, 8))
        (a + a).asnumpy()
        series = [(k, lab) for (k, lab) in telemetry._metrics
                  if k == "op_dispatch_seconds"]
        assert series, "no op_dispatch series recorded"
        assert all(dict(lab).get("op") for _k, lab in series)
    finally:
        profiler.set_config(aggregate_stats=False)
        profiler.reset_stats()


# ---------------------------------------------------------------------------
# xplane.dumps on a synthetic trace (parser no longer only tested e2e)
# ---------------------------------------------------------------------------

def _pb_varint(n):
    out = b""
    while True:
        b7 = n & 0x7F
        n >>= 7
        if n:
            out += bytes([b7 | 0x80])
        else:
            return out + bytes([b7])


def _pb_key(field, wire):
    return _pb_varint((field << 3) | wire)


def _pb_vi(field, value):
    return _pb_key(field, 0) + _pb_varint(value)


def _pb_ld(field, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _pb_key(field, 2) + _pb_varint(len(payload)) + payload


def _synthetic_xplane(path):
    """Hand-encode an XSpace: one device plane, one 'XLA Ops' line, three
    events over two op metadatas (fusion.1 x2, copy.2 x1), one string
    stat (hlo_category) via stat-metadata interning."""
    stat_meta = _pb_ld(5, _pb_ld(2, _pb_vi(1, 7) + _pb_ld(2, "hlo_category")))
    em1 = _pb_ld(4, _pb_ld(2, _pb_vi(1, 1) + _pb_ld(2, "fusion.1")))
    em2 = _pb_ld(4, _pb_ld(2, _pb_vi(1, 2) + _pb_ld(2, "copy.2")))
    stat = _pb_ld(4, _pb_vi(1, 7) + _pb_ld(5, "convolution"))
    ev1 = _pb_ld(4, _pb_vi(1, 1) + _pb_vi(2, 0) + _pb_vi(3, 2_000_000)
                 + stat)
    ev2 = _pb_ld(4, _pb_vi(1, 1) + _pb_vi(2, 5_000_000)
                 + _pb_vi(3, 4_000_000))
    ev3 = _pb_ld(4, _pb_vi(1, 2) + _pb_vi(2, 9_000_000)
                 + _pb_vi(3, 1_000_000))
    line = _pb_ld(3, _pb_ld(11, "XLA Ops") + _pb_vi(3, 123) + ev1 + ev2
                  + ev3)
    plane = _pb_ld(1, _pb_ld(2, "/device:TPU:0") + stat_meta + em1 + em2
                   + line)
    with open(path, "wb") as fh:
        fh.write(plane)
    return path


def test_xplane_dumps_on_synthetic_trace(tmp_path):
    from mxnet_tpu import xplane

    path = _synthetic_xplane(str(tmp_path / "synthetic.xplane.pb"))
    planes = xplane.parse_xspace(path)
    assert len(planes) == 1 and planes[0].name == "/device:TPU:0"
    (line,) = planes[0].lines
    assert line.name == "XLA Ops" and len(line.events) == 3
    assert line.events[0].stats["hlo_category"] == "convolution"

    table = xplane.op_table(path, by="op")
    assert table["fusion"]["count"] == 2
    assert table["fusion"]["total_ps"] == 6_000_000
    assert table["fusion"]["min_ps"] == 2_000_000
    assert table["copy"]["count"] == 1

    by_inst = xplane.op_table(path, by="instance")
    assert set(by_inst) == {"fusion.1", "copy.2"}
    by_cat = xplane.op_table(path, by="category")
    assert by_cat["convolution"]["count"] == 1  # interned stat resolved

    text = xplane.dumps(path, top=10)
    assert "fusion" in text and "copy" in text
    fusion_line = [l for l in text.splitlines()
                   if l.startswith("fusion")][0]
    assert int(fusion_line.split()[1]) == 2
    assert "TOTAL" in text


# ---------------------------------------------------------------------------
# Acceptance: 2-process launched elastic run with chaos -> per-host JSONL
# merged into one chrome trace; dumps() nonzero on every required series
# ---------------------------------------------------------------------------

TELEMETRY_WORKER = r"""
import os, sys
coord, rank, ckdir = sys.argv[1], int(sys.argv[2]), sys.argv[3]
import numpy as np
import jax.numpy as jnp
import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.parallel import dist, elastic

# MXNET_CHAOS armed coordinator.timeout@0x1 at import: the FIRST attach
# attempt times out, the retry layer backs off and reconnects
dist.init(coord, 2, rank)
assert telemetry.host_id() == rank

kv = mx.kv.create("local")  # per-host traffic (no CPU collectives)
kv.init("w", mx.nd.zeros((8, 8)))
kv.push("w", mx.nd.ones((8, 8)))
out = mx.nd.zeros((8, 8))
kv.pull("w", out=out)

def step_fn(state, step):
    return {"w": state["w"] + 1.0}

t = elastic.ElasticTrainer(step_fn, {"w": jnp.zeros(4)}, ckpt_dir=ckdir,
                           ckpt_every=2, dead_node_timeout=None)
res = t.run(4)
assert float(np.asarray(res["w"])[0]) == 4.0

text = telemetry.dumps()
for needle, pat in (
        ("kvstore_push_total", r"kvstore_push_total 1"),
        ("kvstore_pull_total", r"kvstore_pull_total 1"),
        ("kvstore_push_bytes_total", r"kvstore_push_bytes_total 256"),
        ("retry_attempts", r'retry_attempts_total\{call="jax.distributed.initialize"\} 1'),
        ("checkpoint saves", r"elastic_checkpoint_save_seconds_count 2"),
        ("chaos injections", r'chaos_injections_total\{site="coordinator.timeout"\} 1'),
):
    import re as _re
    assert _re.search(pat, text), (needle, text)
print("SERIES_OK", rank, flush=True)
telemetry.flush()
dist.stop_heartbeat()
os._exit(0)  # skip jax shutdown barrier
"""


@pytest.mark.launched
@pytest.mark.timeout(180)
def test_launched_two_host_elastic_chaos_telemetry(tmp_path):
    """Acceptance (ISSUE 2): a 2-process launched elastic run with chaos
    enabled produces per-host JSONL event logs that `telemetry.merge()`
    combines into one chrome-trace file, and every host's
    `telemetry.dumps()` shows nonzero kvstore push/pull, retry,
    checkpoint-duration, and chaos-injection series."""
    worker = tmp_path / "worker.py"
    worker.write_text(TELEMETRY_WORKER)
    teldir = str(tmp_path / "telemetry")
    ckdir = str(tmp_path / "ck")
    coord = "127.0.0.1:%d" % launchutil.free_port()
    procs = []
    for rank in range(2):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=REPO, MXNET_TELEMETRY_DIR=teldir,
                   MXNET_TELEMETRY_HOST=str(rank),
                   MXNET_CHAOS="coordinator.timeout@0x1")
        procs.append(subprocess.Popen(
            [sys.executable, str(worker), coord, str(rank), ckdir],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    results = launchutil.communicate_all(procs)
    for rank, (p, (out, _)) in enumerate(zip(procs, results)):
        assert p.returncode == 0, out[-4000:]
        assert "SERIES_OK %d" % rank in out, out[-4000:]

    # one JSONL event log and one .prom snapshot per host
    jsonls = sorted(f for f in os.listdir(teldir) if f.endswith(".jsonl"))
    assert len(jsonls) == 2, jsonls
    assert {re.match(r"events_host(\d+)_", f).group(1)
            for f in jsonls} == {"0", "1"}
    proms = [f for f in os.listdir(teldir) if f.endswith(".prom")]
    assert len(proms) == 2, proms
    for f in proms:
        assert "elastic_checkpoint_save_seconds_count 2" \
            in open(os.path.join(teldir, f)).read()

    # merge stitches both hosts into ONE chrome trace
    out_path = str(tmp_path / "merged_trace.json")
    trace = telemetry.merge(teldir, out=out_path)
    tev = json.load(open(out_path))["traceEvents"]
    assert tev == trace["traceEvents"]
    metas = {e["args"]["name"] for e in tev if e.get("ph") == "M"}
    assert len(metas) == 2  # two host rows on one timeline
    names = [e["name"] for e in tev]
    assert names.count("elastic.checkpoint.save") == 4  # 2 hosts x 2 saves
    assert "chaos.injection" in names and "retry" in names
    assert "kvstore.push" in names and "dist.init" in names
    # and the CLI produces the same artifact
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "merge_traces.py"),
         teldir, "-o", str(tmp_path / "cli_trace.json")],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "2 process(es)" in r.stdout
