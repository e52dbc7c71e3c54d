"""Serving subsystem (`mxnet_tpu/serving/`): bucketing math, the
dynamic-batching engine (correctness, compile accounting, deadlines,
shedding, chaos-driven worker death + respawn, drain/shutdown), the
HTTP front end, and a launched end-to-end CLI server test."""
import http.client
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import chaos, telemetry, xla_stats
from mxnet_tpu.serving import (EngineConfig, InferenceEngine,
                               RequestRejected, batching, reqtrace,
                               serve)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import launchutil  # noqa: E402

IN_DIM = 12


def _mlp():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu", name="relu1")
    return mx.sym.FullyConnected(act, num_hidden=3, name="fc2")


def _init_params(net):
    exe = net.simple_bind(mx.cpu(), data=(2, IN_DIM))
    rng = np.random.RandomState(0)
    params = {}
    for name, arr in exe.arg_dict.items():
        if name == "data":
            continue
        arr[:] = (rng.randn(*arr.shape) * 0.1).astype(np.float32)
        params[name] = arr
    return params


def _np_forward(params, x):
    """Numpy reference — deliberately NOT an executor, so correctness
    checks add zero XLA compiles to the process (the compile-accounting
    assertions depend on that)."""
    h = x @ params["fc1_weight"].asnumpy().T \
        + params["fc1_bias"].asnumpy()
    h = np.maximum(h, 0.0)
    return h @ params["fc2_weight"].asnumpy().T \
        + params["fc2_bias"].asnumpy()


@pytest.fixture(scope="module")
def net():
    return _mlp()


@pytest.fixture(scope="module")
def params(net):
    return _init_params(net)


@pytest.fixture
def make_engine(net, params):
    engines = []

    def make(**cfg_kwargs):
        cfg = EngineConfig(**cfg_kwargs)
        eng = InferenceEngine(net.tojson(), dict(params),
                              {"data": (IN_DIM,)}, config=cfg)
        engines.append(eng)
        return eng

    yield make
    for eng in engines:
        eng.shutdown(drain=False)


def _x(n, seed=0):
    return np.random.RandomState(seed).rand(n, IN_DIM).astype(np.float32)


# ---------------------------------------------------------------------------
# bucketing math
# ---------------------------------------------------------------------------

def test_bucket_sizes():
    assert batching.bucket_sizes(1) == [1]
    assert batching.bucket_sizes(8) == [1, 2, 4, 8]
    assert batching.bucket_sizes(6) == [1, 2, 4, 6]
    assert batching.bucket_sizes(17) == [1, 2, 4, 8, 16, 17]
    with pytest.raises(ValueError):
        batching.bucket_sizes(0)


def test_pick_bucket():
    buckets = [1, 2, 4, 8]
    assert [batching.pick_bucket(n, buckets)
            for n in (1, 2, 3, 4, 5, 8)] == [1, 2, 4, 4, 8, 8]
    with pytest.raises(ValueError):
        batching.pick_bucket(9, buckets)


def test_pad_and_split_rows():
    arr = np.arange(6, dtype=np.float32).reshape(3, 2)
    assert batching.pad_rows(arr, 3) is arr        # full: no copy
    padded = batching.pad_rows(arr, 8)
    assert padded.shape == (8, 2)
    np.testing.assert_array_equal(padded[3:], np.tile(arr[-1], (5, 1)))
    with pytest.raises(ValueError):
        batching.pad_rows(arr, 2)
    parts = batching.split_rows(padded, [1, 2])    # pad rows dropped
    assert [p.shape[0] for p in parts] == [1, 2]
    np.testing.assert_array_equal(np.concatenate(parts), arr)


def test_engine_config_env(monkeypatch):
    monkeypatch.setenv("MXNET_SERVING_MAX_BATCH", "16")
    monkeypatch.setenv("MXNET_SERVING_MAX_DELAY_MS", "7.5")
    monkeypatch.setenv("MXNET_SERVING_QUEUE_DEPTH", "9")
    cfg = EngineConfig()
    assert (cfg.max_batch_size, cfg.max_batch_delay_ms,
            cfg.max_queue) == (16, 7.5, 9)
    # explicit args win over env
    assert EngineConfig(max_batch_size=4).max_batch_size == 4
    monkeypatch.setenv("MXNET_SERVING_MAX_BATCH", "junk")
    assert EngineConfig().max_batch_size == 8   # bad env -> default


# ---------------------------------------------------------------------------
# engine semantics
# ---------------------------------------------------------------------------

def test_engine_outputs_match_reference(make_engine, params):
    eng = make_engine(max_batch_size=4, max_batch_delay_ms=1.0)
    assert eng.buckets == [1, 2, 4]
    assert eng.warmup_compiles >= len(eng.buckets)
    for n in (1, 2, 3, 4):
        x = _x(n, seed=n)
        out = eng.predict({"data": x}, timeout=30)
        assert len(out) == 1 and out[0].shape == (n, 3)
        np.testing.assert_allclose(out[0], _np_forward(params, x),
                                   atol=1e-5)


def test_request_validation(make_engine):
    eng = make_engine(max_batch_size=4)
    with pytest.raises(mx.MXNetError, match="unknown 'datum'"):
        eng.submit({"datum": _x(1)})
    with pytest.raises(mx.MXNetError, match="missing 'data'"):
        eng.submit({})
    with pytest.raises(mx.MXNetError, match=r"must be \(n,\)"):
        eng.submit({"data": np.zeros((2, IN_DIM + 1), np.float32)})
    with pytest.raises(mx.MXNetError, match="at least one row"):
        eng.submit({"data": np.zeros((0, IN_DIM), np.float32)})
    with pytest.raises(mx.MXNetError, match="exceeds max_batch_size"):
        eng.submit({"data": _x(5)})


def test_concurrent_load_no_cold_compiles(make_engine, params):
    """THE acceptance test: >= 8 client threads, mixed request sizes,
    every response correct, the engine performs ZERO compiles after
    warm-up (all signatures bucket-bounded and pre-compiled) while the
    cache-hit counter does the serving — and every completed request's
    phase anatomy tiles its wall latency (sum of spans within 10%)."""
    reqtrace.reset()
    eng = make_engine(max_batch_size=8, max_batch_delay_ms=2.0,
                      max_queue=256)
    hits_before = xla_stats.compile_counts()["cache_hits"]

    def ok_count():
        m = telemetry.get_metric("serving_requests_total", status="ok")
        return m.value if m else 0.0

    def batch_count():
        entry = telemetry.snapshot().get("serving_batches_total")
        if not entry:
            return 0.0
        return sum(s["value"] for s in entry["series"] if s["labels"])

    ok_before = ok_count()
    batches_before = batch_count()
    n_threads, per_thread = 8, 20
    errors = []

    def client(cid):
        rng = np.random.RandomState(cid)
        for i in range(per_thread):
            n = 1 + (cid + i) % 5          # mixed sizes 1..5
            x = rng.rand(n, IN_DIM).astype(np.float32)
            try:
                out = eng.predict({"data": x}, timeout=60)
                np.testing.assert_allclose(
                    out[0], _np_forward(params, x), atol=1e-5)
            except Exception as exc:   # noqa: BLE001
                errors.append((cid, i, exc))

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errors, errors[:3]
    assert eng.cold_compiles() == 0        # zero compiles under load
    assert xla_stats.compile_counts()["cache_hits"] > hits_before
    assert ok_count() - ok_before == n_threads * per_thread
    # batching actually batched: fewer dispatches than requests served
    batches = batch_count() - batches_before
    assert 0 < batches < n_threads * per_thread

    # request anatomy: every completed request decomposed into the full
    # taxonomy, and the phase spans tile its measured wall latency
    # (telescoping boundaries -> within 10% is the loose public bound)
    recs = [r for r in reqtrace.tracer.records() if r["status"] == "ok"]
    assert len(recs) >= n_threads * per_thread
    for rec in recs:
        assert set(rec["phases"]) == set(reqtrace.PHASES)
        assert abs(sum(rec["phases"].values()) - rec["total"]) \
            <= 0.1 * max(rec["total"], 1e-9), rec
        assert rec["bucket"] in eng.buckets
        assert rec["batch"] is not None
    # pad accounting saw every dispatched batch
    pad = reqtrace.tracer.pad.snapshot()
    assert sum(b["batches"] for b in pad["buckets"].values()) \
        >= batches
    assert 0.0 <= pad["waste_ratio"] < 1.0
    # SLO: everything completed well under the default 250ms target
    slo = eng.stats()["slo"]
    assert slo["bad_total"] == 0
    assert slo["good_total"] >= n_threads * per_thread


def test_deadline_expired_at_submit(make_engine):
    eng = make_engine(max_batch_size=2)
    with pytest.raises(RequestRejected) as ei:
        eng.submit({"data": _x(1)}, deadline_ms=-5)
    assert ei.value.status == "expired"


def test_deadline_expires_while_queued(make_engine):
    eng = make_engine(max_batch_size=2, max_batch_delay_ms=0.0,
                      max_queue=8)
    # first batch stalls in the worker for 0.5 s; the second request's
    # 100 ms deadline passes while it waits behind it
    with chaos.armed("serving.slow_request", value="0.5"):
        f1 = eng.submit({"data": _x(1)})
        f2 = eng.submit({"data": _x(1)}, deadline_ms=100)
        with pytest.raises(RequestRejected) as ei:
            f2.result(timeout=30)
        assert ei.value.status == "expired"
        f1.result(timeout=30)   # the slow one still completes
    m = telemetry.get_metric("serving_requests_total", status="expired")
    assert m is not None and m.value >= 1


def test_load_shedding(make_engine):
    """Backpressure surfaces as RequestRejected(shed), not unbounded
    queueing: with stalled workers and a depth-2 queue, a flood of
    submissions mostly sheds, and everything that was accepted still
    completes."""
    eng = make_engine(max_batch_size=2, max_batch_delay_ms=0.0,
                      max_queue=2)
    shed_before = telemetry.counter("serving_requests_total",
                                    status="shed").value
    chaos.arm("serving.slow_request", times=100, value="0.2")
    futs, shed = [], 0
    for i in range(30):
        try:
            futs.append(eng.submit({"data": _x(1, seed=i)}))
        except RequestRejected as exc:
            assert exc.status == "shed"
            assert "retry" in str(exc)
            shed += 1
    assert shed > 0
    assert len(futs) >= 2          # bounded queue admitted some
    chaos.clear("serving.slow_request")
    for f in futs:
        assert f.result(timeout=60)[0].shape == (1, 3)
    delta = telemetry.counter("serving_requests_total",
                              status="shed").value - shed_before
    assert delta == shed


def test_worker_death_fails_inflight_and_respawns(make_engine, tmp_path,
                                                  monkeypatch):
    """Chaos serving.worker_death: ONLY the in-flight batch fails, the
    worker respawns, later requests succeed, and the crash leaves a
    flight-recorder post-mortem."""
    monkeypatch.setenv("MXNET_TELEMETRY_DIR", str(tmp_path))
    eng = make_engine(max_batch_size=2, max_batch_delay_ms=0.0)
    with chaos.armed("serving.worker_death"):
        fut = eng.submit({"data": _x(1)})
        with pytest.raises(mx.MXNetError, match="worker died mid-batch"):
            fut.result(timeout=30)
    assert chaos.fired("serving.worker_death") == 1
    # the respawned worker serves the NEXT request fine
    out = eng.predict({"data": _x(2)}, timeout=30)
    assert out[0].shape == (2, 3)
    assert telemetry.get_metric("serving_worker_deaths_total",
                                replica="0").value >= 1
    assert telemetry.counter("serving_worker_respawns_total").value >= 1
    rec = os.path.join(str(tmp_path), "flightrecorder-host%d.json"
                       % telemetry.host_id())
    assert os.path.exists(rec)
    doc = json.load(open(rec))
    assert doc["reason"] == "serving.worker_death"


def test_cancelled_future_does_not_kill_engine(make_engine):
    """A client cancelling a queued Future must not crash the batcher
    or worker when they later try to resolve it — the engine keeps
    serving and the request counts as ``cancelled``."""
    eng = make_engine(max_batch_size=2, max_batch_delay_ms=0.0,
                      max_queue=8)
    with chaos.armed("serving.slow_request", value="0.3"):
        f1 = eng.submit({"data": _x(1)})      # occupies the worker
        f2 = eng.submit({"data": _x(2, seed=1)})
        assert f2.cancel()                    # client walks away
        assert f1.result(timeout=30)[0].shape == (1, 3)
    # the threads that resolved the cancelled future are still alive
    out = eng.predict({"data": _x(1, seed=2)}, timeout=30)
    assert out[0].shape == (1, 3)
    m = telemetry.get_metric("serving_requests_total",
                             status="cancelled")
    assert m is not None and m.value >= 1


def test_drain_serves_out_then_rejects(make_engine):
    eng = make_engine(max_batch_size=2, max_batch_delay_ms=0.0,
                      max_queue=16)
    chaos.arm("serving.slow_request", value="0.2")
    futs = [eng.submit({"data": _x(1, seed=i)}) for i in range(3)]
    chaos.clear("serving.slow_request")
    assert eng.drain(timeout=60)
    for f in futs:
        assert f.result(timeout=1)[0].shape == (1, 3)   # already done
    with pytest.raises(RequestRejected) as ei:
        eng.submit({"data": _x(1)})
    assert ei.value.status == "closed"
    eng.shutdown()   # idempotent after drain


def test_shutdown_without_drain_fails_queued(make_engine):
    eng = make_engine(max_batch_size=2, max_batch_delay_ms=0.0,
                      max_queue=16)
    chaos.arm("serving.slow_request", times=20, value="0.3")
    futs = [eng.submit({"data": _x(1, seed=i)}) for i in range(6)]
    eng.shutdown(drain=False)
    statuses = set()
    for f in futs:
        try:
            f.result(timeout=30)
            statuses.add("ok")
        except RequestRejected as exc:
            statuses.add(exc.status)
    # whatever was already in flight may finish; the rest got "closed"
    assert "closed" in statuses
    assert statuses <= {"ok", "closed"}


# ---------------------------------------------------------------------------
# request anatomy: tail attribution + trace propagation
# ---------------------------------------------------------------------------

def test_report_names_queue_delay_under_load(make_engine):
    """Synthetic queue-delay fixture: a worker stalled by chaos makes
    requests tail in queue_wait/batch_wait, and the report CLI names
    that dominant p99 phase and says queue-bound."""
    import io
    reqtrace.reset()
    eng = make_engine(max_batch_size=2, max_batch_delay_ms=0.0,
                      max_queue=64)
    # one warm request so the head of the window is fast
    for i in range(10):
        eng.predict({"data": _x(1, seed=i)}, timeout=30)
    # the stall: each batch sleeps 50ms, so later submissions queue
    chaos.arm("serving.slow_request", times=10, value="0.05")
    futs = [eng.submit({"data": _x(1, seed=100 + i)}) for i in range(8)]
    for f in futs:
        f.result(timeout=60)
    chaos.clear("serving.slow_request")
    out = io.StringIO()
    assert reqtrace.report(out=out) == 0
    text = out.getvalue()
    machine = json.loads(text.strip().splitlines()[-1])
    assert machine["verdict"] == "queue-bound", text
    assert machine["dominant_p99_phase"] in ("queue_wait", "batch_wait")
    assert ("dominant p99 phase: %s" % machine["dominant_p99_phase"]) \
        in text
    # zero cold compiles even through the chaos-stalled tail
    assert eng.cold_compiles() == 0


def test_engine_propagates_rid_and_rejections_carry_it(make_engine):
    eng = make_engine(max_batch_size=2, max_batch_delay_ms=0.0)
    reqtrace.reset()
    eng.predict({"data": _x(1)}, timeout=30, rid="my-trace-1")
    recs = reqtrace.tracer.records()
    assert [r["rid"] for r in recs] == ["my-trace-1"]
    with pytest.raises(RequestRejected) as ei:
        eng.submit({"data": _x(1)}, deadline_ms=-5, rid="dead-1")
    assert ei.value.rid == "dead-1"
    assert reqtrace.tracer.counts().get("expired", 0) >= 1


def test_http_trace_propagation_end_to_end(make_engine, tmp_path):
    """THE propagation test: X-Request-Id in -> the engine's
    serving.request span lands in the telemetry JSONL with that id,
    the serving.batch span links it in args.rids, and the response
    echoes the header back."""
    telemetry.configure(str(tmp_path))
    try:
        eng = make_engine(max_batch_size=4, max_batch_delay_ms=1.0)
        srv = serve(eng, port=0)
        rid = "e2e-trace-42"
        try:
            conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                              timeout=30)
            body = json.dumps({"inputs": {"data": _x(2).tolist()}})
            conn.request("POST", "/predict", body,
                         {"Content-Type": "application/json",
                          "X-Request-Id": rid})
            resp = conn.getresponse()
            raw = resp.read()
            assert resp.status == 200, raw
            assert resp.getheader("X-Request-Id") == rid
            conn.close()

            # error responses carry the trace id too
            conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                              timeout=30)
            conn.request("POST", "/predict",
                         json.dumps({"inputs": {"datum": [[0.0]]}}),
                         {"Content-Type": "application/json",
                          "X-Request-Id": "bad-input-7"})
            resp = conn.getresponse()
            doc = json.loads(resp.read())
            assert resp.status == 400
            assert doc["request_id"] == "bad-input-7"
            conn.close()
        finally:
            srv.stop()
        telemetry.flush()
        events = []
        for fn in os.listdir(str(tmp_path)):
            if fn.endswith(".jsonl"):
                events.extend(telemetry.read_events(
                    os.path.join(str(tmp_path), fn)))
        req_spans = [e for e in events if e["name"] == "serving.request"
                     and e["args"].get("rid") == rid]
        assert len(req_spans) == 1, [e["name"] for e in events][:20]
        span = req_spans[0]
        assert span["ph"] == "X"
        assert span["args"]["status"] == "ok"
        phases = span["args"]["phases"]
        assert set(phases) == set(reqtrace.PHASES)
        assert abs(sum(phases.values()) - span["dur"]) \
            <= 0.1 * span["dur"] + 1e-6
        batch_spans = [e for e in events if e["name"] == "serving.batch"
                       and rid in (e["args"].get("rids") or [])]
        assert len(batch_spans) == 1
        assert batch_spans[0]["args"]["batch"] == span["args"]["batch"]
        # per-route metrics counted both requests
        m = telemetry.get_metric("serving_http_requests_total",
                                 route="/predict", code="200")
        assert m is not None and m.value >= 1
        m = telemetry.get_metric("serving_http_requests_total",
                                 route="/predict", code="400")
        assert m is not None and m.value >= 1
    finally:
        telemetry.configure(None)


def test_healthz_reports_saturation(make_engine):
    eng = make_engine(max_batch_size=2)
    srv = serve(eng, port=0)
    try:
        code, _, raw = _http(srv.port, "GET", "/healthz")
        doc = json.loads(raw)
        assert code == 200
        # the load-balancer saturation triple: queue depth, in-flight,
        # SLO burn rate per window
        assert "queue_depth" in doc and "pending" in doc
        assert set(doc["slo"]["burn_rate"]) \
            == {str(w) for w in eng._slo.windows}
        assert doc["slo"]["target_ms"] == eng._slo.target_ms
    finally:
        srv.stop()


def test_metrics_exposes_anatomy_series(make_engine):
    eng = make_engine(max_batch_size=4, max_batch_delay_ms=0.0)
    srv = serve(eng, port=0)
    try:
        eng.predict({"data": _x(3)}, timeout=30)
        code, _, raw = _http(srv.port, "GET", "/metrics")
        text = raw.decode()
        assert code == 200
        for series in ("serving_req_phase_seconds",
                       "serving_pad_waste_ratio",
                       "serving_bucket_occupancy",
                       "serving_slo_burn_rate",
                       "serving_slo_target_ms",
                       "serving_http_seconds"):
            assert series in text, series
        assert 'phase="queue_wait"' in text
        assert 'phase="device_compute"' in text
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# HTTP front end
# ---------------------------------------------------------------------------

def _http(port, method, path, body=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request(method, path,
                     json.dumps(body) if body is not None else None,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        return resp.status, resp.getheader("Content-Type"), raw
    finally:
        conn.close()


def test_http_server(make_engine, params):
    eng = make_engine(max_batch_size=4, max_batch_delay_ms=1.0)
    srv = serve(eng, port=0, allow_shutdown=True)
    try:
        x = _x(3, seed=7)
        code, ctype, raw = _http(srv.port, "POST", "/predict",
                                 {"inputs": {"data": x.tolist()}})
        assert code == 200 and ctype == "application/json"
        doc = json.loads(raw)
        assert doc["shapes"] == [[3, 3]]
        np.testing.assert_allclose(np.asarray(doc["outputs"][0]),
                                   _np_forward(params, x), atol=1e-4)

        code, _, raw = _http(srv.port, "GET", "/healthz")
        health = json.loads(raw)
        assert code == 200 and health["status"] == "ok"
        assert health["platform"] == "cpu"   # where the buffers are

        code, ctype, raw = _http(srv.port, "GET", "/metrics")
        text = raw.decode()
        assert code == 200 and ctype.startswith("text/plain")
        for series in ("serving_requests_total", "serving_total_seconds",
                       "serving_queue_wait_seconds",
                       "serving_compute_seconds", "jit_compiles_total"):
            assert series in text, series

        # error mapping: bad JSON -> 400, unknown input -> 400,
        # missing body -> 400, bad route -> 404
        assert _http(srv.port, "POST", "/predict",
                     {"inputs": {"datum": [[0.0] * IN_DIM]}})[0] == 400
        assert _http(srv.port, "POST", "/predict", {"nope": 1})[0] == 400
        assert _http(srv.port, "GET", "/nothere")[0] == 404

        # deadline already expired -> 504 (Gateway Timeout semantics)
        code, _, raw = _http(srv.port, "POST", "/predict",
                             {"inputs": {"data": x.tolist()},
                              "deadline_ms": -1})
        assert code == 504 and json.loads(raw)["status"] == "expired"
    finally:
        srv.stop()
    # stop() drained the engine: health gone, submits rejected
    with pytest.raises(RequestRejected):
        eng.submit({"data": _x(1)})


# ---------------------------------------------------------------------------
# launched: the CLI server end-to-end over a real socket
# ---------------------------------------------------------------------------

@pytest.mark.launched
@pytest.mark.timeout(150)
def test_launched_cli_server(net, params, tmp_path):
    sym_path = str(tmp_path / "net.json")
    with open(sym_path, "w") as fh:
        fh.write(net.tojson())
    params_path = str(tmp_path / "net.params")
    mx.nd.save(params_path,
               {"arg:%s" % k: v for k, v in params.items()})

    # the CLI turns the persistent compile cache on; the tests leave
    # nothing in the checkout, so this child runs with it off
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="0",
               PYTHONPATH=REPO, MXNET_SERVING_MAX_BATCH="4")
    proc = subprocess.Popen(
        [sys.executable, "-m", "mxnet_tpu.serving.server",
         "--symbol", sym_path, "--params", params_path,
         "--input", "data:%d" % IN_DIM, "--port", "0",
         "--allow-shutdown"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    try:
        # the SERVING line prints once every bucket is warm-compiled
        deadline = time.monotonic() + launchutil.LAUNCH_TIMEOUT
        line = ""
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if line.startswith("SERVING ") or not line:
                break
        assert line.startswith("SERVING "), line
        info = json.loads(line[len("SERVING "):])
        port = info["port"]
        assert info["buckets"] == [1, 2, 4]
        assert info["warmup_compiles"] >= 3
        assert info["platform"] == "cpu"   # JAX_PLATFORMS=cpu keeps it

        x = _x(3, seed=9)
        code, _, raw = _http(port, "POST", "/predict",
                             {"inputs": {"data": x.tolist()}})
        assert code == 200
        np.testing.assert_allclose(
            np.asarray(json.loads(raw)["outputs"][0]),
            _np_forward(params, x), atol=1e-4)

        code, _, raw = _http(port, "GET", "/metrics")
        text = raw.decode()
        assert code == 200
        assert 'serving_requests_total{status="ok"} 1' in text
        assert "serving_total_seconds" in text

        assert _http(port, "POST", "/shutdown")[0] == 200
        out, _ = launchutil.communicate(proc)
        assert proc.returncode == 0, out[-4000:]
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
