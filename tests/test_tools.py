"""Tools tests: im2rec list+rec round trip, rec2idx, parse_log."""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx

TOOLS = os.path.join(os.path.dirname(__file__), "..", "tools")
ENV = dict(os.environ, PYTHONPATH=os.path.join(TOOLS, ".."))


def _make_image_tree(root):
    from mxnet_tpu.image import codec
    rng = np.random.RandomState(0)
    for cls in ["cat", "dog"]:
        os.makedirs(os.path.join(root, cls), exist_ok=True)
        for i in range(3):
            img = (rng.rand(12, 14, 3) * 255).astype("uint8")
            buf = codec.imencode(img, ".jpg", quality=95)
            with open(os.path.join(root, cls, "%d.jpg" % i), "wb") as f:
                f.write(buf)


def _run(script, *args):
    return subprocess.run(
        [sys.executable, os.path.join(TOOLS, script)] + list(args),
        capture_output=True, text=True, env=ENV)


def test_im2rec_roundtrip(tmp_path):
    root = str(tmp_path / "imgs")
    _make_image_tree(root)
    prefix = str(tmp_path / "data")
    r = _run("im2rec.py", prefix, root, "--list", "--recursive")
    assert r.returncode == 0, r.stderr
    lst = prefix + ".lst"
    assert os.path.exists(lst)
    lines = open(lst).read().strip().split("\n")
    assert len(lines) == 6
    labels = {float(l.split("\t")[1]) for l in lines}
    assert labels == {0.0, 1.0}

    r = _run("im2rec.py", prefix, root)
    assert r.returncode == 0, r.stderr
    assert os.path.exists(prefix + ".rec") and os.path.exists(
        prefix + ".idx")

    # records decode back to images with matching labels
    from mxnet_tpu import recordio
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "r")
    n = 0
    for line in lines:
        idx = int(line.split("\t")[0])
        header, img = recordio.unpack_img(rec.read_idx(idx))
        assert img.shape == (12, 14, 3)
        assert float(header.label) in (0.0, 1.0)
        n += 1
    assert n == 6
    rec.close()


def test_rec2idx(tmp_path):
    from mxnet_tpu import recordio
    rec_path = str(tmp_path / "x.rec")
    w = recordio.MXIndexedRecordIO(str(tmp_path / "orig.idx"), rec_path, "w")
    for i in range(5):
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i), i, 0), b"payload%d" % i))
    w.close()
    r = _run("rec2idx.py", rec_path, str(tmp_path / "rebuilt.idx"))
    assert r.returncode == 0, r.stderr
    orig = open(str(tmp_path / "orig.idx")).read()
    rebuilt = open(str(tmp_path / "rebuilt.idx")).read()
    assert orig == rebuilt


def test_parse_log(tmp_path):
    log = tmp_path / "train.log"
    log.write_text(
        "INFO Epoch[0] Train-accuracy=0.5\n"
        "INFO Epoch[0] Time cost=10.0\n"
        "INFO Epoch[0] Validation-accuracy=0.55\n"
        "INFO Epoch[1] Train-accuracy=0.8\n"
        "INFO Epoch[1] Time cost=9.0\n"
        "INFO Epoch[1] Validation-accuracy=0.75\n")
    r = _run("parse_log.py", str(log))
    assert r.returncode == 0, r.stderr
    assert "| epoch |" in r.stdout
    assert "0.800000" in r.stdout and "0.750000" in r.stdout
    r = _run("parse_log.py", str(log), "--format", "none")
    assert "train-accuracy" in r.stdout


def test_launch_local_spawns_workers(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(
        "import os\n"
        "print('rank', os.environ['DMLC_WORKER_ID'],"
        " 'of', os.environ['DMLC_NUM_WORKER'])\n")
    r = _run("launch.py", "-n", "2", sys.executable, str(script))
    assert r.returncode == 0, r.stderr


def test_launch_dist_sync_kvstore(tmp_path):
    """2-process dist_sync consistency over the local launcher — the
    reference's tests/nightly/dist_sync_kvstore.py trick of running the
    real transport on one machine (ci/docker/runtime_functions.sh:551)."""
    worker = tmp_path / "worker.py"
    worker.write_text(
        "import numpy as np\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu.parallel import dist\n"
        "dist.init()\n"
        "kv = mx.kv.create('dist_sync')\n"
        "rank, nw = kv.rank, kv.num_workers\n"
        "assert nw == 2, nw\n"
        "kv.init('w', mx.nd.zeros((3, 4)))\n"
        "kv.push('w', mx.nd.ones((3, 4)) * (rank + 1))\n"
        "out = mx.nd.zeros((3, 4))\n"
        "kv.pull('w', out=out)\n"
        "np.testing.assert_allclose(out.asnumpy(), 3.0)\n"
        "kv.barrier()\n"
        "rid = mx.nd.array(np.array([1], 'f'))\n"
        "kv.row_sparse_pull('w', out=out, row_ids=rid)\n"
        "np.testing.assert_allclose(out.asnumpy()[1], 3.0)\n"
        "np.testing.assert_allclose(out.asnumpy()[0], 0.0)\n"
        "print('DIST WORKER', rank, 'OK')\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(TOOLS, ".."))
    env.pop("JAX_PLATFORMS", None)  # launcher pins cpu itself
    r = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "launch.py"), "-n", "2",
         "--port", "9441", "--", sys.executable, str(worker)],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr + r.stdout
    assert r.stdout.count("OK") == 2


def test_launch_dist_training_converges(tmp_path):
    """2-process data-parallel Module training over dist_sync — the
    reference's tests/nightly/dist_lenet.py convergence check run with
    the local launcher. Each worker fits its shard; synced params must
    classify the full set."""
    worker = tmp_path / "train_worker.py"
    worker.write_text(
        "import numpy as np\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu.parallel import dist\n"
        "dist.init()\n"
        "kv = mx.kv.create('dist_sync')\n"
        "rank, nw = kv.rank, kv.num_workers\n"
        "rng = np.random.RandomState(0)\n"
        "protos = rng.rand(4, 16).astype('f') * 2\n"
        "y = rng.randint(0, 4, 800)\n"
        "X = protos[y] + rng.randn(800, 16).astype('f') * 0.1\n"
        "sl = slice(rank * 400, (rank + 1) * 400)  # worker shard\n"
        "train = mx.io.NDArrayIter(X[sl], y[sl].astype('f'), 50,\n"
        "                          shuffle=True)\n"
        "data = mx.sym.var('data')\n"
        "net = mx.sym.FullyConnected(data, num_hidden=32, name='fc1')\n"
        "net = mx.sym.Activation(net, act_type='relu')\n"
        "net = mx.sym.FullyConnected(net, num_hidden=4, name='fc2')\n"
        "net = mx.sym.SoftmaxOutput(net, name='softmax')\n"
        "mod = mx.mod.Module(net)\n"
        "mod.fit(train, optimizer='sgd', initializer=mx.init.Xavier(),\n"
        "        optimizer_params={'learning_rate': 0.3}, num_epoch=6,\n"
        "        kvstore=kv)\n"
        "val = mx.io.NDArrayIter(X, y.astype('f'), 50)\n"
        "acc = dict(mod.score(val, 'acc'))['accuracy']\n"
        "assert acc > 0.9, acc\n"
        "# params must be identical across workers after sync training;\n"
        "# each worker prints a digest and the harness compares them\n"
        "arg_params, _ = mod.get_params()\n"
        "w = arg_params['fc1_weight'].asnumpy()\n"
        "digest = float(np.abs(w).sum())\n"
        "print('DIGEST %.6f' % digest)\n"
        "print('DIST TRAIN', rank, 'acc %.3f OK' % acc)\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(TOOLS, ".."))
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "launch.py"), "-n", "2",
         "--port", "9443", "--", sys.executable, str(worker)],
        capture_output=True, text=True, env=env, timeout=420)
    assert r.returncode == 0, r.stderr + r.stdout
    assert r.stdout.count("OK") == 2
    digests = re.findall(r"DIGEST ([0-9.]+)", r.stdout)
    assert len(digests) == 2 and digests[0] == digests[1], digests


def test_launch_dist_gluon_trainer_local_update(tmp_path):
    """2-process gluon Trainer with update_on_kvstore=False: gradients
    sync through the store while the updater runs locally — workers must
    still end bit-identical, which requires the rank-0 init broadcast +
    pull-after-init (reference Trainer._init_kvstore)."""
    worker = tmp_path / "gluon_worker.py"
    worker.write_text(
        "import numpy as np\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu import autograd, gluon\n"
        "from mxnet_tpu.parallel import dist\n"
        "dist.init()\n"
        "kv = mx.kv.create('dist_sync')\n"
        "rank = kv.rank\n"
        "rng = np.random.RandomState(100 + rank)  # divergent local init\n"
        "mx.random.seed(100 + rank)\n"
        "X = rng.rand(200, 8).astype('f')\n"
        "y = (X.sum(1) > 4).astype('f')\n"
        "net = gluon.nn.Dense(1)\n"
        "net.initialize(mx.init.Xavier())\n"
        "net(mx.nd.zeros((2, 8)))  # materialize (per-rank different!)\n"
        "tr = gluon.Trainer(net.collect_params(), 'sgd',\n"
        "                   {'learning_rate': 0.1}, kvstore=kv,\n"
        "                   update_on_kvstore=False)\n"
        "loss_fn = gluon.loss.SigmoidBinaryCrossEntropyLoss()\n"
        "for step in range(5):\n"
        "    i = step * 40\n"
        "    d = mx.nd.array(X[i:i+40]); l = mx.nd.array(y[i:i+40])\n"
        "    with autograd.record():\n"
        "        loss = loss_fn(net(d), l)\n"
        "    loss.backward()\n"
        "    tr.step(40)\n"
        "w = net.weight.data().asnumpy()\n"
        "print('DIGEST %.8f' % float(np.abs(w).sum()))\n"
        "print('GLUON DIST', rank, 'OK')\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(TOOLS, ".."))
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "launch.py"), "-n", "2",
         "--port", "9447", "--", sys.executable, str(worker)],
        capture_output=True, text=True, env=env, timeout=420)
    assert r.returncode == 0, r.stderr + r.stdout
    assert r.stdout.count("OK") == 2
    digests = re.findall(r"DIGEST ([0-9.]+)", r.stdout)
    assert len(digests) == 2 and digests[0] == digests[1], digests


def test_bench_all_child_failure_is_an_error(monkeypatch, capsys):
    """A benchmark child that exits non-zero is an error whatever it
    printed, and a failed config makes the tool itself exit non-zero."""
    sys.path.insert(0, TOOLS)
    import bench_all
    with pytest.raises(RuntimeError, match="exited 3"):
        bench_all._run([sys.executable, "-c",
                        "print('123.0 img/s train'); raise SystemExit(3)"])
    monkeypatch.setitem(bench_all.CONFIGS, "lstm_ptb", lambda: bench_all._run(
        [sys.executable, "-c", "raise SystemExit(1)"]))
    monkeypatch.setattr(sys, "argv", ["bench_all.py", "--only", "lstm_ptb"])
    assert bench_all.main() == 1
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["value"] is None and "exited 1" in rec["error"]
    assert "jax" not in bench_all.__dict__   # the parent stays off jax


def test_launch_refuses_several_local_workers_on_the_tpu():
    """A host's chips belong to one process: `-n 2 --platform tpu` is an
    error at once, not two workers of which the second hangs."""
    r = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "launch.py"), "-n", "2",
         "--platform", "tpu", "--", sys.executable, "-c", "print('ran')"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 2
    assert "one process at a time" in r.stderr and "ran" not in r.stdout


def test_bench_all_emits_json_records(tmp_path):
    """tools/bench_all.py records a north-star config as a bench.py-style
    JSON line + combined file (VERDICT r3 #7: per-round regression
    record for the BASELINE.md configs)."""
    import json
    out = tmp_path / "rec.json"
    r = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                      "bench_all.py"),
         "--only", "sparse_fm", "--out", str(out)],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "sparse_fm_samples_per_sec"
    assert rec["value"] and rec["value"] > 0
    saved = json.loads(out.read_text())
    assert saved[0]["metric"] == rec["metric"]


def test_serve_bench_closed_loop(tmp_path):
    """serve_bench: closed loop against the demo engine emits the
    BENCH-style metric lines and they parse through bench_gate."""
    import json
    out = str(tmp_path / "serve.jsonl")
    r = _run("serve_bench.py", "--mode", "closed", "--clients", "2",
             "--requests", "3", "--sizes", "1,2", "--out", out)
    assert r.returncode == 0, r.stderr
    sys.path.insert(0, TOOLS)
    import bench_gate
    recs = bench_gate.parse_lines(open(out).read().splitlines())
    metrics = {rec["metric"]: rec for rec in recs}
    for name in ("serving_warmup_compiles", "serving_closed_rps",
                 "serving_closed_rows_per_sec", "serving_closed_p50_ms",
                 "serving_closed_p95_ms", "serving_closed_p99_ms",
                 "serving_cold_compiles"):
        assert name in metrics, (name, sorted(metrics))
    assert metrics["serving_closed_rps"]["value"] > 0
    assert metrics["serving_cold_compiles"]["value"] == 0
    # 2 clients x 3 requests, none rejected in an unloaded engine
    assert "serving_closed_shed_total" not in metrics
    # the p99 line carries the request anatomy (phase shares + verdict)
    # so a latency regression gates pre-diagnosed, TRAIN-style
    p99 = metrics["serving_closed_p99_ms"]
    assert p99.get("verdict")
    assert p99.get("phases") and abs(sum(p99["phases"].values()) - 1.0) \
        < 0.01
    assert metrics["serving_closed_pad_waste_ratio"]["value"] >= 0.0
