"""chip_smoke.py on the CPU: the parent's phase runner against fake
phases, the compile-cache helper, and a tiny-width run of each phase's
body through the same functions the chip run calls (rehearsal 1 of
/opt/skills/guides/on-chip-measurement §2). The chip run itself is
`python chip_smoke.py` on a machine with a TPU."""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402  (stdlib only at import)

TINY = {
    "model": "resnet18_v1", "classes": 10, "image": 64,
    "train_batch": 16, "train_batches": 10, "scan_k": 5, "scan_unroll": 1,
    "scan_epochs": 2, "predict_batch": 4, "serve_max_batch": 4,
    "serve_sizes": (1, 2, 1),
    "lstm": {"vocab": 50, "hidden": 16, "layers": 2, "batch": 4,
             "bptt": 5, "batches": 4},
    "dp_steps": 3, "burn_n": 128, "burn_iters": 10,
}
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def _fake(code):
    return [sys.executable, "-c", code]


def _says(device, ok=True):
    return _fake("print('a line of the phase'); print(%r + %r)" % (
        chip_smoke.RESULT, json.dumps({"ok": ok, "device": device})))


def _last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_passing_run_prints_exactly_the_contract_line(capsys):
    ok, device = chip_smoke.run_phases([
        ("device", _says(dict(TPU, extra="ignored")), 30),
        ("serve", _says({"platform": "tpu"}), 30)])
    assert chip_smoke.finish(ok, device) == 0
    out = capsys.readouterr().out
    assert "a line of the phase" in out      # passed through
    last = out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": TPU}
    assert list(json.loads(last)["device"]) == ["platform", "kind", "count"]


@pytest.mark.parametrize("phase, why", [
    (_fake("import sys; sys.exit(3)"), "exit code 3"),
    (_says({"platform": "cpu", "kind": "cpu", "count": 8}), "not 'tpu'"),
    (_fake("import time; time.sleep(60)"), "timed out"),
    (_says(TPU, ok=False), "FAILED"),
    (_fake("print('no result line')"), "no result line"),
], ids=["nonzero-exit", "non-tpu-device", "timeout", "not-ok", "silent"])
def test_failing_phase_fails_the_run(phase, why, capsys):
    ran_after = os.path.join(REPO, "never-written")
    ok, device = chip_smoke.run_phases([
        ("device", _says(TPU), 30), ("bad", phase, 3),
        ("after", _fake("open(%r, 'w')" % ran_after), 30)])
    assert not ok
    assert chip_smoke.finish(ok, device) == 1
    out = capsys.readouterr().out
    assert why in out
    assert json.loads(out.strip().splitlines()[-1])["ok"] is False
    assert not os.path.exists(ran_after)     # the run stops at a failure


def test_timed_out_phase_leaves_no_process(tmp_path):
    # a phase that starts a child of its own (as phase 4 starts the
    # server): the whole group goes when the phase is killed
    pidfile = tmp_path / "pid"
    code = ("import subprocess, sys, time\n"
            "p = subprocess.Popen([sys.executable, '-c', "
            "'import time; time.sleep(120)'])\n"
            "open(%r, 'w').write(str(p.pid)); time.sleep(120)\n"
            % str(pidfile))
    res = chip_smoke.run_phase("hangs", _fake(code), 5)
    assert res["ok"] is False and "timed out" in res["error"]
    stat = "/proc/%d/stat" % int(pidfile.read_text())
    # gone, or a zombie waiting for init to reap it: not running
    assert not os.path.exists(stat) or \
        open(stat).read().rsplit(")", 1)[1].split()[0] == "Z"


def test_hidden_tpu_fails_without_carrying_on(capfd):
    """`python chip_smoke.py` with JAX_PLATFORMS=cpu, as the driver's
    sandbox runs it: non-zero, "ok": false, and no phase after the first."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="0")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert "phase device FAILED" in r.stdout
    assert "phase train" not in r.stdout


def test_cache_helper_is_placed_from_outside(monkeypatch):
    import jax
    from mxnet_tpu import compiled
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compiled.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before  # nothing set
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.setattr(jax.config, "update", lambda *a: None)
    fixed = compiled.enable_compile_cache()
    assert fixed == os.path.join(REPO, ".jax_cache") \
        == compiled.COMPILE_CACHE_DIR


# -- rehearsal 1: every phase's body at a tiny width, on the CPU -------------

@pytest.fixture
def work(tmp_path, monkeypatch):
    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path))
    return tmp_path


def test_phase_device_body(work):
    facts = chip_smoke.phase_device(TINY, "cpu")
    assert facts["device"]["platform"] == "cpu"
    with pytest.raises(chip_smoke.SmokeFailure, match="not 'tpu'"):
        chip_smoke.phase_device(TINY, "tpu")


def test_phase_train_body(work):
    assert chip_smoke.phase_train(TINY, "cpu")["device"]["count"] >= 1


def test_phase_predict_then_serve_bodies(work, monkeypatch):
    chip_smoke.phase_predict(TINY, "cpu")
    # the server child: CPU, and the persistent cache off under test
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("JAX_ENABLE_COMPILATION_CACHE", "0")
    facts = chip_smoke.phase_serve(TINY, "cpu")
    assert facts["device"] == {"platform": "cpu"}
    with pytest.raises(chip_smoke.SmokeFailure, match="buffers are on"):
        chip_smoke.phase_serve(TINY, "tpu")


def test_phase_lstm_body(work):
    chip_smoke.phase_lstm(TINY, "cpu")


def test_phase_dp4_body(work):
    # rehearsal 2: the four-chip path on four of the virtual CPU devices
    chip_smoke.phase_dp4(TINY, "cpu")
