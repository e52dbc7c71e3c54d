"""Elastic training supervisor (`parallel/elastic.py`): commit-marked
step-numbered checkpoints with retention, resume-from-latest, chaos-driven
recovery (injected step failures, coordinator timeouts, torn checkpoint
writes), the fit(elastic=...) hook, and — launched — a 2-process run that
loses a worker mid-run and finishes after a supervised restart from the
last complete checkpoint."""
import os
import subprocess
import sys

import numpy as np
import pytest
import jax.numpy as jnp

import launchutil
import mxnet_tpu as mx
from mxnet_tpu import chaos
from mxnet_tpu.parallel import (ElasticCheckpointer, ElasticTrainer,
                                RetryPolicy, RetryError, abstract_like,
                                elastic, load_sharded)
from mxnet_tpu.parallel import retry as retry_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def no_sleep(monkeypatch):
    sleeps = []
    monkeypatch.setattr(retry_mod, "_sleep", sleeps.append)
    return sleeps


def _count_step(state, step):
    return {"w": state["w"] + 1.0}


# ---------------------------------------------------------------------------
# checkpointer: commit marker, rotation, torn writes
# ---------------------------------------------------------------------------

def test_checkpointer_commit_and_restore(tmp_path):
    ck = ElasticCheckpointer(str(tmp_path / "ck"), keep_last=3)
    tree = {"w": jnp.arange(4.0)}
    ck.save(5, tree)
    assert ck.latest_step() == 5
    assert ck.is_complete(5)
    step, out = ck.restore(abstract_like(tree))
    assert step == 5
    np.testing.assert_allclose(np.asarray(out["w"]), np.arange(4.0))


def test_torn_checkpoint_never_restored(tmp_path):
    """chaos interrupts the write after the payload but before the COMMIT
    marker: the torn step is invisible to latest_step/restore and reaped
    once a newer commit lands."""
    ck = ElasticCheckpointer(str(tmp_path / "ck"), keep_last=3)
    tree = {"w": jnp.arange(4.0)}
    ck.save(5, tree)
    chaos.arm("checkpoint.interrupt")
    with pytest.raises(chaos.ChaosInterrupt):
        ck.save(10, {"w": jnp.arange(4.0) * 3})
    assert os.path.exists(ck.step_dir(10))  # payload landed...
    assert not ck.is_complete(10)           # ...but was never committed
    assert ck.latest_step() == 5
    with pytest.raises(ValueError, match="not committed"):
        ck.restore(abstract_like(tree), step=10)
    ck.save(11, tree)  # newer commit: retention reaps the torn dir
    assert not os.path.exists(ck.step_dir(10))


def test_retention_keeps_last_n(tmp_path):
    ck = ElasticCheckpointer(str(tmp_path / "ck"), keep_last=2)
    tree = {"w": jnp.zeros(3)}
    for s in (1, 2, 3, 4):
        ck.save(s, tree)
    assert ck.steps() == [3, 4]
    assert not os.path.exists(ck.step_dir(1))


def test_restore_with_no_checkpoint_raises(tmp_path):
    ck = ElasticCheckpointer(str(tmp_path / "empty"))
    with pytest.raises(FileNotFoundError, match="COMMIT"):
        ck.restore(abstract_like({"w": jnp.zeros(2)}))


# ---------------------------------------------------------------------------
# load_sharded error contract (satellite: no raw orbax tracebacks)
# ---------------------------------------------------------------------------

def test_load_sharded_missing_path_clear_error(tmp_path):
    tmpl = abstract_like({"w": jnp.zeros(2)})
    missing = str(tmp_path / "nope")
    with pytest.raises(FileNotFoundError, match="commit marker"):
        load_sharded(missing, tmpl)
    with pytest.raises(FileNotFoundError, match="nope"):
        load_sharded(missing, tmpl)


def test_load_sharded_torn_dir_clear_error(tmp_path):
    torn = tmp_path / "step_00000001" / "state"
    torn.mkdir(parents=True)
    (torn / "junk").write_text("not a checkpoint")
    with pytest.raises(ValueError, match="commit marker: absent"):
        load_sharded(str(torn), abstract_like({"w": jnp.zeros(2)}))


def test_local_backend_template_mismatch(tmp_path):
    ck = ElasticCheckpointer(str(tmp_path / "ck"), backend="local")
    ck.save(1, {"a": jnp.zeros(2), "b": jnp.zeros(3)})
    with pytest.raises(ValueError, match="2 saved leaves vs 3"):
        ck.restore(abstract_like({"a": jnp.zeros(2), "b": jnp.zeros(3),
                                  "c": jnp.zeros(4)}), step=1)
    with pytest.raises(ValueError, match="leaf shape"):
        ck.restore(abstract_like({"a": jnp.zeros(2), "b": jnp.zeros(9)}),
                   step=1)


# ---------------------------------------------------------------------------
# trainer: resume, recovery, retried liveness polls
# ---------------------------------------------------------------------------

def test_trainer_checkpoints_and_resumes(tmp_path, no_sleep):
    root = str(tmp_path / "ck")
    t = ElasticTrainer(_count_step, {"w": jnp.zeros(3)}, ckpt_dir=root,
                       ckpt_every=2, on_failure="recover")
    out = t.run(5)
    np.testing.assert_allclose(np.asarray(out["w"]), 5.0)
    assert t.ckpt.latest_step() == 5  # final save
    calls = []

    def counting(state, step):
        calls.append(step)
        return _count_step(state, step)

    t2 = ElasticTrainer(counting, {"w": jnp.zeros(3)}, ckpt_dir=root,
                        ckpt_every=2, on_failure="recover")
    marker_mtime = os.path.getmtime(
        os.path.join(t.ckpt.step_dir(5), "COMMIT"))
    out2 = t2.run(5)
    assert calls == [] and t2.resumed_from == 5  # nothing left to do
    np.testing.assert_allclose(np.asarray(out2["w"]), 5.0)
    # a no-op resume must not rewrite the existing commit
    assert os.path.getmtime(
        os.path.join(t2.ckpt.step_dir(5), "COMMIT")) == marker_mtime
    assert t2.ckpt.latest_step() == 5
    # resumed past num_steps: no mislabeled earlier-step commit either
    t3 = ElasticTrainer(counting, {"w": jnp.zeros(3)}, ckpt_dir=root,
                        ckpt_every=2, on_failure="recover")
    t3.run(3)
    assert calls == [] and not t3.ckpt.is_complete(3)


def test_trainer_recovers_from_step_failures_with_backoff(tmp_path,
                                                          no_sleep):
    chaos.arm("step.fail", after=3, times=2)
    t = ElasticTrainer(
        _count_step, {"w": jnp.zeros(2)}, ckpt_dir=str(tmp_path / "ck"),
        ckpt_every=2, max_restarts=3, on_failure="recover",
        retry_policy=RetryPolicy(max_attempts=4, base_delay=0.1,
                                 jitter=0.0))
    out = t.run(6)
    assert t.restarts_used == 2
    assert chaos.fired("step.fail") == 2
    # state came back from the step-2 checkpoint both times
    np.testing.assert_allclose(np.asarray(out["w"]), 6.0)
    # bounded exponential backoff between recoveries
    assert no_sleep == pytest.approx([0.1, 0.2])


def test_trainer_gives_up_after_max_restarts(no_sleep):
    chaos.arm("step.fail", times=100)
    t = ElasticTrainer(_count_step, {"w": jnp.zeros(2)}, max_restarts=2,
                       on_failure="recover",
                       retry_policy=RetryPolicy(max_attempts=3,
                                                base_delay=0.01))
    with pytest.raises(RetryError):
        t.run(4)
    assert t.restarts_used == 3  # 2 recoveries + the give-up attempt


def test_recover_refuses_blind_reattach(monkeypatch, no_sleep):
    """A distributed recover with no way to reach the coordinator again
    (no reinit_kwargs, no env) must fail loudly — a bare dist.init()
    would no-op the attach and leave failure detection silently dead."""
    monkeypatch.setattr(elastic, "_is_distributed", lambda: True)
    monkeypatch.delenv("MX_COORDINATOR", raising=False)
    monkeypatch.delenv("DMLC_NUM_WORKER", raising=False)
    chaos.arm("step.fail")
    t = ElasticTrainer(_count_step, {"w": jnp.zeros(2)}, max_restarts=2,
                       on_failure="recover")
    with pytest.raises(RetryError, match="re-attach"):
        t.run(2)


def test_coordinator_timeout_retried_with_backoff_not_fatal(no_sleep):
    """Acceptance: an injected coordinator timeout during the liveness
    poll is retried with growing backoff — attempt count asserted — and
    the run completes instead of crashing or triggering a recovery."""
    chaos.arm("coordinator.timeout", times=2)
    t = ElasticTrainer(_count_step, {"w": jnp.zeros(2)},
                       on_failure="recover")
    t.peer_policy = RetryPolicy(max_attempts=4, base_delay=0.1, jitter=0.0)
    out = t.run(1)
    assert t.peer_policy.last_attempts == 3  # 2 timeouts + 1 success
    assert chaos.fired("coordinator.timeout") == 2
    assert t.restarts_used == 0  # retried at the poll, not recovered
    assert no_sleep == pytest.approx([0.1, 0.2])  # backoff grew
    np.testing.assert_allclose(np.asarray(out["w"]), 1.0)


def test_kvstore_barrier_retries_coordinator_timeout(no_sleep):
    kv = mx.kv.create("dist_sync")
    chaos.arm("coordinator.timeout", times=2)
    kv._barrier_with_retry()
    assert kv._last_barrier_attempts == 3
    assert chaos.fired("coordinator.timeout") == 2
    assert len(no_sleep) == 2


def test_get_num_dead_node_unified_signature():
    from mxnet_tpu.kvstore import AsyncKVStore, KVStore
    # one implementation: the subclass overrides only the transport
    assert AsyncKVStore.get_num_dead_node is KVStore.get_num_dead_node
    kv = mx.kv.create("local")
    assert kv.get_num_dead_node() == 0
    # node_id accepted positionally and by name (reference-API parity),
    # but ignored
    assert kv.get_num_dead_node(3, 1) == 0
    assert mx.kv.create("dist_sync").get_num_dead_node(node_id=7,
                                                       timeout=1) == 0


def test_stop_heartbeat_reports_leaked_thread(caplog):
    from mxnet_tpu.parallel import dist
    assert dist.stop_heartbeat() is True  # no writer running: clean stop

    class Wedged:
        def join(self, timeout=None):
            pass

        def is_alive(self):
            return True

    import logging
    import threading
    dist._HB_THREAD = Wedged()
    dist._HB_STOP = threading.Event()
    with caplog.at_level(logging.WARNING):
        assert dist.stop_heartbeat() is False
    assert "did not stop" in caplog.text
    assert dist._HB_THREAD is None  # writer slot freed either way


def test_dist_shutdown_drops_device_caches():
    from mxnet_tpu.parallel import dist, mesh
    dist._AR_JIT[("probe",)] = object()
    dist._PMESH = object()
    mesh._DP_MESHES[("probe",)] = object()
    dist._initialized = True
    dist.shutdown()
    assert dist._AR_JIT == {}
    assert dist._PMESH is None
    assert mesh._DP_MESHES == {}
    assert not dist._initialized


# ---------------------------------------------------------------------------
# fit(elastic=...) hook
# ---------------------------------------------------------------------------

def _make_module():
    data = mx.sym.var("data")
    net = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    return mx.mod.Module(net, context=mx.cpu())


def _make_iter():
    rng = np.random.RandomState(0)
    X = rng.randn(64, 6).astype(np.float32)
    y = (rng.rand(64) * 4).astype(np.float32)
    return mx.io.NDArrayIter(X, y, batch_size=16,
                             label_name="softmax_label")


def test_fit_elastic_checkpoints_and_resumes(tmp_path):
    ckdir = str(tmp_path / "elastic")
    it = _make_iter()
    mod = _make_module()
    mod.fit(it, num_epoch=3, elastic=ckdir, initializer=mx.init.Xavier(),
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9})
    ck = ElasticCheckpointer(ckdir)
    assert ck.latest_step() == 3
    # optimizer state (momentum) rides under the same commit marker
    assert os.path.exists(os.path.join(ck.step_dir(3), "opt_states"))
    a1, _ = mod.get_params()

    # a restarted run with the same dir fast-forwards past done epochs
    batches = []
    mod2 = _make_module()
    mod2.fit(_make_iter(), num_epoch=3, elastic=ckdir,
             initializer=mx.init.Zero(),
             batch_end_callback=lambda p: batches.append(p.nbatch))
    assert batches == []  # resumed at epoch 3 of 3: no training left
    a2, _ = mod2.get_params()
    for k in a1:  # and it carries the trained parameters, not Zero()
        np.testing.assert_allclose(a2[k].asnumpy(), a1[k].asnumpy())

    # extending the run resumes at 3 and trains 2 more epochs; a TUPLE
    # of user callbacks must survive the elastic callback append
    epochs_seen = []
    mod3 = _make_module()
    mod3.fit(_make_iter(), num_epoch=5,
             elastic={"path": ckdir, "keep_last": 2},
             initializer=mx.init.Zero(),
             epoch_end_callback=(lambda e, *a: epochs_seen.append(e),),
             optimizer_params={"learning_rate": 0.05, "momentum": 0.9})
    assert epochs_seen == [3, 4]
    assert ck.latest_step() == 5
    assert ck.steps() == [4, 5]  # keep_last=2 rotation

    # misconfiguration fails loudly, not by silent defaulting
    with pytest.raises(ValueError, match="elastic"):
        _make_module().fit(_make_iter(), num_epoch=1,
                           elastic={"path": ckdir, "keeplast": 10})


# ---------------------------------------------------------------------------
# host-side supervisor
# ---------------------------------------------------------------------------

def test_supervise_relaunches_until_round_succeeds(tmp_path, no_sleep):
    script = tmp_path / "w.py"
    script.write_text(
        "import os, sys\n"
        "r = int(os.environ['MXNET_ELASTIC_RESTART'])\n"
        "print('incarnation', r)\n"
        "sys.exit(0 if r >= 2 else 75)\n")
    restarts, log_dir = elastic.supervise(
        lambda rank, restart, coord: [sys.executable, str(script)],
        nprocs=2, max_restarts=3, log_dir=str(tmp_path / "logs"),
        round_timeout=60)
    assert restarts == 2
    out = open(os.path.join(log_dir, "r2_rank0.log")).read()
    assert "incarnation 2" in out


def test_supervise_gives_up_after_max_restarts(tmp_path, no_sleep):
    script = tmp_path / "w.py"
    script.write_text("import sys; sys.exit(1)\n")
    with pytest.raises(RetryError, match="all 2 rounds failed"):
        elastic.supervise(
            lambda rank, restart, coord: [sys.executable, str(script)],
            nprocs=1, max_restarts=1, log_dir=str(tmp_path / "logs"),
            round_timeout=60)


# ---------------------------------------------------------------------------
# launched: kill a worker mid-run, restart, resume from last commit
# ---------------------------------------------------------------------------

ELASTIC_WORKER = r"""
import os, sys, time
coord, rank, ckdir = sys.argv[1], int(sys.argv[2]), sys.argv[3]
restart = int(os.environ.get("MXNET_ELASTIC_RESTART", "0"))
if restart == 0 and rank == 1:
    # incarnation 0 only: rank 1 crashes at the top of step 7 — strictly
    # AFTER the step-5 checkpoint committed, mid-run (chaos armed via env
    # so it's live before any import)
    os.environ["MXNET_CHAOS"] = "worker.death@7"
import numpy as np
import mxnet_tpu as mx
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
out = t.run(20)
print("RESUMED_FROM", t.resumed_from, flush=True)
print("FINAL", float(np.asarray(out["w"])[0]), flush=True)
dist.stop_heartbeat()
os._exit(0)  # skip jax's shutdown barrier (peer histories differ)
"""


@pytest.mark.launched
@pytest.mark.timeout(180)
def test_kill_and_resume_finishes_training(tmp_path):
    """Acceptance: a launched 2-process elastic run loses a worker
    mid-run (chaos), the pod is torn down and relaunched by the
    supervisor, and the new incarnation restores from the last COMPLETE
    checkpoint and finishes all 20 steps.

    Determinism: commits need BOTH ranks at the host barrier, and rank 1
    dies at step 7, so step 5 is provably the last commit of incarnation
    0 no matter how far rank 0 raced ahead before the heartbeat watchdog
    (or the supervisor reacting to rank 1's exit) tore it down."""
    worker = tmp_path / "worker.py"
    worker.write_text(ELASTIC_WORKER)
    ckdir = str(tmp_path / "ck")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO)
    restarts, log_dir = elastic.supervise(
        lambda rank, restart, coord: [sys.executable, str(worker), coord,
                                      str(rank), ckdir],
        nprocs=2, max_restarts=2, env=env,
        log_dir=str(tmp_path / "logs"), round_timeout=120,
        policy=RetryPolicy(max_attempts=3, base_delay=0.2, max_delay=1.0))
    assert restarts >= 1  # incarnation 0 really did lose the worker
    final = [open(os.path.join(log_dir,
                               "r%d_rank%d.log" % (restarts, r))).read()
             for r in range(2)]
    for out in final:
        assert "RESUMED_FROM 5" in out, out  # last complete checkpoint
        assert "FINAL 20.0" in out, out      # training finished
    # incarnation 0: rank 1 was chaos-killed, not a clean exit
    r0 = open(os.path.join(log_dir, "r0_rank1.log")).read()
    assert "chaos" in r0.lower() and "RESUMED_FROM" not in r0
