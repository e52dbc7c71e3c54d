"""Failure detection (reference include/mxnet/kvstore.h:338 + ps-lite
heartbeats, van.cc): each process heartbeats into the jax.distributed
coordinator KV store; `kv.get_num_dead_node(timeout)` counts stale peers.

Launched test: two jax.distributed CPU processes — one exits early
(simulated death) and the survivor must observe exactly one dead node."""
import os
import subprocess
import sys

import pytest

import launchutil

pytestmark = pytest.mark.launched

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SURVIVOR = r"""
import sys, time
import jax
jax.distributed.initialize(sys.argv[1], 2, 0)
from mxnet_tpu.parallel import dist
dist._initialized = True
dist.start_heartbeat(interval=0.2)
import mxnet_tpu as mx
kv = mx.kv.create("dist_sync")
# wait for the peer's first heartbeat
deadline = time.time() + 30
while kv.get_num_dead_node(timeout=60) != 0:
    if time.time() > deadline:
        print("PEER NEVER BEAT"); sys.exit(2)
    time.sleep(0.2)
print("ALL ALIVE", flush=True)
# peer exits after ~1s; its beat goes stale
deadline = time.time() + 30
while kv.get_num_dead_node(timeout=1.0) != 1:
    if time.time() > deadline:
        print("NEVER SAW DEATH", kv.get_num_dead_node(timeout=1.0))
        sys.exit(3)
    time.sleep(0.3)
print("DEAD NODES 1", flush=True)
import os
os._exit(0)  # skip jax's shutdown barrier (it would fail: peer is dead)
"""

VICTIM = r"""
import sys, time
import jax
jax.distributed.initialize(sys.argv[1], 2, 1)
from mxnet_tpu.parallel import dist
dist._initialized = True
dist.start_heartbeat(interval=0.2)
time.sleep(1.0)
import os
os._exit(0)  # die without cleanup, like a crashed worker
"""


_free_port = launchutil.free_port


@pytest.mark.timeout(180)
def test_dead_worker_detected(tmp_path):
    coord = "127.0.0.1:%d" % _free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO)
    (tmp_path / "survivor.py").write_text(SURVIVOR)
    (tmp_path / "victim.py").write_text(VICTIM)
    survivor = subprocess.Popen(
        [sys.executable, str(tmp_path / "survivor.py"), coord],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    victim = subprocess.Popen(
        [sys.executable, str(tmp_path / "victim.py"), coord],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    out, _ = launchutil.communicate(survivor, timeout=150)
    try:
        victim.wait(timeout=30)
    except subprocess.TimeoutExpired:
        victim.kill()
    assert survivor.returncode == 0, out
    assert "ALL ALIVE" in out and "DEAD NODES 1" in out, out
