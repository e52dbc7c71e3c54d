"""4-process distributed matrix: dist_sync + 2-bit compression + a dead
worker among four (reference CI runs multi-node semantics on one machine,
ci/docker/runtime_functions.sh:551-553; round-4 suites stopped at 2
processes).
"""
import os
import re
import subprocess
import sys

import pytest

import launchutil

pytestmark = pytest.mark.launched

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")


_free_port = launchutil.free_port


def test_compressed_dist_sync_four_workers(tmp_path):
    """4 workers, 2-bit compressed allreduce: codes are the collective
    operand (wire ~ dense/16 on every rank) and the 4-way sum is right."""
    worker = tmp_path / "worker.py"
    worker.write_text(
        "import numpy as np\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu.parallel import dist\n"
        "dist.init()\n"
        "kv = mx.kv.create('dist_sync')\n"
        "assert kv.num_workers == 4, kv.num_workers\n"
        "kv.set_gradient_compression({'type': '2bit', 'threshold': 0.5})\n"
        "rank = kv.rank\n"
        "kv.init('w', mx.nd.zeros((64, 64)))\n"
        "# ranks 0,1 push +0.6; ranks 2,3 push -0.6 -> quantized sum 0\n"
        "g = mx.nd.ones((64, 64)) * (0.6 if rank < 2 else -0.6)\n"
        "kv.push('w', g)\n"
        "out = mx.nd.zeros((64, 64))\n"
        "kv.pull('w', out=out)\n"
        "np.testing.assert_allclose(out.asnumpy(), 0.0, atol=1e-6)\n"
        "wire = kv._last_wire_bytes\n"
        "dense = kv._last_dense_bytes\n"
        "assert wire * 15 <= dense, (wire, dense)\n"
        "print('WIRE4 %d DENSE %d RATIO %.1f OK' % (wire, dense,\n"
        "      dense / wire))\n"
        "# one-sided push: only rank 0 has signal; 4-way mean of the\n"
        "# quantized codes (+0.5, 0, 0, 0) keeps direction\n"
        "g2 = mx.nd.ones((64, 64)) * (0.7 if rank == 0 else 0.0)\n"
        "kv.push('w', g2)\n"
        "kv.pull('w', out=out)\n"
        "assert out.asnumpy().mean() > 0.0\n"
        "print('DIST4', rank, 'OK')\n")
    env = dict(os.environ, PYTHONPATH=REPO)
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "launch.py"), "-n", "4",
         "--port", str(_free_port()), "--", sys.executable, str(worker)],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:] + r.stdout[-2000:]
    assert r.stdout.count("OK") == 8
    for m in re.finditer(r"RATIO ([\d.]+)", r.stdout):
        assert float(m.group(1)) >= 15.0


SURVIVOR = r"""
import sys, time
from mxnet_tpu.parallel import dist
# recoverable: a dying peer must surface through get_num_dead_node, not
# as a coordination-service error broadcast that aborts the survivors
dist.init(sys.argv[1], 4, int(sys.argv[2]), recoverable=True)
dist.stop_heartbeat(); dist.start_heartbeat(interval=0.2)
import mxnet_tpu as mx
kv = mx.kv.create("dist_sync")
deadline = time.time() + 60
while kv.get_num_dead_node(timeout=60) != 0:
    if time.time() > deadline:
        print("PEERS NEVER BEAT"); sys.exit(2)
    time.sleep(0.2)
print("ALL 4 ALIVE", flush=True)
# a beat older than STALE seconds counts as dead: wide enough that a live
# peer's 0.2 s heartbeat thread, starved on a loaded test host, is not
# miscounted
STALE = 3.0
deadline = time.time() + 60
while True:
    dead = kv.get_num_dead_node(timeout=STALE)
    if dead == 1:
        break
    if dead > 1 or time.time() > deadline:
        print("WRONG DEAD COUNT", dead); sys.exit(3)
    time.sleep(0.3)
# stability: the count must stay exactly 1 (three live peers keep beating)
time.sleep(1.0)
dead = kv.get_num_dead_node(timeout=STALE)
if dead != 1:
    print("UNSTABLE DEAD COUNT", dead); sys.exit(4)
print("DEAD NODES 1 OF 4", flush=True)
# rank 0 hosts the coordination service in its own process: if it left
# first, the clients of ranks 1-2 would end their processes with "leader
# task died". So it leaves last, once both have reported.
rank = int(sys.argv[2])
client = dist._coordinator_client()
client.key_value_set("survivor_done/%d" % rank, "1")
if rank == 0:
    for peer in (1, 2):
        client.blocking_key_value_get("survivor_done/%d" % peer, 60_000)
    time.sleep(0.5)  # let the peers' os._exit land
import os
os._exit(0)  # skip jax's shutdown barrier (one peer is gone)
"""

VICTIM = r"""
import sys, time
from mxnet_tpu.parallel import dist
dist.init(sys.argv[1], 4, int(sys.argv[2]), recoverable=True)
dist.stop_heartbeat(); dist.start_heartbeat(interval=0.2)
time.sleep(1.5)
import os
os._exit(0)  # die without cleanup, like a crashed worker
"""


def test_one_dead_of_four_detected(tmp_path):
    """Ranks 0-2 survive, rank 3 dies: every survivor converges on
    get_num_dead_node() == 1 and holds it (no over-count). With the
    recoverable flag the victim's death takes no survivor down."""
    coord = "127.0.0.1:%d" % _free_port()
    sv = tmp_path / "survivor.py"
    vc = tmp_path / "victim.py"
    sv.write_text(SURVIVOR)
    vc.write_text(VICTIM)
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen(
        [sys.executable, str(sv if rank < 3 else vc), coord, str(rank)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env)
        for rank in range(4)]
    outs, errs = [], []
    for out, err in launchutil.communicate_all(procs, timeout=180):
        outs.append(out)
        errs.append(err)
    for rank in (0, 1, 2):
        assert procs[rank].returncode == 0, (
            [p.returncode for p in procs], outs[rank], errs[rank][-2000:])
        assert "ALL 4 ALIVE" in outs[rank]
        assert "DEAD NODES 1 OF 4" in outs[rank]
