#!/usr/bin/env python
"""Launch distributed training jobs (reference tools/launch.py, which
drives dmlc-tracker over ssh/mpi/sge/yarn).

TPU-native model: there are no scheduler/server roles — every process is
a worker in a `jax.distributed` cluster (mxnet_tpu/parallel/dist.py).
This launcher covers the reference's `--launcher local` CI path: spawn N
worker processes on this host with the DMLC-compatible env contract

    MX_COORDINATOR   coordinator ip:port (process 0)
    DMLC_NUM_WORKER  number of workers
    DMLC_WORKER_ID   this worker's rank

`dist_sync` kvstores created inside the workers then allreduce over the
cluster. For multi-host, run the same command per host with --host-rank /
--coordinator pointing at host 0.
"""
from __future__ import print_function

import argparse
import os
import signal
import subprocess
import sys


def launch_local(args, command):
    procs = []
    env_base = dict(os.environ)
    coordinator = args.coordinator or "127.0.0.1:%d" % args.port
    total = args.num_workers * args.num_hosts
    for r in range(args.num_workers):
        env = dict(env_base)
        env["MX_COORDINATOR"] = coordinator
        env["DMLC_NUM_WORKER"] = str(total)
        env["DMLC_WORKER_ID"] = str(args.host_rank * args.num_workers + r)
        env["DMLC_ROLE"] = "worker"
        # each local worker needs devices of its own, and a TPU chip
        # belongs to one process at a time: workers run on the CPU
        # backend unless --platform says otherwise (main() refuses
        # several local workers on the tpu platform)
        env["JAX_PLATFORMS"] = args.platform or "cpu"
        procs.append(subprocess.Popen(command, shell=True, env=env))
    code = 0
    try:
        for p in procs:
            p.wait()
            code = code or p.returncode
    except KeyboardInterrupt:
        for p in procs:
            p.send_signal(signal.SIGINT)
        for p in procs:
            p.wait()
        code = 1
    return code


def main():
    parser = argparse.ArgumentParser(
        description="Launch a distributed job",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    parser.add_argument("-n", "--num-workers", required=True, type=int,
                        help="number of worker processes to launch")
    parser.add_argument("--launcher", type=str, default="local",
                        choices=["local"],
                        help="cluster launcher mode; the reference's "
                             "ssh/mpi/sge/yarn modes are replaced by "
                             "running this command once per host")
    parser.add_argument("--port", type=int, default=9327,
                        help="coordinator port (process 0)")
    parser.add_argument("--coordinator", type=str, default=None,
                        help="ip:port of the rank-0 host for multi-host")
    parser.add_argument("--platform", type=str, default=None,
                        help="JAX_PLATFORMS for workers (default cpu; "
                             "tpu only with one worker per host, since "
                             "local workers cannot share the host's chips)")
    parser.add_argument("--num-hosts", type=int, default=1,
                        help="total hosts running this command")
    parser.add_argument("--host-rank", type=int, default=0,
                        help="this host's index in [0, num-hosts); worker "
                             "ranks are offset by host-rank * num-workers")
    parser.add_argument("command", nargs="+",
                        help="command for launching the program")
    args, unknown = parser.parse_known_args()
    if args.num_workers > 1 and \
            "tpu" in (args.platform or "").split(","):
        # the second worker would wait forever for chips the first holds
        parser.error("--platform tpu needs -n 1: a host's TPU chips "
                     "belong to one process at a time")
    command = " ".join(args.command + unknown)
    sys.exit(launch_local(args, command))


if __name__ == "__main__":
    main()
