#!/usr/bin/env python
"""Multi-process/multi-host launcher (≙ reference tools/launch.py:72-110 +
dmlc_tracker local/ssh submit).

The reference forks scheduler + server + worker processes with DMLC_ROLE env
for the parameter server. Here every process is an equal SPMD worker: the
launcher assigns MXNET_COORDINATOR / MXNET_NUM_PROCESSES / MXNET_PROCESS_ID
and the framework's `mx.parallel.initialize()` bootstraps
jax.distributed over DCN.

Local (N processes on this host — the reference's `--launcher local`
multi-worker test pattern):

    python tools/launch.py -n 4 python train.py --epochs 1

SSH (one process per host):

    python tools/launch.py -n 2 -H hosts.txt python train.py
"""
from __future__ import annotations

import argparse
import os
import shlex
import signal
import socket
import subprocess
import sys


def free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def launch_local(n, command, env_extra):
    coordinator = f"127.0.0.1:{free_port()}"
    procs = []
    for rank in range(n):
        env = dict(os.environ)
        env.update(env_extra)
        env["MXNET_COORDINATOR"] = coordinator
        env["MXNET_NUM_PROCESSES"] = str(n)
        env["MXNET_PROCESS_ID"] = str(rank)
        procs.append(subprocess.Popen(command, env=env))

    def kill_all(*_):
        for p in procs:
            p.terminate()

    signal.signal(signal.SIGINT, kill_all)
    signal.signal(signal.SIGTERM, kill_all)
    rc = 0
    for p in procs:
        p.wait()
        rc = rc or p.returncode
    return rc


def launch_ssh(hosts, command, env_extra):
    coordinator = f"{hosts[0]}:{free_port()}"
    procs = []
    n = len(hosts)
    for rank, host in enumerate(hosts):
        envs = " ".join(
            f"{k}={shlex.quote(v)}" for k, v in {
                **env_extra,
                "MXNET_COORDINATOR": coordinator,
                "MXNET_NUM_PROCESSES": str(n),
                "MXNET_PROCESS_ID": str(rank),
            }.items())
        remote = f"cd {shlex.quote(os.getcwd())} && {envs} " + \
            " ".join(shlex.quote(c) for c in command)
        procs.append(subprocess.Popen(["ssh", "-o",
                                       "StrictHostKeyChecking=no", host,
                                       remote]))
    rc = 0
    for p in procs:
        p.wait()
        rc = rc or p.returncode
    return rc


def main():
    ap = argparse.ArgumentParser(usage=__doc__)
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("-H", "--hostfile", default=None,
                    help="one host per line; omit for local multi-process")
    ap.add_argument("--env", action="append", default=[],
                    help="extra KEY=VALUE env for workers")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if not args.command:
        ap.error("no command given")
    env_extra = dict(e.split("=", 1) for e in args.env)
    if args.hostfile:
        with open(args.hostfile) as f:
            hosts = [h.strip() for h in f if h.strip()][:args.num_workers]
        sys.exit(launch_ssh(hosts, args.command, env_extra))
    sys.exit(launch_local(args.num_workers, args.command, env_extra))


if __name__ == "__main__":
    main()
