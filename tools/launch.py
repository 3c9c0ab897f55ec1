#!/usr/bin/env python
"""Multi-process job launcher (reference: tools/launch.py + the dmlc-core
local tracker — SURVEY.md §3.3 "Launcher": spawns the process group and sets
the bootstrap env contract each process reads).

TPU-native shape: there are no separate server/scheduler roles — every
process is an SPMD worker that calls ``mxnet_tpu.parallel.distributed.init()``
(≙ Postoffice::Start), which reads the env this launcher sets:

    MXNET_COORDINATOR_ADDRESS   host:port of process 0 (jax.distributed)
    MXNET_NUM_WORKERS           process count
    MXNET_WORKER_ID             this process's id

The reference's ``DMLC_*`` names are also set for script compatibility.

Usage (mirrors the reference CLI)::

    python tools/launch.py -n 4 [--launcher local] [--env K=V ...] \
        python train.py --your-args

``--launcher local`` (default) runs all workers on this machine — exactly
how the reference CI ran its dist kvstore tests without a cluster
(integrationtest_ubuntu_cpu_dist_kvstore).  ``ssh``/``mpi`` launchers are
out of scope for a single-pod TPU job: multi-host pods are provisioned by
the TPU runtime which starts one process per host with the coordinator env
already present.

On a host with TPU chips a local job of several workers is refused unless
the workers are pinned to the CPU (``JAX_PLATFORMS=cpu``): every worker
gets a copy of this environment, none gets a chip of its own, so each
would try to take them all.  One process drives all the chips of a host
(``mesh=make_mesh()``).
"""
from __future__ import annotations

import argparse
import glob
import os
import signal
import socket
import subprocess
import sys
import time


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _local_chips():
    """TPU device nodes of this host (``/dev/vfio/<n>``, or ``/dev/accel<n>``
    under the older driver), found without touching JAX: the launcher
    must stay off the chip its workers need."""
    return glob.glob("/dev/vfio/[0-9]*") or glob.glob("/dev/accel[0-9]*")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("-n", "--num-workers", type=int, required=True,
                    help="number of worker processes")
    ap.add_argument("-s", "--num-servers", type=int, default=0,
                    help="accepted for reference CLI compatibility; the TPU "
                         "build has no server role (ignored)")
    ap.add_argument("--launcher", default="local", choices=["local"],
                    help="process launcher (local = this machine)")
    ap.add_argument("--env", action="append", default=[],
                    help="extra K=V env entries for every worker")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="coordinator port (0 = pick a free one)")
    ap.add_argument("command", nargs=argparse.REMAINDER,
                    help="the worker command")
    args = ap.parse_args(argv)
    if not args.command:
        ap.error("missing worker command")
    cmd = args.command
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]

    extra_env = dict(kv.partition("=")[::2] for kv in args.env)
    platforms = extra_env.get("JAX_PLATFORMS",
                              os.environ.get("JAX_PLATFORMS", ""))
    chips = _local_chips()
    if args.num_workers > 1 and platforms != "cpu" and chips:
        ap.error(
            f"{args.num_workers} local workers on a host with TPU chips "
            f"({', '.join(sorted(chips))}): each would try to take "
            "every chip.  Run one process over all chips, or pin the "
            "workers to the CPU with --env JAX_PLATFORMS=cpu")

    port = args.port or _free_port()
    coord = f"{args.host}:{port}"
    procs = []
    try:
        for wid in range(args.num_workers):
            env = dict(os.environ)
            env.update({
                "MXNET_COORDINATOR_ADDRESS": coord,
                "MXNET_NUM_WORKERS": str(args.num_workers),
                "MXNET_WORKER_ID": str(wid),
                # reference env contract (§4.4 bootstrap)
                "DMLC_PS_ROOT_URI": args.host,
                "DMLC_PS_ROOT_PORT": str(port),
                "DMLC_NUM_WORKER": str(args.num_workers),
                "DMLC_WORKER_ID": str(wid),
                "DMLC_ROLE": "worker",
            })
            env.update(extra_env)
            procs.append(subprocess.Popen(cmd, env=env))
        # poll the whole group: one worker dying early must tear the job
        # down immediately (a sequential wait() would hang forever on the
        # survivors blocked in collectives)
        rc = 0
        running = list(procs)
        while running:
            for p in running[:]:
                r = p.poll()
                if r is not None:
                    running.remove(p)
                    rc = rc or r
            if rc:
                break
            time.sleep(0.2)
        return rc
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.time() + 10
        for p in procs:
            if p.poll() is None:
                try:
                    p.wait(timeout=max(0.1, deadline - time.time()))
                except subprocess.TimeoutExpired:
                    p.kill()


if __name__ == "__main__":
    sys.exit(main())
