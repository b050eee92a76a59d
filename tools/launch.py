#!/usr/bin/env python
"""launch: start a multi-process distributed training job on one machine
(or print the per-host commands for a cluster).

TPU-native rebirth of the reference's tools/launch.py (dmlc-core tracker:
local/ssh/mpi launchers setting DMLC_ROLE/DMLC_PS_ROOT_URI for ps-lite).
Here there are no parameter-server roles: every process is a worker and
they rendezvous through the jax coordination service, so launching means
spawning N copies of the command with MX_COORDINATOR / MX_NUM_PROCESSES /
MX_PROCESS_ID set (consumed by parallel/dist.py init_process).

    python tools/launch.py -n 4 python train.py --kv-store dist_sync
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys


def main():
    ap = argparse.ArgumentParser(
        description="Launch a distributed job (ref: tools/launch.py)")
    ap.add_argument("-n", "--num-workers", type=int, required=True,
                    help="number of worker processes")
    ap.add_argument("-H", "--host", default="127.0.0.1",
                    help="coordinator host (process 0's address)")
    ap.add_argument("-p", "--port", type=int, default=9355,
                    help="coordinator port")
    ap.add_argument("--launcher", choices=["local", "print"], default="local",
                    help="'local': fork N processes here; 'print': emit the "
                         "command to run on each host of a cluster")
    ap.add_argument("command", nargs=argparse.REMAINDER,
                    help="training command to launch")
    args = ap.parse_args()
    if not args.command:
        ap.error("no command given")

    coordinator = "%s:%d" % (args.host, args.port)
    if args.launcher == "print":
        for r in range(args.num_workers):
            env = ("MX_COORDINATOR=%s MX_NUM_PROCESSES=%d MX_PROCESS_ID=%d"
                   % (coordinator, args.num_workers, r))
            print("[host %d] %s %s" % (r, env, " ".join(args.command)))
        return 0

    procs = []
    try:
        for r in range(args.num_workers):
            env = dict(os.environ)
            env.update({"MX_COORDINATOR": coordinator,
                        "MX_NUM_PROCESSES": str(args.num_workers),
                        "MX_PROCESS_ID": str(r)})
            if "JAX_PLATFORMS" not in env:
                # Each local process simulates one host of the gloo test
                # harness, on the CPU.  A chip belongs to one process, so
                # N local processes each wanting one is not a layout this
                # launcher supports: one process drives all local chips.
                env["JAX_PLATFORMS"] = "cpu"
                print("launch.py: worker %d runs with JAX_PLATFORMS=cpu "
                      "(local workers simulate hosts; set JAX_PLATFORMS to "
                      "override)" % r, file=sys.stderr)
            procs.append(subprocess.Popen(args.command, env=env))
        rc = 0
        for p in procs:
            rc = p.wait() or rc
        return rc
    except KeyboardInterrupt:
        for p in procs:
            p.send_signal(signal.SIGINT)
        for p in procs:
            p.wait()
        return 1


if __name__ == "__main__":
    sys.exit(main())
