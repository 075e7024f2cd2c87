#!/usr/bin/env python
"""Boot an N-daemon SQLcached cluster on this host.

    PYTHONPATH=src python scripts/cluster_up.py [-n 3] [--host 127.0.0.1]

Spawns N daemon processes (``python -m repro.core.protocol``, each on an
OS-assigned port), waits for every ``SQLCACHED READY`` line, then prints
one line per node plus a ready-to-paste ClusterClient snippet. Runs in
the foreground: Ctrl-C (or SIGTERM) tears the fleet down; killing one
child by hand (``kill -9 <pid>``) is the supported way to poke failover
while a client runs. Ports are OS-assigned by default so several
clusters coexist; pass ``--ports 7001,7002,7003`` to pin them.

A local fleet runs on the CPU backend only: on a host with a TPU the
script exits at once, since every daemon would claim the one chip.
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def boot(host: str, port: int) -> tuple[subprocess.Popen, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(REPO, "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.core.protocol",
         "--host", host, "--port", str(port)],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, env=env, cwd=REPO)
    while True:
        line = proc.stdout.readline()
        if line.startswith("SQLCACHED READY"):
            _, _, h, p = line.split()
            return proc, f"{h}:{int(p)}"
        if not line and proc.poll() is not None:
            raise RuntimeError(f"daemon on {host}:{port} died before READY")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("-n", type=int, default=3, help="number of daemons")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--ports", default="",
                    help="comma-separated fixed ports (default: OS picks)")
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.launch.mesh import refuse_fleet_on_accelerator
    refuse_fleet_on_accelerator("cluster_up")
    ports = ([int(p) for p in args.ports.split(",")] if args.ports
             else [0] * args.n)
    if len(ports) != args.n:
        ap.error(f"--ports needs exactly {args.n} entries")

    fleet: list[tuple[subprocess.Popen, str]] = []
    try:
        for port in ports:
            fleet.append(boot(args.host, port))
        names = [name for _, name in fleet]
        for proc, name in fleet:
            print(f"node {name}  pid {proc.pid}")
        print()
        print("from repro.core.cluster import ClusterClient")
        print(f"cc = ClusterClient({names!r})")
        print()
        print("Ctrl-C stops the fleet; kill -9 a pid to test failover.",
              flush=True)
        signal.sigwait({signal.SIGINT, signal.SIGTERM})
    except KeyboardInterrupt:
        pass
    finally:
        for proc, _ in fleet:
            if proc.poll() is None:
                proc.terminate()
        deadline = time.monotonic() + 5
        for proc, _ in fleet:
            try:
                proc.wait(max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                proc.kill()
        print("cluster down")


if __name__ == "__main__":
    main()
