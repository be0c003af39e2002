#!/usr/bin/env python
"""Multi-host job launcher (reference ``tools/launch.py``† +
dmlc_tracker).

The reference spawns a ps-lite scheduler + servers + workers over
ssh/mpi and wires ``DMLC_*`` env.  The TPU-native job is SPMD: every
host runs the SAME program and ``jax.distributed.initialize`` forms
the mesh, so the launcher's job collapses to exporting the
coordination env and execing one process per host (SURVEY §5.8).

Local simulation of an N-process cluster (the reference's
``--launcher local`` trick, SURVEY §4.5):

  python tools/launch.py -n 4 --launcher local python train.py

On a host with TPU chips a chip belongs to one process at a time, so
``local`` gives each child its own chip (N must then be 1 or the
host's chip count; anything else is refused) — unless the children are
pinned to the CPU with ``JAX_PLATFORMS=cpu``, the test harness's case.
The launcher itself never imports JAX: it would take the chips.

Real multi-host: run on each host with --host-rank set (or under your
scheduler, e.g. one task per host):

  python tools/launch.py -n 16 --coordinator host0:1234 \
      --host-rank $RANK python train.py
"""
import argparse
import os
import subprocess
import sys


# chips of one host -> the process grid libtpu is told to form when
# each process drives one chip (v5e hosts: 1, 2x2, 2x4)
_PROCESS_BOUNDS = {1: "1,1,1", 4: "2,2,1", 8: "2,4,1"}
_TPU_PORT_BASE = 8476


def local_tpu_chips():
    """TPU chips on this host, counted from their device files —
    asking JAX would make this process hold them."""
    import glob
    return len(glob.glob("/dev/accel[0-9]*")) or \
        len(glob.glob("/dev/vfio/[0-9]*"))


def chip_env(rank, n):
    """libtpu settings that hand child ``rank`` of ``n`` exactly one
    chip and let the ``n`` one-chip processes form one slice."""
    return {
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": _PROCESS_BOUNDS[n],
        "TPU_PROCESS_ADDRESSES": ",".join(
            f"localhost:{_TPU_PORT_BASE + r}" for r in range(n)),
        "TPU_PROCESS_PORT": str(_TPU_PORT_BASE + rank),
        "TPU_VISIBLE_CHIPS": str(rank),
        "CLOUD_TPU_TASK_ID": str(rank),
    }


def main():
    p = argparse.ArgumentParser()
    p.add_argument("-n", "--num-processes", type=int, required=True,
                   help="total hosts (processes) in the job")
    p.add_argument("--coordinator", default="127.0.0.1:49375",
                   help="coordinator address host:port")
    p.add_argument("--host-rank", type=int, default=None)
    p.add_argument("--launcher", choices=("local", "env", "ssh"),
                   default="env")
    p.add_argument("-H", "--hostfile", default=None,
                   help="one host per line (ssh launcher); rank = "
                        "line order, coordinator = first host")
    p.add_argument("--ssh-user", default=None)
    p.add_argument("command", nargs=argparse.REMAINDER)
    args = p.parse_args()
    if not args.command:
        p.error("no command given")

    base_env = dict(os.environ)
    base_env["MXTPU_COORDINATOR"] = args.coordinator
    base_env["MXTPU_NUM_PROCESSES"] = str(args.num_processes)
    # jax.distributed.initialize() reads these directly
    base_env["JAX_COORDINATOR_ADDRESS"] = args.coordinator
    base_env["JAX_NUM_PROCESSES"] = str(args.num_processes)

    if args.launcher == "local":
        # N local processes, each pretending to be one host — the
        # distributed test harness (no real multi-chip needed)
        n = args.num_processes
        on_cpu = base_env.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
        chips = 0 if on_cpu else local_tpu_chips()
        if chips and n > 1 and (n != chips or n not in _PROCESS_BOUNDS):
            p.error(f"--launcher local: this host has {chips} TPU "
                    f"chip(s) and a chip belongs to one process at a "
                    f"time, so -n must be 1 or {chips} (got {n}); pin "
                    f"the children to the CPU with JAX_PLATFORMS=cpu "
                    f"to simulate more hosts")
        procs = []
        for rank in range(n):
            env = dict(base_env)
            env["JAX_PROCESS_ID"] = str(rank)
            env["MXTPU_PROCESS_ID"] = str(rank)
            if chips and n > 1:
                env.update(chip_env(rank, n))
            procs.append(subprocess.Popen(args.command, env=env))
        rc = 0
        for proc in procs:
            rc |= proc.wait()
        sys.exit(rc)

    if args.launcher == "ssh":
        # dmlc_tracker's ssh launcher†, SPMD-shaped: ssh to every host
        # in the hostfile, export the coordination env, run the SAME
        # command; rank = hostfile order, coordinator = host 0
        if not args.hostfile:
            p.error("--hostfile required with --launcher ssh")
        with open(args.hostfile) as f:
            hosts = [h.strip() for h in f
                     if h.strip() and not h.strip().startswith("#")]
        if len(hosts) < args.num_processes:
            p.error(f"hostfile has {len(hosts)} hosts, need "
                    f"{args.num_processes}")
        hosts = hosts[:args.num_processes]
        coord = args.coordinator
        if coord.startswith("127.0.0.1"):
            coord = hosts[0] + ":" + coord.split(":")[1]
        import shlex
        procs = []
        for rank, host in enumerate(hosts):
            exports = " ".join(
                f"{k}={shlex.quote(v)}" for k, v in (
                    ("JAX_COORDINATOR_ADDRESS", coord),
                    ("JAX_NUM_PROCESSES", str(args.num_processes)),
                    ("JAX_PROCESS_ID", str(rank)),
                    ("MXTPU_COORDINATOR", coord),
                    ("MXTPU_NUM_PROCESSES", str(args.num_processes)),
                    ("MXTPU_PROCESS_ID", str(rank))))
            remote = f"cd {shlex.quote(os.getcwd())} && env " \
                f"{exports} " + " ".join(
                    shlex.quote(c) for c in args.command)
            target = host if args.ssh_user is None else \
                f"{args.ssh_user}@{host}"
            procs.append(subprocess.Popen(
                ["ssh", "-o", "StrictHostKeyChecking=no", target,
                 remote]))
        rc = 0
        for proc in procs:
            rc |= proc.wait()
        sys.exit(rc)

    rank = args.host_rank
    if rank is None:
        p.error("--host-rank required with --launcher env (or use "
                "--launcher local / ssh)")
    base_env["JAX_PROCESS_ID"] = str(rank)
    base_env["MXTPU_PROCESS_ID"] = str(rank)
    os.execvpe(args.command[0], args.command, base_env)


if __name__ == "__main__":
    main()
