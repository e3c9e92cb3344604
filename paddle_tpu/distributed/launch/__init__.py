"""Multi-host / multi-process launcher.

Reference: python/paddle/distributed/launch/ (main.py arg surface,
controllers/collective.py process management). The TPU-native rendering is
much smaller: there is no parameter-server mode and no per-GPU process
fan-out — JAX is single-controller-per-host, so the launcher's job is

  1. decide (master, world_size, rank) for every process,
  2. export them (PADDLE_MASTER / PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM),
  3. exec the training script once per local process and babysit it.

`init_parallel_env` (distributed/parallel.py) picks the env up and calls
`jax.distributed.initialize`, after which `jax.devices()` is the GLOBAL
device list and every GSPMD mesh spans all hosts — collectives ride
ICI/DCN exactly as laid out by the mesh axes.

Usage (2 hosts):
    host0$ python -m paddle_tpu.distributed.launch --nnodes 2 --rank 0 \
               --master 10.0.0.1:8476 train.py --lr 0.1
    host1$ python -m paddle_tpu.distributed.launch --nnodes 2 --rank 1 \
               --master 10.0.0.1:8476 train.py --lr 0.1

CPU emulation (2 processes x 4 virtual devices on one machine):
    $ python -m paddle_tpu.distributed.launch --nproc_per_node 2 \
          --cpu_devices_per_rank 4 train.py
"""
import argparse
import os
import signal
import socket
import subprocess
import sys
import time

__all__ = ["launch", "main"]


def _free_port():
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _parse(argv):
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.distributed.launch",
        description="paddle_tpu distributed launcher (jax.distributed)")
    p.add_argument("--master", default=None,
                   help="coordinator ip:port (default: local free port)")
    p.add_argument("--rank", type=int, default=0,
                   help="this node's rank in [0, nnodes)")
    p.add_argument("--nnodes", type=int, default=1,
                   help="number of nodes (hosts) in the job")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="processes to start on this node (TPU: 1 per host)")
    p.add_argument("--log_dir", default=None,
                   help="write per-rank stdout/stderr to this directory")
    p.add_argument("--job_id", default="default", help="job name for logs")
    p.add_argument("--devices", default=None,
                   help="restrict visible TPU devices (TPU_VISIBLE_DEVICES)")
    p.add_argument("--cpu_devices_per_rank", type=int, default=0,
                   help="emulate N virtual CPU devices per process "
                        "(JAX_PLATFORMS=cpu; for tests/dry-runs)")
    p.add_argument("training_script", help="script to run")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def force_cpu_devices(env, n):
    """Mutate an env dict so a fresh process comes up with `n` virtual CPU
    devices, even when the parent already initialized the TPU (its
    discovery vars — PJRT_LIBRARY_PATH, TPU_* — would otherwise make the
    child claim the accelerator again)."""
    for k in list(env):
        if k.startswith("TPU_") or k == "PJRT_LIBRARY_PATH":
            env.pop(k)
    env["JAX_PLATFORMS"] = "cpu"
    # multi-PROCESS computations need a CPU collectives backend: without
    # one XLA refuses outright ("Multiprocess computations aren't
    # implemented on the CPU backend") — the root cause of the two-process
    # launch/elastic failures this repo carried since the seed. This
    # jaxlib ships gloo; respect an explicit override.
    env.setdefault("JAX_CPU_COLLECTIVES_IMPLEMENTATION", "gloo")
    flags = env.get("XLA_FLAGS", "")
    flags = " ".join(f for f in flags.split()
                     if "xla_force_host_platform_device_count" not in f)
    env["XLA_FLAGS"] = (flags +
                        f" --xla_force_host_platform_device_count={n}").strip()
    return env


def _child_env(args, master, world, rank):
    env = dict(os.environ)
    env.update(
        PADDLE_MASTER=master,
        PADDLE_TRAINER_ID=str(rank),
        PADDLE_TRAINERS_NUM=str(world),
        PADDLE_JOB_ID=args.job_id,
    )
    if args.devices:
        env["TPU_VISIBLE_DEVICES"] = args.devices
    if args.cpu_devices_per_rank:
        force_cpu_devices(env, args.cpu_devices_per_rank)
    return env


def main(argv=None):
    args = _parse(argv)
    nproc = args.nproc_per_node
    world = args.nnodes * nproc
    master = args.master or f"127.0.0.1:{_free_port()}"
    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)

    procs, logs = [], []
    for p in range(nproc):
        rank = args.rank * nproc + p
        env = _child_env(args, master, world, rank)
        cmd = [sys.executable, args.training_script, *args.training_script_args]
        if args.log_dir:
            out = open(os.path.join(
                args.log_dir, f"{args.job_id}.rank{rank}.log"), "w")
            logs.append(out)
        else:
            out = None
        procs.append((rank, subprocess.Popen(
            cmd, env=env, stdout=out, stderr=subprocess.STDOUT if out else None)))

    rc = 0
    try:
        pending = dict(procs)
        termed_at = None
        while pending:
            for rank, proc in list(pending.items()):
                r = proc.poll()
                if r is None:
                    continue
                del pending[rank]
                if r != 0 and rc == 0:
                    # first failure wins; peers then die by SIGTERM (-15)
                    rc = r
                    print(f"[launch] rank {rank} exited rc={r}; "
                          "terminating peers", file=sys.stderr)
                    for _, q in procs:
                        if q.poll() is None:
                            q.terminate()
                    termed_at = time.time()
            if termed_at is not None and pending and \
                    time.time() - termed_at > 10:
                # SIGTERM can't land on a rank wedged inside a gloo
                # collective whose partner died: the C++ socket read
                # never returns, so a python-level signal handler (e.g.
                # ElasticManager's graceful-exit hook) never runs.
                # Escalate so the group always reaps and the elastic
                # supervisor can restart it.
                print("[launch] peers ignored SIGTERM for 10s; killing",
                      file=sys.stderr)
                for _, q in procs:
                    if q.poll() is None:
                        q.kill()
                termed_at = None
            time.sleep(0.2)
    except KeyboardInterrupt:
        for _, q in procs:
            if q.poll() is None:
                q.send_signal(signal.SIGINT)
        # a rank wedged in a gloo collective never sees SIGINT (same
        # C++-block story as the SIGTERM escalation above) — reap it
        # rather than orphan it past our own exit
        deadline = time.time() + 10
        while time.time() < deadline and any(
                q.poll() is None for _, q in procs):
            time.sleep(0.2)
        for _, q in procs:
            if q.poll() is None:
                q.kill()
                q.wait()
        rc = 130
    finally:
        for f in logs:
            f.close()
    return rc


def launch():
    """Entry point matching reference paddle.distributed.launch.launch()."""
    sys.exit(main())
