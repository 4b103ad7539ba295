"""Process launcher: `python -m paddle_tpu.distributed.launch train.py`.

Role parity: reference python/paddle/distributed/fleet/launch.py:304 +
distributed/utils.py:357 (start_local_trainers) / :417
(watch_local_trainers).  TPU-native difference: the reference spawns one
process per GPU; on TPU one process drives all local chips, so the
launcher spawns ONE trainer per host entry in --ips (loopback testing
spawns N local processes with a shared coordinator for the
jax.distributed rendezvous).
"""
from __future__ import annotations

import argparse
import glob
import os
import signal
import subprocess
import sys
import time


def _parse_args(argv=None):
    p = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    p.add_argument("--ips", type=str, default="127.0.0.1",
                   help="comma list of host ips (one trainer process per host)")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="trainer processes on THIS node (loopback testing)")
    p.add_argument("--coordinator_port", type=int, default=37777)
    p.add_argument("--log_dir", type=str, default=None)
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def tpu_present() -> bool:
    """Does this host have TPU chips the trainers would open?  Asked
    of the device files, not of jax: a launcher that touched jax would
    hold the chip its child needs.  A job that selected the CPU
    (``JAX_PLATFORMS=cpu``, loopback testing) opens none."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return False
    return bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"))


def start_local_trainers(nproc, coordinator, script, script_args, log_dir=None,
                         base_rank=0, total=None):
    """Spawn trainer subprocesses with the fleet env contract set
    (reference utils.py:357)."""
    procs = []
    total = total if total is not None else nproc
    for i in range(nproc):
        rank = base_rank + i
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(total),
            "PADDLE_COORDINATOR": coordinator,
            "PADDLE_TRAINER_ENDPOINTS": coordinator,
            "FLAGS_selected_tpus": "all",
        })
        out = None
        if log_dir:
            os.makedirs(log_dir, exist_ok=True)
            out = open(os.path.join(log_dir, f"workerlog.{rank}"), "w")
        procs.append(subprocess.Popen(
            [sys.executable, script] + list(script_args),
            env=env, stdout=out, stderr=subprocess.STDOUT if out else None))
    return procs


def watch_local_trainers(procs):
    """Poll children; tear the job down if any dies
    (reference utils.py:417 watch + :257 terminate)."""
    try:
        while True:
            alive = False
            for p in procs:
                ret = p.poll()
                if ret is None:
                    alive = True
                elif ret != 0:
                    terminate_local_procs(procs)
                    return ret
            if not alive:
                return 0
            time.sleep(0.5)
    except KeyboardInterrupt:
        terminate_local_procs(procs)
        return 1


def terminate_local_procs(procs):
    for p in procs:
        if p.poll() is None:
            p.send_signal(signal.SIGTERM)
    deadline = time.time() + 5
    for p in procs:
        while p.poll() is None and time.time() < deadline:
            time.sleep(0.1)
        if p.poll() is None:
            p.kill()


def launch(argv=None):
    args = _parse_args(argv)
    ips = [h for h in args.ips.split(",") if h]
    me = os.environ.get("POD_IP")
    if len(ips) > 1:
        if me is None or me not in ips:
            raise SystemExit(
                "multi-host launch needs POD_IP set to this host's entry in "
                f"--ips (got POD_IP={me!r}, ips={ips}); otherwise every host "
                "would claim node rank 0 and the rendezvous fails")
    else:
        me = ips[0]
    if args.nproc_per_node > 1 and tpu_present():
        raise SystemExit(
            f"--nproc_per_node {args.nproc_per_node} on a TPU host: a chip "
            "belongs to one process at a time and every trainer would open "
            "all of them (the second one fails or hangs). One process "
            "drives all local chips: launch one trainer per host and let "
            "init_parallel_env() build the mesh over its chips, or set "
            "JAX_PLATFORMS=cpu for a loopback test")
    node_rank = ips.index(me)
    coordinator = f"{ips[0]}:{args.coordinator_port}"
    total = len(ips) * args.nproc_per_node
    procs = start_local_trainers(
        args.nproc_per_node, coordinator, args.training_script,
        args.training_script_args, log_dir=args.log_dir,
        base_rank=node_rank * args.nproc_per_node, total=total)
    sys.exit(watch_local_trainers(procs))


if __name__ == "__main__":
    launch()
