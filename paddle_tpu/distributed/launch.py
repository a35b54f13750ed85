"""paddle.distributed.launch — the operator's front door for multi-process
training. Parity: python/paddle/distributed/fleet/launch.py (fleetrun:
arg surface, per-rank log files, failure supervision) +
fleet/elastic/manager.py (gang restart loop).

The reference spawns one process per GPU and wires NCCL endpoints. On TPU
the unit is a *host process*: each rank joins a jax.distributed world over
a coordinator (loopback for single-host multi-process, DCN for pods), and
inside each process one Mesh owns that process's chips. Usage:

    # single host, 2 ranks, per-rank logs, restart-on-failure
    python -m paddle_tpu.distributed.launch --nproc_per_node 2 \
        --log_dir out/logs --max_restarts 1 train.py [args...]

    # multi-host (one launcher per host)
    python -m paddle_tpu.distributed.launch \
        --nnodes 4 --node_rank 0 --master addr:port train.py [args...]

The launcher is a pure supervisor: it never imports jax itself (backend
init belongs to the ranks), sets PADDLE_TPU_* + reference-compatible
PADDLE_TRAINER_* env for each rank, streams rank logs to --log_dir/
workerlog.<rank>, kills the surviving gang when any rank fails, reports
the first failure with its log tail, and (elastic) restarts the whole
gang up to --max_restarts times — ranks resume from the latest
checkpoint via ElasticController.maybe_resume().
"""
import argparse
import os
import runpy
import signal
import socket
import subprocess
import sys
import time

__all__ = ["main", "launch"]


def _parse(argv=None):
    p = argparse.ArgumentParser(
        "paddle_tpu.distributed.launch",
        description="start paddle_tpu training in multi-process mode")
    base = p.add_argument_group("Base Parameters")
    base.add_argument("--nproc_per_node", type=int,
                      default=int(os.environ.get("PADDLE_NPROC_PER_NODE",
                                                 "1")),
                      help="ranks to launch on this host. A chip belongs "
                           "to ONE process at a time: on a host with "
                           "chips either one process drives them all "
                           "(the default 1; fleet.init in a single "
                           "controller) or --devices splits them across "
                           "the ranks. More than one rank WITHOUT "
                           "--devices gives every rank every chip, and "
                           "the second rank fails on the chip's lock — "
                           "that combination is for the CPU backend "
                           "(tests) only")
    base.add_argument("--log_dir", default=None,
                      help="per-rank logs as <log_dir>/workerlog.<rank>; "
                           "default: ranks inherit the launcher's stdout")
    base.add_argument("--devices", "--gpus", "--xpus", dest="devices",
                      default=None,
                      help="comma-separated device ids for this host, "
                           "partitioned contiguously across the local "
                           "ranks (count must divide by nproc_per_node); "
                           "each rank sees its slice as "
                           "PADDLE_VISIBLE_DEVICES, consumed by "
                           "init_parallel_env before backend init")
    coll = p.add_argument_group("Collective Parameters")
    coll.add_argument("--nnodes", type=int,
                      default=int(os.environ.get("PADDLE_NNODES", "1")))
    coll.add_argument("--node_rank", type=int,
                      default=int(os.environ.get("PADDLE_NODE_RANK", "0")))
    coll.add_argument("--master", "--ips", dest="master",
                      default=os.environ.get("PADDLE_MASTER", ""),
                      help="coordinator addr:port (required when "
                           "nnodes > 1); single-host runs pick a "
                           "loopback port automatically")
    elastic = p.add_argument_group("Elastic Parameters")
    elastic.add_argument("--max_restarts", type=int,
                         default=int(os.environ.get("PADDLE_MAX_RESTARTS",
                                                    "0")),
                         help="gang restarts after a rank failure; ranks "
                              "resume via ElasticController checkpoints")
    p.add_argument("--run_mode", default="collective",
                   help="collective (default); ps mode is documented "
                        "out-of-scope on TPU (SURVEY §2.8)")
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _rank_devices(devices, nproc, local_rank):
    """Contiguous per-rank slice of the --devices id list (rank i of n
    gets ids [i*k, (i+1)*k) for k = len/n)."""
    ids = [d.strip() for d in str(devices).split(",") if d.strip()]
    if not ids or len(ids) % nproc != 0:
        raise SystemExit(
            f"launch: --devices lists {len(ids)} ids, not divisible "
            f"across --nproc_per_node {nproc}")
    k = len(ids) // nproc
    return ",".join(ids[local_rank * k:(local_rank + 1) * k])


def _rank_env(args, coordinator, local_rank, restart_count):
    world = args.nnodes * args.nproc_per_node
    rank = args.node_rank * args.nproc_per_node + local_rank
    host = coordinator.rsplit(":", 1)[0]
    endpoints = ",".join(
        f"{host}:{_ep_port(coordinator, r)}" for r in range(world))
    env = dict(os.environ)
    if world > 1:
        # multi-process bootstrap (consumed by init_parallel_env). NOT
        # set for a single-rank gang: forcing the coordinator env there
        # made init_parallel_env run jax.distributed.initialize for a
        # 1-process "world", losing the single-controller init path
        # (one process owning every local chip)
        env.update({
            "PADDLE_TPU_COORDINATOR": coordinator,
            "PADDLE_TPU_NUM_PROCESSES": str(world),
            "PADDLE_TPU_PROCESS_ID": str(rank),
        })
    env.update({
        # reference-compatible trainer env (fleet launch_utils contract)
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(world),
        "PADDLE_LOCAL_RANK": str(local_rank),
        "PADDLE_CURRENT_ENDPOINT": f"{host}:{_ep_port(coordinator, rank)}",
        "PADDLE_TRAINER_ENDPOINTS": endpoints,
        "PADDLE_RESTART_COUNT": str(restart_count),
    })
    if args.devices is not None:
        env["PADDLE_VISIBLE_DEVICES"] = _rank_devices(
            args.devices, args.nproc_per_node, local_rank)
    # hang/crash debuggability (profiler/flight_recorder.py): every
    # worker arms a SIGQUIT faulthandler stack dump (`kill -QUIT <pid>`
    # prints all-thread stacks to the rank's workerlog without dying),
    # and an operator-set PADDLE_TPU_DEBUG_DUMP fans out to a per-rank
    # subdirectory so concurrent crash bundles never clobber each other
    env.setdefault("PADDLE_TPU_SIGQUIT_STACKS", "1")
    # the distributed observatory's rank-skew gather: every rank
    # snapshots its periodic rankstat into this shared directory and
    # rank 0 reads the peers to detect stragglers
    # (profiler/dist_observatory.py); an operator-set dir wins
    if args.log_dir:
        env.setdefault("PADDLE_TPU_RANKSTAT_DIR",
                       os.path.join(args.log_dir, "rankstat"))
    if env.get("PADDLE_TPU_DEBUG_DUMP"):
        env["PADDLE_TPU_DEBUG_DUMP"] = os.path.join(
            env["PADDLE_TPU_DEBUG_DUMP"], f"rank{rank}")
    return env


def _ep_port(coordinator, rank):
    # deterministic per-rank "endpoint" ports for the reference-style
    # endpoint list (informational on TPU: the real wiring is the
    # jax.distributed coordinator)
    return int(coordinator.rsplit(":", 1)[1]) + 1 + rank


def _tail(path, n=20):
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return "<no log>"


def _spawn_gang(args, coordinator, restart_count):
    """Start nproc_per_node rank processes; returns [(proc, logpath)]."""
    gang = []
    for local in range(args.nproc_per_node):
        env = _rank_env(args, coordinator, local, restart_count)
        rank = args.node_rank * args.nproc_per_node + local
        if args.log_dir:
            os.makedirs(args.log_dir, exist_ok=True)
            logpath = os.path.join(args.log_dir, f"workerlog.{rank}")
            logf = open(logpath, "a", buffering=1)
            logf.write(f"----- launch rank {rank} restart "
                       f"{restart_count} -----\n")
            stdout = stderr = logf
        else:
            logpath, logf = None, None
            stdout = stderr = None  # inherit the launcher's streams
        proc = subprocess.Popen(
            [sys.executable, "-u", args.training_script,
             *args.training_script_args],
            env=env, stdout=stdout, stderr=stderr)
        proc._logf = logf
        gang.append((proc, logpath))
    return gang


def _kill_gang(gang):
    for proc, _ in gang:
        if proc.poll() is None:
            proc.terminate()
    deadline = time.time() + 10
    for proc, _ in gang:
        try:
            proc.wait(timeout=max(0.1, deadline - time.time()))
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()


def _close_logs(gang):
    for proc, _ in gang:
        if getattr(proc, "_logf", None):
            proc._logf.close()


def _supervise(args):
    """Run the gang to completion; returns the exit code. On any rank
    failure: kill survivors, report the first failure (+ log tail),
    then either gang-restart (elastic) or exit with that rc."""
    coordinator = args.master or f"127.0.0.1:{_free_port()}"
    if args.nnodes > 1 and not args.master:
        raise SystemExit(
            "launch: --master addr:port is required when --nnodes > 1")
    restarts = 0
    while True:
        gang = _spawn_gang(args, coordinator, restarts)
        stop_sig = {}

        def _forward(signum, frame):
            stop_sig["sig"] = signum
            _kill_gang(gang)
        old = {s: signal.signal(s, _forward)
               for s in (signal.SIGTERM, signal.SIGINT)}
        failed = None  # (rank, rc, logpath)
        try:
            pending = dict(enumerate(gang))
            while pending and failed is None:
                time.sleep(0.2)
                for local, (proc, logpath) in list(pending.items()):
                    rc = proc.poll()
                    if rc is None:
                        continue
                    del pending[local]
                    if rc != 0:
                        rank = args.node_rank * args.nproc_per_node + local
                        failed = (rank, rc, logpath)
            if failed is not None:
                _kill_gang(gang)
        finally:
            for s, h in old.items():
                signal.signal(s, h)
            _close_logs(gang)
        if stop_sig:
            return 128 + stop_sig["sig"]
        if failed is None:
            return 0
        rank, rc, logpath = failed
        print(f"launch: rank {rank} exited with code {rc}; "
              f"remaining ranks terminated", file=sys.stderr)
        if logpath:
            print(f"launch: tail of {logpath}:\n{_tail(logpath)}",
                  file=sys.stderr)
        if restarts >= args.max_restarts:
            return rc if rc > 0 else 1
        restarts += 1
        print(f"launch: elastic restart {restarts}/{args.max_restarts} "
              f"(ranks resume from the latest checkpoint)",
              file=sys.stderr)
        # a fresh coordinator port: the old jax.distributed service may
        # linger in TIME_WAIT on the previous one
        if not args.master:
            coordinator = f"127.0.0.1:{_free_port()}"


def launch(script, script_args=(), nnodes=1, node_rank=0, master=""):
    """In-process single-rank entry (library API, kept for compat): set
    the bootstrap env and exec the script in this interpreter."""
    if nnodes > 1:
        if not master:
            raise ValueError("--master addr:port required when nnodes > 1")
        os.environ["PADDLE_TPU_COORDINATOR"] = master
        os.environ["PADDLE_TPU_NUM_PROCESSES"] = str(nnodes)
        os.environ["PADDLE_TPU_PROCESS_ID"] = str(node_rank)
    sys.argv = [script] + list(script_args)
    runpy.run_path(script, run_name="__main__")


def main():
    args = _parse()
    raise SystemExit(_supervise(args))


if __name__ == "__main__":
    main()
