"""Launcher entry: python -m paddle_tpu.distributed.launch train.py

~ distributed/launch/main.py:18 + controllers/collective.py:32 (build_pod)
+ job/container.py:97 (subprocess per rank) + controller watch loop.

Per-node it spawns one process per local rank with the env contract
(PADDLE_MASTER, PADDLE_GLOBAL_RANK, PADDLE_LOCAL_RANK, PADDLE_WORLD_SIZE,
PADDLE_TRAINER_ENDPOINTS); multi-node rendezvous goes through HTTPMaster
(node 0). jax.distributed.initialize in the trainer (init_parallel_env)
then uses PADDLE_MASTER as the coordinator. Elastic mode watches children
and relaunches the pod on failure (~ ElasticManager, bounded restarts).
"""
from __future__ import annotations

import argparse
import glob
import os
import signal
import subprocess
import sys
import time
from typing import List

from .master import HTTPMaster


def _parse_args(argv=None):
    p = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    p.add_argument("--master", default=None,
                   help="host:port of node-0 KV (defaults to localhost)")
    p.add_argument("--nnodes", type=int, default=1)
    p.add_argument("--node_rank", type=int, default=0)
    p.add_argument("--nproc_per_node", type=int, default=None)
    p.add_argument("--log_dir", default=None)
    p.add_argument("--elastic_level", type=int, default=0,
                   help=">0: restart pod on child failure (max_restart times)")
    p.add_argument("--max_restart", type=int, default=3)
    p.add_argument("--np", dest="np_range", default=None,
                   help="MIN:MAX elastic node range; membership changes "
                        "within the range relaunch trainers with rewritten "
                        "rank envs (~ elastic/manager.py:34)")
    p.add_argument("--elastic_node_id", default=None,
                   help="stable node identity in the elastic membership "
                        "registry (default: host:node_rank)")
    p.add_argument("--devices", default=None,
                   help="comma ids exported as PADDLE_VISIBLE_DEVICES")
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def local_tpu_chips() -> int:
    """TPU chips this host exposes, counted from its device nodes — the
    launcher must not ask JAX: a process that has initialised the TPU
    backend holds the chips its children need."""
    if os.environ.get("JAX_PLATFORMS", "").lower().startswith("cpu"):
        return 0  # the children are pinned to the CPU backend
    return len(glob.glob("/dev/accel[0-9]*")) \
        or len(glob.glob("/dev/vfio/[0-9]*"))


class Container:
    """One local rank (~ launch/job/container.py)."""

    def __init__(self, cmd: List[str], env: dict, log_path: str | None):
        self.cmd = cmd
        self.env = env
        self.log_path = log_path
        self.proc = None
        self._log_f = None

    def start(self):
        out = None
        if self.log_path:
            os.makedirs(os.path.dirname(self.log_path), exist_ok=True)
            self._log_f = open(self.log_path, "w")
            out = self._log_f
        self.proc = subprocess.Popen(
            self.cmd, env={**os.environ, **self.env}, stdout=out,
            stderr=subprocess.STDOUT if out else None)

    def alive(self):
        return self.proc is not None and self.proc.poll() is None

    @property
    def returncode(self):
        return self.proc.poll() if self.proc else None

    def terminate(self):
        if self.alive():
            self.proc.terminate()
            try:
                self.proc.wait(10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
        if self._log_f:
            self._log_f.close()
            self._log_f = None


def build_pod(args, n_nodes=None, node_index=None,
              endpoints_override=None) -> List[Container]:
    """~ CollectiveController.build_pod (controllers/collective.py:32).

    ``n_nodes``/``node_index`` override the static --nnodes/--node_rank
    when elastic membership decides the pod size (~ manager.py:130's
    rank-env rewrite on scale events); ``endpoints_override`` then carries
    the endpoint list assembled from the membership registry (each node's
    published IP), since the static HTTPMaster sync expects a fixed node
    count.
    """
    nproc = args.nproc_per_node
    if nproc is None:
        nproc = 1
    if nproc > 1 and local_tpu_chips():
        # nothing here gives a local rank its own chip: every child past
        # the first would fail to take the device the first one holds
        raise SystemExit(
            f"--nproc_per_node={nproc} on a TPU host: a chip belongs to "
            "one process at a time and this launcher does not partition "
            "the host's chips between local ranks. Drive all local chips "
            "from ONE process over a jax Mesh (--nproc_per_node=1), or "
            "set JAX_PLATFORMS=cpu for a CPU run.")
    nn = args.nnodes if n_nodes is None else n_nodes
    ni = args.node_rank if node_index is None else node_index
    world = nn * nproc
    master_ep = args.master or "127.0.0.1:34782"

    if endpoints_override is not None:
        endpoints = endpoints_override
    elif nn > 1 and n_nodes is None:
        master = HTTPMaster(master_ep, is_host=ni == 0)
        import socket
        my_ip = socket.gethostbyname(socket.gethostname())
        peers = master.sync_peers("peers", f"{my_ip}:{nproc}", ni, nn)
        endpoints = ",".join(peers)
    else:
        # single node: one endpoint per local rank (reference contract —
        # PADDLE_TRAINER_ENDPOINTS is always present, collective.py:83-91)
        host, port = (master_ep.split(":") + ["34782"])[:2]
        endpoints = ",".join(f"{host}:{int(port) + 100 + r}"
                             for r in range(world))

    containers = []
    for local_rank in range(nproc):
        rank = ni * nproc + local_rank
        env = {
            "PADDLE_MASTER": master_ep,
            "PADDLE_COORDINATOR": master_ep,
            "PADDLE_GLOBAL_RANK": str(rank),
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_LOCAL_RANK": str(local_rank),
            "PADDLE_WORLD_SIZE": str(world),
            "PADDLE_TRAINERS_NUM": str(world),
            "PADDLE_NNODES": str(args.nnodes),
        }
        if endpoints:
            env["PADDLE_TRAINER_ENDPOINTS"] = endpoints
        if args.devices:
            env["PADDLE_VISIBLE_DEVICES"] = args.devices
        log = None
        if args.log_dir:
            log = os.path.join(args.log_dir, f"workerlog.{local_rank}")
        containers.append(Container(
            [sys.executable, args.training_script]
            + args.training_script_args, env, log))
    return containers


def watch(containers: List[Container], poll: float = 2.0,
          rescale_check=None):
    """~ controller.watch: exit 0 when all done, kill pod on any failure.
    With ``rescale_check`` (elastic), returns "scale" when the membership
    watcher decides the pod must relaunch at a new world size."""
    while True:
        codes = [c.returncode for c in containers]
        if any(c is not None and c != 0 for c in codes):
            for c in containers:
                c.terminate()
            return next(c for c in codes if c)
        if all(c == 0 for c in codes):
            return 0
        if rescale_check is not None and rescale_check():
            for c in containers:
                c.terminate()
            return "scale"
        time.sleep(poll)


def _elastic_manager(args):
    """Membership registry for --np MIN:MAX (~ ElasticManager over etcd,
    elastic/manager.py:34 — here over the TCPStore)."""
    from ..fleet.elastic import ElasticManager
    from ..store import TCPStore
    min_np, _, max_np = args.np_range.partition(":")
    min_np = int(min_np)
    max_np = int(max_np or min_np)
    master_ep = args.master or "127.0.0.1:34782"
    host, port = (master_ep.split(":") + ["34782"])[:2]
    # the membership store lives beside the trainer rendezvous port
    store = TCPStore(host, int(port) + 7, is_master=args.node_rank == 0)
    node_id = args.elastic_node_id or f"{host}:{args.node_rank}"
    mgr = ElasticManager(store, node_id, (min_np, max_np),
                         heartbeat_interval=0.5, dead_after=3.0)
    mgr.start()
    # publish this node's IP so every pod can assemble the true endpoint
    # list from the live membership (the static HTTPMaster sync can't —
    # it expects a fixed node count)
    import socket
    try:
        my_ip = socket.gethostbyname(socket.gethostname())
    except OSError:
        my_ip = "127.0.0.1"
    store.set(f"__node_ip__/{node_id}", my_ip)
    return mgr, node_id, min_np, max_np


def _elastic_endpoints(manager, alive, nproc, base_port):
    """PADDLE_TRAINER_ENDPOINTS from live membership: each node's
    published IP, nproc consecutive ports per node in sorted-member
    order (the reference's rank-env rewrite, manager.py:130)."""
    eps = []
    for idx, node in enumerate(alive):
        ip = manager.store.get(f"__node_ip__/{node}")
        ip = ip.decode() if ip else "127.0.0.1"
        for lr in range(nproc):
            eps.append(f"{ip}:{base_port + 100 + idx * nproc + lr}")
    return ",".join(eps)


def launch(argv=None) -> int:
    args = _parse_args(argv)
    manager = None
    if args.np_range:
        manager, node_id, min_np, max_np = _elastic_manager(args)
        pending = {"flag": False}
        manager.watch(lambda old, new: pending.update(flag=True))
    restarts = 0
    cur = {"n_nodes": None, "node_index": None}
    while True:
        if manager is not None:
            # effective pod size from live membership, clamped to the
            # range; this node must ALSO be in the alive list — assuming
            # index 0 while absent would duplicate the real rank-0 pod
            deadline = time.time() + 60.0
            alive = manager.alive_members()
            while (len(alive) < min_np or node_id not in alive) \
                    and time.time() < deadline:
                time.sleep(0.5)
                alive = manager.alive_members()
            if len(alive) < min_np:
                print(f"[launch] elastic hold: {len(alive)} < np min "
                      f"{min_np}", file=sys.stderr)
                return 1
            if node_id not in alive:
                print(f"[launch] elastic error: this node ({node_id}) "
                      f"missing from membership {alive}", file=sys.stderr)
                return 1
            n_nodes = min(len(alive), max_np)
            node_index = alive.index(node_id)
            pending["flag"] = False
            cur.update(n_nodes=n_nodes, node_index=node_index)
            master_ep = args.master or "127.0.0.1:34782"
            base_port = int((master_ep.split(":") + ["34782"])[1])
            containers = build_pod(
                args, n_nodes=n_nodes, node_index=node_index,
                endpoints_override=_elastic_endpoints(
                    manager, alive[:n_nodes], args.nproc_per_node or 1,
                    base_port))
        else:
            containers = build_pod(args)
        for c in containers:
            c.start()

        def handler(sig, frame):
            for c in containers:
                c.terminate()
            sys.exit(1)
        signal.signal(signal.SIGINT, handler)
        signal.signal(signal.SIGTERM, handler)

        def rescale_check():
            # relaunch only when the EFFECTIVE size/rank changes (a join
            # beyond max_np or a leave still >= current view is a no-op)
            if not pending["flag"]:
                return False
            alive = manager.alive_members()
            if node_id not in alive:
                # transient self-absence (slow heartbeat): never rescale
                # on it — assuming an index would duplicate another node's
                # rank block
                return False
            n_new = min(len(alive), max_np)
            idx_new = alive.index(node_id)
            if n_new >= min_np and (n_new != cur["n_nodes"]
                                    or idx_new != cur["node_index"]):
                return True
            pending["flag"] = False
            return False

        code = watch(containers,
                     rescale_check=rescale_check if manager else None)
        if code == "scale":
            print(f"[launch] elastic scale: membership now "
                  f"{manager.alive_members()} -> relaunch with rewritten "
                  f"rank envs", file=sys.stderr)
            continue  # scale events do not consume the restart budget
        if code == 0:
            if manager is not None:
                manager.stop()
            return 0
        restarts += 1
        if args.elastic_level <= 0 or restarts > args.max_restart:
            if manager is not None:
                manager.stop()
            return code
        print(f"[launch] pod failed (exit {code}); elastic restart "
              f"{restarts}/{args.max_restart}", file=sys.stderr)
        time.sleep(2.0)


def main():
    sys.exit(launch())


if __name__ == "__main__":
    main()
