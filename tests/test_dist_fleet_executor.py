"""Cross-process FleetExecutor: DistMessageBus + DistCarrier.

~ reference fleet_executor multi-rank tests (test_fleet_executor_*.py
with brpc message bus between ranks): a 2-stage pipeline split across two
OS processes on localhost, microbatches fed on rank 0, results gathered
at the sink on rank 1. Payloads are plain python — the bus is transport,
jax arrays convert to numpy at the wire (_host_payload).
"""
import pytest

pytestmark = pytest.mark.slow  # multi-process/e2e: full-suite lane only
import multiprocessing as mp
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _stage0(x):
    return x + 1


def _stage1(x):
    return x * 2


def _rank_main(rank, addrs, q):
    # spawn children don't inherit the parent's jax config: pin the CPU
    import jax
    jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.distributed.fleet_executor import DistCarrier, TaskNode
    tasks = [TaskNode(rank=0, program=_stage0, task_id=0),
             TaskNode(rank=1, program=_stage1, task_id=1)]
    carrier = DistCarrier(tasks, rank=rank, addrs=addrs)
    if rank == 0:
        out = carrier.run([1, 2, 3])
    else:
        out = carrier.run()
    q.put((rank, out))
    carrier.close()


def _two_free_ports():
    import socket
    socks, ports = [], []
    for _ in range(2):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


class TestDistCarrier:
    def _attempt_two_process(self):
        """One attempt; returns results dict or None on an environmental
        failure (dead child / timeout — e.g. the free-port race when the
        ports are reused between probe-close and child bind, or child
        startup starved on a loaded machine)."""
        ctx = mp.get_context("spawn")
        p0, p1 = _two_free_ports()
        addrs = {0: f"127.0.0.1:{p0}", 1: f"127.0.0.1:{p1}"}
        q = ctx.Queue()
        # daemon: a hung child (e.g. import stalled under heavy machine
        # load) must never be able to block pytest shutdown
        procs = [ctx.Process(target=_rank_main, args=(r, addrs, q),
                             daemon=True)
                 for r in (0, 1)]
        for p in procs:
            p.start()
        import queue as _q
        import time as _time
        results = {}
        deadline = _time.time() + 600  # spawn re-imports the whole stack
        try:
            while len(results) < 2 and _time.time() < deadline:
                try:
                    rank, out = q.get(timeout=5)
                    results[rank] = out
                except _q.Empty:
                    # fail fast on a dead child
                    if any(not p_.is_alive() and p_.exitcode != 0
                           for p_ in procs):
                        return None
            if len(results) < 2:
                return None
            return results
        finally:
            for p in procs:
                p.join(timeout=30)
                if p.is_alive():
                    p.kill()
                    p.join(timeout=5)  # reap — kill alone leaves a zombie
            self._last_rcs = [p.exitcode for p in procs]

    @pytest.mark.dist_retry(n=1)
    def test_two_process_pipeline(self):
        results = self._attempt_two_process()
        if results is None:  # environmental (ports/startup): one retry
            rcs_first = self._last_rcs
            results = self._attempt_two_process()
        assert results is not None, (
            f"children did not report in 2 attempts; exit codes: "
            f"first={rcs_first}, second={self._last_rcs}")
        assert results[0] == []            # feeder rank has no sink
        assert results[1] == [4, 6, 8]     # (x+1)*2 per microbatch

    @pytest.mark.dist_retry(n=1)
    def test_single_process_two_rank_buses(self):
        # both "ranks" inside one process: exercises remote send/recv,
        # pre-registration buffering, and STOP forwarding over TCP
        from paddle_tpu.distributed.fleet_executor import (DistCarrier,
                                                           TaskNode)
        p0, p1 = _two_free_ports()
        addrs = {0: f"127.0.0.1:{p0}", 1: f"127.0.0.1:{p1}"}

        import threading
        results = {}

        def run_rank(rank):
            tasks = [TaskNode(rank=0, program=_stage0, task_id=0),
                     TaskNode(rank=1, program=_stage1, task_id=1)]
            carrier = DistCarrier(tasks, rank=rank, addrs=addrs)
            out = carrier.run([5, 6] if rank == 0 else None)
            results[rank] = out
            carrier.close()

        ts = [threading.Thread(target=run_rank, args=(r,)) for r in (0, 1)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert results[0] == []
        assert results[1] == [12, 14]
