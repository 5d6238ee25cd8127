"""Eager-PP p2p transport microbench: direct sockets vs the old KV relay.

Two processes on this host play adjacent pipeline stages. Each sends
REPS activation-sized tensors to its peer (both directions, the 1F1B
traffic shape) over (a) the direct-socket P2PCommunicator and (b) a
minimal TCPStore-KV relay identical to the round-3 transport. Prints
MB/s for both — the VERDICT r3 item-6 'measured MB/s' artifact.

Run: python tools/pp_p2p_bench.py
"""
from __future__ import annotations

import json
import multiprocessing as mp
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

MB = 1 << 20
SIZES = [(4 * MB, 16), (64 * MB, 4)]  # (bytes per tensor, reps)


def _store(port, rank):
    from paddle_tpu.distributed.store import TCPStore
    return TCPStore("127.0.0.1", port, is_master=(rank == 0),
                    world_size=2)


def _stage(rank, port, mode, out_q):
    if os.environ.get("PP_BENCH_DEBUG"):
        import faulthandler
        faulthandler.dump_traceback_later(90, exit=True)
    os.environ["PADDLE_MASTER"] = f"127.0.0.1:{port}"
    store = _store(port, rank)
    peer = 1 - rank
    rows = []
    if mode == "socket":
        from paddle_tpu.distributed.fleet.meta_parallel.pp_utils import (
            P2PCommunicator)
        comm = P2PCommunicator(store, rank)
        send = lambda a, s: comm.send(a, peer, f"t{s}")  # noqa: E731
        recv = lambda s: comm.recv(peer, f"t{s}")        # noqa: E731
    else:  # the round-3 KV relay, for comparison
        seqs = {}

        def send(a, s):
            k = seqs.get(("s", s), 0)
            seqs[("s", s)] = k + 1
            store.set(f"relay/{rank}->{peer}/{s}/{k}", a.tobytes())

        def recv(s):
            k = seqs.get(("r", s), 0)
            seqs[("r", s)] = k + 1
            key = f"relay/{peer}->{rank}/{s}/{k}"
            buf = store.wait(key)
            store.delete_key(key)
            return np.frombuffer(buf, np.float32)

    # the KV relay cannot carry the big rows: multi-MB single values trip
    # the store master's serialized handling — exactly the scaling wall
    # that motivated the direct-socket transport. Compare at 1MB only.
    sizes = SIZES if mode == "socket" else [(MB, 16)]
    for size, reps in sizes:
        arr = np.ones(size // 4, np.float32)
        # warm the connection + JIT-ish costs
        send(arr[:1024], "warm")
        recv("warm")
        t0 = time.perf_counter()
        for i in range(reps):
            send(arr, "bench")
            got = recv("bench")
        dt = time.perf_counter() - t0
        assert np.asarray(got).nbytes == size
        # both directions moved `reps` tensors concurrently
        rows.append({"mode": mode, "tensor_mb": size // MB, "reps": reps,
                     "mb_per_s": round(size * reps / MB / dt, 1)})
    if rank == 0:
        out_q.put(rows)


def _free_port() -> int:
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def main():
    free_port = _free_port
    for mode in ("socket", "kv_relay"):
        port = free_port()
        q = mp.Queue()
        procs = [mp.Process(target=_stage, args=(r, port, mode, q),
                            daemon=True) for r in range(2)]
        for p in procs:
            p.start()
        try:
            rows = q.get(timeout=240)
            for r in rows:
                print(json.dumps(r), flush=True)
        except Exception:  # noqa: BLE001 — report, keep the other mode
            print(json.dumps({"mode": mode, "error": "no result",
                              "exitcodes": [p.exitcode for p in procs]}),
                  flush=True)
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.kill()
                p.join(timeout=5)


if __name__ == "__main__":
    main()
