"""Sync vs async vs geo PS communicator throughput.

The win the communicators exist for: with a realistic DCN round-trip on
every wire op, the synchronous pull->step->push loop pays 2 RTTs per step;
AsyncCommunicator takes the push RTT off the critical path (and merges
pushes, paying it less often); GeoCommunicator takes BOTH off steady-state
(pulls hit the local replica, deltas flush every geo_need_push_nums ids).

ref:paddle/fluid/distributed/ps/service/communicator/communicator.h:427,597.

Latency is injected client-side (sleep per wire call) so the bench isolates
the communication pattern, not localhost socket speed. Usage:

    python benches/ps_async_bench.py [rtt_ms] [steps]
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benches._common import emit  # noqa: E402

# host-side bench (tables + numpy): never claim the TPU
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from paddle_tpu.distributed import ps  # noqa: E402
from paddle_tpu.distributed.ps import create_communicator  # noqa: E402


class DelayedClient:
    """SparseTableClient wrapper adding an artificial RTT per wire op."""

    def __init__(self, client, rtt_s: float):
        self._c = client
        self._rtt = rtt_s

    def pull(self, ids):
        time.sleep(self._rtt)
        return self._c.pull(ids)

    def push(self, ids, grads, lr):
        time.sleep(self._rtt)
        return self._c.push(ids, grads, lr)

    def __getattr__(self, name):
        return getattr(self._c, name)


def run(mode: str, rtt_ms: float, steps: int, batch: int = 512,
        fields: int = 8, dim: int = 16) -> dict:
    svc = ps.start_local_cluster(dim=dim, num_shards=2, rule="sgd")
    try:
        comm = create_communicator(
            DelayedClient(svc.client(), rtt_ms / 1000.0), mode=mode,
            max_merge_var_num=8, send_queue_size=32, geo_need_push_nums=4096)
        rng = np.random.RandomState(0)
        # warm the table + replica
        warm = np.arange(batch * fields, dtype=np.uint64)
        comm.pull(warm)
        t0 = time.perf_counter()
        for _ in range(steps):
            ids = rng.randint(0, batch * fields,
                              size=batch * fields // 4).astype(np.uint64)
            rows = comm.pull(ids)
            g = 0.01 * rows.astype(np.float32)  # stand-in grad
            comm.push(ids, g, lr=0.1)
        if mode != "sync":
            comm.flush()
        dt = time.perf_counter() - t0
        if mode != "sync":
            comm.stop()
        return {"steps_per_sec": steps / dt,
                "samples_per_sec": steps * batch / dt, "wall_s": dt}
    finally:
        svc.stop()


def main():
    rtt_ms = float(sys.argv[1]) if len(sys.argv) > 1 else 5.0
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 40
    out = {}
    for mode in ("sync", "async", "geo"):
        out[mode] = run(mode, rtt_ms, steps)
    rec = {
        "bench": "ps-async",
        "config": f"rtt{rtt_ms}ms b512 f8 dim16 2shards",
        "rtt_ms": rtt_ms,
        "steps": steps,
        "sync_steps_per_sec": round(out["sync"]["steps_per_sec"], 2),
        "async_steps_per_sec": round(out["async"]["steps_per_sec"], 2),
        "geo_steps_per_sec": round(out["geo"]["steps_per_sec"], 2),
        "async_speedup": round(out["async"]["steps_per_sec"]
                               / out["sync"]["steps_per_sec"], 2),
        "geo_speedup": round(out["geo"]["steps_per_sec"]
                             / out["sync"]["steps_per_sec"], 2),
        "platform": "host",
    }
    emit(rec)


if __name__ == "__main__":
    main()
