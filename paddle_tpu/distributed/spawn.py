"""paddle.distributed.spawn parity (ref:python/paddle/distributed/spawn.py:426).

Forks ``nprocs`` Python workers running ``func(*args)`` with the launcher's
env contract set per rank. Used by the spawn-and-compare distributed test
pattern (SURVEY.md §4.3). Workers default to the CPU platform with one
virtual device each so single-host tests don't fight over the TPU chip.
"""
from __future__ import annotations

import multiprocessing as mp
import os
import socket
from typing import Optional, Tuple


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _worker(func, rank, nprocs, endpoints, backend, args, queue):
    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    os.environ["PADDLE_TRAINERS_NUM"] = str(nprocs)
    os.environ["PADDLE_TRAINER_ENDPOINTS"] = ",".join(endpoints)
    os.environ["PADDLE_CURRENT_ENDPOINT"] = endpoints[rank]
    if backend == "cpu":
        # force, not setdefault: the inherited env may point at a TPU the
        # workers must not fight over (a chip belongs to one process)
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            import jax

            jax.config.update("jax_platforms", "cpu")
        except Exception:
            pass
    try:
        result = func(*args)
        queue.put((rank, "ok", result))
    except Exception as e:  # surface the failure to the parent
        import traceback

        queue.put((rank, "error", f"{e}\n{traceback.format_exc()}"))
        raise


def spawn(func, args: Tuple = (), nprocs: int = 1, join: bool = True,
          daemon: bool = False, backend: str = "cpu",
          started_port: Optional[int] = None, **options):
    """Run func on nprocs processes; returns list of per-rank results."""
    ctx = mp.get_context("spawn")
    port = started_port or _free_port()
    endpoints = [f"127.0.0.1:{port + i}" for i in range(nprocs)]
    queue = ctx.Queue()
    procs = []
    for rank in range(nprocs):
        p = ctx.Process(target=_worker,
                        args=(func, rank, nprocs, endpoints, backend, args, queue),
                        daemon=daemon)
        p.start()
        procs.append(p)
    if not join:
        return procs
    results = {}
    errors = []
    for _ in range(nprocs):
        rank, status, payload = queue.get()
        if status == "error":
            errors.append((rank, payload))
        else:
            results[rank] = payload
    for p in procs:
        p.join()
    if errors:
        raise RuntimeError(
            "spawned workers failed:\n" + "\n".join(f"rank {r}: {e}" for r, e in errors))
    return [results.get(i) for i in range(nprocs)]
