"""Multi-process training launcher.

Reference counterpart: the Dask integration's ``_train``
(``python-package/lightgbm/dask.py:415``) — find each worker's address,
build the ``machines`` list, pick free ports, run per-worker training
jobs, collect the results.  Here workers are OS processes bootstrapping
through :func:`lightgbm_tpu.parallel.distributed.init_distributed`
(rank 0 = jax.distributed coordinator), so the same helper serves
single-host multi-process CPU/TPU jobs and, with a user-supplied machine
list, multi-host DCN jobs.

The worker callable runs in a FRESH interpreter (spawn), receives
``(rank, world_size)`` after the distributed runtime is up, and its
return value is sent back to the launcher; any worker exception aborts
the whole job with that traceback.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import socket
import traceback
from typing import Any, Callable, List, Optional, Sequence


def _free_ports(n: int) -> List[int]:
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def _worker_main(rank: int, machines: str, num_machines: int,
                 devices_per_worker: int, fn: Callable, args: tuple,
                 queue) -> None:
    try:
        os.environ["LIGHTGBM_TPU_RANK"] = str(rank)
        if devices_per_worker:
            # must precede jax's backend init in this fresh process
            from ..utils.hermetic import force_cpu
            force_cpu(devices_per_worker)
        from ..config import Config
        from .distributed import init_distributed, shutdown
        got_rank, world = init_distributed(
            Config({"machines": machines, "num_machines": num_machines}))
        try:
            result = fn(got_rank, world, *args)
        finally:
            shutdown()
        queue.put((rank, "ok", result))
    except BaseException:  # noqa: BLE001 — relayed to the launcher
        queue.put((rank, "error", traceback.format_exc()))


def launch(worker: Callable, num_workers: int, *,
           args: Sequence[Any] = (),
           devices_per_worker: int = 0,
           machines: Optional[str] = None,
           timeout: float = 900.0) -> List[Any]:
    """Run ``worker(rank, world_size, *args)`` in ``num_workers`` processes
    under one jax.distributed cluster; returns results ordered by rank.

    ``devices_per_worker`` > 0 forces that many virtual CPU devices per
    process (the hermetic test topology); 0 uses each process's default
    backend.  ``machines`` overrides the auto-generated localhost list for
    multi-host launches (reference dask.py builds it from worker IPs).

    One process per chip: with ``devices_per_worker=0`` on a TPU host,
    every worker's default backend claims EVERY local chip, so the second
    worker fails or hangs at backend init.  Several processes on one TPU
    host are out of scope (ROADMAP retired it) — one process drives all
    of a host's chips through ``tree_learner=data``; ``chip_smoke.py``
    never goes through this launcher.
    """
    if num_workers < 1:
        raise ValueError("num_workers must be >= 1")
    if machines is None:
        ports = _free_ports(num_workers)
        machines = ",".join(f"127.0.0.1:{p}" for p in ports)
    ctx = mp.get_context("spawn")
    queue = ctx.Queue()
    procs = [ctx.Process(
        target=_worker_main,
        args=(rank, machines, num_workers, devices_per_worker, worker,
              tuple(args), queue), daemon=True)
        for rank in range(num_workers)]
    for p in procs:
        p.start()
    results: dict = {}
    try:
        import queue as _q
        import time
        deadline = time.monotonic() + timeout
        while len(results) < num_workers:
            try:
                rank, status, payload = queue.get(timeout=2.0)
            except _q.Empty:
                missing = sorted(set(range(num_workers)) - set(results))
                # a worker killed by signal (segfault, OOM) posts nothing;
                # fail fast on its exit code instead of waiting out the
                # full timeout
                for r in missing:
                    if not procs[r].is_alive() and procs[r].exitcode != 0:
                        raise RuntimeError(
                            f"worker {r} died with exit code "
                            f"{procs[r].exitcode} before reporting")
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"workers {missing} produced no result within "
                        f"{timeout}s (total)") from None
                continue
            if status == "error":
                raise RuntimeError(
                    f"worker {rank} failed:\n{payload}")
            results[rank] = payload
    finally:
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.terminate()
    return [results[r] for r in range(num_workers)]
