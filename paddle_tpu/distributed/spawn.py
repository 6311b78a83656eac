"""paddle.distributed.spawn analog — run fn in worker subprocesses.

Reference: python/paddle/distributed/spawn.py (:114 _get_subprocess_env_list
builds per-proc env, multiprocessing.spawn start method).  One worker per
"host process"; each worker gets PADDLE_TRAINER_ID/PADDLE_TRAINERS_NUM (and
the PADDLE_TPU_* coordination variables when a coordinator is given) before
importing the backend, mirroring launch.py's env contract.

A chip belongs to one process at a time.  On one host the supported layout
is ONE process driving all local chips through a mesh; workers started here
are for CPU simulation (``force_cpu=True``) or for hosts that each own their
chips.  A parent that has already initialised an accelerator backend holds
the chip, so it is refused rather than left to start workers that hang.
"""
from __future__ import annotations

import multiprocessing as mp
import os
from typing import Callable, Sequence


def _worker(rank: int, world: int, coordinator: str | None, fn, args, force_cpu):
    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    os.environ["PADDLE_TRAINERS_NUM"] = str(world)
    if coordinator:
        os.environ["PADDLE_TPU_COORDINATOR"] = coordinator
        os.environ["PADDLE_TPU_NUM_PROCESSES"] = str(world)
        os.environ["PADDLE_TPU_PROCESS_ID"] = str(rank)
    if force_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    fn(*args)


def _holds_accelerator() -> bool:
    from jax._src import xla_bridge

    return any(name != "cpu" for name in xla_bridge._backends)


def spawn(func: Callable, args: Sequence = (), nprocs: int = 1,
          coordinator: str | None = None, join: bool = True,
          force_cpu: bool = False):
    """Start ``nprocs`` processes running ``func(*args)`` with rank env set.

    Returns the list of Process objects (joined if join=True; raises if any
    worker exits non-zero — the reference's context.join behavior)."""
    if not force_cpu and _holds_accelerator():
        raise RuntimeError(
            "spawn: this process has initialised an accelerator backend "
            "and holds the chip; workers that need it would fail or hang. "
            "Drive all local chips from one process through a mesh, or "
            "spawn before touching jax (force_cpu=True for CPU workers).")
    ctx = mp.get_context("spawn")
    procs = []
    for r in range(nprocs):
        p = ctx.Process(target=_worker,
                        args=(r, nprocs, coordinator, func, tuple(args),
                              force_cpu))
        p.start()
        procs.append(p)
    if join:
        for p in procs:
            p.join()
        bad = [p.exitcode for p in procs if p.exitcode != 0]
        if bad:
            raise RuntimeError(f"spawned workers failed with exits {bad}")
    return procs


if __name__ == "__main__":  # light-import guard relies on this module name
    raise SystemExit("use paddle_tpu.distributed.spawn.spawn(fn, ...)")
