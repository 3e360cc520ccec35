"""The one-node cluster a run starts and stops (both drivers)."""

from __future__ import annotations

import time


def start() -> bool:
    """``ray_tpu.init()`` unless a cluster is up already (the tests').
    -> whether this call started it, and so has to stop it.

    The head declares a node dead after 10 s without a heartbeat. While a
    four-chip worker starts, the in-process agent's beats stalled for
    longer than that on the chip (PERF.md, PR 23): the head then buried
    its own node and the trainer returned no metrics and no error. The
    benchmark gives the agent two minutes; the stall then shows as
    set-up time and nothing else."""
    import ray_tpu

    if ray_tpu.is_initialized():
        return False
    ray_tpu.init(object_store_memory=2**30, _heartbeat_timeout_s=120.0)
    return True


def wait_chips_free(timeout: float = 60.0) -> None:
    """Every chip-holding process is gone before this run exits."""
    from ray_tpu._private import accelerator

    deadline = time.monotonic() + timeout
    while accelerator.chip_holders() and time.monotonic() < deadline:
        time.sleep(0.2)
