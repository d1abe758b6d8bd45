"""Distributed environment / bootstrap.

TPU-native equivalent of the reference's env-var contract + comm-id
bootstrap (reference: fleet launcher env contract PADDLE_TRAINER_ID /
PADDLE_TRAINER_ENDPOINTS, launch_utils.py; TCP ncclUniqueId broadcast
platform/gen_comm_id_helper.cc:286 — replaced by jax.distributed's
coordination service). Process-level rank/world-size here is the multi-host
axis; per-process device parallelism is expressed through the mesh
(paddle_tpu.distributed.topology).
"""

from __future__ import annotations

import os
from typing import Optional

import jax

_initialized = False


def init_parallel_env(coordinator_address: Optional[str] = None,
                      num_processes: Optional[int] = None,
                      process_id: Optional[int] = None) -> None:
    """Initialize multi-host JAX (reference: paddle.distributed
    init_parallel_env / fleet.init). Single-process usage is a no-op."""
    global _initialized
    if _initialized:
        return
    coord = coordinator_address or os.environ.get("PT_COORDINATOR_ADDRESS")
    nproc = num_processes if num_processes is not None else int(
        os.environ.get("PT_NUM_PROCESSES", os.environ.get(
            "PADDLE_TRAINERS_NUM", "1")))
    pid = process_id if process_id is not None else int(
        os.environ.get("PT_PROCESS_ID", os.environ.get(
            "PADDLE_TRAINER_ID", "0")))
    if coord and nproc > 1:
        # CPU backend needs an explicit cross-process collectives
        # implementation (the TPU backend rides ICI/DCN natively). gloo is
        # the reference's CPU fabric too (framework/fleet/gloo_wrapper.cc);
        # PT_CPU_COLLECTIVES=none opts out.
        impl = os.environ.get("PT_CPU_COLLECTIVES", "gloo")
        if impl and impl != "none":
            jax.config.update("jax_cpu_collectives_implementation",
                              impl)
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=nproc, process_id=pid)
    _initialized = True


def get_rank() -> int:
    """Process index (multi-host rank)."""
    try:
        return jax.process_index()
    except Exception:
        return int(os.environ.get("PADDLE_TRAINER_ID", "0"))


def get_world_size() -> int:
    """Number of processes (hosts), not devices."""
    try:
        return jax.process_count()
    except Exception:
        return int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))


def device_count() -> int:
    return jax.device_count()


def local_device_count() -> int:
    return jax.local_device_count()


class ParallelEnv:
    """Reference-compatible env facade (reference:
    fluid/dygraph/parallel.py ParallelEnv)."""

    @property
    def rank(self) -> int:
        return get_rank()

    @property
    def world_size(self) -> int:
        return get_world_size()

    @property
    def device_id(self) -> int:
        return 0

    @property
    def nranks(self) -> int:
        return get_world_size()

    @property
    def local_rank(self) -> int:
        return get_rank()
