"""Multi-host bootstrap for real pod deployments.

On real TPU slices, each host process calls :func:`ensure_initialized`
before touching jax devices; the coordinator address, process count and
process id come from ``JAX_COORDINATOR_ADDRESS``, ``JAX_NUM_PROCESSES``
and ``JAX_PROCESS_ID``. With one process this is a no-op, so every
driver can call it unconditionally.
"""
from __future__ import annotations

import os

import jax

_initialized = False


def ensure_initialized() -> bool:
    """Initialize jax.distributed from the environment. Returns True
    if a multi-process runtime was set up."""
    global _initialized
    if _initialized:
        return True
    addr = os.environ.get("JAX_COORDINATOR_ADDRESS")
    nproc = int(os.environ.get("JAX_NUM_PROCESSES", "1"))
    if addr is None or nproc <= 1:
        return False
    jax.distributed.initialize(
        coordinator_address=addr,
        num_processes=nproc,
        process_id=int(os.environ["JAX_PROCESS_ID"]))
    _initialized = True
    return True


def is_multi_pod() -> bool:
    return int(os.environ.get("REPRO_MULTI_POD", "1")) > 1
