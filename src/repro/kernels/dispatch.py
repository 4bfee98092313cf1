"""Kernel dispatch policy.

``use_pallas()`` decides whether the model routes attention / SSD through
the Pallas kernels. On a TPU it is on and ``interpret()`` is off, so the
compiled kernels run. Anywhere else the kernels can only run in
``interpret=True`` mode (Python emulation — correct but slow), so the
default there is the pure-jnp reference path; set ``REPRO_USE_PALLAS=1``
to exercise the kernels end-to-end in interpret mode.
"""
from __future__ import annotations

import os

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_pallas() -> bool:
    env = os.environ.get("REPRO_USE_PALLAS")
    if env is not None:
        return env not in ("0", "false", "")
    return on_tpu()


def interpret() -> bool:
    """Pallas interpret mode: required anywhere but a real TPU."""
    return not on_tpu()
