"""Every mesh of the repository is built here (pure functions — importing
never touches jax device state).

Axes are ``Auto``: GSPMD propagates shardings through the program and
``with_sharding_constraint`` (``core.parallelism.constrain``) applies. Bare
``jax.make_mesh`` gives ``Explicit`` axes, under which those constraints
and plain indexing of sharded values raise ``ShardingTypeError``.
"""
from __future__ import annotations

import os

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape=(1,), axes=("data",), *, devices=None) -> Mesh:
    """A mesh of ``shape`` over the first ``prod(shape)`` of ``devices``
    (default ``jax.devices()``). ``devices`` may be the devices of a
    described topology (``jax.experimental.topologies``) for compiling
    without the chip."""
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, axis_types=types)
    n = int(np.prod(shape))
    return Mesh(np.asarray(devices[:n]).reshape(shape), axes, axis_types=types)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: 16×16 = 256 chips ('data','model'); multi-pod adds a 2-way
    'pod' axis (512 chips)."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def cpu_devices_env(devices: int, env=None) -> dict:
    """Environment for a child process that runs on ``devices`` virtual CPU
    devices: JAX pinned to the CPU, so the child never reaches for a chip
    that its parent may hold, and the device-count flag appended to whatever
    ``XLA_FLAGS`` already holds (the last occurrence of a flag wins)."""
    env = dict(os.environ if env is None else env)
    flag = f"--xla_force_host_platform_device_count={devices}"
    env["XLA_FLAGS"] = f"{env.get('XLA_FLAGS', '')} {flag}".strip()
    env["JAX_PLATFORMS"] = "cpu"
    return env


def use_cpu_devices(devices: int) -> None:
    """Run this process on ``devices`` virtual CPU devices. Call it before
    the first device query; JAX has read ``JAX_PLATFORMS`` at import, so the
    platform is pinned through its config."""
    os.environ.update(cpu_devices_env(devices))
    jax.config.update("jax_platforms", "cpu")
