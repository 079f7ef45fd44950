"""Where the Pallas kernels run, decided from the platform in one place.

On a TPU every kernel is compiled by Mosaic and the serving path attends
through the paged kernel. Off TPU (the CPU test machines) the kernels run in
Pallas interpret mode and the serving path attends through ``ref.py``, the
pure-jnp oracle the kernel tests compare against. Neither is a user option:
interpret mode is never on for a TPU.

The decisions are read while a function is traced, so a test that compiles
for a described TPU from a CPU process steers them by patching
``on_tpu``.
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret() -> bool:
    """Pallas interpret mode: exactly when no TPU compiles the kernel."""
    return not on_tpu()


def paged_attn_impl() -> str:
    """Paged attention path: the Pallas kernel on TPU, the reference off it."""
    return "kernel" if on_tpu() else "ref"
