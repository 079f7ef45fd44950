"""jit'd public wrapper for the blocked GEMM kernel (interpret mode off TPU,
see ``repro.kernels.platform``)."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import platform
from repro.kernels.matmul.kernel import matmul_pallas


@partial(jax.jit, static_argnames=("block_m", "block_n", "block_k"))
def matmul(a, b, *, block_m=256, block_n=256, block_k=512):
    return matmul_pallas(a, b, block_m=block_m, block_n=block_n,
                         block_k=block_k, interpret=platform.interpret())
