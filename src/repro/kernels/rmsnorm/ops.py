"""jit'd public wrapper for fused RMSNorm."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import REFERENCE, KernelPath, choose
from repro.kernels.rmsnorm.kernel import rmsnorm_pallas
from repro.kernels.rmsnorm.ref import rmsnorm_ref


def rmsnorm_path(*, interpret: bool = False, force_ref: bool = False) -> KernelPath:
    """The implementation an RMSNorm call takes: every shape compiles for
    the chip (rows are padded to whole tiles)."""
    return choose("rmsnorm", interpret=interpret, force_ref=force_ref)


@partial(jax.jit, static_argnames=("eps", "offset", "interpret", "force_ref"))
def rmsnorm(
    x: jnp.ndarray,
    scale: jnp.ndarray,
    *,
    eps: float = 1e-6,
    offset: float = 0.0,
    interpret: bool = False,
    force_ref: bool = False,
) -> jnp.ndarray:
    if rmsnorm_path(interpret=interpret, force_ref=force_ref).impl == REFERENCE:
        return rmsnorm_ref(x, scale, eps, offset)
    return rmsnorm_pallas(x, scale, eps, offset, interpret=interpret)


__all__ = ["rmsnorm", "rmsnorm_path", "rmsnorm_ref"]
