"""Which implementation a kernel call takes, and why.

Every public kernel wrapper asks :func:`choose` while it traces.  The answer
is the Pallas kernel (compiled on a TPU, or interpreted when the caller asks
for ``interpret=True``) or the jnp reference, with the reason.  A shape that
sends a TPU call to the reference is logged as a warning on the
``repro.kernels`` logger, so a slow path on the chip is never a silent one.
"""
from __future__ import annotations

import logging
from typing import NamedTuple, Optional

import jax

log = logging.getLogger("repro.kernels")

PALLAS = "pallas"
INTERPRET = "interpret"
REFERENCE = "reference"


class KernelPath(NamedTuple):
    impl: str      # PALLAS | INTERPRET | REFERENCE
    reason: str


def choose(
    kernel: str,
    *,
    interpret: bool,
    force_ref: bool,
    refusal: Optional[str] = None,
) -> KernelPath:
    """``refusal`` is the kernel's own reason why this shape cannot compile
    for the chip (None when it can)."""
    if force_ref:
        return KernelPath(REFERENCE, "force_ref=True")
    if interpret:
        return KernelPath(INTERPRET, "interpret=True")
    backend = jax.default_backend()
    if backend != "tpu":
        return KernelPath(REFERENCE, f"backend is {backend}")
    if refusal is not None:
        log.warning("%s takes the jnp reference on tpu: %s", kernel, refusal)
        return KernelPath(REFERENCE, refusal)
    return KernelPath(PALLAS, "tpu")
