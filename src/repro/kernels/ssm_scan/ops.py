"""jit'd public wrappers for the gated linear recurrence / Mamba2 SSD scan."""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import REFERENCE, KernelPath, choose
from repro.kernels.ssm_scan.kernel import gated_scan_pallas
from repro.kernels.ssm_scan.ref import (
    gated_scan_ref,
    gated_step_ref,
    ssm_scan_ref,
    ssm_step_ref,
)


def _pad_seq(arr, pad, value=0.0):
    cfgpad = [(0, 0)] * arr.ndim
    cfgpad[1] = (0, pad)
    return jnp.pad(arr, cfgpad, constant_values=value)


def gated_scan_path(
    chunk: int, seq: int, *, interpret: bool = False, force_ref: bool = False
) -> KernelPath:
    """The implementation a scan of ``seq`` (chunk-padded) steps in chunks
    of ``chunk`` takes: on the chip a chunk is a lane row of per-step
    scalars, so it must be a multiple of 128 unless it is the whole
    sequence."""
    refusal = (
        f"chunk {chunk} of a {seq}-step scan is not a multiple of 128"
        if chunk % 128 and chunk != seq
        else None
    )
    return choose(
        "gated_scan", interpret=interpret, force_ref=force_ref,
        refusal=refusal,
    )


@partial(jax.jit, static_argnames=("chunk", "interpret", "force_ref"))
def gated_scan(
    x, log_decay, in_scale, Bm, Cm, D=None, *,
    chunk: int = 128, interpret: bool = False, force_ref: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    # pad the sequence to a chunk multiple with identity steps
    # (log-decay 0 keeps the state, input-scale 0 injects nothing)
    s = x.shape[1]
    eff_chunk = min(chunk, s)
    pad = (-s) % eff_chunk
    if pad:
        x_, ld_, gi_ = _pad_seq(x, pad), _pad_seq(log_decay, pad), _pad_seq(in_scale, pad)
        Bm_, Cm_ = _pad_seq(Bm, pad), _pad_seq(Cm, pad)
    else:
        x_, ld_, gi_, Bm_, Cm_ = x, log_decay, in_scale, Bm, Cm

    path = gated_scan_path(
        eff_chunk, s + pad, interpret=interpret, force_ref=force_ref
    )
    if path.impl == REFERENCE:
        y, h = gated_scan_ref(x_, ld_, gi_, Bm_, Cm_, D, chunk=eff_chunk)
    else:
        y, h = gated_scan_pallas(
            x_, ld_, gi_, Bm_, Cm_, D, chunk=eff_chunk, interpret=interpret
        )
    return (y[:, :s] if pad else y), h


@partial(jax.jit, static_argnames=("chunk", "interpret", "force_ref"))
def ssm_scan(
    x, dt, A, Bm, Cm, D, *,
    chunk: int = 128, interpret: bool = False, force_ref: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    ld = dt.astype(jnp.float32) * A.astype(jnp.float32)[None, None, :]
    return gated_scan(
        x, ld, dt, Bm, Cm, D,
        chunk=chunk, interpret=interpret, force_ref=force_ref,
    )


ssm_step = jax.jit(ssm_step_ref)
gated_step = jax.jit(gated_step_ref)

__all__ = [
    "gated_scan", "gated_scan_path", "gated_step", "ssm_scan", "ssm_step",
    "gated_scan_ref", "gated_step_ref", "ssm_scan_ref", "ssm_step_ref",
]
