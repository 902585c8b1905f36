"""jit'd public wrapper for flash attention.

Dispatch policy (``flash_attention_path``):
  * TPU backend → Pallas kernel (compiled);
  * interpret=True (tests) → Pallas kernel body in interpret mode;
  * otherwise (CPU, or a shape the chip cannot tile) → chunked-jnp
    reference, which implements identical blockwise math at O(S) memory.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import REFERENCE, KernelPath, choose
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_chunked, attention_dense


def flash_attention_path(
    q_shape: Sequence[int],
    k_shape: Sequence[int],
    *,
    interpret: bool = False,
    force_ref: bool = False,
) -> KernelPath:
    """The implementation a call with these shapes takes: on the chip both
    sequence lengths must split into whole 128-row tiles."""
    sq, sk = q_shape[1], k_shape[1]
    refusal = None
    if sq % 128 or sk % 128:
        refusal = f"sequence lengths ({sq}, {sk}) are not multiples of 128"
    return choose(
        "flash_attention", interpret=interpret, force_ref=force_ref,
        refusal=refusal,
    )


@partial(
    jax.jit,
    static_argnames=(
        "causal",
        "window",
        "logit_cap",
        "q_offset",
        "interpret",
        "force_ref",
    ),
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    logit_cap: Optional[float] = None,
    q_offset: int = 0,
    interpret: bool = False,
    force_ref: bool = False,
) -> jnp.ndarray:
    """Fused attention: q (B,Sq,Hq,D) × kv (B,Sk,Hkv,D) -> (B,Sq,Hq,D)."""
    path = flash_attention_path(
        q.shape, k.shape, interpret=interpret, force_ref=force_ref
    )
    if path.impl == REFERENCE:
        return attention_chunked(
            q, k, v, causal=causal, window=window, logit_cap=logit_cap,
            q_offset=q_offset,
        )
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, logit_cap=logit_cap,
        q_offset=q_offset, interpret=interpret,
    )


__all__ = [
    "flash_attention", "flash_attention_path", "attention_chunked",
    "attention_dense",
]
