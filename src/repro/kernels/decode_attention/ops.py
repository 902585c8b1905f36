"""jit'd public wrapper for decode attention (one token vs KV cache)."""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import (
    DEFAULT_BLOCK_K,
    decode_attention_pallas,
)
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.dispatch import REFERENCE, KernelPath, choose


def decode_attention_path(
    q_shape: Sequence[int],
    k_shape: Sequence[int],
    *,
    interpret: bool = False,
    force_ref: bool = False,
) -> KernelPath:
    """The implementation a call with these shapes takes.  On the chip the
    kernel's (block_k, D) KV tiles need D to fill whole 128-wide lanes, and
    a cache longer than one tile must split into whole tiles."""
    d = q_shape[-1]
    s = k_shape[1]
    refusal = None
    if d % 128:
        refusal = f"head dim {d} is not a multiple of 128"
    elif s > DEFAULT_BLOCK_K and s % DEFAULT_BLOCK_K:
        refusal = (
            f"cache length {s} is not a multiple of the {DEFAULT_BLOCK_K}-row "
            "tile"
        )
    elif s % 8:
        refusal = f"cache length {s} is not a multiple of 8"
    return choose(
        "decode_attention", interpret=interpret, force_ref=force_ref,
        refusal=refusal,
    )


@partial(jax.jit, static_argnames=("window", "interpret", "force_ref"))
def decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    kv_len: jnp.ndarray,
    *,
    window: Optional[int] = None,
    interpret: bool = False,
    force_ref: bool = False,
) -> jnp.ndarray:
    """q (B,Hq,D) × cache (B,S,Hkv,D), valid lengths (B,) -> (B,Hq,D)."""
    path = decode_attention_path(
        q.shape, k_cache.shape, interpret=interpret, force_ref=force_ref
    )
    if path.impl == REFERENCE:
        return decode_attention_ref(q, k_cache, v_cache, kv_len, window=window)
    return decode_attention_pallas(
        q, k_cache, v_cache, kv_len, window=window,
        interpret=interpret,
    )


__all__ = ["decode_attention", "decode_attention_path", "decode_attention_ref"]
