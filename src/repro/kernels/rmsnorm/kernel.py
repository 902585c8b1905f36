"""Pallas TPU fused RMSNorm kernel.

One VMEM pass per row tile: load (block_rows, D), compute the mean-square in
f32, rescale, multiply by the (offset + scale) weight — no intermediate HBM
round trip between the reduction and the scale (XLA often splits these).
D is the model width (<= 16k fits VMEM comfortably: 256 rows x 8192 x 4 B
= 8 MiB; block_rows is chosen accordingly).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, scale_ref, o_ref, *, eps: float, offset: float):
    x = x_ref[...].astype(jnp.float32)                 # (rows, d)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    w = offset + scale_ref[...].astype(jnp.float32)    # (1, d)
    o_ref[...] = (y * w).astype(o_ref.dtype)


def rmsnorm_pallas(
    x: jnp.ndarray,
    scale: jnp.ndarray,
    eps: float = 1e-6,
    offset: float = 0.0,
    block_rows: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    orig_shape = x.shape
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    if rows <= block_rows:
        # one block holding every row: a block equal to the whole array is
        # legal on the chip whatever the row count
        block_rows = rows
        pad = 0
    else:
        # whole tiles of block_rows (a multiple of 8, as the chip's compiler
        # requires of a second-minor block); the padded rows are sliced off
        pad = (-rows) % block_rows
        if pad:
            x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    kernel = functools.partial(_rmsnorm_kernel, eps=eps, offset=offset)
    out = pl.pallas_call(
        kernel,
        grid=((rows + pad) // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, d), x.dtype),
        interpret=interpret,
        name="rmsnorm",
    )(x2, scale.reshape(1, d))
    if pad:
        out = out[:rows]
    return out.reshape(orig_shape)
