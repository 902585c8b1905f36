"""Pallas TPU decode-attention kernel (flash-decode style).

Decode is HBM-bandwidth-bound: the whole KV cache is streamed once per step.
The kernel therefore tiles over the cache sequence dimension with the
streaming-softmax state in VMEM, loading each (block_k, D) KV tile exactly
once and serving *all* q heads of its KV group from that tile (GQA groups are
rows of the score matrix — the q-head group is padded up to the 8-row VPU
sublane so tiny groups still map onto full tiles).

Grid: (batch, kv_heads, kv_blocks); the kv-block axis is innermost/sequential
so m/l/acc scratch carries across cache tiles — the classic split-KV reduce
expressed TPU-natively (sequential grid instead of a second combine kernel).

The cache is viewed as (B, S, Hkv*D) — a free reshape of the (B, S, Hkv, D)
layout — so a KV head's tile is a (block_k, D) column slab: the head axis
never sits in the second-minor block position, where the chip's compiler
only accepts blocks of 8 rows or the whole axis.  On the chip this needs
D % 128 == 0 (the lane width); ``ops.decode_attention_path`` names the
shapes that take the reference instead.

``kv_len`` rides in SMEM (scalar per batch row) and masks the tail tile.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_K = 512
_MIN_ROWS = 8  # VPU sublane count — pad q-head group rows up to this


def _decode_kernel(
    kv_len_ref,   # SMEM (B,), scalar-prefetched
    q_ref,        # (1, 1, rows, d)
    k_ref,        # (1, block_k, d)
    v_ref,        # (1, block_k, d)
    o_ref,        # (1, 1, rows, d)
    m_scratch,
    l_scratch,
    acc_scratch,
    *,
    block_k: int,
    num_kv_blocks: int,
    window: Optional[int],
    sm_scale: float,
):
    bi = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    kv_len = kv_len_ref[bi]
    block_start = ki * block_k
    lo = 0 if window is None else kv_len - window
    run = block_start < kv_len
    if window is not None:
        run &= block_start + block_k > lo

    @pl.when(run)
    def _body():
        q = q_ref[0, 0, :, :].astype(jnp.float32)          # (rows, d)
        k = k_ref[0].astype(jnp.float32)                    # (block_k, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale                                        # (rows, block_k)
        pos = block_start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        )
        ok = pos < kv_len
        if window is not None:
            ok &= pos >= kv_len - window
        s = jnp.where(ok, s, NEG_INF)

        m_prev, l_prev = m_scratch[...], l_scratch[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scratch[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scratch[...] = acc_scratch[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scratch[...] = m_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scratch[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_scratch[...] / denom).astype(o_ref.dtype)


def decode_attention_pallas(
    q: jnp.ndarray,          # (B, Hq, D)
    k_cache: jnp.ndarray,    # (B, S, Hkv, D)
    v_cache: jnp.ndarray,
    kv_len: jnp.ndarray,     # (B,) int32
    *,
    window: Optional[int] = None,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jnp.ndarray:
    b, hq, d = q.shape
    _, s, hkv, _ = k_cache.shape
    n_rep = hq // hkv
    rows = max(n_rep, _MIN_ROWS)
    pad = rows - n_rep
    block_k = min(block_k, s)
    if s % block_k:
        raise ValueError(f"cache length {s} not divisible by block_k {block_k}")

    # (B, Hkv, rows, D): q heads grouped by their KV head, rows padded to the
    # VPU sublane count so each KV tile load serves a full tile of queries
    qg = q.reshape(b, hkv, n_rep, d)
    if pad:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, pad), (0, 0)))

    # (B, S, Hkv*D): KV head g is the column block g of width D
    k2 = k_cache.reshape(b, s, hkv * d)
    v2 = v_cache.reshape(b, s, hkv * d)
    kernel = functools.partial(
        _decode_kernel,
        block_k=block_k,
        num_kv_blocks=s // block_k,
        window=window,
        sm_scale=1.0 / float(d) ** 0.5,
    )
    # kv_len is scalar-prefetched into SMEM: a prefetch operand keeps the
    # kernel legal under jax.vmap (the batched server step), where a plain
    # SMEM block of the now two-dimensional lengths is refused
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, s // block_k),
            in_specs=[
                pl.BlockSpec(
                    (1, 1, rows, d), lambda b_, g, ki, lens: (b_, g, 0, 0)
                ),
                pl.BlockSpec((1, block_k, d), lambda b_, g, ki, lens: (b_, ki, g)),
                pl.BlockSpec((1, block_k, d), lambda b_, g, ki, lens: (b_, ki, g)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, rows, d), lambda b_, g, ki, lens: (b_, g, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, d), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(kv_len.astype(jnp.int32), qg, k2, v2)
    return out[:, :, :n_rep, :].reshape(b, hq, d)
