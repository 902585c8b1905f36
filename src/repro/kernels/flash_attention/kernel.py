"""Pallas TPU flash-attention kernel (forward).

TPU-native tiling: the grid is (batch, q_heads, q_blocks, kv_blocks) with the
kv-block axis innermost — TPU grids execute the last axis sequentially per
core, so the streaming-softmax state (m, l, acc) lives in VMEM scratch and is
carried across kv iterations.  Causal/window blocks that are fully masked are
skipped with ``pl.when`` (block-level causal skip ~halves work).

Block sizes default to (128, 128): MXU-aligned (multiples of 8×128 for f32,
16×128 for bf16 tiles) and small enough that q/k/v/acc tiles fit VMEM:
  q (128, D) + k (128, D) + v (128, D) + acc (128, D) at D<=256, f32
  = 4 * 128 * 256 * 4 B = 512 KiB  « 16 MiB VMEM/core.

GQA is expressed in the k/v BlockSpec index maps (kv head = q head // n_rep)
so no KV replication ever materializes.

The kernel runs head-major, (B, H, S, D): each block is a (block, D) tile of
one head, so the head axis never sits in the second-minor block position,
where the chip's compiler only accepts 8-row multiples or the whole axis.
The wrapper transposes in and out of the (B, S, H, D) layout the model uses.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _flash_fwd_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_scratch,
    l_scratch,
    acc_scratch,
    *,
    causal: bool,
    window: Optional[int],
    logit_cap: Optional[float],
    q_offset: int,
    block_q: int,
    block_k: int,
    num_kv_blocks: int,
    sm_scale: float,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    q_block_start = q_offset + qi * block_q
    k_block_start = ki * block_k

    # block-level skip: causal blocks fully above the diagonal, window blocks
    # fully outside the sliding window
    run = jnp.array(True)
    if causal:
        run &= k_block_start <= q_block_start + block_q - 1
    if window is not None:
        run &= k_block_start + block_k - 1 > q_block_start - window

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)              # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        s = s * sm_scale
        if logit_cap is not None:
            s = logit_cap * jnp.tanh(s / logit_cap)

        q_pos = q_block_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_block_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        ok = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            ok &= k_pos <= q_pos
        if window is not None:
            ok &= k_pos > q_pos - window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scratch[...]                          # (bq, 1)
        l_prev = l_scratch[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                           # (bq, bk)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scratch[...] = acc_scratch[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scratch[...] = m_new
        l_scratch[...] = l_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scratch[...], 1e-30)
        o_ref[0, 0] = (acc_scratch[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    logit_cap: Optional[float] = None,
    q_offset: int = 0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jnp.ndarray:
    """q: (B, Sq, Hq, D); k, v: (B, Sk, Hkv, D) -> (B, Sq, Hq, D)."""
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    if hq % hkv != 0:
        raise ValueError(f"Hq={hq} not a multiple of Hkv={hkv}")
    n_rep = hq // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(f"seq lengths ({sq},{sk}) not divisible by blocks ({block_q},{block_k})")
    grid = (b, hq, sq // block_q, sk // block_k)

    kernel = functools.partial(
        _flash_fwd_kernel,
        causal=causal,
        window=window,
        logit_cap=logit_cap,
        q_offset=q_offset,
        block_q=block_q,
        block_k=block_k,
        num_kv_blocks=sk // block_k,
        sm_scale=1.0 / float(d) ** 0.5,
    )
    qh = jnp.swapaxes(q, 1, 2)                         # (B, Hq, Sq, D)
    kh = jnp.swapaxes(k, 1, 2)                         # (B, Hkv, Sk, D)
    vh = jnp.swapaxes(v, 1, 2)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, d), lambda b_, h, qi, ki: (b_, h, qi, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, d),
                lambda b_, h, qi, ki, n_rep=n_rep: (b_, h // n_rep, ki, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, d),
                lambda b_, h, qi, ki, n_rep=n_rep: (b_, h // n_rep, ki, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda b_, h, qi, ki: (b_, h, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # m
            pltpu.VMEM((block_q, 1), jnp.float32),   # l
            pltpu.VMEM((block_q, d), jnp.float32),   # acc
        ],
        interpret=interpret,
        name="flash_attention",
    )(qh, kh, vh)
    return jnp.swapaxes(out, 1, 2)
