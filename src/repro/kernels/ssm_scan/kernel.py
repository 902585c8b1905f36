"""Pallas TPU kernel for the chunked gated linear recurrence (SSD form).

Serves both Mamba2 (ld = dt*A, gi = dt) and mLSTM (ld = logsigmoid(f), gi =
exp(i), B/C/x = k/q/v) — see ref.py for the algebra.

Operands are head-major, (B, H, S, ·), so each block is a (chunk, width)
tile of one head: the chip's compiler accepts a second-minor block only of
8-row multiples or the whole axis, so the head axis cannot sit there.  The
per-step scalars (log-decay, input scale) travel both as a row and as a
column, which keeps every value in the kernel two-dimensional.

Grid: (batch, heads, chunks) with the chunk axis innermost/sequential — the
inter-chunk state h (N x P) lives in VMEM scratch and is carried across chunk
iterations, so the whole recurrence runs in one kernel launch with no HBM
state round-trips (the GPU reference implementation writes chunk states to
HBM and launches a second scan kernel; on TPU the sequential-grid carry makes
that unnecessary — the TPU-native adaptation of the SSD algorithm).

Per chunk (Q=128): builds the (Q,Q) decay-masked score matrix in VMEM, three
MXU matmuls (C·Bᵀ, scores·x, Bᵀ·x) and one state update.  VMEM at Q=128,
N=P=64, f32 ≈ 0.3 MiB — far under budget, so larger Q/N/P still fit.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    D_ref,      # SMEM (H,)
    x_ref,      # (1, 1, Q, P)
    ldr_ref,    # (1, 1, 1, Q)  log-decay as a row
    ldc_ref,    # (1, 1, Q, 1)  ... and as a column
    gir_ref,    # (1, 1, 1, Q)  input scale as a row
    gic_ref,    # (1, 1, Q, 1)  ... and as a column
    B_ref,      # (1, 1, Q, N)
    C_ref,      # (1, 1, Q, N)
    y_ref,      # (1, 1, Q, P)
    hout_ref,   # (1, 1, N, P)
    h_scratch,  # VMEM (N, P)
    *,
    chunk: int,
    num_chunks: int,
    use_d: bool,
):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scratch[...] = jnp.zeros_like(h_scratch)

    x = x_ref[0, 0].astype(jnp.float32)                # (Q, P)
    ld_r = ldr_ref[0, 0].astype(jnp.float32)           # (1, Q)
    ld_c = ldc_ref[0, 0].astype(jnp.float32)           # (Q, 1)
    gi_r = gir_ref[0, 0].astype(jnp.float32)           # (1, Q)
    gi_c = gic_ref[0, 0].astype(jnp.float32)           # (Q, 1)
    Bm = B_ref[0, 0].astype(jnp.float32)               # (Q, N)
    Cm = C_ref[0, 0].astype(jnp.float32)               # (Q, N)

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = row >= col
    # inclusive prefix sums of the log-decay as a column and as a row, by
    # masked reductions (2-D only: no 1-D vectors or cumsum in the kernel)
    cs_c = jnp.sum(jnp.where(causal, ld_r, 0.0), axis=1, keepdims=True)
    cs_r = jnp.sum(jnp.where(row <= col, ld_c, 0.0), axis=0, keepdims=True)
    total = jnp.sum(ld_c, axis=0, keepdims=True)       # (1, 1)
    decay = jnp.where(causal, jnp.exp(cs_c - cs_r), 0.0)

    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    scores = scores * decay * gi_r
    y = jax.lax.dot_general(
        scores, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    h_prev = h_scratch[...]                             # (N, P)
    y = y + jnp.exp(cs_c) * jax.lax.dot_general(
        Cm, h_prev, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    if use_d:
        y = y + x * D_ref[hi]
    y_ref[0, 0] = y.astype(y_ref.dtype)

    decay_to_end = jnp.exp(total - cs_c) * gi_c         # (Q, 1)
    h_new = jnp.exp(total) * h_prev + jax.lax.dot_general(
        Bm * decay_to_end, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    h_scratch[...] = h_new

    @pl.when(ci == num_chunks - 1)
    def _emit_state():
        hout_ref[0, 0] = h_new.astype(hout_ref.dtype)


def gated_scan_pallas(
    x: jnp.ndarray,
    log_decay: jnp.ndarray,
    in_scale: jnp.ndarray,
    Bm: jnp.ndarray,
    Cm: jnp.ndarray,
    D: Optional[jnp.ndarray] = None,
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    b, s, h, p = x.shape
    _, _, g, n = Bm.shape
    rep = h // g
    chunk = min(chunk, s)
    if s % chunk:
        raise ValueError(f"seq {s} not divisible by chunk {chunk}")
    nc = s // chunk

    use_d = D is not None
    d_arr = (D if use_d else jnp.zeros((h,), jnp.float32)).astype(jnp.float32)

    # head-major operands: every block is a (chunk, width) tile of one head
    xh = jnp.swapaxes(x, 1, 2)                          # (B, H, S, P)
    ld = jnp.swapaxes(log_decay, 1, 2)                  # (B, H, S)
    gi = jnp.swapaxes(in_scale, 1, 2)
    Bh = jnp.swapaxes(Bm, 1, 2)                         # (B, G, S, N)
    Ch = jnp.swapaxes(Cm, 1, 2)
    row_spec = pl.BlockSpec((1, 1, 1, chunk), lambda b_, h_, c: (b_, h_, 0, c))
    col_spec = pl.BlockSpec((1, 1, chunk, 1), lambda b_, h_, c: (b_, h_, c, 0))
    group_spec = pl.BlockSpec(
        (1, 1, chunk, n), lambda b_, h_, c, rep=rep: (b_, h_ // rep, c, 0)
    )
    kernel = functools.partial(
        _ssd_kernel, chunk=chunk, num_chunks=nc, use_d=use_d
    )
    y, h_final = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, c: (b_, h_, c, 0)),
            row_spec,
            col_spec,
            row_spec,
            col_spec,
            group_spec,
            group_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, 1, n, p), lambda b_, h_, c: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(xh.shape, x.dtype),
            jax.ShapeDtypeStruct((b, h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
        name="gated_scan",
    )(
        d_arr, xh, ld[:, :, None, :], ld[..., None], gi[:, :, None, :],
        gi[..., None], Bh, Ch,
    )
    return jnp.swapaxes(y, 1, 2), h_final


def ssm_scan_pallas(
    x, dt, A, Bm, Cm, D, *, chunk: int = 128, interpret: bool = False
):
    """Mamba2 wrapper: log-decay = dt*A, input scale = dt."""
    ld = dt.astype(jnp.float32) * A.astype(jnp.float32)[None, None, :]
    return gated_scan_pallas(
        x, ld, dt, Bm, Cm, D, chunk=chunk, interpret=interpret
    )
