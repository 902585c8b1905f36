"""Compile-only checks of the Pallas kernels for a TPU v5e chip.

The chip's compiler is installed here, and it compiles for a chip that is
described rather than attached, so these tests catch a kernel the chip
would refuse (block shapes off the tiling, too much fast memory) without
one.  Shapes are the main path's published widths: qwen3-0.6b attention
(16 q heads, 8 KV heads of 128) and its d_model 1024, zamba2-1.2b's
Mamba2 scan and 64-wide attention heads, and xlstm-1.3b's mLSTM scan.
Nothing runs, so nothing here says anything about results or speed.

The topology is described inside a module fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
test workers that are not given this file must not try.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.rmsnorm.kernel import rmsnorm_pallas
from repro.kernels.ssm_scan.kernel import gated_scan_pallas
from repro.launch.hlo_analysis import tpu_custom_calls

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile_kernels(fn, one_chip, *shapes):
    """Compile ``fn`` for one described v5e chip; return the Pallas kernel
    calls in the executable (raises what the chip's compiler raises)."""
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    return tpu_custom_calls(compiled.as_text())


def _only(calls, name):
    return len(calls) == 1 and name in calls[0]


def _decode(q, k, v, kv_len):
    return decode_attention_pallas(q, k, v, kv_len)


@pytest.mark.parametrize(
    "s", [512, 144, 2048], ids=lambda s: f"cache{s}"
)
def test_decode_attention_compiles(one_chip, s):
    # qwen3-0.6b decode: q (1, 16, 128) against a bf16 (1, S, 8, 128) cache;
    # S=512 is the served bucket, 144 the plain engine's prompt+answer cache
    calls = _compile_kernels(
        _decode, one_chip,
        ((1, 16, 128), BF16), ((1, s, 8, 128), BF16), ((1, s, 8, 128), BF16),
        ((1,), I32),
    )
    assert _only(calls, "decode_attention"), calls


def test_decode_attention_vmapped_compiles(one_chip):
    # the batched server step maps the decode step over 4 co-tenant caches
    calls = _compile_kernels(
        jax.vmap(_decode), one_chip,
        ((4, 1, 16, 128), BF16), ((4, 1, 512, 8, 128), BF16),
        ((4, 1, 512, 8, 128), BF16), ((4, 1), I32),
    )
    assert _only(calls, "decode_attention"), calls


@pytest.mark.parametrize(
    "hq,hkv,d",
    [(16, 8, 128), (32, 32, 64)],
    ids=["qwen3-0.6b", "zamba2-1.2b"],
)
def test_flash_attention_compiles(one_chip, hq, hkv, d):
    calls = _compile_kernels(
        flash_attention_pallas, one_chip,
        ((1, 512, hq, d), BF16), ((1, 512, hkv, d), BF16),
        ((1, 512, hkv, d), BF16),
    )
    assert _only(calls, "flash_attention"), calls


@pytest.mark.parametrize(
    "h,p,g,n,use_d",
    [
        (32, 64, 1, 64, True),       # the (1, 512, 32, 64) scan, chunk 128
        (64, 64, 1, 64, True),       # zamba2-1.2b Mamba2: d_inner 4096 / 64
        (4, 1025, 4, 1024, False),   # xlstm-1.3b mLSTM: 4 heads of 1024 (+1)
    ],
    ids=["h32", "zamba2-1.2b", "xlstm-1.3b"],
)
def test_gated_scan_compiles(one_chip, h, p, g, n, use_d):
    def scan(x, ld, gi, bm, cm, d):
        return gated_scan_pallas(
            x, ld, gi, bm, cm, d if use_d else None, chunk=128
        )

    calls = _compile_kernels(
        scan, one_chip,
        ((1, 512, h, p), BF16), ((1, 512, h), F32), ((1, 512, h), F32),
        ((1, 512, g, n), BF16), ((1, 512, g, n), BF16), ((h,), F32),
    )
    assert _only(calls, "gated_scan"), calls


@pytest.mark.parametrize(
    "rows,d",
    [(1, 1024), (8, 128), (16, 128), (300, 1024), (512, 1024)],
    ids=lambda v: str(v),
)
def test_rmsnorm_compiles(one_chip, rows, d):
    # 1 x 1024: a decode token; 16/8 x 128: qwen3 q/k head norms; 300: a
    # row count that is not a whole number of tiles
    calls = _compile_kernels(
        rmsnorm_pallas, one_chip, ((rows, d), BF16), ((d,), BF16)
    )
    assert _only(calls, "rmsnorm"), calls


def test_rmsnorm_vmapped_compiles(one_chip):
    calls = _compile_kernels(
        jax.vmap(rmsnorm_pallas, in_axes=(0, None)), one_chip,
        ((4, 1, 1024), BF16), ((1024,), BF16),
    )
    assert _only(calls, "rmsnorm"), calls
