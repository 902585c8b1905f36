"""Per-kernel correctness sweeps: Pallas kernels (interpret mode) and the
chunked portable paths vs the pure-jnp dense oracles, across shapes/dtypes.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.kernels.decode_attention import decode_attention, decode_attention_ref
from repro.kernels.flash_attention import (
    attention_chunked,
    attention_dense,
    flash_attention,
)
from repro.kernels.rmsnorm import rmsnorm, rmsnorm_ref
from repro.kernels.ssm_scan import (
    gated_scan,
    gated_scan_ref,
    ssm_scan,
    ssm_scan_ref,
    ssm_step_ref,
)

TOL = dict(rtol=2e-4, atol=2e-4)


class TestFlashAttention:
    @pytest.mark.parametrize(
        "b,sq,sk,hq,hkv,d,causal,window",
        [
            (2, 128, 128, 4, 2, 64, True, None),
            (1, 256, 256, 8, 8, 128, True, 128),
            (1, 128, 384, 4, 1, 64, True, None),
            (2, 128, 128, 4, 4, 64, False, None),
            (1, 256, 256, 2, 2, 128, True, None),
        ],
    )
    def test_pallas_vs_dense(self, rng, b, sq, sk, hq, hkv, d, causal, window):
        q = rng.normal(0, 1, (b, sq, hq, d)).astype(np.float32)
        k = rng.normal(0, 1, (b, sk, hkv, d)).astype(np.float32)
        v = rng.normal(0, 1, (b, sk, hkv, d)).astype(np.float32)
        qoff = sk - sq
        ref = attention_dense(q, k, v, causal=causal, window=window, q_offset=qoff)
        out = flash_attention(
            q, k, v, causal=causal, window=window, q_offset=qoff, interpret=True
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_dtypes(self, rng, dtype):
        import jax.numpy as jnp

        q = rng.normal(0, 1, (1, 128, 4, 64)).astype(dtype)
        k = rng.normal(0, 1, (1, 128, 2, 64)).astype(dtype)
        v = rng.normal(0, 1, (1, 128, 2, 64)).astype(dtype)
        ref = attention_dense(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        out = flash_attention(q, k, v, interpret=True)
        tol = 2e-2 if dtype == "bfloat16" else 2e-4
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=tol, atol=tol,
        )

    def test_chunked_nondivisible_kv(self, rng):
        # whisper cross-attention case: 1500 keys, chunk 1024
        q = rng.normal(0, 1, (1, 64, 4, 32)).astype(np.float32)
        k = rng.normal(0, 1, (1, 1500, 4, 32)).astype(np.float32)
        v = rng.normal(0, 1, (1, 1500, 4, 32)).astype(np.float32)
        ref = attention_dense(q, k, v, causal=False)
        out = attention_chunked(q, k, v, causal=False, kv_chunk=1024)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


class TestDecodeAttention:
    @pytest.mark.parametrize(
        "b,s,hq,hkv,d,window",
        [
            (2, 1024, 8, 2, 64, None),
            (1, 2048, 16, 8, 128, None),
            (2, 1024, 4, 4, 64, 256),
            (1, 512, 8, 1, 64, None),
            (3, 512, 40, 40, 64, None),     # MHA-style
        ],
    )
    def test_pallas_vs_ref(self, rng, b, s, hq, hkv, d, window):
        import jax.numpy as jnp

        q = rng.normal(0, 1, (b, hq, d)).astype(np.float32)
        kc = rng.normal(0, 1, (b, s, hkv, d)).astype(np.float32)
        vc = rng.normal(0, 1, (b, s, hkv, d)).astype(np.float32)
        kv_len = jnp.asarray(
            (np.arange(b) * 97 % (s - 8) + 8).astype(np.int32)
        )
        ref = decode_attention_ref(q, kc, vc, kv_len, window=window)
        out = decode_attention(q, kc, vc, kv_len, window=window, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


class TestRMSNorm:
    @pytest.mark.parametrize(
        "shape,offset", [((4, 128, 256), 0.0), ((2, 64, 512), 1.0), ((3, 7, 96), 0.0)]
    )
    def test_pallas_vs_ref(self, rng, shape, offset):
        x = rng.normal(0, 1, shape).astype(np.float32)
        s = rng.normal(0, 0.1, shape[-1:]).astype(np.float32)
        ref = rmsnorm_ref(x, s, offset=offset)
        out = rmsnorm(x, s, offset=offset, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-6)


class TestSSMScan:
    def _naive(self, x, dt, A, Bm, Cm, D):
        b, s, h, p = x.shape
        g, n = Bm.shape[2], Bm.shape[3]
        rep = h // g
        hst = np.zeros((b, h, n, p), np.float64)
        ys = np.zeros_like(x, dtype=np.float64)
        for t in range(s):
            for bb in range(b):
                for hh in range(h):
                    gg = hh // rep
                    dA = np.exp(dt[bb, t, hh] * A[hh])
                    hst[bb, hh] = dA * hst[bb, hh] + dt[bb, t, hh] * np.outer(
                        Bm[bb, t, gg], x[bb, t, hh]
                    )
                    ys[bb, t, hh] = Cm[bb, t, gg] @ hst[bb, hh] + D[hh] * x[bb, t, hh]
        return ys, hst

    @pytest.mark.parametrize(
        "b,s,h,p,g,n,chunk",
        [(2, 64, 4, 8, 2, 16, 16), (1, 96, 8, 16, 1, 32, 32), (1, 48, 2, 8, 2, 8, 16)],
    )
    def test_chunked_vs_naive(self, rng, b, s, h, p, g, n, chunk):
        x = rng.normal(0, 1, (b, s, h, p)).astype(np.float32)
        dt = (np.abs(rng.normal(0.5, 0.2, (b, s, h))) + 0.01).astype(np.float32)
        A = -np.abs(rng.normal(1, 0.3, (h,))).astype(np.float32)
        Bm = rng.normal(0, 1, (b, s, g, n)).astype(np.float32)
        Cm = rng.normal(0, 1, (b, s, g, n)).astype(np.float32)
        D = rng.normal(0, 1, (h,)).astype(np.float32)
        y_naive, h_naive = self._naive(x, dt, A, Bm, Cm, D)
        y_ref, h_ref = ssm_scan_ref(x, dt, A, Bm, Cm, D, chunk=chunk)
        np.testing.assert_allclose(np.asarray(y_ref), y_naive, rtol=3e-4, atol=3e-4)
        y_pl, h_pl = ssm_scan(x, dt, A, Bm, Cm, D, chunk=chunk, interpret=True)
        np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_ref), **TOL)
        np.testing.assert_allclose(np.asarray(h_pl), np.asarray(h_ref), **TOL)

    def test_step_matches_scan(self, rng):
        b, s, h, p, g, n = 2, 32, 4, 8, 2, 16
        x = rng.normal(0, 1, (b, s, h, p)).astype(np.float32)
        dt = (np.abs(rng.normal(0.5, 0.2, (b, s, h))) + 0.01).astype(np.float32)
        A = -np.abs(rng.normal(1, 0.3, (h,))).astype(np.float32)
        Bm = rng.normal(0, 1, (b, s, g, n)).astype(np.float32)
        Cm = rng.normal(0, 1, (b, s, g, n)).astype(np.float32)
        D = rng.normal(0, 1, (h,)).astype(np.float32)
        y_scan, h_scan = ssm_scan_ref(x, dt, A, Bm, Cm, D, chunk=8)
        hst = np.zeros((b, h, n, p), np.float32)
        for t in range(s):
            y_t, hst = ssm_step_ref(
                x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], D, hst
            )
        np.testing.assert_allclose(
            np.asarray(y_t), np.asarray(y_scan[:, -1]), rtol=2e-3, atol=2e-3
        )
        np.testing.assert_allclose(np.asarray(hst), np.asarray(h_scan), rtol=2e-3, atol=2e-3)

    def test_gated_form_mlstm(self, rng):
        b, s, h, p, g, n, chunk = 2, 48, 4, 8, 4, 8, 16
        x = rng.normal(0, 1, (b, s, h, p)).astype(np.float32)
        ld = -np.abs(rng.normal(0.3, 0.2, (b, s, h))).astype(np.float32)
        gi = np.abs(rng.normal(0.8, 0.3, (b, s, h))).astype(np.float32)
        Bm = rng.normal(0, 1, (b, s, g, n)).astype(np.float32)
        Cm = rng.normal(0, 1, (b, s, g, n)).astype(np.float32)
        y_ref, h_ref = gated_scan_ref(x, ld, gi, Bm, Cm, None, chunk=chunk)
        y_pl, h_pl = gated_scan(x, ld, gi, Bm, Cm, None, chunk=chunk, interpret=True)
        np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_ref), **TOL)
        np.testing.assert_allclose(np.asarray(h_pl), np.asarray(h_ref), **TOL)

    def test_nondivisible_seq_padding(self, rng):
        b, s, h, p, g, n = 1, 17, 2, 4, 1, 8
        x = rng.normal(0, 1, (b, s, h, p)).astype(np.float32)
        dt = (np.abs(rng.normal(0.5, 0.2, (b, s, h))) + 0.01).astype(np.float32)
        A = -np.abs(rng.normal(1, 0.3, (h,))).astype(np.float32)
        Bm = rng.normal(0, 1, (b, s, g, n)).astype(np.float32)
        Cm = rng.normal(0, 1, (b, s, g, n)).astype(np.float32)
        D = rng.normal(0, 1, (h,)).astype(np.float32)
        y_naive, h_naive = self._naive(x, dt, A, Bm, Cm, D)
        y, h_f = ssm_scan(x, dt, A, Bm, Cm, D, chunk=8)
        np.testing.assert_allclose(np.asarray(y), y_naive, rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(h_f), h_naive, rtol=3e-4, atol=3e-4)


class TestKernelPaths:
    """Which implementation each dispatch takes on a TPU backend, and why —
    the choice is named, never silent."""

    @pytest.fixture
    def on_tpu(self, monkeypatch):
        import repro.kernels.dispatch as dispatch

        monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")

    @pytest.mark.parametrize(
        "q_shape,k_shape,impl",
        [
            ((1, 16, 128), (1, 512, 8, 128), "pallas"),     # qwen3 served bucket
            ((1, 16, 128), (1, 144, 8, 128), "pallas"),     # one whole tile
            ((1, 32, 64), (1, 512, 32, 64), "reference"),   # 64-wide heads
            ((1, 16, 128), (1, 1000, 8, 128), "reference"),  # no whole tiles
        ],
    )
    def test_decode_attention_path(self, on_tpu, q_shape, k_shape, impl):
        from repro.kernels.decode_attention import decode_attention_path

        path = decode_attention_path(q_shape, k_shape)
        assert path.impl == impl and path.reason

    def test_reference_on_tpu_is_logged(self, on_tpu, caplog):
        from repro.kernels.decode_attention import decode_attention_path

        with caplog.at_level("WARNING", logger="repro.kernels"):
            decode_attention_path((1, 32, 64), (1, 512, 32, 64))
        assert "head dim 64" in caplog.text

    @pytest.mark.parametrize(
        "sq,impl", [(512, "pallas"), (300, "reference")]
    )
    def test_flash_attention_path(self, on_tpu, sq, impl):
        from repro.kernels.flash_attention.ops import flash_attention_path

        assert flash_attention_path((1, sq, 16, 128), (1, sq, 8, 128)).impl == impl

    @pytest.mark.parametrize(
        "chunk,seq,impl",
        [(128, 512, "pallas"), (64, 64, "pallas"), (16, 48, "reference")],
    )
    def test_gated_scan_path(self, on_tpu, chunk, seq, impl):
        from repro.kernels.ssm_scan.ops import gated_scan_path

        assert gated_scan_path(chunk, seq).impl == impl

    def test_cpu_takes_reference_and_says_so(self):
        from repro.kernels.rmsnorm.ops import rmsnorm_path

        path = rmsnorm_path()
        assert path.impl == "reference" and "backend" in path.reason
        assert rmsnorm_path(interpret=True).impl == "interpret"

    def test_vmapped_decode_kernel_matches_ref(self, rng):
        import jax
        import jax.numpy as jnp

        q = rng.normal(0, 1, (3, 1, 8, 128)).astype(np.float32)
        kc = rng.normal(0, 1, (3, 1, 256, 2, 128)).astype(np.float32)
        vc = rng.normal(0, 1, (3, 1, 256, 2, 128)).astype(np.float32)
        kv_len = jnp.asarray([[17], [200], [256]], jnp.int32)
        out = jax.vmap(
            lambda *a: decode_attention(*a, interpret=True)
        )(q, kc, vc, kv_len)
        ref = jax.vmap(decode_attention_ref)(q, kc, vc, kv_len)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)
