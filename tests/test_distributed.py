"""Distributed utilities: sharding translation, ZeRO-1 spec derivation,
int8 gradient compression with error feedback, straggler mitigation."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.compression import (
    compressed_psum,
    dequantize_int8,
    quantize_int8,
)
from repro.distributed.sharding import (
    auto_mesh,
    translate_spec,
    zero1_spec,
)
from repro.distributed.straggler import (
    HedgedRouter,
    ReplicaModel,
    SkipAndRescale,
)


class TestShardingTranslate:
    def test_logical_axes(self):
        assert translate_spec(P("dp", None, "tp"), ("data", "model")) == P(
            "data", None, "model"
        )
        assert translate_spec(P("dp", "tp"), ("pod", "data", "model")) == P(
            ("pod", "data"), "model"
        )

    def test_unknown_axis_dropped(self):
        assert translate_spec(P("tp"), ("data",)) == P(None)

    def test_zero1_adds_dp_on_first_divisible(self):
        assert zero1_spec(P(None, "tp"), (64, 128), 16) == P("dp", "tp")
        # first dim not divisible -> second
        assert zero1_spec(P(None, None), (7, 32), 16) == P(None, "dp")
        # nothing divisible -> unchanged
        assert zero1_spec(P(None,), (7,), 16) == P(None)


class TestCompression:
    def test_quantize_roundtrip_error_bound(self, rng):
        x = jnp.asarray(rng.normal(0, 1, (128,)).astype(np.float32))
        q, scale = quantize_int8(x)
        err = jnp.abs(dequantize_int8(q, scale) - x).max()
        assert float(err) <= float(scale) * 0.5 + 1e-6

    def test_compressed_psum_shard_map(self, rng):
        mesh = auto_mesh((1,), ("data",))
        x = jnp.asarray(rng.normal(0, 1, (64,)).astype(np.float32))

        shard_map = jax.shard_map

        f = shard_map(
            lambda v: compressed_psum(v, "data")[0],
            mesh=mesh,
            in_specs=P(None),
            out_specs=P(None),
        )
        out = f(x)
        # single shard: mean == dequantized self
        q, s = quantize_int8(x)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(dequantize_int8(q, s)), rtol=1e-6
        )

    def test_error_feedback_converges(self, rng):
        """Repeated compressed reductions of the same gradient with error
        feedback: the accumulated applied update converges to the true sum."""
        x = jnp.asarray(rng.normal(0, 1, (256,)).astype(np.float32))
        err = jnp.zeros_like(x)
        applied = jnp.zeros_like(x)
        mesh = auto_mesh((1,), ("data",))
        shard_map = jax.shard_map

        step = shard_map(
            lambda v, e: compressed_psum(v, "data", e),
            mesh=mesh, in_specs=(P(None), P(None)), out_specs=(P(None), P(None)),
        )
        n = 50
        for _ in range(n):
            out, err = step(x, err)
            applied = applied + out
        np.testing.assert_allclose(
            np.asarray(applied) / n, np.asarray(x), rtol=0, atol=2e-2
        )

    def test_wire_bytes_reduction(self):
        x = jnp.zeros((1024,), jnp.float32)
        q, _ = quantize_int8(x)
        assert q.dtype == jnp.int8 and q.nbytes * 4 == x.nbytes


class TestStraggler:
    def test_hedge_cuts_tail(self):
        def spiky(i):
            return 0.5 if i % 10 == 3 else 0.0

        replicas = [
            ReplicaModel("a", 0.010, spiky),
            ReplicaModel("b", 0.010, lambda i: 0.0),
            ReplicaModel("c", 0.010, lambda i: 0.0),
        ]
        router = HedgedRouter(replicas, hedge_multiplier=2.0)
        for i in range(300):
            router.dispatch(i)
        assert router.stats.hedged > 0
        assert router.stats.p99 < 0.2  # without hedging p99 would be ~0.51

    def test_failed_replica_recovered(self):
        replicas = [
            ReplicaModel("dead", 0.01, lambda i: 0.0, failed=True),
            ReplicaModel("alive", 0.01, lambda i: 0.0),
        ]
        router = HedgedRouter(replicas, hedge_multiplier=2.0)
        for i in range(20):
            t, winner = router.dispatch(i)
            assert winner == "alive"

    def test_skip_and_rescale(self):
        pol = SkipAndRescale(world=10, quorum_fraction=0.8)
        ok, scale = pol.step([True] * 9 + [False])
        assert ok and scale == pytest.approx(10 / 9)
        ok, _ = pol.step([True] * 7 + [False] * 3)
        assert not ok
