"""Trip-count-weighted HLO analysis: validated against a compiled module with
a known layer-scan structure (flops must scale with the scan trip count, which
XLA's own cost_analysis misses)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_analysis import analyze_hlo, parse_module


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


class TestWeightedAnalysis:
    def test_scan_trip_count_scaling(self):
        d, L = 64, 12
        w = jnp.ones((L, d, d), jnp.float32) * 0.01
        x = jnp.ones((8, d), jnp.float32)

        def stack(x, w):
            return jax.lax.scan(lambda h, wi: (jnp.tanh(h @ wi), None), x, w)[0]

        hlo = _compile(stack, x, w).as_text()
        a = analyze_hlo(hlo)
        expected_dot = 2 * 8 * d * d * L
        assert a["dot_flops"] == pytest.approx(expected_dot, rel=0.05)

    def test_unrolled_matches_scan(self):
        d, L = 32, 6
        w = jnp.ones((L, d, d), jnp.float32) * 0.01
        x = jnp.ones((4, d), jnp.float32)

        def scanned(x, w):
            return jax.lax.scan(lambda h, wi: (h @ wi, None), x, w)[0]

        def unrolled(x, w):
            for i in range(L):
                x = x @ w[i]
            return x

        a = analyze_hlo(_compile(scanned, x, w).as_text())
        b = analyze_hlo(_compile(unrolled, x, w).as_text())
        assert a["dot_flops"] == pytest.approx(b["dot_flops"], rel=0.05)

    def test_collectives_detected(self):
        from jax.sharding import PartitionSpec as P

        from repro.distributed.sharding import auto_mesh

        mesh = auto_mesh((1,), ("d",))
        shard_map = jax.shard_map

        f = shard_map(
            lambda v: jax.lax.psum(v, "d"), mesh=mesh,
            in_specs=P(None), out_specs=P(None),
        )
        hlo = _compile(f, jnp.ones((128,), jnp.float32)).as_text()
        a = analyze_hlo(hlo)
        assert a["collective_bytes"] >= 128 * 4

    def test_parse_module_structure(self):
        hlo = _compile(lambda x: jnp.tanh(x) @ x.T, jnp.ones((8, 8))).as_text()
        comps = parse_module(hlo)
        assert len(comps) >= 1
        total_instrs = sum(len(v) for v in comps.values())
        assert total_instrs > 2
