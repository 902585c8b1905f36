"""Serving engine: local generation vs RRTO-served generation equivalence,
per-token RPC collapse, and the op-sequence identification on decode."""
from __future__ import annotations

import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.serving.engine import LocalServing, RRTOServedLM

CFG = ArchConfig(
    name="t", family="dense", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
    d_head=16, d_ff=128, vocab=256, dtype="float32", rope_theta=1e4,
)


@pytest.fixture(scope="module")
def generated():
    prompt = np.random.default_rng(0).integers(0, 256, (1, 8)).astype(np.int32)
    local = LocalServing(CFG, seed=3)
    r_local = local.generate({"tokens": prompt}, max_new_tokens=12)
    served = RRTOServedLM(CFG, bucket_len=32, batch=1, seed=3, min_repeats=3)
    r_srv = served.generate(prompt, max_new_tokens=12)
    return r_local, r_srv, served


class TestRRTOServing:
    def test_tokens_identical(self, generated):
        """The fast path (stateful, donation-aware replay) is token-for-token
        equal with LocalServing — the KV cache advancing server-side inside
        the donated step executable computes exactly the local decode loop."""
        r_local, r_srv, _ = generated
        np.testing.assert_array_equal(r_srv.tokens, r_local.tokens)

    def test_rpc_collapse(self, generated):
        _, _, served = generated
        hist = served.session.history
        assert hist[0].rpcs > 100          # recording: per-operator RPCs
        assert hist[-1].rpcs <= 3          # replaying: token/pos up, token down
        assert served.session.client.mode == "replaying"

    def test_replay_speedup(self, generated):
        _, _, served = generated
        hist = served.session.history
        assert hist[-1].wall_seconds < hist[0].wall_seconds / 5

    def test_stateful_replay_is_o1(self, generated):
        """The replayed decode step never ships or recomputes the prefix:
        the KV cache is loop-carried (detected + donated), steady per-token
        wire bytes exclude it, and per-token replay compute is the intrinsic
        step cost, orders below the full-prefix forward."""
        _, _, served = generated
        client = served.session.client
        assert client.stateful_replay
        assert len(client.ios.carried_pairs) >= 1
        program = served.session.server.context(client.client_id).replay.program
        assert program.is_stateful and program.step_fn is not None
        cache_bytes = sum(
            np.asarray(leaf).nbytes for leaf in served._cache_leaves
        )
        steady = [r for r in served.session.history if r.mode == "replaying"][1:]
        assert steady and all(r.network_bytes < cache_bytes for r in steady)

    def test_legacy_stateless_mode_matches(self):
        """The seed prefix-recompute formulation is still available and still
        exact — it is the benchmark baseline for decode_scaling."""
        prompt = np.random.default_rng(0).integers(0, 256, (1, 8)).astype(np.int32)
        local = LocalServing(CFG, seed=3)
        r_local = local.generate({"tokens": prompt}, max_new_tokens=6)
        served = RRTOServedLM(
            CFG, bucket_len=32, batch=1, seed=3, min_repeats=3, stateful=False
        )
        r_srv = served.generate(prompt, max_new_tokens=6)
        np.testing.assert_array_equal(r_srv.tokens, r_local.tokens)
        assert not served.session.client.stateful_replay

    def test_cricket_served_stays_slow(self):
        prompt = np.random.default_rng(0).integers(0, 256, (1, 8)).astype(np.int32)
        served = RRTOServedLM(
            CFG, system="cricket", bucket_len=16, batch=1, seed=3
        )
        r = served.generate(prompt, max_new_tokens=4)
        hist = served.session.history
        assert hist[-1].rpcs > 100


QK_CFG = ArchConfig(
    name="tq", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_head=16, d_ff=128, vocab=256, dtype="float32",
    rope_theta=1e4, qk_norm=True,
)


@pytest.fixture
def interpret_kernels(monkeypatch):
    """Route the decode path through the Pallas kernels in interpret mode,
    as the chip routes it through the compiled kernels: the recorded IOS
    then holds ``pallas_call`` operators (the CPU dispatch otherwise picks
    the jnp references)."""
    import functools

    import repro.layers.attention as attn
    import repro.models.lm as lm
    from repro.kernels.decode_attention import decode_attention
    from repro.kernels.rmsnorm import rmsnorm

    monkeypatch.setattr(
        attn, "decode_attention",
        functools.partial(decode_attention, interpret=True),
    )
    monkeypatch.setattr(attn, "rmsnorm", functools.partial(rmsnorm, interpret=True))
    monkeypatch.setattr(lm, "rmsnorm", functools.partial(rmsnorm, interpret=True))


class TestPallasInRecordedIOS:
    """A recorded decode step whose kernels are ``pallas_call``s: recorded
    eagerly, fingerprinted, replayed by the stateful executable, batched."""

    PROMPT = np.array([[3, 7, 11, 13, 17, 19]], np.int32)

    def test_record_replay_matches_local(self, interpret_kernels):
        ref = LocalServing(QK_CFG, seed=0).generate(
            {"tokens": self.PROMPT}, 6, max_seq=32
        )
        served = RRTOServedLM(QK_CFG, bucket_len=32, seed=0)
        out = served.generate(self.PROMPT, 6)
        np.testing.assert_array_equal(out.tokens, ref.tokens)
        client = served.session.client
        assert client.mode == "replaying" and client.stateful_replay
        assert served.session.history[-1].rpcs == 3
        funcs = {c.record.func for c in client._ios_calls if c.prim is not None}
        # the final norm is a top-level kernel; decode attention rides
        # inside the layer scan
        assert "kernel:pallas_call" in funcs and "kernel:scan" in funcs

    def test_fingerprint_and_cost_model(self, interpret_kernels):
        from repro.core.costmodel import eqn_flops

        a = RRTOServedLM(QK_CFG, bucket_len=32, seed=0)
        b = RRTOServedLM(QK_CFG, bucket_len=32, seed=0)
        for lm in (a, b):
            lm.generate(self.PROMPT, 2)
        # the pallas_call params (kernel jaxpr, grid mapping) hash the same
        # in two independent traces: co-tenants share one fingerprint
        assert a.session.client.ios_fp == b.session.client.ios_fp
        pallas = [
            e for e in a.session._steady_jaxpr.eqns
            if e.primitive.name == "pallas_call"
        ]
        assert pallas and all(np.isfinite(eqn_flops(e)) for e in pallas)

    def test_vmap_batched_rounds(self, interpret_kernels):
        from repro.serving.engine import MultiClientServedLM

        mc = MultiClientServedLM(QK_CFG, 2, bucket_len=32, seed=0)
        res = mc.generate([self.PROMPT, self.PROMPT[:, :4]], 4)
        solo = RRTOServedLM(QK_CFG, bucket_len=32, seed=0)
        for r, prompt in zip(res, [self.PROMPT, self.PROMPT[:, :4]]):
            np.testing.assert_array_equal(
                r.tokens, solo.generate(prompt, 4).tokens
            )
        assert mc.edge.batcher.vmap_batches > 0


class TestServerMemoryOnDevice:
    def test_weights_are_device_arrays(self):
        import jax

        served = RRTOServedLM(CFG, bucket_len=32, seed=0)
        served.generate(np.array([[1, 2, 3]], np.int32), 4)
        ctx = served.session.server.context(served.session.client_id)
        params = [ctx.env[a] for a in ctx.replay.param_addrs]
        assert params and all(isinstance(p, jax.Array) for p in params)
        assert all(
            isinstance(s, jax.Array) for s in ctx.replay.carried_state
        )

    def test_fleet_replicas_take_devices_round_robin(self):
        import jax

        from repro.serving import EdgeFleet

        devs = jax.devices()
        fleet = EdgeFleet(3)
        for i, rep in enumerate(fleet.replicas):
            assert rep.edge.server.jax_device is devs[i % len(devs)]


class TestServeEntryPoint:
    def test_reduced_rrto_run(self, monkeypatch, tmp_path):
        from repro.launch import serve

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        out = serve.main([
            "--arch", "qwen3-0.6b", "--reduced", "--system", "rrto",
            "--prompt-len", "6", "--tokens", "4", "--bucket-len", "32",
        ])
        assert out["mode"] == "replaying"
        assert out["rpcs_last"] == 3
        assert out["phases"]["steady_rpcs"] == [3]
        assert len(out["tokens"][0]) == 4

    def test_compile_cache_dir(self, monkeypatch, tmp_path):
        import jax

        from repro.launch import serve

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        calls = []
        monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
        assert serve.configure_compile_cache() == str(tmp_path)
        assert calls == [], "an env-set cache directory is left to JAX"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = serve.configure_compile_cache()
        assert path == str(serve.REPO_ROOT / ".jax_cache")
        assert calls == [("jax_compilation_cache_dir", path)]


class TestChipSmokeRefusesCPU:
    @pytest.mark.parametrize("alone", [False, True], ids=["repo", "alone"])
    def test_exits_nonzero_without_tpu(self, tmp_path, alone):
        import os
        import shutil
        import subprocess
        import sys

        root = os.path.join(os.path.dirname(__file__), "..")
        script = os.path.join(root, "chip_smoke.py")
        if alone:
            script = shutil.copy(script, tmp_path / "chip_smoke.py")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("PYTHONPATH", None)
        proc = subprocess.run(
            [sys.executable, str(script)], cwd=tmp_path, env=env,
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode != 0
        assert "no TPU found" in proc.stderr
        assert '"ok"' not in proc.stdout
