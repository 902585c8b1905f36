"""Multi-tenant replay-cache serving: fingerprint stability across clients,
cache-hit adoption skipping the recording phase, LRU eviction, cross-client
batched replay correctness, per-client state isolation, and single-client
equivalence with the pre-refactor path."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.netsim import ServerIngress, indoor_network
from repro.core.offload import OffloadableModel, OffloadSession
from repro.core.opseq import ios_fingerprint
from repro.serving.multitenant import RRTOEdgeServer
from repro.serving.replay_cache import ReplayCache


def make_mlp(seed=0, d_in=16, d_hidden=32, d_out=8):
    rng = np.random.default_rng(seed)
    params = {
        "w1": rng.normal(0, 0.1, (d_in, d_hidden)).astype(np.float32),
        "w2": rng.normal(0, 0.1, (d_hidden, d_out)).astype(np.float32),
    }

    def apply(p, x):
        return [jnp.tanh(x @ p["w1"]) @ p["w2"]]

    x = rng.normal(0, 1, (2, d_in)).astype(np.float32)
    return OffloadableModel(f"mlp{seed}", apply, params, (x,)), x


def make_deep_mlp(seed=0, d=16):
    rng = np.random.default_rng(seed)
    params = {
        "w1": rng.normal(0, 0.1, (d, d)).astype(np.float32),
        "w2": rng.normal(0, 0.1, (d, d)).astype(np.float32),
        "w3": rng.normal(0, 0.1, (d, d)).astype(np.float32),
    }

    def apply(p, x):
        h = jnp.tanh(x @ p["w1"])
        h = jax.nn.relu(h @ p["w2"])
        return [h @ p["w3"]]

    x = rng.normal(0, 1, (2, d)).astype(np.float32)
    return OffloadableModel(f"deep{seed}", apply, params, (x,)), x


def make_rnn():
    """A stateful co-tenant app: the state output feeds the next call."""
    rng = np.random.default_rng(0)
    params = {"w": rng.normal(0, 0.1, (8, 8)).astype(np.float32)}

    def apply(p, x, state):
        new_state = jnp.tanh(state @ p["w"] + x)
        return [new_state.sum(axis=1), new_state]

    x = rng.normal(0, 1, (2, 8)).astype(np.float32)
    state0 = np.zeros((2, 8), np.float32)
    return OffloadableModel("rnn", apply, params, (x, state0)), x, state0


class TestFingerprint:
    def test_stable_across_clients(self):
        """Two independent sessions (own interceptor, own allocator) running
        the same model must produce the same IOS fingerprint."""
        ios = []
        for seed in (0, 1):  # different network seeds, same model structure
            model, x = make_mlp()
            sess = OffloadSession(
                model, "rrto", min_repeats=3, seed=seed, execute=False
            )
            sess.load()
            for _ in range(5):
                sess.infer(x)
            assert sess.client.ios is not None
            ios.append(sess.client.ios)
        assert ios_fingerprint(ios[0].records) == ios_fingerprint(ios[1].records)

    def test_differs_across_models(self):
        fps = []
        for maker in (make_mlp, make_deep_mlp):
            model, x = maker()
            sess = OffloadSession(model, "rrto", min_repeats=3, execute=False)
            sess.load()
            for _ in range(5):
                sess.infer(x)
            fps.append(ios_fingerprint(sess.client.ios.records))
        assert fps[0] != fps[1]

    def test_param_values_do_not_matter(self):
        """Same architecture, different weights -> same fingerprint (the
        structure, not the data, is the content address)."""
        fps = []
        for seed in (0, 7):
            model, x = make_mlp(seed=seed)
            sess = OffloadSession(model, "rrto", min_repeats=3, execute=False)
            sess.load()
            for _ in range(5):
                sess.infer(x)
            fps.append(ios_fingerprint(sess.client.ios.records))
        assert fps[0] == fps[1]


class TestCacheAdoption:
    def test_late_client_skips_recording(self):
        """A client joining after the cache is warm adopts the IOS after a
        single recorded inference instead of min_repeats of them."""
        model, x = make_mlp()
        edge = RRTOEdgeServer(execute=True)
        first = edge.connect(model, min_repeats=3)
        for _ in range(3):
            edge.run_round({"c0": (x,)})
        assert first.client.mode == "replaying"
        assert not first.client.cache_adopted

        late = edge.connect(model, min_repeats=3)
        edge.run_round({"c0": (x,), "c1": (x,)})
        assert late.client.mode == "replaying"
        assert late.client.cache_adopted
        rec = [r for r in late.history if r.mode == "recording"]
        assert len(rec) == 1  # one recorded inference, not three

    def test_compile_exactly_once(self):
        model, x = make_mlp()
        edge = RRTOEdgeServer(execute=True)
        edge.connect(model)
        for _ in range(3):
            edge.run_round({"c0": (x,)})
        for i in range(3):
            edge.connect(model)
            edge.run_round({f"c{j}": (x,) for j in range(i + 2)})
        assert edge.compile_count == 1
        assert edge.cache.stats.hits == 3  # one bind per adopting client

    def test_batched_replay_outputs_correct(self):
        model, x = make_mlp()
        ref = np.asarray(jax.jit(model.apply)(model.params, x)[0])
        edge = RRTOEdgeServer(execute=True)
        for _ in range(3):
            edge.connect(model)
        all_ids = list(edge.sessions)
        for _ in range(4):
            results = edge.run_round({c: (x,) for c in all_ids})
        assert all(
            s.client.mode == "replaying" for s in edge.sessions.values()
        )
        for r in results.values():
            np.testing.assert_allclose(
                np.asarray(r.outputs[0]), ref, rtol=1e-5, atol=1e-5
            )
        assert edge.batcher.batches_executed >= 1
        assert max(edge.batcher.batch_sizes) == 3

    def test_vmap_batch_bitwise_equals_loop(self):
        """Shared-param co-tenants execute as one true vmap-batched call;
        the outputs must be bitwise identical to the per-client loop."""
        model, _ = make_mlp()
        rng = np.random.default_rng(5)
        per_client = {f"c{i}": rng.normal(0, 1, (2, 16)).astype(np.float32)
                      for i in range(3)}

        def run(enable_vmap):
            edge = RRTOEdgeServer(execute=True)
            edge.batcher.enable_vmap = enable_vmap
            for _ in range(3):
                edge.connect(model)
            for _ in range(5):
                results = edge.run_round(
                    {c: (x,) for c, x in per_client.items()}
                )
            return results, edge

        vmapped, edge_v = run(True)
        looped, edge_l = run(False)
        assert edge_v.batcher.vmap_batches >= 1
        assert edge_l.batcher.vmap_batches == 0
        for c in per_client:
            np.testing.assert_array_equal(
                np.asarray(vmapped[c].outputs[0]),
                np.asarray(looped[c].outputs[0]),
            )

    def test_vmap_disabled_falls_back_to_loop(self):
        model, x = make_mlp()
        edge = RRTOEdgeServer(execute=True)
        edge.batcher.enable_vmap = False
        for _ in range(3):
            edge.connect(model)
        ids = list(edge.sessions)
        for _ in range(5):
            results = edge.run_round({c: (x,) for c in ids})
        assert edge.batcher.vmap_batches == 0
        assert edge.batcher.batches_executed >= 1
        ref = np.asarray(jax.jit(model.apply)(model.params, x)[0])
        for r in results.values():
            np.testing.assert_allclose(
                np.asarray(r.outputs[0]), ref, rtol=1e-5, atol=1e-5
            )

    def test_per_client_params_isolated(self):
        """Clients with the same architecture but different weights share one
        compiled program yet keep their own parameter memory."""
        m0, x = make_mlp(seed=0)
        m1, _ = make_mlp(seed=7)
        edge = RRTOEdgeServer(execute=True)
        edge.connect(m0)
        edge.connect(m1)
        for _ in range(4):
            results = edge.run_round({"c0": (x,), "c1": (x,)})
        assert edge.compile_count == 1  # same fingerprint, one program
        for model, cid in ((m0, "c0"), (m1, "c1")):
            ref = np.asarray(jax.jit(model.apply)(model.params, x)[0])
            np.testing.assert_allclose(
                np.asarray(results[cid].outputs[0]), ref, rtol=1e-5, atol=1e-5
            )


class TestParamAliasing:
    """Co-tenants that each uploaded the same weights: the first vmap round
    proves every leaf equal on the device and re-points the co-tenants' env
    entries to the first member's buffer, so later rounds pass by identity
    with no compare."""

    @staticmethod
    def _edge(n):
        model, x = make_mlp()
        edge = RRTOEdgeServer(execute=True)
        for _ in range(n):
            edge.connect(model)
        return edge, list(edge.sessions), model, x

    @staticmethod
    def _leaves(edge, cid):
        ctx = edge.server.context(cid)
        return [ctx.env[a] for a in ctx.replay.param_addrs]

    def test_first_vmap_round_aliases_every_leaf(self):
        edge, ids, _, x = self._edge(4)
        for _ in range(8):
            edge.run_round({c: (x,) for c in ids})
            if edge.batcher.vmap_batches:
                break
        assert edge.batcher.vmap_batches == 1
        first = self._leaves(edge, ids[0])
        for c in ids[1:]:
            assert all(
                a is b for a, b in zip(self._leaves(edge, c), first)
            )

    def test_compares_stop_after_the_proof(self):
        edge, ids, _, x = self._edge(3)
        per_round = []
        for _ in range(7):
            before = (edge.batcher.vmap_batches, edge.batcher.param_compares)
            edge.run_round({c: (x,) for c in ids})
            per_round.append((edge.batcher.vmap_batches - before[0],
                              edge.batcher.param_compares - before[1]))
        leaves = len(edge.server.context(ids[0]).replay.param_addrs)
        vmap_compares = [n for batches, n in per_round if batches]
        assert len(vmap_compares) >= 3
        assert vmap_compares[0] == (3 - 1) * leaves
        assert not any(vmap_compares[1:])
        assert edge.batcher.param_compares == (3 - 1) * leaves
        assert edge.batcher.param_aliases == (3 - 1) * leaves

    @pytest.mark.parametrize("app", ["mlp", "rnn"])
    def test_aliased_rounds_bitwise_equal_loop(self, app):
        model, x0, state0 = (make_rnn() if app == "rnn"
                             else (*make_mlp(), None))
        rng = np.random.default_rng(11)
        ids = [f"c{i}" for i in range(3)]
        feeds = [{c: rng.normal(0, 1, x0.shape).astype(np.float32)
                  for c in ids} for _ in range(7)]

        def run(enable_vmap):
            edge = RRTOEdgeServer(execute=True)
            edge.batcher.enable_vmap = enable_vmap
            for _ in ids:
                edge.connect(model)
            states = {c: state0 for c in ids}
            rounds = []
            for feed in feeds:
                res = edge.run_round({
                    c: (feed[c],) if state0 is None else (feed[c], states[c])
                    for c in ids
                })
                if state0 is not None:
                    states = {c: res[c].outputs[1] for c in ids}
                rounds.append({c: [np.asarray(o) for o in res[c].outputs]
                               for c in ids})
            return rounds, edge

        vmapped, edge_v = run(True)
        looped, edge_l = run(False)
        assert edge_v.batcher.vmap_batches >= 3
        assert edge_v.batcher.param_aliases > 0
        assert edge_l.batcher.vmap_batches == 0
        for rv, rl in zip(vmapped, looped):
            for c in ids:
                for a, b in zip(rv[c], rl[c]):
                    np.testing.assert_array_equal(a, b)

    def test_rewritten_weight_is_compared_and_falls_back(self):
        edge, ids, model, x = self._edge(3)
        for _ in range(6):
            before = edge.run_round({c: (x,) for c in ids})
        assert edge.batcher.vmap_batches >= 2
        shared = self._leaves(edge, ids[0])
        ctx = edge.server.context(ids[1])
        w1_addr = next(a for a in ctx.replay.param_addrs
                       if ctx.env[a].shape == model.params["w1"].shape)
        w1_new = np.random.default_rng(3).normal(
            0, 0.1, model.params["w1"].shape).astype(np.float32)
        # a client that writes new weights replaces its env entry
        rewritten = ctx.env[w1_addr] = edge.server.to_device(w1_new)
        batches = edge.batcher.vmap_batches
        compares = edge.batcher.param_compares
        after = edge.run_round({c: (x,) for c in ids})
        assert edge.batcher.vmap_batches == batches
        assert edge.batcher.param_compares == compares + 1
        ref = np.asarray(jax.jit(model.apply)(
            dict(model.params, w1=w1_new), x)[0])
        np.testing.assert_allclose(
            np.asarray(after[ids[1]].outputs[0]), ref, rtol=1e-5, atol=1e-5
        )
        assert not np.allclose(np.asarray(before[ids[1]].outputs[0]), ref)
        for c in (ids[0], ids[2]):
            np.testing.assert_array_equal(
                np.asarray(after[c].outputs[0]),
                np.asarray(before[c].outputs[0]),
            )
            assert all(a is b for a, b in zip(self._leaves(edge, c), shared))
        # the differing leaf is never aliased over; the proven one stays
        for a, mine in zip(ctx.replay.param_addrs, shared):
            assert ctx.env[a] is (rewritten if a == w1_addr else mine)

    def test_new_first_member_passes_by_identity(self):
        edge, ids, _, x = self._edge(4)
        for _ in range(6):
            edge.run_round({c: (x,) for c in ids})
        assert edge.batcher.param_aliases == 3 * len(
            edge.server.context(ids[0]).replay.param_addrs)
        # the first member replays alone; the next group leads with another
        edge.run_round({ids[0]: (x,)})
        batches = edge.batcher.vmap_batches
        compares = edge.batcher.param_compares
        edge.run_round({c: (x,) for c in ids[1:]})
        assert edge.batcher.vmap_batches == batches + 1
        assert edge.batcher.param_compares == compares


class TestPaddedVmapWidths:
    def test_padded_widths_reuse_executables(self):
        """Batch widths pad to the next power of two: a width-3 round reuses
        the width-4 executable a width-4 round compiled (O(log N) compiles
        per fingerprint instead of one per width), with correct outputs."""
        model, x = make_mlp()
        ref = np.asarray(jax.jit(model.apply)(model.params, x)[0])
        edge = RRTOEdgeServer(execute=True)
        for _ in range(4):
            edge.connect(model)
        ids = list(edge.sessions)
        for _ in range(4):
            edge.run_round({c: (x,) for c in ids})
        assert all(
            s.client.mode == "replaying" for s in edge.sessions.values()
        )
        edge.run_round({c: (x,) for c in ids})      # width 4 -> #vmap4
        assert any("#vmap4" in k for k in edge.cache.fingerprints)
        compiles = edge.batcher.vmap_compiles
        avoided = edge.batcher.vmap_compiles_avoided
        results = edge.run_round({c: (x,) for c in ids[:3]})  # width 3 -> pads to 4
        assert edge.batcher.vmap_compiles == compiles       # no new build
        assert edge.batcher.vmap_compiles_avoided == avoided + 1
        assert edge.batcher.vmap_padded_lanes >= 1
        assert not any("#vmap3" in k for k in edge.cache.fingerprints)
        for r in results.values():
            np.testing.assert_allclose(
                np.asarray(r.outputs[0]), ref, rtol=1e-5, atol=1e-5
            )

    def test_padded_width_helper(self):
        from repro.serving.multitenant import _padded_width

        assert [_padded_width(n) for n in (1, 2, 3, 4, 5, 8, 9)] == [
            2, 2, 4, 4, 8, 8, 16,
        ]

    def test_padded_lanes_never_inflate_energy_or_occupancy(self):
        """A width-3 group executes through a padded width-4 vmap
        executable, but billing is by REAL lanes: per-client energy and the
        group's GPU occupancy are identical to the unpadded per-client loop
        of the same width."""
        model, x = make_mlp()

        def run(enable_vmap):
            edge = RRTOEdgeServer(execute=True)
            edge.batcher.enable_vmap = enable_vmap
            for _ in range(3):
                edge.connect(model)
            ids = list(edge.sessions)
            for _ in range(4):
                edge.run_round({c: (x,) for c in ids})
            assert all(
                s.client.mode == "replaying"
                for s in edge.sessions.values()
            )
            busy0 = edge.server.busy_seconds
            results = edge.run_round({c: (x,) for c in ids})
            return edge, results, edge.server.busy_seconds - busy0

        vmap_edge, vmap_res, vmap_busy = run(True)
        loop_edge, loop_res, loop_busy = run(False)
        assert vmap_edge.batcher.vmap_padded_lanes >= 1  # width 3 -> 4
        assert loop_edge.batcher.vmap_padded_lanes == 0
        # occupancy billed at the real width on both paths
        assert vmap_busy == pytest.approx(loop_busy, rel=1e-12)
        program = vmap_edge.server.context("c0").replay.program
        assert vmap_busy == pytest.approx(
            program.batched_compute_seconds(vmap_edge.server.device, 3),
            rel=1e-12,
        )
        # ...and per-client energy is identical: the masked lane exists only
        # inside the compiled executable, never in the accounting
        for cid in vmap_res:
            assert vmap_res[cid].joules == pytest.approx(
                loop_res[cid].joules, rel=1e-12
            )

    def test_aborted_vmap_batch_leaves_padding_stats_clean(self):
        """A group that bails out of the vmap path (a stateful member whose
        carried state is not seeded) falls back to the per-client loop: no
        padded lanes or avoided compiles may be recorded for the aborted
        batch — they would inflate the padding accounting for lanes that
        never executed."""

        model, x, state0 = make_rnn()
        edge = RRTOEdgeServer(execute=True)
        for _ in range(3):
            edge.connect(model)
        ids = list(edge.sessions)
        states = {c: state0 for c in ids}
        for _ in range(5):
            results = edge.run_round(
                {c: (x, states[c]) for c in ids}
            )
            for c in ids:
                states[c] = results[c].outputs[1]
        assert all(
            s.client.mode == "replaying" for s in edge.sessions.values()
        )
        padded0 = edge.batcher.vmap_padded_lanes
        avoided0 = edge.batcher.vmap_compiles_avoided
        batches0 = edge.batcher.vmap_batches
        # sabotage one member's seeded state: the vmap path must bail before
        # any padding accounting and fall back to the per-client loop
        saved = edge.server.context(ids[-1]).replay.carried_state
        edge.server.context(ids[-1]).replay.carried_state = None
        try:
            edge.batcher.begin_round(
                {
                    edge.sessions[ids[0]].client.replay_key: [
                        (
                            edge.sessions[c].client,
                            edge.sessions[c].replay_wire_inputs(
                                (x, states[c])
                            ),
                        )
                        for c in ids
                    ]
                }
            )
            group = edge.batcher._execute_group(
                edge.sessions[ids[0]].client.replay_key, edge.clock.t
            )
        finally:
            edge.server.context(ids[-1]).replay.carried_state = saved
        assert group is not None and group.outs is None  # loop fallback
        assert edge.batcher.vmap_batches == batches0
        assert edge.batcher.vmap_padded_lanes == padded0
        assert edge.batcher.vmap_compiles_avoided == avoided0


class TestDigestCache:
    def test_digest_cached_per_bound_replay(self):
        """The wire-input shape/dtype digest is computed once per binding and
        reused across rounds (the hot path under many co-tenants)."""
        model, x = make_mlp()
        edge = RRTOEdgeServer(execute=True)
        for _ in range(2):
            edge.connect(model)
        ids = list(edge.sessions)
        for _ in range(4):
            edge.run_round({c: (x,) for c in ids})
        assert all(
            s.client.mode == "replaying" for s in edge.sessions.values()
        )
        edge.run_round({c: (x,) for c in ids})       # digest computed once
        hits0 = edge.batcher.digest_cache_hits
        for _ in range(3):
            edge.run_round({c: (x,) for c in ids})
        assert edge.batcher.digest_cache_hits >= hits0 + 3

    def test_mismatched_submission_still_rejected(self):
        """The cached digest must not weaken the claim check: a submission
        whose values differ from the preload falls back to solo replay."""
        model, x = make_mlp()
        edge = RRTOEdgeServer(execute=True)
        sess = edge.connect(model)
        for _ in range(4):
            edge.run_round({"c0": (x,)})
        assert sess.client.mode == "replaying"
        cl = sess.client
        wire = sess.replay_wire_inputs((x,))
        edge.batcher.begin_round({cl.replay_key: [(cl, wire)]})
        wrong = [np.asarray(w) + 1.0 for w in wire]
        solo0 = edge.batcher.solo_replays
        outs, _ = edge.batcher.submit(cl, wrong, edge.clock.t)
        assert edge.batcher.solo_replays == solo0 + 1
        ref = np.asarray(
            jax.jit(model.apply)(model.params, np.asarray(wrong[0]))[0]
        )
        np.testing.assert_allclose(
            np.asarray(outs[0]), ref, rtol=1e-5, atol=1e-5
        )


class TestServerSegmentBatching:
    MBPS = 1e6 / 8.0

    def _locked_split_edge(self, n_clients=2, execute=True):
        """Co-tenant split sessions on one shared IOS, all replay-locked,
        with adaptive re-planning off so forced plans stay installed."""
        from repro.models.cnn_zoo import make_sensor_encoder
        from repro.partition import PartitionConfig

        model = make_sensor_encoder(scale=0.25, input_size=32, n_blocks=2)
        edge = RRTOEdgeServer(execute=execute)
        cfg = PartitionConfig(adaptive=False)
        sessions = []
        for _ in range(n_clients):
            s = edge.connect(model, min_repeats=2, partition=cfg)
            s.network.trace_bytes_per_s = np.full(16, 8.0 * self.MBPS)
            sessions.append(s)
        x = model.example_inputs
        for _ in range(6):
            edge.run_round({s.client_id: x for s in sessions})
        assert all(s.client.mode == "replaying" for s in sessions)
        return edge, sessions, model

    def test_same_server_segments_batch(self):
        """Split co-tenants whose plans share a server segment execute it as
        one batched GPU occupancy, and outputs stay exact."""
        from repro.partition import SegmentGraph, SplitPlan
        from repro.partition.segments import PLACE_DEVICE, PLACE_SERVER

        edge, sessions, model = self._locked_split_edge()
        n = SegmentGraph(sessions[0].client._ios_calls).n_ops
        plan = SplitPlan.from_placements(
            [PLACE_DEVICE] * 3 + [PLACE_SERVER] * (n - 3)
        )
        for s in sessions:
            s.client._install_plan(plan)
        x = model.example_inputs
        ref = None
        batches0 = edge.batcher.seg_batches
        results = edge.run_round({s.client_id: x for s in sessions})
        assert edge.batcher.seg_batches >= batches0 + 1
        assert edge.batcher.seg_batched >= 2
        for s in sessions:
            out = np.asarray(results[s.client_id].outputs[0])
            if ref is None:
                ref = out
            np.testing.assert_array_equal(out, ref)

    def test_different_device_cuts_still_share_server_segment(self):
        """The group key is (fingerprint, server-segment bounds), not the
        full plan: clients on *different* split plans of one shared IOS
        batch the server segment their plans have in common."""
        from repro.partition import SegmentGraph, SplitPlan
        from repro.partition.segments import PLACE_DEVICE, PLACE_SERVER

        edge, sessions, model = self._locked_split_edge()
        n = SegmentGraph(sessions[0].client._ios_calls).n_ops
        mid = max(5, n // 2)
        # plan A: device prefix, shared server segment, device tail, second
        # server segment; plan B: same prefix + shared segment, device tail
        plan_a = SplitPlan.from_placements(
            [PLACE_DEVICE] * 3
            + [PLACE_SERVER] * (mid - 3)
            + [PLACE_DEVICE] * 2
            + [PLACE_SERVER] * (n - mid - 2)
        )
        plan_b = SplitPlan.from_placements(
            [PLACE_DEVICE] * 3
            + [PLACE_SERVER] * (mid - 3)
            + [PLACE_DEVICE] * (n - mid)
        )
        assert plan_a.signature() != plan_b.signature()
        sessions[0].client._install_plan(plan_a)
        sessions[1].client._install_plan(plan_b)
        x = model.example_inputs
        batches0 = edge.batcher.seg_batches
        results = edge.run_round({s.client_id: x for s in sessions})
        # the shared (3, mid) segment batched; plan A's tail segment ran solo
        assert edge.batcher.seg_batches >= batches0 + 1
        assert edge.batcher.seg_solo >= 1
        a = np.asarray(results[sessions[0].client_id].outputs[0])
        b = np.asarray(results[sessions[1].client_id].outputs[0])
        np.testing.assert_array_equal(a, b)

    def test_full_server_clients_keep_whole_program_batching(self):
        """Split-segment batching must not siphon full-server replays out of
        the existing whole-program batch groups."""
        model, x = make_mlp()
        edge = RRTOEdgeServer(execute=True)
        for _ in range(2):
            edge.connect(model)
        ids = list(edge.sessions)
        for _ in range(4):
            edge.run_round({c: (x,) for c in ids})
        batches0 = edge.batcher.batches_executed
        edge.run_round({c: (x,) for c in ids})
        assert edge.batcher.batches_executed == batches0 + 1
        assert edge.batcher.seg_batches == 0


class TestLRUEviction:
    def test_evicts_least_recently_used(self):
        class P:  # stand-in program
            pass

        cache = ReplayCache(capacity=2)
        pa, pb, pc = P(), P(), P()
        cache.put("a", pa)
        cache.put("b", pb)
        assert cache.get("a") is pa  # touch a -> b becomes LRU
        cache.put("c", pc)
        assert "b" not in cache and "a" in cache and "c" in cache
        assert cache.stats.evictions == 1

    def test_refetch_after_eviction_recompiles(self):
        """Evicting a fingerprint forces a rebuild on the next miss."""
        model_a, xa = make_mlp()
        model_b, xb = make_deep_mlp()
        edge = RRTOEdgeServer(execute=True)
        edge.cache.capacity = 1
        edge.connect(model_a)           # c0 locks model A -> cached
        for _ in range(3):
            edge.run_round({"c0": (xa,)})
        edge.connect(model_b)           # c1 locks model B -> evicts A
        for _ in range(3):
            edge.run_round({"c0": (xa,), "c1": (xb,)})
        assert edge.compile_count == 2
        assert edge.cache.stats.evictions == 1
        # a third client on model A misses the (evicted) entry and recompiles
        edge.connect(model_a)
        for _ in range(3):
            edge.run_round({"c0": (xa,), "c1": (xb,), "c2": (xa,)})
        assert edge.sessions["c2"].client.mode == "replaying"
        assert edge.compile_count == 3


class TestCachePersistence:
    def test_save_load_roundtrip_metadata(self, tmp_path):
        model, x = make_mlp()
        edge = RRTOEdgeServer(execute=True)
        edge.connect(model)
        for _ in range(3):
            edge.run_round({"c0": (x,)})
        path = str(tmp_path / "replay_cache.json")
        assert edge.save_cache(path) == 1
        fp = edge.cache.fingerprints[0]

        fresh = ReplayCache()
        assert fresh.load(path) == 1
        assert fp in fresh                      # membership: IOS validated
        assert fresh.get(fp) is None            # but no compiled program yet
        meta = fresh.known_metadata(fp)
        assert meta["n_kernels"] > 0 and meta["total_flops"] > 0

    def test_restarted_server_skips_revalidation(self, tmp_path):
        """A client joining the restarted server adopts the persisted IOS
        after ONE recorded inference; the executable recompiles once."""
        model, x = make_mlp()
        warm = RRTOEdgeServer(execute=True)
        warm.connect(model)
        for _ in range(3):
            warm.run_round({"c0": (x,)})
        path = str(tmp_path / "cache.json")
        warm.save_cache(path)

        cold = RRTOEdgeServer(execute=True)      # simulated restart
        cold.load_cache(path)
        sess = cold.connect(model)
        cold.run_round({"c0": (x,)})
        assert sess.client.mode == "replaying"
        assert sess.client.cache_adopted
        rec = [r for r in sess.history if r.mode == "recording"]
        assert len(rec) == 1
        res = cold.run_round({"c0": (x,)})["c0"]
        ref = np.asarray(jax.jit(model.apply)(model.params, x)[0])
        np.testing.assert_allclose(
            np.asarray(res.outputs[0]), ref, rtol=1e-5, atol=1e-5
        )
        assert cold.compile_count == 1

    def test_version_mismatch_rejected(self, tmp_path):
        import json

        path = tmp_path / "bad.json"
        path.write_text(json.dumps({"version": 99, "fingerprints": {}}))
        with pytest.raises(ValueError, match="version"):
            ReplayCache().load(str(path))


class TestSingleClientEquivalence:
    def test_edge_single_client_matches_plain_session(self):
        """One client through the multi-tenant stack behaves like the plain
        single-tenant OffloadSession: same outputs, same mode trajectory,
        same per-inference RPC counts."""
        model, x = make_mlp()
        plain = OffloadSession(
            model, "rrto", network=indoor_network(0), min_repeats=3
        )
        plain.load()
        plain_hist = [plain.infer(x) for _ in range(6)]

        edge = RRTOEdgeServer(execute=True)
        sess = edge.connect(model, seed=0)
        edge_hist = [edge.run_round({"c0": (x,)})["c0"] for _ in range(6)]

        for p, e in zip(plain_hist, edge_hist):
            assert p.mode == e.mode
            assert p.rpcs == e.rpcs
            np.testing.assert_allclose(
                np.asarray(p.outputs[0]),
                np.asarray(e.outputs[0]),
                rtol=1e-6,
                atol=1e-6,
            )

    def test_ingress_contention_slows_transfers(self):
        ing = ServerIngress(capacity_bytes_per_s=10e6)
        net = indoor_network(0)
        net.ingress = ing
        ing.active_clients = 1
        t1 = net.transfer_time(1e6, 0.0)
        ing.active_clients = 10
        t10 = net.transfer_time(1e6, 0.0)
        assert t10 > t1 * 5  # fair share: 10 MB/s -> 1 MB/s per client


class TestDeviationDuringFormedRound:
    """A DAM deviation (``_fallback``) firing while the batcher already
    holds the client's preload in a formed round: the deviating client must
    exit the round cleanly (revert to recording, produce a correct result)
    and its co-tenants' batched replays must stay bitwise-identical to an
    edge that never saw the deviation."""

    CIDS = ("c0", "c1", "c2")

    def _build(self):
        edge = RRTOEdgeServer(execute=True)
        model, x = make_mlp()
        for cid in self.CIDS:
            edge.connect(model, client_id=cid, min_repeats=2)
        for _ in range(4):
            edge.run_round({cid: (x,) for cid in self.CIDS})
        for cid in self.CIDS:
            assert edge.sessions[cid].client.mode == "replaying"
        keys = {edge.sessions[cid].client.replay_key for cid in self.CIDS}
        assert len(keys) == 1       # one shared batched-replay group
        return edge, x

    def test_deviant_exits_round_cleanly_cotenants_bitwise(self):
        from repro.core.flatten import flatten_closed_jaxpr

        edge, x = self._build()
        control, x_ctl = self._build()
        want = control.run_round({cid: (x_ctl,) for cid in self.CIDS})

        # form the round exactly as run_round does: all three replaying
        # clients preloaded under their shared fingerprint
        entries = {}
        for cid in self.CIDS:
            sess = edge.sessions[cid]
            entries.setdefault(sess.client.replay_key, []).append(
                (sess.client, sess.replay_wire_inputs((x,)))
            )
        edge.batcher.begin_round(entries, {})

        # co-tenants claim their batch lanes first
        res = {cid: edge.sessions[cid].infer(x) for cid in ("c0", "c1")}

        # ... then c2 — still preloaded in the formed round — runs a
        # different op stream through its own interceptor: relu where the
        # locked IOS recorded tanh@w2.  The DAM must fall back mid-round.
        sess2 = edge.sessions["c2"]
        rng = np.random.default_rng(0)
        w1 = rng.normal(0, 0.1, (16, 32)).astype(np.float32)
        jb = flatten_closed_jaxpr(
            jax.make_jaxpr(lambda xx: [jax.nn.relu(xx @ w1)])(x)
        )
        addrs_b = sess2.interceptor.upload_params(
            [np.asarray(c) for c in jb.consts]
        )
        out2 = sess2.interceptor.run(jb, addrs_b, [x])
        edge.batcher.end_round()

        deviant = sess2.client
        assert deviant.fallbacks >= 1
        assert deviant.mode == "recording"
        assert np.asarray(out2[0]).shape == (2, 32)    # the relu stream ran

        # co-tenants' batched replays: bitwise-equal to the clean twin
        for cid in ("c0", "c1"):
            assert np.array_equal(
                np.asarray(res[cid].outputs[0]),
                np.asarray(want[cid].outputs[0]),
            )
        # exactly one unclaimed lane remains — the deviant's preloaded
        # batch slot, abandoned when the DAM fell back; the next round's
        # formation sweeps it, so the no-show never leaks across rounds
        assert edge.batcher.pending_depth == 1

        # the edge still serves the deviant: it re-records through normal
        # rounds and re-locks into batched replay alongside its co-tenants
        for _ in range(4):
            edge.run_round({cid: (x,) for cid in self.CIDS})
        assert edge.batcher.pending_depth == 0
        assert edge.sessions["c2"].client.mode == "replaying"
        final = edge.run_round({cid: (x,) for cid in self.CIDS})
        ctl_final = control.run_round({cid: (x_ctl,) for cid in self.CIDS})
        for cid in self.CIDS:
            assert np.array_equal(
                np.asarray(final[cid].outputs[0]),
                np.asarray(ctl_final[cid].outputs[0]),
            )
