"""Stateful split replay — carried-pinned partitioning of KV-cached IOSes.

The acceptance property: for ANY carried-feasible plan, segmented
device/server execution with the donated stateful server suffix is bitwise
identical to the stateful full-server replay, step for step, across registry
models including the KV-cached decode workload.  Plus: feasibility edge
cases (no feasible device prefix -> full-server endpoint, not an exception),
persistence round-trips rebuilding both carried_pairs and the plan
signature, plan-swap state continuity, the split-aware DAM fallback state
download, pipelined stateful streaming, and co-tenant segment batching with
per-client state isolation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core.engine import BoundSegmentedReplay, SegmentedReplayProgram
from repro.core.offload import OffloadableModel, OffloadSession
from repro.models.cnn_zoo import make_recurrent_sensor_decoder
from repro.partition import (
    PLACE_DEVICE,
    PLACE_SERVER,
    PartitionConfig,
    SegmentGraph,
    SplitPlan,
    plan_partition,
)
from repro.serving.engine import RRTOServedLM

MBPS = 1e6 / 8.0

DECODE_CFG = ArchConfig(
    name="t", family="dense", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
    d_head=16, d_ff=128, vocab=256, dtype="float32", rope_theta=1e4,
)


def make_rnn(seed=0, d=8, batch=2):
    """An RNN with a stateless input encoder (the prologue a split can keep
    on the device) ahead of the carried-state cell."""
    rng = np.random.default_rng(seed)
    params = {
        "w_in": rng.normal(0, 0.1, (d, d)).astype(np.float32),
        "w": rng.normal(0, 0.1, (d, d)).astype(np.float32),
    }

    def apply(p, x, state):
        z = jnp.tanh(x @ p["w_in"])             # stateless prologue
        new_state = jnp.tanh(state @ p["w"] + z)
        return [new_state.sum(axis=1), new_state]

    x = rng.normal(0, 1, (batch, d)).astype(np.float32)
    state0 = np.zeros((batch, d), np.float32)
    return OffloadableModel(f"rnn{seed}", apply, params, (x, state0)), x, state0


def lock_stateful_session(model, inputs, state_in=1, state_out=1, steps=5,
                          min_repeats=3, **session_kwargs):
    """Drive a stateful app to replay lock, threading the carried state
    (input position ``state_in`` <- output position ``state_out``)."""
    sess = OffloadSession(model, "rrto", min_repeats=min_repeats,
                          **session_kwargs)
    sess.load()
    args = list(inputs)
    for _ in range(steps):
        res = sess.infer(*args)
        args[state_in] = res.outputs[state_out]
    assert sess.client.mode == "replaying", "IOS never locked"
    assert sess.client.stateful_replay, "carried state not detected"
    return sess


def lock_decode_session(new_tokens=8):
    """The KV-cached decode workload: an offloaded LLM decode_step whose
    cache pytree is loop-carried."""
    prompt = np.random.default_rng(0).integers(0, 256, (1, 4)).astype(np.int32)
    served = RRTOServedLM(DECODE_CFG, bucket_len=16, batch=1, seed=3,
                          min_repeats=3)
    served.generate(prompt, new_tokens)
    sess = served.session
    assert sess.client.mode == "replaying"
    assert sess.client.stateful_replay
    return sess


def feasible_plans(graph, max_plans=4):
    """A spread of carried-feasible device-prefix/server-suffix plans."""
    limit = graph.carried_cut_limit()
    n = graph.n_ops
    bmax = min(limit, n - 1)
    if bmax < 1:
        return []
    bounds = sorted({1, max(1, bmax // 2), bmax})[:max_plans]
    return [
        SplitPlan.from_placements(
            [PLACE_DEVICE] * b + [PLACE_SERVER] * (n - b)
        )
        for b in bounds
    ]


def snapshot_state(sess):
    ctx = sess.server.context(sess.client_id)
    src = ctx.split if ctx.split is not None else ctx.replay
    return [np.array(np.asarray(s), copy=True) for s in src.carried_state]


class TestStatefulSplitEquivalence:
    """Acceptance property: stateful split replay is bitwise identical to
    stateful full-server replay, step for step, across >= 2 registry models
    including the KV-cached decode workload."""

    def _assert_bitwise(self, sess, steps=4):
        client = sess.client
        calls = client._ios_calls
        pairs = client.ios.carried_pairs
        ctx = sess.server.context(sess.client_id)
        env = ctx.env
        ref_bound = ctx.replay
        program = ref_bound.program
        params_flat = [env[a] for a in ref_bound.param_addrs]
        state0 = [
            np.array(np.asarray(s), copy=True)
            for s in ref_bound.carried_state
        ]
        wire = sess.replay_wire_inputs(sess.model.example_inputs)

        graph = SegmentGraph(calls, carried_pairs=pairs)
        plans = feasible_plans(graph)
        assert plans, "no feasible device prefix in this workload"
        for plan in plans:
            prog = SegmentedReplayProgram(calls, plan, carried_pairs=pairs)
            bound = BoundSegmentedReplay.from_own(prog)
            bound.carried_state = [jnp.asarray(s) for s in state0]
            ref_state = [jnp.asarray(s) for s in state0]
            split_env = dict(env)
            for step in range(steps):
                ref_outs, ref_state = program.step_fn(
                    params_flat, [np.asarray(w) for w in wire], ref_state
                )
                ref_state = list(ref_state)
                outs = bound.execute(wire, split_env)
                assert len(outs) == len(ref_outs)
                for got, want in zip(outs, ref_outs):
                    assert np.array_equal(
                        np.asarray(got), np.asarray(want)
                    ), f"plan {plan.signature()} diverged at step {step}"
                for got, want in zip(bound.carried_state, ref_state):
                    assert np.array_equal(
                        np.asarray(got), np.asarray(want)
                    ), f"plan {plan.signature()} state diverged at {step}"

    def test_rnn_bitwise(self):
        model, x, state0 = make_rnn()
        sess = lock_stateful_session(model, (x, state0))
        self._assert_bitwise(sess)

    def test_recurrent_sensor_decoder_bitwise(self):
        model = make_recurrent_sensor_decoder(
            scale=0.25, input_size=32, n_blocks=2, d_state=32
        )
        sess = lock_stateful_session(
            model, model.example_inputs, min_repeats=2
        )
        self._assert_bitwise(sess)

    def test_kv_cached_decode_bitwise(self):
        """The decode workload: every KV-cache leaf is loop-carried; the
        split suffix advances the whole cache pytree in place."""
        sess = lock_decode_session()
        assert len(sess.client.ios.carried_pairs) >= 2  # a cache pytree
        self._assert_bitwise(sess, steps=3)

    def test_rebinding_across_clients(self):
        """A stateful segmented program compiled from one client's calls
        executes correctly bound to a second client's address space, with
        the second client's own carried state."""
        model, x, state0 = make_rnn()
        sess_a = lock_stateful_session(model, (x, state0))
        sess_b = lock_stateful_session(model, (x, state0), seed=5)
        pairs = sess_a.client.ios.carried_pairs
        graph = SegmentGraph(sess_a.client._ios_calls, carried_pairs=pairs)
        plan = feasible_plans(graph)[-1]
        prog = SegmentedReplayProgram(
            sess_a.client._ios_calls, plan, carried_pairs=pairs
        )
        bound = BoundSegmentedReplay.bind(prog, sess_b.client._ios_calls)
        env_b = sess_b.server.context(sess_b.client_id).env
        bound.seed_carried(env_b)
        assert bound.carried_state is not None
        ref_bound = sess_b.server.context(sess_b.client_id).replay
        state0_b = [
            np.array(np.asarray(s), copy=True)
            for s in ref_bound.carried_state
        ]
        bound.carried_state = [jnp.asarray(s) for s in state0_b]
        wire = sess_b.replay_wire_inputs(model.example_inputs)
        params_flat = [env_b[a] for a in ref_bound.param_addrs]
        ref_outs, _ = ref_bound.program.step_fn(
            params_flat, [np.asarray(w) for w in wire],
            [jnp.asarray(s) for s in state0_b],
        )
        outs = bound.execute(wire, env_b)
        for got, want in zip(outs, ref_outs):
            assert np.array_equal(np.asarray(got), np.asarray(want))


class TestCarriedFeasibility:
    def test_first_op_carried_returns_full_server(self):
        """An IOS whose FIRST op consumes carried state has no feasible
        device prefix: the planner must return the full-server endpoint,
        not raise."""
        rng = np.random.default_rng(0)
        params = {"w": rng.normal(0, 0.1, (8, 8)).astype(np.float32)}

        def apply(p, state, x):
            z = state @ p["w"]          # op 0 consumes the carried state
            new_state = jnp.tanh(z + x)
            return [new_state.sum(axis=1), new_state]

        x = rng.normal(0, 1, (2, 8)).astype(np.float32)
        state0 = np.zeros((2, 8), np.float32)
        model = OffloadableModel("first_carried", apply, params, (state0, x))
        sess = lock_stateful_session(
            model, (state0, x), state_in=0, state_out=1,
            partition=PartitionConfig(),
        )
        client = sess.client
        graph = client.replanner.graph
        assert graph.carried_cut_limit() == 0
        ev = plan_partition(
            graph, sess.client_device, sess.server_device, 16 * MBPS
        )
        assert ev.plan.is_full_server
        # the live session holds the full-server endpoint, still correct
        assert client.split_plan is None
        f = jax.jit(model.apply)
        state_ref = jnp.asarray(state0)
        for _ in range(len(sess.history)):
            y_ref, state_ref = f(model.params, state_ref, x)
        state_arg = sess.history[-1].outputs[1]
        for _ in range(2):
            res = sess.infer(state_arg, x)
            state_arg = res.outputs[1]
            y_ref, state_ref = f(model.params, state_ref, x)
            np.testing.assert_allclose(
                np.asarray(res.outputs[0]), np.asarray(y_ref),
                rtol=1e-6, atol=1e-6,
            )

    def test_infeasible_plan_rejected_at_compile(self):
        model, x, state0 = make_rnn()
        sess = lock_stateful_session(model, (x, state0))
        pairs = sess.client.ios.carried_pairs
        calls = sess.client._ios_calls
        graph = SegmentGraph(calls, carried_pairs=pairs)
        n = graph.n_ops
        # device suffix strands the carried region on the device side
        bad = SplitPlan.from_placements(
            [PLACE_SERVER] * (n - 1) + [PLACE_DEVICE]
        )
        assert not graph.plan_carried_feasible(bad)
        with pytest.raises(ValueError, match="carried-feasible"):
            SegmentedReplayProgram(calls, bad, carried_pairs=pairs)

    def test_planner_only_feasible_plans_across_bandwidths(self):
        model = make_recurrent_sensor_decoder(
            scale=0.25, input_size=32, n_blocks=2, d_state=32
        )
        sess = lock_stateful_session(
            model, model.example_inputs, min_repeats=2
        )
        pairs = sess.client.ios.carried_pairs
        graph = SegmentGraph(sess.client._ios_calls, carried_pairs=pairs)
        for mbps in (0.5, 8.0, 64.0, 512.0):
            ev = plan_partition(
                graph, sess.client_device, sess.server_device, mbps * MBPS
            )
            assert graph.plan_carried_feasible(ev.plan)
            assert not ev.plan.is_full_device


class TestStatefulSplitSession:
    """End-to-end: a stateful session on an installed split plan keeps the
    state server-resident and its outputs bitwise-track the plain stateful
    session."""

    def _locked_pair(self):
        model = make_recurrent_sensor_decoder(
            scale=0.25, input_size=32, n_blocks=2, d_state=32
        )
        plain = lock_stateful_session(
            model, model.example_inputs, min_repeats=2, seed=0
        )
        split = lock_stateful_session(
            model, model.example_inputs, min_repeats=2, seed=0,
            partition=PartitionConfig(adaptive=False),
        )
        pairs = split.client.ios.carried_pairs
        graph = SegmentGraph(split.client._ios_calls, carried_pairs=pairs)
        plan = feasible_plans(graph)[-1]
        split.client._install_plan(plan)
        return model, plain, split, plan

    def test_outputs_match_plain_stateful(self):
        model, plain, split, plan = self._locked_pair()
        assert split.client.split_plan is not None
        frame = np.asarray(model.example_inputs[0])
        h_plain = plain.history[-1].outputs[1]
        h_split = split.history[-1].outputs[1]
        for _ in range(4):
            want = plain.infer(frame, h_plain)
            got = split.infer(frame, h_split)
            h_plain = want.outputs[1]
            h_split = got.outputs[1]
            assert np.array_equal(
                np.asarray(got.outputs[0]), np.asarray(want.outputs[0])
            )

    def test_state_never_crosses_on_split(self):
        """Steady split replay bills only the boundary tensors + wire
        output: neither the carried state nor the raw frame (held back by
        the device prefix) contributes wire bytes."""
        model, plain, split, plan = self._locked_pair()
        h = split.history[-1].outputs[1]
        frame = np.asarray(model.example_inputs[0])
        res1 = split.infer(frame, h)
        res2 = split.infer(frame, res1.outputs[1])
        # steady state: identical wire volume round over round, smaller
        # than the raw frame alone (let alone frame + state)
        assert res2.network_bytes == res1.network_bytes
        assert res2.network_bytes < frame.nbytes
        full = plain.infer(frame, plain.history[-1].outputs[1])
        # plain stateful full-server ships the whole frame; the split ships
        # the (much smaller) stem boundary — and neither ships the state
        assert res2.network_bytes < full.network_bytes

    def test_plan_swap_preserves_state(self):
        """Swapping split -> full-server -> split mid-session migrates the
        live carried state between the bindings: outputs keep tracking the
        single-plan reference."""
        model, plain, split, plan = self._locked_pair()
        frame = np.asarray(model.example_inputs[0])
        h_plain = plain.history[-1].outputs[1]
        h_split = split.history[-1].outputs[1]
        n = SegmentGraph(split.client._ios_calls).n_ops
        for swap_to in (SplitPlan.full_server(n), plan,
                        SplitPlan.full_server(n)):
            want = plain.infer(frame, h_plain)
            got = split.infer(frame, h_split)
            h_plain, h_split = want.outputs[1], got.outputs[1]
            assert np.array_equal(
                np.asarray(got.outputs[0]), np.asarray(want.outputs[0])
            )
            split.client._install_plan(swap_to)
        # one more round on the final plan
        want = plain.infer(frame, h_plain)
        got = split.infer(frame, h_split)
        assert np.array_equal(
            np.asarray(got.outputs[0]), np.asarray(want.outputs[0])
        )

    def test_fresh_state_reships_once_on_split(self):
        """Supplying genuinely new state mid-split-session overrides the
        server-resident suffix state (one extra RPC), like full-server."""
        model, plain, split, plan = self._locked_pair()
        frame = np.asarray(model.example_inputs[0])
        h = split.history[-1].outputs[1]
        steady = split.infer(frame, h)
        fresh = np.full_like(np.asarray(model.example_inputs[1]), 0.125)
        res = split.infer(frame, fresh)
        assert res.rpcs == steady.rpcs + 1
        f = jax.jit(model.apply)
        want_y, _ = f(model.params, frame, jnp.asarray(fresh))
        np.testing.assert_allclose(
            np.asarray(res.outputs[0]), np.asarray(want_y),
            rtol=1e-5, atol=1e-6,
        )


class TestStatefulSplitFallback:
    def test_materializer_reads_split_suffix_state(self):
        """After split steps, the live state lives in the split binding —
        the DAM materializer must download THAT, not the whole-program
        binding's stale lock-time snapshot."""
        model = make_recurrent_sensor_decoder(
            scale=0.25, input_size=32, n_blocks=2, d_state=32
        )
        sess = lock_stateful_session(
            model, model.example_inputs, min_repeats=2,
            partition=PartitionConfig(adaptive=False, pipelined=True),
        )
        client = sess.client
        pairs = client.ios.carried_pairs
        graph = SegmentGraph(client._ios_calls, carried_pairs=pairs)
        client._install_plan(feasible_plans(graph)[-1])
        assert client.pipelined_exec is not None
        frame = np.asarray(model.example_inputs[0])
        h = sess.history[-1].outputs[1]
        for _ in range(3):
            res = sess.infer(frame, h)
            h = res.outputs[1]
        ctx = sess.server.context(client.client_id)
        live = np.asarray(ctx.split.carried_state[0])
        stale = np.asarray(ctx.replay.carried_state[0])
        assert not np.array_equal(live, stale)  # split advanced past lock

        ph = client._carried_placeholders[0]
        h2d_calls = [
            c for c in client._ios_calls
            if c.record.func == "cudaMemcpyHtoD"
        ]
        carried_ordinal = next(iter(client._carried_in_map))
        client._replay_prefix = list(h2d_calls)
        client._replay_prefix[carried_ordinal].h2d_value = ph
        rpcs0 = client.stats.rpcs
        client._materialize_carried_prefix()
        assert client.stats.rpcs == rpcs0 + 1
        np.testing.assert_array_equal(ph, live)

    def test_dam_fallback_refreshes_handle_and_recovers(self):
        """End-to-end deviation on a pipelined stateful split session: the
        app-held handle is refreshed with the live state BEFORE the stream
        executor drops, and the post-fallback computation continues from the
        true state."""
        from repro.core.costmodel import GTX_2080TI
        from repro.core.energy import EnergyMeter
        from repro.core.engine import OffloadServer, RRTOClient, SimClock
        from repro.core.flatten import flatten_closed_jaxpr
        from repro.core.intercept import NO_NOISE, JaxprInterceptor
        from repro.core.netsim import indoor_network

        rng = np.random.default_rng(0)
        w = rng.normal(0, 0.1, (8, 8)).astype(np.float32)
        x = rng.normal(0, 1, (2, 8)).astype(np.float32)

        def graph_a(w, xx, state):
            z = jnp.tanh(xx @ w)
            new = jnp.tanh(z + state @ w)
            return [new.sum(axis=1), new]

        def graph_b(w, xx, state):
            z = jax.nn.relu(xx @ w)
            new = jnp.tanh(z + state)
            return [new.sum(axis=1), new]

        state0 = np.zeros((2, 8), np.float32)
        ja = flatten_closed_jaxpr(
            jax.make_jaxpr(lambda xx, st: graph_a(w, xx, st))(x, state0)
        )
        jb = flatten_closed_jaxpr(
            jax.make_jaxpr(lambda xx, st: graph_b(w, xx, st))(x, state0)
        )
        client = RRTOClient(
            OffloadServer(GTX_2080TI, execute=True),
            indoor_network(), SimClock(), EnergyMeter(),
            variant="rrto", min_repeats=2,
            partition=PartitionConfig(adaptive=False, pipelined=True),
        )
        icp = JaxprInterceptor(client, NO_NOISE)
        addrs_a = icp.upload_params([np.asarray(c) for c in ja.consts])
        addrs_b = icp.upload_params([np.asarray(c) for c in jb.consts])
        state = state0
        for _ in range(5):
            outs = icp.run(ja, addrs_a, [x, state])
            state = outs[1]
        assert client.mode == "replaying" and client.stateful_replay
        pairs = client.ios.carried_pairs
        graph = SegmentGraph(client._ios_calls, carried_pairs=pairs)
        plans = feasible_plans(graph)
        if plans:
            client._install_plan(plans[-1])
        # a few split/stateful replay rounds advance the server state
        for _ in range(3):
            outs = icp.run(ja, addrs_a, [x, state])
            state = outs[1]
        # the reference trajectory the server should be holding
        fa = jax.jit(lambda xx, st: graph_a(w, xx, st))
        ref_state = jnp.asarray(state0)
        for _ in range(8):
            _, ref_state = fa(x, ref_state)
        # deviate: graph B starts with the same H2D uploads, so the carried
        # upload sits in the replayed prefix when the first kernel diverges
        outs_b = icp.run(jb, addrs_b, [x, state])
        assert client.fallbacks >= 1 and client.mode == "recording"
        assert client.pipelined_exec is None
        # the app's handle was refreshed in place with the live state
        # (fused-jit reference vs per-op replay: float32 drift over the
        # 8-step trajectory, hence the loose tolerance)
        np.testing.assert_allclose(
            np.asarray(state), np.asarray(ref_state), rtol=1e-3, atol=1e-4
        )
        fb = jax.jit(lambda xx, st: graph_b(w, xx, st))
        want_b, _ = fb(x, ref_state)
        np.testing.assert_allclose(
            np.asarray(outs_b[0]), np.asarray(want_b), rtol=1e-3, atol=1e-4
        )


class TestStatefulPipelinedStream:
    def test_stream_bitwise_equals_sequential_split(self):
        """infer_stream over a stateful split plan advances the suffix state
        per submission, in order — outputs bitwise equal the sequential
        split session's trajectory."""
        model = make_recurrent_sensor_decoder(
            scale=0.25, input_size=32, n_blocks=2, d_state=32
        )
        seq = lock_stateful_session(
            model, model.example_inputs, min_repeats=2, seed=0,
            partition=PartitionConfig(adaptive=False),
        )
        piped = lock_stateful_session(
            model, model.example_inputs, min_repeats=2, seed=0,
            partition=PartitionConfig(adaptive=False, pipelined=True),
        )
        pairs = piped.client.ios.carried_pairs
        graph = SegmentGraph(piped.client._ios_calls, carried_pairs=pairs)
        plan = feasible_plans(graph)[-1]
        seq.client._install_plan(plan)
        piped.client._install_plan(plan)
        assert piped.client.pipelined_exec is not None

        rng = np.random.default_rng(3)
        frames = [
            np.asarray(model.example_inputs[0])
            + rng.normal(0, 0.01, np.shape(model.example_inputs[0])).astype(
                np.float32
            )
            for _ in range(4)
        ]
        h_seq = seq.history[-1].outputs[1]
        # the app threads the stable handle through the stream, exactly as
        # it would through sequential infer() calls
        h_piped = piped.history[-1].outputs[1]
        results = piped.infer_stream([(f, h_piped) for f in frames])
        assert len(results) == len(frames)
        assert all(
            a.done_at <= b.done_at for a, b in zip(results, results[1:])
        )
        for r, f in zip(results, frames):
            want = seq.infer(f, h_seq)
            h_seq = want.outputs[1]
            # same arity and meaning as sequential infer(): [y, state handle]
            assert len(r.outputs) == len(want.outputs)
            assert r.outputs[1] is h_piped
            assert np.array_equal(
                np.asarray(r.outputs[0]), np.asarray(want.outputs[0])
            )

    def test_stream_fresh_state_override(self):
        """A non-handle state value in a stream arrival overwrites the
        server-resident suffix state (one extra billed RPC), matching the
        sequential fresh-state semantics — it must not be silently dropped."""
        model = make_recurrent_sensor_decoder(
            scale=0.25, input_size=32, n_blocks=2, d_state=32
        )
        sess = lock_stateful_session(
            model, model.example_inputs, min_repeats=2, seed=0,
            partition=PartitionConfig(adaptive=False, pipelined=True),
        )
        pairs = sess.client.ios.carried_pairs
        graph = SegmentGraph(sess.client._ios_calls, carried_pairs=pairs)
        sess.client._install_plan(feasible_plans(graph)[-1])
        frame = np.asarray(model.example_inputs[0])
        fresh = np.full_like(np.asarray(model.example_inputs[1]), 0.25)
        rpcs0 = sess.client.stats.rpcs
        results = sess.infer_stream([(frame, fresh)])
        assert sess.client.stats.rpcs > rpcs0  # override + boundary traffic
        # a fresh upload mints a new handle (like the sequential path); the
        # app threads it into the next stream window
        new_handle = results[0].outputs[1]
        results2 = sess.infer_stream([(frame, new_handle)])
        f = jax.jit(model.apply)
        y1, h1 = f(model.params, frame, jnp.asarray(fresh))
        y2, _ = f(model.params, frame, h1)
        np.testing.assert_allclose(
            np.asarray(results[0].outputs[0]), np.asarray(y1),
            rtol=1e-5, atol=1e-6,
        )
        np.testing.assert_allclose(
            np.asarray(results2[0].outputs[0]), np.asarray(y2),
            rtol=1e-5, atol=1e-6,
        )


class TestStreamExecutorClaims:
    def test_installed_stream_executor_pins_its_base(self):
        """While a pipelined stream executor is installed, its derived
        fp|plan key holds a cache claim pinning the base program; reverting
        to full-server (or a DAM fallback) releases it."""
        from repro.serving.multitenant import RRTOEdgeServer

        model = make_recurrent_sensor_decoder(
            scale=0.25, input_size=32, n_blocks=2, d_state=32
        )
        edge = RRTOEdgeServer(execute=True)
        sess = edge.connect(
            model, min_repeats=2,
            partition=PartitionConfig(adaptive=False, pipelined=True),
        )
        state = np.asarray(model.example_inputs[1])
        frame = np.asarray(model.example_inputs[0])
        for _ in range(4):
            res = edge.run_round({"c0": (frame, state)})["c0"]
            state = res.outputs[1]
        client = sess.client
        assert client.mode == "replaying"
        edge.batcher.begin_round({})  # expire the last round's claims
        pairs = client.ios.carried_pairs
        graph = SegmentGraph(client._ios_calls, carried_pairs=pairs)
        plan = feasible_plans(graph)[-1]
        client._install_plan(plan)
        assert client.pipelined_exec is not None
        fp = client.ios_fp
        assert client._stream_claim == f"{fp}|{plan.signature()}"
        assert edge.cache.is_pinned(fp)
        n = graph.n_ops
        client._install_plan(SplitPlan.full_server(n))
        assert client.pipelined_exec is None
        assert client._stream_claim is None
        assert not edge.cache.is_pinned(fp)


class TestStatefulSplitPersistence:
    def test_split_plan_roundtrip_rebuilds_state_and_signature(self, tmp_path):
        """ReplayCache.save/load of a stateful split entry: the fp|plan key
        persists both the plan signature and the carried pairs, and a
        restarted server's prepare_split rebuilds a *stateful* segmented
        program from metadata alone."""
        from repro.serving.replay_cache import ReplayCache

        model, x, state0 = make_rnn()
        sess = lock_stateful_session(model, (x, state0))
        client = sess.client
        pairs = client.ios.carried_pairs
        calls = client._ios_calls
        graph = SegmentGraph(calls, carried_pairs=pairs)
        plan = feasible_plans(graph)[-1]

        server = sess.server
        server.replay_cache = cache = ReplayCache(capacity=8)
        fp = "f" * 8
        server.prepare_split(
            calls, plan, "c0", fp, carried_pairs=pairs
        )
        key = f"{fp}|{plan.signature()}"
        assert key in cache
        path = str(tmp_path / "cache.json")
        cache.save(path)

        fresh = ReplayCache()
        fresh.load(path)
        meta = fresh.known_metadata(key)
        assert meta is not None
        assert meta["plan"] == plan.signature()
        assert meta["carried_pairs"] == [[int(i), int(j)] for i, j in pairs]

        # a restarted server rebuilds the executable stateful from metadata
        # (the adopting client recorded one round: it passes no pairs)
        from repro.core.costmodel import GTX_2080TI
        from repro.core.engine import OffloadServer

        cold = OffloadServer(GTX_2080TI, execute=True, replay_cache=fresh)
        cold.context("c0").env.update(
            sess.server.context(sess.client_id).env
        )
        cold.prepare_split(calls, plan, "c0", fp, carried_pairs=())
        bound = cold.context("c0").split
        assert bound.program.is_stateful
        assert bound.program.carried_pairs == pairs
        assert bound.program.plan.signature() == plan.signature()
        assert bound.carried_state is not None  # seeded from the env
        server.replay_cache = None


class TestStatefulSegmentBatching:
    def test_cotenant_stateful_split_batches_and_isolates_state(self):
        """Two stateful split co-tenants on one shared IOS batch their
        server suffix on the GPU (seg_batches grows) while their per-client
        carried states evolve independently and correctly."""
        from repro.serving.multitenant import RRTOEdgeServer

        model = make_recurrent_sensor_decoder(
            scale=0.25, input_size=32, n_blocks=2, d_state=32
        )
        edge = RRTOEdgeServer(execute=True)
        cfg = PartitionConfig(adaptive=False)
        sessions = [
            edge.connect(model, min_repeats=2, partition=cfg)
            for _ in range(2)
        ]
        rng = np.random.default_rng(9)
        frames = {
            s.client_id: np.asarray(model.example_inputs[0])
            + rng.normal(0, 0.02, np.shape(model.example_inputs[0])).astype(
                np.float32
            )
            for s in sessions
        }
        h0 = np.asarray(model.example_inputs[1])
        states = {s.client_id: h0 for s in sessions}
        for _ in range(5):
            results = edge.run_round(
                {c: (frames[c], states[c]) for c in states}
            )
            for c in states:
                states[c] = results[c].outputs[1]
        assert all(s.client.mode == "replaying" for s in sessions)
        assert all(s.client.stateful_replay for s in sessions)
        pairs = sessions[0].client.ios.carried_pairs
        graph = SegmentGraph(
            sessions[0].client._ios_calls, carried_pairs=pairs
        )
        plan = feasible_plans(graph)[-1]
        for s in sessions:
            s.client._install_plan(plan)
        batches0 = edge.batcher.seg_batches
        for _ in range(3):
            results = edge.run_round(
                {c: (frames[c], states[c]) for c in states}
            )
            for c in states:
                states[c] = results[c].outputs[1]
        assert edge.batcher.seg_batches >= batches0 + 1
        # per-client trajectories match the local reference
        f = jax.jit(model.apply)
        for s in sessions:
            cid = s.client_id
            state = jnp.asarray(h0)
            for _ in range(8):
                y, state = f(model.params, frames[cid], state)
            np.testing.assert_allclose(
                np.asarray(results[cid].outputs[0]), np.asarray(y),
                rtol=1e-5, atol=1e-5,
            )
