"""Unified tracing + metrics layer (repro.obs).

Load-bearing properties, in order:

* a *disabled* tracer is provably free — the same fleet workload with and
  without tracing produces bitwise-identical outputs and identical legacy
  counters, and an unattached tracer records zero events;
* spans nest (begin/end parent links) and per-track timestamps are monotone
  on the shared :class:`~repro.core.engine.SimClock`;
* hedged dispatch emits a primary *and* a backup ``hedge_dispatch`` span
  and the race loser is annotated ``cancelled=True`` after resolution;
* the Chrome trace-event export is schema-valid and carries the
  record/replay/hedge/migration spans across >= 2 replica tracks;
* one root ``MetricsRegistry.snapshot()`` agrees with every legacy stats
  surface (client RPCs, cache hits, hedge counts, migrations).
"""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.offload import OffloadableModel, OffloadSession
from repro.obs import (
    MetricsRegistry,
    RegistryBackedStats,
    Tracer,
    host_span,
    percentile,
    to_chrome_trace,
    write_chrome_trace,
)
from repro.partition.planner import plan_cost, plan_partition
from repro.partition.segments import SegmentGraph
from repro.serving import EdgeFleet
from repro.serving.multitenant import RRTOEdgeServer

MBPS = 1e6 / 8.0


def make_mlp(seed=0, d_in=16, d_hidden=32, d_out=8):
    rng = np.random.default_rng(seed)
    params = {
        "w1": jnp.asarray(rng.normal(size=(d_in, d_hidden)), jnp.float32),
        "w2": jnp.asarray(rng.normal(size=(d_hidden, d_out)), jnp.float32),
    }

    def apply(p, x):
        return [jnp.tanh(x @ p["w1"]) @ p["w2"]]

    x = jnp.asarray(rng.normal(size=(1, d_in)), jnp.float32)
    return OffloadableModel(f"mlp{seed}", apply, params, (x,)), np.asarray(x)


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------
class TestMetrics:
    def test_counter_gauge_histogram(self):
        reg = MetricsRegistry()
        reg.counter("n").value += 3
        assert reg.counter("n").value == 3
        reg.gauge("depth").set(2.5)
        h = reg.histogram("lat")
        for v in (1.0, 2.0, 3.0, 4.0):
            h.observe(v)
        assert h.count == 4 and h.mean == pytest.approx(2.5)
        assert h.p50 <= h.p95 <= h.p99 <= 4.0
        s = h.summary()
        assert set(s) == {"count", "mean", "p50", "p95", "p99"}

    def test_percentile_nearest_rank(self):
        xs = list(range(1, 101))
        assert percentile(xs, 0) == 1
        assert percentile(xs, 100) == 100
        assert percentile(xs, 99) == 99
        assert percentile([], 50) == 0.0

    def test_scope_shares_one_store(self):
        root = MetricsRegistry()
        root.scope("r0").scope("cache").counter("hits").value += 2
        root.scope("r1").scope("cache").counter("hits").value += 5
        snap = root.snapshot()
        assert snap["r0.cache.hits"] == 2
        assert snap["r1.cache.hits"] == 5
        # a scoped snapshot sees only its subtree, unprefixed
        assert root.scope("r1").snapshot() == {"cache.hits": 5}

    def test_registry_backed_stats_proxy(self):
        class S(RegistryBackedStats):
            _fields = (("n", 0), ("bytes", 0.0))

        s = S()
        s.n += 2
        s.bytes += 0.5
        assert s.n == 2 and s.bytes == 0.5
        assert s.as_dict() == {"n": 2, "bytes": 0.5}
        # numbers live in the handed-in registry scope, not the instance
        root = MetricsRegistry()
        s2 = S(registry=root.scope("x"))
        s2.n += 7
        assert root.snapshot()["x.n"] == 7
        with pytest.raises(AttributeError):
            s2.nonexistent_field


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------
class TestTracer:
    def test_spans_nest(self):
        t = Tracer()
        outer = t.begin("x", "outer", 0.0)
        inner = t.begin("x", "inner", 1.0)
        t.end(inner, 2.0)
        t.end(outer, 3.0)
        assert t.spans[outer].parent is None
        assert t.spans[inner].parent == outer
        assert t.spans[inner].dur == pytest.approx(1.0)
        # tracks nest independently
        other = t.begin("y", "solo", 0.5)
        assert t.spans[other].parent is None

    def test_end_pops_unclosed_children(self):
        t = Tracer()
        outer = t.begin("x", "outer", 0.0)
        t.begin("x", "dangling", 1.0)
        t.end(outer, 2.0)   # pops the dangling child too
        fresh = t.begin("x", "fresh", 3.0)
        assert t.spans[fresh].parent is None

    def test_complete_span_parents_without_pushing(self):
        t = Tracer()
        outer = t.begin("x", "outer", 0.0)
        leaf = t.span("x", "leaf", 0.5, 1.0)
        assert t.spans[leaf].parent == outer
        # the complete span is not on the stack: the next leaf still
        # parents under `outer`, not under `leaf`
        leaf2 = t.span("x", "leaf2", 1.0, 1.5)
        assert t.spans[leaf2].parent == outer

    def test_annotate_patches_args(self):
        t = Tracer()
        sid = t.span("x", "race", 0.0, 1.0, role="primary")
        t.annotate(sid, winner=False, cancelled=True)
        assert t.spans[sid].args == {
            "role": "primary", "winner": False, "cancelled": True
        }


# ---------------------------------------------------------------------------
# a fully traced fleet run: straggler -> hedge, plus one live migration
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def traced_fleet():
    tracer = Tracer()
    fleet = EdgeFleet(2, hedging=True, min_observations=4, tracer=tracer)
    model, x = make_mlp(0)
    c = fleet.connect(model, client_id="u0", min_repeats=2)
    for _ in range(8):
        c.infer(x)
    assert c.session.client.mode == "replaying"
    # stall the primary hard on every request: the adaptive deadline trips
    # and the router hedges to the second replica
    prim = fleet.replica(c.primary)
    prim.slowdown = lambda i: 1.0
    for _ in range(6):
        c.infer(x)
    prim.slowdown = lambda i: 0.0
    assert fleet.router.stats.hedged > 0
    # a second client, migrated live between replicas; speculation is
    # suspended for this phase so its recording rounds (slow vs. the
    # replay-built deadline) don't fork a backup onto the migration target
    fleet.router.hedge_multiplier = float("inf")
    model2, x2 = make_mlp(1)
    c2 = fleet.connect(model2, client_id="u1", min_repeats=2)
    for _ in range(4):
        c2.infer(x2)
    fleet.migrate("u1")
    c2.infer(x2)
    return tracer, fleet, c


class TestTracedFleet:
    def test_hedge_primary_and_backup_spans_loser_cancelled(
        self, traced_fleet
    ):
        tracer, _fleet, _c = traced_fleet
        by_req = {}
        for sp in tracer.find("hedge_dispatch"):
            key = (sp.args["client"], sp.args["req"])
            by_req.setdefault(key, []).append(sp)
        raced = [sps for sps in by_req.values() if len(sps) >= 2]
        assert raced, "no request ever raced primary vs backup"
        for sps in raced:
            roles = {sp.args["role"] for sp in sps}
            assert roles == {"primary", "backup"}
            winners = [sp for sp in sps if sp.args["winner"]]
            assert len(winners) == 1
            for sp in sps:
                assert sp.args["cancelled"] == (not sp.args["winner"])

    def test_timestamps_monotone_per_track(self, traced_fleet):
        tracer, _fleet, _c = traced_fleet
        assert all(sp.t1 is None or sp.t1 >= sp.t0 for sp in tracer.spans)
        last = {}
        for sp in tracer.spans:
            assert sp.t0 >= last.get(sp.track, 0.0), (
                f"track {sp.track} went backwards at {sp.name}"
            )
            last[sp.track] = sp.t0
        for ins in tracer.instants:
            assert ins.t >= 0.0

    def test_chrome_trace_schema(self, traced_fleet, tmp_path):
        tracer, _fleet, _c = traced_fleet
        doc = json.loads(json.dumps(to_chrome_trace(tracer), default=str))
        assert doc["displayTimeUnit"] == "ms"
        events = doc["traceEvents"]
        assert events
        names = set()
        tracks = set()
        for e in events:
            assert e["ph"] in {"X", "i", "C", "M"}
            if e["ph"] == "M":
                assert e["name"] in {"process_name", "thread_name"}
                continue
            assert isinstance(e["ts"], (int, float))
            assert e["pid"] == e["tid"].split("/", 1)[0]
            names.add(e["name"])
            tracks.add(e["tid"])
            if e["ph"] == "X":
                assert e["dur"] >= 0.0
            if e["ph"] == "i":
                assert e["s"] == "t"
        assert {"record_rpc", "replay_call", "hedge_dispatch",
                "migrate"} <= names
        replica_tracks = {t for t in tracks if re.match(r"^r\d+/", t)}
        assert len({t.split("/", 1)[0] for t in replica_tracks}) >= 2
        # file round-trip
        path = tmp_path / "trace.json"
        write_chrome_trace(tracer, str(path))
        assert json.loads(path.read_text())["traceEvents"]

    def test_root_snapshot_agrees_with_legacy_counters(self, traced_fleet):
        _tracer, fleet, c = traced_fleet
        snap = fleet.metrics.snapshot()
        assert snap["fleet.migrations"] == fleet.stats.migrations == 1
        assert snap["fleet.placements"] == fleet.stats.placements
        assert snap["hedge.requests"] == fleet.router.stats.requests
        assert snap["hedge.hedged"] == fleet.router.stats.hedged > 0
        assert (
            snap["hedge.latency_s"]["count"]
            == len(fleet.router.stats.latencies)
        )
        for i, rep in enumerate(fleet.replicas):
            assert snap[f"r{i}.cache.hits"] == rep.edge.cache.stats.hits
            assert (
                snap[f"r{i}.batcher.batches_executed"]
                == rep.edge.batcher.stats.batches_executed
            )
        # u0 never migrated: each of its sessions reports under the scope
        # of the replica that owns it, and RPC/byte counts agree
        for name, sess in c.sessions.items():
            assert (
                snap[f"{name}.client.u0.rpcs"] == sess.client.stats.rpcs > 0
            )
            assert (
                snap[f"{name}.client.u0.network_bytes"]
                == sess.client.stats.network_bytes
            )


# ---------------------------------------------------------------------------
# disabled tracing is provably free
# ---------------------------------------------------------------------------
class TestDisabledTracer:
    @staticmethod
    def _run(tracer):
        fleet = EdgeFleet(2, min_observations=4, tracer=tracer)
        model, x = make_mlp(7)
        c = fleet.connect(model, client_id="u0", min_repeats=2)
        outs = [np.asarray(c.infer(x).outputs[0]) for _ in range(6)]
        return outs, c.session.client.stats.as_dict(), fleet.summary()

    def test_disabled_is_bitwise_identical_and_silent(self):
        idle = Tracer()               # constructed but never attached
        base_outs, base_stats, base_sum = self._run(None)
        assert idle.n_events == 0     # tracing off => zero events
        tr = Tracer()
        t_outs, t_stats, t_sum = self._run(tr)
        assert tr.n_events > 0
        for a, b in zip(base_outs, t_outs):
            assert np.array_equal(a, b)
        assert base_stats == t_stats
        assert base_sum["fleet"] == t_sum["fleet"]
        assert base_sum["router"] == t_sum["router"]
        assert base_sum["backhaul_bytes"] == t_sum["backhaul_bytes"]


# ---------------------------------------------------------------------------
# host spans on the profiler clock, and the counters at their boundaries
# ---------------------------------------------------------------------------
def _edge_rounds(n_clients=3, rounds=5):
    """Co-tenants of one model through vmap-batched rounds: each round's
    outputs, and the batcher's counters after every round."""
    model, x = make_mlp(3)
    edge = RRTOEdgeServer(execute=True)
    for _ in range(n_clients):
        edge.connect(model)
    ids = list(edge.sessions)
    outs, stats = [], []
    for _ in range(rounds):
        res = edge.run_round({c: (x,) for c in ids})
        outs.append([np.asarray(res[c].outputs[0]) for c in ids])
        stats.append(edge.batcher.stats.as_dict())
    return outs, stats, edge


class TestHostSpans:
    def test_host_span_is_a_profiler_annotation(self):
        span = host_span("rrto.replay", client="u0")
        assert isinstance(span, jax.profiler.TraceAnnotation)
        with span:
            pass

    def test_profiler_on_is_bitwise_identical(self, tmp_path):
        base_outs, base_stats, base_sum = TestDisabledTracer._run(None)
        base_edge, base_batcher, _ = _edge_rounds()
        with jax.profiler.trace(str(tmp_path)):
            p_outs, p_stats, p_sum = TestDisabledTracer._run(None)
            p_edge, p_batcher, _ = _edge_rounds()
        for a, b in zip(base_outs, p_outs):
            assert np.array_equal(a, b)
        assert base_stats == p_stats
        assert base_sum["fleet"] == p_sum["fleet"]
        for round_a, round_b in zip(base_edge, p_edge):
            for a, b in zip(round_a, round_b):
                assert np.array_equal(a, b)
        assert base_batcher == p_batcher
        assert p_batcher[-1]["vmap_batches"] >= 1

    def test_replayed_records_grow_by_the_ios_per_replayed_call(self):
        model, x = make_mlp(4)
        sess = OffloadSession(model, "rrto", min_repeats=2)
        sess.load()
        while sess.client.mode != "replaying":
            sess.infer(x)
        stats = sess.client.stats
        n = len(sess.client.ios)
        assert n > 0
        for _ in range(3):
            before = stats.replayed_records
            sess.infer(x)
            assert stats.replayed_records - before == n

    def test_param_compares_per_vmap_round(self):
        _, stats, edge = _edge_rounds(n_clients=3, rounds=6)
        cid = next(iter(edge.sessions))
        leaves = len(edge.server.context(cid).replay.param_addrs)
        batched = [(b["vmap_batches"] - a["vmap_batches"],
                    b["param_compares"] - a["param_compares"])
                   for a, b in zip(stats, stats[1:])]
        # the first vmap round proves each co-tenant leaf once and aliases
        # it; every later round passes by buffer identity, with no compare
        vmap_rounds = [compares for rounds, compares in batched if rounds]
        assert vmap_rounds[0] == (3 - 1) * leaves
        assert len(vmap_rounds) >= 2 and not any(vmap_rounds[1:])
        assert sum(compares for _, compares in batched) == (3 - 1) * leaves
        assert stats[-1]["param_aliases"] == (3 - 1) * leaves
        # co-tenants whose weights differ stop at the first unequal leaf
        m0, x = make_mlp(0)
        m1, _ = make_mlp(7)
        edge = RRTOEdgeServer(execute=True)
        edge.connect(m0)
        edge.connect(m1)
        for _ in range(4):
            edge.run_round({"c0": (x,), "c1": (x,)})
        assert edge.batcher.vmap_batches == 0
        groups = edge.batcher.batch_sizes.count(2)
        assert groups >= 1
        assert edge.batcher.param_compares == groups


# ---------------------------------------------------------------------------
# planner explain report
# ---------------------------------------------------------------------------
class TestPlanExplain:
    def test_plan_explain_event_matches_choice(self):
        model, x = make_mlp(3)
        sess = OffloadSession(model, "rrto", min_repeats=2)
        sess.load()
        for _ in range(4):
            sess.infer(x)
        graph = SegmentGraph(sess.client._ios_calls)
        tracer = Tracer()
        best = plan_partition(
            graph, sess.client_device, sess.server_device, 16 * MBPS,
            tracer=tracer, trace_track="planner", now=1.5,
        )
        explains = [
            i for i in tracer.instants if i.name == "plan_explain"
        ]
        assert len(explains) == 1
        ev = explains[0]
        assert ev.track == "planner" and ev.t == 1.5
        rows = ev.args["candidates"]
        assert len(rows) >= 2          # at least both binary endpoints
        assert ev.args["chosen"] == best.plan.signature()
        by_cost = min(rows, key=lambda r: r["cost"])
        assert by_cost["plan"] == best.plan.signature()
        assert by_cost["cost"] == pytest.approx(
            plan_cost(best, "latency")
        )
