"""Fleet-scale replicated serving: N edge replicas behind a hedged router.

RRTO's record/replay serving has so far been grown against a single
:class:`~repro.serving.multitenant.RRTOEdgeServer`; a real MEC deployment is
multi-server, and at that scale user-visible behaviour is dominated by tail
latency and replica failure, not steady-state throughput.  This module
composes the existing single-box pieces into a replicated fleet:

* **Placement** — :meth:`EdgeFleet.connect` places each client on a replica
  by affinity (a replica already serving this model/fingerprint keeps
  collecting its co-tenants, so the shared-cache and batched-replay wins
  compound) with least-load as the tie-break.

* **Hedged dispatch** — every request goes through a
  :class:`~repro.distributed.straggler.HedgedRouter` whose completion source
  executes the *real* replay on the chosen replica (the standalone
  ``ReplicaModel`` latency simulation replaced by actual
  :class:`~repro.core.engine.BoundReplay` /
  :class:`~repro.core.engine.BoundSegmentedReplay` execution): if the
  primary's completion latency exceeds the adaptive deadline — or the
  primary is failed — the request re-dispatches to a backup replica and the
  first completion wins.  Open-loop request streams ride the
  :class:`~repro.core.netsim.EventTimeline` (:meth:`EdgeFleet.serve`).

* **Cache replication** — validated IOS fingerprints travel between replicas
  through the :meth:`~repro.serving.replay_cache.ReplayCache.save` /
  :meth:`~repro.serving.replay_cache.ReplayCache.load` persistence layer
  (the shared cache tier): a hedged request landing on a cold replica adopts
  the replicated fingerprint after a *single* recorded inference instead of
  re-running the full ``min_repeats`` Operator Sequence Search.

* **Carried-state migration** — a stateful session's donated server-resident
  state (the KV cache) migrates between replicas mid-stream on failure or
  rebalance: the source exports the live state
  (:meth:`~repro.core.engine.OffloadServer.export_carried_state`), the
  device-memory namespace transfers over the site backhaul, the destination
  rebinds the replay executable from the client's recorded calls (adopting
  the replicated fingerprint) and imports the state — bitwise-identical
  continuation, asserted by tests/test_fleet.py.  The in-process precedent
  is ``RRTOClient._install_plan``'s whole-program <-> segmented state
  handoff.

Hedging discipline: a speculative re-dispatch re-executes the request, so it
requires idempotence.  Stateless inference is idempotent (wire inputs fully
determine outputs — the hedge winner's outputs are bitwise equal to the
loser's).  A *stateful* replay step advances donated server-resident state
and is not: stateful clients therefore hedge only on outright primary
failure, where the step never executed, and the re-dispatch first migrates
the session (with its carried state) to the backup.
"""
from __future__ import annotations

import dataclasses
import os
import tempfile
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax

from repro.core.costmodel import GTX_2080TI, DeviceSpec
from repro.core.engine import SimClock
from repro.core.netsim import (
    EventTimeline,
    FaultInjector,
    SharedBackhaul,
    multi_node_ingress,
)
from repro.core.offload import InferenceResult, OffloadableModel, OffloadSession
from repro.distributed.straggler import (
    HedgedRouter,
    NoHealthyReplicaError,
)
from repro.obs import MetricsRegistry, RegistryBackedStats, Tracer
from repro.serving.multitenant import RRTOEdgeServer
from repro.serving.recovery import SessionCheckpointer


@dataclasses.dataclass
class FleetReplica:
    """One edge box in the fleet: a full multi-tenant edge server plus the
    health / latency-injection knobs the fault-injection test layer drives.

    ``slowdown`` adds injected completion latency (request index -> extra
    seconds) on top of the measured inference wall time — modelling
    preemptions and network hiccups on this box without perturbing the
    underlying simulation.  ``failed=True`` makes the box stop completing
    requests (dispatches observe ``None`` and hedge away)."""

    name: str
    edge: RRTOEdgeServer
    failed: bool = False
    slowdown: Callable[[int], float] = lambda i: 0.0

    @property
    def load(self) -> int:
        return len(self.edge.sessions)


class CircuitBreaker:
    """Per-replica saturation breaker (closed / open / half-open).

    A replica that keeps failing or completing far beyond the fleet's
    observed baseline is *saturated*; hedging into it only deepens its queue.
    The breaker counts consecutive bad outcomes (failure, or latency above
    ``latency_multiplier`` x the router's observed median); at
    ``failure_threshold`` it opens for ``cooldown_s`` of simulated time, the
    router's health hook routes around it, and after the cooldown one probe
    request (half-open) decides: good closes the breaker, bad re-opens it.

    The breaker is a *soft* signal — the router falls back to open-breaker
    replicas when nothing else is healthy, so a fleet-wide brownout degrades
    instead of erroring."""

    CLOSED = "closed"
    OPEN = "open"
    HALF_OPEN = "half_open"

    def __init__(
        self,
        *,
        failure_threshold: int = 3,
        cooldown_s: float = 0.25,
        latency_multiplier: float = 4.0,
    ):
        if failure_threshold < 1:
            raise ValueError(
                f"failure_threshold must be >= 1, got {failure_threshold}"
            )
        self.failure_threshold = int(failure_threshold)
        self.cooldown_s = float(cooldown_s)
        self.latency_multiplier = float(latency_multiplier)
        self.state = self.CLOSED
        self.consecutive_bad = 0
        self.open_until = 0.0
        self.opens = 0

    def allow(self, t: float) -> bool:
        """May this replica take a request at ``t``?  An elapsed cooldown
        transitions open -> half-open and admits the probe."""
        if self.state == self.OPEN:
            if t >= self.open_until:
                self.state = self.HALF_OPEN
                return True
            return False
        return True

    def record(
        self,
        t: float,
        *,
        failed: bool,
        latency_s: Optional[float] = None,
        baseline_s: Optional[float] = None,
    ) -> None:
        """Score one completed (or failed) dispatch on this replica."""
        bad = failed or (
            latency_s is not None
            and baseline_s is not None
            and baseline_s > 0.0
            and latency_s > self.latency_multiplier * baseline_s
        )
        if bad:
            self.consecutive_bad += 1
            if (
                self.state == self.HALF_OPEN
                or self.consecutive_bad >= self.failure_threshold
            ):
                self.state = self.OPEN
                self.open_until = t + self.cooldown_s
                self.opens += 1
                self.consecutive_bad = 0
        else:
            self.consecutive_bad = 0
            self.state = self.CLOSED


class FleetStats(RegistryBackedStats):
    """Fleet-wide counters, registry-backed (see
    :class:`repro.obs.MetricsRegistry`)."""

    _fields = (
        ("placements", 0),
        ("affinity_hits", 0),
        ("migrations", 0),
        ("migration_bytes", 0.0),
        ("cache_syncs", 0),
        ("replicated_fingerprints", 0),
        ("backup_sessions", 0),
        ("crashes", 0),
        ("crash_restores", 0),
        ("checkpoints", 0),
        ("checkpoint_bytes", 0.0),
        ("steps_replayed", 0),
    )


@dataclasses.dataclass
class FleetResult:
    """One completed request of an open-loop fleet stream."""

    client_id: str
    outputs: List[Any]
    arrival_t: float
    done_at: float
    winner: str               # replica that served the winning completion

    @property
    def latency_seconds(self) -> float:
        return self.done_at - self.arrival_t


class FleetClient:
    """One mobile client served by the fleet.

    Holds the client's sessions per replica: a stateless client may hold a
    primary session plus lazily-created backup sessions (hedge targets); a
    stateful client holds exactly one session, which *migrates* between
    replicas instead of forking — the donated carried state is single-home."""

    def __init__(
        self,
        fleet: "EdgeFleet",
        model: OffloadableModel,
        client_id: str,
        session: OffloadSession,
        primary: str,
        *,
        min_repeats: int = 3,
        stateful: bool = False,
    ):
        self.fleet = fleet
        self.model = model
        self.client_id = client_id
        self.min_repeats = min_repeats
        self.stateful = stateful
        self.sessions: Dict[str, OffloadSession] = {primary: session}
        self.primary = primary
        self._req_idx = 0

    @property
    def session(self) -> OffloadSession:
        """The session on the client's current primary replica."""
        return self.sessions[self.primary]

    def infer(
        self, *inputs, deadline_s: Optional[float] = None
    ) -> InferenceResult:
        """Hedged inference; returns the winning replica's result."""
        res, _, _ = self.dispatch(*inputs, deadline_s=deadline_s)
        return res

    def dispatch(
        self, *inputs, deadline_s: Optional[float] = None
    ) -> Tuple[InferenceResult, float, str]:
        """One hedged request through the fleet router; returns
        ``(winning result, completion latency, winner replica name)``.

        The router's completion source runs the real replay on the chosen
        replica and reports ``wall_seconds`` plus that replica's injected
        slowdown; a failed replica reports no completion and the router
        re-dispatches.  May raise
        :class:`~repro.distributed.straggler.AllReplicasFailedError`."""
        fleet = self.fleet
        fleet.apply_due_faults()
        tracer = fleet.tracer
        req = self._req_idx
        self._req_idx += 1
        results: Dict[str, InferenceResult] = {}
        hedge_spans: Dict[str, int] = {}
        primary_at_dispatch = self.primary

        def complete(replica: FleetReplica, idx: int) -> Optional[float]:
            t0 = fleet.clock.t
            res = self._execute_on(replica, inputs, deadline_s=deadline_s)
            breaker = (
                fleet.breakers.get(replica.name)
                if fleet.breakers is not None
                else None
            )
            if res is None:
                if breaker is not None:
                    breaker.record(fleet.clock.t, failed=True)
                if tracer is not None:
                    tracer.instant(
                        f"{replica.name}/hedge", "hedge_failed", t0,
                        client=self.client_id, req=req,
                    )
                return None
            results[replica.name] = res
            lat = res.wall_seconds + max(0.0, replica.slowdown(idx))
            if breaker is not None:
                breaker.record(
                    fleet.clock.t,
                    failed=False,
                    latency_s=lat,
                    baseline_s=fleet.router.observed_median,
                )
            if tracer is not None:
                hedge_spans[replica.name] = tracer.span(
                    f"{replica.name}/hedge", "hedge_dispatch", t0, t0 + lat,
                    client=self.client_id, req=req,
                    role=(
                        "primary"
                        if replica.name == primary_at_dispatch
                        else "backup"
                    ),
                )
            return lat

        # a live stateful session's replay step is non-idempotent (donated
        # carried state advances server-side) — hedge it on failure only
        primary_idx = fleet.replica_index(self.primary)
        if (
            fleet.breakers is not None
            and not self.stateful
            and not fleet.breakers[self.primary].allow(fleet.clock.t)
        ):
            # the primary's breaker is open: route around the saturated box
            # *before* dispatching into it (a stateful session stays home —
            # its carried state is single-homed)
            try:
                primary_idx = fleet.router._pick(exclude=primary_idx)
            except NoHealthyReplicaError:
                pass  # nothing better: the saturated primary still serves
        latency, winner = fleet.router.dispatch(
            req,
            primary=primary_idx,
            completion=complete,
            speculative=not (self.stateful and self.session.client.stateful_replay),
        )
        if tracer is not None:
            for name, sid in hedge_spans.items():
                tracer.annotate(
                    sid, winner=(name == winner), cancelled=(name != winner)
                )
        if winner != self.primary and fleet.replica(self.primary).failed:
            # the primary is dead: re-place this client on the winner for
            # every future request (a stateful client already migrated
            # inside the completion source)
            self.primary = winner
        self._note_lock()
        if self.stateful and fleet.checkpointer is not None:
            fleet._maybe_checkpoint(self)
        return results[winner], latency, winner

    # ------------------------------------------------------------------
    def _execute_on(
        self,
        replica: FleetReplica,
        inputs: Sequence[Any],
        deadline_s: Optional[float] = None,
    ) -> Optional[InferenceResult]:
        if replica.failed:
            return None
        sess = self.sessions.get(replica.name)
        if sess is None:
            if self.stateful:
                # failure re-dispatch of a stateful session: move it —
                # carried state and all — then execute the step exactly
                # once.  A merely-failed source still exports its live
                # state (migration); a *crashed* source lost it, so the
                # session restores from the last checkpoint instead
                src = self.fleet.locate(self.client_id)
                if self.fleet.is_crashed(src.name):
                    self.fleet.recover(self.client_id, replica.name)
                else:
                    self.fleet.migrate(self.client_id, replica.name)
                sess = self.sessions[replica.name]
            else:
                sess = self.fleet._backup_session(self, replica)
        return sess.infer(*inputs, deadline_s=deadline_s)

    def _note_lock(self) -> None:
        """Record fingerprint affinity once this client's IOS locks, so
        future placements of the same sequence co-locate with it."""
        cl = self.session.client
        if cl.ios_fp is not None and cl.ios_fp not in self.fleet._affinity:
            self.fleet._affinity[cl.ios_fp] = self.primary
            # a freshly validated fingerprint immediately enters the shared
            # cache tier: every replica knows it before any hedge lands there
            self.fleet.replicate_caches()


class EdgeFleet:
    """N replicated edge servers behind a hedged, affinity-placing router.

    All replicas share one :class:`~repro.core.engine.SimClock` (sessions
    migrate between them without time jumps) and hang their per-node ingress
    off one site :class:`~repro.core.netsim.SharedBackhaul`.  Request
    streams are driven on a :class:`~repro.core.netsim.EventTimeline`
    (:meth:`serve`)."""

    def __init__(
        self,
        n_replicas: int = 2,
        *,
        server_device: DeviceSpec = GTX_2080TI,
        execute: bool = True,
        cache_capacity: int = 8,
        cache_capacity_bytes: Optional[float] = None,
        batch_window_s: float = 2e-3,
        environment: str = "indoor",
        node_capacity_bytes_per_s: float = 1e9 / 8.0,
        backhaul_bytes_per_s: float = 10e9 / 8.0,
        hedging: bool = True,
        hedge_multiplier: float = 2.0,
        min_observations: int = 8,
        tracer: Optional[Tracer] = None,
        metrics: Optional[MetricsRegistry] = None,
        fault: Optional[FaultInjector] = None,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 4,
        circuit_breaker: bool = False,
        breaker_cooldown_s: float = 0.25,
        breaker_threshold: int = 3,
        breaker_latency_multiplier: float = 4.0,
        admission_factory: Optional[Callable[[str], Any]] = None,
    ):
        if n_replicas < 1:
            raise ValueError(f"need at least one replica, got {n_replicas}")
        # replica i serves from device i % len(devices): on a four-chip host
        # each of four replicas owns a chip
        devices = jax.devices()
        self.clock = SimClock()
        self.timeline = EventTimeline()
        self.tracer = tracer
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        ingresses = multi_node_ingress(
            n_replicas,
            node_capacity_bytes_per_s=node_capacity_bytes_per_s,
            backhaul_bytes_per_s=backhaul_bytes_per_s,
        )
        self.backhaul: SharedBackhaul = ingresses[0].backhaul
        self.replicas: List[FleetReplica] = [
            FleetReplica(
                name=f"r{i}",
                edge=RRTOEdgeServer(
                    server_device=server_device,
                    execute=execute,
                    cache_capacity=cache_capacity,
                    cache_capacity_bytes=cache_capacity_bytes,
                    batch_window_s=batch_window_s,
                    environment=environment,
                    ingress=ingresses[i],
                    clock=self.clock,
                    name=f"r{i}",
                    jax_device=devices[i % len(devices)],
                    tracer=tracer,
                    metrics=self.metrics.scope(f"r{i}"),
                    fault=fault,
                    # one controller per box (each guards its own queue and
                    # ingress); None = no admission layer on this fleet
                    admission=(
                        admission_factory(f"r{i}")
                        if admission_factory is not None
                        else None
                    ),
                ),
            )
            for i in range(n_replicas)
        ]
        self.hedging = hedging
        # per-replica circuit breakers: the router's soft health signal.
        # None (the default) leaves routing bitwise pre-breaker.
        self.breakers: Optional[Dict[str, CircuitBreaker]] = (
            {
                rep.name: CircuitBreaker(
                    failure_threshold=breaker_threshold,
                    cooldown_s=breaker_cooldown_s,
                    latency_multiplier=breaker_latency_multiplier,
                )
                for rep in self.replicas
            }
            if circuit_breaker
            else None
        )
        self.router = HedgedRouter(
            self.replicas,
            # hedge_multiplier=inf never trips the speculative deadline, so
            # a no-hedge fleet still recovers from outright failures
            hedge_multiplier=hedge_multiplier if hedging else float("inf"),
            min_observations=min_observations,
            metrics=self.metrics.scope("hedge"),
            health=(
                (
                    lambda i: self.breakers[
                        self.replicas[i].name
                    ].allow(self.clock.t)
                )
                if circuit_breaker
                else None
            ),
        )
        self.clients: Dict[str, FleetClient] = {}
        self._affinity: Dict[str, str] = {}   # model name / IOS fp -> replica
        self.stats = FleetStats(registry=self.metrics.scope("fleet"))
        self.fault = fault
        self.checkpointer = (
            SessionCheckpointer(checkpoint_dir, every=checkpoint_every)
            if checkpoint_dir is not None
            else None
        )
        self._crashed: set = set()

    # -- replica lookup -------------------------------------------------
    def replica(self, name: str) -> FleetReplica:
        for rep in self.replicas:
            if rep.name == name:
                return rep
        raise KeyError(f"unknown replica {name!r}")

    def replica_index(self, name: str) -> int:
        for i, rep in enumerate(self.replicas):
            if rep.name == name:
                return i
        raise KeyError(f"unknown replica {name!r}")

    def locate(self, client_id: str) -> FleetReplica:
        """The replica currently hosting ``client_id``'s session."""
        for rep in self.replicas:
            if client_id in rep.edge.sessions:
                return rep
        raise KeyError(f"client {client_id!r} not connected to any replica")

    # -- placement ------------------------------------------------------
    def place(
        self, model: OffloadableModel, fingerprint: Optional[str] = None
    ) -> FleetReplica:
        """Pick a replica for a new client: affinity first (a replica
        already serving this model — or, for a reconnecting client, its IOS
        fingerprint — keeps collecting co-tenants so the shared-cache and
        batched-replay wins compound), least load as the tie-break."""
        healthy = [r for r in self.replicas if not r.failed]
        if not healthy:
            raise NoHealthyReplicaError("every fleet replica is failed")
        self.stats.placements += 1
        for key in (fingerprint, model.name):
            if key is None:
                continue
            owner = self._affinity.get(key)
            if owner is not None and not self.replica(owner).failed:
                self.stats.affinity_hits += 1
                if self.tracer is not None:
                    self.tracer.instant(
                        "fleet", "place", self.clock.t,
                        model=model.name, replica=owner, affinity=True,
                    )
                return self.replica(owner)
        rep = min(healthy, key=lambda r: r.load)
        self._affinity.setdefault(model.name, rep.name)
        if self.tracer is not None:
            self.tracer.instant(
                "fleet", "place", self.clock.t,
                model=model.name, replica=rep.name, affinity=False,
            )
        return rep

    def connect(
        self,
        model: OffloadableModel,
        *,
        client_id: Optional[str] = None,
        min_repeats: int = 3,
        stateful: bool = False,
        fingerprint: Optional[str] = None,
        **session_kwargs: Any,
    ) -> FleetClient:
        """Place and attach one client; ``stateful=True`` declares that the
        model carries loop state (KV cache) so the fleet never forks its
        session — hedging is failure-only and moves the session by
        migration."""
        cid = (
            client_id
            if client_id is not None
            else f"u{sum(len(r.edge.sessions) for r in self.replicas)}"
        )
        if cid in self.clients:
            raise ValueError(f"client id {cid!r} already connected")
        rep = self.place(model, fingerprint)
        sess = rep.edge.connect(
            model, client_id=cid, min_repeats=min_repeats, **session_kwargs
        )
        client = FleetClient(
            self, model, cid, sess, rep.name,
            min_repeats=min_repeats, stateful=stateful,
        )
        if stateful and self.checkpointer is not None:
            self.checkpointer.attach(sess.client)
        self.clients[cid] = client
        return client

    def _backup_session(
        self, client: FleetClient, replica: FleetReplica
    ) -> OffloadSession:
        """Create a hedge-target session on a replica the client has never
        used.  The validated fingerprint reaches the cold replica through
        the shared cache tier first, so the backup adopts the IOS after one
        recorded inference instead of re-running the full ``min_repeats``
        search."""
        self.replicate_caches()
        sess = replica.edge.connect(
            client.model,
            client_id=client.client_id,
            min_repeats=client.min_repeats,
        )
        client.sessions[replica.name] = sess
        self.stats.backup_sessions += 1
        return sess

    # -- cache replication ----------------------------------------------
    def replicate_caches(self) -> int:
        """Push every replica's validated fingerprints to every other
        replica through the :class:`ReplayCache` persistence layer (each
        replica publishes its metadata file to the shared cache tier, every
        peer merges all of them).  A failed replica's file still replicates
        — that is how its validated fingerprints survive the box.  Returns
        the number of fingerprints known fleet-wide afterwards."""
        self.stats.cache_syncs += 1
        with tempfile.TemporaryDirectory() as tier:
            paths = {}
            for rep in self.replicas:
                paths[rep.name] = os.path.join(tier, f"{rep.name}.json")
                rep.edge.save_cache(paths[rep.name])
            for rep in self.replicas:
                for other, path in paths.items():
                    if other != rep.name:
                        rep.edge.load_cache(path)
        known = set()
        for rep in self.replicas:
            known.update(rep.edge.cache.fingerprints)
            known.update(rep.edge.cache.persisted_fingerprints)
        self.stats.replicated_fingerprints = len(known)
        return len(known)

    # -- carried-state migration ----------------------------------------
    def migrate(self, client_id: str, to: Optional[str] = None) -> str:
        """Move one client's session — including its live donated carried
        state — to another replica mid-stream; returns the destination name.

        Steps: (1) the validated fingerprint travels through the shared
        cache tier, (2) the live carried state is exported from the source
        binding, (3) the device-memory namespace (parameters + staged
        buffers) transfers over the site backhaul, (4) the destination
        rebinds the replay executable from the client's recorded calls and
        imports the carried state, (5) the session re-associates with the
        destination box.  The continuation is bitwise-identical to never
        having migrated (tests/test_fleet.py pins this per step and for the
        final state).

        The source box's memory is read directly even when it is marked
        failed — the modelled deployment checkpoints carried state to the
        shared tier, and the simulation's stand-in for that checkpoint is
        the in-process context."""
        src = self.locate(client_id)
        if to is None:
            candidates = [
                r for r in self.replicas
                if r.name != src.name and not r.failed
            ]
            if not candidates:
                raise NoHealthyReplicaError(
                    f"no healthy migration target for {client_id!r}"
                )
            dst = min(candidates, key=lambda r: r.load)
        else:
            dst = self.replica(to)
        if dst.name == src.name:
            return src.name

        t_mig = self.clock.t
        mig_span = (
            self.tracer.begin(
                "fleet", "migrate", t_mig,
                client=client_id, src=src.name, dst=dst.name,
            )
            if self.tracer is not None
            else None
        )
        sess = src.edge.sessions[client_id]
        cl = sess.client
        self.replicate_caches()
        state = src.edge.server.export_carried_state(client_id)
        src_ctx = src.edge.server.contexts.get(client_id)

        src.edge.disconnect(client_id)
        dst.edge.adopt_session(sess)
        moved = 0.0
        if src_ctx is not None:
            # device to device: the buffers land in the destination
            # server's own memory
            moved = dst.edge.server.receive_env(client_id, src_ctx.env)
            self.stats.migration_bytes += moved
            # replica-to-replica state transfer rides the site backhaul,
            # not any client radio
            self.backhaul.bytes_total += moved
            if self.tracer is not None:
                self.tracer.instant(
                    "fleet", "state_transfer", self.clock.t,
                    client=client_id, bytes=moved,
                )
        if cl.ios is not None:
            # rebind the replay executable(s) on the destination: the
            # replicated fingerprint is already known there, so the rebuild
            # is a single compile, and seeding reads the transferred env
            dst.edge.server.prepare_replay(
                cl._ios_calls,
                client_id=client_id,
                fingerprint=cl.ios_fp,
                carried_pairs=cl.ios.carried_pairs,
            )
            if cl.split_plan is not None:
                dst.edge.server.prepare_split(
                    cl._ios_calls,
                    cl.split_plan,
                    client_id=client_id,
                    fingerprint=cl.ios_fp,
                    carried_pairs=cl.ios.carried_pairs,
                )
            if state is not None:
                dst.edge.server.import_carried_state(client_id, state)
            if cl.ios_fp is not None:
                self._affinity[cl.ios_fp] = dst.name
        src.edge.server.contexts.pop(client_id, None)

        client = self.clients.get(client_id)
        if client is not None:
            client.sessions.pop(src.name, None)
            client.sessions[dst.name] = sess
            client.primary = dst.name
        self.stats.migrations += 1
        if mig_span is not None:
            self.tracer.annotate(mig_span, bytes=moved)
            self.tracer.end(mig_span, self.clock.t)
        return dst.name

    # -- crash recovery --------------------------------------------------
    def apply_due_faults(self) -> None:
        """Fire any scheduled replica crashes whose time has come (consulted
        at every dispatch entry, so crashes land between steps exactly as a
        dead box would be noticed at the next request)."""
        if self.fault is None:
            return
        for name in self.fault.due_crashes(self.clock.t):
            if any(r.name == name for r in self.replicas):
                self.crash(name)

    def crash(self, name: str) -> None:
        """Kill a replica: unlike a soft failure (``failed=True``, memory
        intact, migration still possible), a crash wipes the box's
        device-memory contexts and its dedup table — every donated carried
        state on it is gone, recoverable only from checkpoints."""
        rep = self.replica(name)
        rep.failed = True
        rep.edge.server.contexts.clear()
        rep.edge.server.dedup.clear()
        self._crashed.add(name)
        self.stats.crashes += 1
        if self.tracer is not None:
            self.tracer.instant("fleet", "crash", self.clock.t, replica=name)

    def is_crashed(self, name: str) -> bool:
        return name in self._crashed

    def _maybe_checkpoint(self, client: FleetClient) -> None:
        """Publish a due carried-state checkpoint for one stateful client;
        the write travels to the shared checkpoint tier over the site
        backhaul, like cache replication and migration traffic."""
        rep = self.replica(client.primary)
        nbytes = self.checkpointer.maybe_checkpoint(
            client.client_id, rep.edge.server, client.session.client
        )
        if nbytes > 0.0:
            self.stats.checkpoints += 1
            self.stats.checkpoint_bytes += nbytes
            self.backhaul.bytes_total += nbytes
            if self.tracer is not None:
                self.tracer.instant(
                    "fleet", "checkpoint", self.clock.t,
                    client=client.client_id, bytes=nbytes,
                    seq=client.session.client.step_seq,
                )

    def recover(self, client_id: str, to: Optional[str] = None) -> str:
        """Restore a stateful session whose home replica *crashed* (its
        donated carried state is gone — :meth:`migrate` cannot help) onto a
        healthy peer; returns the destination name.

        Steps: (1) the newest complete checkpoint is read from the shared
        tier, (2) the session re-associates with the destination and the
        checkpointed device-memory namespace + carried state are installed
        under a freshly-rebuilt replay binding (the replicated fingerprint
        makes that a single compile), (3) the client re-drives the logged
        steps the checkpoint misses — deterministic replay of the same
        wire inputs through the same executable, so the recovered stream
        is token-for-token what a crash-free run would have produced."""
        if self.checkpointer is None:
            raise RuntimeError(
                "crash recovery requires an EdgeFleet checkpoint_dir"
            )
        src = self.locate(client_id)
        if to is None:
            candidates = [
                r for r in self.replicas
                if r.name != src.name and not r.failed
            ]
            if not candidates:
                raise NoHealthyReplicaError(
                    f"no healthy recovery target for {client_id!r}"
                )
            dst = min(candidates, key=lambda r: r.load)
        else:
            dst = self.replica(to)
        sess = src.edge.sessions[client_id]
        cl = sess.client
        if cl.split_plan is not None:
            raise NotImplementedError(
                "crash recovery replays through the whole-program binding; "
                "split-plan sessions are not supported yet"
            )
        ckpt = self.checkpointer.load_latest(client_id)
        if ckpt is None:
            raise RuntimeError(
                f"no checkpoint for {client_id!r}: its carried state died "
                f"with {src.name!r} before the first checkpoint boundary"
            )
        t0 = self.clock.t
        span = (
            self.tracer.begin(
                "fleet", "crash_restore", t0,
                client=client_id, src=src.name, dst=dst.name, seq=ckpt.seq,
            )
            if self.tracer is not None
            else None
        )
        self.replicate_caches()
        src.edge.disconnect(client_id)
        dst.edge.adopt_session(sess)
        dst.edge.server.receive_env(client_id, ckpt.env)
        self.backhaul.bytes_total += ckpt.nbytes
        if cl.ios is not None:
            dst.edge.server.prepare_replay(
                cl._ios_calls,
                client_id=client_id,
                fingerprint=cl.ios_fp,
                carried_pairs=cl.ios.carried_pairs,
            )
            if ckpt.carried:
                dst.edge.server.import_carried_state(
                    client_id, list(ckpt.carried)
                )
            if cl.ios_fp is not None:
                self._affinity[cl.ios_fp] = dst.name
        # re-drive the logged steps the checkpoint predates: the client
        # retransmits each step's recorded wire inputs and the restored
        # binding advances the carried state exactly as the dead box did
        replayed = 0
        for entry in list(cl.step_log or ()):
            if entry.seq < ckpt.seq or entry.seq >= cl.step_seq:
                continue
            payload = float(
                sum(a.nbytes for a in entry.wire_inputs)
            ) / cl.input_wire_divisor
            cl._rpc(payload, 32)
            _, done_at = dst.edge.server.run_replay(
                entry.wire_inputs,
                self.clock.t,
                client_id,
                fresh_carried=entry.fresh_carried,
            )
            cl._wait_until(done_at)
            replayed += 1
        self.stats.steps_replayed += replayed
        self.stats.crash_restores += 1
        cl.stats.crash_restores += 1

        client = self.clients.get(client_id)
        if client is not None:
            client.sessions.pop(src.name, None)
            client.sessions[dst.name] = sess
            client.primary = dst.name
        if span is not None:
            self.tracer.annotate(
                span, bytes=ckpt.nbytes, steps_replayed=replayed
            )
            self.tracer.end(span, self.clock.t)
        return dst.name

    # -- open-loop serving on the event timeline -------------------------
    def serve(
        self,
        requests: Sequence[Tuple[float, str, Tuple[Any, ...]]],
        until: Optional[float] = None,
    ) -> List[FleetResult]:
        """Drive an open-loop request stream on the event timeline: each
        ``(arrival_t, client_id, inputs)`` dispatches at its (absolute,
        global-time, non-decreasing vs. the timeline's ``now``) arrival, and
        a completion event fires at ``arrival + hedged latency`` — so
        interleaving across clients and replicas is deterministic and
        completions are first-class timeline events."""
        results: List[Optional[FleetResult]] = [None] * len(requests)

        def fire(k: int, cid: str, inputs: Tuple[Any, ...]) -> None:
            client = self.clients[cid]
            arrival = self.timeline.now
            res, latency, winner = client.dispatch(*inputs)

            def complete() -> None:
                results[k] = FleetResult(
                    client_id=cid,
                    outputs=res.outputs,
                    arrival_t=arrival,
                    done_at=arrival + latency,
                    winner=winner,
                )

            self.timeline.at(arrival + latency, complete)

        for k, (t, cid, inputs) in enumerate(requests):
            self.timeline.at(
                float(t), lambda k=k, cid=cid, inputs=inputs: fire(k, cid, inputs)
            )
        self.timeline.run(until)
        return [r for r in results if r is not None]

    # ------------------------------------------------------------------
    def summary(self) -> Dict[str, Any]:
        return dict(
            replicas=len(self.replicas),
            clients=len(self.clients),
            hedging=self.hedging,
            fleet=self.stats.as_dict(),
            router=self.router.stats.as_dict(),
            breakers=(
                {
                    name: dict(state=b.state, opens=b.opens)
                    for name, b in self.breakers.items()
                }
                if self.breakers is not None
                else None
            ),
            backhaul_bytes=self.backhaul.bytes_total,
            events_fired=self.timeline.fired,
            per_replica={
                rep.name: rep.edge.summary() for rep in self.replicas
            },
        )
