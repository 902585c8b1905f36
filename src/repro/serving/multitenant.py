"""Multi-tenant RRTO edge server — N concurrent clients over one GPU server.

Single-tenant RRTO (``core/offload.py``) gives one mobile client a private
simulated server.  An edge deployment is the opposite shape: one GPU box, many
clients, most of them running the *same* model.  This module composes the
shared pieces:

* :class:`RRTOEdgeServer` — the shared state: one simulated
  :class:`~repro.core.engine.OffloadServer` (kernel queue + GPU occupancy),
  one :class:`~repro.serving.replay_cache.ReplayCache` (fingerprint ->
  compiled replay executable), one
  :class:`~repro.core.netsim.ServerIngress` (clients contend for server
  ingress bandwidth), one :class:`ReplayBatcher`, and a shared
  :class:`~repro.core.engine.SimClock`.  Per-client state (mode, log, energy
  meter, device-memory namespace) lives in each
  :class:`~repro.core.offload.OffloadSession` / server-side
  :class:`~repro.core.engine.ClientContext`.

* :class:`ReplayBatcher` — cross-client batched replay.  Replay submissions
  for the same IOS fingerprint arriving within a batching window execute as
  one batched call on the shared GPU: the first submission flushes the
  round's preloaded group, pays the window wait plus one sub-linear batched
  execution (``ReplayProgram.batched_compute_seconds``), and every member
  completes at the group's finish time.  When the group's members share
  parameter *values* (the common edge deployment: one app binary on every
  device), the group executes as **one true ``jax.vmap``-compiled batched
  call** — a :class:`~repro.core.engine.BatchedReplayProgram` cached per
  (replay key, padded batch width) in the shared :class:`ReplayCache` —
  whose outputs are bitwise identical to the per-client execution loop;
  members with distinct parameters fall back to per-client functional
  execution under the same modeled batch timing.  Sharing is proven once,
  then aliased: each session uploads its own weights, so a co-tenant leaf
  found bitwise equal to the first member's is re-pointed to that buffer,
  and later rounds pass by identity with no device work (sound because
  arrays are immutable and parameters are never donated; see
  ``ReplayBatcher._shared_params``).  Batch widths pad to the
  next power of two (masked lanes replay lane 0 and are discarded), so a
  fingerprint compiles O(log N) batched executables instead of one per
  width.  Split-mode co-tenants batch too, at *segment* granularity: their
  server-resident segments group by (fingerprint, segment bounds) — clients
  on different device-side cuts of one shared IOS share the GPU slot for
  the segments their plans have in common (``submit_segment``, wired
  through ``RRTOClient.split_submit``).

Simulation contract: sessions share one clock, so ``run_round`` drives them
cooperatively — recording-phase clients serialize their RPC storms through
the shared server (contention is real and visible in the latency numbers),
and replay-phase clients batch.  Because a member's outputs must be available
synchronously inside its own ``infer()`` call, the harness *preloads* each
round's replay inputs into the batcher; the first submitter executes the
whole group functionally, and later members collect their precomputed
outputs.  A member that misses the window (submits after ``t_open +
window_s``) keeps its precomputed values but pays a solo GPU slot.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.costmodel import GTX_2080TI, DeviceSpec
from repro.core.engine import (
    BATCH_MARGINAL_COST,
    MODE_REPLAYING,
    OffloadServer,
    RRTOClient,
    SimClock,
)
from repro.core.netsim import FaultInjector, ServerIngress, get_network
from repro.core.offload import InferenceResult, OffloadableModel, OffloadSession
from repro.obs import MetricsRegistry, RegistryBackedStats, Tracer, host_span
from repro.partition.segments import PLACE_SERVER
from repro.serving.admission import AdmissionController, drr_select
from repro.serving.replay_cache import ReplayCache


def _inputs_digest(arrs: Sequence[np.ndarray]) -> Tuple:
    """Cheap structural signature (shape/dtype per tensor) — the batching
    window compares every submission against its preload, so the full-array
    compare must be short-circuited for mixed-shape co-tenants."""
    return tuple((a.shape, str(a.dtype)) for a in arrs)


def _inputs_equal(
    a: Sequence[np.ndarray],
    b: Sequence[np.ndarray],
    digest: Optional[Tuple] = None,
) -> bool:
    """Element-wise equality with a structural short-circuit.  ``digest`` is
    the bound replay's cached wire-input signature: when supplied, both sides
    are checked against it in place instead of rebuilding two signature
    tuples per round (the wire structure is a program property, stable for
    the life of the binding)."""
    if len(a) != len(b):
        return False
    a = [np.asarray(x) for x in a]
    b = [np.asarray(y) for y in b]
    if digest is not None:
        if len(a) != len(digest):
            return False
        for x, y, (shape, dtype) in zip(a, b, digest):
            if (
                x.shape != shape
                or y.shape != shape
                or str(x.dtype) != dtype
                or str(y.dtype) != dtype
            ):
                return False
    elif _inputs_digest(a) != _inputs_digest(b):
        return False
    return all(np.array_equal(x, y) for x, y in zip(a, b))


def _padded_width(n: int) -> int:
    """Round a batch width up to the next power of two (min 2): co-tenant
    groups of width 2..N share O(log N) compiled batched executables instead
    of one per width; padded lanes replay lane 0 and are discarded."""
    return max(2, 1 << (int(n) - 1).bit_length())


@dataclasses.dataclass
class _BatchGroup:
    done_at: float                   # batched execution completion time
    # client_id -> preloaded inputs (values execute lazily at submit time, so
    # a member that never submits — e.g. a DAM fallback mid-walk — leaves no
    # speculative writes in its device-memory namespace)
    pending: Dict[str, List[np.ndarray]]
    # true-vmap results per member (None: per-client functional execution);
    # outputs/state are installed into a member's namespace only at claim
    # time, so an unclaimed member's env and carried state stay untouched
    outs: Optional[Dict[str, List[np.ndarray]]] = None
    carried: Optional[Dict[str, List[Any]]] = None
    # shared wire-input digest of the group's program (all members run the
    # same program, so one cached signature verifies every claim)
    digest: Optional[Tuple] = None

    def claim(self, client_id: str, inputs: Sequence[np.ndarray]) -> bool:
        preloaded = self.pending.pop(client_id, None)
        return preloaded is not None and _inputs_equal(
            preloaded, inputs, digest=self.digest
        )


@dataclasses.dataclass
class _SegmentGroup:
    """One co-tenant server-segment batch: same IOS fingerprint, same server
    segment bounds, possibly *different* device-side cuts."""

    done_at: float
    remaining: set                   # client ids that may still claim a slot
    width: int


class BatcherStats(RegistryBackedStats):
    """Batch-formation counters, registry-backed (one fleet snapshot
    reports every replica's batching behaviour).  ``batch_sizes`` aliases
    the ``batch_width`` histogram's value list, so width percentiles show
    up in ``MetricsRegistry.snapshot()`` while the legacy ``.append`` /
    ``np.mean`` call sites keep working."""

    _fields = (
        ("batches_executed", 0),
        ("batched_replays", 0),      # submissions served from a batch
        ("solo_replays", 0),         # submissions that fell back to solo
        ("vmap_batches", 0),         # groups executed as one true vmap call
        ("param_compares", 0),       # co-tenant weight leaves compared on device
        ("param_aliases", 0),        # co-tenant leaves re-pointed after a proof
        ("vmap_compiles", 0),        # batched executables built (not cached)
        ("vmap_compiles_avoided", 0),  # widths served by a padded executable
        ("vmap_padded_lanes", 0),    # masked lanes executed across batches
        ("digest_cache_hits", 0),
        ("seg_batches", 0),          # co-tenant server-segment batched execs
        ("seg_batched", 0),          # segment submissions served from a batch
        ("seg_solo", 0),             # segment submissions that ran solo
    )

    @property
    def batch_sizes(self) -> List[int]:
        return self.registry.histogram("batch_width").values


class ReplayBatcher:
    """Groups same-fingerprint replay submissions into batched executions."""

    def __init__(
        self,
        server: OffloadServer,
        *,
        window_s: float = 2e-3,
        tracer: Optional[Tracer] = None,
        track: str = "edge",
        metrics: Optional[MetricsRegistry] = None,
    ):
        self.server = server
        self.window_s = window_s
        self.tracer = tracer
        self.track = track
        # escape hatch (benchmarks/tests): False forces the per-client
        # functional execution loop even for shared-param groups, so the
        # vmap-batched path can be diffed bitwise against it
        self.enable_vmap = True
        # fingerprint -> list of (client, wire inputs) preloaded for the round
        self._pending: Dict[str, List[Tuple[RRTOClient, List[np.ndarray]]]] = {}
        self._groups: Dict[str, _BatchGroup] = {}
        # (fingerprint, seg.start, seg.end) -> client ids expected this round
        self._seg_pending: Dict[Tuple[str, int, int], List[str]] = {}
        self._seg_groups: Dict[Tuple[str, int, int], _SegmentGroup] = {}
        # client id -> (bound replay, wire-input digest): the structural
        # signature is a program property, computed once per binding instead
        # of twice per round (hot path under many co-tenants)
        self._digest_cache: Dict[str, Tuple[Any, Tuple]] = {}
        # padded-vmap bookkeeping: raw widths served per padded cache key
        self._vmap_widths_served: Dict[str, set] = {}
        # cache claims held for the current round: derived-entry use pins the
        # base program so size-aware eviction cannot purge it (and its
        # derived executables) while the round is still executing/claiming
        self._round_claims: List[str] = []
        # overload protection (bound by RRTOEdgeServer when it carries an
        # AdmissionController): supplies SLO priority/weight for EDF ordering
        # and DRR slot selection.  None = formation order is submission order,
        # bitwise the pre-admission behaviour.
        self.admission: Optional[AdmissionController] = None
        # max batch slots per round per fingerprint; None = unbounded.  Only
        # enforced with an admission controller attached (weights come from
        # its SLO classes); the deficit counters persist across rounds, so a
        # tenant short-changed one round is made whole in the next.
        self.round_capacity: Optional[int] = None
        self._drr_deficits: Dict[str, float] = {}
        self.depth_gauge = (
            metrics.gauge("pending_depth") if metrics is not None else None
        )
        # every legacy counter attribute (``batcher.vmap_batches`` etc.)
        # delegates to this registry-backed object — see the property loop
        # below the class definition
        self.stats = BatcherStats(registry=metrics)

    def begin_round(
        self,
        entries: Dict[str, List[Tuple[RRTOClient, List[np.ndarray]]]],
        seg_entries: Optional[Dict[Tuple[str, int, int], List[str]]] = None,
    ) -> None:
        """Preload one driving round: for each fingerprint, the replay-phase
        clients that will submit this round and their wire inputs; for each
        (fingerprint, server-segment) key, the split-mode clients whose plans
        execute that segment on the GPU this round.

        With an admission controller attached (or any member carrying a
        deadline), each fingerprint's members are ordered
        earliest-deadline-first and — when ``round_capacity`` bounds the
        round — selected deficit-round-robin across tenants, so one chatty
        tenant cannot monopolize the batch slots.  Members not selected keep
        no preload and replay solo.  Without deadlines or a controller the
        formation order is the submission order, bitwise identical to the
        pre-admission batcher."""
        self._pending = {
            fp: self._order_members(list(members))
            for fp, members in entries.items()
        }
        self._groups = {}
        self._seg_pending = (
            {k: list(v) for k, v in seg_entries.items()}
            if seg_entries
            else {}
        )
        self._seg_groups = {}
        # pin the bases behind this round's *derived* executions (``fp|plan``
        # split groups, server-segment batches of segmented programs) for the
        # round's duration — eviction must not purge a base whose derived
        # executable an in-flight group is still claiming.  Plain whole-
        # program fingerprints are not claimed: their groups hold direct
        # references, and pinning every base would starve admission for
        # co-tenants locking new models mid-round.  ``end_round`` releases
        # the claims when the round completes (begin_round re-releases
        # defensively for drivers that never call it); vmap batches add
        # theirs at execution time.
        cache = self.server.replay_cache
        if cache is not None and hasattr(cache, "claim"):
            self.end_round()
            for key in self._pending:
                if "|" in key or "#" in key:
                    cache.claim(key)
                    self._round_claims.append(key)
            for key in self._seg_pending:
                cache.claim(f"{key[0]}|seg")
                self._round_claims.append(f"{key[0]}|seg")

    def end_round(self) -> None:
        """Release the current round's cache claims — the in-flight derived
        executables have all been claimed by their members, so the bases are
        fair eviction game again.  Idempotent."""
        cache = self.server.replay_cache
        if cache is not None and hasattr(cache, "release"):
            for key in self._round_claims:
                cache.release(key)
        self._round_claims = []

    def _order_members(
        self, members: List[Tuple[RRTOClient, List[np.ndarray]]]
    ) -> List[Tuple[RRTOClient, List[np.ndarray]]]:
        """EDF-order one fingerprint's round members (deadline, then SLO
        priority, then arrival order), then DRR-select down to
        ``round_capacity`` slots across tenants.  Pure pass-through when no
        member has a deadline and no controller is attached."""
        adm = self.admission
        if adm is None and not any(
            cl.deadline_t is not None for cl, _ in members
        ):
            return members
        if len(members) > 1:
            def edf_key(item):
                idx, (cl, _) = item
                deadline = (
                    cl.deadline_t if cl.deadline_t is not None else float("inf")
                )
                prio = adm.slo(cl.tenant).priority if adm is not None else 0
                return (deadline, -prio, idx)

            members = [
                m for _, m in sorted(enumerate(members), key=edf_key)
            ]
        if (
            adm is not None
            and self.round_capacity is not None
            and len(members) > self.round_capacity
        ):
            members = drr_select(
                members,
                self.round_capacity,
                lambda m: m[0].tenant,
                lambda tenant: adm.slo(tenant).weight,
                self._drr_deficits,
            )
        return members

    @property
    def pending_depth(self) -> int:
        """Preloaded-but-unclaimed submissions in the current round (whole-
        program members, split segments, and formed-group slots not yet
        collected) — the batcher's contribution to the edge backlog."""
        depth = sum(len(m) for m in self._pending.values())
        depth += sum(len(m) for m in self._seg_pending.values())
        depth += sum(len(g.pending) for g in self._groups.values())
        return depth

    def sample_depth(self, now: Optional[float] = None) -> int:
        """Sample the pending-round depth onto the obs gauge (and, with an
        admission controller driving overload runs, the trace counter)."""
        depth = self.pending_depth
        if self.depth_gauge is not None:
            self.depth_gauge.set(depth)
        if (
            self.tracer is not None
            and now is not None
            and self.admission is not None
        ):
            self.tracer.counter(
                f"{self.track}/batcher", "pending_depth", now, float(depth)
            )
        return depth

    def _wire_digest(self, client_id: str) -> Optional[Tuple]:
        """The cached wire-input shape/dtype digest of one client's bound
        replay (recomputed only when the binding changes)."""
        bound = self.server.context(client_id).replay
        if bound is None:
            return None
        ent = self._digest_cache.get(client_id)
        if ent is not None and ent[0] is bound:
            self.digest_cache_hits += 1
            return ent[1]
        avals = bound.program.wire_in_avals
        if any(a is None for a in avals):
            return None  # recorded payload was trimmed; fall back per round
        digest = tuple(
            (tuple(shape), str(np.dtype(dtype))) for shape, dtype in avals
        )
        self._digest_cache[client_id] = (bound, digest)
        return digest

    def make_submit(self, client: RRTOClient):
        """A bound submit hook for ``RRTOClient.replay_submit``."""

        def submit(inputs: List[np.ndarray], t: float, fresh_carried=None):
            return self.submit(
                client, inputs, t, fresh_carried=fresh_carried
            )

        return submit

    def make_split_submit(self, client: RRTOClient):
        """A bound server-segment hook for ``RRTOClient.split_submit``."""

        def submit(seg, solo_seconds: float, start: float) -> float:
            return self.submit_segment(client, seg, solo_seconds, start)

        return submit

    def submit_segment(
        self, client: RRTOClient, seg, solo_seconds: float, start: float
    ) -> float:
        """One split-mode client's server segment reaching the GPU.

        Co-tenants whose plans share this (fingerprint, segment-bounds) key —
        even when their *device-side* cuts differ — execute the segment as
        one batched GPU occupancy: the first submitter reserves the
        sub-linear batched slot for the whole preloaded group and every
        member completes at the group's finish time.  Functional execution
        stays per-client (each client's segment walk already produced its own
        bitwise-exact values); the batch is a shared-GPU scheduling win, the
        same modeling contract as ``batched_compute_seconds``."""
        fp = client.ios_fp
        key = (fp, seg.start, seg.end) if fp is not None else None
        group = self._seg_groups.get(key) if key is not None else None
        if group is None and key is not None:
            members = self._seg_pending.pop(key, None)
            if members and client.client_id in members:
                width = len(members)
                compute = solo_seconds * (
                    1.0 + BATCH_MARGINAL_COST * (width - 1)
                )
                begin = start + (self.window_s if width > 1 else 0.0)
                done = self.server.occupy(compute, begin)
                group = _SegmentGroup(
                    done_at=done, remaining=set(members), width=width
                )
                self._seg_groups[key] = group
                if width > 1:
                    self.seg_batches += 1
                if self.tracer is not None:
                    self.tracer.span(
                        f"{self.track}/batcher", "batch_round", begin, done,
                        fp=fp, width=width,
                        segment=f"{seg.start}:{seg.end}",
                    )
        if group is not None and client.client_id in group.remaining:
            group.remaining.discard(client.client_id)
            if group.width > 1:
                self.seg_batched += 1
            else:
                self.seg_solo += 1
            return max(group.done_at, start)
        # not preloaded (or already claimed): plain solo occupancy
        self.seg_solo += 1
        return self.server.occupy(solo_seconds, start)

    def submit(
        self,
        client: RRTOClient,
        inputs: List[np.ndarray],
        t: float,
        *,
        fresh_carried: Optional[Dict[int, np.ndarray]] = None,
    ) -> Tuple[List[Any], float]:
        fp = client.replay_key
        if fresh_carried:
            # the member is overriding its server-resident carried state
            # (fresh prefill); the preloaded batch ran without the override,
            # so this round must execute solo
            self.solo_replays += 1
            return self.server.run_replay(
                inputs, t, client.client_id, fresh_carried=fresh_carried
            )
        group = self._groups.get(fp) if fp is not None else None
        if group is None:
            group = self._execute_group(fp, t)
        if group is None:
            # nothing preloaded for this fingerprint: plain solo replay
            self.solo_replays += 1
            return self.server.run_replay(inputs, t, client.client_id)
        if not group.claim(client.client_id, inputs):
            self.solo_replays += 1
            return self.server.run_replay(inputs, t, client.client_id)
        # Preloaded members are concurrent by construction (the harness
        # declared them one round); the serialized shared-clock driving means
        # a later member's submit time can already exceed the group's finish,
        # in which case its wait is simply zero.
        if group.outs is not None:
            # true vmap batch: this member's slice was computed in the one
            # batched call — install it as if it had executed solo
            outs = group.outs[client.client_id]
            self.server.adopt_replay_results(
                client.client_id,
                inputs,
                outs,
                group.carried.get(client.client_id)
                if group.carried is not None
                else None,
            )
        else:
            outs = self.server.replay_values(inputs, client.client_id)
        self.batched_replays += 1
        return outs, max(group.done_at, t)

    # ------------------------------------------------------------------
    def _shared_params(
        self, members: List[Tuple[RRTOClient, List[np.ndarray]]]
    ) -> Optional[List[Any]]:
        """The members' shared parameter buffers, or None when any differ.

        Proof, then alias, then identity.  A co-tenant leaf that is the
        first member's buffer passes at once.  Any other leaf of the same
        shape and dtype is compared bitwise where the buffers live; when
        equal, the co-tenant's env entry is re-pointed to the first
        member's buffer, so from the next round on identity decides with no
        device work and no sync, and the duplicate copy is freed once
        nothing else holds it.  A proof holds while both objects stay in
        the env: a ``jax.Array`` is immutable, the replay executables
        donate only the carried state (argument 2, never the parameters),
        and a client that writes new weights replaces the entry with a new
        object, which fails identity and is compared afresh.  Leaves proven
        equal before a later leaf differs stay aliased, since they are
        equal."""
        first_ctx = self.server.context(members[0][0].client_id)
        first_bound = first_ctx.replay
        params = [first_ctx.env[a] for a in first_bound.param_addrs]
        compares = aliases = 0
        try:
            for cl, _ in members[1:]:
                ctx = self.server.context(cl.client_id)
                bound = ctx.replay
                if bound is None or bound.program is not first_bound.program:
                    return None
                for addr, mine in zip(bound.param_addrs, params):
                    other = ctx.env[addr]
                    if mine is other:
                        continue
                    if mine.shape != other.shape or mine.dtype != other.dtype:
                        return None
                    # compared where the buffers live: no host round trip
                    compares += 1
                    if not bool(jnp.array_equal(mine, other)):
                        return None
                    ctx.env[addr] = mine
                    aliases += 1
            return params
        finally:
            # one bump per call, not one per leaf
            self.param_compares += compares
            self.param_aliases += aliases

    def _run_vmap_batch(
        self,
        fp: str,
        members: List[Tuple[RRTOClient, List[np.ndarray]]],
        params_flat: List[Any],
    ) -> Optional[_BatchGroup]:
        """Execute the whole group as one ``jax.vmap``-compiled batched call;
        returns per-member outputs (and carried states) keyed by client id."""
        from repro.core.engine import BatchedReplayProgram

        program = self.server.context(members[0][0].client_id).replay.program
        if not members[0][1] and not program.is_stateful:
            return None  # no mapped axis to batch over
        width = len(members)
        # every bail-out below must happen BEFORE the padded-lane/compile
        # stats update: an aborted vmap batch falls back to the per-client
        # loop, where no padded lane ever executes and no width was served —
        # counting them would inflate the padding accounting
        states: List[List[Any]] = []
        if program.is_stateful:
            for cl, _ in members:
                st = self.server.context(cl.client_id).replay.carried_state
                if st is None:
                    return None
                states.append(st)
        # pad to the next power of two: one compiled executable serves every
        # group width in (padded/2, padded], so a fingerprint needs O(log N)
        # batched executables instead of one per width.  Padded lanes
        # replicate lane 0 (any valid data — their outputs are discarded,
        # and only the ``width`` real lanes are billed: the group's modeled
        # occupancy is ``batched_compute_seconds(device, width)``).
        padded = _padded_width(width)
        key = f"{fp}#vmap{padded}"
        cache = self.server.replay_cache
        batched: Optional[BatchedReplayProgram] = (
            cache.get(key) if cache is not None else None
        )
        if cache is not None and hasattr(cache, "claim"):
            # the batch executes this derived entry now: its base must not be
            # evicted (purging the derived executable with it) mid-round
            cache.claim(key)
            self._round_claims.append(key)
        compiled_now = batched is None or batched.base is not program
        if compiled_now:
            batched = program.build_batched(padded)
            self.vmap_compiles += 1
            if cache is not None:
                cache.put(key, batched)
        served = self._vmap_widths_served.setdefault(key, set())
        if not compiled_now and width not in served:
            # an exact-width scheme would have compiled a fresh executable
            # for this group width; the padded one absorbed it
            self.vmap_compiles_avoided += 1
        served.add(width)
        self.vmap_padded_lanes += padded - width
        pad = padded - width
        with host_span("rrto.batch_stack"):
            stacked_inputs = [
                np.stack(
                    [np.asarray(m[1][k]) for m in members]
                    + [np.asarray(members[0][1][k])] * pad
                )
                for k in range(len(members[0][1]))
            ]
            stacked_state = [
                jnp.stack([st[k] for st in states] + [states[0][k]] * pad)
                for k in range(len(states[0]))
            ] if program.is_stateful else None
        if program.is_stateful:
            with host_span("rrto.launch"):
                wire_outs, new_carried = batched.fn(
                    params_flat, stacked_inputs, stacked_state
                )
            with host_span("rrto.batch_unstack"):
                outs = {
                    cl.client_id: [np.asarray(o[b]) for o in wire_outs]
                    for b, (cl, _) in enumerate(members)
                }
                carried = {
                    cl.client_id: [c[b] for c in new_carried]
                    for b, (cl, _) in enumerate(members)
                }
            return _BatchGroup(0.0, {}, outs=outs, carried=carried)
        with host_span("rrto.launch"):
            raw = batched.fn(params_flat, stacked_inputs)
        with host_span("rrto.batch_unstack"):
            outs = {
                cl.client_id: [np.asarray(o[b]) for o in raw]
                for b, (cl, _) in enumerate(members)
            }
        return _BatchGroup(0.0, {}, outs=outs)

    def _execute_group(self, fp: Optional[str], t: float) -> Optional[_BatchGroup]:
        members = self._pending.pop(fp, None) if fp is not None else None
        if not members:
            return None
        with host_span("rrto.batch", width=len(members)):
            first = members[0][0]
            program = self.server.context(first.client_id).replay.program
            # the batch slot count is the admitted membership; a member that ends
            # up falling back mid-walk still occupied its scheduled slot
            batch = len(members)
            group: Optional[_BatchGroup] = None
            if batch > 1 and self.server.execute and self.enable_vmap:
                with host_span("rrto.batch_params_check"):
                    params_flat = self._shared_params(members)
                if params_flat is not None:
                    group = self._run_vmap_batch(fp, members, params_flat)
                    if group is not None:
                        self.vmap_batches += 1
            if group is None:
                group = _BatchGroup(done_at=0.0, pending={})
            compute = program.batched_compute_seconds(self.server.device, batch)
            # a lone submitter flushes immediately; a real group waits out the
            # batching window for its co-tenants before the one-shot execution
            start = t + (self.window_s if batch > 1 else 0.0)
            group.done_at = self.server.occupy(compute, start)
            group.pending = {cl.client_id: wire for cl, wire in members}
            group.digest = self._wire_digest(first.client_id)
            self._groups[fp] = group
            self.batches_executed += 1
            self.batch_sizes.append(batch)
            if self.tracer is not None:
                self.tracer.span(
                    f"{self.track}/batcher", "batch_round", start, group.done_at,
                    fp=fp, width=batch, vmap=group.outs is not None,
                )
            return group


def _delegate_stat(name: str) -> property:
    return property(
        lambda self: getattr(self.stats, name),
        lambda self, v: setattr(self.stats, name, v),
    )


# back-compat attribute surface: ``batcher.vmap_batches`` and friends keep
# reading/writing, but the numbers live in the registry-backed stats object
for _stat_name, _ in BatcherStats._fields:
    setattr(ReplayBatcher, _stat_name, _delegate_stat(_stat_name))
ReplayBatcher.batch_sizes = property(lambda self: self.stats.batch_sizes)


class RRTOEdgeServer:
    """Shared edge-server state + the cooperative multi-client driver."""

    def __init__(
        self,
        *,
        server_device: DeviceSpec = GTX_2080TI,
        execute: bool = True,
        cache_capacity: int = 8,
        cache_capacity_bytes: Optional[float] = None,
        batch_window_s: float = 2e-3,
        environment: str = "indoor",
        ingress: Optional[ServerIngress] = None,
        clock: Optional[SimClock] = None,
        name: str = "edge",
        tracer: Optional[Tracer] = None,
        metrics: Optional[MetricsRegistry] = None,
        fault: Optional["FaultInjector"] = None,
        admission: Optional[AdmissionController] = None,
        verify: bool = False,
        jax_device: Optional[Any] = None,
    ):
        self.clock = clock or SimClock()
        self.name = name
        self.tracer = tracer
        self.fault = fault
        self.verify = verify
        # the root (or fleet-scoped) registry behind every counter on this
        # box: cache.*, batcher.*, client.<id>.* all land under it
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.cache = ReplayCache(
            cache_capacity, cache_capacity_bytes,
            metrics=self.metrics.scope("cache"),
        )
        self.server = OffloadServer(
            server_device, execute=execute, replay_cache=self.cache,
            name=name, tracer=tracer, verify=verify, jax_device=jax_device,
        )
        self.ingress = ingress or ServerIngress()
        if tracer is not None:
            self.ingress.tracer = tracer
            self.ingress.track = f"{name}/ingress"
        if fault is not None:
            self.ingress.fault = fault
        self.batcher = ReplayBatcher(
            self.server, window_s=batch_window_s,
            tracer=tracer, track=name,
            metrics=self.metrics.scope("batcher"),
        )
        # overload protection: None (the default) leaves every path bitwise
        # pre-admission, the FaultInjector discipline
        self.admission = admission
        if admission is not None:
            admission.bind(server=self.server, ingress=self.ingress)
            if admission.tracer is None:
                admission.tracer = tracer
            self.batcher.admission = admission
        self.environment = environment
        self.sessions: Dict[str, OffloadSession] = {}
        # fleet bookkeeping: sessions migrated onto / off this box
        self.sessions_adopted = 0
        self.sessions_migrated_out = 0

    def connect(
        self,
        model: OffloadableModel,
        *,
        client_id: Optional[str] = None,
        seed: Optional[int] = None,
        min_repeats: int = 3,
        environment: Optional[str] = None,
        tenant: str = "default",
        **session_kwargs: Any,
    ) -> OffloadSession:
        """Attach one mobile client running ``model`` to this edge server.

        Each client gets its own wireless link (seeded per client) tied to the
        shared server ingress, its own energy meter, and a server-side
        device-memory namespace keyed by ``client_id``.  ``environment``
        overrides the server default per client — an indoor and an outdoor
        client can share the edge box (and, with a ``partition`` config, plan
        different cuts of the same IOS)."""
        cid = client_id if client_id is not None else f"c{len(self.sessions)}"
        if cid in self.sessions:
            raise ValueError(f"client id {cid!r} already connected")
        network = get_network(
            environment if environment is not None else self.environment,
            seed if seed is not None else len(self.sessions),
        )
        network.ingress = self.ingress
        if self.fault is not None:
            session_kwargs.setdefault("fault", self.fault)
        if self.admission is not None:
            session_kwargs.setdefault("admission", self.admission)
        session_kwargs.setdefault("tenant", tenant)
        session_kwargs.setdefault("verify", self.verify)
        sess = OffloadSession(
            model,
            "rrto",
            network=network,
            server=self.server,
            clock=self.clock,
            client_id=cid,
            min_repeats=min_repeats,
            tracer=self.tracer,
            trace_track=f"{self.name}/client/{cid}",
            metrics=self.metrics.scope(f"client.{cid}"),
            **session_kwargs,
        )
        sess.client.replay_submit = self.batcher.make_submit(sess.client)
        sess.client.split_submit = self.batcher.make_split_submit(sess.client)
        self.sessions[cid] = sess
        self.ingress.active_clients = len(self.sessions)
        return sess

    # ------------------------------------------------------------------
    def run_round(
        self, inputs_by_client: Dict[str, Tuple[Any, ...]]
    ) -> Dict[str, InferenceResult]:
        """Drive one inference per listed client, batching replays.

        Replay-phase clients' wire inputs are preloaded into the batcher so
        same-fingerprint submissions within the batching window execute as one
        batched call; recording-phase clients run their per-operator RPC
        storms serialized through the shared server and ingress."""
        self.ingress.active_clients = len(inputs_by_client)
        if self.admission is not None:
            # stamp each member's absolute deadline at round-formation time
            # so the batcher's EDF ordering sees it before anyone submits
            for cid in inputs_by_client:
                self.sessions[cid].client.deadline_t = (
                    self.admission.deadline_for(cid, self.clock.t)
                )
        with host_span("rrto.round_prepare"):
            entries: Dict[str, List[Tuple[RRTOClient, List[np.ndarray]]]] = {}
            seg_entries: Dict[Tuple[str, int, int], List[str]] = {}
            for cid, inputs in inputs_by_client.items():
                sess = self.sessions[cid]
                cl = sess.client
                # full-server replays batch as whole programs (key = the full
                # replay identity); split-plan clients run their own segmented
                # schedule, but their *server-resident* segments still batch —
                # keyed by (fingerprint, segment bounds), so co-tenants on
                # different device-side cuts of one shared IOS share the GPU slot
                if cl.mode != MODE_REPLAYING or cl.replay_key is None:
                    continue
                if cl.split_plan is None:
                    entries.setdefault(cl.replay_key, []).append(
                        (cl, sess.replay_wire_inputs(inputs))
                    )
                else:
                    for seg in cl.split_plan.segments:
                        if seg.placement == PLACE_SERVER:
                            seg_entries.setdefault(
                                (cl.ios_fp, seg.start, seg.end), []
                            ).append(cid)
            self.batcher.begin_round(entries, seg_entries)
        self.batcher.sample_depth(self.clock.t)
        if self.admission is not None:
            # refresh the ingress queue-depth gauge on the sim clock
            self.admission.queue_depth(self.clock.t)
        try:
            return {
                cid: self.sessions[cid].infer(*inputs)
                for cid, inputs in inputs_by_client.items()
            }
        finally:
            # the round is over: its claims must not outlive it, or the
            # claimed bases would stay pinned through every idle gap
            self.batcher.end_round()

    # ------------------------------------------------------------------
    def adopt_session(self, sess: OffloadSession) -> None:
        """Attach an existing session migrated from another edge server.

        The client re-associates with this box: the server handle, the
        batcher submit hooks and the ingress binding move; client-side state
        (mode, locked IOS, recorded calls, energy meter) rides along
        untouched.  The server-side context (device-memory namespace, bound
        replay, carried state) does NOT move here — the fleet layer
        transfers it explicitly (see ``repro.serving.fleet.EdgeFleet
        .migrate``).  Both edges must share one ``SimClock``: a migrated
        session keeps its clock, and a disagreeing server clock would jump
        simulated time."""
        cid = sess.client_id
        if cid in self.sessions:
            raise ValueError(f"client id {cid!r} already connected")
        if sess.clock is not self.clock:
            raise ValueError(
                "session migration requires edge servers sharing one SimClock"
            )
        if sess.execute != self.server.execute:
            raise ValueError(
                f"session execute={sess.execute} conflicts with this "
                f"server's execute={self.server.execute}"
            )
        sess.server = self.server
        sess.client.server = self.server
        sess.network.ingress = self.ingress
        if self.fault is not None:
            sess.network.fault = self.fault
        sess.client.replay_submit = self.batcher.make_submit(sess.client)
        sess.client.split_submit = self.batcher.make_split_submit(sess.client)
        self.sessions[cid] = sess
        self.ingress.active_clients = len(self.sessions)
        self.sessions_adopted += 1

    def disconnect(self, client_id: str) -> OffloadSession:
        """Detach one client (the source half of a migration).  The
        server-side context is left in place — the fleet layer reads it for
        the state transfer and drops it once the destination adopted."""
        sess = self.sessions.pop(client_id)
        self.ingress.active_clients = max(1, len(self.sessions))
        self.sessions_migrated_out += 1
        return sess

    # ------------------------------------------------------------------
    def save_cache(self, path: str) -> int:
        """Persist validated IOS fingerprints across server restarts."""
        return self.cache.save(path)

    def load_cache(self, path: str) -> int:
        """Adopt a previous incarnation's validated fingerprints: joining
        clients skip the ``min_repeats`` recording wait immediately."""
        return self.cache.load(path)

    # ------------------------------------------------------------------
    @property
    def compile_count(self) -> int:
        """Replay executables actually built (cache misses), not bindings."""
        return self.server.compile_count

    def recording_rpc_total(self) -> int:
        """Total RPCs issued by clients while in the recording phase."""
        total = 0
        for sess in self.sessions.values():
            for r in sess.history:
                if r.mode == "recording":
                    total += r.rpcs
        return total

    def summary(self) -> Dict[str, Any]:
        return dict(
            clients=len(self.sessions),
            sessions_adopted=self.sessions_adopted,
            sessions_migrated_out=self.sessions_migrated_out,
            cache=self.cache.stats.as_dict(),
            cached_programs=len(self.cache),
            compiles=self.compile_count,
            batches=self.batcher.batches_executed,
            batched_replays=self.batcher.batched_replays,
            solo_replays=self.batcher.solo_replays,
            vmap_batches=self.batcher.vmap_batches,
            vmap_compiles=self.batcher.vmap_compiles,
            vmap_compiles_avoided=self.batcher.vmap_compiles_avoided,
            vmap_padded_lanes=self.batcher.vmap_padded_lanes,
            digest_cache_hits=self.batcher.digest_cache_hits,
            seg_batches=self.batcher.seg_batches,
            seg_batched=self.batcher.seg_batched,
            seg_solo=self.batcher.seg_solo,
            mean_batch=(
                float(np.mean(self.batcher.batch_sizes))
                if self.batcher.batch_sizes
                else 0.0
            ),
            link_bytes=self.ingress.bytes_total,  # both directions
            gpu_busy_seconds=self.server.busy_seconds,
            queue_depth=self.ingress.queue_depth,
            pending_depth=self.batcher.pending_depth,
            admission=(
                self.admission.stats.as_dict()
                if self.admission is not None
                else None
            ),
        )
