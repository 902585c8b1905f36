"""End-to-end offload sessions — the five systems the paper compares.

    device_only   run the model on the mobile device (no offloading)
    nnto          native non-transparent offloading (model lives on the
                  server; app ships input, receives output — code modified)
    cricket       traditional transparent offloading: one RPC per call
    semi_rrto     cricket + client-side caching of device-query RPCs (Fig. 11)
    rrto          full record/replay with Operator Sequence Search

Every system runs the *same* model function; transparent systems execute it
through the jaxpr interceptor (the app is unmodified — interception happens
below it), non-transparent systems call it directly (the "code modification").
Latency and energy come from the simulated clock/network/power models; the
*computed values* are real JAX executions and must agree across systems
(asserted by tests).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.costmodel import (
    GTX_2080TI,
    JETSON_XAVIER_NX,
    DeviceSpec,
    jaxpr_bytes,
    jaxpr_flops,
)
from repro.core.energy import (
    STATE_COMM,
    STATE_CONTROL,
    STATE_INFERENCE,
    STATE_STANDBY,
    EnergyMeter,
    PowerModel,
)
from repro.core.engine import (
    MODE_REPLAYING,
    REPLAY_FUSION_FACTOR,
    REPLAY_KERNELS_PER_FUSION,
    OffloadServer,
    RRTOClient,
    SimClock,
)
from repro.core.intercept import FrameworkNoiseModel, JaxprInterceptor
from repro.core.flatten import flatten_closed_jaxpr
from repro.core.netsim import (
    FaultInjector,
    NetworkModel,
    RetryPolicy,
    get_network,
)
from repro.obs import MetricsRegistry, Tracer, host_span
from repro.partition.planner import PartitionConfig

SYSTEMS = ("device_only", "nnto", "cricket", "semi_rrto", "rrto")

# client-side application logic per inference (pre/post-processing)
CLIENT_CONTROL_S = 0.5e-3


@dataclasses.dataclass
class OffloadableModel:
    """A model as the offloading layer sees it: an apply function, parameters,
    example inputs, and an optional one-time setup graph (initialization
    inference variability, e.g. KAPAO's mesh-grid generation)."""

    name: str
    apply: Callable[..., Any]            # apply(params, [aux,] *inputs)
    params: Any                          # pytree
    example_inputs: Tuple[Any, ...]
    setup: Optional[Callable[..., Any]] = None   # setup(params, *inputs) -> aux
    # wire-format divisor for inference inputs (e.g. ~10x JPEG for camera
    # frames); parameters always travel raw
    input_wire_divisor: float = 1.0


@dataclasses.dataclass
class InferenceResult:
    outputs: List[Any]
    wall_seconds: float
    joules: float
    rpcs: int
    network_bytes: float
    server_busy_seconds: float
    mode: str


@dataclasses.dataclass
class StreamResult:
    """One inference of an open-loop stream (see ``infer_stream``)."""

    outputs: List[Any]
    arrival_t: float          # absolute simulated arrival time
    done_at: float            # absolute in-order completion time

    @property
    def latency_seconds(self) -> float:
        return self.done_at - self.arrival_t


class OffloadSession:
    """One application process using one offloading system.

    By default the session is single-tenant: it owns its clock and GPU
    server.  Pass a shared ``server`` (and usually a shared ``clock``) plus a
    unique ``client_id`` to multiplex several sessions over one simulated
    edge server — per-client state (mode, log, energy meter, device-memory
    namespace) stays separated while the kernel queue, replay cache and GPU
    occupancy are shared (see ``repro.serving.multitenant``)."""

    def __init__(
        self,
        model: OffloadableModel,
        system: str,
        *,
        environment: str = "indoor",
        network: Optional[NetworkModel] = None,
        client_device: DeviceSpec = JETSON_XAVIER_NX,
        server_device: DeviceSpec = GTX_2080TI,
        noise: Optional[FrameworkNoiseModel] = None,
        power: Optional[PowerModel] = None,
        min_repeats: int = 3,
        seed: int = 0,
        execute: Optional[bool] = None,
        server: Optional[OffloadServer] = None,
        clock: Optional[SimClock] = None,
        client_id: str = "c0",
        partition: Optional["PartitionConfig"] = None,
        tracer: Optional["Tracer"] = None,
        trace_track: Optional[str] = None,
        metrics: Optional["MetricsRegistry"] = None,
        fault: Optional[FaultInjector] = None,
        retry_policy: Optional[RetryPolicy] = None,
        admission: Optional[Any] = None,
        tenant: str = "default",
        verify: bool = False,
    ):
        if system not in SYSTEMS:
            raise ValueError(f"unknown system {system!r}; pick from {SYSTEMS}")
        if server is not None:
            # the realism level is a server property; a conflicting per-client
            # request would silently produce placeholder outputs
            if execute is not None and execute != server.execute:
                raise ValueError(
                    f"execute={execute} conflicts with the shared server's "
                    f"execute={server.execute}"
                )
            execute = server.execute
        elif execute is None:
            execute = True
        self.model = model
        self.system = system
        self.client_id = client_id
        self.network = network or get_network(environment, seed)
        self.client_device = client_device
        self.server_device = server_device
        self.clock = clock or SimClock()
        self.meter = EnergyMeter(power or PowerModel())
        self.execute = execute
        self.server = server or OffloadServer(
            server_device, execute=execute, verify=verify
        )
        self.history: List[InferenceResult] = []
        self._loaded = False
        self._infer_count = 0
        self.stage_marks: Dict[str, int] = {}
        # overload protection (serving.admission.AdmissionController); None =
        # no admission layer, every path below is bitwise pre-admission
        self.admission = admission
        self.tenant = tenant
        self._device_fallback_s: Optional[float] = None

        # ---- trace the model once (shapes only; concrete consts captured)
        params = model.params
        ex = tuple(np.asarray(x) for x in model.example_inputs)
        if model.setup is not None:
            aux = jax.tree.map(np.asarray, jax.jit(model.setup)(params, *ex))
            self._aux_leaves, self._aux_treedef = jax.tree.flatten(aux)
            self._setup_jaxpr = jax.make_jaxpr(
                lambda *i: jax.tree.leaves(model.setup(params, *i))
            )(*ex)
        else:
            self._aux_leaves, self._aux_treedef = [], None
            self._setup_jaxpr = None

        n_aux = len(self._aux_leaves)

        def _full_apply(args):
            if model.setup is not None:
                aux_l = list(args[:n_aux])
                ins = args[n_aux:]
                return model.apply(
                    params, jax.tree.unflatten(self._aux_treedef, aux_l), *ins
                )
            return model.apply(params, *args)

        self._full_apply = _full_apply
        self._steady_jaxpr = flatten_closed_jaxpr(
            jax.make_jaxpr(lambda *a: _full_apply(a))(*self._aux_leaves, *ex)
        )
        if self._setup_jaxpr is not None:
            self._setup_jaxpr = flatten_closed_jaxpr(self._setup_jaxpr)

        self._steady_flops = jaxpr_flops(self._steady_jaxpr)
        self._steady_bytes = jaxpr_bytes(self._steady_jaxpr)
        self._n_kernels = len(self._steady_jaxpr.eqns)

        if system in ("cricket", "semi_rrto", "rrto"):
            variant = "transparent" if system == "cricket" else system
            self.client = RRTOClient(
                self.server,
                self.network,
                self.clock,
                self.meter,
                variant=variant,
                min_repeats=min_repeats,
                client_id=client_id,
                client_device=client_device,
                partition=partition if system == "rrto" else None,
                input_wire_divisor=model.input_wire_divisor,
                tracer=tracer,
                trace_track=trace_track,
                metrics=metrics,
                fault=fault,
                retry_policy=retry_policy,
                verify=verify,
            )
            self.interceptor = JaxprInterceptor(
                self.client,
                noise or FrameworkNoiseModel(),
                input_wire_divisor=model.input_wire_divisor,
            )
            self.client.tenant = tenant
            if admission is not None:
                admission.register(client_id, tenant)
            if fault is not None:
                self.network.fault = fault
            # built lazily on the first outage fallback; fault-free sessions
            # never pay the extra jit
            self._direct_fn = None
        else:
            self.client = None
            self.interceptor = None
            self._direct_fn = jax.jit(self._full_apply)
        self._aux_addrs: Optional[Dict[int, int]] = None

    # ------------------------------------------------------------------
    @staticmethod
    def _const_key(arr: np.ndarray) -> Tuple:
        import hashlib

        arr = np.asarray(arr)
        return (arr.shape, str(arr.dtype), hashlib.md5(arr.tobytes()).hexdigest())

    def load(self) -> None:
        """Model-load phase: parameters travel to where they execute."""
        if self._loaded:
            return
        if self.system == "device_only":
            # local disk -> device memory; negligible for the comparison
            self.meter.add(STATE_CONTROL, 0.1)
            self.clock.advance(0.1)
        elif self.system == "nnto":
            # the server hosts the model; nothing crosses the radio
            self.meter.add(STATE_CONTROL, 0.05)
            self.clock.advance(0.05)
        else:
            # upload every traced constant (the model parameters as captured
            # by the jaxprs), deduplicated by content
            registry: Dict[Tuple, int] = {}
            unique: List[np.ndarray] = []
            keys: List[Tuple] = []
            jaxprs = [self._steady_jaxpr]
            if self._setup_jaxpr is not None:
                jaxprs.insert(0, self._setup_jaxpr)
            const_keys = [
                [self._const_key(c) for c in cj.consts] for cj in jaxprs
            ]
            for cj, cks in zip(jaxprs, const_keys):
                for c, k in zip(cj.consts, cks):
                    if k not in registry:
                        registry[k] = -1
                        unique.append(np.asarray(c))
                        keys.append(k)
            addrs = self.interceptor.upload_params(unique)
            for k, a in zip(keys, addrs):
                registry[k] = a
            # each jaxpr's constvar addresses, resolved once here: hashing
            # every weight again on each intercepted inference would cost a
            # full pass over the model per token
            self._param_addrs = {
                id(cj): [registry[k] for k in cks]
                for cj, cks in zip(jaxprs, const_keys)
            }
        self.stage_marks["after_load"] = (
            len(self.client.logs) if self.client else 0
        )
        self._loaded = True

    # ------------------------------------------------------------------
    def _param_addrs_for(self, closed_jaxpr) -> List[int]:
        return self._param_addrs[id(closed_jaxpr)]

    def _steady_invars(self, inputs: Sequence[Any]):
        """One steady inference's invar values (in order) + resident map
        (invar index -> device address).  The single source for both the
        interceptor walk and the batcher's wire-input preview."""
        values = list(self._aux_leaves) + [np.asarray(x) for x in inputs]
        return values, dict(self._aux_addrs or {})

    def _run_intercepted(self, inputs: Sequence[np.ndarray]) -> List[Any]:
        if self.model.setup is not None and self._aux_addrs is None:
            # initialization inference: extra setup graph, outputs cached
            _, aux_addrs = self.interceptor.run(
                self._setup_jaxpr,
                self._param_addrs_for(self._setup_jaxpr),
                inputs,
                download_outputs=False,
                keep_outputs=True,
            )
            self._aux_addrs = {i: a for i, a in enumerate(aux_addrs)}
        values, resident = self._steady_invars(inputs)
        with host_span("rrto.intercept", client=self.client_id):
            return self.interceptor.run(
                self._steady_jaxpr,
                self._param_addrs_for(self._steady_jaxpr),
                values,
                resident_inputs=resident,
            )

    def replay_wire_inputs(self, inputs: Sequence[Any]) -> List[np.ndarray]:
        """The HtoD payloads one replay-phase inference of ``inputs`` ships,
        in wire order (non-resident invars only, mirroring the interceptor's
        upload loop; loop-carried inputs are server-resident state and never
        ship).  Used by the multi-tenant batcher to preload a round's inputs
        before clients submit."""
        values, resident = self._steady_invars(inputs)
        uploads = [
            np.asarray(v) for i, v in enumerate(values) if i not in resident
        ]
        carried = (
            self.client.carried_input_ordinals
            if self.client is not None
            else frozenset()
        )
        if not carried:
            return uploads
        return [v for i, v in enumerate(uploads) if i not in carried]

    def device_fallback_seconds(self) -> float:
        """Latency of one eager device-local inference — the degradation
        ladder's tier-2 cost estimate (must fit the tenant's deadline budget
        for a degraded response to be worth returning)."""
        if self._device_fallback_s is None:
            self._device_fallback_s = self.client_device.sequence_time(
                self._steady_flops,
                self._steady_bytes,
                num_kernels=self._n_kernels,
                fusion_factor=1.0,
            )
        return self._device_fallback_s

    def _admission_decision(self, deadline_s: Optional[float]):
        """Consult the admission controller for one arriving request and walk
        the degradation ladder's *decision* half: raise on shed, install the
        device-heavy plan on tier 1, and return the decision + the request's
        absolute deadline.  ``admission is None`` short-circuits to the
        bitwise pre-admission behaviour."""
        adm, cl = self.admission, self.client
        if adm is None or cl is None:
            return None, None
        t = self.clock.t
        decision = adm.decide(
            self.client_id,
            t,
            can_degrade_split=(
                cl.mode == MODE_REPLAYING and cl.replanner is not None
            ),
            can_degrade_device=not cl.stateful_replay,
            degraded_latency_s=self.device_fallback_seconds(),
        )
        if decision.action == "shed":
            raise adm.shed_error(self.client_id, decision)
        budget = (
            deadline_s if deadline_s is not None
            else adm.slo(adm.tenant_of(self.client_id)).deadline_s
        )
        deadline_t = t + budget
        cl.deadline_t = deadline_t
        if decision.action == "degrade_split":
            plan = cl.replanner.degrade(t)
            if plan is not None:
                cl._install_plan(plan)
        return decision, deadline_t

    def infer(self, *inputs, deadline_s: Optional[float] = None) -> InferenceResult:
        if not self._loaded:
            self.load()
        t0, e0 = self.clock.t, self.meter.snapshot()
        busy0 = self.server.busy_seconds
        rpcs0 = self.client.stats.rpcs if self.client else 0
        bytes0 = self.client.stats.network_bytes if self.client else 0.0
        inputs = tuple(np.asarray(x) for x in inputs)

        if self.system == "device_only":
            outputs = self._device_only(inputs)
            mode = "local"
        elif self.system == "nnto":
            outputs = self._nnto(inputs)
            mode = "offloaded"
        else:
            self.meter.add(STATE_CONTROL, CLIENT_CONTROL_S)
            self.clock.advance(CLIENT_CONTROL_S)
            cl = self.client
            decision, deadline_t = self._admission_decision(deadline_s)
            arrival_t = self.clock.t
            if decision is not None and decision.action == "degrade_device":
                mode = "degraded_device"
                outputs = self._device_fallback(inputs)
            elif cl.fault is not None and cl.fault.in_outage(self.clock.t):
                mode, outputs = self._infer_during_outage(inputs)
            else:
                if cl.outage_active:
                    cl.outage_active = False
                    if cl.tracer is not None:
                        cl.tracer.instant(
                            cl.trace_track, "link_healed", self.clock.t
                        )
                mode = cl.mode
                outputs = self._run_intercepted(inputs)
                if decision is not None and decision.action == "degrade_split":
                    mode = "degraded_split"
            if decision is not None:
                if decision.action == "admit":
                    self.admission.note_admitted(arrival_t, self.clock.t)
                self.admission.note_completion(
                    arrival_t, self.clock.t, deadline_t
                )
                cl.deadline_t = None
        self._infer_count += 1
        if self._infer_count == 1:
            self.stage_marks["after_first_inference"] = (
                len(self.client.logs) if self.client else 0
            )

        res = InferenceResult(
            outputs=outputs,
            wall_seconds=self.clock.t - t0,
            joules=self.meter.since(e0).joules,
            rpcs=(self.client.stats.rpcs - rpcs0) if self.client else 0,
            network_bytes=(
                (self.client.stats.network_bytes - bytes0) if self.client else 0.0
            ),
            server_busy_seconds=self.server.busy_seconds - busy0,
            mode=mode,
        )
        self.history.append(res)
        return res

    # ------------------------------------------------------------------
    def infer_stream(
        self,
        inputs_seq: Sequence[Tuple[Any, ...]],
        *,
        arrivals: Optional[Any] = None,
        deadlines: Optional[Any] = None,
    ) -> List["StreamResult"]:
        """Open-loop streaming inference: submit every element of
        ``inputs_seq`` at its arrival offset (seconds from now; default 0 —
        a saturated back-to-back stream) without waiting for earlier
        completions.

        On a replay-locked split session with
        ``PartitionConfig(pipelined=True)``, submissions double-buffer the
        device/server cut through the client's
        :class:`~repro.core.engine.PipelinedSegmentedReplay`: while the
        server runs inference *i*'s server segments, the device computes
        inference *i+1*'s device segments — steady-state per-inference
        latency is bottleneck-bound instead of sum-bound.  Results are
        delivered in order, bitwise identical to sequential split replay.
        Any other state (still recording, full-server plan, pipelining off)
        falls back to closed-loop sequential ``infer()`` per arrival, so a
        cold session can be streamed from the start and warms itself up.
        """
        if self.system != "rrto":
            raise ValueError("infer_stream requires an rrto session")
        if not self._loaded:
            self.load()
        inputs_seq = list(inputs_seq)
        n = len(inputs_seq)
        if n == 0:
            return []
        # arrivals/deadlines accept any iterable — a generator straight from
        # poisson_arrivals is fine; both are materialized here
        offs = [0.0] * n if arrivals is None else [float(a) for a in arrivals]
        if len(offs) != n:
            raise ValueError(
                f"{n} inputs but {len(offs)} arrival offsets"
            )
        for i, a in enumerate(offs):
            if a < 0:
                raise ValueError(
                    f"arrival offset at index {i} is negative ({a!r}); "
                    "offsets are seconds from now and must be >= 0"
                )
            if i > 0 and a < offs[i - 1]:
                raise ValueError(
                    f"arrival offsets must be non-decreasing: offset at "
                    f"index {i} ({a!r}) precedes offset at index {i - 1} "
                    f"({offs[i - 1]!r})"
                )
        deads = None
        if deadlines is not None:
            deads = [float(d) for d in deadlines]
            if len(deads) != n:
                raise ValueError(
                    f"{n} inputs but {len(deads)} deadline budgets"
                )
        base = self.clock.t
        # the pipelined executor is only valid while the session is replay-
        # locked (a DAM fallback reverts to recording and drops it)
        pipe = (
            self.client.pipelined_exec
            if self.client.mode == MODE_REPLAYING
            else None
        )
        if pipe is None:
            results = []
            for i, (off, ins) in enumerate(zip(offs, inputs_seq)):
                self.client._wait_until(base + off)
                r = self.infer(
                    *ins,
                    deadline_s=None if deads is None else deads[i],
                )
                results.append(
                    StreamResult(
                        outputs=r.outputs,
                        arrival_t=base + off,
                        done_at=self.clock.t,
                    )
                )
            return results
        env = self.server.context(self.client_id).env
        dev0, link0 = pipe.busy_snapshot()
        bytes0, cross0 = pipe.comm_bytes, pipe.crossings
        outputs = []
        for off, ins in zip(offs, inputs_seq):
            values, resident = self._steady_invars(ins)
            uploads = [v for i, v in enumerate(values) if i not in resident]
            wire, fresh = self.client.extract_fresh_carried(uploads)
            if fresh:
                # a fresh-state override ships once, like the sequential
                # path (billed on the aggregate stream counters; its bytes
                # are not modeled in the pipeline chain's steady state)
                self.client._account_network(
                    1, float(sum(a.nbytes for a in fresh.values()))
                )
            wire_outs = pipe.submit(
                wire, env, base + off, fresh_carried=fresh
            )
            # carried ordinals are answered with the stable handle, so a
            # StreamResult's outputs match sequential infer()'s arity
            outputs.append(self.client.expand_stream_outputs(wire_outs))
        dones = pipe.flush()
        results = [
            StreamResult(outputs=o, arrival_t=base + off, done_at=done)
            for o, off, done in zip(outputs, offs, dones)
        ]
        if deads is not None and self.admission is not None:
            # pipelined submissions bypass per-call infer(); score deadlines
            # post-hoc against the in-order completion times
            for r, d in zip(results, deads):
                self.admission.note_completion(
                    r.arrival_t, r.done_at, r.arrival_t + d
                )
        # completions are in-order, so the last one closes the window
        wall = max(0.0, results[-1].done_at - base)
        dev1, link1 = pipe.busy_snapshot()
        dev_busy = dev1 - dev0
        link_busy = link1 - link0
        # phase-integrated billing sums exactly to the wall time: radio time
        # overlapped with device compute sits inside the inference draw
        # (same convention as Schedule.radio_only_seconds)
        comm = min(link_busy, max(0.0, wall - dev_busy))
        self.meter.add(STATE_INFERENCE, dev_busy)
        self.meter.add(STATE_COMM, comm)
        self.meter.add(STATE_STANDBY, max(0.0, wall - dev_busy - comm))
        self.clock.advance(wall)
        self.client._account_network(
            pipe.crossings - cross0, pipe.comm_bytes - bytes0
        )
        self._infer_count += n
        return results

    # ------------------------------------------------------------------
    def _infer_during_outage(self, inputs) -> Tuple[str, List[Any]]:
        """One inference with the link declared down.  Three escape hatches,
        picked by what the session has to lose:

        * stateful replay — the carried state lives in donated server
          buffers and cannot be recomputed locally, so the client sits out
          the window (standby) and resumes through the at-most-once retry
          protocol once the link heals;
        * split replay with a replanner — adopt the outage plan (bandwidth
          collapsed to the simulated floor, which lands every segment on the
          device) and keep replaying through the normal split machinery;
        * anything else — run the whole model on the device: identical
          values at device-class latency, exactly the Intra-DP-style local
          path the offloader exists to beat.
        """
        cl = self.client
        if not cl.outage_active:
            # the probe that discovered the dead link: one timeout burned
            cl.outage_active = True
            dt = cl.retry_policy.base_timeout_s
            t0 = self.clock.t
            self.clock.advance(dt)
            self.meter.add(STATE_STANDBY, dt)
            if cl.tracer is not None:
                cl.tracer.instant(cl.trace_track, "outage_declared", t0)
        if cl.stateful_replay:
            end = cl.fault.outage_until(self.clock.t)
            cl.stats.outage_waits += 1
            if cl.tracer is not None:
                cl.tracer.span(
                    cl.trace_track, "outage_wait", self.clock.t, end
                )
            cl._wait_until(end)
            return cl.mode, self._run_intercepted(inputs)
        if cl.mode == MODE_REPLAYING and cl.replanner is not None:
            cl.stats.outage_fallbacks += 1
            if cl.tracer is not None:
                cl.tracer.instant(
                    cl.trace_track, "outage_fallback", self.clock.t,
                    path="split",
                )
            plan = cl.replanner.declare_outage(self.clock.t)
            if plan is not None:
                cl._install_plan(plan)
            return cl.mode, self._run_intercepted(inputs)
        cl.stats.outage_fallbacks += 1
        if cl.tracer is not None:
            cl.tracer.instant(
                cl.trace_track, "outage_fallback", self.clock.t,
                path="device",
            )
        return "outage_fallback", self._device_fallback(inputs)

    def _device_fallback(self, inputs) -> List[Any]:
        """Device-local execution for a declared outage.  Values are
        computed *eagerly per-op* — bitwise-identical to the replay
        executable, where a whole-graph ``jax.jit`` is not (fusion reorders
        float math) — and timed as the device's eager dispatch, same as
        :meth:`_device_only`."""
        args = list(self._aux_leaves) + list(inputs)
        if self.execute:
            outs = self._full_apply(tuple(args))
        else:
            outs = [
                np.zeros(v.aval.shape, v.aval.dtype)
                for v in self._steady_jaxpr.outvars
            ]
        if not isinstance(outs, (list, tuple)):
            outs = [outs]
        dt = self.client_device.sequence_time(
            self._steady_flops,
            self._steady_bytes,
            num_kernels=self._n_kernels,
            fusion_factor=1.0,
        )
        self.clock.advance(dt)
        self.meter.add(STATE_INFERENCE, dt)
        return [np.asarray(o) for o in outs]

    # ------------------------------------------------------------------
    def _device_only(self, inputs) -> List[Any]:
        args = list(self._aux_leaves) + list(inputs)
        if self.execute:
            if self._direct_fn is None:
                self._direct_fn = jax.jit(self._full_apply)
            outs = self._direct_fn(tuple(args))
        else:
            outs = [
                np.zeros(v.aval.shape, v.aval.dtype)
                for v in self._steady_jaxpr.outvars
            ]
        if not isinstance(outs, (list, tuple)):
            outs = [outs]
        dt = self.client_device.sequence_time(
            self._steady_flops,
            self._steady_bytes,
            num_kernels=self._n_kernels,
            fusion_factor=1.0,  # eager per-op dispatch on the device
        )
        self.clock.advance(dt)
        self.meter.add(STATE_INFERENCE, dt)
        return [np.asarray(o) for o in outs]

    def _nnto(self, inputs) -> List[Any]:
        args = list(self._aux_leaves) + list(inputs)
        if self.execute:
            outs = self._direct_fn(tuple(args))
        else:
            outs = [
                np.zeros(v.aval.shape, v.aval.dtype)
                for v in self._steady_jaxpr.outvars
            ]
        if not isinstance(outs, (list, tuple)):
            outs = [outs]
        outs = [np.asarray(o) for o in outs]
        in_bytes = float(
            sum(np.asarray(x).nbytes for x in inputs)
            / self.model.input_wire_divisor
        )
        out_bytes = float(sum(o.nbytes for o in outs))
        # app-level send -> server compute -> receive
        up = self.network._rtt_at(self.clock.t) + self.network.transfer_time(
            in_bytes, self.clock.t
        )
        self.clock.advance(up)
        self.meter.add(STATE_COMM, up)
        compute = self.server_device.sequence_time(
            self._steady_flops,
            self._steady_bytes,
            num_kernels=max(1, self._n_kernels // REPLAY_KERNELS_PER_FUSION),
            fusion_factor=REPLAY_FUSION_FACTOR,
        )
        self.server.busy_seconds += compute
        self.clock.advance(compute)
        self.meter.add(STATE_STANDBY, compute)
        down = self.network.transfer_time(out_bytes, self.clock.t)
        self.clock.advance(down)
        self.meter.add(STATE_COMM, down)
        self.meter.add(STATE_CONTROL, CLIENT_CONTROL_S)
        self.clock.advance(CLIENT_CONTROL_S)
        return outs

    # ------------------------------------------------------------------
    @property
    def gpu_utilization(self) -> float:
        """Server busy time / wall time — the Tab. IV proxy."""
        if self.clock.t <= 0:
            return 0.0
        return self.server.busy_seconds / self.clock.t
