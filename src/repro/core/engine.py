"""RRTO client/server engines — Alg. 3 (RRTO_on_Client) + Alg. 4
(RRTO_on_Server), driven by a simulated clock, network and energy meter.

The client is a call sink for :class:`JaxprInterceptor`.  In the recording
phase it behaves exactly like a traditional transparent offloader (one RPC per
intercepted call) while logging records and running the Operator Sequence
Search after every DtoH.  Once the inference operator sequence (IOS) is
identified, it switches to the replaying phase: intermediate operators are
answered locally from recorded results, only the HtoD input upload and the
DtoH output download cross the network, and the server executes the whole
sequence one-shot as a compiled XLA executable (replay-as-compilation — the
TPU-native analogue of the paper's server-side kernel replay).

Deviation from the IOS (a Dynamic Activation Model changing its op stream) is
detected record-by-record; the client ships the locally-answered prefix to the
server for catch-up execution and falls back to the recording phase
(Sec. III-B1 fallback).
"""
from __future__ import annotations

import dataclasses
import time as _time
import warnings
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.costmodel import JETSON_XAVIER_NX, DeviceSpec
from repro.core.energy import (
    STATE_COMM,
    STATE_CONTROL,
    STATE_INFERENCE,
    STATE_STANDBY,
    EnergyMeter,
)
from repro.core.intercept import InterceptedCall
from repro.core.netsim import (
    FaultInjector,
    NetworkModel,
    RetryPolicy,
    RpcTimeoutError,
)
from repro.core.opseq import (
    candidate_sequences,
    detect_loop_carried,
    ios_fingerprint,
    operator_sequence_search,
)
from repro.core.records import (
    CAT_D2H,
    CAT_H2D,
    FUNC_D2H,
    FUNC_H2D,
    InferenceSequence,
    OperatorRecord,
)
from repro.obs import MetricsRegistry, RegistryBackedStats, Tracer, host_span
from typing import TYPE_CHECKING

if TYPE_CHECKING:  # pragma: no cover — avoids core <-> partition import cycle
    from repro.partition.adaptive import AdaptiveReplanner
    from repro.partition.planner import PartitionConfig
    from repro.partition.segments import SplitPlan

MODE_RECORDING = "recording"
MODE_REPLAYING = "replaying"

DEFAULT_CLIENT = "c0"

# fused-executable advantage of replay-as-compilation over per-op dispatch
REPLAY_FUSION_FACTOR = 0.6
REPLAY_KERNELS_PER_FUSION = 6
# marginal cost of each extra client in a cross-client batched replay, as a
# fraction of the solo sequence time (sub-linear batching on the shared GPU)
BATCH_MARGINAL_COST = 0.25
PER_LOCAL_OP_S = 2e-7  # answering an intercepted call from the local cache
# crude compiled-executable footprint: per-fused-kernel machine code + the
# output staging buffers (used by the size-aware replay-cache eviction)
EXEC_BYTES_PER_KERNEL = 2048
# live H2D/D2H payloads are kept on this many trailing recorded calls (the
# loop-carried detection needs ~3 repeats of the IOS); older payloads are
# dropped so a client whose search never succeeds (dynamic-sequence apps,
# cricket mode) does not pin every tensor it ever transferred
PAYLOAD_RETENTION_CALLS = 4096
# ...but the trailing transfer calls keep their payloads regardless of log
# depth: a framework-noise-heavy app can emit thousands of records per
# inference, and a call-count horizon alone would cut the loop-carried
# detection window (~3 repeats of h2d/d2h payloads) out from under the
# search.  Bounded by transfer count, so the pinned-tensor set stays small.
PAYLOAD_RETENTION_TRANSFERS = 64
# at-most-once dedup: replies cached per (client, sequence number).  A client
# retries one in-flight step at a time and moves on once it has the reply, so
# a small window is ample; the bound keeps a long decode stream from pinning
# every step's outputs server-side.
DEDUP_WINDOW = 64


def _avals_nbytes(avals) -> int:
    total = 0
    for shape, dtype in avals:
        n = int(np.dtype(dtype).itemsize)
        for s in shape:
            n *= int(s)
        total += n
    return total


@dataclasses.dataclass
class StepLogEntry:
    """One completed stateful replay step, as the crash-recovery layer needs
    it: the wire inputs (and any fresh-state override) re-executed
    deterministically against a restored checkpoint reproduce the lost
    carried state token-for-token."""

    seq: int
    wire_inputs: List[np.ndarray]
    fresh_carried: Optional[Dict[int, np.ndarray]]


class SimClock:
    def __init__(self):
        self.t = 0.0

    def advance(self, dt: float) -> None:
        if dt < 0:
            raise ValueError(f"time went backwards: {dt}")
        self.t += dt


# ---------------------------------------------------------------------------
# server (Alg. 4)
# ---------------------------------------------------------------------------

def replay_address_plan(calls: List[InterceptedCall]) -> dict:
    """Walk a recorded IOS and extract its address plan: which buffers are
    replay inputs (HtoD), outputs (DtoH) and resident parameters (read before
    any in-window write).  The walk is a pure function of the calls, so the
    same walk over an isomorphic sequence recorded by *another* client yields
    that client's concrete addresses in the identical canonical order — which
    is what lets one compiled :class:`ReplayProgram` be rebound per client."""
    h2d_addrs: List[int] = []
    d2h_addrs: List[int] = []
    kernel_calls: List[InterceptedCall] = []
    written: set = set()
    param_addrs: List[int] = []
    total_flops = 0.0
    total_bytes = 0.0
    for c in calls:
        rec = c.record
        if rec.func == FUNC_H2D:
            h2d_addrs.append(c.out_addrs[0])
            written.add(c.out_addrs[0])
        elif rec.func == FUNC_D2H:
            d2h_addrs.append(c.in_operands[0][1])
        elif c.prim is not None:
            kernel_calls.append(c)
            for tag, v in c.in_operands:
                if tag == "a" and v not in written and v not in param_addrs:
                    param_addrs.append(v)
            written.update(c.out_addrs)
            total_flops += rec.flops
            total_bytes += rec.mem_bytes
    return dict(
        h2d_addrs=h2d_addrs,
        d2h_addrs=d2h_addrs,
        kernel_calls=kernel_calls,
        param_addrs=param_addrs,
        total_flops=total_flops,
        total_bytes=total_bytes,
    )


class ReplayProgram:
    """One compiled IOS replay executable (replay-as-compilation).

    The function is rebuilt purely from the recorded RPC payloads (primitive +
    params + operand addresses) — not from the original model definition —
    which is what makes this a *replayer*.  A program is content-addressed by
    its IOS fingerprint and shareable across clients: the executable takes
    ``(params_flat, inputs_flat)`` positionally, and each client supplies its
    own parameter buffers through a :class:`BoundReplay`.

    With ``carried_pairs`` (loop-carried tensors detected across IOS repeats,
    see :func:`repro.core.opseq.detect_loop_carried`) the program is
    *stateful*: a second executable ``step_fn(params_flat, wire_inputs,
    carried_inputs)`` is compiled with the carried buffers **donated**
    (``jax.jit(..., donate_argnums=...)``), so recurrent state (a KV cache)
    stays server-resident, is updated in place, and never crosses the
    network — the per-round replay is the model's intrinsic step cost."""

    def __init__(
        self,
        calls: List[InterceptedCall],
        *,
        execute: bool = True,
        carried_pairs: Tuple[Tuple[int, int], ...] = (),
        verify: bool = False,
    ):
        t0 = _time.perf_counter()
        if verify:
            # fail-fast static analysis before compiling anything: raises
            # ReplaySoundnessError listing every ERROR diagnostic
            from repro.analysis.verify import raise_on_errors, verify_calls

            raise_on_errors(verify_calls(calls, carried_pairs))
        plan = replay_address_plan(calls)
        param_addrs = plan["param_addrs"]
        h2d_addrs = plan["h2d_addrs"]
        d2h_addrs = plan["d2h_addrs"]
        kernel_calls = plan["kernel_calls"]

        self.carried_pairs = tuple(
            (int(i), int(j)) for i, j in carried_pairs
        )
        carried_in = {i for i, _ in self.carried_pairs}
        carried_out = {j for _, j in self.carried_pairs}
        # h2d/d2h ordinals that still travel over the wire, in wire order
        self.wire_in = [
            i for i in range(len(h2d_addrs)) if i not in carried_in
        ]
        self.wire_out = [
            j for j in range(len(d2h_addrs)) if j not in carried_out
        ]

        def run_kernels(env: Dict[int, Any]) -> None:
            for c in kernel_calls:
                invals = [
                    env[v] if tag == "a" else v for tag, v in c.in_operands
                ]
                outs = c.prim.bind(*invals, **c.params)
                if not c.prim.multiple_results:
                    outs = [outs]
                for addr, val in zip(c.out_addrs, outs):
                    env[addr] = val

        def replay(params_flat, inputs_flat):
            env: Dict[int, Any] = dict(zip(param_addrs, params_flat))
            for addr, v in zip(h2d_addrs, inputs_flat):
                env[addr] = v
            run_kernels(env)
            return [env[a] for a in d2h_addrs]

        def replay_step(params_flat, wire_inputs, carried_inputs):
            env: Dict[int, Any] = dict(zip(param_addrs, params_flat))
            for ordinal, v in zip(self.wire_in, wire_inputs):
                env[h2d_addrs[ordinal]] = v
            for (ordinal, _), v in zip(self.carried_pairs, carried_inputs):
                env[h2d_addrs[ordinal]] = v
            run_kernels(env)
            return (
                [env[d2h_addrs[j]] for j in self.wire_out],
                [env[d2h_addrs[j]] for _, j in self.carried_pairs],
            )

        # the un-jitted impls stay around so a cross-client batched
        # executable can be built from them with jax.vmap
        self._replay_impl = replay
        self._step_impl = replay_step
        self.fn = jax.jit(replay) if execute else None
        self.step_fn = (
            jax.jit(replay_step, donate_argnums=(2,))
            if execute and self.carried_pairs
            else None
        )
        self.d2h_avals = [
            c.out_avals[0] for c in calls if c.record.func == FUNC_D2H
        ]
        # H2D records carry no avals — the upload's structural signature
        # comes from the recorded live payload (present for the IOS calls a
        # program is ever built from; None-safe for exotic callers)
        self.h2d_avals = [
            (
                (tuple(np.asarray(c.h2d_value).shape),
                 np.asarray(c.h2d_value).dtype)
                if c.h2d_value is not None
                else None
            )
            for c in calls
            if c.record.func == FUNC_H2D
        ]
        self.n_kernels = len(kernel_calls)
        self.total_flops = plan["total_flops"]
        self.total_bytes = plan["total_bytes"]
        # the compiling client's own address plan, so its binding needn't
        # re-walk the calls it was just built from
        self.plan = plan
        self.compile_seconds = _time.perf_counter() - t0
        # size estimate for byte-aware cache eviction: machine code plus the
        # output staging buffers (carried state is donated, not staged twice)
        self.nbytes_estimate = (
            EXEC_BYTES_PER_KERNEL * max(1, self.n_kernels)
            + _avals_nbytes(self.d2h_avals)
        )

    @property
    def is_stateful(self) -> bool:
        return bool(self.carried_pairs)

    @property
    def wire_in_avals(self):
        """(shape, dtype) of each H2D payload that still crosses the wire,
        in wire order — the structural signature of one replay submission
        (the multi-tenant batcher caches its digest per bound replay)."""
        return [self.h2d_avals[i] for i in self.wire_in]

    def build_batched(self, width: int) -> "BatchedReplayProgram":
        """Compile a true ``jax.vmap``-batched executable over ``width``
        co-tenant replays of this program (shared parameter values)."""
        return BatchedReplayProgram(self, width)

    def compute_seconds(self, device: DeviceSpec) -> float:
        """Modeled one-shot execution time of the fused sequence."""
        return device.sequence_time(
            self.total_flops,
            self.total_bytes,
            num_kernels=max(1, self.n_kernels // REPLAY_KERNELS_PER_FUSION),
            fusion_factor=REPLAY_FUSION_FACTOR,
        )

    def batched_compute_seconds(self, device: DeviceSpec, batch: int) -> float:
        """Modeled time for one cross-client batched execution of ``batch``
        same-fingerprint replays (sub-linear in batch size)."""
        solo = self.compute_seconds(device)
        return solo * (1.0 + BATCH_MARGINAL_COST * (max(1, batch) - 1))


class BatchedReplayProgram:
    """A ``jax.vmap``-compiled cross-client batched replay executable.

    One per (fingerprint, batch width), derived from the solo
    :class:`ReplayProgram` and cached in the :class:`ReplayCache` under
    ``<fingerprint>#vmap<width>`` so co-tenant rounds of the same width reuse
    it.  Parameters are shared (``in_axes=None``); wire inputs — and, for a
    stateful program, the per-client carried states — are stacked on a new
    leading batch axis.  Executing the batched function is bitwise identical
    to running the solo executable once per client (asserted by tests)."""

    def __init__(self, program: ReplayProgram, width: int):
        if width < 2:
            raise ValueError(f"batched replay needs width >= 2, got {width}")
        t0 = _time.perf_counter()
        self.base = program
        self.width = int(width)
        self.stateful = program.is_stateful
        if self.stateful:
            self.fn = jax.jit(
                jax.vmap(program._step_impl, in_axes=(None, 0, 0)),
                donate_argnums=(2,),
            )
        else:
            self.fn = jax.jit(jax.vmap(program._replay_impl, in_axes=(None, 0)))
        self.compile_seconds = _time.perf_counter() - t0
        self.n_kernels = program.n_kernels
        self.nbytes_estimate = program.nbytes_estimate * self.width


@dataclasses.dataclass
class BoundReplay:
    """A shared :class:`ReplayProgram` bound to one client's address space.

    For a stateful program the binding also owns this client's
    server-resident ``carried_state`` (live device arrays, updated in place
    by the donated step executable — they never revisit the host)."""

    program: ReplayProgram
    param_addrs: List[int]
    h2d_addrs: List[int]
    d2h_addrs: List[int]
    carried_state: Optional[List[Any]] = None

    @classmethod
    def from_plan(cls, program: ReplayProgram, plan: dict) -> "BoundReplay":
        return cls(
            program=program,
            param_addrs=plan["param_addrs"],
            h2d_addrs=plan["h2d_addrs"],
            d2h_addrs=plan["d2h_addrs"],
        )

    @classmethod
    def bind(cls, program: ReplayProgram, calls: List[InterceptedCall]) -> "BoundReplay":
        return cls.from_plan(program, replay_address_plan(calls))

    def seed_carried(self, env: Dict[int, Any]) -> None:
        """Adopt the carried state left in this client's device memory by its
        last recorded inference: the replay phase starts exactly where the
        recording phase stopped, with the state already server-resident."""
        if not self.program.carried_pairs:
            return
        vals = [
            env.get(self.d2h_addrs[j]) for _, j in self.program.carried_pairs
        ]
        if any(v is None for v in vals):
            return
        self.carried_state = [jnp.asarray(v) for v in vals]


class SegmentedReplayProgram:
    """Per-segment replay executables for one (IOS, split plan) pair.

    Where :class:`ReplayProgram` compiles the whole kernel stream into one
    server-side executable, this compiles one executable *per plan segment*
    so device-resident segments can run on the mobile client and
    server-resident segments on the GPU, with only the cut-crossing tensors
    on the wire.  Content-addressed by ``(IOS fingerprint, plan signature)``
    and shareable across clients: segment functions take
    ``(params_flat, carried_flat)`` positionally, in the canonical
    tid/first-read order both endpoints derive from their own recorded calls.

    With ``carried_pairs`` the program is *stateful*: the plan must be
    carried-feasible (every op touching loop-carried state inside the
    trailing server segment — see ``SegmentGraph.plan_carried_feasible``),
    and that suffix compiles as a donation-aware **step** executable
    ``step(params_flat, boundary_flat, carried_flat)`` with the carried
    buffers donated, exactly like the whole-program ``ReplayProgram.step_fn``
    — the KV cache stays server-resident across the cut, never on the wire.
    """

    def __init__(self, calls: List[InterceptedCall], plan: "SplitPlan", *,
                 execute: bool = True,
                 carried_pairs: Tuple[Tuple[int, int], ...] = (),
                 verify: bool = False):
        from repro.partition.segments import SegmentGraph

        t0 = _time.perf_counter()
        if verify:
            from repro.analysis.verify import (
                raise_on_errors,
                verify_split_calls,
            )

            raise_on_errors(verify_split_calls(calls, plan, carried_pairs))
        self.carried_pairs = tuple((int(i), int(j)) for i, j in carried_pairs)
        graph = SegmentGraph(calls, carried_pairs=self.carried_pairs)
        if plan.n_ops != graph.n_ops:
            raise ValueError(
                f"plan covers {plan.n_ops} ops, IOS has {graph.n_ops}"
            )
        if not graph.plan_carried_feasible(plan):
            raise ValueError(
                f"plan {plan.signature()} is not carried-feasible: a "
                "stateful IOS needs every carried-touching op in the "
                "trailing server segment"
            )
        self.plan = plan
        self.graph = graph            # the compiling client's binding
        ops = [c for c in calls if c.prim is not None]
        self.d2h_avals = [
            c.out_avals[0] for c in calls if c.record.func == FUNC_D2H
        ]
        carried_out = {j for _, j in self.carried_pairs}
        # d2h ordinals still on the wire, in wire order (mirrors ReplayProgram)
        self.wire_out = [
            j for j in range(len(self.d2h_avals)) if j not in carried_out
        ]
        carried_in_tids = set(graph.carried_in_tids)
        carried_out_tids = set(graph.carried_out_tids)
        self.segments: List[dict] = []
        for si, seg in enumerate(plan.segments):
            in_tids = graph.segment_inputs(seg)
            out_tids = graph.segment_outputs(seg)
            param_tids = [
                t.tid
                for t in graph.tensors
                if t.is_param
                and any(seg.start <= c < seg.end for c in t.consumers)
            ]
            # the trailing server segment of a stateful plan is the step
            # segment: carried inputs arrive via the donated state argument,
            # carried outputs return separately so the binding can thread them
            stateful = (
                bool(self.carried_pairs) and si == len(plan.segments) - 1
            )
            spec = dict(
                segment=seg,
                in_tids=in_tids,
                out_tids=out_tids,
                param_tids=param_tids,
                stateful=stateful,
                fn=None,
            )
            if stateful:
                spec["boundary_tids"] = [
                    t for t in in_tids if t not in carried_in_tids
                ]
                spec["out_tids"] = [
                    t for t in out_tids if t not in carried_out_tids
                ]
                if execute:
                    spec["fn"] = self._compile_step_segment(
                        ops[seg.start : seg.end], graph,
                        spec["boundary_tids"], list(graph.carried_in_tids),
                        spec["out_tids"], list(graph.carried_out_tids),
                        param_tids,
                    )
            elif execute:
                spec["fn"] = self._compile_segment(
                    ops[seg.start : seg.end], graph, in_tids, out_tids,
                    param_tids,
                )
            self.segments.append(spec)
        self.compile_seconds = _time.perf_counter() - t0
        self.n_kernels = len(ops)
        self.nbytes_estimate = (
            EXEC_BYTES_PER_KERNEL * max(1, len(ops))
            + _avals_nbytes(self.d2h_avals)
        )

    @property
    def is_stateful(self) -> bool:
        return bool(self.carried_pairs)

    @staticmethod
    def _compile_segment(kernel_calls, graph, in_tids, out_tids, param_tids):
        in_addrs = [graph.tensors[t].addr for t in in_tids]
        out_addrs = [graph.tensors[t].addr for t in out_tids]
        param_addrs = [graph.tensors[t].addr for t in param_tids]

        def run(params_flat, carried_flat):
            env: Dict[int, Any] = dict(zip(param_addrs, params_flat))
            env.update(zip(in_addrs, carried_flat))
            for c in kernel_calls:
                invals = [
                    env[v] if tag == "a" else v for tag, v in c.in_operands
                ]
                outs = c.prim.bind(*invals, **c.params)
                if not c.prim.multiple_results:
                    outs = [outs]
                for addr, val in zip(c.out_addrs, outs):
                    env[addr] = val
            return [env[a] for a in out_addrs]

        return jax.jit(run)

    @staticmethod
    def _compile_step_segment(
        kernel_calls, graph, boundary_tids, carried_in_tids, out_tids,
        carried_out_tids, param_tids,
    ):
        boundary_addrs = [graph.tensors[t].addr for t in boundary_tids]
        carried_in_addrs = [graph.tensors[t].addr for t in carried_in_tids]
        out_addrs = [graph.tensors[t].addr for t in out_tids]
        carried_out_addrs = [graph.tensors[t].addr for t in carried_out_tids]
        param_addrs = [graph.tensors[t].addr for t in param_tids]

        def step(params_flat, boundary_flat, carried_flat):
            env: Dict[int, Any] = dict(zip(param_addrs, params_flat))
            env.update(zip(boundary_addrs, boundary_flat))
            env.update(zip(carried_in_addrs, carried_flat))
            for c in kernel_calls:
                invals = [
                    env[v] if tag == "a" else v for tag, v in c.in_operands
                ]
                outs = c.prim.bind(*invals, **c.params)
                if not c.prim.multiple_results:
                    outs = [outs]
                for addr, val in zip(c.out_addrs, outs):
                    env[addr] = val
            return (
                [env[a] for a in out_addrs],
                [env[a] for a in carried_out_addrs],
            )

        return jax.jit(step, donate_argnums=(2,))


@dataclasses.dataclass
class BoundSegmentedReplay:
    """A shared :class:`SegmentedReplayProgram` bound to one client's address
    space: the client's own :class:`SegmentGraph` supplies the concrete
    parameter/input addresses; the structural tid order is shared.

    For a stateful program the binding also owns this client's
    server-resident ``carried_state`` — exactly like :class:`BoundReplay`,
    advanced in place by the donated step suffix and never revisiting the
    host."""

    program: SegmentedReplayProgram
    graph: SegmentGraph
    carried_state: Optional[List[Any]] = None

    @classmethod
    def from_own(cls, program: SegmentedReplayProgram) -> "BoundSegmentedReplay":
        return cls(program=program, graph=program.graph)

    @classmethod
    def bind(
        cls, program: SegmentedReplayProgram, calls: List[InterceptedCall]
    ) -> "BoundSegmentedReplay":
        from repro.partition.segments import SegmentGraph

        return cls(
            program=program,
            graph=SegmentGraph(calls, carried_pairs=program.carried_pairs),
        )

    @property
    def plan(self) -> "SplitPlan":
        return self.program.plan

    def seed_carried(self, env: Dict[int, Any]) -> None:
        """Adopt the carried state this client's device memory holds (left by
        the last recorded round, or refreshed by the previously-active
        stateful executable): split replay starts exactly where the previous
        phase stopped, with the state already server-resident."""
        if not self.program.carried_pairs:
            return
        vals = [
            env.get(self.graph.tensors[t].addr)
            for t in self.graph.carried_out_tids
        ]
        if any(v is None for v in vals):
            return
        self.carried_state = [jnp.asarray(v) for v in vals]

    def _wire_in_tids(self) -> List[int]:
        carried = set(self.graph.carried_in_tids)
        return [t for t in self.graph.input_tids if t not in carried]

    def execute(
        self, inputs: List[np.ndarray], env: Dict[int, Any], *,
        execute: bool = True,
        fresh_carried: Optional[Dict[int, np.ndarray]] = None,
    ) -> List[Any]:
        """Run every segment functionally (no timing), threading the
        cut-crossing tensors; parameters come from ``env`` (this client's
        server-side memory namespace, which mirrors its on-device weights).

        For a stateless program ``inputs`` are all H2D uploads and the full
        D2H output list is returned.  For a stateful program ``inputs`` are
        the *wire* inputs only and the wire outputs are returned; the carried
        state lives in the binding, is advanced in place by the donated step
        suffix, and ``fresh_carried`` (pair index -> value) overwrites it
        first — the same contract as ``OffloadServer.replay_values``."""
        program = self.program
        if program.is_stateful:
            return self._execute_stateful(
                inputs, env, execute=execute, fresh_carried=fresh_carried
            )
        if not execute:
            return [np.zeros(s, d) for s, d in program.d2h_avals]
        val: Dict[int, Any] = {
            tid: np.asarray(v)
            for tid, v in zip(self.graph.input_tids, inputs)
        }
        for spec in program.segments:
            params = [
                env[self.graph.tensors[t].addr] for t in spec["param_tids"]
            ]
            carried = [val[t] for t in spec["in_tids"]]
            outs = spec["fn"](params, carried)
            val.update(zip(spec["out_tids"], outs))
        results: List[Any] = []
        for tid in self.graph.output_tids:
            if tid in val:
                results.append(np.asarray(val[tid]))
            else:  # an output aliasing a parameter buffer
                results.append(np.asarray(env[self.graph.tensors[tid].addr]))
        # refresh the env so a post-fallback recording phase sees the outputs
        for tid, v in zip(self.graph.output_tids, results):
            env[self.graph.tensors[tid].addr] = v
        return results

    def _execute_stateful(
        self, inputs: List[np.ndarray], env: Dict[int, Any], *,
        execute: bool = True,
        fresh_carried: Optional[Dict[int, np.ndarray]] = None,
    ) -> List[Any]:
        program = self.program
        graph = self.graph
        if not execute:
            return [np.zeros(*program.d2h_avals[j]) for j in program.wire_out]
        if self.carried_state is None:
            raise RuntimeError(
                "stateful split replay has no seeded carried state"
            )
        if fresh_carried:
            for idx, v in fresh_carried.items():
                self.carried_state[idx] = jnp.asarray(v)
        wire_in_tids = self._wire_in_tids()
        val: Dict[int, Any] = {
            tid: np.asarray(v) for tid, v in zip(wire_in_tids, inputs)
        }
        for spec in program.segments:
            params = [
                env[graph.tensors[t].addr] for t in spec["param_tids"]
            ]
            if spec["stateful"]:
                boundary = [val[t] for t in spec["boundary_tids"]]
                outs, new_carried = spec["fn"](
                    params, boundary, self.carried_state
                )
                self.carried_state = list(new_carried)
                val.update(zip(spec["out_tids"], outs))
                # publish the carried outputs too: a wire D2H that reads the
                # *same* buffer as a carried download (aliased output) must
                # see the live value, not the env's pre-step snapshot
                val.update(zip(graph.carried_out_tids, self.carried_state))
            else:
                carried = [val[t] for t in spec["in_tids"]]
                outs = spec["fn"](params, carried)
                val.update(zip(spec["out_tids"], outs))
        results: List[Any] = []
        wire_out_tids = [graph.output_tids[j] for j in program.wire_out]
        for tid in wire_out_tids:
            if tid in val:
                results.append(np.asarray(val[tid]))
            else:  # an output aliasing a parameter buffer
                results.append(np.asarray(env[graph.tensors[tid].addr]))
        # env refresh mirrors OffloadServer._refresh_env: wire buffers get
        # this round's values, carried buffers alias the live resident state
        # — a post-fallback catch-up (or a plan swap's re-seeding) sees the
        # true current state
        for tid, v in zip(wire_in_tids, inputs):
            env[graph.tensors[tid].addr] = np.asarray(v)
        for tid, v in zip(wire_out_tids, results):
            env[graph.tensors[tid].addr] = v
        for in_tid, out_tid, state in zip(
            graph.carried_in_tids, graph.carried_out_tids, self.carried_state
        ):
            env[graph.tensors[in_tid].addr] = state
            env[graph.tensors[out_tid].addr] = state
        return results


class PipelinedSegmentedReplay:
    """Streaming executor over a :class:`BoundSegmentedReplay`: double-buffers
    the device/server cut across *consecutive* inferences.

    The sequential split path finishes inference *i* end-to-end before
    inference *i+1* begins, so the link and one of the two compute resources
    idle at any instant.  A sustained stream admits the pipeline transform:
    while the server executes inference *i*'s server segments, the device
    computes inference *i+1*'s device segments and streams its cut-crossing
    tensors.  Timing comes from the event-driven scheduler
    (:func:`repro.partition.pipeline.simulate_pipeline`): the device and the
    (half-duplex) radio are private
    :class:`~repro.core.netsim.CapacityResource`\\ s whose busy frontiers
    persist across flushes, and server segments occupy the *shared* GPU
    queue through ``OffloadServer.occupy`` so co-tenant contention stays
    visible.  Steady-state per-inference latency is therefore bottleneck-
    bound (``max(device, link, server)``) instead of sum-bound.

    Functional execution is the *same* per-segment walk as the sequential
    path (``BoundSegmentedReplay.execute``), run in submission order with
    in-order completion per client — pipelined outputs are bitwise identical
    to sequential split replay by construction, and the property is tested.
    ``submit()`` queues an arrival and returns its outputs immediately;
    ``flush()`` schedules every queued arrival on the timeline and returns
    the in-order completion times."""

    def __init__(
        self,
        bound: BoundSegmentedReplay,
        client_device: DeviceSpec,
        server: "OffloadServer",
        network: NetworkModel,
        *,
        input_wire_divisor: float = 1.0,
        t0: float = 0.0,
        tracer: Optional[Tracer] = None,
        trace_track: str = "stream",
    ):
        from repro.core.netsim import CapacityResource
        from repro.partition.pipeline import (
            RES_LINK,
            RES_SERVER,
            stage_chain,
        )
        from repro.partition.segments import NetworkLink

        self.bound = bound
        self.server = server
        self.network = network
        self.chain = stage_chain(
            bound.graph,
            bound.plan,
            client_device,
            server.device,
            input_wire_divisor=input_wire_divisor,
        )
        # the engine's live-trace link adapter (ingress bytes accumulate);
        # the chain already carries wire-divided input bytes, so the adapter
        # must not divide again
        self._link_model = NetworkLink(network, 1.0)
        # session-lifetime resources on an unbounded stream: keep the O(1)
        # running totals, not the per-interval history
        self.tracer = tracer
        self.trace_track = trace_track
        self.device = CapacityResource(
            "device", free_at=t0, record_intervals=False,
            tracer=tracer, track=f"{trace_track}/device",
        )
        self.link = CapacityResource(
            "link", free_at=t0, record_intervals=False,
            tracer=tracer, track=f"{trace_track}/radio",
        )
        self._per_inference_server_s = sum(
            s.seconds for s in self.chain if s.resource == RES_SERVER
        )
        self._per_inference_crossings = sum(
            1 for s in self.chain if s.resource == RES_LINK
        )
        self._per_inference_bytes = sum(
            s.nbytes for s in self.chain if s.resource == RES_LINK
        )
        self.submitted = 0
        self._queued: List[float] = []
        self._last_done = t0
        self.crossings = 0
        self.comm_bytes = 0.0
        self.server_seconds = 0.0

    def submit(
        self,
        inputs: List[np.ndarray],
        env: Dict[int, Any],
        t_arrival: float,
        fresh_carried: Optional[Dict[int, np.ndarray]] = None,
    ) -> List[Any]:
        """Queue one inference at ``t_arrival`` and return its outputs (the
        functional walk runs now, in submission order).  Arrivals must be
        nondecreasing within a flush window.  ``fresh_carried`` overwrites
        the stateful suffix's server-resident state before this submission
        executes — the stream analogue of the sequential fresh-state
        override."""
        if self._queued and t_arrival < self._queued[-1]:
            raise ValueError(
                f"arrival {t_arrival} precedes queued arrival "
                f"{self._queued[-1]}"
            )
        outs = self.bound.execute(
            inputs, env, execute=self.server.execute,
            fresh_carried=fresh_carried,
        )
        self._queued.append(float(t_arrival))
        self.submitted += 1
        self.crossings += self._per_inference_crossings
        self.comm_bytes += self._per_inference_bytes
        self.server_seconds += self._per_inference_server_s
        return outs

    def flush(self) -> List[float]:
        """Schedule every queued arrival event-driven over the persistent
        resources; returns in-order completion times (one per arrival)."""
        from repro.partition.pipeline import (
            SharedGPUResource,
            simulate_pipeline,
        )

        if not self._queued:
            return []
        sim = simulate_pipeline(
            self.chain,
            self._link_model,
            self._queued,
            device=self.device,
            server=SharedGPUResource(self.server),
            link_resource=self.link,
        )
        self._queued = []
        dones: List[float] = []
        for s in sim.inferences:
            self._last_done = max(self._last_done, s.done)
            dones.append(self._last_done)
        return dones

    def busy_snapshot(self) -> Tuple[float, float]:
        """(device busy, link busy) seconds accumulated so far — the stream
        driver diffs these around a window to bill energy phases."""
        return self.device.busy_total, self.link.busy_total


@dataclasses.dataclass
class ClientContext:
    """Per-client server-side state: device memory namespace + bound replay.

    The GPU occupancy (``busy_until``/``busy_seconds``) and the replay cache
    stay on the :class:`OffloadServer` — they are shared across tenants."""

    env: Dict[int, Any] = dataclasses.field(default_factory=dict)
    replay: Optional[BoundReplay] = None
    split: Optional[BoundSegmentedReplay] = None


class OffloadServer:
    """GPU-server side: executes RPCs in recording mode, compiles + replays
    the IOS in replaying mode.

    Multi-tenant: each client id owns a :class:`ClientContext` (device-memory
    namespace + bound replay executable); the kernel queue (``busy_until``),
    accumulated compute (``busy_seconds``) and the optional content-addressed
    ``replay_cache`` (fingerprint -> :class:`ReplayProgram`) are shared.  With
    the default single client and no cache, behaviour is identical to the
    original single-tenant server."""

    def __init__(
        self,
        device: DeviceSpec,
        *,
        execute: bool = True,
        replay_cache: Optional["ReplayCacheLike"] = None,
        name: str = "server",
        tracer: Optional[Tracer] = None,
        verify: bool = False,
        jax_device: Optional[Any] = None,
    ):
        self.device = device
        # where this server's memory lives: H2D payloads land here as device
        # arrays and stay resident between replayed steps (None: JAX's
        # default device)
        self.jax_device = jax_device
        self.name = name
        self.tracer = tracer
        self.execute = execute  # False: account time/bytes only (no compute)
        self.verify = verify    # static soundness analysis before compiling
        self.contexts: Dict[str, ClientContext] = {}
        self.busy_until = 0.0          # async kernel-queue completion time
        self.busy_seconds = 0.0        # accumulated compute (GPU-util proxy)
        self.replay_cache = replay_cache
        self.compile_seconds = 0.0
        self.compile_count = 0         # actual program builds (not cache hits)
        # at-most-once reply cache: (client id) -> {seq -> cached reply}.
        # A retried sequence number returns the cached reply and never
        # re-executes — the guard that keeps a retransmitted stateful step
        # from advancing the donated KV cache twice.
        self.dedup: Dict[str, Dict[int, Any]] = {}
        self.dedup_hits = 0

    def to_device(self, value: Any) -> Any:
        """Place one buffer in this server's device memory."""
        return jax.device_put(value, self.jax_device)

    def receive_env(self, client_id: str, env: Dict[int, Any]) -> float:
        """Install another server's device-memory namespace for
        ``client_id`` (a migration or a checkpoint restore), placing every
        buffer on this server's device; returns the bytes moved."""
        dst = self.context(client_id).env
        moved = 0.0
        for addr, val in env.items():
            dst[addr] = self.to_device(val)
            moved += float(dst[addr].nbytes)
        return moved

    def context(self, client_id: str = DEFAULT_CLIENT) -> ClientContext:
        ctx = self.contexts.get(client_id)
        if ctx is None:
            ctx = self.contexts[client_id] = ClientContext()
        return ctx

    @property
    def env(self) -> Dict[int, Any]:
        """Default client's device memory (single-tenant back-compat)."""
        return self.context().env

    # -- recording-phase execution (one call at a time) ---------------------
    def exec_call(
        self,
        call: InterceptedCall,
        arrival_t: float,
        client_id: str = DEFAULT_CLIENT,
    ) -> Any:
        env = self.context(client_id).env
        rec = call.record
        ret: Any = "cudaSuccess"
        if rec.func == FUNC_H2D:
            if self.execute:
                env[call.out_addrs[0]] = self.to_device(
                    np.asarray(call.h2d_value)
                )
        elif rec.func == FUNC_D2H:
            addr = call.in_operands[0][1]
            # DtoH must drain the kernel queue first
            self.busy_until = max(self.busy_until, arrival_t)
            if self.execute:
                ret = np.asarray(env[addr])
            else:
                shape, dtype = call.out_avals[0]
                ret = np.zeros(shape, dtype)
        elif call.prim is not None:
            if self.execute:
                invals = [
                    env[v] if tag == "a" else v
                    for tag, v in call.in_operands
                ]
                outs = call.prim.bind(*invals, **call.params)
                if not call.prim.multiple_results:
                    outs = [outs]
                for addr, val in zip(call.out_addrs, outs):
                    env[addr] = val
            op_t = self.device.op_time(rec.flops, rec.mem_bytes)
            op_t += self.device.kernel_launch_s
            self.busy_until = max(self.busy_until, arrival_t) + op_t
            self.busy_seconds += op_t
        return ret

    # -- replaying phase -----------------------------------------------------
    def _stale_metadata(
        self,
        key: str,
        meta: Dict[str, Any],
        calls: List[InterceptedCall],
    ) -> bool:
        """Cross-check persisted cache metadata against the calls about to
        be compiled under it.  A hand-edited or stale cache file used to
        bind a donated stateful executable to carried-pair ordinals that do
        not exist in this recording; now the entry is evicted with a
        warning and the program is rebuilt stateless instead."""
        import warnings

        from repro.analysis.plancheck import verify_metadata_against_calls

        diags = verify_metadata_against_calls(key, meta, calls)
        if not diags:
            return False
        warnings.warn(
            f"{self.name}: evicting stale replay-cache metadata for "
            f"{key!r}: " + "; ".join(
                f"{d.code}: {d.message}" for d in diags
            ),
            stacklevel=3,
        )
        forget = getattr(self.replay_cache, "forget_known", None)
        if callable(forget):
            forget(key)
        return True

    def prepare_replay(
        self,
        calls: List[InterceptedCall],
        client_id: str = DEFAULT_CLIENT,
        fingerprint: Optional[str] = None,
        carried_pairs: Tuple[Tuple[int, int], ...] = (),
    ) -> bool:
        """Install a replay executable for ``client_id``.

        With a ``replay_cache`` attached and a fingerprint given, the compiled
        program is looked up first — a hit binds the cached executable to this
        client's address space without recompiling.  ``carried_pairs`` is the
        recording client's loop-carried-tensor detection; a cache hit uses the
        cached program's pairs instead (the adopting client recorded a single
        round and could not detect them itself), and a restart-persisted
        fingerprint recovers the pairs from the cache metadata so the rebuilt
        executable is stateful again.  Returns True iff the program came from
        the cache."""
        program: Optional[ReplayProgram] = None
        from_cache = False
        if self.replay_cache is not None and fingerprint is not None:
            program = self.replay_cache.get(fingerprint)
            from_cache = program is not None
        if program is None:
            pairs = tuple(carried_pairs)
            if (
                not pairs
                and self.replay_cache is not None
                and fingerprint is not None
            ):
                meta = self.replay_cache.known_metadata(fingerprint)
                if meta and meta.get("carried_pairs"):
                    if self._stale_metadata(fingerprint, meta, calls):
                        meta = None   # stateless rebuild; entry evicted
                if meta and meta.get("carried_pairs"):
                    pairs = tuple(
                        (int(i), int(j)) for i, j in meta["carried_pairs"]
                    )
            program = ReplayProgram(
                calls, execute=self.execute, carried_pairs=pairs,
                verify=self.verify,
            )
            self.compile_count += 1
            self.compile_seconds = program.compile_seconds
            if self.replay_cache is not None and fingerprint is not None:
                self.replay_cache.put(fingerprint, program)
            # the fresh program was built from this client's calls: its plan
            # is this client's binding
            bound = BoundReplay.from_plan(program, program.plan)
        else:
            bound = BoundReplay.bind(program, calls)
        if self.execute:
            bound.seed_carried(self.context(client_id).env)
        self.context(client_id).replay = bound
        return from_cache

    def prepare_split(
        self,
        calls: List[InterceptedCall],
        plan: "SplitPlan",
        client_id: str = DEFAULT_CLIENT,
        fingerprint: Optional[str] = None,
        carried_pairs: Tuple[Tuple[int, int], ...] = (),
    ) -> bool:
        """Install per-segment replay executables for ``client_id``.

        Segmented programs are cached under the composite key
        ``(fingerprint, plan signature)`` — co-tenants on different networks
        plan different cuts of the same shared IOS, and each cut is compiled
        exactly once.  ``carried_pairs`` makes the program stateful (donated
        server suffix); a cache hit uses the cached program's pairs, and a
        restart-persisted key recovers them from the cache metadata so the
        rebuilt split is stateful again.  Returns True iff the program came
        from the cache."""
        key = (
            f"{fingerprint}|{plan.signature()}"
            if fingerprint is not None
            else None
        )
        program: Optional[SegmentedReplayProgram] = None
        from_cache = False
        if self.replay_cache is not None and key is not None:
            program = self.replay_cache.get(key)
            from_cache = program is not None
        if program is None:
            pairs = tuple(carried_pairs)
            if not pairs and self.replay_cache is not None:
                for k in (key, fingerprint):
                    if k is None:
                        continue
                    meta = self.replay_cache.known_metadata(k)
                    if meta and meta.get("carried_pairs"):
                        if self._stale_metadata(k, meta, calls):
                            continue
                        pairs = tuple(
                            (int(i), int(j))
                            for i, j in meta["carried_pairs"]
                        )
                        break
            program = SegmentedReplayProgram(
                calls, plan, execute=self.execute, carried_pairs=pairs,
                verify=self.verify,
            )
            self.compile_count += 1
            self.compile_seconds = program.compile_seconds
            if self.replay_cache is not None and key is not None:
                self.replay_cache.put(key, program)
            bound = BoundSegmentedReplay.from_own(program)
        else:
            bound = BoundSegmentedReplay.bind(program, calls)
        if self.execute:
            bound.seed_carried(self.context(client_id).env)
        self.context(client_id).split = bound
        return from_cache

    @property
    def replay_ready(self) -> bool:
        return self.has_replay()

    def has_replay(self, client_id: str = DEFAULT_CLIENT) -> bool:
        ctx = self.contexts.get(client_id)
        return ctx is not None and ctx.replay is not None

    def replay_compute_seconds(self, client_id: str = DEFAULT_CLIENT) -> float:
        return self.context(client_id).replay.program.compute_seconds(self.device)

    def replay_values(
        self,
        inputs: List[np.ndarray],
        client_id: str = DEFAULT_CLIENT,
        *,
        fresh_carried: Optional[Dict[int, np.ndarray]] = None,
    ) -> List[Any]:
        """Functionally execute the bound replay for one client (no timing).

        For a stateless program ``inputs`` are all H2D uploads and the full
        D2H output list is returned.  For a stateful program ``inputs`` are
        the *wire* inputs only; the carried state lives server-side in the
        binding, is advanced in place by the donated step executable, and
        only the wire outputs are returned.  ``fresh_carried`` (pair index ->
        value) overwrites the resident state first — the path a client takes
        when its application supplies genuinely new state (e.g. a new
        prompt's prefill) instead of threading the resident handle."""
        ctx = self.context(client_id)
        bound = ctx.replay
        program = bound.program
        if not self.execute:
            avals = program.d2h_avals
            if program.is_stateful:
                return [np.zeros(*avals[j]) for j in program.wire_out]
            return [np.zeros(s, d) for s, d in avals]
        with host_span("rrto.replay"):
            params_flat = [ctx.env[a] for a in bound.param_addrs]
            if program.is_stateful:
                if bound.carried_state is None:
                    raise RuntimeError(
                        f"stateful replay for {client_id!r} has no seeded "
                        "carried state"
                    )
                if fresh_carried:
                    with host_span("rrto.fresh_upload"):
                        for idx, v in fresh_carried.items():
                            bound.carried_state[idx] = self.to_device(v)
                wire = [np.asarray(x) for x in inputs]
                with host_span("rrto.launch"):
                    wire_outs, new_carried = program.step_fn(
                        params_flat, wire, bound.carried_state
                    )
                bound.carried_state = list(new_carried)
                with host_span("rrto.fetch"):
                    wire_outs = [np.asarray(o) for o in wire_outs]
                self._refresh_env(ctx, bound, wire, wire_outs)
                return wire_outs
            with host_span("rrto.launch"):
                outs = program.fn(params_flat, [np.asarray(x) for x in inputs])
            with host_span("rrto.fetch"):
                outs = [np.asarray(o) for o in outs]
            # refresh the env (inputs AND outputs) so a post-fallback
            # recording-phase catch-up replays against this inference's
            # buffers, not the last recorded one's
            for addr, val in zip(bound.h2d_addrs, inputs):
                ctx.env[addr] = np.asarray(val)
            for addr, val in zip(bound.d2h_addrs, outs):
                ctx.env[addr] = val
            return outs

    @staticmethod
    def _refresh_env(
        ctx: ClientContext,
        bound: BoundReplay,
        wire_inputs: List[Any],
        wire_outs: List[Any],
    ) -> None:
        """Post-stateful-step env refresh: wire buffers get this round's
        values, carried buffers alias the live resident state — so a
        post-fallback recording-phase catch-up executes against the true
        current state, not the last recorded round's."""
        program = bound.program
        for ordinal, val in zip(program.wire_in, wire_inputs):
            ctx.env[bound.h2d_addrs[ordinal]] = np.asarray(val)
        for ordinal, val in zip(program.wire_out, wire_outs):
            ctx.env[bound.d2h_addrs[ordinal]] = val
        for (i, j), state in zip(program.carried_pairs, bound.carried_state):
            ctx.env[bound.h2d_addrs[i]] = state
            ctx.env[bound.d2h_addrs[j]] = state

    def adopt_replay_results(
        self,
        client_id: str,
        inputs: List[np.ndarray],
        outs: List[Any],
        new_carried: Optional[List[Any]] = None,
    ) -> None:
        """Install the results of a cross-client *batched* execution for one
        member as if it had executed solo: refresh the device-memory env and,
        for a stateful program, advance the resident carried state to the
        batch-computed value.  Called at claim time only, so a member that
        never submits (a DAM fallback mid-walk) keeps its state untouched."""
        if not self.execute:
            return
        ctx = self.context(client_id)
        bound = ctx.replay
        with host_span("rrto.adopt", client=client_id):
            if bound.program.is_stateful:
                if new_carried is not None:
                    bound.carried_state = list(new_carried)
                self._refresh_env(ctx, bound, list(inputs), list(outs))
                return
            for addr, val in zip(bound.h2d_addrs, inputs):
                ctx.env[addr] = np.asarray(val)
            for addr, val in zip(bound.d2h_addrs, outs):
                ctx.env[addr] = val

    # -- carried-state migration --------------------------------------------
    def export_carried_state(
        self, client_id: str = DEFAULT_CLIENT
    ) -> Optional[List[np.ndarray]]:
        """Snapshot one client's live server-resident carried state (the
        donated KV cache advanced in place by the stateful step executable)
        as host arrays — the wire format of a replica-to-replica session
        migration.  The split binding takes precedence over the whole-program
        one (when a split plan is active it owns the live state, the same
        source order as ``RRTOClient._carried_state_source``).  Returns None
        when the client has no stateful binding or no seeded state yet."""
        ctx = self.contexts.get(client_id)
        if ctx is None:
            return None
        bound = ctx.split or ctx.replay
        if bound is None or bound.carried_state is None:
            return None
        return [np.asarray(v) for v in bound.carried_state]

    def import_carried_state(
        self, client_id: str, state: List[Any]
    ) -> None:
        """Install an exported carried-state snapshot into this client's
        bound replay — the receiving half of a migration.  The binding's
        resident state is replaced and the env's carried buffers re-aliased
        (the in-process precedent is ``_install_plan``'s whole-program <->
        segmented handoff, which re-seeds the adopting binding from the env),
        so the next stateful step — and any post-fallback recording-phase
        catch-up — runs from exactly the migrated state."""
        ctx = self.context(client_id)
        bound = ctx.split or ctx.replay
        if bound is None or not bound.program.is_stateful:
            raise ValueError(
                f"client {client_id!r} has no stateful replay binding to "
                "import carried state into"
            )
        pairs = bound.program.carried_pairs
        if len(state) != len(pairs):
            raise ValueError(
                f"carried-state arity mismatch: {len(state)} tensors for "
                f"{len(pairs)} carried pairs"
            )
        bound.carried_state = [self.to_device(v) for v in state]
        if isinstance(bound, BoundSegmentedReplay):
            # segmented binding: the carried buffers live at the graph's
            # carried-output tensor addresses (what seed_carried reads back)
            graph = bound.graph
            for t, val in zip(graph.carried_out_tids, bound.carried_state):
                ctx.env[graph.tensors[t].addr] = val
        else:
            for (i, j), val in zip(pairs, bound.carried_state):
                ctx.env[bound.h2d_addrs[i]] = val
                ctx.env[bound.d2h_addrs[j]] = val

    def step_once(
        self, client_id: str, seq: Optional[int], thunk
    ) -> Tuple[Any, bool]:
        """Execute ``thunk`` at-most-once under ``(client_id, seq)``.

        The reliability protocol's server half: a sequence number already in
        the dedup table means this request was executed and its response
        lost in flight — the cached reply is returned and the thunk (which
        advances donated carried state in place and therefore MUST NOT run
        twice) is not re-executed.  Returns ``(reply, was_cached)``.  A None
        sequence number bypasses dedup entirely (the fault-free path)."""
        if seq is None:
            return thunk(), False
        table = self.dedup.setdefault(client_id, {})
        if seq in table:
            self.dedup_hits += 1
            return table[seq], True
        reply = thunk()
        table[seq] = reply
        while len(table) > DEDUP_WINDOW:
            del table[min(table)]
        return reply, False

    def occupy(self, compute_seconds: float, start_t: float) -> float:
        """Reserve the shared GPU queue; returns the completion time."""
        begin = max(self.busy_until, start_t)
        self.busy_until = begin + compute_seconds
        self.busy_seconds += compute_seconds
        if self.tracer is not None and compute_seconds > 0.0:
            self.tracer.span(
                f"{self.name}/gpu", "gpu_exec", begin, self.busy_until
            )
        return self.busy_until

    def run_replay(
        self,
        inputs: List[np.ndarray],
        start_t: float,
        client_id: str = DEFAULT_CLIENT,
        fresh_carried: Optional[Dict[int, np.ndarray]] = None,
    ) -> Tuple[List[Any], float]:
        """Execute the compiled IOS solo; returns (outputs, completion time)."""
        outs = self.replay_values(
            inputs, client_id, fresh_carried=fresh_carried
        )
        done_at = self.occupy(self.replay_compute_seconds(client_id), start_t)
        return outs, done_at


# ---------------------------------------------------------------------------
# client (Alg. 3)
# ---------------------------------------------------------------------------

class InferenceStats(RegistryBackedStats):
    """Per-client traffic/energy counters, registry-backed: attribute
    bumps land in a :class:`~repro.obs.MetricsRegistry` scope so a fleet
    root ``snapshot()`` reports every client's RPC count and wire bytes.
    ``mode`` stays a plain attribute (it is a label, not a counter)."""

    _fields = (
        ("rpcs", 0),
        ("network_bytes", 0.0),
        ("wall_seconds", 0.0),
        ("joules", 0.0),
        ("cache_adoptions", 0),
        ("replayed_records", 0),      # IOS records of replayed inferences
        # fault-tolerance counters (all zero without a FaultInjector)
        ("retries", 0),               # lost-message timeouts paid
        ("dedup_replies", 0),         # retried steps answered from the cache
        ("outage_fallbacks", 0),      # inferences served device-locally
        ("outage_waits", 0),          # stateful inferences that sat out an outage
        ("crash_restores", 0),        # checkpoint+replay recoveries absorbed
    )

    def __init__(
        self,
        registry: Optional[MetricsRegistry] = None,
        mode: str = MODE_RECORDING,
    ):
        super().__init__(registry)
        self.mode = mode


class RRTOClient:
    """Call sink implementing Alg. 3.  Modes:

    * ``transparent`` (Cricket) — always record-phase behaviour, no search;
    * ``semi_rrto`` — Cricket + client-side caching of device-query RPCs;
    * ``rrto`` — full record/replay with Operator Sequence Search.
    """

    def __init__(
        self,
        server: OffloadServer,
        network: NetworkModel,
        clock: SimClock,
        meter: EnergyMeter,
        *,
        variant: str = "rrto",
        min_repeats: int = 3,
        search_on_d2h: bool = True,
        client_id: str = DEFAULT_CLIENT,
        client_device: DeviceSpec = JETSON_XAVIER_NX,
        partition: Optional["PartitionConfig"] = None,
        input_wire_divisor: float = 1.0,
        tracer: Optional[Tracer] = None,
        trace_track: Optional[str] = None,
        metrics: Optional[MetricsRegistry] = None,
        fault: Optional[FaultInjector] = None,
        retry_policy: Optional[RetryPolicy] = None,
        verify: bool = False,
    ):
        if variant not in ("rrto", "semi_rrto", "transparent"):
            raise ValueError(variant)
        self.server = server
        # static soundness analysis of the locked IOS / each installed plan
        # before any executable compiles from them (fail-fast, off by default)
        self.verify = verify
        self.network = network
        self.clock = clock
        self.meter = meter
        self.variant = variant
        self.min_repeats = min_repeats
        self.search_on_d2h = search_on_d2h
        self.client_id = client_id
        self.client_device = client_device
        self.input_wire_divisor = input_wire_divisor
        # multi-tenant hooks: the IOS fingerprint once identified, whether it
        # was adopted from the shared cache (skipping the min_repeats wait),
        # and an optional replay-execution backend (cross-client batching)
        self.ios_fp: Optional[str] = None
        self.cache_adopted = False
        self.replay_submit: Optional[Any] = None
        # split-replay partitioning (None = classic full-server replay)
        self.partition = partition
        self.replanner: Optional["AdaptiveReplanner"] = None
        self.split_plan: Optional["SplitPlan"] = None
        self._split_output_local: List[bool] = []
        self._inputs_uploaded = False
        # multi-tenant hook: co-tenant server-resident segments of one shared
        # IOS batch on the GPU (set by the edge server, like replay_submit)
        self.split_submit: Optional[Any] = None
        # pipelined streaming executor (partition.pipelined=True): rebuilt on
        # every plan install, consumed by OffloadSession.infer_stream.  While
        # installed it holds a cache *claim* on its derived fp|plan key so
        # size-aware eviction cannot purge the base program (and with it the
        # segmented executable the stream is driving) mid-stream.
        self.pipelined_exec: Optional[PipelinedSegmentedReplay] = None
        self._stream_claim: Optional[str] = None

        self.mode = MODE_RECORDING
        self.logs: List[OperatorRecord] = []
        self.calls: List[InterceptedCall] = []
        self._payload_trimmed = 0   # calls below this index hold no payloads
        self._transfer_log: List[int] = []  # indices of recent h2d/d2h calls
        self.ios: Optional[InferenceSequence] = None
        self._ios_calls: List[InterceptedCall] = []
        self._replay_pos = 0
        self._replay_prefix: List[InterceptedCall] = []
        self._replay_inputs: List[np.ndarray] = []
        self._replay_outputs: Optional[List[Any]] = None
        self._replay_done_at = 0.0
        self._out_cursor = 0
        self._h2d_seen = 0
        # stateful replay: loop-carried tensors stay server-resident.  The
        # maps go from h2d/d2h ordinal to carried-pair index; the client hands
        # the application a stable placeholder (the state value at replay
        # entry) for each carried download and recognizes it by identity on
        # the way back in — a non-placeholder upload is genuinely new state
        # and is shipped to the server as an override.
        self._carried_in_map: Dict[int, int] = {}
        self._carried_out_map: Dict[int, int] = {}
        self._wire_out_index: Dict[int, int] = {}
        self._carried_placeholders: Dict[int, np.ndarray] = {}
        self._fresh_carried: Dict[int, np.ndarray] = {}
        self.search_seconds = 0.0
        self.searches_run = 0
        self.fallbacks = 0
        self._query_cache: set = set()
        # fault tolerance: injected link faults + retry discipline (None =
        # perfect wire, every hook below is pass-through), the per-stateful-
        # step sequence number driving the server's at-most-once dedup, and
        # an optional bounded log of completed steps since the last carried-
        # state checkpoint (attached by the recovery layer; replayed
        # deterministically after a replica crash)
        self.fault = fault
        self.retry_policy = retry_policy or RetryPolicy()
        self.step_seq = 0
        self.step_log: Optional[Any] = None    # deque of _StepLogEntry
        self.outage_active = False
        # overload protection: the tenant this client bills against and the
        # absolute sim-time deadline of the in-flight request (None = no SLO
        # attached; EDF round formation treats it as "no deadline, last")
        self.tenant = "default"
        self.deadline_t: Optional[float] = None
        # observability: spans land on this client's track; None = tracing
        # off (every emission site guards on it, so the disabled path does
        # no per-event work)
        self.tracer = tracer
        self.trace_track = trace_track or f"client/{client_id}"
        # per-inference counters (reset by the session), registry-backed
        self.stats = InferenceStats(registry=metrics)

    # -- helpers -------------------------------------------------------------
    @property
    def replay_key(self) -> Optional[str]:
        """Cache/batch identity of this client's replay executable:
        the IOS fingerprint, extended by the split-plan signature when a
        partition is active (co-tenants on different networks run different
        cuts of the same IOS and must not share executables or batches)."""
        if self.ios_fp is None:
            return None
        if self.split_plan is None:
            return self.ios_fp
        return f"{self.ios_fp}|{self.split_plan.signature()}"

    @property
    def carried_input_ordinals(self) -> frozenset:
        """H2D ordinals (position among one round's uploads) answered locally
        because the tensor is loop-carried server-resident state."""
        return frozenset(self._carried_in_map)

    @property
    def stateful_replay(self) -> bool:
        return bool(self._carried_in_map)

    def expand_stream_outputs(self, wire_outs: List[Any]) -> List[Any]:
        """Rebuild the app-visible output list from a stream executor's wire
        outputs: carried D2H ordinals get the stable placeholder handle,
        wire ordinals their computed value — so a ``StreamResult``'s outputs
        have the same arity and meaning as sequential ``infer()``, whether
        the arrival was served by the pipelined executor or the closed-loop
        fallback."""
        if not self._carried_out_map:
            return list(wire_outs)
        n_out = len(wire_outs) + len(self._carried_out_map)
        outs: List[Any] = []
        for cursor in range(n_out):
            idx = self._carried_out_map.get(cursor)
            if idx is not None:
                outs.append(self._carried_placeholders.get(idx))
            else:
                outs.append(wire_outs[self._wire_out_index[cursor]])
        return outs

    def extract_fresh_carried(
        self, uploads: List[Any]
    ) -> Tuple[List[np.ndarray], Optional[Dict[int, np.ndarray]]]:
        """Split one arrival's uploads into (wire inputs, fresh-state
        overrides), mirroring the sequential H2D walk: a carried position
        holding the threaded placeholder handle costs nothing; any other
        value is genuinely new state and must overwrite the server-resident
        suffix state before the submission executes."""
        if not self._carried_in_map:
            return [np.asarray(v) for v in uploads], None
        wire: List[np.ndarray] = []
        fresh: Dict[int, np.ndarray] = {}
        for ordinal, v in enumerate(uploads):
            idx = self._carried_in_map.get(ordinal)
            if idx is None:
                wire.append(np.asarray(v))
                continue
            ph = self._carried_placeholders.get(idx)
            if ph is not None and (
                v is ph or getattr(v, "base", None) is ph
            ):
                continue
            arr = np.asarray(v)
            fresh[idx] = arr
            # the handle the app threads from now on is a writable copy, so
            # a DAM fallback can refresh it in place (same contract as the
            # sequential carried-upload path)
            self._carried_placeholders[idx] = np.array(arr, copy=True)
        return wire, (fresh or None)

    def _account_network(self, rpcs: int, nbytes: float) -> None:
        """THE accounting site for client network traffic: the full-server,
        DAM-fallback and split paths (and ``infer_stream``'s executor) all
        bump through here, so RPC/byte counts cannot drift between paths."""
        self.stats.rpcs += rpcs
        self.stats.network_bytes += nbytes

    def _rpc(self, payload: float, response: float) -> None:
        if self.fault is not None:
            self._ride_out_losses(payload)
        t0 = self.clock.t
        dt = self.network.rpc_time(payload, response, self.clock.t)
        self.clock.advance(dt)
        self.meter.add(STATE_COMM, dt)
        self._account_network(1, payload + response)
        if self.tracer is not None:
            self.tracer.span(
                self.trace_track,
                "record_rpc" if self.mode == MODE_RECORDING else "rpc",
                t0,
                t0 + dt,
                payload=payload,
                response=response,
            )

    def _retry_timeout(self, attempt: int) -> None:
        """Pay one lost-message timeout: the client sat waiting for a reply
        that never came, then retransmits.  Billed standby (the radio idles
        listening) plus the retransmitted bytes; exponential backoff with
        deterministic jitter keeps repeated losses from hammering the link."""
        dt = self.retry_policy.timeout_s(attempt, self.fault.jitter_unit())
        t0 = self.clock.t
        self.clock.advance(dt)
        self.meter.add(STATE_STANDBY, dt)
        self.stats.retries += 1
        if self.tracer is not None:
            self.tracer.instant(
                self.trace_track, "retry", t0, attempt=attempt, timeout=dt,
            )

    def _ride_out_losses(self, payload: float) -> int:
        """Simulate the lost attempts preceding one delivered message: each
        loss costs a timeout (backoff + jitter) and a retransmission of the
        payload.  Raises :class:`RpcTimeoutError` once the retry budget is
        exhausted — the caller's cue to declare an outage.  Used for
        *idempotent* traffic (recording-phase RPCs re-execute functionally
        identical work; uploads just rewrite the same buffers), where only
        the delivered attempt has server-side effect by construction."""
        attempts = 0
        while self.fault.rpc_fate() != "ok":
            if attempts >= self.retry_policy.max_attempts:
                raise RpcTimeoutError(
                    f"client {self.client_id!r}: RPC lost "
                    f"{attempts + 1} consecutive times"
                )
            self._retry_timeout(attempts)
            self._account_network(1, payload)   # the retransmission
            attempts += 1
        return attempts

    def _reliable_step(
        self, submit, inputs: List[np.ndarray], fresh: Optional[Dict[int, np.ndarray]]
    ) -> Tuple[List[Any], float]:
        """One sequence-numbered stateful step under the at-most-once
        protocol.  The donated step executable advances server-resident
        state in place, so a retransmission must never re-execute it: the
        server's dedup table (:meth:`OffloadServer.step_once`) executes the
        submission on first receipt and answers every retry of the same
        sequence number from the reply cache.

        Loss is drawn per transmission: a lost *request* never reached the
        server (the retry executes fresh); a lost *response* means the step
        DID execute — the retry returns the cached reply, and carried state
        has advanced exactly once either way."""
        seq = self.step_seq
        payload = float(sum(np.asarray(a).nbytes for a in inputs))
        attempts = 0
        while True:
            fate = self.fault.rpc_fate()
            if fate != "lost_request":
                # the request was delivered: the server executes (or answers
                # from the dedup cache if this seq already ran)
                reply, cached = self.server.step_once(
                    self.client_id, seq,
                    lambda: submit(inputs, self.clock.t, fresh_carried=fresh),
                )
                if cached:
                    self.stats.dedup_replies += 1
                if fate == "ok":
                    return reply
            # this attempt's reply never arrived — pay the timeout and resend
            if attempts >= self.retry_policy.max_attempts:
                raise RpcTimeoutError(
                    f"client {self.client_id!r}: stateful step {seq} lost "
                    f"{attempts + 1} consecutive times"
                )
            self._retry_timeout(attempts)
            self._account_network(1, payload)   # the retransmission
            attempts += 1

    def _note_step(
        self,
        wire_inputs: List[np.ndarray],
        fresh: Optional[Dict[int, np.ndarray]],
    ) -> None:
        """Advance the stateful-step sequence number and, when the recovery
        layer attached a step log, record the completed step for
        deterministic crash replay.  Copies, not views: the app may mutate
        its buffers between steps, and a replayed step must ship exactly
        what the original shipped."""
        if not self.stateful_replay:
            return
        if self.step_log is not None:
            self.step_log.append(
                StepLogEntry(
                    seq=self.step_seq,
                    wire_inputs=[
                        np.array(np.asarray(a), copy=True)
                        for a in wire_inputs
                    ],
                    fresh_carried=(
                        {
                            k: np.array(np.asarray(v), copy=True)
                            for k, v in fresh.items()
                        }
                        if fresh
                        else None
                    ),
                )
            )
        self.step_seq += 1

    def _local(self, dt: float = PER_LOCAL_OP_S) -> None:
        self.clock.advance(dt)
        self.meter.add(STATE_CONTROL, dt)

    def _wait_until(self, t: float) -> None:
        if t > self.clock.t:
            dt = t - self.clock.t
            self.clock.advance(dt)
            self.meter.add(STATE_STANDBY, dt)

    # -- recording-phase handling --------------------------------------------
    def _record_call(self, call: InterceptedCall) -> Any:
        rec = call.record
        # semi-RRTO (Fig. 11) caches device-query RPCs; full RRTO stays
        # faithful to traditional transparent offloading while recording.
        cached_query = self.variant == "semi_rrto" and rec.category == "q"
        if cached_query and self._seen_query(rec):
            # semi-RRTO optimization: device-state queries are answered from
            # the client cache (Fig. 11) — no network traffic
            self._local()
            ret = "cached"
        else:
            self._rpc(rec.payload_bytes, rec.response_bytes)
            if rec.category == CAT_D2H:
                # drain the server kernel queue before download completes
                self._wait_until(self.server.busy_until)
            ret = self.server.exec_call(call, self.clock.t, self.client_id)
            if rec.category == CAT_D2H and isinstance(ret, np.ndarray):
                # Alg. 3 logs the full (func, args, ret) triple; the download
                # payload feeds the loop-carried-tensor detection.  A copy,
                # not the array handed to the app: an app that mutates the
                # download in place before re-uploading it would otherwise
                # self-alias into a guaranteed (false) bitwise match.
                call.d2h_value = np.array(ret, copy=True)

        self.logs.append(rec)
        self.calls.append(call)
        if rec.func in (FUNC_H2D, FUNC_D2H):
            self._transfer_log.append(len(self.calls) - 1)
            if len(self._transfer_log) > PAYLOAD_RETENTION_TRANSFERS:
                old = self._transfer_log.pop(0)
                if old < self._payload_trimmed:
                    # it outlived the call-count horizon under protection;
                    # the protection window has slid past it now
                    self.calls[old].h2d_value = None
                    self.calls[old].d2h_value = None
        n = len(self.calls)
        if n - self._payload_trimmed > PAYLOAD_RETENTION_CALLS:
            protected = set(self._transfer_log)
            for i in range(self._payload_trimmed, n - PAYLOAD_RETENTION_CALLS):
                if i in protected:
                    continue
                self.calls[i].h2d_value = None
                self.calls[i].d2h_value = None
            self._payload_trimmed = n - PAYLOAD_RETENTION_CALLS

        if self.variant == "rrto" and self.search_on_d2h:
            # run the search whenever a DtoH sync group closes: after the DtoH
            # itself and after each trailing synchronize (the paper overlaps
            # the search with the RPC wait, so per-op invocation is free)
            tail_is_boundary = rec.category == CAT_D2H or (
                rec.category == "s"
                and any(r.category == CAT_D2H for r in self.logs[-3:-1])
            )
            if tail_is_boundary:
                # The cache-adoption probe is an extra full search, so run it
                # only on the sync-triggered searches (which close the DtoH
                # sync group), not at the DtoH itself: a cached IOS ends at
                # the group-closing sync, so a probe window cut at the bare
                # DtoH could never match its fingerprint.
                self._try_identify_sequence(
                    probe_cache=rec.category != CAT_D2H
                )
        return ret

    def _seen_query(self, rec: OperatorRecord) -> bool:
        key = rec.identity()
        if key in self._query_cache:
            return True
        self._query_cache.add(key)
        return False

    def _try_identify_sequence(self, probe_cache: bool = True) -> None:
        t0 = _time.perf_counter()
        ios = operator_sequence_search(self.logs, self.min_repeats)
        fp: Optional[str] = None
        cache = self.server.replay_cache
        if ios is None and probe_cache and cache is not None and len(cache) > 0:
            # Shared-cache shortcut: a single boundary-aligned, dependency-
            # closed window (min_repeats=1) is not yet *proof* of the IOS, but
            # if its fingerprint matches a sequence another client already
            # validated and the server already compiled, adopting it skips the
            # remaining recording iterations.  A one-repetition log of a
            # multi-input app admits several shifted windows, so every
            # alignment is probed — cache membership disambiguates.  A wrong
            # adoption is caught by the record-level comparison in the replay
            # phase and falls back (same safety net as a DAM deviation).
            for candidate in candidate_sequences(self.logs):
                cand_fp = ios_fingerprint(candidate.records)
                if cand_fp in cache:
                    ios, fp = candidate, cand_fp
                    self.cache_adopted = True
                    self.stats.cache_adoptions += 1
                    if self.tracer is not None:
                        self.tracer.instant(
                            self.trace_track, "cache_adopt", self.clock.t,
                            fp=cand_fp,
                        )
                    break
        self.search_seconds += _time.perf_counter() - t0
        self.searches_run += 1
        if ios is None:
            return
        self.ios = ios
        self._ios_calls = list(
            self.calls[ios.start_index : ios.start_index + len(ios)]
        )
        if cache is not None and fp is None:
            fp = ios_fingerprint(ios.records)
        self.ios_fp = fp
        # loop-carried tensors across the recorded repeats (KV-cache pytrees
        # and the like); a cache-adopting client recorded a single round, so
        # detection yields () and the cached program's pairs apply instead
        pairs = detect_loop_carried(self.calls, ios)
        ios.carried_pairs = pairs
        # recorded live payloads are only needed inside the detection horizon
        # (the last few repeats); for a stateful app every retained round
        # pins a full state pytree on the host, so drop the older ones
        horizon = ios.start_index - 2 * len(ios)
        for c in self.calls[: max(0, horizon)]:
            c.h2d_value = None
            c.d2h_value = None
        if self.verify:
            # fail fast on an unsound recording before the server compiles
            # (and caches, and possibly shares) an executable from it
            from repro.analysis.verify import raise_on_errors, verify_calls

            raise_on_errors(verify_calls(self._ios_calls, pairs))
        self.server.prepare_replay(
            self._ios_calls,
            client_id=self.client_id,
            fingerprint=fp,
            carried_pairs=pairs,
        )
        program = self.server.context(self.client_id).replay.program
        self._configure_carried(program)
        if self.partition is not None:
            from repro.partition.adaptive import AdaptiveReplanner
            from repro.partition.segments import SegmentGraph

            # a stateful IOS partitions too: building the graph with the
            # carried pairs constrains the planner to carried-feasible cuts
            # (device prefix = the stateless prologue, server suffix = the
            # KV-touching core with donated carried buffers), so the state
            # stays server-resident across any plan it ever returns
            self.replanner = AdaptiveReplanner(
                SegmentGraph(
                    self._ios_calls, carried_pairs=program.carried_pairs
                ),
                self.client_device,
                self.server.device,
                rtt_s=self.network.base_rtt_s,
                power=self.meter.power_model,
                config=self.partition,
                input_wire_divisor=self.input_wire_divisor,
                tracer=self.tracer,
                trace_track=self.trace_track,
            )
            self._install_plan(
                self.replanner.initial_plan(
                    self.network.bandwidth_at(self.clock.t), self.clock.t
                )
            )
        self.mode = MODE_REPLAYING
        self._replay_pos = 0
        if self.tracer is not None:
            self.tracer.instant(
                self.trace_track, "ios_locked", self.clock.t,
                fp=self.ios_fp or "", adopted=self.cache_adopted,
            )

    def _configure_carried(self, program: ReplayProgram) -> None:
        """Adopt a (possibly cached) program's loop-carried spec: build the
        ordinal maps and seed the app-facing placeholders from the state the
        recording phase left behind."""
        self._carried_in_map = {
            i: idx for idx, (i, _) in enumerate(program.carried_pairs)
        }
        self._carried_out_map = {
            j: idx for idx, (_, j) in enumerate(program.carried_pairs)
        }
        self._wire_out_index = {
            j: w for w, j in enumerate(program.wire_out)
        }
        self._carried_placeholders = {}
        self._fresh_carried = {}
        if not program.carried_pairs:
            return
        if self.ios is not None and not self.ios.carried_pairs:
            self.ios.carried_pairs = program.carried_pairs
        bound = self.server.context(self.client_id).replay
        env = self.server.context(self.client_id).env
        for idx, (_, j) in enumerate(program.carried_pairs):
            v = env.get(bound.d2h_addrs[j])
            if v is not None:
                # a writable copy: after a DAM fallback the materializer
                # refreshes the app-held handle in place
                self._carried_placeholders[idx] = np.array(v, copy=True)

    def _claim_stream_key(self, key: Optional[str]) -> None:
        """Swap the stream executor's cache claim: release the previous
        derived-key claim (if any) and claim ``key`` — so the base program
        behind an installed :class:`PipelinedSegmentedReplay` stays pinned
        for exactly the executor's lifetime."""
        cache = self.server.replay_cache
        if cache is None or not hasattr(cache, "claim"):
            self._stream_claim = None
            return
        if self._stream_claim is not None:
            cache.release(self._stream_claim)
            self._stream_claim = None
        if key is not None:
            cache.claim(key)
            self._stream_claim = key

    def _install_plan(self, plan: "SplitPlan") -> None:
        """Adopt a split plan; a full-server plan reverts to classic replay.

        Carried state survives every swap: the stateful executables refresh
        the env's carried buffers after each step, and each install re-seeds
        the adopting binding from the env — so the live KV cache migrates
        between the whole-program and the segmented executable without ever
        visiting the host."""
        if plan.is_full_server:
            if self.split_plan is not None and self.stateful_replay:
                # the split suffix held the live state; hand it back to the
                # whole-program binding before classic replay resumes
                ctx = self.server.context(self.client_id)
                if ctx.replay is not None and self.server.execute:
                    ctx.replay.seed_carried(ctx.env)
            self.split_plan = None
            self.pipelined_exec = None
            self._claim_stream_key(None)
            return
        pairs = self.ios.carried_pairs if self.ios is not None else ()
        if self.verify:
            # statically prove the plan against the IOS segment graph (and
            # its derived cache key) before the server compiles segments
            from repro.analysis.plancheck import (
                verify_cache_key,
                verify_plan_for_calls,
            )
            from repro.analysis.verify import raise_on_errors

            diags = verify_plan_for_calls(self._ios_calls, plan, pairs)
            if self.ios_fp is not None:
                from repro.partition.segments import SegmentGraph

                diags.extend(verify_cache_key(
                    f"{self.ios_fp}|{plan.signature()}",
                    n_ops=SegmentGraph(self._ios_calls).n_ops,
                ))
            raise_on_errors(diags)
        self.split_plan = plan
        self.server.prepare_split(
            self._ios_calls, plan, client_id=self.client_id,
            fingerprint=self.ios_fp,
            carried_pairs=pairs,
        )
        if self.partition is not None and self.partition.pipelined:
            self.pipelined_exec = PipelinedSegmentedReplay(
                self.server.context(self.client_id).split,
                self.client_device,
                self.server,
                self.network,
                input_wire_divisor=self.input_wire_divisor,
                t0=self.clock.t,
                tracer=self.tracer,
                trace_track=self.trace_track,
            )
            self._claim_stream_key(
                f"{self.ios_fp}|{plan.signature()}"
                if self.ios_fp is not None
                else None
            )
        else:
            self.pipelined_exec = None
            self._claim_stream_key(None)

    # -- replaying-phase handling ----------------------------------------------
    def _replay_call(self, call: InterceptedCall) -> Any:
        rec = call.record
        expected = self.ios.records[self._replay_pos]
        if rec != expected:
            return self._fallback(call)

        if self._replay_pos == 0:
            # STARTRRTO: new inference begins (Alg. 3 line 12)
            self.stats.replayed_records += len(self.ios)
            self._replay_prefix = []
            self._replay_inputs = []
            self._replay_outputs = None
            self._out_cursor = 0
            self._h2d_seen = 0
            self._split_output_local = []
            self._inputs_uploaded = False

        self._replay_pos = (self._replay_pos + 1) % len(self.ios)
        self._replay_prefix.append(call)

        if rec.category == CAT_H2D:
            ordinal = self._h2d_seen
            self._h2d_seen += 1
            if ordinal in self._carried_in_map:
                # loop-carried state: the server already holds it — in the
                # whole-program step executable or in the split plan's
                # donated server suffix, either way it never ships.  The app
                # threading back the handle we gave it costs nothing; any
                # other value is genuinely new state and ships as override.
                idx = self._carried_in_map[ordinal]
                ph = self._carried_placeholders.get(idx)
                v = call.h2d_value
                if ph is not None and (
                    v is ph or getattr(v, "base", None) is ph
                ):
                    self._local()
                else:
                    self._rpc(rec.payload_bytes, 32)
                    arr = np.asarray(v)
                    self._fresh_carried[idx] = arr
                    # the handle handed back at the paired D2H (and threaded
                    # by the app from then on) is a writable copy, so a DAM
                    # fallback can refresh it in place
                    self._carried_placeholders[idx] = np.array(
                        arr, copy=True
                    )
            elif self.split_plan is not None:
                # split replay: wire inputs stay on the device until a
                # segment schedule actually needs them on the wire
                self._local()
                self._replay_inputs.append(np.asarray(call.h2d_value))
            else:
                # the only client->server RPC left: ship the raw input
                self._rpc(rec.payload_bytes, 32)
                self._inputs_uploaded = True
                self._replay_inputs.append(np.asarray(call.h2d_value))
            if self._h2d_seen == len(self.ios.h2d_positions):
                if self.split_plan is not None:
                    self._run_split_replay()
                else:
                    fresh = self._fresh_carried or None
                    self._fresh_carried = {}
                    t_sub = self.clock.t
                    # cross-client batched backend when the edge server
                    # installed one (multi-tenant serving), solo otherwise
                    submit = self.replay_submit or (
                        lambda ins, t, fresh_carried=None: self.server.run_replay(
                            ins, t, self.client_id, fresh_carried=fresh_carried
                        )
                    )
                    if self.fault is not None and self.stateful_replay:
                        # the donated step is non-idempotent: retries ride
                        # the sequence-numbered at-most-once protocol
                        outs, done_at = self._reliable_step(
                            submit, self._replay_inputs, fresh
                        )
                    else:
                        outs, done_at = submit(
                            self._replay_inputs, self.clock.t,
                            fresh_carried=fresh,
                        )
                    self._note_step(self._replay_inputs, fresh)
                    self._replay_outputs = outs
                    self._replay_done_at = done_at
                    if self.tracer is not None:
                        self.tracer.span(
                            self.trace_track,
                            "replay_call",
                            t_sub,
                            max(done_at, t_sub),
                            fp=self.ios_fp or "",
                            batched=self.replay_submit is not None,
                        )
                    # a full-server plan must keep watching the link, or a
                    # bandwidth collapse could never swap it back to a split
                    self._maybe_replan()
            return "cudaSuccess"

        if rec.category == CAT_D2H:
            cursor = self._out_cursor
            self._out_cursor += 1
            if cursor in self._carried_out_map:
                # carried state is answered locally with a stable handle —
                # the live buffers stay on the server, nothing crosses the
                # network and nothing is copied back to the host
                self._local()
                idx = self._carried_out_map[cursor]
                ph = self._carried_placeholders.get(idx)
                if ph is None:
                    shape, dtype = call.out_avals[0]
                    ph = np.zeros(shape, dtype)
                    self._carried_placeholders[idx] = ph
                return ph
            # wait for the one-shot (or segmented) execution to finish
            self._wait_until(self._replay_done_at)
            if (
                cursor < len(self._split_output_local)
                and self._split_output_local[cursor]
            ):
                # this output was produced by a device-resident segment: the
                # download is a local memcpy, no network round trip
                self._local()
                return self._replay_outputs[
                    self._wire_out_index.get(cursor, cursor)
                ]
            t0 = self.clock.t
            dt = (
                self.network._rtt_at(self.clock.t)
                + self.network.transfer_time(rec.response_bytes, self.clock.t)
            )
            self.clock.advance(dt)
            self.meter.add(STATE_COMM, dt)
            self._account_network(1, rec.payload_bytes + rec.response_bytes)
            if self.tracer is not None:
                self.tracer.span(
                    self.trace_track, "replay_d2h", t0, t0 + dt,
                    bytes=rec.response_bytes,
                )
            return self._replay_outputs[self._wire_out_index.get(cursor, cursor)]

        # intermediate operator: answered from the recorded result, locally
        self._local()
        return expected.ret

    def _run_split_replay(self) -> None:
        """Execute the split plan: device segments run locally (device-class
        cost + inference-power accounting), server segments occupy the shared
        GPU, and boundary tensors ship with uplink overlapped against the
        device compute that follows their producers.  Afterwards the adaptive
        re-planner observes the live bandwidth and may swap plans."""
        from repro.partition.segments import (
            PLACE_SERVER,
            NetworkLink,
            compute_schedule,
        )

        ctx = self.server.context(self.client_id)
        bound = ctx.split
        t0 = self.clock.t
        sched = compute_schedule(
            bound.graph,
            self.split_plan,
            self.client_device,
            self.server.device,
            NetworkLink(self.network, self.input_wire_divisor),
            t0=t0,
            # the D2H records pay the real output downlink; modeling it here
            # would double-charge the shared ingress
            include_output_downlink=False,
        )
        fresh = self._fresh_carried or None
        self._fresh_carried = {}
        outs = bound.execute(
            self._replay_inputs, ctx.env, execute=self.server.execute,
            fresh_carried=fresh,
        )
        self._note_step(self._replay_inputs, fresh)
        # server segments occupy the shared GPU — through the co-tenant
        # segment batcher when the edge server installed one (same-segment
        # submissions of one shared IOS execute as one batched occupancy)
        server_segs = [
            s for s in self.split_plan.segments
            if s.placement == PLACE_SERVER
        ]
        completions: List[float] = []
        for seg, (start, dur) in zip(server_segs, sched.server_busy):
            if self.split_submit is not None:
                completions.append(self.split_submit(seg, dur, start))
            else:
                completions.append(self.server.occupy(dur, start))
            if self.tracer is not None:
                self.tracer.span(
                    f"{self.server.name}/gpu", "segment_exec",
                    start, start + dur,
                    client=self.client_id, ops=f"{seg.start}:{seg.end}",
                )
        # phase-integrated billing covers the body exactly once: overlapped
        # uplink is inside the inference draw (see Schedule.radio_only_seconds)
        self.meter.add(STATE_INFERENCE, sched.device_seconds)
        self.meter.add(STATE_COMM, sched.radio_only_seconds)
        self.meter.add(STATE_STANDBY, sched.wait_seconds)
        self.clock.advance(sched.body_seconds)
        if completions:
            # co-tenant GPU contention extended our server segments; with the
            # segment batcher the wait is our own segments' group completion,
            # without it the conservative shared-queue frontier
            horizon = (
                max(completions)
                if self.split_submit is not None
                else self.server.busy_until
            )
            if horizon > self.clock.t:
                self._wait_until(horizon)
        self._account_network(sched.crossings, sched.comm_bytes)
        if self.tracer is not None:
            self.tracer.span(
                self.trace_track, "cut_uplink",
                t0, t0 + sched.radio_only_seconds,
                bytes=sched.comm_bytes, crossings=sched.crossings,
            )
            self.tracer.span(
                self.trace_track, "device_exec",
                t0, t0 + sched.device_seconds,
                plan=self.split_plan.signature(),
            )
        self._split_output_local = list(sched.output_local)
        self._replay_outputs = outs
        self._replay_done_at = self.clock.t
        self._maybe_replan()

    def _maybe_replan(self) -> None:
        """Feed the live bandwidth to the adaptive re-planner; an adopted
        swap takes effect from the next inference (this inference's D2H
        locality is pinned by ``_split_output_local``)."""
        if self.replanner is None:
            return
        new_plan = self.replanner.observe(
            self.network.bandwidth_at(self.clock.t), self.clock.t
        )
        if new_plan is not None:
            self._install_plan(new_plan)

    def _fallback(self, call: InterceptedCall) -> Any:
        """Sequence deviation (DAM): ship the locally-answered prefix to the
        server for catch-up, revert to recording, re-search later."""
        self.fallbacks += 1
        self.mode = MODE_RECORDING
        # download + refresh the app-held carried-state handle from the live
        # stateful executable FIRST — while the binding that owns the true
        # state (split suffix or whole program) is still installed — then
        # drop the stream executor: infer_stream falls back to closed-loop
        # recording until a fresh lock reinstalls a plan (and an executor)
        if self._carried_in_map:
            self._materialize_carried_prefix()
        self.pipelined_exec = None
        self._claim_stream_key(None)
        # when the inputs never reached the server this inference (split mode
        # holds them back for the segment schedule), the catch-up batch must
        # carry the H2D calls too or the server replays against stale buffers
        skip = (CAT_H2D, CAT_D2H) if self._inputs_uploaded else (CAT_D2H,)
        prefix = [
            c for c in self._replay_prefix if c.record.category not in skip
        ]
        if prefix:
            payload = sum(c.record.payload_bytes for c in prefix)
            self._rpc(payload, 32)
            for c in prefix:
                self.server.exec_call(c, self.clock.t, self.client_id)
            self.logs.extend(c.record for c in prefix)
            self.calls.extend(prefix)
        self._replay_prefix = []
        self._replay_pos = 0
        self._h2d_seen = 0
        return self._record_call(call)

    def _carried_state_source(self) -> Optional[List[Any]]:
        """The live server-resident carried state: the split suffix's binding
        when a split plan is active (it advanced the state last), otherwise
        the whole-program binding's."""
        ctx = self.server.context(self.client_id)
        if (
            self.split_plan is not None
            and ctx.split is not None
            and ctx.split.carried_state is not None
        ):
            return ctx.split.carried_state
        if ctx.replay is not None:
            return ctx.replay.carried_state
        return None

    def _materialize_carried_prefix(self) -> None:
        """Before a catch-up after a mid-round deviation, turn the carried
        placeholder uploads in the prefix into the real server-resident
        values (the app only ever held handles).  The download is a real RPC
        — this is the price of deviating from a stateful IOS.  The state
        comes from whichever stateful executable ran last (the split plan's
        donated suffix or the whole program), so a pipelined split stream
        that deviates mid-stream refreshes the app's handle with the truth,
        not the lock-time snapshot."""
        state = self._carried_state_source()
        if state is None:
            return
        ordinal = 0
        for c in self._replay_prefix:
            if c.record.category != CAT_H2D:
                continue
            idx = self._carried_in_map.get(ordinal)
            ordinal += 1
            if idx is None:
                continue
            ph = self._carried_placeholders.get(idx)
            if not (
                c.h2d_value is ph or getattr(c.h2d_value, "base", None) is ph
            ):
                continue  # the app supplied real state itself
            arr = np.asarray(state[idx])
            self._rpc(64, arr.nbytes + 64)  # state download for catch-up
            c.h2d_value = arr
            if ph is not None and ph.shape == arr.shape:
                try:
                    # the app keeps threading its handle through the
                    # post-fallback recording rounds — give it the truth
                    ph[...] = arr
                except ValueError:  # read-only handle
                    pass
            self._carried_placeholders[idx] = arr

    # -- the sink ------------------------------------------------------------
    def __call__(self, call: InterceptedCall) -> Any:
        if self.variant != "rrto" or self.mode == MODE_RECORDING:
            return self._record_call(call)
        return self._replay_call(call)
