"""The closed loop every serving driver shares: clients, rounds, records.

A driver (``bench/drivers/<name>.py``) builds the clients on the program's
public entry points and says how one round of calls is made; this module
runs set-up and the measured window on top of that and keeps one record
per call and per request.  Every call is timed with the host clock from its
entry into the program until its token is on the host.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import numpy as np

REPLAYING = "replaying"


@dataclasses.dataclass
class CallRecord:
    """One client's offloaded decode call (in a batched round, each member
    has one, all with the round's time)."""

    round_id: int
    host_s: float
    kv_len: int          # valid cache positions after the call
    output: bool         # the call produced an output token
    rpcs: int
    mode: str            # the client's mode when the call began
    traced: bool


@dataclasses.dataclass
class RequestRecord:
    prompt: np.ndarray   # (1, P)
    new_tokens: int
    t_begin: float
    t_first: Optional[float] = None
    t_end: Optional[float] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    failed: bool = False
    counted: bool = False  # begun inside the measured window
    client: int = 0

    @property
    def ttft_s(self) -> Optional[float]:
        return None if self.t_first is None else self.t_first - self.t_begin


@dataclasses.dataclass
class RunData:
    """Everything a metric reader may read about one run."""

    spec: dict                       # the configuration file
    calls: List[CallRecord]          # the window's calls
    requests: List[RequestRecord]    # requests begun in the window
    window_s: float
    setup: Dict[str, float]          # setup_s, record_s, compile_s
    counters: Dict[str, float]       # program counters over the window
    trace: Optional[dict]            # bench.trace_reduce output (trace 1)
    peak: Optional[Dict[str, float]]  # the device's peaks (bench/peaks.json)


class CompileCounter:
    """Counts JAX's compile events (tracing, lowering, backend compile,
    persistent-cache reads) by name, from a monitoring listener."""

    def __init__(self):
        self.counts: Dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if "compil" in event:
            self.counts[event] = self.counts.get(event, 0) + 1

    def total(self) -> int:
        return sum(self.counts.values())

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)


class Client:
    """One mobile client: an ``RRTOServedLM`` and its generation cursor."""

    def __init__(self, cid: int, lm, traffic):
        self.cid = cid
        self.lm = lm
        self.traffic = traffic
        self.next_index = 0
        self.req: Optional[RequestRecord] = None
        self.g = None

    @property
    def session(self):
        return self.lm.session

    @property
    def mode(self) -> str:
        return self.lm.session.client.mode

    def begin(self, request, counted: bool) -> RequestRecord:
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.new_request"):
            self.g = self.lm.start_generation(request.prompt,
                                              request.new_tokens)
        self.req = RequestRecord(request.prompt, request.new_tokens, t,
                                 counted=counted, client=self.cid)
        return self.req

    def begin_next(self, counted: bool) -> RequestRecord:
        req = self.traffic.request(self.cid, self.next_index)
        self.next_index += 1
        return self.begin(req, counted)

    def inputs(self) -> tuple:
        return self.lm.step_inputs(self.g)

    def absorb(self, res, round_id: int, host_s: float,
               traced: bool) -> CallRecord:
        """Take one call's result; returns its record.  A call the client
        did not serve in replay (a fallback to recording) fails the
        request."""
        g = self.g
        pos = g["pos"]
        n_out = len(g["out"])
        with jax.profiler.TraceAnnotation("bench.absorb"):
            self.lm.absorb_step(g, res.outputs)
        t = time.perf_counter()
        req = self.req
        output = len(g["out"]) > n_out
        if output:
            req.tokens.append(int(np.asarray(g["out"][-1]).reshape(-1)[0]))
            if req.t_first is None:
                req.t_first = t
        if res.mode != REPLAYING or self.mode != REPLAYING:
            req.failed = True
        if g["pos"] >= self.lm.steps_total(g):
            req.t_end = t
            self.req = None
        return CallRecord(round_id, host_s, pos + 1, output, int(res.rpcs),
                          res.mode, traced)


class Profiler:
    """Starts and stops JAX's profiler around the first ``seconds`` of the
    window, into ``log_dir``, with the Python tracer off."""

    def __init__(self, log_dir: str, seconds: float):
        self.log_dir = log_dir
        self.seconds = seconds
        self.t_start: Optional[float] = None
        self.active = False

    def start(self) -> None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.t_start = time.perf_counter()
        self.active = True

    def maybe_stop(self) -> None:
        if self.active and time.perf_counter() - self.t_start >= self.seconds:
            self.stop()

    def stop(self) -> None:
        if self.active:
            jax.profiler.stop_trace()
            self.active = False


class ServingDriver:
    """Set-up and the measured window of a closed loop of clients.

    A subclass builds ``self.clients`` and implements ``step``: one round in
    which every listed client makes one call through the program, returning
    each client's ``InferenceResult``."""

    annotation = "bench.infer"

    def __init__(self, program_cfg, params, traffic, bucket_len: int,
                 log, compiles: CompileCounter):
        self.cfg = program_cfg
        self.params = params
        self.traffic = traffic
        self.bucket_len = bucket_len
        self.log = log
        self.compiles = compiles
        self.clients: List[Client] = []
        self.calls: List[CallRecord] = []
        self.requests: List[RequestRecord] = []
        self.setup_calls: List[CallRecord] = []
        self.setup_compile_s = 0.0
        self.rounds = 0

    # -- what a driver provides ---------------------------------------------
    def build(self) -> None:
        raise NotImplementedError

    def step(self, clients: List[Client],
             inputs: Dict[int, tuple]) -> Dict[int, object]:
        raise NotImplementedError

    def counters(self) -> Dict[str, float]:
        """The program's own counters that the metrics read."""
        out: Dict[str, float] = {}
        servers = {id(c.session.server): c.session.server
                   for c in self.clients}
        out["compile_count"] = sum(s.compile_count for s in servers.values())
        out["fallbacks"] = sum(c.session.client.fallbacks
                               for c in self.clients)
        return out

    def warm_check(self) -> None:
        """Raise unless set-up has used every shape the window will use."""

    def close(self) -> None:
        self.clients = []
        self.params = None

    # -- shared loop ----------------------------------------------------------
    def _round(self, clients: List[Client], sink: Optional[list], *,
               traced: bool = False) -> List[CallRecord]:
        """One round; its call records go to ``sink`` (None: nowhere)."""
        inputs = {c.cid: c.inputs() for c in clients}
        n0 = self.compiles.total()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(self.annotation):
            results = self.step(clients, inputs)
        host_s = time.perf_counter() - t0
        compiled = self.compiles.total() > n0
        rid = self.rounds
        self.rounds += 1
        recs = [c.absorb(results[c.cid], rid, host_s, traced)
                for c in clients]
        if sink is not None:
            sink.extend(recs)
        if sink is self.setup_calls and compiled and all(
                r.mode == REPLAYING for r in recs):
            self.setup_compile_s += host_s
        return recs

    def warm_up(self, requests_per_client: int = 2,
                max_rounds: int = 400) -> None:
        """Serve short requests until every shape the window uses has run:
        the first client alone records, locks and compiles its replay
        executable; then every client serves ``requests_per_client``
        requests (the others adopt the cached sequence after one recorded
        call), so batched rounds of the window's width run, and a request
        begun on a replaying client uploads a fresh cache."""
        self._serve_warmup(self.clients[:1], 1, max_rounds)
        self._serve_warmup(self.clients, requests_per_client, max_rounds)
        modes = [c.mode for c in self.clients]
        if any(m != REPLAYING for m in modes):
            raise RuntimeError(f"clients not replaying after warm-up: {modes}")
        self.warm_check()

    def _serve_warmup(self, clients: List[Client], n: int,
                      max_rounds: int) -> None:
        done = {c.cid: 0 for c in clients}
        for _ in range(max_rounds):
            for c in clients:
                if c.req is None and done[c.cid] < n:
                    c.begin(self.traffic.warmup_request(c.cid), counted=False)
            busy = [c for c in clients if c.req is not None]
            if not busy:
                return
            self._round(busy, self.setup_calls)
            for c in busy:
                if c.req is None:
                    done[c.cid] += 1
        raise RuntimeError(f"warm-up did not finish in {max_rounds} rounds")

    def run_window(self, seconds: float,
                   profiler: Optional[Profiler] = None) -> float:
        """Closed loop for ``seconds``: each client begins its next request
        as soon as its last one ends.  Returns the window's length."""
        t_start = time.perf_counter()
        t_close = t_start + seconds
        if profiler is not None:
            profiler.start()
        while time.perf_counter() < t_close:
            for c in self.clients:
                if c.req is None:
                    self.requests.append(c.begin_next(counted=True))
            traced = profiler is not None and profiler.active
            self._round(self.clients, self.calls, traced=traced)
            if profiler is not None:
                profiler.maybe_stop()
        window = time.perf_counter() - t_start
        if profiler is not None:
            profiler.stop()
        return window

    def finish_first_tokens(self, max_rounds: int = 2000) -> None:
        """After the window closes, keep the loop going (uncounted) until
        every request begun in the window has its first token, so the time
        to first token of the last ones counts its whole wait."""
        for _ in range(max_rounds):
            if all(r.t_first is not None or r.failed for r in self.requests):
                return
            for c in self.clients:
                if c.req is None:
                    c.begin_next(counted=False)
            self._round(self.clients, None)
        raise RuntimeError("requests begun in the window never produced a "
                           "first token")
