"""Serving engine: batched prefill + decode, plus RRTO record/replay serving
at the edge.

Two deployment modes:

* ``LocalServing`` — the plain engine (prefill -> KV-cached decode loop) used
  by the examples and smoke tests.

* ``RRTOServedLM`` — the paper's scenario mapped to LLM generation: a mobile
  client drives next-token computation through the *transparent offloading*
  stack.  The default (stateful) formulation offloads the KV-cached
  ``decode_step(token, pos, cache)`` app: every call executes the identical
  operator sequence (a Static Activation Model), the Operator Sequence
  Search locks it after a few recorded calls, and the loop-carried KV-cache
  pytree is detected across repeats and **donated** into a stateful replay
  executable — the cache stays server-resident, never crosses the network,
  and each replayed token costs the model's intrinsic O(1) step compute plus
  3 RPCs.  Outputs match ``LocalServing`` token-for-token (asserted by the
  fast-path test in tests/test_serving.py).  ``stateful=False`` keeps the
  seed formulation — ``next_token(padded_tokens, cur_len)`` over a static
  padded bucket, which recomputes the whole prefix every step (O(seq)
  per-token replay compute; see benchmarks/decode_scaling.py for the
  head-to-head).

* ``MultiClientServedLM`` — the multi-tenant edge deployment: N mobile
  clients run the same LM app against one shared
  :class:`~repro.serving.multitenant.RRTOEdgeServer`.  All clients emit the
  same IOS fingerprint, so the first client's Operator Sequence Search and
  replay compilation are amortized across the fleet (later clients adopt the
  cached IOS after a single recorded inference), and same-step replay
  submissions execute as one cross-client batched call.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.offload import OffloadableModel, OffloadSession
from repro.models.registry import get_model
from repro.serving.multitenant import RRTOEdgeServer


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray            # (B, steps)
    steps: int


class LocalServing:
    """Greedy batched generation against the family model API."""

    def __init__(self, cfg: ArchConfig, params=None, seed: int = 0):
        self.cfg = cfg
        self.model = get_model(cfg)
        self.params = (
            params
            if params is not None
            else self.model.init_params(jax.random.PRNGKey(seed), cfg)
        )
        self._prefill = jax.jit(
            lambda p, b, m: self.model.prefill(p, b, self.cfg, m),
            static_argnums=(2,),
        )
        self._step = jax.jit(
            lambda p, t, c, pos: self.model.decode_step(p, t, c, pos, self.cfg)
        )

    def generate(
        self,
        batch: Dict[str, np.ndarray],
        max_new_tokens: int,
        max_seq: Optional[int] = None,
    ) -> GenerationResult:
        tokens = np.asarray(batch["tokens"])
        b, s = tokens.shape
        max_seq = max_seq or (s + max_new_tokens)
        logits, cache = self._prefill(self.params, batch, max_seq)
        out: List[np.ndarray] = []
        nxt = jnp.argmax(logits[:, 0, : self.cfg.vocab], axis=-1).astype(jnp.int32)[
            :, None
        ]
        pos = s
        for _ in range(max_new_tokens):
            out.append(np.asarray(nxt))
            logits, cache = self._step(self.params, nxt, cache, jnp.int32(pos))
            nxt = jnp.argmax(logits[:, 0, : self.cfg.vocab], axis=-1).astype(
                jnp.int32
            )[:, None]
            pos += 1
        return GenerationResult(
            tokens=np.concatenate(out, axis=1), steps=max_new_tokens
        )


class RRTOServedLM:
    """LLM generation through the RRTO transparent-offloading stack.

    Single-client by default.  Pass ``edge`` (a shared
    :class:`~repro.serving.multitenant.RRTOEdgeServer`) plus a unique
    ``client_id`` to attach this client to a multi-tenant edge server instead
    of a private one — the session then shares that server's replay cache,
    GPU queue, ingress link and clock with its co-tenants.

    ``stateful=True`` (default) offloads the KV-cached decode step and
    threads the cache pytree through the offloading boundary; once the IOS
    locks, the engine detects the cache as loop-carried, compiles a
    donation-aware stateful replay executable, and each token replays as an
    O(1) step with the cache server-resident.  ``stateful=False`` keeps the
    seed prefix-recompute formulation for comparison."""

    def __init__(
        self,
        cfg: ArchConfig,
        *,
        system: str = "rrto",
        environment: Optional[str] = None,
        bucket_len: int = 64,
        batch: int = 1,
        seed: int = 0,
        min_repeats: int = 3,
        execute: Optional[bool] = None,
        params=None,
        edge: Optional[RRTOEdgeServer] = None,
        client_id: Optional[str] = None,
        partition=None,
        stateful: bool = True,
    ):
        if edge is not None and (environment is not None or execute is not None):
            # these are edge-server properties; a per-client override would be
            # silently ignored, so reject it loudly
            raise ValueError(
                "environment/execute are set on the RRTOEdgeServer in "
                "multi-tenant mode"
            )
        self.cfg = cfg
        self.bucket_len = bucket_len
        self.stateful = stateful
        model = get_model(cfg)
        params = (
            params
            if params is not None
            else model.init_params(jax.random.PRNGKey(seed), cfg)
        )

        if stateful:
            cache0 = model.init_cache(cfg, batch, bucket_len)
            self._cache_leaves, self._cache_treedef = jax.tree.flatten(cache0)
            treedef = self._cache_treedef

            def decode_step(p, token, pos, *cache_leaves):
                cache = jax.tree.unflatten(treedef, list(cache_leaves))
                logits, new_cache = model.decode_step(p, token, cache, pos, cfg)
                nxt = jnp.argmax(
                    logits[:, 0, : cfg.vocab], axis=-1
                ).astype(jnp.int32)
                return [nxt, *jax.tree.leaves(new_cache)]

            offloadable = OffloadableModel(
                name=f"{cfg.name}-decodestep",
                apply=decode_step,
                params=params,
                example_inputs=(
                    np.zeros((batch, 1), np.int32),
                    np.zeros((), np.int32),
                    *(np.asarray(leaf) for leaf in self._cache_leaves),
                ),
            )
        else:
            def next_token(p, padded_tokens, cur_len):
                logits = model.forward(p, {"tokens": padded_tokens}, cfg)
                idx = jnp.clip(cur_len - 1, 0, padded_tokens.shape[1] - 1)
                last = jax.lax.dynamic_slice_in_dim(logits, idx, 1, axis=1)
                return [
                    jnp.argmax(last[:, 0, : cfg.vocab], axis=-1).astype(jnp.int32)
                ]

            offloadable = OffloadableModel(
                name=f"{cfg.name}-nexttoken",
                apply=next_token,
                params=params,
                example_inputs=(
                    np.zeros((batch, bucket_len), np.int32),
                    np.zeros((), np.int32),
                ),
            )
        if edge is not None:
            if system != "rrto":
                raise ValueError("multi-tenant mode serves the rrto system only")
            self.session = edge.connect(
                offloadable, client_id=client_id, min_repeats=min_repeats,
                partition=partition,
            )
        else:
            self.session = OffloadSession(
                offloadable,
                system,
                environment=environment if environment is not None else "indoor",
                min_repeats=min_repeats,
                execute=execute if execute is not None else True,
                partition=partition,
            )

    # -- generation drivers -------------------------------------------------
    def start_generation(self, prompt: np.ndarray, max_new_tokens: int):
        """Initialize per-generation state; returns the driving cursor.

        Stateful mode feeds the prompt token-by-token through the offloaded
        decode step (prefill-via-decode: the cache warms up through the same
        IOS every subsequent token replays), then feeds each sampled token
        back.  The cache leaves the app threads are opaque handles once the
        replay turns stateful — the server advances the real state."""
        b, s = prompt.shape
        assert s + max_new_tokens <= self.bucket_len, "bucket overflow"
        return {
            "prompt": prompt,
            "b": b,
            "s": s,
            "state": [np.asarray(leaf) for leaf in self._cache_leaves],
            "tok": prompt[:, 0:1].astype(np.int32),
            "pos": 0,
            "out": [],
            "max_new": max_new_tokens,
        }

    def step_inputs(self, g) -> tuple:
        """The offload-session inputs for the next decode call."""
        return (g["tok"], np.int32(g["pos"]), *g["state"])

    def absorb_step(self, g, outputs: List[Any]) -> None:
        """Consume one decode call's outputs and advance the cursor."""
        nxt = np.asarray(outputs[0]).astype(np.int32)
        g["state"] = list(outputs[1:])
        pos = g["pos"]
        if pos + 1 < g["s"]:
            g["tok"] = g["prompt"][:, pos + 1 : pos + 2].astype(np.int32)
        else:
            g["out"].append(nxt[:, None])
            g["tok"] = nxt[:, None]
        g["pos"] = pos + 1

    def steps_total(self, g) -> int:
        return g["s"] + g["max_new"] - 1

    def generate(self, prompt: np.ndarray, max_new_tokens: int) -> GenerationResult:
        """Greedy generation; every decode call goes through the offloading
        stack (recording first, replaying once the sequence is identified —
        statefully, with the KV cache donated server-side, in the default
        formulation)."""
        if self.stateful:
            g = self.start_generation(prompt, max_new_tokens)
            for _ in range(self.steps_total(g)):
                res = self.session.infer(*self.step_inputs(g))
                self.absorb_step(g, res.outputs)
            return GenerationResult(
                tokens=np.concatenate(g["out"], axis=1), steps=max_new_tokens
            )
        b, s = prompt.shape
        assert s + max_new_tokens <= self.bucket_len, "bucket overflow"
        buf = np.zeros((b, self.bucket_len), np.int32)
        buf[:, :s] = prompt
        out: List[np.ndarray] = []
        cur = s
        for _ in range(max_new_tokens):
            res = self.session.infer(buf, np.int32(cur))
            nxt = np.asarray(res.outputs[0]).astype(np.int32)
            out.append(nxt[:, None])
            buf[:, cur] = nxt
            cur += 1
        return GenerationResult(
            tokens=np.concatenate(out, axis=1), steps=max_new_tokens
        )


class MultiClientServedLM:
    """N mobile clients generating with the same LM over one edge server.

    Every client runs the identical ``next_token`` app (same model, same
    parameters, its own prompt), so all of them produce the same IOS
    fingerprint: the first client to finish the Operator Sequence Search
    populates the shared replay cache, every later client adopts the cached
    IOS after a single recorded inference, and same-step replay submissions
    are batched into one GPU call by the edge server's
    :class:`~repro.serving.multitenant.ReplayBatcher`."""

    def __init__(
        self,
        cfg: ArchConfig,
        num_clients: int,
        *,
        bucket_len: int = 64,
        seed: int = 0,
        min_repeats: int = 3,
        execute: bool = True,
        environment: str = "indoor",
        cache_capacity: int = 8,
        batch_window_s: float = 2e-3,
        edge: Optional[RRTOEdgeServer] = None,
        stateful: bool = True,
        params=None,
    ):
        if num_clients < 1:
            raise ValueError(f"need at least one client, got {num_clients}")
        self.cfg = cfg
        self.bucket_len = bucket_len
        self.stateful = stateful
        model = get_model(cfg)
        # one app binary on every device: identical parameters, so the replay
        # executable (not just the IOS) is shareable verbatim — and in the
        # stateful formulation, same-round decode submissions run as one true
        # vmap-batched stateful step over the stacked per-client KV caches
        if params is None:
            params = model.init_params(jax.random.PRNGKey(seed), cfg)
        self.edge = edge or RRTOEdgeServer(
            execute=execute,
            cache_capacity=cache_capacity,
            batch_window_s=batch_window_s,
            environment=environment,
        )
        self.clients = [
            RRTOServedLM(
                cfg,
                bucket_len=bucket_len,
                batch=1,
                min_repeats=min_repeats,
                params=params,
                edge=self.edge,
                client_id=f"c{i}",
                stateful=stateful,
            )
            for i in range(num_clients)
        ]

    def generate(
        self, prompts: Sequence[np.ndarray], max_new_tokens: int
    ) -> List[GenerationResult]:
        """Lockstep greedy generation: one token per client per round, with
        replay-phase clients batched on the shared GPU."""
        if len(prompts) != len(self.clients):
            raise ValueError(
                f"{len(prompts)} prompts for {len(self.clients)} clients"
            )
        if self.stateful:
            return self._generate_stateful(prompts, max_new_tokens)
        bufs: List[np.ndarray] = []
        curs: List[int] = []
        for prompt in prompts:
            b, s = prompt.shape
            assert s + max_new_tokens <= self.bucket_len, "bucket overflow"
            buf = np.zeros((b, self.bucket_len), np.int32)
            buf[:, :s] = prompt
            bufs.append(buf)
            curs.append(s)
        outs: List[List[np.ndarray]] = [[] for _ in self.clients]
        for _ in range(max_new_tokens):
            round_inputs = {
                client.session.client_id: (bufs[i], np.int32(curs[i]))
                for i, client in enumerate(self.clients)
            }
            results = self.edge.run_round(round_inputs)
            for i, client in enumerate(self.clients):
                res = results[client.session.client_id]
                nxt = np.asarray(res.outputs[0]).astype(np.int32)
                outs[i].append(nxt[:, None])
                bufs[i][:, curs[i]] = nxt
                curs[i] += 1
        return [
            GenerationResult(
                tokens=np.concatenate(o, axis=1), steps=max_new_tokens
            )
            for o in outs
        ]

    def _generate_stateful(
        self, prompts: Sequence[np.ndarray], max_new_tokens: int
    ) -> List[GenerationResult]:
        """Stateful lockstep: every client advances its decode step once per
        round (prompts may differ in length, so positions diverge — the
        vmap-batched stateful executable maps over per-client ``pos`` and
        cache slices); clients whose generation completed drop out of the
        round."""
        gens = [
            client.start_generation(np.asarray(prompts[i]), max_new_tokens)
            for i, client in enumerate(self.clients)
        ]
        remaining = {
            client.session.client_id: (client, g)
            for client, g in zip(self.clients, gens)
        }
        while remaining:
            round_inputs = {
                cid: client.step_inputs(g)
                for cid, (client, g) in remaining.items()
            }
            results = self.edge.run_round(round_inputs)
            done: List[str] = []
            for cid, (client, g) in remaining.items():
                client.absorb_step(g, results[cid].outputs)
                if g["pos"] >= client.steps_total(g):
                    done.append(cid)
            for cid in done:
                del remaining[cid]
        return [
            GenerationResult(
                tokens=np.concatenate(g["out"], axis=1), steps=max_new_tokens
            )
            for g in gens
        ]
