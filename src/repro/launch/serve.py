"""Serving entry point: greedy generation with one arch, locally or through
the RRTO transparent-offloading stack, at published widths or reduced.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
        --system rrto --bucket-len 512 --prompt-len 128 --tokens 16

Two kinds of time are printed, and each says which it is.  ``host wall``
is ``time.perf_counter`` around work that ends with the token copied back
to the host, which waits for the device.  ``sim`` is the simulated clock:
the modelled wireless link and the cost model of the offloading stack, not
a device measurement.  ``chip_smoke.py`` at the repository root drives the
same functions on a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import pathlib
import time
from typing import List, Optional, Tuple

import jax
import numpy as np

from repro.configs import get_config, get_reduced_config
from repro.configs.base import ArchConfig
from repro.models.registry import get_model
from repro.serving.engine import LocalServing, RRTOServedLM

# the persistent compilation cache's one fixed place inside the checkout
# (listed in .gitignore); the path is part of the cache's key, so it never
# depends on a temporary name, a pid or the time
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
IN_REPO_CACHE = REPO_ROOT / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at one fixed directory and
    return it: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it
    itself, and nothing else is set here), else ``.jax_cache`` at the root
    of this checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(IN_REPO_CACHE))
    return str(IN_REPO_CACHE)


def init_params(cfg: ArchConfig, seed: int):
    """Random weights from ``seed``, made on the default device in one
    compiled call (no host copy of the model)."""
    model = get_model(cfg)
    params = jax.jit(model.init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg
    )
    return jax.block_until_ready(params)


def make_prompt(cfg: ArchConfig, batch: int, length: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab, (batch, length)).astype(np.int32)


@dataclasses.dataclass
class StepTime:
    """One offloaded decode call: the client's mode when it started, its
    host wall time, its RPC count and its simulated latency."""

    mode: str
    host_s: float
    rpcs: int
    sim_s: float


def timed_generate(
    served: RRTOServedLM, prompt: np.ndarray, max_new_tokens: int
) -> Tuple[np.ndarray, List[StepTime]]:
    """Stateful greedy generation through the offloading stack, one decode
    call at a time (the prompt goes through the decode step first).  Each
    call's host wall time ends with its token on the host."""
    g = served.start_generation(prompt, max_new_tokens)
    steps: List[StepTime] = []
    for _ in range(served.steps_total(g)):
        mode = served.session.client.mode
        t0 = time.perf_counter()
        res = served.session.infer(*served.step_inputs(g))
        served.absorb_step(g, res.outputs)
        steps.append(
            StepTime(mode, time.perf_counter() - t0, res.rpcs, res.wall_seconds)
        )
    return np.concatenate(g["out"], axis=1), steps


def summarize_steps(steps: List[StepTime]) -> dict:
    """Host wall time by phase: recording, the first replayed call (which
    compiles the replay executable), the second (the one-time state
    handoff), and the steady replayed calls after them."""
    rec = [s for s in steps if s.mode == "recording"]
    rep = [s for s in steps if s.mode == "replaying"]
    steady = rep[2:]
    return {
        "record_calls": len(rec),
        "record_host_s": sum(s.host_s for s in rec),
        "first_replay_host_s": rep[0].host_s if rep else None,
        "steady_calls": len(steady),
        "steady_host_s_per_call": (
            sum(s.host_s for s in steady) / len(steady) if steady else None
        ),
        "steady_rpcs": sorted({s.rpcs for s in steady}),
        "steady_sim_s_per_call": (
            sum(s.sim_s for s in steady) / len(steady) if steady else None
        ),
    }


def main(argv: Optional[List[str]] = None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--system", default="local",
                    choices=["local", "rrto", "cricket", "semi_rrto"])
    ap.add_argument("--environment", default="indoor", choices=["indoor", "outdoor"])
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--bucket-len", type=int, default=None,
                    help="KV-cache length (default: prompt-len + tokens)")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    configure_compile_cache()
    cfg = get_reduced_config(args.arch) if args.reduced else get_config(args.arch)
    bucket = args.bucket_len or args.prompt_len + args.tokens
    prompt = make_prompt(cfg, args.batch, args.prompt_len, args.seed)
    params = init_params(cfg, args.seed)

    if args.system == "local":
        engine = LocalServing(cfg, params=params)
        t0 = time.perf_counter()
        res = engine.generate({"tokens": prompt}, args.tokens, max_seq=bucket)
        host_s = time.perf_counter() - t0
        print(f"[serve] local generation: {res.tokens.tolist()}")
        print(f"[serve] host wall {host_s:.3f} s (compile included)")
        return {"tokens": res.tokens.tolist(), "host_s": host_s}

    served = RRTOServedLM(
        cfg,
        system=args.system,
        environment=args.environment,
        bucket_len=bucket,
        batch=args.batch,
        params=params,
    )
    tokens, steps = timed_generate(served, prompt, args.tokens)
    phases = summarize_steps(steps)
    mode = served.session.client.mode
    print(f"[serve] {args.system} generation: {tokens.tolist()}")
    print(f"[serve] mode={mode}; RPCs/call first={steps[0].rpcs} "
          f"last={steps[-1].rpcs}")
    print(f"[serve] host wall: {phases}")
    print(f"[serve] sim latency/call last={steps[-1].sim_s * 1e3:.2f} ms "
          "(simulated link + server cost model)")
    return {
        "tokens": tokens.tolist(),
        "rpcs_first": steps[0].rpcs,
        "rpcs_last": steps[-1].rpcs,
        "mode": mode,
        "phases": phases,
    }


if __name__ == "__main__":
    main()
