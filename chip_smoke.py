#!/usr/bin/env python3
"""Chip smoke test: the RRTO edge server's main path on a TPU.

Runs qwen3-0.6b at its published widths (28 layers, d_model 1024, 16 query
and 8 KV heads of 128, d_ff 3072, vocab 151936), in bfloat16 with random
weights from a seed, through the entry points a user calls:

  load    the weights, made on the chip;
  local   ``LocalServing`` (prefill, then the KV-cached decode step under
          ``jax.jit``): what the served tokens are compared with;
  served  one client through ``RRTOServedLM`` (``OffloadSession`` ->
          ``RRTOClient`` -> ``OffloadServer``) with a 512-position KV
          bucket: a 128-token prompt goes through the decode step, then 16
          new tokens.  Record -> lock -> stateful replay.  Checks: the
          client replays, a steady replayed call costs 3 RPCs, the compiled
          replay executable holds the decode-attention kernel, the weights
          are device arrays on the chip, and the tokens equal ``local``'s
          up to a near-tie of the reference's logits (see TOKEN_MARGIN);
  logits  a decode step that returns its logits, offloaded the same way:
          the replayed step's logits against a direct ``jax.jit`` of the
          same decode step on the same inputs;
  multi   4 clients through ``MultiClientServedLM`` on one
          ``RRTOEdgeServer``, whose rounds run the ``jax.vmap``-batched
          donated step: each client's tokens equal its prompt's solo tokens,
          up to a near-tie as above.

``--fleet4`` runs only the fleet phase, on four chips: ``EdgeFleet(4)``
with each replica's server on its own chip and stateful decode sessions,
one of them migrated r0 -> r1 mid-stream, against the same session never
migrated (tokens and carried KV cache).

Every time printed is host wall time (``time.perf_counter``) around work
that ends with results on the host, which waits for the device.  The last
line of standard output is one JSON object.  The script exits nonzero, and
prints no result, when JAX finds no TPU or a check fails.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --fleet4    # four chips
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

ARCH = "qwen3-0.6b"
SEED = 0
BUCKET = 512          # KV positions per client (a multiple of the kernel tile)
PROMPT_LEN = 128
NEW_TOKENS = 16
MULTI_CLIENTS = 4
MULTI_PROMPT_LEN = 32
MULTI_NEW_TOKENS = 8
LOGIT_STEPS = 12      # decode calls of the logits probe (record, then replay)
FLEET_PROMPT_LEN = 32
FLEET_NEW_TOKENS = 8
FLEET_MIGRATE_AT = 20  # decode call before which the session moves r0 -> r1

# Replayed vs direct-jit logits.  Both run the same bf16 decode step on the
# same chip, but the replay executable is rebuilt from the recorded
# operators, so XLA fuses (and rounds) it differently.  The logits leave
# the head matmul as bf16 values: at magnitudes 4-16 one bf16 ulp is
# 2**-5-2**-4, so the two may differ by an ulp or two (0.0625 measured on
# a v5e).  0.1 admits that and nothing more; a step in fp8 (ulp 2**-1 at
# those magnitudes) would miss by far more.
LOGIT_ATOL = 0.1

# Served tokens against the plain engine.  The served client feeds its
# prompt through the decode step one token at a time; LocalServing prefills
# it in one pass.  In bf16 the two paths round differently, so where the
# reference's two largest logits nearly tie, the greedy token can flip and
# the sequences part from there.  So the tokens must be equal up to the
# first position where they part, and there both tokens must lie within
# TOKEN_MARGIN of the largest logit of a teacher-forced full forward pass
# of the same weights; every served token is held to that reference too.
# The same holds for a batched client against its solo run.  The top
# logits of ~150k unit-variance logits are ~4-5, where one bf16 rounding is
# ~0.02 and the two paths differ by a few such roundings per layer; 0.25 is
# a quarter of one logit unit, and a path in fp8 (one rounding ~0.25 there)
# would miss.
TOKEN_MARGIN = 0.25


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def host_s(t0: float) -> str:
    return f"{time.perf_counter() - t0:.3f} s host wall"


def device_report(jax) -> dict:
    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_served(jax, cfg, params, prompt, local_tokens):
    from repro.launch.hlo_analysis import has_tpu_kernel
    from repro.launch.serve import summarize_steps, timed_generate
    from repro.serving.engine import RRTOServedLM

    t0 = time.perf_counter()
    served = RRTOServedLM(cfg, bucket_len=BUCKET, params=params)
    log(f"served: session traced and weights uploaded in {host_s(t0)}")
    t0 = time.perf_counter()
    tokens, steps = timed_generate(served, prompt, NEW_TOKENS)
    log(f"served: {len(steps)} decode calls in {host_s(t0)}")
    phases = summarize_steps(steps)
    log(f"served: record {phases['record_calls']} calls "
        f"{phases['record_host_s']:.3f} s host wall; first replay (compiles "
        f"the replay executable) {phases['first_replay_host_s']:.3f} s host "
        f"wall; steady replay {phases['steady_calls']} calls "
        f"{phases['steady_host_s_per_call'] * 1e3:.3f} ms/call host wall")

    client = served.session.client
    server = served.session.server
    check(client.mode == "replaying", f"client mode {client.mode!r}")
    check(client.stateful_replay, "replay is not stateful")
    check(phases["steady_rpcs"] == [3],
          f"steady replayed calls cost {phases['steady_rpcs']} RPCs, not 3")

    ctx = server.context(client.client_id)
    bound = ctx.replay
    params_flat = [ctx.env[a] for a in bound.param_addrs]
    chip = jax.devices()[0]
    check(
        all(isinstance(p, jax.Array) and p.devices() == {chip}
            for p in params_flat),
        "replay parameters are not device arrays on the chip",
    )
    wire = [jax.ShapeDtypeStruct(s, d) for s, d in bound.program.wire_in_avals]
    t0 = time.perf_counter()
    hlo = bound.program.step_fn.lower(
        params_flat, wire, bound.carried_state
    ).compile().as_text()
    log(f"served: replay executable re-lowered for inspection in {host_s(t0)}")
    for kernel in ("decode_attention", "rmsnorm"):
        check(has_tpu_kernel(hlo, kernel),
              f"{kernel} kernel not in the compiled replay executable")
    log("served: compiled replay executable holds the decode_attention and "
        "rmsnorm kernels (tpu_custom_call)")

    log(f"served tokens: {tokens.tolist()}")
    log(f"local tokens:  {local_tokens.tolist()}")
    check_tokens(jax, cfg, params, prompt, tokens, local_tokens,
                 "served", "LocalServing")
    return served


def check_tokens(jax, cfg, params, prompt, got, want, who, against):
    """Hold the (1, n) tokens ``got`` to ``want``: equal up to the first
    position where they part, and there both tokens lie within TOKEN_MARGIN
    of the largest logit of a teacher-forced full forward pass (a near-tie
    that bf16 rounding may break either way).  Every token of ``got`` is
    held to that reference too."""
    n = got.shape[1]
    seq = np.concatenate([prompt, got[:, :-1]], axis=1)
    ref = forward_logits(jax, cfg, params, seq)[prompt.shape[1] - 1:]
    margins = ref.max(-1) - ref[np.arange(n), got[0]]
    same = int(np.argmin(np.append(got[0] == want[0], False)))
    log(f"{who}: tokens equal {against}'s for the first {same} of {n}; "
        f"teacher-forced margin of each token below the reference's largest "
        f"logit: {margins.tolist()}")
    check(float(margins.max()) <= TOKEN_MARGIN,
          f"a {who} token is {margins.max()} below the reference's largest "
          f"logit (> {TOKEN_MARGIN})")
    if same < n:
        # the prefixes agree up to here, so this reference row is the one
        # both sequences chose their token from
        parted = float(ref[same].max() - ref[same, want[0, same]])
        log(f"{who}: parts from {against} at token {same}, where the two "
            f"tokens lie {float(margins[same])} and {parted} below the "
            f"reference's largest logit")
        check(parted <= TOKEN_MARGIN,
              f"{who} parts from {against} at token {same}, where "
              f"{against}'s token is {parted} below the reference's largest "
              f"logit (> {TOKEN_MARGIN}): not a near-tie")


def forward_logits(jax, cfg, params, seq):
    """Float32 logits of the full forward pass over ``seq`` (1, S): the
    plain prefill math, one row per position."""
    from repro.models.registry import get_model

    model = get_model(cfg)
    fwd = jax.jit(lambda p, t: model.forward(p, {"tokens": t}, cfg))
    return np.asarray(fwd(params, seq)[0, :, : cfg.vocab], np.float32)


def phase_logits(jax, cfg, params):
    """Offload a decode step that returns its logits, feed it the same
    tokens as a direct jit of the model's decode step, and compare the
    logits of every replayed call."""
    import jax.numpy as jnp

    from repro.core.offload import OffloadableModel, OffloadSession
    from repro.launch.serve import make_prompt
    from repro.models.registry import get_model

    model = get_model(cfg)
    cache0 = model.init_cache(cfg, 1, BUCKET)
    leaves0, treedef = jax.tree.flatten(cache0)

    def logits_step(p, token, pos, *cache_leaves):
        cache = jax.tree.unflatten(treedef, list(cache_leaves))
        logits, new_cache = model.decode_step(p, token, cache, pos, cfg)
        return [logits[:, 0, : cfg.vocab], *jax.tree.leaves(new_cache)]

    host_leaves = [np.asarray(x) for x in leaves0]
    t0 = time.perf_counter()
    session = OffloadSession(
        OffloadableModel(
            name=f"{cfg.name}-logits", apply=logits_step, params=params,
            example_inputs=(
                np.zeros((1, 1), np.int32), np.zeros((), np.int32),
                *host_leaves,
            ),
        ),
        "rrto",
    )
    direct = jax.jit(
        lambda p, t, c, pos: model.decode_step(p, t, c, pos, cfg)
    )
    tokens = make_prompt(cfg, 1, LOGIT_STEPS, SEED + 1)
    state = list(host_leaves)
    cache = cache0
    diffs = []
    direct_logits = []
    for pos in range(LOGIT_STEPS):
        tok = tokens[:, pos : pos + 1]
        replaying = session.client.mode == "replaying"
        res = session.infer(tok, np.int32(pos), *state)
        state = list(res.outputs[1:])
        ref, cache = direct(params, jnp.asarray(tok), cache, jnp.int32(pos))
        ref = np.asarray(ref[:, 0, : cfg.vocab], np.float32)
        got = np.asarray(res.outputs[0], np.float32)
        check(got.shape == ref.shape and np.isfinite(got).all(),
              "replayed logits are not finite or have the wrong shape")
        direct_logits.append(ref[0])
        if replaying:
            diffs.append(float(np.max(np.abs(got - ref))))
    log(f"logits: {len(diffs)} replayed calls in {host_s(t0)}; max |replay - "
        f"direct jit| per call: {diffs}")
    fwd = forward_logits(jax, cfg, params, tokens)
    gap = float(np.max(np.abs(np.stack(direct_logits) - fwd)))
    log(f"logits: max |direct-jit decode step - full forward pass| over "
        f"{LOGIT_STEPS} positions: {gap}")
    check(session.client.mode == "replaying" and len(diffs) >= 4,
          "the logits probe never reached stateful replay")
    worst = max(diffs)
    check(worst <= LOGIT_ATOL,
          f"replayed logits differ from the direct jit by {worst} > {LOGIT_ATOL}")
    return worst


def phase_multi(jax, cfg, params, served):
    from repro.launch.serve import make_prompt
    from repro.serving.engine import MultiClientServedLM

    prompts = [
        make_prompt(cfg, 1, MULTI_PROMPT_LEN, SEED + 10 + i)
        for i in range(MULTI_CLIENTS)
    ]
    t0 = time.perf_counter()
    solo = [served.generate(p, MULTI_NEW_TOKENS).tokens for p in prompts]
    log(f"multi: {MULTI_CLIENTS} solo generations on the served client in "
        f"{host_s(t0)}")
    del served
    gc.collect()

    t0 = time.perf_counter()
    multi = MultiClientServedLM(
        cfg, MULTI_CLIENTS, bucket_len=BUCKET, params=params
    )
    log(f"multi: {MULTI_CLIENTS} clients connected in {host_s(t0)}")
    t0 = time.perf_counter()
    results = multi.generate(prompts, MULTI_NEW_TOKENS)
    log(f"multi: {MULTI_PROMPT_LEN + MULTI_NEW_TOKENS - 1} lockstep rounds "
        f"in {host_s(t0)}")
    batcher = multi.edge.batcher
    modes = [c.session.client.mode for c in multi.clients]
    log(f"multi: vmap batches {batcher.vmap_batches}, modes {modes}")
    check(all(m == "replaying" for m in modes), f"client modes {modes}")
    check(batcher.vmap_batches > 0, "no vmap-batched round ran")
    for i, (res, want, prompt) in enumerate(zip(results, solo, prompts)):
        log(f"multi: client c{i} {res.tokens.tolist()} solo {want.tolist()}")
        # the batched step maps the decode step over the clients, which
        # rounds in bf16 differently from the solo step
        check_tokens(jax, cfg, params, prompt, res.tokens, want,
                     f"multi client c{i}", "its solo run")


def run_one_chip(jax):
    from repro.configs import get_config
    from repro.launch.serve import init_params, make_prompt
    from repro.serving.engine import LocalServing

    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params = init_params(cfg, SEED)
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    log(f"load: {n_params} parameters ({cfg.dtype}) made on the chip in "
        f"{host_s(t0)}")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"load: memory_stats bytes_in_use={stats.get('bytes_in_use')} "
        f"bytes_limit={stats.get('bytes_limit')}")

    prompt = make_prompt(cfg, 1, PROMPT_LEN, SEED)
    local = LocalServing(cfg, params=params)
    t0 = time.perf_counter()
    local_tokens = local.generate(
        {"tokens": prompt}, NEW_TOKENS, max_seq=BUCKET
    ).tokens
    log(f"local: prefill {PROMPT_LEN} + {NEW_TOKENS} decode steps in "
        f"{host_s(t0)} (compile included)")

    served = phase_served(jax, cfg, params, prompt, local_tokens)
    phase_logits(jax, cfg, params)
    phase_multi(jax, cfg, params, served)
    stats = jax.devices()[0].memory_stats() or {}
    log(f"end: memory_stats peak_bytes_in_use="
        f"{stats.get('peak_bytes_in_use')}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def run_fleet4(jax):
    from repro.configs import get_config
    from repro.launch.serve import init_params, make_prompt
    from repro.serving import EdgeFleet, RRTOServedLM

    devices = jax.devices()
    check(len(devices) == 4, f"--fleet4 needs 4 chips, found {len(devices)}")
    cfg = get_config(ARCH)
    params = init_params(cfg, SEED)
    fleet = EdgeFleet(4, hedging=False)
    placed = [rep.edge.server.jax_device for rep in fleet.replicas]
    check(len({d.id for d in placed}) == 4, f"replica devices {placed}")
    log(f"fleet: replicas on devices {[d.id for d in placed]}")

    prompt = make_prompt(cfg, 1, FLEET_PROMPT_LEN, SEED)
    # "base" never moves; "moved" starts beside it on r0 and migrates to r1;
    # r2 and r3 each serve the same prompt from their own chip
    homes = {"base": 0, "moved": 0, "s2": 2, "s3": 3}
    sessions = {
        cid: RRTOServedLM(
            cfg, bucket_len=BUCKET, params=params,
            edge=fleet.replicas[i].edge, client_id=cid,
        )
        for cid, i in homes.items()
    }
    for cid, lm in sessions.items():
        lm.session.load()
        env = lm.session.server.context(cid).env
        want = fleet.replicas[homes[cid]].edge.server.jax_device
        check(all(v.devices() == {want} for v in env.values()),
              f"{cid}'s weights are not on its replica's chip")
    gens = {
        cid: lm.start_generation(prompt, FLEET_NEW_TOKENS)
        for cid, lm in sessions.items()
    }
    t0 = time.perf_counter()
    n_calls = sessions["base"].steps_total(gens["base"])
    for step in range(n_calls):
        if step == FLEET_MIGRATE_AT:
            t1 = time.perf_counter()
            check(fleet.migrate("moved", "r1") == "r1", "migration failed")
            log(f"fleet: 'moved' migrated r0 -> r1 before call {step} in "
                f"{host_s(t1)}")
        for cid, lm in sessions.items():
            res = lm.session.infer(*lm.step_inputs(gens[cid]))
            lm.absorb_step(gens[cid], res.outputs)
    log(f"fleet: {n_calls} decode calls x {len(sessions)} sessions in "
        f"{host_s(t0)}")

    tokens = {
        cid: np.concatenate(g["out"], axis=1) for cid, g in gens.items()
    }
    for cid, lm in sessions.items():
        check(lm.session.client.mode == "replaying", f"{cid} never replayed")
        log(f"fleet: {cid} on {fleet.locate(cid).name}: "
            f"{tokens[cid].tolist()}")
    check(fleet.locate("moved").name == "r1", "'moved' is not on r1")
    moved = fleet.replicas[1].edge.server.context("moved")
    resident = [moved.env[a] for a in moved.replay.param_addrs]
    resident += moved.replay.carried_state
    check(all(v.devices() == {placed[1]} for v in resident),
          "the migrated session's weights and KV cache are not on r1's chip")
    for cid in ("moved", "s2", "s3"):
        check(np.array_equal(tokens[cid], tokens["base"]),
              f"{cid}'s tokens differ from the never-migrated session's")
    base_state = fleet.replicas[0].edge.server.export_carried_state("base")
    moved_state = fleet.replicas[1].edge.server.export_carried_state("moved")
    check(base_state is not None and moved_state is not None
          and len(base_state) == len(moved_state)
          and all(np.array_equal(a, b)
                  for a, b in zip(base_state, moved_state)),
          "the migrated KV cache differs from the never-migrated one")
    log("fleet: migrated session equals the never-migrated one in tokens "
        "and carried KV cache")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--fleet4", action="store_true",
                    help="run only the four-chip fleet phase")
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(HERE, "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is {platform!r})",
              file=sys.stderr)
        return 1
    try:
        from repro.launch.serve import configure_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script: {e}",
              file=sys.stderr)
        return 1
    cache_dir = configure_compile_cache()
    log(f"device {device_report(jax)}; compile cache {cache_dir}")

    t0 = time.perf_counter()
    if args.fleet4:
        run_fleet4(jax)
    else:
        run_one_chip(jax)
    log(f"all phases passed in {host_s(t0)}")
    n_cache = sum(len(f) for _, _, f in os.walk(cache_dir))
    log(f"compile cache {cache_dir} holds {n_cache} files")
    print(json.dumps({"ok": True, "device": device_report(jax)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
