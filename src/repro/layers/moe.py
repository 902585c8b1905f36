"""Mixture-of-Experts FFN with *static-shape* capacity dispatch.

This is deliberately the XLA-friendly formulation: top-k routing, stable sort
by expert, per-expert capacity C = ceil(T*k/E * capacity_factor) with drop-on-
overflow, scatter into an (E, C, D) buffer, batched per-expert SwiGLU, and a
weighted scatter-add back.  Every shape is input-invariant, which is exactly
what makes MoE a *Static Activation Model* in this framework (the paper
classifies MoE as dynamic and falls back; under XLA's static-shape discipline
the recorded operator sequence is input-independent, so record/replay applies
— the beyond-paper extension documented in DESIGN.md §2).

Sharding: experts over "tp" when E divides the axis (EP), else the per-expert
FFN dim over "tp" (TP-in-expert).  Chosen in ``moe_specs`` per config.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.layers.common import dense, dense_init, stacked_init
from repro.layers.mlp import mlp_apply, mlp_init, mlp_specs


def moe_capacity(n_tokens: int, cfg) -> int:
    cap = math.ceil(n_tokens * cfg.moe_top_k / cfg.moe_experts * cfg.capacity_factor)
    return max(8, (cap + 7) // 8 * 8)


def moe_init(key, cfg, dtype) -> Dict[str, Any]:
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe_experts
    p = {
        "router": dense_init(kr, d, (e,), jnp.float32),
        "w_gate": stacked_init(kg, e, dense_init, d, (f,), dtype),
        "w_up": stacked_init(ku, e, dense_init, d, (f,), dtype),
        "w_down": stacked_init(kd, e, dense_init, f, (d,), dtype),
    }
    if cfg.moe_shared_expert:
        p["shared"] = mlp_init(ks, d, f, dtype)
    return p


def moe_specs(cfg, tp_size: int = 16) -> Dict[str, Any]:
    if cfg.moe_experts % tp_size == 0:
        # expert parallelism: experts sharded over tp
        s = {
            "router": P(None, None),
            "w_gate": P("tp", None, None),
            "w_up": P("tp", None, None),
            "w_down": P("tp", None, None),
        }
    else:
        # TP within each expert
        s = {
            "router": P(None, None),
            "w_gate": P(None, None, "tp"),
            "w_up": P(None, None, "tp"),
            "w_down": P(None, "tp", None),
        }
    if cfg.moe_shared_expert:
        s["shared"] = mlp_specs()
    return s


def _dispatch_one(p: Dict[str, Any], xf: jnp.ndarray, cfg, cap: int) -> jnp.ndarray:
    """Capacity dispatch + per-expert SwiGLU for one token group (T, D)."""
    t, d = xf.shape
    k = cfg.moe_top_k
    e = cfg.moe_experts

    logits = dense(xf.astype(jnp.float32), p["router"])          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)                       # (T, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    flat_e = top_i.reshape(t * k)
    flat_w = top_w.reshape(t * k).astype(xf.dtype)
    flat_t = jnp.arange(t * k, dtype=jnp.int32) // k

    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]

    counts = jnp.zeros((e,), jnp.int32).at[se].add(1)
    starts = jnp.cumsum(counts) - counts                          # (E,)
    pos = jnp.arange(t * k, dtype=jnp.int32) - starts[se]         # position in expert
    # overflow positions land out of range -> dropped by mode="drop"
    buf = jnp.zeros((e, cap, d), xf.dtype).at[se, pos].set(
        xf[st], mode="drop"
    )

    # batched per-expert SwiGLU
    g = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", buf, p["w_gate"]).astype(jnp.float32)
    ).astype(xf.dtype)
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    out_buf = jnp.einsum("ecf,efd->ecd", g * u, p["w_down"])      # (E, C, D)

    vals = out_buf.at[se, pos].get(mode="fill", fill_value=0)     # (T*k, D)
    y = jnp.zeros((t, d), xf.dtype).at[st].add(vals * sw[:, None])
    return y


def _local_dispatch_shardmap(p, x, cfg, mesh):
    """Explicit shard_map dispatch: each data shard routes ONLY its local
    tokens (sort/scatter/gather never leave the shard); expert FFN weights
    stay tensor-parallel over 'model' with one small psum to complete the
    down-projection.  GSPMD's scatter partitioner replicates the global-token
    dispatch (measured in EXPERIMENTS.md §Perf) — shard_map removes its
    freedom to do so."""
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    dp_axes = tuple(n for n in ("pod", "data") if n in mesh.axis_names)
    tp = "model" if "model" in mesh.axis_names else None
    dp_size = 1
    for n in dp_axes:
        dp_size *= mesh.shape[n]
    t_local = (b * s) // dp_size
    cap = moe_capacity(t_local, cfg)
    e = cfg.moe_experts

    # per-expert weight specs: EP over 'model' when divisible, else TP-in-expert
    ep = e % mesh.shape.get("model", 1) == 0 if tp else False
    if ep:
        w_specs = {"router": P(), "w_gate": P(tp, None, None),
                   "w_up": P(tp, None, None), "w_down": P(tp, None, None)}
    else:
        w_specs = {"router": P(), "w_gate": P(None, None, tp),
                   "w_up": P(None, None, tp), "w_down": P(None, tp, None)}

    def local(xl, router, w_gate, w_up, w_down):
        # xl: (1, t_local, d) — this shard's tokens; weights: local tp shards
        xf = xl.reshape(t_local, d)
        k = cfg.moe_top_k
        logits = dense(xf.astype(jnp.float32), router)
        if ep:
            # experts sharded over 'model': route against the global logits,
            # keep only this shard's experts
            e_local = w_gate.shape[0]
            e_start = jax.lax.axis_index(tp) * e_local
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_i = jax.lax.top_k(probs, k)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
        flat_e = top_i.reshape(t_local * k)
        flat_w = top_w.reshape(t_local * k).astype(xl.dtype)
        flat_t = jnp.arange(t_local * k, dtype=jnp.int32) // k
        order = jnp.argsort(flat_e, stable=True)
        se, st, sw = flat_e[order], flat_t[order], flat_w[order]
        counts = jnp.zeros((e,), jnp.int32).at[se].add(1)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(t_local * k, dtype=jnp.int32) - starts[se]
        if ep:
            se_local = se - e_start
            keep = (se_local >= 0) & (se_local < e_local)
            se_idx = jnp.where(keep, se_local, e_local)  # OOB -> dropped
            buf = jnp.zeros((e_local, cap, d), xl.dtype).at[se_idx, pos].set(
                xf[st], mode="drop"
            )
        else:
            buf = jnp.zeros((e, cap, d), xl.dtype).at[se, pos].set(
                xf[st], mode="drop"
            )
        g = jax.nn.silu(
            jnp.einsum("ecd,edf->ecf", buf, w_gate).astype(jnp.float32)
        ).astype(xl.dtype)
        u = jnp.einsum("ecd,edf->ecf", buf, w_up)
        out_buf = jnp.einsum("ecf,efd->ecd", g * u, w_down)
        if ep:
            vals = out_buf.at[se_idx, pos].get(mode="fill", fill_value=0)
        else:
            vals = out_buf.at[se, pos].get(mode="fill", fill_value=0)
        y = jnp.zeros((t_local, d), xl.dtype).at[st].add(vals * sw[:, None])
        if tp is not None:
            # EP: each shard computed its experts' share of every token;
            # TP-in-expert: partial down-proj sums — either way, one psum
            y = jax.lax.psum(y, tp)
        return y.reshape(1, t_local, d)

    xg = x.reshape(dp_size, t_local, d)
    yg = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(dp_axes, None, None), w_specs["router"], w_specs["w_gate"],
                  w_specs["w_up"], w_specs["w_down"]),
        out_specs=P(dp_axes, None, None),
    )(xg, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return yg.reshape(b * s, d)


def moe_apply(p: Dict[str, Any], x: jnp.ndarray, cfg) -> jnp.ndarray:
    """cfg.moe_groups == 0 (baseline): one global dispatch over all tokens
    under GSPMD.  cfg.moe_groups > 0 (optimized): explicit shard_map dispatch
    with shard-local routing (EXPERIMENTS.md §Perf)."""
    b, s, d = x.shape
    t = b * s
    mesh = jax.sharding.get_abstract_mesh()
    use_sm = (
        cfg.moe_groups
        and not mesh.empty
        and "model" in mesh.axis_names
    )
    if use_sm:
        dp = 1
        for n in ("pod", "data"):
            if n in mesh.axis_names:
                dp *= mesh.shape[n]
        if t % dp == 0 and t // dp >= 8:
            y = _local_dispatch_shardmap(p, x, cfg, mesh)
        else:
            y = _dispatch_one(p, x.reshape(t, d), cfg, moe_capacity(t, cfg))
    else:
        y = _dispatch_one(p, x.reshape(t, d), cfg, moe_capacity(t, cfg))

    if cfg.moe_shared_expert:
        y = y + mlp_apply(p["shared"], x.reshape(t, d))
    return y.reshape(b, s, d)
