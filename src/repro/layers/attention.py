"""GQA attention block: fused QKV projection, optional per-head qk RMSNorm
(Qwen3), RoPE, flash attention for train/prefill, decode-attention kernel for
single-token steps against a static KV cache, optional sliding window (SWA).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.decode_attention import (
    decode_attention,
    decode_attention_q8_ref,
    quantize_kv,
)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.layers.common import dense, dense_init
from repro.layers.rope import apply_rope


def attn_init(key, cfg, dtype) -> Dict[str, Any]:
    kq, kk, kv, ko, kn = jax.random.split(key, 5)
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    p = {
        "wq": dense_init(kq, d, (hq * dh,), dtype),
        "wk": dense_init(kk, d, (hkv * dh,), dtype),
        "wv": dense_init(kv, d, (hkv * dh,), dtype),
        "wo": dense_init(ko, hq * dh, (d,), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dtype)
        p["k_norm"] = jnp.ones((dh,), dtype)
    return p


def attn_specs(cfg) -> Dict[str, Any]:
    s = {
        "wq": P(None, "tp"),
        "wk": P(None, "tp"),
        "wv": P(None, "tp"),
        "wo": P("tp", None),
    }
    if cfg.qk_norm:
        s["q_norm"] = P(None)
        s["k_norm"] = P(None)
    return s


def _project_qkv(p, x, cfg, positions):
    b, s, _ = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = dense(x, p["wq"]).reshape(b, s, hq, dh)
    k = dense(x, p["wk"]).reshape(b, s, hkv, dh)
    v = dense(x, p["wv"]).reshape(b, s, hkv, dh)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], eps=cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], eps=cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_forward(
    p: Dict[str, Any],
    x: jnp.ndarray,                    # (B, S, D)
    cfg,
    *,
    positions: Optional[jnp.ndarray] = None,
    causal: bool = True,
    return_kv: bool = False,
):
    """Training / prefill path (full sequence, flash attention)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    q, k, v = _project_qkv(p, x, cfg, positions)
    out = flash_attention(q, k, v, causal=causal, window=cfg.window)
    out = dense(out.reshape(b, s, -1), p["wo"])
    if return_kv:
        return out, (k, v)
    return out


def init_kv_cache(cfg, batch: int, max_seq: int, dtype) -> Dict[str, jnp.ndarray]:
    if getattr(cfg, "kv_cache_bits", 16) == 8:
        return {
            "k": jnp.zeros((batch, max_seq, cfg.n_kv_heads, cfg.d_head), jnp.int8),
            "v": jnp.zeros((batch, max_seq, cfg.n_kv_heads, cfg.d_head), jnp.int8),
            "ks": jnp.zeros((batch, max_seq, cfg.n_kv_heads), jnp.float32),
            "vs": jnp.zeros((batch, max_seq, cfg.n_kv_heads), jnp.float32),
        }
    return {
        "k": jnp.zeros((batch, max_seq, cfg.n_kv_heads, cfg.d_head), dtype),
        "v": jnp.zeros((batch, max_seq, cfg.n_kv_heads, cfg.d_head), dtype),
    }


def kv_cache_specs(cfg) -> Dict[str, Any]:
    # long-context decode: shard the cache sequence dim over dp when batch
    # cannot fill it (SP); heads over tp when divisible
    return {"k": P(None, "dp", "tp", None), "v": P(None, "dp", "tp", None)}


def _sp_decode_attention(q, k_cache, v_cache, kv_len, cfg, mesh):
    """Distributed flash-decode: the KV cache stays sharded over the "model"
    axis on the sequence dim; each shard computes a LOCAL streaming-softmax
    partial (m, l, o) over its cache slice and the combine is one tiny psum of
    (Hq, D)-sized tensors — the flash-decode split-KV reduce expressed across
    chips.  This is what GSPMD fails to find for the masked-softmax pattern
    (it replicates the cache instead — 'involuntary full rematerialization').
    """
    b, hq, d = q.shape
    _, s, hkv, _ = k_cache.shape
    n_rep = hq // hkv
    tp = "model"
    tp_size = mesh.shape[tp]
    s_local = s // tp_size
    dp_axes = tuple(n for n in ("pod", "data") if n in mesh.axis_names)
    scale = 1.0 / float(d) ** 0.5
    neg = -1e30

    def local(qb, kl, vl, kvl):
        # qb (B_l,1,Hq,D) replicated over tp; kl/vl (B_l,S_l,Hkv,D) local slice
        qb = qb[:, 0]
        bl = qb.shape[0]
        idx = jax.lax.axis_index(tp)
        start = idx * s_local
        # keep K/V in their storage dtype: the MXU accumulates in f32 via
        # preferred_element_type, so no f32 cast of the cache ever hits HBM
        qf = qb.reshape(bl, hkv, n_rep, d).astype(kl.dtype)
        sm = jnp.einsum(
            "bgrd,bsgd->bgrs", qf, kl, preferred_element_type=jnp.float32
        ) * scale
        pos = start + jnp.arange(s_local)[None, :]
        ok = pos < kvl[:, None]
        if cfg.window is not None:
            ok &= pos >= kvl[:, None] - cfg.window
        sm = jnp.where(ok[:, None, None, :], sm, neg)
        m_loc = sm.max(-1)                                   # (B,g,r)
        p = jnp.exp(sm - m_loc[..., None])
        l_loc = p.sum(-1)
        o_loc = jnp.einsum(
            "bgrs,bsgd->bgrd", p.astype(vl.dtype), vl,
            preferred_element_type=jnp.float32,
        )
        # cross-shard flash combine
        m_g = jax.lax.pmax(m_loc, tp)
        corr = jnp.exp(m_loc - m_g)
        l_g = jax.lax.psum(l_loc * corr, tp)
        o_g = jax.lax.psum(o_loc * corr[..., None], tp)
        out = o_g / jnp.maximum(l_g, 1e-30)[..., None]
        return out.reshape(bl, 1, hq, d).astype(q.dtype)

    q4 = q.reshape(b, 1, hq, d)
    kv_spec = P(dp_axes if b >= 16 else None, tp, None, None)
    qspec = P(dp_axes if b >= 16 else None, None, None, None)
    out = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(qspec, kv_spec, kv_spec, P(dp_axes if b >= 16 else None)),
        out_specs=qspec,
    )(q4, k_cache, v_cache, kv_len)
    return out[:, 0]


def attn_decode_step(
    p: Dict[str, Any],
    x: jnp.ndarray,                  # (B, 1, D)
    cache: Dict[str, jnp.ndarray],   # k/v (B, S, Hkv, Dh)
    pos: jnp.ndarray,                # scalar int32 — current length (uniform)
    cfg,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    b = x.shape[0]
    positions = jnp.broadcast_to(pos, (b, 1)).astype(jnp.int32)
    q, k, v = _project_qkv(p, x, cfg, positions)
    kv_len = jnp.broadcast_to(pos + 1, (b,)).astype(jnp.int32)
    if getattr(cfg, "kv_cache_bits", 16) == 8:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        new_cache = {
            "k": jax.lax.dynamic_update_slice(cache["k"], kq, (0, pos, 0, 0)),
            "v": jax.lax.dynamic_update_slice(cache["v"], vq, (0, pos, 0, 0)),
            "ks": jax.lax.dynamic_update_slice(cache["ks"], ks, (0, pos, 0)),
            "vs": jax.lax.dynamic_update_slice(cache["vs"], vs, (0, pos, 0)),
        }
        out = decode_attention_q8_ref(
            q.reshape(b, cfg.n_heads, cfg.d_head),
            new_cache["k"], new_cache["v"], new_cache["ks"], new_cache["vs"],
            kv_len, window=cfg.window,
        )
        out = dense(out.reshape(b, 1, -1), p["wo"])
        return out, new_cache
    k_cache = jax.lax.dynamic_update_slice(cache["k"], k, (0, pos, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(cache["v"], v, (0, pos, 0, 0))
    mesh = jax.sharding.get_abstract_mesh()
    if (
        getattr(cfg, "sp_decode", False)
        and not mesh.empty
        and "model" in mesh.axis_names
        and k_cache.shape[1] % mesh.shape["model"] == 0
    ):
        out = _sp_decode_attention(
            q.reshape(b, cfg.n_heads, cfg.d_head), k_cache, v_cache, kv_len,
            cfg, mesh,
        )
    else:
        out = decode_attention(
            q.reshape(b, cfg.n_heads, cfg.d_head),
            k_cache,
            v_cache,
            kv_len,
            window=cfg.window,
        )
    out = dense(out.reshape(b, 1, -1), p["wo"])
    return out, {"k": k_cache, "v": v_cache}
