"""Qwen3 dense decoder: the benchmark's side of one model family.

Five things live here (the family contract of ``bench/models/__init__.py``),
and nothing of them comes from the program:

* ``program_config``: the program's ``ArchConfig`` for the published sizes in
  a configuration file (the registry entry named there, with every size
  replaced by the file's own number, so the run is of the file as written);
* ``make_params``: random weights from a seed, made on the device in one
  jitted call, in the layout and type the program serves them in;
* ``logit_stats``: the plain reference, a float32 full forward pass over a
  whole sequence (Qwen3 as published: RMSNorm, GQA with per-head q/k RMSNorm
  before a rotate-half RoPE, SwiGLU MLP, tied embeddings), computed layer by
  layer at ``highest`` matmul precision.  With ``fp8=True`` every weight
  matrix is rounded to float8 e4m3 (per output channel scaled) first: the
  control, one precision step below the configuration's bfloat16;
* ``decode_step`` and ``kernel_calls``: the operations and bytes of one
  token, for the whole step and for each call of the ``decode_attention``
  and ``rmsnorm`` kernels.
"""
from __future__ import annotations

import dataclasses
import functools
import json
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# the program's embedding table holds the vocabulary padded to a multiple of
# 512 rows; the rows past the vocabulary are never read by a served token
VOCAB_PAD = 512

# published key -> ArchConfig field
_FIELDS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "d_head",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype",
}


def program_config(spec: dict):
    """The program's config for ``spec`` (a configuration file's contents)."""
    from repro.configs import get_config

    hf = spec["config"]
    if not hf["tie_word_embeddings"]:
        raise ValueError("this reference covers tied embeddings only")
    values = {field: hf[key] for key, field in _FIELDS.items()}
    values["rope_theta"] = float(values["rope_theta"])
    values["qk_norm"] = True
    values["window"] = None
    return dataclasses.replace(get_config(spec["arch"]), **values)


def _padded_vocab(hf: dict) -> int:
    return -(-hf["vocab_size"] // VOCAB_PAD) * VOCAB_PAD


def _shapes(hf: dict) -> dict:
    d, L = hf["hidden_size"], hf["num_hidden_layers"]
    hq, hkv, dh = (hf["num_attention_heads"], hf["num_key_value_heads"],
                   hf["head_dim"])
    ff = hf["intermediate_size"]
    return {
        "embed": (_padded_vocab(hf), d),
        "final_norm": (d,),
        "attn_norm": (L, d),
        "mlp_norm": (L, d),
        "wq": (L, d, hq * dh),
        "wk": (L, d, hkv * dh),
        "wv": (L, d, hkv * dh),
        "wo": (L, hq * dh, d),
        "q_norm": (L, dh),
        "k_norm": (L, dh),
        "w_gate": (L, d, ff),
        "w_up": (L, d, ff),
        "w_down": (L, ff, d),
    }


def _leaves(hf: dict, key) -> dict:
    """Every weight from one key: matrices N(0, 1/fan_in), norm scales
    1 + N(0, 0.1^2) (not all ones, so a norm that ignored its scale would
    show)."""
    out = {}
    for i, (name, shape) in enumerate(sorted(_shapes(hf).items())):
        k = jax.random.fold_in(key, i)
        x = jax.random.normal(k, shape, jnp.float32)
        if name.endswith("norm"):
            x = 1.0 + 0.1 * x
        elif name == "embed":
            x = x * hf["hidden_size"] ** -0.5
        else:
            x = x * shape[-2] ** -0.5
        out[name] = x.astype(jnp.dtype(hf["torch_dtype"]))
    return out


def _nest(w: dict) -> dict:
    """The program's parameter pytree (one scan super-block per layer)."""
    return {
        "embed": w["embed"],
        "final_norm": w["final_norm"],
        "blocks": {"sub0": {
            "attn_norm": w["attn_norm"],
            "mlp_norm": w["mlp_norm"],
            "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo", "q_norm",
                                        "k_norm")},
            "ffn": {k: w[k] for k in ("w_gate", "w_up", "w_down")},
        }},
    }


def weight_key(seed: int):
    """A PRNG key for any whole-number seed (more than 32 bits allowed)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def make_params(spec: dict, seed: int):
    """The program's weights for ``seed``, made on the device in one call."""
    hf = spec["config"]
    fn = jax.jit(lambda k: _nest(_leaves(hf, k)))
    return jax.block_until_ready(fn(weight_key(seed)))


# ---------------------------------------------------------------------------
# operation and byte counts (the definitions of bench/models/__init__.py)
# ---------------------------------------------------------------------------

def _dtype_bytes(hf: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[hf["torch_dtype"]]


def parameter_count(hf: dict) -> int:
    """Parameters of a tied-embedding dense decoder (embedding counted once)."""
    d, ff, L = hf["hidden_size"], hf["intermediate_size"], hf["num_hidden_layers"]
    hq, hkv, dh = (hf["num_attention_heads"], hf["num_key_value_heads"],
                   hf["head_dim"])
    per_layer = d * (hq + 2 * hkv) * dh + hq * dh * d + 3 * d * ff
    per_layer += 2 * d + 2 * dh
    return L * per_layer + hf["vocab_size"] * d + d


def decode_step(hf: dict, kv_len: int) -> Tuple[float, float]:
    """(operations, bytes) of one token through the whole model: every
    weight read once, the cache read up to ``kv_len`` and one position of it
    written, the logits over the vocabulary."""
    d, ff, L = hf["hidden_size"], hf["intermediate_size"], hf["num_hidden_layers"]
    hq, hkv, dh = (hf["num_attention_heads"], hf["num_key_value_heads"],
                   hf["head_dim"])
    v = hf["vocab_size"]
    per_layer = 2 * d * (hq + 2 * hkv) * dh + 2 * hq * dh * d + 6 * d * ff
    per_layer += 4 * hq * dh * kv_len
    flops = L * per_layer + 2 * d * v
    b = _dtype_bytes(hf)
    kv = 2 * hkv * dh * b
    nbytes = parameter_count(hf) * b + L * kv * (kv_len + 1)
    return float(flops), float(nbytes)


def decode_attention(hf: dict, kv_len: int) -> Tuple[float, float]:
    """(operations, bytes) of one decode-attention kernel call (one layer,
    one client): q against keys and values of ``kv_len`` positions."""
    hq, hkv, dh = (hf["num_attention_heads"], hf["num_key_value_heads"],
                   hf["head_dim"])
    b = _dtype_bytes(hf)
    flops = 4 * hq * dh * kv_len
    nbytes = 2 * hq * dh * b + 2 * kv_len * hkv * dh * b
    return float(flops), float(nbytes)


def rmsnorm_calls(hf: dict) -> List[Tuple[int, int]]:
    """(rows, width) of each RMSNorm kernel call of one token: per layer the
    attention input, the per-head q and k norms and the MLP input, then the
    final norm."""
    d, L = hf["hidden_size"], hf["num_hidden_layers"]
    hq, hkv, dh = (hf["num_attention_heads"], hf["num_key_value_heads"],
                   hf["head_dim"])
    return [(1, d), (hq, dh), (hkv, dh), (1, d)] * L + [(1, d)]


def rmsnorm(hf: dict, rows: int, width: int) -> Tuple[float, float]:
    """(operations, bytes) of one RMSNorm call: square, mean, rsqrt scale
    and the gain, about 4 operations an element; x read, y written, the gain
    read."""
    b = _dtype_bytes(hf)
    return float(4 * rows * width), float((2 * rows * width + width) * b)


def kernel_calls(hf: dict, kv_len: int) -> Dict[str, List[Tuple[int, float, float]]]:
    """Every kernel call of one token: decode attention once a layer, and
    each RMSNorm of ``rmsnorm_calls``."""
    return {
        "decode_attention": [(hf["num_hidden_layers"],
                              *decode_attention(hf, kv_len))],
        "rmsnorm": [(1, *rmsnorm(hf, rows, width))
                    for rows, width in rmsnorm_calls(hf)],
    }


# ---------------------------------------------------------------------------
# plain reference
# ---------------------------------------------------------------------------

def _fp8(w):
    """Round to float8 e4m3 (3 mantissa bits), scaled per output channel so
    each column's largest magnitude maps to the format's largest, 448."""
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    m, e = jnp.frexp(w / scale)
    return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e) * scale


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """Rotate-half RoPE over (T, H, D) at positions 0..T-1."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _forward(hf: dict, fp8: bool, w: dict, tokens):
    """Float32 logits (T, vocab) of the full causal forward pass over the
    weights ``w`` of ``make_params``."""
    hq, hkv, dh = (hf["num_attention_heads"], hf["num_key_value_heads"],
                   hf["head_dim"])
    eps, theta = hf["rms_norm_eps"], float(hf["rope_theta"])
    t = tokens.shape[0]
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    mat = (lambda a: _fp8(f32(a))) if fp8 else f32
    embed = mat(w["embed"][: hf["vocab_size"]])
    x = embed[tokens]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def layer(x, lw):
        h = _rms(x, f32(lw["attn_norm"]), eps)
        q = (h @ mat(lw["wq"])).reshape(t, hq, dh)
        k = (h @ mat(lw["wk"])).reshape(t, hkv, dh)
        v = (h @ mat(lw["wv"])).reshape(t, hkv, dh)
        q = _rope(_rms(q, f32(lw["q_norm"]), eps), theta)
        k = _rope(_rms(k, f32(lw["k_norm"]), eps), theta)
        # query head i reads key/value head i // (hq // hkv)
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k) * dh ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        a = jnp.einsum("hts,shd->thd", p, v).reshape(t, hq * dh)
        x = x + a @ mat(lw["wo"])
        h = _rms(x, f32(lw["mlp_norm"]), eps)
        g = h @ mat(lw["w_gate"])
        x = x + (jax.nn.silu(g) * (h @ mat(lw["w_up"]))) @ mat(lw["w_down"])
        return x, None

    blk = w["blocks"]["sub0"]
    layers = {"attn_norm": blk["attn_norm"], "mlp_norm": blk["mlp_norm"],
              **blk["attn"], **blk["ffn"]}
    x, _ = jax.lax.scan(layer, x, layers)
    x = _rms(x, f32(w["final_norm"]), eps)
    return x @ embed.T


@functools.lru_cache(maxsize=None)
def _stats_fn(hf_json: str, fp8: bool):
    hf = json.loads(hf_json)

    def stats(w, tokens, picks):
        logits = _forward(hf, fp8, w, tokens)
        return (logits.max(-1),
                logits[jnp.arange(tokens.shape[0])[None, :], picks],
                jnp.argmax(logits, -1).astype(jnp.int32))

    return jax.jit(stats)


def logit_stats(spec: dict, weights: dict, tokens: np.ndarray,
                picks: np.ndarray, *, fp8: bool = False):
    """Run the reference (or, with ``fp8``, the control) with the weights of
    ``make_params`` over one padded sequence ``tokens`` (T,).  Returns host
    arrays: per position the largest logit (T,), the logits of the tokens
    in each row of ``picks`` (K, T), and the token put first (T,)."""
    hf = json.dumps(spec["config"], sort_keys=True)
    with jax.default_matmul_precision("highest"):
        out = _stats_fn(hf, fp8)(weights, jnp.asarray(tokens, jnp.int32),
                                 jnp.asarray(picks, jnp.int32))
    return tuple(np.asarray(o) for o in out)
