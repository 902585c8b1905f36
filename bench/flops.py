"""Operations and bytes that one decode call needs, from the shapes alone.

Counts are of the work the algorithm requires for one token of one client:
the real vocabulary (not the program's padded table), keys and values up to
the valid length ``kv_len`` (not the whole cache bucket), weights read once.
A matrix product of (m, k) by (k, n) is 2·m·k·n operations.  ``hf`` is a
configuration's published ``config`` dict.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, List, Tuple

HERE = pathlib.Path(__file__).resolve().parent


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


def peaks(device_kind: str) -> Dict[str, float]:
    """The device's published peaks; a device not in the table is an error."""
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (has {sorted(table)})")
    return table[device_kind]


def bound_seconds(flops: float, nbytes: float, peak: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
