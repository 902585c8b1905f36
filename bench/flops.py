"""The chip's side of the operation counts, and the dispatch to the family.

The chip's published peaks (``bench/peaks.json``), the least time a count of
operations and bytes needs on it, and ``family``, which finds the module
``bench/models/<model>.py`` that a configuration names: a model's
arithmetic lives there (``decode_step``, ``kernel_calls``; see
``bench/models/__init__.py``), and nothing here reads a model's shapes.
"""
from __future__ import annotations

import importlib
import json
import pathlib
from typing import Dict, Optional

from bench import trace_reduce

HERE = pathlib.Path(__file__).resolve().parent


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


def family(spec: dict):
    """The family module of the configuration file ``spec``."""
    return importlib.import_module(f"bench.models.{spec['model']}")


def kernel_roofline(run, kernel: str) -> Optional[float]:
    """``kernel``'s share of its roofline in the traced window, in percent:
    the least time its calls for the traced decode calls could take on this
    chip (the family's ``kernel_calls`` at each call's valid length) over the
    kernel's device time in the trace.  None where there is no trace, peak,
    traced call or device time of the kernel, or where the family lists no
    such kernel."""
    if run.trace is None or run.peak is None:
        return None
    spent = trace_reduce.kernel_seconds(run.trace, kernel)
    traced = [c for c in run.calls if c.traced]
    if spent <= 0 or not traced:
        return None
    counts, hf = family(run.spec).kernel_calls, run.spec["config"]
    bound = 0.0
    for c in traced:
        calls = counts(hf, c.kv_len).get(kernel)
        if not calls:
            return None
        bound += sum(n * bound_seconds(ops, nbytes, run.peak)
                     for n, ops, nbytes in calls)
    return 100.0 * bound / spent
