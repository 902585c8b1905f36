"""The one general generator of requests: it reads a traffic mix's data file
(``bench/traffic/<name>.json``) and hands each client its requests.

The seed changes the token ids, never the sizes.  A mix holds a pool of
``pool`` (a power of two) request sizes: the prompt lengths are the pool's
evenly spaced quantiles of the clipped lognormal, and the output lengths the
same quantiles of theirs, paired with the prompts by a fixed permutation.
Requests are served in the bit-reversed order of the pool, so any run of
consecutive requests spreads evenly over the distribution, and the clients
take turns along that one sequence.  Every seed thus serves the same sizes
in the same order: a window that holds only part of the pool sees the same
part each time, and the work of a run does not change with its seed.
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib
from statistics import NormalDist
from typing import List

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


def load_mix(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def quantile_lengths(dist: dict, k: int) -> List[int]:
    """The k evenly spaced quantiles (i + 0.5) / k of a clipped lognormal."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = NormalDist()
    out = []
    for i in range(k):
        x = dist["median"] * math.exp(dist["sigma"] * z.inv_cdf((i + 0.5) / k))
        out.append(int(min(max(round(x), dist["min"]), dist["max"])))
    return out


def bit_reversed(k: int) -> List[int]:
    bits = k.bit_length() - 1
    if k != 1 << bits:
        raise ValueError(f"pool size {k} is not a power of two")
    return [int(format(i, f"0{bits}b")[::-1], 2) if bits else 0
            for i in range(k)]


@dataclasses.dataclass(frozen=True)
class Request:
    prompt: np.ndarray     # (1, P) int32
    new_tokens: int        # N


class Traffic:
    """Requests of one mix for one seed."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix = mix
        self.clients = int(mix["clients"])
        self.vocab = int(vocab)
        self.seed = int(seed)
        k = int(mix["pool"])
        prompts = quantile_lengths(mix["prompt_len"], k)
        outputs = quantile_lengths(mix["output_len"], k)
        pair = np.random.default_rng(int(mix["pairing_seed"])).permutation(k)
        cap = int(mix["max_total"])
        self.sizes = [
            (p, max(1, min(outputs[pair[i]], cap - p)))
            for i, p in enumerate(prompts)
        ]
        self.order = bit_reversed(k)

    def request(self, client: int, index: int) -> Request:
        """The ``index``-th request of ``client``: clients take turns along
        the bit-reversed pool order."""
        g = index * self.clients + client
        p, n = self.sizes[self.order[g % len(self.order)]]
        rng = np.random.default_rng([self.seed, 1, client, index])
        prompt = rng.integers(0, self.vocab, (1, p), dtype=np.int64)
        return Request(prompt.astype(np.int32), n)

    def warmup_request(self, client: int) -> Request:
        """A short request for set-up (its sizes come from the mix file)."""
        w = self.mix["warmup"]
        rng = np.random.default_rng([self.seed, 2, client])
        prompt = rng.integers(0, self.vocab, (1, w["prompt_len"]),
                              dtype=np.int64)
        return Request(prompt.astype(np.int32), int(w["output_len"]))
