"""The comparison that decides ``correct`` fails when the timed path is
broken underneath it, and fails for the control.

Each test drives the rest of a run at a tiny size on the CPU (the look for
a chip skipped) with one fault planted in the program where the served
token is produced, and sees ``correct`` come out false.
"""
from __future__ import annotations

import json

import jax.numpy as jnp
import numpy as np
import pytest

from bench.tests.test_bench import BENCH, run_tiny, tiny_mix, tiny_spec

SOLO = ("qwen3-1.7b.chat1", "chat1", 1)
EDGE = ("qwen3-0.6b.edge8", "edge8", 3)


@pytest.fixture(scope="module")
def bench():
    return json.loads((BENCH.parent / "BENCHMARK.json").read_text())


def _assert_caught(res):
    gap = res["checks"]["max_logit_gap"]
    assert res["correct"] is False
    assert gap["value"] > gap["limit"]


def _bump(token):
    return (np.asarray(token) + 1) % 256


def test_token_altered_in_solo_replay(bench, monkeypatch):
    from repro.core.engine import OffloadServer

    orig = OffloadServer.replay_values

    def altered(self, inputs, client_id="c0", **kw):
        outs = orig(self, inputs, client_id, **kw)
        return [_bump(outs[0])] + list(outs[1:])

    monkeypatch.setattr(OffloadServer, "replay_values", altered)
    _assert_caught(run_tiny(bench, *SOLO))


def test_state_left_unchanged_in_solo_replay(bench, monkeypatch):
    """The KV cache comes back from each step as it went in."""
    from repro.core.engine import OffloadServer

    orig = OffloadServer.replay_values

    def frozen(self, inputs, client_id="c0", *, fresh_carried=None):
        bound = self.context(client_id).replay
        for idx, v in (fresh_carried or {}).items():
            bound.carried_state[idx] = self.to_device(v)
        saved = [jnp.array(x, copy=True) for x in bound.carried_state]
        outs = orig(self, inputs, client_id)
        bound.carried_state = saved
        return outs

    monkeypatch.setattr(OffloadServer, "replay_values", frozen)
    _assert_caught(run_tiny(bench, *SOLO))


def test_token_altered_in_batched_round(bench, monkeypatch):
    from repro.serving.multitenant import ReplayBatcher

    orig = ReplayBatcher.submit

    def altered(self, client, inputs, t, **kw):
        outs, done = orig(self, client, inputs, t, **kw)
        return [_bump(outs[0])] + list(outs[1:]), done

    monkeypatch.setattr(ReplayBatcher, "submit", altered)
    _assert_caught(run_tiny(bench, *EDGE))


def test_lanes_swapped_in_batched_round(bench, monkeypatch):
    """Each member of a vmap-batched round gets the next member's token."""
    from repro.serving.multitenant import ReplayBatcher

    orig = ReplayBatcher._run_vmap_batch

    def swapped(self, fp, members, params_flat):
        group = orig(self, fp, members, params_flat)
        if group is not None:
            ids = list(group.outs)
            outs = [group.outs[i] for i in ids]
            group.outs = dict(zip(ids, outs[1:] + outs[:1]))
        return group

    monkeypatch.setattr(ReplayBatcher, "_run_vmap_batch", swapped)
    _assert_caught(run_tiny(bench, *EDGE))


def test_control_fails_and_the_program_passes(bench):
    """The reference with float8 weights, read at the same positions as the
    served tokens, lies beyond the limit; the program within it.  The size
    is the smallest at which a run checks a few hundred served tokens over
    a vocabulary of some thousands, as a run on the chip does."""
    from bench import run

    spec = tiny_spec()
    spec["config"].update(hidden_size=128, intermediate_size=256,
                          head_dim=32, num_hidden_layers=4, vocab_size=2048)
    spec["bucket_len"] = 128
    mix = tiny_mix("chat1", 1)
    mix.update(max_total=127, check_requests=6)
    mix["output_len"].update(median=40, min=8, max=100)
    cell = next(w for w in bench["workloads"] if w["name"] == SOLO[0])
    check = json.loads((BENCH / "checks" / f"{SOLO[0]}.json").read_text())
    res = run.run_cell(cell, spec, mix, check, bench, 4, 2.0, False,
                       require_tpu=False, controls=True)
    gap = res["checks"]["max_logit_gap"]
    assert res["correct"] is True and gap["value"] <= gap["limit"]
    assert res["control_gap"] > gap["limit"]
    assert res["control_correct"] is False
