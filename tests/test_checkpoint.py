"""Checkpoint store: save/restore round-trip, atomic publish, restart
resume, async writes, elastic resharding via device_put shardings."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import store


def make_tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (8, 16)), "b": jnp.zeros((16,))},
        "opt": {"m": jnp.ones((8, 16)), "step": jnp.int32(7)},
    }


class TestStore:
    def test_roundtrip(self, tmp_path):
        tree = make_tree()
        store.save(str(tmp_path), 10, tree)
        restored = store.restore(str(tmp_path), 10, tree)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_latest_step(self, tmp_path):
        tree = make_tree()
        store.save(str(tmp_path), 5, tree)
        store.save(str(tmp_path), 15, tree)
        assert store.latest_step(str(tmp_path)) == 15

    def test_latest_ignores_partial_tmp(self, tmp_path):
        tree = make_tree()
        store.save(str(tmp_path), 5, tree)
        os.makedirs(tmp_path / "step_00000009.tmp")  # crashed writer remnant
        assert store.latest_step(str(tmp_path)) == 5

    def test_latest_none_when_empty(self, tmp_path):
        assert store.latest_step(str(tmp_path)) is None

    def test_async_save(self, tmp_path):
        tree = make_tree()
        t = store.save_async(str(tmp_path), 3, tree)
        t.join()
        assert store.latest_step(str(tmp_path)) == 3

    def test_concurrent_nonblocking_saves_never_corrupt(self, tmp_path):
        """Regression: two non-blocking writers publishing the *same* step
        used to share one ``.tmp`` staging dir — writer B could rmtree the
        dir writer A was mid-rename on.  Each writer now stages under a
        unique tmp name; one rename wins, the loser withdraws, and the
        published checkpoint is always a complete tree."""
        trees = [make_tree(seed=s) for s in range(6)]
        threads = [
            store.save(str(tmp_path), 7, t, blocking=False) for t in trees
        ]
        for t in threads:
            t.join()
        assert store.latest_step(str(tmp_path)) == 7
        # whatever writer won, the tree restores completely and matches one
        # of the racers exactly (no interleaved halves)
        restored = store.restore(str(tmp_path), 7, trees[0])
        leaves = jax.tree.leaves(restored)
        matches = sum(
            all(
                np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(leaves, jax.tree.leaves(t))
            )
            for t in trees
        )
        assert matches == 1
        # no staging remnants survive the race, and the scan ignores any
        leftovers = [d for d in os.listdir(tmp_path) if ".tmp" in d]
        assert leftovers == []

    def test_load_flat_roundtrip(self, tmp_path):
        flat = {
            "meta_seq": np.int64(12),
            "carried_000": np.arange(6, dtype=np.float32).reshape(2, 3),
            "env_140001234": np.ones((4,), np.float32),
        }
        store.save(str(tmp_path), 12, flat)
        back = store.load_flat(str(tmp_path), 12)
        assert set(back) == set(flat)
        for k, v in flat.items():
            np.testing.assert_array_equal(back[k], np.asarray(v))

    def test_shape_mismatch_rejected(self, tmp_path):
        store.save(str(tmp_path), 1, make_tree())
        bad = make_tree()
        bad["params"]["w"] = jnp.zeros((4, 4))
        with pytest.raises(ValueError, match="shape mismatch"):
            store.restore(str(tmp_path), 1, bad)

    def test_restore_with_shardings(self, tmp_path):
        """Elastic path: restore with explicit shardings (single-device mesh
        here; the 256<->512-chip reshard is exercised by the dry-run meshes)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.distributed.sharding import auto_mesh

        tree = make_tree()
        store.save(str(tmp_path), 2, tree)
        mesh = auto_mesh((1,), ("data",))
        shardings = jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)
        restored = store.restore(str(tmp_path), 2, tree, shardings=shardings)
        np.testing.assert_array_equal(
            np.asarray(restored["params"]["w"]), np.asarray(tree["params"]["w"])
        )


class TestTrainRestart:
    def test_crash_and_resume_reproduces_stream(self, tmp_path):
        """Train 30 steps with a crash at 20: resumed losses must continue
        from the checkpoint (deterministic data stream + state restore)."""
        from repro.launch import train

        ckpt = str(tmp_path / "ckpt")
        args = [
            "--arch", "qwen3-0.6b", "--reduced", "--steps", "30",
            "--batch", "2", "--seq", "32", "--ckpt-dir", ckpt,
            "--ckpt-every", "10", "--log-every", "5",
        ]
        crashed = train.main(args + ["--kill-at", "20"])
        assert crashed["crashed_at"] == 20
        assert store.latest_step(ckpt) == 20

        resumed = train.main(args)
        assert resumed["final_loss"] is not None
        straight = train.main(
            [
                "--arch", "qwen3-0.6b", "--reduced", "--steps", "30",
                "--batch", "2", "--seq", "32", "--log-every", "5",
            ]
        )
        # resumed run ends at the same loss as the uninterrupted run
        np.testing.assert_allclose(
            resumed["final_loss"], straight["final_loss"], rtol=1e-4
        )
