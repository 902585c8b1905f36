"""Elastic rescale: a checkpoint saved from a 4-device (2x2) mesh restores
onto a 2-device (2x1) mesh with different shardings and identical values —
the restart-after-topology-change path.  Runs in subprocesses so the main
test process keeps the single real device."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

_SAVE = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, os.environ["REPRO_SRC"])
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint import store

    from repro.distributed.sharding import auto_mesh
    mesh = auto_mesh((2, 2), ("data", "model"))
    w = jnp.arange(64 * 32, dtype=jnp.float32).reshape(64, 32)
    sharded = jax.device_put(w, NamedSharding(mesh, P("data", "model")))
    tree = {"params": {"w": sharded}, "step": jnp.int32(9)}
    store.save(os.environ["CKPT_DIR"], 9, tree)
    print("SAVED", sharded.sharding)
    """
)

_RESTORE = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import sys
    sys.path.insert(0, os.environ["REPRO_SRC"])
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint import store

    from repro.distributed.sharding import auto_mesh
    mesh = auto_mesh((2, 1), ("data", "model"))
    target = {
        "params": {"w": jax.ShapeDtypeStruct((64, 32), jnp.float32)},
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    shardings = {
        "params": {"w": NamedSharding(mesh, P("data", "model"))},
        "step": NamedSharding(mesh, P()),
    }
    step = store.latest_step(os.environ["CKPT_DIR"])
    assert step == 9, step
    restored = store.restore(os.environ["CKPT_DIR"], step, target,
                             shardings=shardings)
    w = restored["params"]["w"]
    assert len(w.sharding.device_set) == 2, w.sharding
    expected = np.arange(64 * 32, dtype=np.float32).reshape(64, 32)
    np.testing.assert_array_equal(np.asarray(w), expected)
    assert int(restored["step"]) == 9
    print("RESTORED OK on", len(jax.devices()), "devices")
    """
)


@pytest.mark.timeout(300)
def test_elastic_reshard_across_device_counts(tmp_path):
    env = dict(os.environ)
    env["REPRO_SRC"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    env["CKPT_DIR"] = str(tmp_path)
    env.pop("XLA_FLAGS", None)
    for script, marker in ((_SAVE, "SAVED"), (_RESTORE, "RESTORED OK")):
        out = subprocess.run(
            [sys.executable, "-c", script], env=env,
            capture_output=True, text=True, timeout=280,
        )
        assert out.returncode == 0, f"stdout={out.stdout}\nstderr={out.stderr[-2000:]}"
        assert marker in out.stdout
