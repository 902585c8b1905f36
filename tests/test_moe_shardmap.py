"""shard_map MoE dispatch correctness: the optimized shard-local dispatch
must match the baseline global dispatch numerically.  The multi-shard case
needs >1 device, so it runs in a subprocess with 4 placeholder host devices
(the main test process keeps the single real device)."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, os.environ["REPRO_SRC"])
    import dataclasses
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import ArchConfig
    from repro.layers import moe as moe_mod

    cfg = ArchConfig(
        name="t", family="moe", n_layers=2, d_model=32, n_heads=4, n_kv_heads=4,
        d_head=8, d_ff=64, vocab=128, dtype="float32",
        moe_experts=4, moe_top_k=2, capacity_factor=8.0,
    )
    cfg_sm = dataclasses.replace(cfg, moe_groups=2)

    from repro.distributed.sharding import auto_mesh
    mesh = auto_mesh((2, 2), ("data", "model"))
    rng = np.random.default_rng(0)
    p = moe_mod.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (4, 8, 32)).astype(np.float32))

    y_base = moe_mod.moe_apply(p, x, cfg)   # global dispatch, no mesh needed

    with jax.set_mesh(mesh):
        f = jax.jit(lambda p_, x_: moe_mod.moe_apply(p_, x_, cfg_sm),
                    in_shardings=(None, NamedSharding(mesh, P(("data",), None, None))),
                    out_shardings=NamedSharding(mesh, P(("data",), None, None)))
        y_sm = f(p, x)

    err = float(jnp.abs(y_sm - y_base).max())
    # identical routing + drop-free capacity => exact (up to reduction order)
    assert err < 1e-4, f"shard_map dispatch diverged: {err}"
    print("OK", err)
    """
)


@pytest.mark.timeout(300)
def test_shardmap_dispatch_matches_global():
    env = dict(os.environ)
    env["REPRO_SRC"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=env, capture_output=True, text=True,
        timeout=280,
    )
    assert out.returncode == 0, f"stdout={out.stdout}\nstderr={out.stderr[-2000:]}"
    assert "OK" in out.stdout
