"""shard_map all-to-all MoE dispatch vs the dense reference.

The multi-shard case needs >1 device, so it runs in a subprocess with
forced host devices (the test process itself must keep seeing 1 device —
see conftest.py).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.models import moe as moe_mod
from repro.models.moe_dispatch import moe_apply_a2a, set_dispatch_mesh


def test_a2a_matches_reference_single_shard():
    """On a 1x1 mesh the dispatch degenerates to the plain expert FFN."""
    mesh = make_mesh((1, 1), ("data", "model"))
    d, f, E, k = 8, 16, 4, 2
    params = moe_mod.moe_init(jax.random.PRNGKey(0), d, E, f, "silu")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, d))
    want = moe_mod.moe_reference(params, x, top_k=k, act="silu")
    set_dispatch_mesh(mesh)
    with jax.set_mesh(mesh):
        got, aux = moe_apply_a2a(params, x, top_k=k, act="silu",
                                 capacity_factor=float(E))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-4)
    assert np.isfinite(float(aux))


def test_a2a_matches_reference_multi_shard():
    """4 data shards x 1 model shard: full-capacity dispatch == reference."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from repro.models import moe as moe_mod
        from repro.models.moe_dispatch import moe_apply_a2a, set_dispatch_mesh

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("data",))
        d, f, E, k = 16, 32, 8, 2
        params = moe_mod.moe_init(jax.random.PRNGKey(0), d, E, f, "silu")
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, d))
        want = moe_mod.moe_reference(params, x, top_k=k, act="silu")
        set_dispatch_mesh(mesh)
        with jax.set_mesh(mesh):
            got, _ = jax.jit(lambda p, xx: moe_apply_a2a(
                p, xx, top_k=k, act="silu", capacity_factor=float(E)))(
                    params, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-4)
        print("MULTI_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert "MULTI_OK" in out.stdout, out.stderr[-2000:]


def test_a2a_ep_tp_matches_reference():
    """2 data x 2 model shards: the EP x TP path (psum over model)."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from repro.models import moe as moe_mod
        from repro.models.moe_dispatch import moe_apply_a2a, set_dispatch_mesh

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        d, f, E, k = 16, 32, 4, 2
        params = moe_mod.moe_init(jax.random.PRNGKey(0), d, E, f, "silu")
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 4, d))
        want = moe_mod.moe_reference(params, x, top_k=k, act="silu")
        set_dispatch_mesh(mesh)
        with jax.set_mesh(mesh):
            got, _ = jax.jit(lambda p, xx: moe_apply_a2a(
                p, xx, top_k=k, act="silu", capacity_factor=float(E)))(
                    params, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-4)
        print("EPTP_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert "EPTP_OK" in out.stdout, out.stderr[-2000:]


def test_a2a_tight_capacity_drops_like_gather_path():
    """With a tight factor the dispatch drops tokens (documented trade-off)
    but stays finite and shaped correctly."""
    mesh = make_mesh((1, 1), ("data", "model"))
    d, f, E, k = 8, 16, 4, 1
    params = moe_mod.moe_init(jax.random.PRNGKey(0), d, E, f, "silu")
    x = jnp.broadcast_to(
        jax.random.normal(jax.random.PRNGKey(1), (1, 1, d)), (1, 16, d))
    set_dispatch_mesh(mesh)
    with jax.set_mesh(mesh):
        tight, _ = moe_apply_a2a(params, x, top_k=k, act="silu",
                                 capacity_factor=0.25)
        full, _ = moe_apply_a2a(params, x, top_k=k, act="silu",
                                capacity_factor=float(E))
    assert bool(jnp.all(jnp.isfinite(tight)))
    assert float(jnp.abs(tight - full).max()) > 1e-6
