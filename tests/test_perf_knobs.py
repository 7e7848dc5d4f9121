"""Perf knobs must not change semantics: remat == same loss & gradients."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_CONFIGS
from repro.models import transformer as tfm


def _loss_fn(cfg):
    def loss(params, batch, labels):
        out = tfm.forward_seq(cfg, params, batch)
        lg = out["logits"].astype(jnp.float32)
        lz = jax.nn.logsumexp(lg, -1)
        oh = jax.nn.one_hot(labels, lg.shape[-1])
        return jnp.mean(lz - jnp.sum(lg * oh, -1))
    return loss


@pytest.mark.parametrize("arch", ["smollm-135m", "granite-moe-1b-a400m",
                                  "gemma3-1b"])
@pytest.mark.parametrize("remat", ["attn", "layer"])
def test_remat_preserves_loss_and_grads(arch, remat):
    base = ARCH_CONFIGS[arch].reduced()
    cfg_r = dataclasses.replace(base, remat=remat)
    params = tfm.init_params(base, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                          base.vocab_size)}
    labels = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                                base.vocab_size)

    l0, g0 = jax.value_and_grad(_loss_fn(base))(params, batch, labels)
    l1, g1 = jax.value_and_grad(_loss_fn(cfg_r))(params, batch, labels)
    np.testing.assert_allclose(l0, l1, rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4),
        g0, g1)


def test_serve_ep_flag_accepted_on_host_mesh():
    """EP sharding rules produce valid specs on any mesh (host mesh here;
    the 256-chip layout is proven by the dry-run artifacts)."""
    from repro.launch import sharding as sh
    from repro.launch.mesh import make_host_mesh
    cfg = ARCH_CONFIGS["granite-moe-1b-a400m"].reduced()
    struct = jax.eval_shape(lambda k: tfm.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    mesh = make_host_mesh()
    shardings = sh.param_shardings(mesh, struct, fsdp=False, ep=True)
    jax.tree.map(lambda leaf, s: None, struct, shardings)  # structure match


def test_pallas_attn_impl_matches_jnp_end_to_end():
    """attn_impl='pallas_interpret' (the flash train kernel in the Pallas
    interpreter) gives the same loss and gradients as the jnp scan path
    inside a full model."""
    base = ARCH_CONFIGS["smollm-135m"].reduced()
    cfg_p = dataclasses.replace(base, attn_impl="pallas_interpret")
    params = tfm.init_params(base, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                          base.vocab_size)}
    labels = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                                base.vocab_size)
    l0, g0 = jax.value_and_grad(_loss_fn(base))(params, batch, labels)
    l1, g1 = jax.value_and_grad(_loss_fn(cfg_p))(params, batch, labels)
    np.testing.assert_allclose(l0, l1, rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-3),
        g0, g1)
