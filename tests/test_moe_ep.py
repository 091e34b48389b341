"""MoE expert-parallel (shard_map) path vs the sort-dispatch oracle.

On a 1x1 mesh the EP path must be numerically identical to the sort
implementation (same routing, same capacity math, e_base=0)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoECfg
from repro.models import moe as MOE


def _setup(seed=0, B=2, T=16, D=32, E=8, k=2, F=16, shared=0):
    mo = MoECfg(num_experts=E, top_k=k, d_expert_ff=F, n_shared=shared,
                d_shared_ff=F if shared else 0, capacity_factor=2.0)
    key = jax.random.PRNGKey(seed)
    p, _ = MOE.moe_params(key, D, mo, n_layers=1)
    pl = jax.tree.map(lambda a: a[0], p)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, T, D),
                          jnp.float32)
    return pl, x, mo


@pytest.mark.parametrize("shared", [0, 1])
def test_ep_matches_sort_on_1x1_mesh(shared):
    pl, x, mo = _setup(shared=shared)
    want, aux_want = MOE.moe_ffn(pl, x, mo, impl="sort")
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1])
    with jax.sharding.set_mesh(mesh):
        got, aux_got = jax.jit(
            lambda p_, x_: MOE.moe_ffn(p_, x_, mo, impl="auto"))(pl, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(float(aux_got), float(aux_want), rtol=1e-4)


def test_auto_without_mesh_is_sort():
    pl, x, mo = _setup()
    a, _ = MOE.moe_ffn(pl, x, mo, impl="auto")
    b, _ = MOE.moe_ffn(pl, x, mo, impl="sort")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ep_grads_match_sort():
    pl, x, mo = _setup()

    def loss_sort(p_, x_):
        o, aux = MOE.moe_ffn(p_, x_, mo, impl="sort")
        return jnp.sum(o * o) + aux

    def loss_ep(p_, x_):
        o, aux = MOE.moe_ffn(p_, x_, mo, impl="auto")
        return jnp.sum(o * o) + aux

    g1 = jax.grad(loss_sort)(pl, x)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1])
    with jax.sharding.set_mesh(mesh):
        g2 = jax.jit(jax.grad(loss_ep))(pl, x)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-5, atol=5e-5)
