"""shard_map expert-parallel MoE == the dropless grouped MoE (exact)."""
import os

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.configs import get_config
from repro.models.common import ParamBuilder
from repro.models.moe import init_moe, moe_ffn
from repro.models.moe_ep import moe_ffn_ep
from repro.sharding import TRAIN_RULES, tree_shardings
from repro.sharding.context import activation_sharding


def _setup(cf=8.0, experts=8, topk=2, seed=0):
    cfg = get_config("qwen3-moe-30b-a3b").smoke().replace(
        dtype="float32", moe_experts=experts, moe_top_k=topk,
        moe_capacity_factor=cf)
    b = ParamBuilder(jax.random.PRNGKey(seed), jnp.float32)
    init_moe(b, cfg)
    p, specs = b.build()
    _setup.specs = specs
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (4, 16, cfg.d_model), jnp.float32) * 0.3
    return cfg, p, x


@pytest.mark.parametrize("mesh_shape,names", [
    ((1,), ("data",)),
    ((4,), ("data",)),
    ((2, 2), ("data", "model")),
])
def test_ep_matches_gspmd(mesh_shape, names):
    if jax.device_count() < int(np.prod(mesh_shape)):
        pytest.skip("not enough devices")
    cfg, p, x = _setup()
    y0, p0, _ = moe_ffn(p, x, cfg)
    mesh = make_mesh(mesh_shape, names)
    y1, p1 = jax.jit(lambda pp, xx: moe_ffn_ep(pp, xx, cfg, mesh))(p, x)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(p0), np.asarray(p1).reshape(p0.shape),
                               rtol=1e-6, atol=1e-6)


def test_ep_gradients_flow():
    if jax.device_count() < 2:
        pytest.skip("needs 2 devices (full suite may init jax early)")
    cfg, p, x = _setup()
    mesh = make_mesh((2,), ("data",))

    def loss(pp):
        y, _ = moe_ffn_ep(pp, x, cfg, mesh)
        return jnp.sum(jnp.square(y))

    g = jax.jit(jax.grad(loss))(p)
    leaves = jax.tree.leaves(g)
    assert all(np.all(np.isfinite(np.asarray(l))) for l in leaves)
    assert any(float(jnp.abs(l).max()) > 0 for l in leaves)


def test_ep_capacity_drops_are_bounded():
    """With tight capacity the EP path drops tokens but stays finite and
    close to the (equally-dropping) reference in aggregate."""
    if jax.device_count() < 2:
        pytest.skip("needs 2 devices (full suite may init jax early)")
    cfg, p, x = _setup(cf=1.0)
    mesh = make_mesh((2,), ("data",))
    y1, _ = jax.jit(lambda: moe_ffn_ep(p, x, cfg, mesh))()
    assert np.all(np.isfinite(np.asarray(y1)))


@pytest.mark.parametrize("mesh_shape,names", [
    ((2,), ("data",)),
    ((2, 2), ("data", "model")),
])
def test_sharded_experts_take_the_capacity_dispatch(mesh_shape, names):
    """Where a mesh splits the experts, the layer takes the capacity
    dispatch (its rows are the experts' buffers, E*C), so GSPMD computes
    each expert where its weights live; with room for every pair it
    equals the dropless layer."""
    if jax.device_count() < int(np.prod(mesh_shape)):
        pytest.skip("not enough devices")
    cfg, p, x = _setup()
    y0, _, c0 = moe_ffn(p, x, cfg)
    mesh = make_mesh(mesh_shape, names)
    p = jax.device_put(p, tree_shardings(p, _setup.specs, TRAIN_RULES, mesh))

    def run(pp, xx):
        with activation_sharding(mesh, TRAIN_RULES):
            return moe_ffn(pp, xx, cfg)

    y1, _, c1 = jax.jit(run)(p, x)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               rtol=2e-5, atol=2e-5)
    t, k, e = x.shape[0] * x.shape[1], cfg.moe_top_k, cfg.moe_experts
    cap = int(t * k / e * cfg.moe_capacity_factor)
    assert int(c1["moe_pairs"]) == int(c0["moe_pairs"]) == t * k
    assert int(c0["moe_rows"]) == t * k and int(c1["moe_rows"]) == e * cap
