"""Sharding rule engine: divisibility fallbacks, axis reuse, full-zoo specs."""
import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.models import get_model
from repro.sharding import (SERVE_RULES, TRAIN_RULES, resolve_spec,
                            tree_specs)


def _mesh(shape=(2, 2), names=("data", "model")):
    devs = np.array(jax.devices()[:1] * int(np.prod(shape))).reshape(shape)
    return Mesh(devs, names)


MESH = _mesh()


class TestResolve:
    def test_basic_two_dim(self):
        # (embed, ff) with both divisible -> ('data', 'model')
        s = resolve_spec((64, 128), ("embed", "ff"), TRAIN_RULES, MESH)
        assert s == P("data", "model")

    def test_non_divisible_falls_back_to_replication(self):
        s = resolve_spec((63, 128), ("embed", "ff"), TRAIN_RULES, MESH)
        assert s == P(None, "model")

    def test_axis_reuse_forbidden(self):
        # experts -> data; embed also wants data but it's taken.
        s = resolve_spec((4, 64, 128), ("experts", "embed", "ff"),
                         TRAIN_RULES, MESH)
        assert s == P("data", None, "model")

    def test_multi_axis_batch(self):
        mesh = _mesh((2, 4, 2), ("pod", "data", "model"))
        s = resolve_spec((16, 128), ("batch", "seq"), TRAIN_RULES, mesh)
        assert s == P(("pod", "data"))

    def test_multi_axis_partial_fallback(self):
        # batch=2 on (pod=2, data=4): full product 8 fails, pick largest fit.
        mesh = _mesh((2, 4, 2), ("pod", "data", "model"))
        s = resolve_spec((2, 128), ("batch", "seq"), TRAIN_RULES, mesh)
        assert s == P("pod")

    def test_unknown_axis_replicates(self):
        s = resolve_spec((10, 10), ("mystery", "layers"), TRAIN_RULES, MESH)
        assert s == P()


@pytest.mark.parametrize("arch", sorted(ARCHS))
@pytest.mark.parametrize("rules", [TRAIN_RULES, SERVE_RULES],
                         ids=["train", "serve"])
def test_full_zoo_param_specs_resolve(arch, rules):
    """Every parameter of every arch gets a valid PartitionSpec on the
    production mesh shape (16, 16) — divisibility enforced by construction."""
    mesh = _mesh((16, 16), ("data", "model"))
    model = get_model(get_config(arch))
    shapes = model.param_shapes()
    specs_logical = model.param_specs()
    pspecs = tree_specs(shapes, specs_logical, rules, mesh)

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    checked = 0
    for sds, spec in zip(jax.tree.leaves(shapes),
                         jax.tree.leaves(pspecs, is_leaf=lambda x: isinstance(x, P))):
        for dim, entry in zip(sds.shape, tuple(spec) + (None,) * 10):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            prod = int(np.prod([sizes[a] for a in axes]))
            assert dim % prod == 0, (arch, sds.shape, spec)
            checked += 1
    assert checked > 0, f"{arch}: nothing sharded at all"


def test_moe_expert_weights_sharded_over_data_and_ff():
    mesh = _mesh((16, 16), ("data", "model"))
    model = get_model(get_config("qwen3-moe-235b-a22b"))
    shapes = model.param_shapes()
    logical = model.param_specs()
    pspecs = tree_specs(shapes, logical, TRAIN_RULES, mesh)
    w1 = pspecs["layers"]["moe"]["w1"]          # (layers, E, d, ff)
    assert w1 == P(None, "data", None, "model")


def test_kv_cache_sequence_sharded_for_serve():
    mesh = _mesh((16, 16), ("data", "model"))
    cfg = get_config("qwen3-14b")
    model = get_model(cfg)
    from repro.configs import shape_for
    shape = shape_for("decode_32k")
    cache_shapes = model.cache_input_specs(shape)
    cache_logical = model.cache_specs()
    pspecs = tree_specs(cache_shapes, cache_logical, SERVE_RULES, mesh)
    # (L, B, S, kv, hd): batch over data, seq over model (kv=8 not div 16)
    assert pspecs["layers"]["k"] == P(None, "data", "model")
