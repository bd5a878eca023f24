"""Unit tests for the repro.dist substrate that need no subprocess mesh:
the batch-axes context protocol, filter_spec's adaptation rules, the
param-spec name rules (lead/fsdp variants), dp_param_specs, shard_attn_qkv
off-mesh behaviour, and the collectives helpers' numerics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist import collectives
from repro.dist.sharding import (DEFAULT_BATCH_AXES, batch_axes, batch_spec,
                                 current_batch_axes, dp_param_specs,
                                 filter_spec, named_shardings, param_specs,
                                 shape_of, shard, shard_attn_qkv)


class FakeMesh:
    """Duck-typed mesh: filter_spec/param_specs only read axis_names and
    axis_sizes, so spec logic is testable on a single CPU device."""

    def __init__(self, dims: dict):
        self.axis_names = tuple(dims)
        self.axis_sizes = tuple(dims.values())


MESH = FakeMesh({"data": 4, "model": 2})
MESH3 = FakeMesh({"pod": 2, "data": 4, "model": 2})


# ---------------------------------------------------------------------------
# batch_axes context
# ---------------------------------------------------------------------------


def test_batch_axes_nesting_restores_on_exit():
    assert current_batch_axes() == DEFAULT_BATCH_AXES
    with batch_axes("model"):
        assert current_batch_axes() == ("model",)
        with batch_axes():
            assert current_batch_axes() == ()
            assert batch_spec(None) == (None, None)
        assert current_batch_axes() == ("model",)
    assert current_batch_axes() == DEFAULT_BATCH_AXES


def test_batch_axes_restores_on_exception():
    with pytest.raises(RuntimeError):
        with batch_axes("data"):
            raise RuntimeError("boom")
    assert current_batch_axes() == DEFAULT_BATCH_AXES


def test_batch_spec_prepends_current_axes():
    assert batch_spec(None, "model") == (("pod", "data"), None, "model")
    with batch_axes("data"):
        assert batch_spec() == (("data",),)


# ---------------------------------------------------------------------------
# filter_spec
# ---------------------------------------------------------------------------


def test_filter_spec_drops_unknown_axes():
    spec = filter_spec(MESH, (("pod", "data"), None), (8, 16))
    assert spec == P("data", None)


def test_filter_spec_divisibility_fallback():
    # 6 % 4 != 0 -> dim replicated, NOT unevenly sharded
    assert filter_spec(MESH, ("data", None), (6, 16)) == P(None, None)
    # tuple entry: (pod, data) product 8 divides 16
    assert filter_spec(MESH3, (("pod", "data"), None), (16, 3)) == \
        P(("pod", "data"), None)
    # product 8 does not divide 12 -> whole entry replicated
    assert filter_spec(MESH3, (("pod", "data"), None), (12, 3)) == P(None, None)


def test_filter_spec_axis_reuse_first_dim_wins():
    # "model" consumed by dim 0 (the DP-plan batch) is dropped from dim 2
    spec = filter_spec(MESH, (("model",), None, "model"), (8, 4, 16))
    assert spec == P("model", None, None)


def test_filter_spec_rejects_excess_entries():
    with pytest.raises(ValueError):
        filter_spec(MESH, (None, None, None), (4, 4))


# ---------------------------------------------------------------------------
# param_specs name rules
# ---------------------------------------------------------------------------


def _sds(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


PARAMS = {
    "embed": {"table": _sds(512, 64)},
    "lm_head": {"w": _sds(64, 512)},
    "blocks": {
        "attn": {"wq": {"w": _sds(8, 64, 128)},
                 "wo": {"w": _sds(8, 128, 64)}},
        "mlp": {"w_up": {"w": _sds(8, 64, 128)},
                "w_down": {"w": _sds(8, 128, 64)}},
        "ln1": {"scale": _sds(8, 64)},
    },
}


def test_param_specs_col_row_and_replicated():
    specs = param_specs(PARAMS, MESH)
    assert specs["embed"]["table"] == P(None, "model")
    assert specs["lm_head"]["w"] == P(None, "model")
    assert specs["blocks"]["attn"]["wq"]["w"] == P(None, None, "model")
    assert specs["blocks"]["attn"]["wo"]["w"] == P(None, "model", None)
    assert specs["blocks"]["mlp"]["w_down"]["w"] == P(None, "model", None)
    assert specs["blocks"]["ln1"]["scale"] == P(None, None)


def test_param_specs_lead_consumes_leading_dims():
    stacked = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((2, 4) + s.shape, s.dtype), PARAMS)
    specs = param_specs(stacked, MESH3, lead=("pod", "data"))
    assert specs["embed"]["table"] == P("pod", "data", None, "model")
    assert specs["blocks"]["attn"]["wq"]["w"] == \
        P("pod", "data", None, None, "model")
    # lead axes missing from the mesh are dropped, not errors
    specs2 = param_specs(stacked, MESH, lead=("pod", "data"))
    assert specs2["embed"]["table"] == P(None, "data", None, "model")


def test_param_specs_fsdp_axis_shards_complement_dim():
    specs = param_specs(PARAMS, MESH, fsdp_axis="data")
    # column-parallel: model on -1, fsdp on -2
    assert specs["embed"]["table"] == P("data", "model")
    assert specs["blocks"]["attn"]["wq"]["w"] == P(None, "data", "model")
    # row-parallel: model on -2, fsdp on -1
    assert specs["blocks"]["attn"]["wo"]["w"] == P(None, "model", "data")
    # unmatched leaves get plain trailing-dim FSDP
    assert specs["blocks"]["ln1"]["scale"] == P(None, "data")


def test_param_specs_divisibility_falls_back_per_dim():
    odd = {"wq": {"w": _sds(64, 3)}, "w_down": {"w": _sds(3, 64)}}
    specs = param_specs(odd, MESH)
    assert specs["wq"]["w"] == P(None, None)        # 3 % model(2) != 0
    assert specs["w_down"]["w"] == P(None, None)


def test_dp_param_specs_shards_innermost_divisible_dim():
    specs = dp_param_specs(PARAMS, MESH, lead=())
    assert specs["embed"]["table"] == P(None, "model")
    assert specs["blocks"]["ln1"]["scale"] == P(None, "model")  # 64 % 2 == 0
    odd = {"x": _sds(8, 3)}
    assert dp_param_specs(odd, MESH)["x"] == P("model", None)   # falls inward
    assert dp_param_specs({"x": _sds(3, 3)}, MESH)["x"] == P(None, None)


def test_dp_param_specs_respects_lead():
    stacked = {"w": _sds(2, 4, 64)}
    specs = dp_param_specs(stacked, MESH3, lead=("pod", "data"))
    assert specs["w"] == P("pod", "data", "model")
    # lead dims are never candidates for the model shard
    scalarish = {"count": _sds(2, 4)}
    assert dp_param_specs(scalarish, MESH3, lead=("pod", "data"))["count"] == \
        P("pod", "data")


# ---------------------------------------------------------------------------
# off-mesh behaviour + utilities
# ---------------------------------------------------------------------------


def test_shard_is_identity_without_mesh_context():
    x = jnp.ones((4, 8))
    assert shard(x, "data", "model") is x
    q = k = v = jnp.ones((2, 4, 4, 8))
    q2, k2, v2 = shard_attn_qkv(q, k, v)
    assert q2 is q and k2 is k and v2 is v


def test_named_shardings_passthrough_and_shape_of():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    tree = {"a": P("data"), "b": None}
    mapped = named_shardings(mesh, tree)
    assert isinstance(mapped["a"], jax.sharding.NamedSharding)
    assert mapped["a"].spec == P("data")
    assert mapped["b"] is None  # non-spec leaves pass through untouched
    assert shape_of(jax.ShapeDtypeStruct((3, 5), jnp.float32)) == (3, 5)


# ---------------------------------------------------------------------------
# collectives numerics (CPU, no mesh)
# ---------------------------------------------------------------------------


def test_average_agents_matches_manual_weighted_mean():
    k = jax.random.key(0)
    x = jax.random.normal(k, (2, 3, 5))
    w = jnp.array([[0.1, 0.2, 0.1], [0.2, 0.3, 0.1]], jnp.float32)
    out = collectives.average_agents({"x": x}, w)["x"]
    want = jnp.einsum("pa,pa...->...", w, x)
    np.testing.assert_allclose(out[0, 0], want, rtol=1e-6)
    np.testing.assert_allclose(out[1, 2], want, rtol=1e-6)  # broadcast back


def test_average_agents_sync_dtype_quantises():
    x = jnp.full((1, 2, 4), 1.0 + 2 ** -12, jnp.float32)
    w = jnp.full((1, 2), 0.5, jnp.float32)
    out = collectives.average_agents({"x": x}, w, sync_dtype=jnp.bfloat16)["x"]
    assert out.dtype == jnp.float32           # master copy stays f32
    np.testing.assert_allclose(out, 1.0)      # but the wire word dropped 2^-12


def test_average_intra_pod_is_per_pod():
    x = jnp.stack([jnp.zeros((2, 3)), jnp.ones((2, 3))])  # (P=2, A=2, 3)
    w = jnp.full((2, 2), 0.25, jnp.float32)
    out = collectives.average_intra_pod({"x": x}, w)["x"]
    np.testing.assert_allclose(out[0], 0.0)
    np.testing.assert_allclose(out[1], 1.0)


def test_sync_and_tree_bytes():
    tree = {"a": jnp.zeros((4, 4), jnp.float32), "b": jnp.zeros((8,), jnp.float32)}
    assert collectives.tree_bytes(tree) == (16 + 8) * 4
    assert collectives.sync_bytes(tree) == (16 + 8) * 4
    assert collectives.sync_bytes(tree, sync_dtype=jnp.bfloat16) == (16 + 8) * 2
    assert collectives.agent_axes() == ("pod", "data")
