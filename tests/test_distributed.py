"""Mesh / collectives / fleet tests on the 8-virtual-device CPU mesh
(SURVEY.md §4 implication (c): fake-mesh layer for distributed logic)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist


def setup_function(_):
    dist.destroy_process_group()
    dist.set_mesh(None)


def test_build_mesh_axes():
    m = dist.build_mesh({"data": 2, "model": 4})
    assert m.shape == {"data": 2, "model": 4}
    assert m.axis_names == ("data", "model")


def test_hybrid_mesh_autofill_dp():
    m = dist.init_hybrid_mesh(mp=2, pp=2)  # dp auto-fills to 2 on 8 devices
    assert m.shape["data"] == 2 and m.shape["model"] == 2 and m.shape["pipe"] == 2


def test_all_reduce_traced_psum():
    m = dist.init_hybrid_mesh(dp=8)
    g = dist.new_group(axis="data")
    from jax.sharding import PartitionSpec as P

    def f(x):
        t = paddle.Tensor(x)
        return dist.all_reduce(t, group=g)._data

    fn = jax.jit(jax.shard_map(f, mesh=m, in_specs=(P("data"),), out_specs=P(), check_vma=False))
    x = jnp.arange(8.0)
    out = fn(x)
    assert np.allclose(np.asarray(out), 28.0)


def test_all_reduce_eager_sharded():
    m = dist.init_hybrid_mesh(dp=8)
    g = dist.new_group(axis="data")
    x = paddle.to_tensor(np.arange(16.0, dtype=np.float32).reshape(8, 2))
    x = dist.shard_batch(x)
    dist.all_reduce(x, group=g)
    # each shard (1,2) summed over axis -> result shape (1,2)? all_reduce over
    # the sharded dim sums shard-local blocks: (8,2) sharded into 8 x (1,2)
    assert np.allclose(x.numpy(), np.arange(16.0).reshape(8, 2).sum(0, keepdims=True))


def test_all_reduce_degenerate_identity():
    dist.init_hybrid_mesh(dp=8)
    g = dist.new_group(axis="model")  # size-1 axis
    x = paddle.to_tensor([1.0, 2.0])
    out = dist.all_reduce(x, group=g)
    assert np.allclose(out.numpy(), [1.0, 2.0])


def test_all_gather_traced():
    m = dist.init_hybrid_mesh(dp=4, mp=2)
    g = dist.new_group(axis="model")
    from jax.sharding import PartitionSpec as P

    def f(x):
        outs = []
        dist.all_gather(outs, paddle.Tensor(x), group=g)
        return jnp.concatenate([o._data for o in outs])

    fn = jax.jit(jax.shard_map(f, mesh=m, in_specs=(P(("data", "model")),), out_specs=P("data"), check_vma=False))
    out = fn(jnp.arange(8.0))
    # each model-pair gathers its two shards; stitched over data -> identity
    assert out.shape == (8,) and np.allclose(np.asarray(out), np.arange(8.0))


def test_fleet_init_dp_model():
    strat = dist.fleet.DistributedStrategy()
    dist.fleet.init(is_collective=True, strategy=strat)
    hcg = dist.fleet.get_hybrid_communicate_group()
    assert hcg.get_data_parallel_world_size() == 8
    assert hcg.get_parallel_mode() == "data_parallel"

    lin = paddle.nn.Linear(4, 2)
    m = dist.fleet.distributed_model(lin)
    x = paddle.to_tensor(np.random.rand(8, 4).astype(np.float32))
    y = m(x)
    assert y.shape == [8, 2]


def test_fleet_hybrid_topology():
    strat = dist.fleet.DistributedStrategy()
    strat.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 2}
    dist.fleet.init(strategy=strat)
    hcg = dist.fleet.get_hybrid_communicate_group()
    assert hcg.get_model_parallel_world_size() == 2
    assert hcg.get_pipe_parallel_world_size() == 2
    assert hcg.get_parallel_mode() == "hybrid"


def test_shard_batch_places_on_mesh():
    m = dist.init_hybrid_mesh(dp=8)
    x = paddle.to_tensor(np.zeros((16, 3), np.float32))
    xs = dist.shard_batch(x)
    assert "data" in str(xs._data.sharding.spec)


def test_barrier_and_world_size():
    dist.init_parallel_env()
    assert dist.get_world_size() >= 1
    dist.barrier()


def test_get_group_unknown_gid_raises():
    dist.init_parallel_env()
    with pytest.raises(ValueError):
        dist.collective.get_group(999999)


def test_get_rank_group_local():
    dist.init_hybrid_mesh(dp=8)
    g = dist.new_group(axis="data")
    g.ranks = [3, 4, 5]  # simulate a subgroup not containing rank 0 at pos 0
    assert dist.get_rank(g) == g.get_group_rank(0)


def test_broadcast_src_maps_to_group_index():
    m = dist.init_hybrid_mesh(dp=4, mp=2)
    g = dist.new_group(axis="model")
    with pytest.raises(ValueError):
        dist.broadcast(paddle.to_tensor(np.ones(4, np.float32)), src=5, group=g)


def test_eager_unsharded_collectives_raise_not_silent():
    dist.init_hybrid_mesh(dp=8)
    g = dist.new_group(axis="data")
    t = paddle.to_tensor(np.ones((8, 2), np.float32))
    with pytest.raises(NotImplementedError):
        dist.collective.scatter(t, [t] * 8, group=g)
    with pytest.raises(NotImplementedError):
        dist.collective.shift(t, offset=1, group=g)
    with pytest.raises(NotImplementedError):
        dist.collective.reduce_scatter(t, [t] * 8, group=g)


def test_reduce_scatter_degenerate_tensor_list():
    dist.init_hybrid_mesh(dp=8)
    g = dist.Group(dist.get_mesh(), "")  # nranks == 1
    out = paddle.to_tensor(np.zeros((2,), np.float32))
    src = paddle.to_tensor(np.ones((2,), np.float32))
    dist.collective.reduce_scatter(out, [src], group=g)
    np.testing.assert_allclose(out.numpy(), np.ones((2,), np.float32))


def test_fleet_explicit_dp_mismatch_raises():
    strat = dist.fleet.DistributedStrategy()
    strat.hybrid_configs = {"dp_degree": 2, "mp_degree": 2}  # 4 != 8 devices
    with pytest.raises(ValueError):
        dist.fleet.init(strategy=strat)


def test_attention_dropout_on_probs():
    from paddle_tpu.nn import functional as F

    q = paddle.to_tensor(np.random.rand(2, 8, 2, 4).astype(np.float32))
    out0 = F.scaled_dot_product_attention(q, q, q, dropout_p=0.0)
    out_eval = F.scaled_dot_product_attention(q, q, q, dropout_p=0.9, training=False)
    np.testing.assert_allclose(out0.numpy(), out_eval.numpy(), atol=1e-6)
    out_tr = F.scaled_dot_product_attention(q, q, q, dropout_p=0.9, training=True)
    # prob-dropout changes values but never whole-output zeroing with renorm
    assert not np.allclose(out0.numpy(), out_tr.numpy())


def test_axis_group_ranks_are_global_device_ids():
    m = dist.init_hybrid_mesh(dp=4, mp=2)
    g = dist.new_group(axis="model")
    # local device 0 sits at dp-coord 0; its model-axis peers are the two
    # device ids in that dp row of the mesh array
    row = [int(d.id) for d in m.devices[0]]
    assert g.ranks == row
