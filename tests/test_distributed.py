"""Distributed stack tests on the virtual 8-device CPU mesh (the "fake
backend" strategy from SURVEY.md §4: real XLA collectives, no TPU pod)."""

import jax

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import nn, optimizer
from paddle_tpu.distributed import fleet


@pytest.fixture()
def hybrid_env():
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 1,
                               "sharding_degree": 2, "sep_degree": 1}
    hcg = fleet.init(is_collective=True, strategy=strategy)
    yield hcg


def test_mesh_build_and_axes(hybrid_env):
    m = dist.get_mesh()
    assert dict(m.shape) == {"pp": 1, "dp": 2, "sharding": 2, "sep": 1,
                             "mp": 2}
    assert hybrid_env.get_model_parallel_world_size() == 2
    assert hybrid_env.get_data_parallel_world_size() == 2
    assert hybrid_env.get_sharding_parallel_world_size() == 2


def test_mesh_infers_remainder_axis():
    from paddle_tpu.distributed.mesh import build_mesh
    m = build_mesh({"dp": -1, "mp": 2})
    assert m.shape["dp"] == 4 and m.shape["mp"] == 2


def test_topology_comm_lists():
    from paddle_tpu.distributed.fleet import CommunicateTopology
    topo = CommunicateTopology(["data", "model"], [2, 4])
    assert topo.world_size() == 8
    groups = topo.get_comm_list("model")
    assert len(groups) == 2 and len(groups[0]) == 4
    assert topo.get_rank(data=1, model=2) == 6


def test_column_row_parallel_matches_dense(hybrid_env):
    paddle.seed(0)
    col = fleet.ColumnParallelLinear(8, 16, gather_output=False)
    row = fleet.RowParallelLinear(16, 8, input_is_parallel=True)
    x = paddle.randn([4, 8])
    out = row(col(x))
    dense = (x._value @ col.weight._value) @ row.weight._value \
        + row.bias._value + (col.bias._value @ row.weight._value)
    np.testing.assert_allclose(np.asarray(out._value), np.asarray(dense),
                               rtol=1e-4, atol=1e-5)
    assert col.weight._value.sharding.spec == P(None, "mp")
    assert row.weight._value.sharding.spec == P("mp", None)


def test_tp_backward_grad_sharded(hybrid_env):
    col = fleet.ColumnParallelLinear(4, 8, gather_output=True)
    out = col(paddle.randn([2, 4]))
    out.sum().backward()
    assert col.weight.grad is not None
    assert col.weight.grad._value.sharding.spec == P(None, "mp")


def test_vocab_parallel_embedding(hybrid_env):
    emb = fleet.VocabParallelEmbedding(64, 16)
    out = emb(paddle.randint(0, 64, [2, 5]))
    assert out.shape == [2, 5, 16]
    out.sum().backward()
    assert emb.weight.grad is not None


def test_data_parallel_batch_sharding(hybrid_env):
    net = nn.Linear(8, 2)
    dp = paddle.DataParallel(net)
    out = dp(paddle.randn([8, 8]))
    assert out._value.sharding.spec == P("dp", None)
    out.sum().backward()
    # grads on replicated params come out replicated (= allreduced)
    assert net.weight.grad._value.sharding.spec == P()


def test_dp_no_sync(hybrid_env):
    net = nn.Linear(4, 2)
    dp = paddle.DataParallel(net)
    with dp.no_sync():
        out = dp(paddle.randn([8, 4]))
    # inside no_sync the batch is NOT dp-sharded
    assert getattr(out._value.sharding, "spec", P()) != P("dp", None)


def test_zero1_sharded_optimizer_state(hybrid_env):
    net = nn.Linear(8, 2)
    opt = optimizer.Adam(learning_rate=0.01, parameters=net.parameters())
    hopt = fleet.distributed_optimizer(opt)
    net.weight.grad = paddle.randn([8, 2])
    net.bias.grad = paddle.randn([2])
    hopt.step()
    m1 = opt._accumulators["moment1"][id(net.weight)]
    assert tuple(m1.sharding.spec) == ("sharding",)
    # bias (size 2, not divisible by shard degree 2? it is) — just exists
    assert id(net.bias) in opt._accumulators["moment1"]


def test_dp_training_matches_single_device(hybrid_env):
    """Golden-loss parity: DP over 2 ranks == single device (same data)."""
    def run(parallel):
        paddle.seed(9)
        net = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
        model = paddle.DataParallel(net) if parallel else net
        opt = optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
        X = paddle.to_tensor(
            np.random.RandomState(0).rand(16, 4).astype("float32"))
        Y = X.sum(axis=1, keepdim=True)
        losses = []
        for _ in range(5):
            loss = nn.MSELoss()(model(X), Y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.item()))
        return losses

    np.testing.assert_allclose(run(True), run(False), rtol=1e-5)


def test_shard_tensor_and_reshard():
    mesh = dist.ProcessMesh([[0, 1, 2, 3], [4, 5, 6, 7]],
                            dim_names=["x", "y"])
    t = dist.shard_tensor(paddle.randn([8, 4]), mesh,
                          [dist.Shard(0), dist.Replicate()])
    assert t._value.sharding.spec == P("x", None)
    r = dist.reshard(t, mesh, [dist.Replicate(), dist.Shard(1)])
    assert r._value.sharding.spec == P(None, "y")
    np.testing.assert_allclose(np.asarray(dist.unshard_dtensor(r)._value),
                               np.asarray(t._value))


def test_placements_api():
    assert dist.Shard(1).get_dim() == 1
    assert dist.Replicate().is_replicated()
    assert dist.Partial().is_partial()
    assert dist.Shard(0) == dist.Shard(0)


def test_shard_layer():
    mesh = dist.ProcessMesh(list(range(8)), dim_names=["x"])
    net = nn.Linear(8, 8)

    def shard_fn(name, sublayer, m):
        for p in sublayer._parameters.values():
            if p is not None and p.ndim == 2:
                s = dist.shard_tensor(p, m, [dist.Shard(0)])
                p._value = s._value

    dist.shard_layer(net, mesh, shard_fn)
    assert net.weight._value.sharding.spec == P("x", None)


def test_shard_optimizer_inherits_param_sharding():
    mesh = dist.ProcessMesh(list(range(8)), dim_names=["x"])
    net = nn.Linear(8, 8)
    s = dist.shard_tensor(net.weight, mesh, [dist.Shard(0), dist.Replicate()])
    net.weight._value = s._value
    opt = dist.shard_optimizer(
        optimizer.Adam(learning_rate=0.01, parameters=net.parameters()))
    net.weight.grad = paddle.randn([8, 8])
    net.bias.grad = paddle.randn([8])
    opt.step()
    m1 = opt._inner._accumulators["moment1"][id(net.weight)]
    assert m1.sharding.spec == P("x", None)


def test_collectives_inside_shard_map(hybrid_env):
    m = dist.get_mesh()
    g = dist.new_group(axis="mp")

    def worker(x):
        with dist.axis_context("mp"):
            t = paddle.Tensor._wrap(x)
            dist.all_reduce(t, group=g)
            return t._value

    y = jax.jit(jax.shard_map(worker, mesh=m, in_specs=P("mp"),
                              out_specs=P("mp")))(
        jnp.arange(8, dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(y), [4, 6, 8, 10, 4, 6, 8, 10])


def test_allgather_reducescatter_inside_shard_map(hybrid_env):
    m = dist.get_mesh()
    g = dist.new_group(axis="dp")

    def worker(x):
        with dist.axis_context("dp"):
            t = paddle.Tensor._wrap(x)
            outs = []
            dist.all_gather(outs, t, group=g)
            summed = outs[0] + outs[1]
            return summed._value

    x = jnp.arange(8, dtype=jnp.float32)
    y = jax.jit(jax.shard_map(worker, mesh=m, in_specs=P("dp"),
                              out_specs=P("dp")))(x)
    np.testing.assert_allclose(np.asarray(y), [4, 6, 8, 10, 4, 6, 8, 10])


def test_spmd_pipeline_matches_serial():
    from paddle_tpu.distributed.fleet.spmd_pipeline import (
        pipeline_forward, stack_stage_params)
    devs = np.array(jax.devices()[:4]).reshape(4, 1)
    mesh = Mesh(devs, ("pp", "dp"))
    rng = np.random.RandomState(0)
    Ws = [rng.rand(8, 8).astype(np.float32) * 0.1 for _ in range(4)]
    stacked = stack_stage_params([{"w": jnp.asarray(W)} for W in Ws])

    def stage_fn(params, h):
        return jnp.tanh(h @ params["w"])

    M = 3
    x = rng.rand(M, 2, 8).astype(np.float32)

    def pipe(params, inputs):
        local = jax.tree_util.tree_map(lambda a: a[0], params)
        return pipeline_forward(stage_fn, local, inputs, n_microbatches=M)

    out = jax.jit(jax.shard_map(pipe, mesh=mesh, in_specs=(P("pp"), P()),
                                out_specs=P()))(stacked, jnp.asarray(x))
    ref = x.copy()
    for W in Ws:
        ref = np.tanh(ref @ W)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)


def test_pipeline_layer_and_host_schedule(hybrid_env):
    from paddle_tpu.distributed.fleet import (LayerDesc, PipelineLayer,
                                              PipelineParallel)
    paddle.seed(1)
    pipe = PipelineLayer(
        layers=[LayerDesc(nn.Linear, 4, 8), LayerDesc(nn.Tanh),
                LayerDesc(nn.Linear, 8, 4), LayerDesc(nn.Linear, 4, 1)],
        num_stages=2, loss_fn=nn.MSELoss())
    assert pipe.segment_parts == [0, 2, 4]
    strategy = fleet.DistributedStrategy()
    strategy.pipeline_configs["accumulate_steps"] = 2
    pp = PipelineParallel(pipe, hybrid_env, strategy)
    X = paddle.randn([8, 4])
    Y = X.sum(axis=1, keepdim=True)
    opt = optimizer.SGD(learning_rate=0.05, parameters=pipe.parameters())
    l0 = float(pp.train_batch((X, Y), opt).item())
    # graft-lint: disable=R010 (2-stage toy pipeline; ~1s measured)
    for _ in range(30):
        l = float(pp.train_batch((X, Y), opt).item())
    assert l < l0


def test_shared_layer_desc_ties_weights():
    from paddle_tpu.distributed.fleet import (PipelineLayer, SharedLayerDesc)
    pipe = PipelineLayer(layers=[
        SharedLayerDesc("emb", nn.Linear, None, "weight", 4, 4),
        nn.Tanh(),
        SharedLayerDesc("emb", nn.Linear, None, "weight", 4, 4)],
        num_stages=1)
    layers = list(pipe.run_function)
    assert layers[0] is layers[2]


def test_rng_tracker(hybrid_env):
    from paddle_tpu.distributed.fleet import get_rng_state_tracker
    from paddle_tpu.distributed.fleet.random import model_parallel_random_seed
    model_parallel_random_seed(123)
    tracker = get_rng_state_tracker()
    with tracker.rng_state():
        a = paddle.randn([4]).numpy()
    with tracker.rng_state():
        b = paddle.randn([4]).numpy()
    assert not np.array_equal(a, b)  # stateful within the tracker


def test_group_sharded_parallel_api(hybrid_env):
    net = nn.Linear(8, 8)
    opt = optimizer.Adam(learning_rate=0.01, parameters=net.parameters())
    model, opt2, _ = dist.sharding.group_sharded_parallel(net, opt, "p_g_os")
    assert net.weight._value.sharding.spec[0] == "sharding"


def test_distributed_batch_sampler_epoch_shuffle(hybrid_env):
    from paddle_tpu.io import DistributedBatchSampler

    class DS:
        def __len__(self):
            return 16

    s = DistributedBatchSampler(DS(), 4, num_replicas=2, rank=0, shuffle=True)
    e0 = [i for b in s for i in b]
    s.set_epoch(5)
    e1 = [i for b in s for i in b]
    assert e0 != e1


def test_zero_sharding_uses_any_divisible_dim(hybrid_mesh):
    """A (3, 8) param (dim0 not divisible by sharding=2) must still shard
    on dim 1 instead of silently replicating."""
    import warnings as _w
    from paddle_tpu.distributed.fleet import sharding as shmod

    sh = shmod._shard_spec_for((3, 8))
    assert sh is not None and sh.spec == P(None, "sharding")
    # dim0 divisible: prefers dim0
    sh0 = shmod._shard_spec_for((4, 6))
    assert sh0.spec[0] == "sharding"
    # nothing divisible: warns once, returns None
    shmod._warned_shapes.clear()
    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        assert shmod._shard_spec_for((3, 5)) is None
        assert shmod._shard_spec_for((3, 5)) is None
    assert len([r for r in rec if "sharding" in str(r.message)]) == 1


def test_stage2_validates_params(hybrid_mesh):
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.sharding import (
        GroupShardedOptimizerStage2)

    lin = paddle.nn.Linear(4, 4)
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=lin.parameters())
    GroupShardedOptimizerStage2(lin.parameters(), opt)  # ok
    other = paddle.nn.Linear(2, 2)
    with pytest.raises(ValueError):
        GroupShardedOptimizerStage2(other.parameters(), opt)


def test_stage2_offload_places_state_in_host_memory(hybrid_mesh):
    """ZeRO-Offload: optimizer state lives in pinned host memory (the
    jax memory_kind equivalent of the reference's CPU-side Adam)."""
    kinds = {m.kind for m in jax.local_devices()[0].addressable_memories()}
    if "pinned_host" not in kinds:
        pytest.skip("backend exposes no pinned_host memory space "
                    f"(has {sorted(kinds)}); offload degrades to default")
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.sharding import (
        GroupShardedOptimizerStage2)

    lin = paddle.nn.Linear(4, 4)
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=lin.parameters())
    sharded = GroupShardedOptimizerStage2(lin.parameters(), opt,
                                          offload=True)
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    for _ in range(2):  # second step exercises host->device staging too
        loss = (lin(x) * lin(x)).sum()
        loss.backward()
        sharded.step()
        sharded.clear_grad()
    mks = {getattr(a.sharding, "memory_kind", None)
           for accs in opt._accumulators.values()
           for a in accs.values()
           if hasattr(a, "sharding")}
    assert "pinned_host" in mks
    assert np.isfinite(np.asarray(lin.weight._value)).all()


def test_zero_sharding_preserves_tp_layout(hybrid_mesh):
    """A param already mp-sharded on some dim must keep that dim; ZeRO
    goes on a FREE divisible dim (and never double-applies)."""
    from paddle_tpu.distributed.fleet import sharding as shmod
    from paddle_tpu.distributed import mesh as meshmod

    m = meshmod.get_mesh()
    # vocab-parallel style: dim0 mp-sharded, dim1 free and divisible
    existing = NamedSharding(m, P("mp", None))
    sh = shmod._shard_spec_for((30522, 8), existing)
    assert sh is not None
    assert sh.spec[0] == "mp" and sh.spec[1] == "sharding"
    # already ZeRO-sharded: no double application
    assert shmod._shard_spec_for((8, 8), sh) is None
    # every dim taken or indivisible: keeps layout, returns None
    shmod._warned_shapes.clear()
    assert shmod._shard_spec_for((30521,), NamedSharding(m, P("mp"))) is None


def test_stage3_tp_composed_jitted_parity(hybrid_env):
    """ZeRO-3 (params sharded over 'sharding') composed with TP (mp) must
    train to the SAME losses as the unsharded model, with the whole step
    captured by to_static — the sharding lives as layout constraints
    inside one jitted program, not per-step host reshards."""
    from paddle_tpu.jit import to_static

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.col = fleet.ColumnParallelLinear(8, 16, gather_output=True)
            self.out = nn.Linear(16, 4)

        def forward(self, x):
            return self.out(paddle.nn.functional.relu(self.col(x)))

    def run(stage3):
        paddle.seed(7)
        net = Net()
        opt = optimizer.Adam(learning_rate=0.05,
                             parameters=net.parameters())
        if stage3:
            net, opt, _ = dist.sharding.group_sharded_parallel(
                net, opt, "p_g_os")
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.rand(4, 8).astype(np.float32))
        y = paddle.to_tensor(rng.rand(4, 4).astype(np.float32))

        def train_step(xb, yb):
            loss = ((net(xb) - yb) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        step = to_static(train_step)
        return [float(step(x, y).item()) for _ in range(3)]

    base = run(False)
    sharded = run(True)
    np.testing.assert_allclose(sharded, base, rtol=2e-5, atol=2e-6)
    assert base[-1] < base[0]  # actually trains


def test_stage3_param_layout_survives_jitted_steps(hybrid_env):
    """After jitted updates, stage-3 params must still carry the
    'sharding' axis in their layout (donated outputs keep shardings)."""
    from paddle_tpu.jit import to_static
    paddle.seed(0)
    net = nn.Linear(8, 8)
    opt = optimizer.SGD(learning_rate=0.01, parameters=net.parameters())
    net, opt, _ = dist.sharding.group_sharded_parallel(net, opt, "p_g_os")
    assert net.weight._value.sharding.spec[0] == "sharding"
    x = paddle.to_tensor(np.ones((2, 8), np.float32))

    def train_step(xb):
        loss = (net(xb) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = to_static(train_step)
    for _ in range(2):
        step(x)
    spec = net.weight._value.sharding.spec
    assert "sharding" in tuple(spec), spec


def test_stage2_custom_group_composes_with_tp(hybrid_mesh):
    """VERDICT r3 item 10: custom sharding groups — a group IS a mesh
    axis on TPU — compose eager ZeRO-2 with tensor parallelism: an
    mp-sharded (column-parallel) weight keeps its TP layout while its
    optimizer state and gradients shard over the CUSTOM group axis
    ('dp' here, not the default 'sharding')."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import paddle_tpu as paddle
    from paddle_tpu.distributed import mesh as _mesh
    from paddle_tpu.distributed.collective import new_group
    from paddle_tpu.distributed.fleet.sharding import (
        GroupShardedOptimizerStage2)

    mesh = _mesh.get_mesh()
    lin = paddle.nn.Linear(8, 8)
    # TP: column-parallel weight layout over mp
    lin.weight._value = jax.device_put(
        lin.weight._value, NamedSharding(mesh, P(None, "mp")))
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=lin.parameters())
    grp = new_group(axis="dp")
    sharded = GroupShardedOptimizerStage2(lin.parameters(), opt, group=grp)
    x = paddle.to_tensor(np.random.RandomState(0).randn(4, 8)
                         .astype(np.float32))
    losses = []
    for _ in range(3):
        loss = ((lin(x) - 1.0) ** 2).mean()
        loss.backward()
        sharded.step()
        sharded.clear_grad()
        losses.append(float(loss.numpy()))
    assert losses[-1] < losses[0]
    # TP layout preserved on the param
    assert "mp" in str(lin.weight._value.sharding.spec)
    # optimizer moments sharded over the CUSTOM axis, composing with mp
    m_acc = opt._accumulators["moment1"][id(lin.weight)]
    spec = m_acc.sharding.spec
    assert "dp" in str(spec), spec
    assert "sharding" not in str(spec), spec


def test_stage2_rejects_rank_list_groups(hybrid_mesh):
    from paddle_tpu.distributed.collective import new_group
    from paddle_tpu.distributed.fleet.sharding import (
        GroupShardedOptimizerStage2)
    import paddle_tpu as paddle
    lin = paddle.nn.Linear(4, 4)
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=lin.parameters())
    with pytest.raises(ValueError, match="mesh-axis"):
        GroupShardedOptimizerStage2(lin.parameters(), opt,
                                    group=new_group(ranks=[0, 1]))


def test_next_key_is_accepted_beside_mesh_sharded_arguments():
    """The framework RNG hands out UNCOMMITTED keys: a jit whose other
    arguments live on a multi-device mesh takes them (a key committed to
    device 0 is refused as "incompatible devices") — also after the
    chain was restored from a key that arrived committed."""
    import paddle_tpu as paddle
    from paddle_tpu.framework import random as prandom
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "mp"))
    x = jax.device_put(jnp.ones((4, 4)), NamedSharding(mesh, P("dp", "mp")))
    f = jax.jit(lambda x, k: x + jax.random.normal(k, x.shape))
    paddle.seed(11)
    key = prandom.next_key()
    assert not key.committed
    assert f(x, key).sharding.is_equivalent_to(x.sharding, 2)
    with pytest.raises(ValueError, match="incompatible devices"):
        f(x, jax.device_put(key, jax.devices()[1]))
    prandom.set_rng_state(jax.device_put(prandom.get_rng_state(),
                                         jax.devices()[1]))
    key2 = prandom.next_key()
    assert not key2.committed and not prandom.get_rng_state().committed
    f(x, key2)
