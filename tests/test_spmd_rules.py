"""SPMD rule library + reshard engine with Partial semantics.

Mirrors the reference's `test/auto_parallel/spmd_rules/test_matmul_rule.py`
etc. (dims_mapping in/out assertions) plus value-level reshard checks on
the CPU mesh.
"""

import numpy as np
import pytest

import jax

import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.distributed.auto_parallel import (DistAttr, PartialTensor,
                                                  infer_spmd, make_partial,
                                                  reshard_partial)
from paddle_tpu.distributed.auto_parallel.placement import (Partial,
                                                            Replicate, Shard)


# ------------------------------------------------------------------- rules
def test_matmul_rule_row_parallel():
    # x: [M/mesh0, K], y: [K, N] -> out [M/mesh0, N]
    ins, out = infer_spmd("matmul", DistAttr([0, -1]), DistAttr([-1, -1]))
    assert out == DistAttr([0, -1])


def test_matmul_rule_contraction_becomes_partial():
    # x: [M, K/mesh1], y: [K/mesh1, N] -> out [M, N] partial over mesh1
    ins, out = infer_spmd("matmul", DistAttr([-1, 1]), DistAttr([1, -1]))
    assert out.dims_mapping == [-1, -1]
    assert out.partial_dims == {1}


def test_matmul_rule_conflicting_shards_replicate():
    ins, out = infer_spmd("matmul", DistAttr([-1, 0]), DistAttr([1, -1]))
    # k mapped to both 0 and 1 -> conflict resolved; no crash
    assert out.ndim == 2


def test_matmul_rule_batched_and_transposed():
    # batched: [B/mesh0, M, K] @ [B/mesh0, K, N]
    ins, out = infer_spmd("matmul", DistAttr([0, -1, -1]),
                          DistAttr([0, -1, -1]))
    assert out == DistAttr([0, -1, -1])
    # trans_y: y is [N/mesh1, K]
    ins, out = infer_spmd("matmul", DistAttr([-1, -1]), DistAttr([1, -1]),
                          trans_y=True)
    assert out == DistAttr([-1, 1])


def test_elementwise_broadcast_merge():
    ins, out = infer_spmd("elementwise", DistAttr([0, -1]), DistAttr([-1]))
    assert out == DistAttr([0, -1])
    assert ins[1] == DistAttr([-1])
    ins, out = infer_spmd("elementwise", DistAttr([0, -1]), DistAttr([-1, 1]))
    assert out == DistAttr([0, 1])


def test_reduction_rule_partial():
    ins, out = infer_spmd("reduction", DistAttr([0, 1]), axis=1)
    assert out.dims_mapping == [0]
    assert out.partial_dims == {1}
    ins, out = infer_spmd("reduction", DistAttr([0, 1]), axis=1,
                          keep_dim=True)
    assert out.dims_mapping == [0, -1]
    # non-linear reductions (max) don't produce partials
    ins, out = infer_spmd("reduction", DistAttr([0, 1]), axis=1,
                          linear=False)
    assert out.partial_dims == set()


def test_reshape_rule_split_and_merge():
    # [B/mesh0, S*H] -> [B/mesh0, S, H]: shard follows leading group dim
    ins, out = infer_spmd("reshape", DistAttr([0, -1]),
                          src_shape=[8, 12], dst_shape=[8, 3, 4])
    assert out == DistAttr([0, -1, -1])
    # merge [B/mesh0, S, H] -> [B/mesh0, S*H]
    ins, out = infer_spmd("reshape", DistAttr([0, 1, -1]),
                          src_shape=[8, 3, 4], dst_shape=[8, 12])
    assert out == DistAttr([0, 1])


def test_transpose_embedding_softmax_rules():
    ins, out = infer_spmd("transpose", DistAttr([0, -1, 1]), perm=[2, 0, 1])
    assert out == DistAttr([1, 0, -1])

    ins, out = infer_spmd("embedding", DistAttr([0, -1]), DistAttr([1, -1]))
    assert out.dims_mapping == [0, -1, -1]
    assert out.partial_dims == {1}  # vocab-parallel partial

    ins, out = infer_spmd("softmax", DistAttr([0, 1]), axis=-1)
    assert out == DistAttr([0, -1])


def test_layer_norm_cross_entropy_concat_split_flash_rules():
    ins, out = infer_spmd("layer_norm", DistAttr([0, -1, 1]),
                          DistAttr([-1]), DistAttr([-1]),
                          begin_norm_axis=2)
    assert out == DistAttr([0, -1, -1])

    ins, out = infer_spmd("cross_entropy_with_softmax",
                          DistAttr([0, 1]), DistAttr([0]))
    assert out.dims_mapping == [0]
    assert out.partial_dims == {1}

    ins, out = infer_spmd("concat", [DistAttr([0, -1]), DistAttr([0, 1])],
                          axis=1)
    assert out == DistAttr([0, -1])

    ins, outs = infer_spmd("split", DistAttr([0, 1]), num=2, axis=1)
    assert all(o == DistAttr([0, -1]) for o in outs)

    # [B, S, H, D] layout: heads (dim 2) stay TP-sharded, seq must clear
    ins, out = infer_spmd("flash_attention", DistAttr([0, -1, 1, -1]),
                          DistAttr([0, -1, 1, -1]),
                          DistAttr([0, -1, 1, -1]))
    assert out == DistAttr([0, -1, 1, -1])
    ins, out = infer_spmd("flash_attention", DistAttr([0, 1, -1, -1]),
                          DistAttr([0, -1, -1, -1]),
                          DistAttr([0, -1, -1, -1]))
    assert out.dims_mapping[1] == -1  # sequence sharding cleared


def test_nonlinear_rules_force_partial_resolution():
    """softmax/layer_norm must demand p->r before running: inferred input
    clears partial (softmax of a partial sum is not a partial softmax)."""
    ins, out = infer_spmd("softmax", DistAttr([0, -1], partial_dims=[1]))
    assert ins[0].partial_dims == set()
    assert out.partial_dims == set()
    ins, out = infer_spmd("layer_norm", DistAttr([0, -1], partial_dims=[1]),
                          DistAttr([-1]), DistAttr([-1]))
    assert ins[0].partial_dims == set()


def test_concat_keeps_partials():
    ins, out = infer_spmd("concat",
                          [DistAttr([0, -1], partial_dims=[1]),
                           DistAttr([0, -1], partial_dims=[1])], axis=1)
    assert out.partial_dims == {1}


def test_flash_attention_no_double_mesh_dim():
    ins, out = infer_spmd("flash_attention", DistAttr([0, -1, -1, -1]),
                          DistAttr([-1, 0, -1, -1]),
                          DistAttr([-1, -1, -1, -1]))
    dms = [d for d in out.dims_mapping if d != -1]
    assert len(dms) == len(set(dms))  # each mesh dim at most once


def test_cross_entropy_merges_label_batch():
    ins, out = infer_spmd("cross_entropy_with_softmax",
                          DistAttr([-1, 1]), DistAttr([0]))
    # label batch shard merges into logits batch dim
    assert ins[0].dims_mapping[0] == 0
    assert ins[1].dims_mapping == [0]
    assert out.dims_mapping == [0]
    assert out.partial_dims == {1}


def test_mixed_partial_demands_resolution():
    """add(A_partial, B_full): the output must NOT be partial — B would be
    summed n times; the partial input's inferred attr drops the dim."""
    ins, out = infer_spmd("elementwise",
                          DistAttr([0, -1], partial_dims=[1]),
                          DistAttr([0, -1]))
    assert out.partial_dims == set()
    assert ins[0].partial_dims == set()
    # both partial: flows through
    ins, out = infer_spmd("elementwise",
                          DistAttr([0, -1], partial_dims=[1]),
                          DistAttr([0, -1], partial_dims=[1]))
    assert out.partial_dims == {1}
    # concat mixed
    ins, out = infer_spmd("concat",
                          [DistAttr([0, -1], partial_dims=[1]),
                           DistAttr([0, -1])], axis=1)
    assert out.partial_dims == set()


def test_nonlinear_reduction_clears_input_partial():
    ins, out = infer_spmd("reduction", DistAttr([0, -1], partial_dims=[1]),
                          axis=1, linear=False)
    assert ins[0].partial_dims == set()
    assert out.partial_dims == set()


def test_reshape_merged_group_forces_reshard_of_inner_shard():
    ins, out = infer_spmd("reshape", DistAttr([0, -1, 1]),
                          src_shape=[8, 3, 4], dst_shape=[8, 12])
    assert ins[0].dims_mapping == [0, -1, -1]  # inner shard must resolve
    assert out == DistAttr([0, -1])


def test_cross_entropy_hard_label_trailing_dim():
    ins, out = infer_spmd("cross_entropy_with_softmax",
                          DistAttr([0, 1]), DistAttr([0, -1]))
    assert ins[1].ndim == 2          # label keeps its rank
    assert ins[1].dims_mapping == [0, -1]
    assert out.dims_mapping == [0]
    assert out.partial_dims == {1}


def test_dist_reshard_api_still_callable():
    """The reshard submodule must not shadow the reshard() function."""
    import paddle_tpu.distributed as dist
    assert callable(dist.reshard)
    assert callable(dist.auto_parallel.reshard)


def test_make_partial_row_parallel_specs():
    mesh = _mesh(4)
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(8, 16).astype(np.float32))
    w = jnp.asarray(rng.randn(16, 4).astype(np.float32))
    pt = make_partial(lambda xl, wl: xl @ wl, mesh, "mp", x, w,
                      in_specs=(P(None, "mp"), P("mp", None)))
    out = reshard_partial(pt, Replicate())
    np.testing.assert_allclose(np.asarray(out._value), np.asarray(x @ w),
                               rtol=3e-5, atol=3e-5)


def test_unknown_rule_raises():
    with pytest.raises(KeyError):
        infer_spmd("no_such_op", DistAttr([-1]))


# ---------------------------------------------------------------- reshard
def _mesh(n=4, name="mp"):
    return Mesh(np.array(jax.devices()[:n]), (name,))


def test_partial_to_replicate_matches_full_matmul():
    """Row-parallel matmul -> PartialTensor -> p2r == serial result."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(8, 16).astype(np.float32))   # [M, K]
    w = jnp.asarray(rng.randn(16, 4).astype(np.float32))   # [K, N]
    mesh = _mesh(4)
    # shard K over mp: each rank multiplies its K/4 slice -> partial sums
    xs = jax.device_put(x, NamedSharding(mesh, P(None, "mp")))
    ws = jax.device_put(w, NamedSharding(mesh, P("mp", None)))

    def local_mm(x_loc, w_loc):
        return x_loc @ w_loc

    import functools

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P(None, "mp"), P("mp", None)),
                       out_specs=P("mp"))
    def partial_mm(xl, wl):
        return (xl @ wl)[None]

    pt = PartialTensor(partial_mm(xs, ws), mesh, "mp")
    out = reshard_partial(pt, Replicate())
    np.testing.assert_allclose(np.asarray(out._value), np.asarray(x @ w),
                               rtol=2e-5, atol=1e-5)
    assert out._value.sharding.is_fully_replicated


def test_partial_to_shard_reduce_scatter():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(8, 16).astype(np.float32))
    w = jnp.asarray(rng.randn(16, 8).astype(np.float32))
    mesh = _mesh(4)

    import functools

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P(None, "mp"), P("mp", None)),
                       out_specs=P("mp"))
    def partial_mm(xl, wl):
        return (xl @ wl)[None]

    xs = jax.device_put(x, NamedSharding(mesh, P(None, "mp")))
    ws = jax.device_put(w, NamedSharding(mesh, P("mp", None)))
    pt = PartialTensor(partial_mm(xs, ws), mesh, "mp")
    out = reshard_partial(pt, Shard(0))
    np.testing.assert_allclose(np.asarray(out._value), np.asarray(x @ w),
                               rtol=2e-5, atol=1e-5)
    spec = out._value.sharding.spec
    assert spec[0] == "mp"


def test_make_partial_helper():
    mesh = _mesh(4)
    a = jnp.arange(16, dtype=jnp.float32)  # sharded into 4 chunks of 4
    pt = make_partial(lambda chunk: chunk.sum(keepdims=True), mesh, "mp", a)
    assert isinstance(pt, PartialTensor)
    out = reshard_partial(pt, Replicate())
    assert float(np.asarray(out._value)[0]) == float(a.sum())


def test_shard_replicate_moves():
    from paddle_tpu.distributed.auto_parallel.reshard import get_reshard_fn
    mesh = _mesh(4)
    v = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)
    # r -> s
    vs = get_reshard_fn(Replicate(), Shard(0))(v, Shard(0), mesh=mesh,
                                               axis_name="mp")
    assert vs.sharding.spec[0] == "mp"
    # s -> s (axis move)
    vss = get_reshard_fn(Shard(0), Shard(1))(vs, Shard(1), mesh=mesh,
                                             axis_name="mp")
    assert vss.sharding.spec[1] == "mp"
    # s -> r
    vr = get_reshard_fn(Shard(1), Replicate())(vss, Replicate(), mesh=mesh,
                                               axis_name="mp")
    assert vr.sharding.is_fully_replicated
    np.testing.assert_array_equal(np.asarray(vr), np.asarray(v))


def test_cross_mesh_reshard():
    """Reshard between DIFFERENT meshes (reference `reshard/nd_mesh_...` +
    cross-mesh functions): device_put re-lays the array out on the target
    mesh; values survive any (mesh, placement) -> (mesh, placement) hop."""
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist

    x = np.arange(32, dtype=np.float32).reshape(8, 4)
    mesh_a = dist.ProcessMesh(np.arange(8).reshape(4, 2),
                              dim_names=["dp", "mp"])
    mesh_b = dist.ProcessMesh(np.arange(8).reshape(2, 4),
                              dim_names=["x", "y"])
    t = dist.shard_tensor(paddle.to_tensor(x), mesh_a,
                          [dist.Shard(0), dist.Shard(1)])
    out = dist.reshard(t, mesh_b, [dist.Replicate(), dist.Shard(0)])
    np.testing.assert_array_equal(np.asarray(out._value), x)
    assert out._dist_attr["mesh"] is mesh_b
    # and back again with a different placement
    back = dist.reshard(out, mesh_a, [dist.Shard(1), dist.Replicate()])
    np.testing.assert_array_equal(np.asarray(back._value), x)


# ---------------------------------------------------------------- new rules
def _attr(*dm, partial=()):
    return DistAttr(list(dm), partial)


def test_squeeze_unsqueeze_rules():
    ins, out = infer_spmd("squeeze", _attr(0, -1, 1), axis=1)
    assert out.dims_mapping == [0, 1]
    ins, out = infer_spmd("unsqueeze", _attr(0, 1), axis=1)
    assert out.dims_mapping == [0, -1, 1]


def test_slice_stack_tile_rules():
    ins, out = infer_spmd("slice", _attr(0, 1), axes=[1])
    assert out.dims_mapping == [0, -1] and ins[0].dims_mapping == [0, -1]
    ins, out = infer_spmd("stack", [_attr(0, -1), _attr(-1, 1)], axis=0)
    assert out.dims_mapping == [-1, 0, 1]
    ins, out = infer_spmd("tile", _attr(0, 1), repeat_times=[1, 2])
    assert out.dims_mapping == [0, -1] and ins[0].dims_mapping == [0, -1]


def test_gather_scatter_rules():
    ins, out = infer_spmd("gather", _attr(0, 1), _attr(-1), axis=0)
    assert ins[0].dims_mapping == [-1, 1]
    assert out.dims_mapping == [-1, 1]
    ins, out = infer_spmd("scatter", _attr(0, 1), _attr(-1), _attr(-1, -1),
                          axis=0)
    assert ins[0].dims_mapping == [-1, 1]
    assert out.dims_mapping == [-1, 1]


def test_cumsum_dropout_rules_resolve_partial():
    ins, out = infer_spmd("cumsum", _attr(0, 1, partial=[2]), axis=1)
    assert out.dims_mapping == [0, -1] and not ins[0].partial_dims
    ins, out = infer_spmd("dropout", _attr(0, -1, partial=[1]))
    assert not ins[0].partial_dims and out.dims_mapping == [0, -1]


def test_rms_norm_fused_rope_rules():
    ins, out = infer_spmd("rms_norm", _attr(0, 1, 2), _attr(2),
                          begin_norm_axis=2)
    assert out.dims_mapping == [0, 1, -1]
    assert ins[1].dims_mapping == [-1]
    ins, outs = infer_spmd("fused_rope", _attr(0, 1, 2, -1),
                           _attr(0, -1, 2, -1))
    assert outs[0].dims_mapping == [0, -1, 2, -1]
    assert outs[1].dims_mapping == [0, -1, 2, -1]


def test_topk_sort_argmax_rules():
    ins, outs = infer_spmd("topk", _attr(0, 1), k=2, axis=1)
    assert outs[0].dims_mapping == [0, -1]
    ins, out = infer_spmd("sort", _attr(0, 1), axis=0)
    assert out.dims_mapping == [-1, 1]
    ins, out = infer_spmd("argmax", _attr(0, 1), axis=1)
    assert out.dims_mapping == [0]


def test_pad_flip_roll_triu_rules():
    ins, out = infer_spmd("pad", _attr(0, 1), paddings=[0, 0, 1, 1])
    assert out.dims_mapping == [0, -1]
    ins, out = infer_spmd("flip", _attr(0, 1), axis=0)
    assert out.dims_mapping == [-1, 1]
    ins, out = infer_spmd("roll", _attr(0, 1), shifts=1, axis=1)
    assert out.dims_mapping == [0, -1]
    ins, out = infer_spmd("triu", _attr(0, 1, 2))
    assert out.dims_mapping == [0, -1, -1]


def test_optimizer_update_rules():
    ins, out = infer_spmd("adam", _attr(0, -1), _attr(-1, 1),
                          _attr(-1, -1), _attr(-1, -1))
    assert out.dims_mapping == [0, 1]
    assert all(i.dims_mapping == [0, 1] for i in ins)
    ins, out = infer_spmd("sgd", _attr(0), _attr(-1, ))
    assert out.dims_mapping == [0]


def test_where_one_hot_unbind_take_rules():
    ins, out = infer_spmd("where", _attr(0, -1), _attr(-1, 1), _attr(-1, -1))
    assert out.dims_mapping == [0, 1]
    ins, out = infer_spmd("one_hot", _attr(0, 1), num_classes=8)
    assert out.dims_mapping == [0, 1, -1]
    ins, out = infer_spmd("unbind", _attr(0, 1), axis=0)
    assert out.dims_mapping == [1]
    ins, out = infer_spmd("take_along_axis", _attr(0, 1), _attr(0, -1),
                          axis=1)
    assert out.dims_mapping == [0, -1]


# --------------------------------------------- property tests: rule vs GSPMD
def _gspmd_decision(fn, in_attrs, shapes, mesh_axes=("dp", "mp")):
    """Lay inputs out per the rule's INFERRED attrs, jit with no output
    constraint, and return the output dims_mapping GSPMD chose."""
    n = 4
    devs = np.array(jax.devices()[:n]).reshape(2, 2)
    mesh = Mesh(devs, mesh_axes)
    args = []
    for attr, shape in zip(in_attrs, shapes):
        spec = P(*[mesh_axes[d] if d != -1 else None
                   for d in attr.dims_mapping])
        x = jnp.arange(np.prod(shape), dtype=jnp.float32).reshape(shape)
        args.append(jax.device_put(x, NamedSharding(mesh, spec)))
    out = jax.jit(fn)(*args)
    spec = out.sharding.spec if hasattr(out.sharding, "spec") else ()
    got = []
    for i in range(out.ndim):
        ax = spec[i] if i < len(spec) else None
        got.append(-1 if ax is None else mesh_axes.index(ax))
    return got


@pytest.mark.parametrize("case", [
    ("transpose", lambda x: jnp.transpose(x, (1, 0)),
     [_attr(0, 1)], [(8, 8)], {"perm": (1, 0)}),
    ("unsqueeze", lambda x: x[:, None, :],
     [_attr(0, 1)], [(8, 8)], {"axis": 1}),
    ("squeeze", lambda x: x[:, 0, :],
     [_attr(0, -1, 1)], [(8, 1, 8)], {"axis": 1}),
    ("one_hot", lambda x: jax.nn.one_hot(x.astype(jnp.int32), 4),
     [_attr(0, 1)], [(8, 8)], {"num_classes": 4}),
])
def test_rule_matches_gspmd_decision(case):
    """The rule's predicted output placement must match XLA's actual
    propagation on the virtual mesh for shard-preserving ops."""
    name, fn, attrs, shapes, kw = case
    ins, out = infer_spmd(name, *attrs, **kw)
    got = _gspmd_decision(fn, ins if isinstance(ins, list) else [ins],
                          shapes)
    want = out.dims_mapping
    assert got == want, (name, got, want)


def test_elementwise_matches_gspmd():
    ins, out = infer_spmd("elementwise", _attr(0, -1), _attr(-1, 1))
    got = _gspmd_decision(lambda a, b: a + b, ins, [(8, 8), (8, 8)])
    assert got == out.dims_mapping


def test_reduction_partial_matches_gspmd_allreduce():
    """A linear reduction over a sharded axis: the rule says 'partial over
    that mesh dim'; GSPMD realizes it as an immediate all-reduce — the
    VALUES must equal the unsharded reduction."""
    x = np.arange(64, dtype=np.float32).reshape(8, 8)
    ins, out = infer_spmd("reduction", _attr(-1, 1), axis=1)
    assert out.partial_dims == {1}
    devs = np.array(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(devs, ("dp", "mp"))
    xs = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P(None, "mp")))
    got = jax.jit(lambda v: v.sum(1))(xs)
    np.testing.assert_allclose(np.asarray(got), x.sum(1))


def test_nd_mesh_reshard_decomposition():
    """N-D mesh reshard decomposes into per-axis steps (ref
    nd_mesh_reshard_function.cc): values survive any placement change."""
    from paddle_tpu.distributed.auto_parallel.reshard import nd_mesh_reshard
    from paddle_tpu.distributed.auto_parallel.placement import (
        Partial, Replicate, Shard)

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("x", "y"))
    v = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)
    src = jax.device_put(v, NamedSharding(mesh, P("x", "y")))
    out = nd_mesh_reshard(src, mesh, [Shard(0), Shard(1)],
                          [Replicate(), Shard(0)])
    assert out.sharding.spec == P("y", None) or \
        tuple(out.sharding.spec) == ("y",)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(v))
    # partial-over-x resolves by psum before relayout
    half = jax.device_put(v / 2, NamedSharding(mesh, P(None, "y")))
    outp = nd_mesh_reshard(half, mesh, [Partial(), Shard(1)],
                           [Replicate(), Shard(1)])
    np.testing.assert_allclose(np.asarray(outp), np.asarray(v))
    # x->p is not materializable: explicit error, not silent wrongness
    with pytest.raises(NotImplementedError):
        nd_mesh_reshard(src, mesh, [Shard(0), Shard(1)],
                        [Partial(), Shard(1)])


def test_r_to_p_roundtrip():
    from paddle_tpu.distributed.auto_parallel import (
        PartialTensor, get_reshard_fn)
    from paddle_tpu.distributed.auto_parallel.placement import (
        Partial, Replicate)
    mesh = Mesh(np.array(jax.devices()[:4]), ("mp",))
    v = jnp.arange(8, dtype=jnp.float32)
    pt = get_reshard_fn(Replicate(), Partial())(
        v, Partial(), mesh=mesh, axis_name="mp")
    back = get_reshard_fn(Partial(), Replicate())(pt, Replicate())
    np.testing.assert_array_equal(np.asarray(back), np.asarray(v))
