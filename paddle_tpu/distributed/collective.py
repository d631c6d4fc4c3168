"""Groups + functional collectives.

Parity: `python/paddle/distributed/communication/` (all_reduce `:20`,
group.py:22 Group) and the C++ ProcessGroup hierarchy
(`fluid/distributed/collective/process_group.h:47`).

TPU-native semantics: a Group names a mesh axis (or a sub-axis set).
Collectives have two execution modes:

* **inside shard_map / pipeline code** (an axis context is active): lower to
  `jax.lax.psum/all_gather/ppermute/all_to_all` over the named axis — these
  compile to ICI collectives;
* **eager on global arrays**: values are jax Arrays laid out over the global
  mesh; an all_reduce over axis X means "reduce the X-sharded/partial data",
  executed as a tiny cached jitted program.  With world_size==1 / no mesh the
  ops degrade to paddle's single-rank no-op semantics.
"""

from __future__ import annotations

import threading
from typing import Any, List, Optional

import jax
import jax.numpy as jnp

from ..framework.tensor import Tensor
from ..observability import metrics as _metrics
from ..ops.registry import dispatch as _d, register_op
from . import mesh as _mesh

_M_COLL_CALLS = _metrics.counter(
    "collective.calls", "collective API invocations per op")
_M_COLL_BYTES = _metrics.counter(
    "collective.bytes", "payload bytes entering each collective (per "
    "invocation; inside jit capture this counts per trace, not per run)")


def _instrument(op_name: str, *tensors) -> None:
    """Count one collective call + its input payload bytes."""
    if not _metrics.enabled():
        return
    nbytes = 0
    for t in tensors:
        try:
            v = t._value if isinstance(t, Tensor) else t
            n = 1
            for d in v.shape:
                n *= int(d)
            nbytes += n * jnp.dtype(v.dtype).itemsize
        except Exception:  # noqa: BLE001 - sizing is best-effort (tracers)
            pass
    _M_COLL_CALLS.inc(op=op_name)
    if nbytes:
        _M_COLL_BYTES.inc(nbytes, op=op_name)

__all__ = ["ReduceOp", "Group", "new_group", "get_group", "is_initialized",
           "all_reduce", "all_gather", "all_gather_object", "reduce",
           "reduce_scatter", "alltoall", "alltoall_single", "broadcast",
           "scatter", "gather", "send", "recv", "isend", "irecv", "barrier",
           "axis_context", "current_axis_for", "wait", "stream",
           "destroy_process_group"]


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A communication group = a named mesh axis (TPU-native ring)."""

    _counter = 0

    def __init__(self, axis: Optional[str] = None, ranks: Optional[List[int]] = None,
                 gid: Optional[int] = None):
        Group._counter += 1
        self.id = gid if gid is not None else Group._counter
        self.axis = axis
        self._ranks = ranks

    @property
    def nranks(self) -> int:
        if self.axis is not None:
            return _mesh.axis_size(self.axis)
        if self._ranks:
            return len(self._ranks)
        from .env import get_world_size
        return get_world_size()

    @property
    def world_size(self):
        return self.nranks

    @property
    def ranks(self):
        if self._ranks is not None:
            return self._ranks
        return list(range(self.nranks))

    def get_group_rank(self, global_rank: int) -> int:
        if self._ranks is not None and global_rank in self._ranks:
            return self._ranks.index(global_rank)
        return global_rank % max(self.nranks, 1)

    @property
    def rank(self):
        from .env import get_rank
        return self.get_group_rank(get_rank())

    @property
    def process_group(self):
        return self

    def __repr__(self):
        return f"Group(id={self.id}, axis={self.axis}, nranks={self.nranks})"


_groups = {}
_default_group: Optional[Group] = None


def _get_default_group() -> Group:
    global _default_group
    if _default_group is None:
        axes = _mesh.mesh_axes()
        _default_group = Group(axis=axes[0] if len(axes) == 1 else None, gid=0)
    return _default_group


def new_group(ranks=None, backend=None, timeout=None, axis=None) -> Group:
    g = Group(axis=axis, ranks=list(ranks) if ranks is not None else None)
    _groups[g.id] = g
    return g


def get_group(gid: int = 0) -> Group:
    if gid == 0:
        return _get_default_group()
    return _groups[gid]


def is_initialized() -> bool:
    from . import env
    return env.is_initialized()


def destroy_process_group(group=None):
    global _default_group
    _default_group = None
    _groups.clear()


# ------------------------------------------------------------ axis context
# Active named axes (inside shard_map'd pipeline/parallel code). paddle's
# ring-id plumbing is replaced by this stack.
_axis_state = threading.local()


class axis_context:
    """Marks named mesh axes as live (code runs under shard_map over them)."""

    def __init__(self, *axes: str):
        self.axes = axes

    def __enter__(self):
        stack = getattr(_axis_state, "stack", None)
        if stack is None:
            stack = _axis_state.stack = []
        stack.append(self.axes)
        return self

    def __exit__(self, *exc):
        _axis_state.stack.pop()
        return False


def _active_axes() -> tuple:
    stack = getattr(_axis_state, "stack", None)
    out = ()
    for axes in (stack or []):
        out += axes
    return out


def current_axis_for(group: Optional[Group]) -> Optional[str]:
    """Resolve which live named axis a collective over `group` targets."""
    group = group or _get_default_group()
    active = _active_axes()
    if group.axis is not None and group.axis in active:
        return group.axis
    if group.axis is None and len(active) == 1:
        return active[0]
    return None


# ------------------------------------------------------------ primitives
_REDUCERS = {
    ReduceOp.SUM: lambda x, ax: jax.lax.psum(x, ax),
    ReduceOp.MAX: lambda x, ax: jax.lax.pmax(x, ax),
    ReduceOp.MIN: lambda x, ax: jax.lax.pmin(x, ax),
    # exact product (exp∘psum∘log breaks on zeros/negatives)
    ReduceOp.PROD: lambda x, ax: jnp.prod(jax.lax.all_gather(x, ax), axis=0),
    ReduceOp.AVG: lambda x, ax: jax.lax.pmean(x, ax),
}

register_op("c_allreduce", lambda x, *, op, axis: _REDUCERS[op](x, axis))
register_op("c_allgather", lambda x, *, axis, tiled:
            jax.lax.all_gather(x, axis, tiled=tiled))
def _reducescatter_impl(x, op, axis):
    if op == ReduceOp.SUM:
        return jax.lax.psum_scatter(x, axis, tiled=True)
    if op == ReduceOp.AVG:
        return jax.lax.psum_scatter(x, axis, tiled=True) / \
            jax.lax.axis_size(axis)
    # MAX/MIN/PROD: full reduce then slice out this rank's tile
    n = jax.lax.axis_size(axis)
    if x.shape[0] % n != 0:
        raise ValueError(
            f"reduce_scatter: dim0 {x.shape[0]} not divisible by group "
            f"size {n}")
    full = _REDUCERS[op](x, axis)
    tile = x.shape[0] // n
    idx = jax.lax.axis_index(axis)
    return jax.lax.dynamic_slice_in_dim(full, idx * tile, tile, axis=0)


register_op("c_reducescatter", lambda x, *, op, axis:
            _reducescatter_impl(x, op, axis))
register_op("c_alltoall", lambda x, *, axis, split_axis, concat_axis:
            jax.lax.all_to_all(x, axis, split_axis=split_axis,
                               concat_axis=concat_axis, tiled=True))
register_op("c_ppermute", lambda x, *, axis, perm:
            jax.lax.ppermute(x, axis, perm))
register_op("c_broadcast_in_axis", lambda x, *, axis, src:
            _broadcast_impl(x, axis, src))
register_op("c_axis_index", lambda x, *, axis: jax.lax.axis_index(axis) + x * 0)


def _broadcast_impl(x, axis, src):
    idx = jax.lax.axis_index(axis)
    masked = jnp.where(idx == src, x, jnp.zeros_like(x))
    return jax.lax.psum(masked, axis)


def _single_rank(group: Optional[Group]) -> bool:
    group = group or _get_default_group()
    return group.nranks <= 1


# ------------------------------------------------------------ functional API
def _maybe_static_check(op_name: str, tensor, group=None) -> None:
    """FLAGS_comm_static_check: cross-process meta verification before the
    collective (reference `CommStaticCheck`, static_check.h:24).  Active in
    multi-process jobs for WORLD-spanning collectives; in-process SPMD
    shapes are uniform by construction, and sub-group collectives are
    skipped (their rank sets don't include the rank-0 verifier; checking
    them needs per-group stores, which the reference scopes the same way)."""
    from .. import flags as _fl
    if not _fl.get_flag("comm_static_check"):
        return
    store = _host_store()
    if store is None:
        return
    import os
    world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    if group is not None and (group._ranks is not None
                              and len(group._ranks) != world):
        return
    from .watchdog import static_check_meta
    seqs = _store_state.setdefault("check_seq", {})
    seq = seqs.get(op_name, 0)
    seqs[op_name] = seq + 1
    static_check_meta(
        store, int(os.environ.get("PADDLE_TRAINER_ID", "0")),
        int(os.environ.get("PADDLE_TRAINERS_NUM", "1")), op_name, seq,
        shape=tuple(tensor.shape), dtype=tensor.dtype,
        generation=_generation())


def _eager_multiproc(group) -> bool:
    """True when this is a real multi-process job and the collective is
    called eagerly (no axis context): route to the cached jitted
    global-array programs in `eager_comm.py` — the seat of the
    reference's eager ProcessGroup (`process_group.h:47`)."""
    from . import eager_comm
    return eager_comm.in_multiprocess()


def all_reduce(tensor: Tensor, op: str = ReduceOp.SUM, group: Optional[Group] = None,
               sync_op: bool = True):
    """In-place all-reduce (paddle semantics: mutates `tensor`).

    Eager-granularity contract: outside an axis context (jit/shard_map
    mesh), the collective is PROCESS-granular — each launched process
    contributes exactly one tensor, the reference's one-rank-per-GPU
    model (`process_group.h:47`).  A multi-process job where a process
    owns several local jax devices has no defined eager semantics
    (which device's value is "the" contribution?) and raises
    RuntimeError from `eager_comm`; run the collective inside
    jit/shard_map, or launch one process per device.  Inside an axis
    context the op lowers to the mesh collective and this contract does
    not apply."""
    _instrument("all_reduce", tensor)
    _maybe_static_check("all_reduce", tensor, group)
    axis = current_axis_for(group)
    if axis is not None:
        out = _d("c_allreduce", (tensor,), {"op": op, "axis": axis})
        tensor._value = out._value
        tensor._grad_node = out._grad_node
        tensor._output_slot = out._output_slot
        tensor.stop_gradient = out.stop_gradient
        return tensor
    if _single_rank(group):
        return tensor
    if _eager_multiproc(group):
        from . import eager_comm
        tensor._value = eager_comm.all_reduce(tensor._value, op, group)
        return tensor
    raise NotImplementedError(
        "eager cross-process all_reduce outside an axis context needs a "
        "multi-process runtime (init_parallel_env under distributed.launch)")


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    # all ranks compute the reduction; paddle keeps result only on dst but
    # on TPU the psum result is replicated — semantically a superset
    return all_reduce(tensor, op, group, sync_op)


def all_gather(tensor_list: List[Tensor], tensor: Tensor,
               group: Optional[Group] = None, sync_op: bool = True):
    _instrument("all_gather", tensor)
    _maybe_static_check("all_gather", tensor, group)
    axis = current_axis_for(group)
    group = group or _get_default_group()
    if axis is not None:
        out = _d("c_allgather", (tensor,), {"axis": axis, "tiled": False})
        # out shape [nranks, *shape]: split into the list
        from ..ops.manipulation import split, squeeze
        parts = split(out, group.nranks, axis=0)
        tensor_list.clear()
        tensor_list.extend(squeeze(p, 0) for p in parts)
        return tensor_list
    if _single_rank(group):
        tensor_list.clear()
        tensor_list.append(tensor)
        return tensor_list
    if _eager_multiproc(group):
        from . import eager_comm
        stacked = eager_comm.all_gather(tensor._value, group)
        tensor_list.clear()
        tensor_list.extend(Tensor._wrap(stacked[i])
                           for i in range(stacked.shape[0]))
        return tensor_list
    raise NotImplementedError("eager cross-process all_gather: use jit/shard_map")


def all_gather_into_tensor(out: Tensor, tensor: Tensor, group=None,
                           sync_op=True):
    _instrument("all_gather", tensor)
    axis = current_axis_for(group)
    if axis is not None:
        res = _d("c_allgather", (tensor,), {"axis": axis, "tiled": True})
        out._value = res._value
        return out
    if _single_rank(group):
        out._value = tensor._value
        return out
    if _eager_multiproc(group):
        from . import eager_comm
        stacked = eager_comm.all_gather(tensor._value, group)
        out._value = stacked.reshape(
            (stacked.shape[0] * stacked.shape[1],) + stacked.shape[2:])
        return out
    raise NotImplementedError


_NON_MEMBER = object()   # sentinel: caller is not in the group


def _store_object_exchange(obj, op_name, group, src_only=None):
    """Object collectives ride the launcher's TCPStore (the reference's
    ProcessGroup::AllGatherObject path uses the NCCL byte transport; the
    control-plane store is the TPU-native seat — object payloads are
    pickles, not device data).  Returns the ordered per-rank object list."""
    import os
    import pickle
    store = _host_store()
    if store is None:
        return None
    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    ranks = (group._ranks if group is not None
             and getattr(group, "_ranks", None) is not None
             else list(range(world)))
    if rank not in ranks:
        # paddle group semantics: only members call; tolerate a stray
        # call from a non-member without touching the members' barrier
        return _NON_MEMBER
    # seq counters are PER (op, group): a member and a non-member of some
    # subgroup must still agree on the sequence numbers of every group
    # they are BOTH in (a global counter would desynchronize them)
    if src_only is not None and src_only not in ranks:
        raise ValueError(
            f"{op_name}: src rank {src_only} is not in the group "
            f"{sorted(ranks)}")
    gkey = (op_name, tuple(sorted(ranks)))
    seqs = _store_state.setdefault("obj_seq", {})
    seq = seqs.get(gkey, 0)
    seqs[gkey] = seq + 1
    gen = _generation()
    gid = "-".join(map(str, sorted(ranks)))
    key = lambda r: f"objcoll/{gen}/{op_name}/{gid}/{seq}/{r}"  # noqa: E731
    if src_only is None or rank == src_only:
        store.set(key(rank), pickle.dumps(obj))
    out = []
    read_from = ranks if src_only is None else [src_only]
    from .watchdog import comm_task
    with comm_task(f"{op_name}#{seq}", rank=rank, world_size=len(ranks),
                   store=store, generation=gen):
        for r in read_from:
            store.wait(key(r))
            out.append(pickle.loads(store.get(key(r))))
    # everyone has read every payload once the member barrier passes;
    # each member then deletes only ITS OWN key
    store.barrier(f"objcoll/{gen}/{op_name}/{gid}/{seq}/done", len(ranks))
    if src_only is None or rank == src_only:
        try:
            store.delete_key(key(rank))
        except Exception:  # noqa: BLE001 - cleanup is best-effort
            pass
    return out


def all_gather_object(object_list: list, obj: Any, group=None):
    if _single_rank(group):
        object_list.clear()
        object_list.append(obj)
        return object_list
    got = _store_object_exchange(obj, "all_gather_object", group)
    if got is _NON_MEMBER:
        return object_list
    if got is not None:
        object_list.clear()
        object_list.extend(got)
        return object_list
    raise NotImplementedError("object collectives need the launcher store")


def reduce_scatter(tensor: Tensor, tensor_or_tensor_list,
                   op=ReduceOp.SUM, group=None, sync_op=True):
    axis = current_axis_for(group)
    src = tensor_or_tensor_list
    _instrument("reduce_scatter", *(src if isinstance(src, (list, tuple))
                                    else (src,)))
    if isinstance(src, (list, tuple)):
        from ..ops.manipulation import concat
        src = concat(list(src), axis=0)
    if axis is not None:
        out = _d("c_reducescatter", (src,), {"op": op, "axis": axis})
        tensor._value = out._value
        tensor._grad_node = out._grad_node
        tensor._output_slot = out._output_slot
        tensor.stop_gradient = out.stop_gradient
        return tensor
    if _single_rank(group):
        tensor._value = src._value
        return tensor
    if _eager_multiproc(group):
        from . import eager_comm
        out = eager_comm.reduce_scatter(src._value, op, group)
        tensor._value = out
        return tensor
    raise NotImplementedError


def alltoall(out_tensor_list: List[Tensor], in_tensor_list: List[Tensor],
             group=None, sync_op=True):
    _instrument("alltoall", *in_tensor_list)
    axis = current_axis_for(group)
    from ..ops.manipulation import split, squeeze, stack
    if axis is not None:
        x = stack(list(in_tensor_list), axis=0)
        out = _d("c_alltoall", (x,), {"axis": axis, "split_axis": 0,
                                      "concat_axis": 0})
        group = group or _get_default_group()
        parts = split(out, group.nranks, axis=0)
        out_tensor_list.clear()
        out_tensor_list.extend(squeeze(p, 0) for p in parts)
        return out_tensor_list
    if _single_rank(group):
        out_tensor_list.clear()
        out_tensor_list.extend(in_tensor_list)
        return out_tensor_list
    if _eager_multiproc(group):
        from . import eager_comm
        rows = jnp.stack([t._value for t in in_tensor_list], axis=0)
        got = eager_comm.alltoall(rows, group)
        out_tensor_list.clear()
        out_tensor_list.extend(Tensor._wrap(got[i])
                               for i in range(got.shape[0]))
        return out_tensor_list
    raise NotImplementedError


def alltoall_single(out_tensor: Tensor, in_tensor: Tensor,
                    in_split_sizes=None, out_split_sizes=None, group=None,
                    sync_op=True):
    _instrument("alltoall", in_tensor)
    axis = current_axis_for(group)
    if axis is not None:
        out = _d("c_alltoall", (in_tensor,), {"axis": axis, "split_axis": 0,
                                              "concat_axis": 0})
        out_tensor._value = out._value
        return out_tensor
    if _single_rank(group):
        out_tensor._value = in_tensor._value
        return out_tensor
    if _eager_multiproc(group):
        from . import eager_comm
        W = eager_comm.group_size(group)
        rows = in_tensor._value.reshape(
            (W, in_tensor.shape[0] // W) + tuple(in_tensor.shape[1:]))
        got = eager_comm.alltoall(rows, group)
        out_tensor._value = got.reshape(
            (got.shape[0] * got.shape[1],) + got.shape[2:])
        return out_tensor
    raise NotImplementedError


def broadcast(tensor: Tensor, src: int = 0, group=None, sync_op=True):
    _instrument("broadcast", tensor)
    axis = current_axis_for(group)
    if axis is not None:
        group = group or _get_default_group()
        src_local = group.get_group_rank(src)
        out = _d("c_broadcast_in_axis", (tensor,), {"axis": axis,
                                                    "src": src_local})
        tensor._value = out._value
        return tensor
    if _single_rank(group):
        return tensor
    if _eager_multiproc(group):
        from . import eager_comm
        tensor._value = eager_comm.broadcast(
            tensor._value, eager_comm.row_of(group, src), group)
        return tensor
    raise NotImplementedError


def broadcast_object_list(object_list, src=0, group=None):
    if _single_rank(group):
        return object_list
    got = _store_object_exchange(list(object_list), "broadcast_object_list",
                                 group, src_only=src)
    if got is _NON_MEMBER:
        return object_list
    if got is not None:
        object_list[:] = got[0]
        return object_list
    raise NotImplementedError


def scatter(tensor: Tensor, tensor_list=None, src=0, group=None, sync_op=True):
    _instrument("scatter", tensor)
    axis = current_axis_for(group)
    if axis is not None:
        from ..ops.manipulation import stack
        x = stack(list(tensor_list), axis=0)
        bcast = _d("c_broadcast_in_axis", (x,), {"axis": axis, "src": src})
        idx = _d("c_axis_index", (Tensor(jnp.zeros((), jnp.int32)),),
                 {"axis": axis})
        out = bcast[idx]
        tensor._value = out._value
        return tensor
    if _single_rank(group):
        tensor._value = tensor_list[src]._value if tensor_list else tensor._value
        return tensor
    if _eager_multiproc(group):
        from . import eager_comm
        W = eager_comm.group_size(group)
        me = eager_comm.my_row(group)
        src_row = eager_comm.row_of(group, src)
        if me == src_row:
            stacked = jnp.stack([t._value for t in tensor_list], axis=0)
        else:
            stacked = jnp.zeros(
                (W,) + tuple(tensor.shape), tensor._value.dtype)
        full = eager_comm.broadcast(stacked, src_row, group)
        tensor._value = full[me]
        return tensor
    raise NotImplementedError


def gather(tensor, gather_list=None, dst=0, group=None, sync_op=True):
    if gather_list is None:
        gather_list = []
    return all_gather(gather_list, tensor, group, sync_op)


_store_state = {"store": None, "barrier_seq": 0, "p2p_seq": {}}


def _generation() -> str:
    """Elastic restart generation: restarted workers must not collide with
    keys a previous generation left in the launcher's store."""
    import os
    return os.environ.get("PADDLE_RESTART_GENERATION", "0")


def _host_store():
    """Cross-process control-plane store (hosted by the launcher).

    Returns None when not in a multi-process job.  Workers connect to
    PADDLE_MASTER, the rendezvous server `paddle_tpu.distributed.launch`
    hosts (reference: the ProcessGroup's TCPStore, `tcp_store.h:121`).
    """
    import os
    if _store_state["store"] is not None:
        return _store_state["store"]
    master = os.environ.get("PADDLE_MASTER")
    if not master or int(os.environ.get("PADDLE_TRAINERS_NUM", "1")) <= 1:
        return None
    from .store import TCPStore
    host, port = master.rsplit(":", 1)
    _store_state["store"] = TCPStore(
        host=host, port=int(port),
        world_size=int(os.environ["PADDLE_TRAINERS_NUM"]))
    return _store_state["store"]


def _host_p2p(tensor, peer, is_send, group):
    """Eager cross-process p2p through the store (control path only; inside
    compiled pipeline schedules use ppermute, which rides ICI)."""
    import os
    import pickle
    import numpy as np
    store = _host_store()
    if store is None:
        return None
    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    src, dst = (rank, peer) if is_send else (peer, rank)
    key_id = (src, dst)
    seq = _store_state["p2p_seq"].get(key_id, 0)
    _store_state["p2p_seq"][key_id] = seq + 1
    key = f"__p2p__/{_generation()}/{src}->{dst}/{seq}"
    world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    if is_send:
        arr = np.asarray(tensor._value)
        # meta travels with the payload: the NCCLDynamicCheck equivalent
        store.set(key, pickle.dumps(
            {"shape": arr.shape, "dtype": str(arr.dtype), "data": arr}))
    else:
        from .watchdog import comm_task
        with comm_task(f"recv({src}->{dst})", key=key, rank=rank,
                       world_size=world, store=store,
                       generation=_generation()):
            store.wait(key)
        msg = pickle.loads(store.get(key))
        store.delete_key(key)  # free the payload in the server
        if tuple(msg["shape"]) != tuple(tensor.shape):
            raise RuntimeError(
                f"p2p dynamic check: sender {src} shipped shape "
                f"{tuple(msg['shape'])} but receiver expects "
                f"{tuple(tensor.shape)}")
        if msg["dtype"] != str(np.dtype(tensor._value.dtype)):
            raise RuntimeError(
                f"p2p dynamic check: sender {src} shipped dtype "
                f"{msg['dtype']} but receiver tensor is "
                f"{np.dtype(tensor._value.dtype)}")
        tensor._value = jnp.asarray(msg["data"], dtype=tensor._value.dtype)
    return tensor


def send(tensor: Tensor, dst: int = 0, group=None, sync_op=True):
    """Point-to-point over a pipeline axis = ppermute (see fleet pp_utils)."""
    _instrument("send", tensor)
    axis = current_axis_for(group)
    if axis is None:
        if _single_rank(group):
            return tensor
        out = _host_p2p(tensor, dst, True, group)
        if out is not None:
            return out
        raise NotImplementedError("p2p outside axis context")
    group = group or _get_default_group()
    n = group.nranks
    perm = [(i, (i + 1) % n) for i in range(n)]
    out = _d("c_ppermute", (tensor,), {"axis": axis, "perm": tuple(perm)})
    tensor._pp_sendbuf = out  # consumed by the matching recv
    return tensor


def recv(tensor: Tensor, src: int = 0, group=None, sync_op=True):
    _instrument("recv", tensor)
    axis = current_axis_for(group)
    if axis is None:
        if _single_rank(group):
            return tensor
        out = _host_p2p(tensor, src, False, group)
        if out is not None:
            return out
        raise NotImplementedError("p2p outside axis context")
    raise NotImplementedError(
        "use fleet pp_utils.p2p helpers inside pipeline schedules; raw "
        "send/recv pairs don't compose under SPMD")


isend = send
irecv = recv


def barrier(group=None):
    """Block until every process of the job arrived.

    Single-process (incl. single-process-many-devices SPMD): no-op, the
    compiler orders collectives.  Multi-process: synchronizes through the
    launcher's TCPStore (reference: ProcessGroup::Barrier).
    """
    _instrument("barrier")
    store = _host_store()
    if store is None:
        return None
    import os
    seq = _store_state["barrier_seq"]
    _store_state["barrier_seq"] = seq + 1
    from .watchdog import comm_task
    with comm_task(f"barrier#{seq}",
                   rank=int(os.environ.get("PADDLE_TRAINER_ID", "0")),
                   world_size=int(os.environ.get("PADDLE_TRAINERS_NUM", "1")),
                   store=store, generation=_generation()):
        store.barrier(f"collective/{_generation()}/{seq}")
    return None


def wait(tensor, group=None, use_calc_stream=True):
    if isinstance(tensor, Tensor) and hasattr(tensor._value, "block_until_ready"):
        tensor._value.block_until_ready()
    return tensor


class stream:
    """paddle.distributed.stream namespace shim: on TPU all collectives are
    compiler-scheduled; stream variants alias the sync API."""
    all_reduce = staticmethod(all_reduce)
    all_gather = staticmethod(all_gather)
    reduce_scatter = staticmethod(reduce_scatter)
    alltoall = staticmethod(alltoall)
    broadcast = staticmethod(broadcast)
    send = staticmethod(send)
    recv = staticmethod(recv)
