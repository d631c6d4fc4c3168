"""init_parallel_env + DataParallel.

Parity: `python/paddle/distributed/parallel.py` (init_parallel_env `:943`,
DataParallel `:202` + C++ EagerReducer
`fluid/distributed/collective/reducer.h:88`).

TPU-native DataParallel: parameters stay replicated on the mesh; input
batches are sharded over the 'dp' axis (shard_batch); the gradient
all-reduce the reference implements with bucketed NCCL calls is inserted by
GSPMD when the sharded-batch loss is differentiated — eagerly per-op, or
fused inside a captured train step.  no_sync() suppresses the constraint.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..framework.tensor import Tensor
from ..nn.layer.layers import Layer
from . import env as _env
from . import mesh as _mesh

_heartbeat = None  # rank-liveness publisher; started once per process

__all__ = ["init_parallel_env", "DataParallel", "shard_batch", "ParallelEnv"]

from .env import ParallelEnv  # noqa: F401  (re-export)


def init_parallel_env(backend: Optional[str] = None):
    """Bootstrap the distributed runtime.

    Single-host (tests, 1 chip): builds a trivial mesh over local devices.
    Multi-host: jax.distributed.initialize from the launcher env
    (coordinator address replaces the reference's TCPStore rendezvous)."""
    import os
    world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    # the jax.distributed coordinator is its OWN endpoint (the launcher
    # publishes COORDINATOR_ADDRESS) — PADDLE_MASTER is the TCPStore and
    # cannot double as the coordinator port
    addr = os.environ.get("COORDINATOR_ADDRESS")
    if addr and world > 1 and not jax.distributed.is_initialized():
        jax.distributed.initialize(
            coordinator_address=addr,
            num_processes=world,
            process_id=int(os.environ.get("PADDLE_TRAINER_ID", "0")))
    if _mesh.get_mesh() is None:
        _mesh.set_mesh(_mesh.build_mesh({"dp": -1}))
    # liveness heartbeat through the launcher's store so watchdog hang
    # reports can name the missing rank (reference Watcher polling);
    # idempotent across repeated init calls, stoppable via its handle
    global _heartbeat
    if _heartbeat is None:
        from .collective import _generation, _host_store
        store = _host_store()
        if store is not None:
            from .watchdog import Heartbeat
            _heartbeat = Heartbeat(
                store, int(os.environ.get("PADDLE_TRAINER_ID", "0")),
                generation=_generation()).start()
    _env._mark_initialized()
    return _env.ParallelEnv()


def shard_batch(tensor: Tensor, axis: str = "dp", dim: int = 0) -> Tensor:
    """Lay a batch out over a mesh axis (the DP input split)."""
    m = _mesh.get_mesh()
    if m is None or axis not in m.axis_names or m.shape[axis] <= 1:
        return tensor
    spec = [None] * tensor.ndim
    spec[dim] = axis
    sh = NamedSharding(m, P(*spec))
    if tensor._is_traced():
        tensor._value = jax.lax.with_sharding_constraint(tensor._value, sh)
    else:
        tensor._value = jax.device_put(tensor._value, sh)
    return tensor


class DataParallel(Layer):
    """DDP wrapper (ref `python/paddle/DataParallel`, reducer.h:88).

    In-process SPMD mode (one process, many devices): forward shards the
    batch over the 'dp' mesh axis and XLA inserts the gradient psums.

    Multi-process eager mode (under `distributed.launch`): each process
    computes grads on its own batch; reducer hooks on every parameter's
    accumulation node all-reduce(avg) the gradient the moment it lands in
    `loss.backward()` — the reference's Reducer, with the cached jitted
    global-array programs of `eager_comm.py` as the transport."""

    def __init__(self, layers: Layer, strategy=None, comm_buffer_size=25,
                 last_comm_buffer_size=1, find_unused_parameters=False,
                 group=None):
        super().__init__()
        self._layers = layers
        self._sync = True
        self._group = group
        self.find_unused_parameters = find_unused_parameters
        from . import eager_comm
        self._multiproc = eager_comm.in_multiprocess()
        if self._multiproc:
            self._register_reducer_hooks()
            self._broadcast_initial_params()

    def _register_reducer_hooks(self):
        from .collective import ReduceOp, all_reduce
        dp = self

        def sync(t):
            if not dp._sync:
                return
            g = t._grad
            if g is not None:
                all_reduce(g, op=ReduceOp.AVG, group=dp._group)

        for p in self._layers.parameters():
            if not p.stop_gradient:
                node = p._get_accum_node()
                node.reducer_hooks.append(sync)

    def _broadcast_initial_params(self):
        """Rank-0 weights AND buffers win at construction (the
        reference's sync_params_buffers: BatchNorm running stats are
        buffers, outside parameters(), and must start identical too)."""
        from .collective import broadcast
        for p in self._layers.parameters():
            broadcast(p, src=0, group=self._group)
        buffers = getattr(self._layers, "buffers", None)
        if callable(buffers):
            for b in buffers():
                broadcast(b, src=0, group=self._group)

    def forward(self, *inputs, **kwargs):
        if self._sync and not self._multiproc:
            inputs = tuple(shard_batch(i) if isinstance(i, Tensor) else i
                           for i in inputs)
            kwargs = {k: shard_batch(v) if isinstance(v, Tensor) else v
                      for k, v in kwargs.items()}
        return self._layers(*inputs, **kwargs)

    class _NoSync:
        def __init__(self, dp):
            self.dp = dp

        def __enter__(self):
            self.dp._sync = False
            return self

        def __exit__(self, *exc):
            self.dp._sync = True
            return False

    def no_sync(self):
        """Within this context batches are NOT dp-sharded, so no gradient
        all-reduce is induced (grad accumulation then happens locally)."""
        return DataParallel._NoSync(self)

    # transparent delegation
    def parameters(self, *a, **k):
        return self._layers.parameters(*a, **k)

    def named_parameters(self, *a, **k):
        return self._layers.named_parameters(*a, **k)

    def state_dict(self, *a, **k):
        return self._layers.state_dict(*a, **k)

    def set_state_dict(self, *a, **k):
        return self._layers.set_state_dict(*a, **k)

    def train(self):
        self._layers.train()
        self.training = True
        return self

    def eval(self):
        self._layers.eval()
        self.training = False
        return self

    def scale_loss(self, loss):
        return loss

    def apply_collective_grads(self):
        """Manual grad sync after `no_sync` accumulation (reference
        `DataParallel.apply_collective_grads`)."""
        if not self._multiproc:
            return
        from .collective import ReduceOp, all_reduce
        for p in self._layers.parameters():
            if p._grad is not None:
                all_reduce(p._grad, op=ReduceOp.AVG, group=self._group)
