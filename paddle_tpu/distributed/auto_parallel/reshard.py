"""Reshard engine: placement-transition registry with Partial semantics.

Parity: `paddle/phi/core/distributed/auto_parallel/reshard/` —
s_to_r_reshard_function.cc (all-gather), r_to_s (slice), p_to_r
(all-reduce), p_to_s (reduce-scatter), s_to_s (all-to-all),
same_status / cross-mesh (send-recv), and the registry in
reshard_function_registry.cc.

TPU-native: a pending-sum ("Partial") value is represented explicitly as a
jax array with a leading unreduced axis of length `mesh_dim_size`, sharded
over that mesh dim — the canonical unreduced layout.  Transitions out of
Partial are a `sum` over that axis with the target sharding constrained;
XLA lowers exactly to the all-reduce (p2r) / reduce-scatter (p2s) the
reference codes by hand.  Shard<->Shard and Shard<->Replicate transitions
are sharding moves (device_put / with_sharding_constraint) that GSPMD
lowers to all-to-all / all-gather / slice.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...framework.tensor import Tensor
from .placement import Partial, Placement, Replicate, Shard
from .process_mesh import ProcessMesh

__all__ = ["PartialTensor", "reshard_partial", "make_partial",
           "register_reshard", "get_reshard_fn"]


_RESHARD: Dict[Tuple[str, str], Callable] = {}


def _kind(p: Placement) -> str:
    if p.is_partial():
        return "p"
    if p.is_shard():
        return "s"
    return "r"


def register_reshard(src: str, dst: str):
    def deco(fn):
        _RESHARD[(src, dst)] = fn
        return fn
    return deco


def get_reshard_fn(src: Placement, dst: Placement) -> Callable:
    key = (_kind(src), _kind(dst))
    if key not in _RESHARD:
        raise NotImplementedError(f"no reshard rule {key[0]}->{key[1]}")
    return _RESHARD[key]


class PartialTensor:
    """A pending-sum DistTensor along one mesh dim.

    `unreduced` has shape (mesh_dim_size, *logical_shape) and is sharded on
    dim 0 over `axis_name` — shard i holds rank i's partial contribution.
    """

    def __init__(self, unreduced: jax.Array, mesh: Mesh, axis_name: str):
        self.unreduced = unreduced
        self.mesh = mesh
        self.axis_name = axis_name

    @property
    def logical_shape(self):
        return tuple(self.unreduced.shape[1:])


def make_partial(fn_per_rank, mesh: Mesh, axis_name: str, *args,
                 in_specs=None) -> PartialTensor:
    """Build a PartialTensor by running `fn_per_rank(local_slices...)`
    under shard_map.  `in_specs` gives each arg's PartitionSpec (default:
    sharded on its leading dim) — a row-parallel matmul needs
    in_specs=(P(None, axis), P(axis, None))."""
    import functools

    if in_specs is None:
        in_specs = tuple(P(axis_name) for _ in args)
    else:
        in_specs = tuple(in_specs)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=P(axis_name))
    def run(*local_args):
        out = fn_per_rank(*local_args)
        return out[None]  # leading unreduced axis

    return PartialTensor(run(*args), mesh, axis_name)


def _move(val, sharding):
    if isinstance(val, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(val, sharding)
    return jax.device_put(val, sharding)


# ------------------------------------------------------------- transitions
@register_reshard("p", "r")
def p_to_r(pt: PartialTensor, dst: Placement, **kw):
    """Pending sum -> replicated: one all-reduce (`p_to_r_reshard...cc`)."""
    out = jnp.sum(pt.unreduced, axis=0)
    repl = NamedSharding(pt.mesh, P(*([None] * out.ndim)))
    return _move(out, repl)


@register_reshard("p", "s")
def p_to_s(pt: PartialTensor, dst: Shard, **kw):
    """Pending sum -> sharded: reduce-scatter (`p_to_s_reshard...cc`)."""
    out = jnp.sum(pt.unreduced, axis=0)
    entries = [None] * out.ndim
    entries[dst.get_dim()] = pt.axis_name
    return _move(out, NamedSharding(pt.mesh, P(*entries)))


@register_reshard("s", "r")
def s_to_r(val, dst: Placement, mesh=None, axis_name=None, **kw):
    """Sharded -> replicated: all-gather (`s_to_r_reshard...cc`)."""
    return _move(val, NamedSharding(mesh, P(*([None] * val.ndim))))


@register_reshard("r", "s")
def r_to_s(val, dst: Shard, mesh=None, axis_name=None, **kw):
    """Replicated -> sharded: local slice (`r_to_s_reshard...cc`)."""
    entries = [None] * val.ndim
    entries[dst.get_dim()] = axis_name
    return _move(val, NamedSharding(mesh, P(*entries)))


@register_reshard("s", "s")
def s_to_s(val, dst: Shard, mesh=None, axis_name=None, src_dim=None, **kw):
    """Shard(i) -> Shard(j): all-to-all (`s_to_s_reshard...cc`)."""
    entries = [None] * val.ndim
    entries[dst.get_dim()] = axis_name
    return _move(val, NamedSharding(mesh, P(*entries)))


def reshard_partial(pt: PartialTensor, dst: Placement) -> Tensor:
    """Materialize a PartialTensor under the destination placement."""
    fn = get_reshard_fn(Partial(), dst)
    return Tensor._wrap(fn(pt, dst))


@register_reshard("r", "p")
def r_to_p(val, dst: Placement, mesh=None, axis_name=None, **kw):
    """Replicated -> pending-sum: rank 0 of the axis keeps the value,
    every other rank holds zeros, so a later p->r restores the original
    (`r_to_p_reshard_function.cc` semantics).  The unreduced stack is
    laid out dim-0-sharded over the axis (PartialTensor's contract: one
    slice per rank, not n replicated copies)."""
    n = mesh.shape[axis_name]
    tiles = jnp.stack([val] + [jnp.zeros_like(val)] * (n - 1))
    tiles = _move(tiles, NamedSharding(
        mesh, P(axis_name, *([None] * val.ndim))))
    return PartialTensor(tiles, mesh, axis_name)


def nd_mesh_reshard(value, mesh, src_placements, dst_placements,
                    mesh_dim_names=None):
    """Reshard over an N-D mesh by decomposing into per-axis pairwise
    steps (`nd_mesh_reshard_function.cc`: SetVirtualMeshDim + one 1-D
    reshard per changed axis).

    value: jax array laid out per `src_placements` (one Placement per
    mesh axis).  Returns the array laid out per `dst_placements`.
    Partial placements are handled first (p->r / p->s on their axis),
    then shard/replicate changes axis by axis — the same ordering the
    reference uses so intermediate layouts stay materializable."""
    names = list(mesh_dim_names or mesh.axis_names)
    assert len(src_placements) == len(names) == len(dst_placements)

    def spec_of(placements):
        entries = [None] * value.ndim
        for ax_name, p in zip(names, placements):
            if _kind(p) == "s":
                d = p.get_dim()
                if entries[d] is None:
                    entries[d] = ax_name
                elif isinstance(entries[d], tuple):
                    entries[d] = entries[d] + (ax_name,)
                else:
                    entries[d] = (entries[d], ax_name)
        return P(*entries)

    cur = list(src_placements)
    # phase 1: resolve partials (their axis must reduce before any
    # shard-dim juggling references the true values)
    for i, (s, d) in enumerate(zip(list(cur), dst_placements)):
        if _kind(s) == "p" and _kind(d) != "p":
            psum_axis = names[i]
            # value carries an unreduced leading stack only inside
            # PartialTensor flows; at the jax-array level a partial axis
            # means "sum over replicas of that axis" — express it as a
            # shard_map psum over the axis
            in_spec = spec_of(cur)
            mid = list(cur)
            mid[i] = Replicate()
            out_spec = spec_of(mid)
            value = jax.jit(jax.shard_map(
                lambda x: jax.lax.psum(x, psum_axis), mesh=mesh,
                in_specs=in_spec, out_specs=out_spec,
                check_vma=False))(value)
            cur = mid
    # phase 2: one GSPMD relayout per remaining changed axis
    for i, d in enumerate(dst_placements):
        if _kind(cur[i]) == _kind(d) and (
                _kind(d) != "s" or cur[i].get_dim() == d.get_dim()):
            continue
        if _kind(d) == "p":
            raise NotImplementedError(
                "nd reshard to a Partial placement (x->p) is not a "
                "materializable layout; reshard to r or s instead")
        step = list(cur)
        step[i] = d
        value = _move(value, NamedSharding(mesh, spec_of(step)))
        cur = step
    return value
