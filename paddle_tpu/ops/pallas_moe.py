"""Fused MoE routing dispatch/combine over capacity-bucketed buffers.

Role of the reference's MoEScatter/MoEGather
(`python/paddle/incubate/distributed/models/moe/moe_layer.py:99/:149` +
the index plumbing of `utils.py:prepare_forward`): move each routed
token's activation row into its expert's fixed-capacity buffer slot and
mix the expert outputs back, WITHOUT materializing the dense
(tokens, experts, capacity) one-hot tensors the einsum formulation
contracts against.  The dense dispatch/combine einsums cost
``T*E*C*M`` FLOPs each — an ``E*C/k``-fold blowup over the useful work
— and were exactly the "stock gather/scatter" rows the X-ray
kernel-coverage audit flagged (ISSUE 18).

One-pass formulation: routing is carried as INDICES — per token and
routing choice, the flat destination slot ``eid * C + slot`` (or a
reserved dummy slot when dropped) — plus the renormalized combine
weights.  Dispatch is then a single gather of token rows by the
inverse slot->token map (each capacity slot holds at most one token,
so the inverse is exact), and combine is a k-row gather weighted by
the combine weights.  Both are ``O(T*k*M)``.  Dispatch is bit-exact
vs the dense einsum (every row is either copied or an exact zero);
combine matches to one float-rounding step — the dense contraction
fuses multiply-add inside ``dot_general`` while the kernel rounds the
``w * row`` product before accumulating — so parity is pinned at
~1e-6 absolute, far inside the layer tests' tolerance.

Kernel strategy (one Pallas kernel per direction): rows move by DMA.
The source buffer stays in HBM (``pl.ANY``) and a grid step issues one
row copy per destination row of its tile, all in flight on one
semaphore, then waits for them — the gather happens in the DMA
engine's addressing, as in the paged attention kernels, and no more
than one tile of rows is ever resident in VMEM (dispatch: none at all,
its copies run HBM to HBM; combine: ``k`` source rows per token of the
tile).  A single-row slice of a 2-D ``[N, M]`` buffer is not
tile-aligned for Mosaic, so the wrappers present every buffer as
``[N, p, M // p]`` with ``p = 4 // itemsize`` (1 for float32, 2 for
bf16): one row is then exactly one ``(p, M // p)`` tile of its dtype's
packing and ``ref.at[i]`` is a legal DMA operand.  The reshape is
row-major, so it changes no element order.  Gradients are custom VJPs
in plain XLA (gather <-> scatter-add transposes), so the ops sit on the
tape like any registered op.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_common

__all__ = ["routing_indices", "moe_dispatch", "moe_combine",
           "moe_dispatch_reference", "moe_combine_reference",
           "grouped_matmul", "grouped_matmul_reference"]


# destination rows per grid step (that many row copies in flight, k times
# as many for combine); combine shrinks its tile further so the k
# gathered source rows per token stay within _COMBINE_VMEM of scratch
_ROW_TILE = 128
_COMBINE_VMEM = 2 << 20


def _as_rows(x):
    """[N, M] -> [N, p, M // p]: one row = one DMA-addressable tile
    (module docstring)."""
    N, M = x.shape
    p = max(1, 4 // x.dtype.itemsize)
    if M % p:
        raise ValueError(
            f"fused MoE routing needs the model width ({M}) to be a "
            f"multiple of {p} for {x.dtype} rows")
    return x.reshape(N, p, M // p)


def _zero_row(rows):
    """The one all-zero row, in the buffers' row layout."""
    return jnp.zeros((1,) + rows.shape[1:], rows.dtype)


def _pad_rows(a, n, value):
    """Pad the leading dim of an index/weight array to n with `value`."""
    if a.shape[0] == n:
        return a
    fill = jnp.full((n - a.shape[0],) + a.shape[1:], value, a.dtype)
    return jnp.concatenate([a, fill], axis=0)


def _start_row_copy(src_hbm, zero_hbm, idx, dst_row, sem):
    """Start the copy of row `idx` of `src_hbm` into `dst_row`; the
    reserved index one past the end (empty slot / dropped choice) copies
    the zero row instead, so the buffers never need a padded copy."""
    n = src_hbm.shape[0]

    @pl.when(idx < n)
    def _():
        pltpu.make_async_copy(src_hbm.at[idx], dst_row, sem).start()

    @pl.when(idx >= n)
    def _():
        pltpu.make_async_copy(zero_hbm.at[0], dst_row, sem).start()


def _wait_all(zero_hbm, dst_row, sem, n):
    """Wait for n row copies signalled on `sem` (every copy moves one
    row, so one representative descriptor stands for each)."""
    def body(_, c):
        pltpu.make_async_copy(zero_hbm.at[0], dst_row, sem).wait()
        return c
    jax.lax.fori_loop(0, n, body, 0)


def routing_indices(eid, slot, keep, num_experts, capacity):
    """Index plumbing for the fused path (integer ops, no gradient —
    the block-table role of the paged attention kernels).

    eid/slot: [T, k] int routing choice -> expert id / buffer slot;
    keep: [T, k] 0/1 float (dropped choices).  Returns
    ``(flat [T, k], inv [E*C])``: the flat destination slot per choice
    (``E*C`` = reserved dummy for drops) and the inverse slot->token
    map (``T`` = empty slot)."""
    E, C = int(num_experts), int(capacity)
    T, k = eid.shape
    flat = jnp.where(keep > 0.5,
                     eid.astype(jnp.int32) * C + slot.astype(jnp.int32),
                     E * C)
    tok = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[:, None],
                           (T, k))
    inv = jnp.full((E * C + 1,), T, jnp.int32).at[
        flat.reshape(-1)].set(tok.reshape(-1))[:E * C]
    return flat, inv


def _dispatch_kernel(inv_ref, x_hbm, zero_hbm, o_hbm, sem, *, tile):
    """One grid step packs `tile` expert-buffer rows by the inverse map
    (row i of the output is token ``inv[i]``'s activation; empty slots
    take the zero row), HBM to HBM."""
    base = pl.program_id(0) * tile

    def start(r, c):
        _start_row_copy(x_hbm, zero_hbm, inv_ref[base + r],
                        o_hbm.at[base + r], sem)
        return c
    jax.lax.fori_loop(0, tile, start, 0)
    _wait_all(zero_hbm, o_hbm.at[0], sem, tile)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _dispatch(x, inv, T, interpret):
    """x: [T, M]; inv: [E*C] int32 (T = empty slot).  Returns the
    packed expert buffers as flat rows [E*C, M]."""
    M = x.shape[1]
    rows = inv.shape[0]
    x_rows = _as_rows(x)
    tile = min(_ROW_TILE, rows)
    n = pl.cdiv(rows, tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    out = pl.pallas_call(
        functools.partial(_dispatch_kernel, tile=tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n * tile,) + x_rows.shape[1:],
                                       x.dtype),
        interpret=interpret,
    )(_pad_rows(inv, n * tile, T), x_rows, _zero_row(x_rows))
    return out[:rows].reshape(rows, M)


def _dispatch_fwd(x, inv, T, interpret):
    return _dispatch(x, inv, T, interpret), inv


def _dispatch_bwd(T, interpret, inv, g):
    # transpose of the gather: scatter each buffer row's cotangent back
    # to its source token (a token routed k ways accumulates k rows)
    dx = jnp.zeros((T + 1, g.shape[1]), g.dtype).at[inv].add(g)[:T]
    return dx, np.zeros(inv.shape, jax.dtypes.float0)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _combine_kernel(flat_ref, eo_hbm, zero_hbm, w_ref, o_ref, buf, sem,
                    *, tile, k):
    """One grid step: gather the k routed expert-output rows of each of
    `tile` tokens into VMEM, then each token's output row is their
    w-weighted sum (the dummy slot E*C reads the zero row, so dropped
    choices contribute exact zeros — the dense-einsum semantics)."""
    base = pl.program_id(0) * tile * k

    def start(i, c):
        _start_row_copy(eo_hbm, zero_hbm, flat_ref[base + i],
                        buf.at[i % k, i // k], sem)
        return c
    jax.lax.fori_loop(0, tile * k, start, 0)
    _wait_all(zero_hbm, buf.at[0, 0], sem, tile * k)
    acc = w_ref[0] * buf[0].astype(jnp.float32)
    for j in range(1, k):
        acc = acc + w_ref[j] * buf[j].astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _combine(expert_rows, w, flat, interpret):
    """expert_rows: [E*C, M]; w/flat: [T, k].  Returns [T, M]."""
    T, k = w.shape
    EC, M = expert_rows.shape
    eo_rows = _as_rows(expert_rows)
    row_shape = eo_rows.shape[1:]
    tile = max(1, min(T, _ROW_TILE, _COMBINE_VMEM
                      // (k * M * expert_rows.dtype.itemsize)))
    n = pl.cdiv(T, tile)
    # weights ride as [k, T, 1, 1] float32 so w_ref[j] broadcasts over a
    # [tile, p, M // p] row tile without an in-kernel relayout
    w_col = jnp.transpose(_pad_rows(w.astype(jnp.float32), n * tile, 0.0)
                          )[:, :, None, None]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((k, tile, 1, 1), lambda i, flat: (0, i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tile,) + row_shape,
                               lambda i, flat: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((k, tile) + row_shape, expert_rows.dtype),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_combine_kernel, tile=tile, k=k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n * tile,) + row_shape,
                                       expert_rows.dtype),
        interpret=interpret,
    )(_pad_rows(flat, n * tile, EC).reshape(-1), eo_rows,
      _zero_row(eo_rows), w_col)
    return out[:T].reshape(T, M)


def _combine_fwd(expert_rows, w, flat, interpret):
    return (_combine(expert_rows, w, flat, interpret),
            (expert_rows, w, flat))


def _combine_bwd(interpret, res, g):
    expert_rows, w, flat = res
    EC, M = expert_rows.shape
    eo_pad = jnp.concatenate(
        [expert_rows, jnp.zeros((1, M), expert_rows.dtype)], axis=0)
    gathered = eo_pad[flat]                                # [T, k, M]
    dw = jnp.einsum("tkm,tm->tk", gathered.astype(jnp.float32),
                    g.astype(jnp.float32)).astype(w.dtype)
    d_rows = jnp.zeros((EC + 1, M), g.dtype).at[flat].add(
        w[:, :, None].astype(g.dtype) * g[:, None, :])[:EC]
    return d_rows, dw, np.zeros(flat.shape, jax.dtypes.float0)


_combine.defvjp(_combine_fwd, _combine_bwd)


def moe_dispatch(x, inv, interpret=None):
    """Pack token rows into the flat expert buffers: ``out[i] =
    x[inv[i]]`` (zeros for empty slots).  x: [T, M]; inv: [E*C] int32.
    Returns [E*C, M]; reshape to (E, C, M) for the batched experts."""
    if interpret is None:
        interpret = pallas_common.interpret_default()
    pallas_common.claim("moe_fused_dispatch", interpret)
    return _dispatch(x, inv, x.shape[0], interpret)


def moe_combine(expert_rows, w, flat, interpret=None):
    """Weighted un-dispatch: ``out[t] = sum_j w[t, j] *
    expert_rows[flat[t, j]]`` (dummy slot rows are zero).
    expert_rows: [E*C, M] (the experts' output, flattened); w/flat:
    [T, k].  Returns [T, M]."""
    if interpret is None:
        interpret = pallas_common.interpret_default()
    pallas_common.claim("moe_fused_combine", interpret)
    return _combine(expert_rows, w, flat, interpret)


def moe_dispatch_reference(x, inv):
    """Pure-XLA oracle for :func:`moe_dispatch` (one gather)."""
    x_pad = jnp.concatenate(
        [x, jnp.zeros((1, x.shape[1]), x.dtype)], axis=0)
    return x_pad[inv]


def moe_combine_reference(expert_rows, w, flat):
    """Pure-XLA oracle for :func:`moe_combine` (k-row gather + sum)."""
    M = expert_rows.shape[1]
    eo_pad = jnp.concatenate(
        [expert_rows, jnp.zeros((1, M), expert_rows.dtype)], axis=0)
    gathered = eo_pad[flat]                                # [T, k, M]
    out = jnp.sum(w[:, :, None].astype(jnp.float32)
                  * gathered.astype(jnp.float32), axis=1)
    return out.astype(expert_rows.dtype)


# ---- grouped matmul over the experts held (dropless, expert-parallel)

_GMM_TILE = (128, 1024, 1024)      # rows, contraction, columns


def grouped_matmul(lhs, rhs, group_sizes, group_offset: int = 0,
                   interpret=None):
    """Rows sorted by group times their group's matrix, for the groups
    held here: `lhs` `[m, k]` holds `group_sizes[0]` rows of group 0, then
    group 1's, ... over ALL `G` groups (the router's width, and any
    trailing group of rows that belong to no expert); `rhs`
    `[held, k, n]` holds the matrices of groups `group_offset ..
    group_offset + held`.  Returns `[m, n]` float32 with zeros in the
    rows of groups not held.  The kernel is jax's own Pallas grouped
    matmul (`pallas.ops.tpu.megablox.gmm`): its grid visits only the
    row tiles of non-empty held groups, so an expert that got no row
    costs no read of its weights."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    if interpret is None:
        interpret = pallas_common.interpret_default()
    pallas_common.claim("moe_grouped_matmul", interpret)
    m, k = lhs.shape
    n = rhs.shape[2]
    tm = _GMM_TILE[0] if m >= _GMM_TILE[0] else -(-m // 16) * 16
    pad = -m % tm
    if pad:
        # pad rows join the last group: zero rows, cut off below
        lhs = jnp.concatenate([lhs, jnp.zeros((pad, k), lhs.dtype)])
        group_sizes = group_sizes.at[-1].add(pad)
    out = gmm(lhs, rhs, group_sizes.astype(jnp.int32),
              preferred_element_type=jnp.float32,
              tiling=(tm, min(k, _GMM_TILE[1]), min(n, _GMM_TILE[2])),
              group_offset=jnp.int32(group_offset), interpret=interpret)
    return out[:m]


def grouped_matmul_reference(lhs, rhs, group_sizes, group_offset: int = 0):
    """Pure-XLA oracle of `grouped_matmul`: every held matrix against
    every row, masked."""
    ends = jnp.cumsum(group_sizes)
    gid = jnp.searchsorted(ends, jnp.arange(lhs.shape[0]), side="right")
    local = gid - group_offset
    ok = (local >= 0) & (local < rhs.shape[0])
    w = jnp.take(rhs, jnp.clip(local, 0, rhs.shape[0] - 1), axis=0)
    out = jnp.einsum("mk,mkn->mn", lhs.astype(jnp.float32),
                     w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return jnp.where(ok[:, None], out, 0.0)
