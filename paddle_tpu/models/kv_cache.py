"""Static (preallocated) KV cache for autoregressive decoding.

Parity target: the reference's serving decode path keeps fixed-capacity
KV buffers and writes each new token in place
(`paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu` and
`masked_multihead_attention_kernel.cu` — the write-then-attend decode
step against a preallocated cache).

TPU-native redesign: the eager dense cache concatenates and grows
([B, t, nh, hd] -> [B, t+1, nh, hd]), so every decode position is a NEW
shape and XLA compiles a fresh program per token — fine on GPUs with
cheap JIT-less kernels, pathological under XLA.  A StaticKVCache holds
[B, max_len, nh, hd] buffers and a traced int32 write position: every
step runs the SAME compiled program (`jax.lax.dynamic_update_slice` +
masked attention over the full buffer), so a whole generation costs one
compile.  The over-length attention work is masked dead weight but tiny
at decode batch sizes; the paged Pallas kernel (`ops/pallas_paged.py`)
is the bandwidth-optimal variant of the same idea.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["StaticKVCache", "PagedKVCache", "PagedChunkView",
           "PagedChunkKernelView", "PagedVerifyKernelView", "PoolRow",
           "CacheSpec", "BlockDiffusion", "SelfDraft", "kv_cache_spec",
           "LatentPagedCache", "LatentDenseCache"]


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _update_and_attend(cache_k, cache_v, length, q, k, v):
    """Write (k, v) at `length` and attend q against the valid prefix.

    cache_k/v: [B, L, nh, hd]; q/k/v: [B, s, nh, hd]; length: int32 [].
    Returns (new_k, new_v, out[B, s, nh, hd]).  One program for every
    decode step: shapes are static, the position is a traced scalar.
    """
    cache_k = jax.lax.dynamic_update_slice(
        cache_k, k.astype(cache_k.dtype), (0, length, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(
        cache_v, v.astype(cache_v.dtype), (0, length, 0, 0))
    s, hd = q.shape[1], q.shape[3]
    qpos = length + jnp.arange(s)[:, None]            # [s, 1] absolute
    kpos = jnp.arange(cache_k.shape[1])[None, :]      # [1, L]
    mask = kpos <= qpos                               # causal + valid-prefix
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, cache_k) / math.sqrt(hd)
    logits = jnp.where(mask[None, None],
                       logits.astype(jnp.float32), -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, cache_v)
    return cache_k, cache_v, out


class StaticKVCache:
    """Fixed-capacity per-layer KV cache; functional update (returns a
    new cache object, buffers donated to XLA so the update is in-place
    on device).  Registered as a jax pytree so whole decode loops —
    `lax.scan` with the cache as carry — compile into ONE program."""

    def __init__(self, batch: int, max_len: int, num_heads: int,
                 head_dim: int, dtype=jnp.float32):
        self.k = jnp.zeros((batch, max_len, num_heads, head_dim), dtype)
        self.v = jnp.zeros_like(self.k)
        self.length = jnp.zeros((), jnp.int32)

    def update_and_attend(self, q, k, v):
        """q/k/v: jnp [B, s, nh, hd] (new tokens, post-RoPE).  Returns
        (new_cache, out[B, s, nh, hd])."""
        s = q.shape[1]
        if s > self.k.shape[1]:
            raise ValueError(f"prefill of {s} tokens exceeds cache "
                             f"capacity {self.k.shape[1]}")
        if not isinstance(self.k, jax.core.Tracer):
            # eager path: length is concrete — writing past capacity would
            # silently clamp (dynamic_update_slice semantics) and corrupt
            # the last slots, so raise instead
            if not isinstance(self.length, jax.core.Tracer) and \
                    int(self.length) + s > self.k.shape[1]:
                raise ValueError(
                    f"decode past cache capacity: length {int(self.length)}"
                    f" + {s} new > {self.k.shape[1]}")
            new = StaticKVCache.__new__(StaticKVCache)
            new.k, new.v, out = _update_and_attend(
                self.k, self.v, self.length, q, k, v)
            new.length = self.length + jnp.int32(s)
            return new, out
        # traced (inside an outer jit, e.g. a served decode graph): inline
        new = StaticKVCache.__new__(StaticKVCache)
        new.k, new.v, out = _update_and_attend.__wrapped__(
            self.k, self.v, self.length, q, k, v)
        new.length = self.length + jnp.int32(s)
        return new, out


class PagedKVCache:
    """Functional paged KV cache for COMPILED decode loops.

    Parity seat: the reference's block-paged serving cache
    (`paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu`,
    `fused_multi_transformer_op.cu.h:171` cache-KV branch) — fixed-size
    physical blocks, a per-sequence block table, decode attends through
    the table.

    TPU-native redesign: everything is a traced array so the WHOLE
    generation (prefill write + `lax.scan` over decode steps) compiles
    into one XLA program — round 3 drove the paged Pallas kernel through
    per-token eager dispatch and measured 5.3 tok/s vs 2017 static.  The
    block table is built host-side before tracing: a lockstep
    `generate()` allocates deterministically (sequence b owns blocks
    1 + b*nb .. 1 + (b+1)*nb - 1; block 0 is the pad block), which is the
    same contiguous layout any pool allocator produces from empty.
    Dynamic per-sequence allocation (continuous batching: join/free
    between compiled segments) stays host-side in `BlockKVCache` —
    exactly where serving schedulers do it.

    The memory win vs `StaticKVCache`: the pool is sized by the ACTUAL
    max context of this generation (prompt + new tokens), not the model's
    max_seq_len rectangle — `bench.py`'s long-context rung runs a batch
    whose static rectangle exceeds HBM.
    """

    def __init__(self, batch: int, max_context: int, num_heads: int,
                 head_dim: int, dtype=jnp.float32, block_size: int = 64):
        nb = (max_context + block_size - 1) // block_size
        self.bs = block_size
        # heads lead so each streamed block is a clean [bs, hd] tile
        # (Mosaic tiling needs the trailing two dims tile-friendly)
        self.k = jnp.zeros((num_heads, batch * nb + 1, block_size,
                            head_dim), dtype)
        self.v = jnp.zeros_like(self.k)
        self.tables = (1 + jnp.arange(batch * nb, dtype=jnp.int32)
                       ).reshape(batch, nb)
        self.seq_lens = jnp.zeros((batch,), jnp.int32)

    # a layer's arrays that are not pools (`PoolRow.paged` false: an
    # expert layer's row counts), threaded with them: `()` for GPT / Llama
    state = ()
    # whether the view was made for a forward of a block-diffusion tick
    # (the engine says so: `ServingEngine._forward`) and not for a prompt
    # chunk of as many rows; read on the view a layer is handed
    in_tick = False

    @classmethod
    def from_parts(cls, k, v, *rest, block_size=None):
        """The one constructor for views over existing pools (used by the
        pytree unflattener and the serving engine's per-call views):
        `(k, v, *state, tables, seq_lens, block_size)`, `state` the
        layer's arrays that are not paged, in `CacheSpec.rows` order."""
        if block_size is None:
            *rest, block_size = rest
        *state, tables, seq_lens = rest
        c = cls.__new__(cls)
        c.k, c.v, c.tables, c.seq_lens, c.bs = k, v, tables, seq_lens, \
            block_size
        c.state = tuple(state)
        return c

    @property
    def pools(self):
        """The layer's device arrays, in its `CacheSpec.rows` order."""
        return (self.k, self.v) + self.state

    @property
    def active(self):
        """`[B]`: which sequences of the batch are real.  An idle slot
        has a zero table row (its writes go to the pad block 0)."""
        return self.tables[:, 0] != 0

    def with_state(self, *state):
        """This view over the same pools with `state` in `self.state`'s
        place."""
        return type(self).from_parts(self.k, self.v, *state, self.tables,
                                     self.seq_lens, self.bs)

    def update_and_attend(self, q, k, v):
        """q/k/v: jnp [B, s, nh, hd] (post-RoPE).  s == 1 -> paged decode
        kernel; s > 1 -> bulk prefill write + dense causal attention
        (all sequences at equal length, the prefill contract).  Returns
        (new_cache, out [B, s, nh, hd])."""
        from ..ops import pallas_paged
        B, s, nh, hd = q.shape
        new = PagedKVCache.__new__(PagedKVCache)
        new.bs, new.tables, new.state = self.bs, self.tables, self.state
        if s == 1:
            out, new.k, new.v = pallas_paged.paged_decode_step(
                q[:, 0], k[:, 0], v[:, 0], self.k, self.v, self.tables,
                self.seq_lens)
            new.seq_lens = self.seq_lens + 1
            return new, out[:, None]
        if not isinstance(self.seq_lens, jax.core.Tracer):
            # prefill writes into each sequence's FIRST blocks and attends
            # only within the chunk — valid solely from empty sequences.
            # (Inside the compiled generate the cache is always freshly
            # built, so the concrete-value check covers the misuse case.)
            if int(jnp.max(self.seq_lens)) != 0:
                raise NotImplementedError(
                    "multi-token append to non-empty sequences needs the "
                    "offset-aware PagedChunkView (the serving engine's "
                    "suffix/chunked-prefill view); PagedKVCache prefills "
                    "from empty only — or use cache_impl='dense'")
        new.k, new.v = pallas_paged.paged_write_prefill(
            self.k, self.v, self.tables, k, v)
        new.seq_lens = self.seq_lens + s
        return new, _dense_causal(q, k, v)


class PagedChunkView(PagedKVCache):
    """Offset-aware CHUNK prefill over a paged pool: ``s > 1`` new
    tokens appended to sequences that already hold ``seq_lens`` cached
    tokens, attending over the cached prefix AND the chunk.

    This is the program shape BOTH prefix-cache admission (ISSUE 9: a
    request whose prompt prefix is resident in shared blocks writes
    only its SUFFIX) and chunked prefill (ISSUE 11: every arriving
    prompt is absorbed as bounded chunks between decode ticks) run on —
    `update_and_attend` writes token j of the chunk at absolute
    position ``seq_lens + j`` through the block table (an in-place
    read-modify-write of the blocks the chunk touches, not a scatter:
    XLA:TPU would re-lay-out the whole pool around one) and runs dense
    attention of the chunk queries against the table's linearized
    blocks with an offset causal mask.  Positions beyond the table's
    capacity route their writes to the reserved pad block 0 (same
    convention as the serving engine's padded prompts).

    The base class intentionally rejects this case (prefill from empty
    in one chunk): from-empty prefill never needs the gather, and the
    serving engine keeps using the cheaper base program when neither a
    cached prefix nor chunking is in play.  Decode steps (``s == 1``)
    fall through to the base paged kernel unchanged.

    The pools hold the model's KV heads (`[nkv, ...]`): a grouped-query
    model hands over its `nkv` heads as they are, they are written as
    they are, and query head `j` reads pool head `j // (nh / nkv)`
    (nothing repeats K or V).  `mask_block = L` makes the mask causal
    over blocks of `L` positions and full inside one (`key < (query // L
    + 1) * L`; a block-diffusion model's, whose chunks start at multiples
    of `L`); 1 is the offset causal mask."""

    def update_and_attend(self, q, k, v, mask_block: int = 1):
        if q.shape[1] == 1 and mask_block == 1 \
                and k.shape[2] == q.shape[2]:
            return super().update_and_attend(q, k, v)
        new = self._write_chunk(q, k, v, mask_block)
        return new, self._attend_chunk(q, new, mask_block)

    def _write_chunk(self, q, k, v, mask_block: int = 1):
        """Write the chunk through the block table at absolute positions
        ``seq_lens + j`` (`pallas_paged.paged_write_chunk`: in place, a
        block at a time); returns the advanced view."""
        from ..ops import pallas_paged
        if k.shape[2] != self.k.shape[0]:
            raise ValueError(
                f"the chunk carries {k.shape[2]} kv heads, the pool holds "
                f"{self.k.shape[0]}")
        cls = type(self)
        new = cls.__new__(cls)
        new.bs, new.tables, new.state = self.bs, self.tables, self.state
        new.k, new.v = pallas_paged.paged_write_chunk(
            self.k, self.v, self.tables, self.seq_lens, k, v,
            align=mask_block if self.bs % mask_block == 0 else 1)
        new.seq_lens = self.seq_lens + q.shape[1]
        return new

    def _attend_chunk(self, q, new, mask_block: int = 1):
        """Linearize the table (cached prefix + just-written chunk) and
        attend under the mask (`paged_chunk_attention_reference`): a
        query at absolute position p sees keys 0..p, or to the end of
        its mask block — all real written positions for real queries
        (padded chunk rows attend garbage and are discarded upstream)."""
        from ..ops import pallas_paged
        return pallas_paged.paged_chunk_attention_reference(
            q, new.k, new.v, self.tables, self.seq_lens, mask_block)


class PagedChunkKernelView(PagedChunkView):
    """`PagedChunkView` with the dense linearized-table attend replaced
    by the chunked paged-prefill Pallas kernel
    (`ops/pallas_paged.paged_chunk_attention`).  The write path —
    table-routed block writes of the kv heads, pad-block overflow — is
    inherited unchanged, so the two views differ only in how the attend
    lowers.
    Selected by the serving engine when `FLAGS_serving_pallas_prefill`
    is on (snapshotted at engine init, never read under trace)."""

    def _attend_chunk(self, q, new, mask_block: int = 1):
        from ..ops import pallas_paged
        return pallas_paged.paged_chunk_attention(
            q, new.k, new.v, self.tables, self.seq_lens,
            mask_block=mask_block)


class PagedVerifyKernelView(PagedChunkKernelView):
    """Spec-verify twin of `PagedChunkKernelView`: same kernel contract
    (the k candidate positions are an offset-causal chunk), but a
    distinct entry point so the verify program carries its own audit
    claim and its own flag (`FLAGS_serving_pallas_verify`)."""

    def _attend_chunk(self, q, new, mask_block: int = 1):
        from ..ops import pallas_paged
        if mask_block != 1:
            raise ValueError("the verify kernel runs under the causal mask "
                             f"only (mask_block={mask_block})")
        return pallas_paged.paged_verify_attention(
            q, new.k, new.v, self.tables, self.seq_lens)


@dataclasses.dataclass(frozen=True)
class PoolRow:
    """One device array of a layer's cache.  A paged row is a pool
    `lead + (num_blocks + 1, block_size) + trail` under the layer's block
    table (heads lead a (K, V) pool; a latent pool has none).  A row with
    `paged=False` is per-layer device state of the fixed shape `lead`
    that the programs thread and donate with the pools but no block
    refers to: copy-on-write and the prefix cache pass it over, the tick
    program hands a copy of it back with its tokens and the engine keeps
    that copy on the host (`stats()["cache_state"]`)."""
    name: str
    lead: tuple = ()
    trail: tuple = ()
    dtype: object = None          # None: the model's parameter dtype
    paged: bool = True

    @property
    def block_axis(self) -> int:
        return len(self.lead)

    def shape(self, num_blocks: int, block_size: int) -> tuple:
        if not self.paged:
            return tuple(self.lead)
        return tuple(self.lead) + (num_blocks + 1, block_size) \
            + tuple(self.trail)


@dataclasses.dataclass(frozen=True)
class BlockDiffusion:
    """How a block-diffusion model generates (`CacheSpec.generation`): a
    block of `block_length` positions is denoised in `denoising_steps`
    forwards, each revealing the `block_length / denoising_steps` masked
    positions of highest confidence, from `mask_token_id`, and committed
    by one more forward; the mask is causal over such blocks and full
    inside one."""
    block_length: int
    denoising_steps: int
    mask_token_id: int


@dataclasses.dataclass(frozen=True)
class SelfDraft:
    """How a model with a multi-token-prediction module drafts for itself
    (`CacheSpec.generation`): a forward of a sequence runs the last token
    and the module's draft of the next, and yields one token or, where
    the draft was the model's own choice, two; the module then drafts
    again from the hidden states of the emitted positions.  Its cache is
    the spec's LAST `depth` layers, whose row for position i sits in slot
    i + 1: the row is made of token i + 1, so a block's content depends
    only on tokens up to the block's own end (what prefix sharing needs).
    The model offers `forward_hidden`, `head` and `draft_hidden`."""
    depth: int = 1


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """What `ServingEngine` needs to know of a model's cache, from
    `model.cache_spec()`: the arrays a layer keeps (`rows`, the same for
    every layer) and the views its programs hand `forward_with_cache`.
    Every view class is built by `from_parts(*layer_arrays, tables,
    seq_lens, block_size)` and gives the arrays back as `.pools`.  A
    cache with one attention path for every program names `view` alone.
    `unsupported` names the engine mechanisms this cache cannot run
    under, with the reason: the engine raises at construction.
    `attend_limit` is the size of a sparse selection, for the spans'
    `selected_tokens`.  `generation` is None for a model that yields one
    token a forward and sequence, or says what else it does
    (`BlockDiffusion`, `SelfDraft`): the engine builds its tick from it."""
    num_layers: int
    rows: tuple
    view: type                    # decode step, prefill from empty
    chunk_view: type = None       # a chunk at an offset, plain XLA
    chunk_kernel_view: type = None   # ... through the Pallas chunk kernel
    verify_kernel_view: type = None  # spec-decode verify
    unsupported: dict = dataclasses.field(default_factory=dict)
    attend_limit: int = 0         # tokens a query attends at most; 0: all
    generation: object = None

    def __post_init__(self):
        for name in ("chunk_view", "chunk_kernel_view",
                     "verify_kernel_view"):
            if getattr(self, name) is None:
                object.__setattr__(self, name, self.view)

    def init_pools(self, num_blocks, block_size, dtype, place=None):
        """Zeroed arrays of every layer; `place(row, array)` may move or
        shard one."""
        def one(row):
            z = jnp.zeros(row.shape(num_blocks, block_size),
                          row.dtype or dtype)
            return z if place is None else place(row, z)
        return [tuple(one(r) for r in self.rows)
                for _ in range(self.num_layers)]


def kv_cache_spec(num_layers: int, num_kv_heads: int, head_dim: int,
                  state_rows: tuple = (), **spec):
    """The (K, V) cache of the GPT / Llama families: two pools a layer,
    the KV heads leading (`[num_kv_heads, blocks + 1, block_size,
    head_dim]`: a grouped-query model's are fewer than its query heads),
    one row of `head_dim` a KV head and token.  `state_rows`: the layer's
    `PoolRow`s that are not paged, behind the pools; `spec`: further
    `CacheSpec` fields."""
    row = dict(lead=(num_kv_heads,), trail=(head_dim,))
    return CacheSpec(num_layers, (PoolRow("k", **row), PoolRow("v", **row))
                     + tuple(state_rows),
                     PagedKVCache, PagedChunkView, PagedChunkKernelView,
                     PagedVerifyKernelView, **spec)


class LatentPagedCache:
    """A layer's cache under latent attention with a learned sparse
    index (MLA + DSA): a pool of latent rows (`c_kv | k_rope`, one a
    token, shared by every head), a pool of the indexer's keys beside it
    under the SAME block table, and `moe_rows`, the layer's count of the
    rows its held experts were given (device state, not paged).

    One class serves every program: a decode step, a chunk at an offset
    and a prefill from empty all append `s` rows at `seq_lens` and attend
    each query over its own selection (`ops/sparse_mla.py`)."""

    def __init__(self, ckv, kidx, moe_rows, tables, seq_lens, block_size):
        self.ckv, self.kidx, self.moe_rows = ckv, kidx, moe_rows
        self.tables, self.seq_lens, self.bs = tables, seq_lens, block_size

    from_parts = classmethod(lambda cls, *a: cls(*a))

    @property
    def pools(self):
        return (self.ckv, self.kidx, self.moe_rows)

    @property
    def active(self):
        """`[B]`: which sequences of the batch are real.  An idle slot of
        a decode step has a zero table row (its writes go to the pad
        block 0); a chunk's or a prefill's row begins with a real block."""
        return self.tables[:, 0] != 0

    def replace(self, **kw):
        new = LatentPagedCache(*self.pools, self.tables, self.seq_lens,
                               self.bs)
        for k, v in kw.items():
            setattr(new, k, v)
        return new

    def append_and_attend(self, q_cat, q_idx, w_idx, row, kidx_row, *,
                          topk, scale, d_latent):
        """Append `row` `[B, s, d_latent + rope]` and `kidx_row`
        `[B, s, Di]` at `seq_lens`, then attend `q_cat` `[B, s, nh,
        d_latent + rope]` over the rows the indexer (`q_idx` `[B, s, Hi,
        Di]`, `w_idx` `[B, s, Hi]`) selects.  Returns (advanced view,
        `sum p c_kv` `[B, s, nh, d_latent]` float32, selected positions
        `[B, s, k]` with -1 where fewer than k exist)."""
        from ..ops import sparse_mla
        s = row.shape[1]
        start = self.seq_lens
        new = self.replace(
            ckv=sparse_mla.write_rows(self.ckv, self.tables, start, row),
            kidx=sparse_mla.write_rows(self.kidx, self.tables, start,
                                       kidx_row),
            seq_lens=start + s)
        pos = start[:, None] + jnp.arange(s, dtype=start.dtype)
        o, idx, valid = sparse_mla.sparse_latent_attention(
            q_cat, q_idx, w_idx, new.ckv, new.kidx, self.tables, pos,
            topk=topk, scale=scale, d_latent=d_latent)
        return new, o, jnp.where(valid, idx, -1)


jax.tree_util.register_pytree_node(
    LatentPagedCache,
    lambda c: ((c.ckv, c.kidx, c.moe_rows, c.tables, c.seq_lens), c.bs),
    lambda bs, ch: LatentPagedCache(*ch, bs))


class LatentDenseCache:
    """A layer's cache under latent attention WITHOUT an index (MLA as
    `glm4_moe_lite` has it): one pool of latent rows (`c_kv | k_rope`, one
    a token, shared by every head) that every query attends whole,
    `moe_rows`, the layer's count of the rows its held experts were given,
    and `mtp`, the self-drafter's (drafted, accepted) counts (device
    state, not paged; the engine's tick adds to the last layer's).

    One class serves every program: `s` rows are appended at `seq_lens`
    and each query attends all rows up to its own, through the Pallas
    kernel for the few queries of a decode step or a verify and in plain
    XLA for a chunk (`ops/pallas_latent.py`).  `shift=1` is the
    multi-token-prediction module's layout (`SelfDraft`): rows land one
    slot later and slot 0 is never read."""

    in_tick = False      # as `PagedKVCache.in_tick`: set by the engine

    def __init__(self, ckv, moe_rows, mtp, tables, seq_lens, block_size):
        self.ckv, self.moe_rows, self.mtp = ckv, moe_rows, mtp
        self.tables, self.seq_lens, self.bs = tables, seq_lens, block_size

    from_parts = classmethod(lambda cls, *a: cls(*a))

    @property
    def pools(self):
        return (self.ckv, self.moe_rows, self.mtp)

    @property
    def active(self):
        return self.tables[:, 0] != 0

    def replace(self, **kw):
        new = LatentDenseCache(*self.pools, self.tables, self.seq_lens,
                               self.bs)
        new.in_tick = self.in_tick
        for k, v in kw.items():
            setattr(new, k, v)
        return new

    def append_and_attend(self, q_cat, row, *, scale, d_latent, shift=0):
        """Append `row` `[B, s, d_latent + rope]` at `seq_lens + shift`,
        then attend `q_cat` `[B, s, nh, d_latent + rope]` over every row
        from slot `shift` to the query's own.  Returns (advanced view,
        `sum p c_kv` `[B, s, nh, d_latent]` float32)."""
        from ..ops import pallas_latent, sparse_mla
        s = row.shape[1]
        start = self.seq_lens + shift
        new = self.replace(
            ckv=sparse_mla.write_rows(self.ckv, self.tables, start, row),
            seq_lens=self.seq_lens + s)
        lens = jnp.where(self.active, start + s, 0)
        attend = pallas_latent.paged_latent_attention \
            if s <= pallas_latent.KERNEL_MAX_QUERIES \
            else pallas_latent.latent_chunk_attention
        with jax.named_scope("mla_attend"):
            o = attend(q_cat, new.ckv, self.tables, lens, scale=scale,
                       d_latent=d_latent, first=shift)
        return new, o


jax.tree_util.register_pytree_node(
    LatentDenseCache,
    lambda c: ((c.ckv, c.moe_rows, c.mtp, c.tables, c.seq_lens), c.bs),
    lambda bs, ch: LatentDenseCache(*ch, bs))


def _dense_causal(q, k, v):
    """Prefill attention (no cache read needed: the prompt IS the whole
    context).  Flash kernel when applicable, jnp oracle otherwise."""
    from ..ops import pallas_flash, pallas_kernels
    if pallas_kernels.flash_attention_available(q, k, v):
        return pallas_flash.flash_attention_fwd(q, k, v, causal=True)[0]
    B, s, nh, hd = q.shape
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs,
                      v.astype(jnp.float32)).astype(q.dtype)


def _paged_flatten(c):
    return (c.k, c.v, c.tables, c.seq_lens), c.bs


def _paged_unflatten(bs, children):
    return PagedKVCache.from_parts(*children, block_size=bs)


jax.tree_util.register_pytree_node(
    PagedKVCache, _paged_flatten, _paged_unflatten)


def _cache_flatten(c):
    return (c.k, c.v, c.length), None


def _cache_unflatten(_, children):
    c = StaticKVCache.__new__(StaticKVCache)
    c.k, c.v, c.length = children
    return c


# pytree registration lets whole decode loops carry the cache through
# lax.scan / jit boundaries (one compiled program per generation)
jax.tree_util.register_pytree_node(
    StaticKVCache, _cache_flatten, _cache_unflatten)
