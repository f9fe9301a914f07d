"""The KV cache's layout and the attention that reads it.

A cache of [S, max_seq, H, D] per layer would burn the same HBM for a
40-token chat as for a full-context one (VERDICT r4 weak #5), so the
engine's one cache is a shared block pool plus a per-slot block
table — HBM scales with tokens actually resident, and identical prompt
prefixes can share blocks (prefix reuse).  This is the TPU analogue of
vLLM's PagedAttention; the reference has no serving-cache concept at
all (its `Memory` field is a k8s resource quantity, reference
pkg/apis/serving/v1alpha1/trained_model.go:68-69).

The pool's layout is this module's to decide (`pool_shape`): one minor
dimension of all heads, [num_blocks, block_size, H*D].  A pool stored
[.., H, D] tiles its two minor dimensions, and 20 heads of 64 fill a
bfloat16 tile of 16 x 128 to 3.2 times their bytes in the row-major
layout a Mosaic kernel asks for; H*D pads for no head geometry.
Row-major, [BS, H, D] and [BS, H*D] are the same bytes, so a block's
payload outside the device (host tier, hand-off) does not know.
Activations stay [.., H, D] and are reshaped at this module's edge.

Two implementations with one contract:

- `paged_attention_xla`: gather the slot's blocks into a contiguous
  [B, MB*BS, H, D] view and run masked attention.  Compiles anywhere
  (the hermetic CPU tests run it), but materializes the gathered copy
  every step.
- a Pallas TPU kernel (paged_attention_tpu) that never materializes:
  one program walks, in one loop, each row's own blocks and no others
  (`paged_walk` lists them from the table and the lengths, once a
  step), bringing K and V blocks from the pools in HBM through VMEM by
  its own copies, the next pair's in flight while this one is
  computed; where the pool is narrow a loop iteration takes several
  consecutive blocks of a row at once (`blocks_per_iteration`), so
  that its copies, and not the iteration itself, are what it costs.
  Its work is the blocks that hold context: a short
  sequence in a long-context pool costs its length, not the pool
  width, and a slot nobody decodes in costs a scalar test.  The
  dispatcher picks it from shapes and the backend; under a mesh it
  runs per heads shard.  Where it serves, the decode step's write is a
  Mosaic call too (paged_write_tpu), so nothing of XLA's own touches a
  pool inside the decode program.

Grouped-query attention: the pools hold the KV heads, `H` below, and the
query may bring `G` heads for each (query head j reads KV head j // G;
`G = q heads * D / pool width`, 1 for the models whose heads all have
their own K/V).  The kernel multiplies a KV head's G query rows by its
block in the same product as everything else.

A sliding-window layer (`window` = W, a static int; None for a layer
that sees its whole context) keeps a RING of MB = ceil(W / BS) + 1 blocks a
sequence and no more, whatever its length: absolute block j lives in
column j % MB of the layer's own [B, MB] table (`ring_blocks`), a position
past the ring's end overwrites the block that left the window, and the
table stops changing once its columns are held.  A query at t sees key s
iff t - W < s <= t, and those keys always lie within the ring's MB
blocks.  Every function below takes `window`; with None it is what it was.

Contract (per layer):
    q           [B, 1, G*H, D] current step's query
    pool_k/v    [NB, BS, H*D]  shared block pools (`pool_shape`)
    block_table [B, MB] int32  block ids per slot, -1 = unallocated
    lengths     [B] int32      valid tokens INCLUDING the current
                               step's write
Returns [B, 1, H, D].  A row whose length no table covers (a freed
slot's goes on counting; a parked one sits on max_seq + 1) holds no
request: the engine discards it, the gather answers it with garbage
and the kernel with zeros.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from kfserving_tpu.ops import attention

_NEG_INF = -1e30


def pool_shape(num_blocks: int, block_size: int, heads: int,
               head_dim: int):
    """Shape of one layer's K (or V) block pool; `heads` are KV heads."""
    return (num_blocks, block_size, heads * head_dim)


def _sublanes(dtype) -> int:
    """Rows of one tile of `dtype`: 8 of 32 bits, 16 of 16."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


# K and V blocks in flight or in use at once: the kernel's own
# pipeline, in place of the one a grid would have given it.
_BUFFERS = 3

# K + V bytes one loop iteration of the paged kernel should bring in,
# and the most blocks it takes for them.  An iteration costs about half
# a microsecond whatever it copies (two semaphore waits, the scalar
# reads, one max / exp / sum, the accumulator's rescale, in a chain the
# next iteration waits for); a MiB is what a pool 2048 wide copies in
# one block pair, where the kernel reads 91% of the bandwidth.  Past 4
# blocks nothing is gained on the chip (PERF.md, PR 47): by then the
# copies are what an iteration costs, and a short row's last iteration
# still multiplies by its whole slot, so 8 cost a pool 256 wide a
# quarter more a block than 4 on rows of 4 blocks.
_ITERATION_BYTES = 1 << 20
_ITERATION_BLOCKS = 4


def blocks_per_iteration(block_size: int, pool_width: int, dtype,
                         table_width: int, copies: int = 2) -> int:
    """Consecutive blocks of a row that one loop iteration of
    `paged_attention_tpu` takes: as many as bring its K and V copies to
    `_ITERATION_BYTES`, at least 1 and at most `_ITERATION_BLOCKS` or a
    table's columns.  `pool_width` is the H*D the kernel sees (one
    shard's under a mesh).  In bfloat16 with blocks of 128: 1 for pools
    1280 and 2048 wide, 4 for 512 and 256.  A latent pool's block is
    key and value in one copy (`copies` 1): 4 at 576 wide."""
    pair = copies * block_size * pool_width * jnp.dtype(dtype).itemsize
    return max(1, min(_ITERATION_BYTES // pair, _ITERATION_BLOCKS,
                      table_width))


def ring_blocks(window: int, block_size: int) -> int:
    """Columns of a sliding-window layer's table: the blocks a window of
    `window` keys can touch when it starts anywhere in a block."""
    return -(-window // block_size) + 1


def _ring_positions(lengths, columns, table_width: int, block_size: int):
    """Absolute position of the first row of ring column `columns` for a
    sequence of `lengths` tokens (the step's own included): the latest
    block congruent to the column that the sequence has reached.
    Negative for a column the sequence has not reached."""
    last = (lengths - 1) // block_size
    return (last - (last - columns) % table_width) * block_size


def _blocks_walked(block_table, lengths, block_size: int, window=None):
    """[B] int32: the leading columns of its table row that each row's
    decode step reads (`paged_walk`)."""
    mb = block_table.shape[1]
    if window is None:
        wanted = jnp.where((lengths > 0) & (lengths <= mb * block_size),
                           -(-lengths // block_size), 0)
    else:
        wanted = jnp.where(lengths > 0,
                           jnp.minimum(-(-lengths // block_size), mb), 0)
    columns = jnp.arange(mb, dtype=jnp.int32)
    held = jnp.min(jnp.where(block_table < 0, columns, mb), axis=1)
    return jnp.minimum(wanted, held)


def paged_walk(block_table, lengths, block_size: int, window=None,
               chunk: int = 1):
    """The (row, column) pairs of `block_table` a decode step must
    read, in the order `paged_attention_tpu` walks them, as flat table
    indices `row * MB + column` in a [B*MB] int32 list, and how many of
    its entries count, [1] int32.  Row r walks its first
    ceil(len[r] / BS) columns, as far as their entries are allocated; a
    row whose length no table covers (0: never fed; past MB*BS: parked
    on the position sentinel, or free and still counting) walks none.
    It depends on the table and the lengths alone, which every layer of
    a step shares, so XLA computes it once a step.  With a `window` the
    table is a ring (`ring_blocks`): a row walks the columns it has
    reached, all MB of them once it is a window long, and it is the
    table alone (all -1 for a free or a parked row) that says a row
    holds no request.
    With `chunk` = n > 1 (`blocks_per_iteration`) an entry stands for
    up to n consecutive columns of its row and names the first: a row
    that walks c columns is listed ceil(c / n) times, at columns 0, n,
    2n, ..., and its last entry stands for the c - n * (ceil(c / n) - 1)
    columns that are left."""
    b, mb = block_table.shape
    counts = _blocks_walked(block_table, lengths, block_size, window)
    if chunk > 1:
        counts = -(-counts // chunk)                # entries a row
    ends = jnp.cumsum(counts)
    at = jnp.arange(b * mb, dtype=jnp.int32)
    # Entry `at` belongs to the row after those whose walks end at or
    # before it, and is that row's entry `at` less theirs.
    before = at[:, None] >= ends[None, :]                   # [B*MB, B]
    row = jnp.minimum(jnp.sum(before, axis=1), b - 1)
    column = jnp.clip(
        (at - jnp.sum(jnp.where(before, counts[None, :], 0), axis=1))
        * chunk, 0, mb - 1)
    return ((row * mb + column).astype(jnp.int32),
            ends[-1:].astype(jnp.int32))


def _paged_kernel(walked_ref, pairs_ref, count_ref, table_ref, len_ref,
                  q_ref, pool_k, pool_v, o_ref, k_blocks, v_blocks, sems,
                  q_scratch, m_scratch, l_scratch, acc_scratch, *,
                  block_size: int, table_width: int, scale: float,
                  head_dim: int, group: int, chunk: int, window=None):
    """Every row's online-softmax walk over its own blocks, all heads
    at once, on blocks [BS, H*D] as the pool stores them: one program,
    one loop over `paged_walk`'s pairs, so the work is the blocks that
    hold context and nothing is in proportion to B x MB.  The pools
    stay in HBM; pair i's K and V blocks come through VMEM by this
    kernel's own copies, `_BUFFERS` deep, and the copy of a later pair
    starts before this one is computed whichever row it belongs to, so
    a row of one block does not wait for its read.
    The per-head reduction is two MXU products and no [.., H, D] view:
    the query becomes block-diagonal, `q_bd[r, c] = q[c]` where column
    c is one of head r's, so `q_bd . K^T` is every head's scores
    [H_pad, BS]; `p . V` is [H_pad, H*D], of which head r's columns of
    row r are the answer, taken once at the row's last block.  With
    `group` query heads a KV head, row r is query head r, its columns
    are KV head r // group's, and the query and the answer are [G*H, D]
    a row (not flat): the same two products.  A row
    that walks nothing is never touched: its output stays zeros.  The
    gathered [B, MB*BS, H, D] view the XLA fallback materializes every
    step never exists here.
    With `chunk` = n > 1 a pair is a row's next n columns or what is
    left of them (`walked_ref`: the columns each row walks; None when
    n is 1): a VMEM slot is n blocks long, each block the chunk holds
    is a copy of its own into its BS rows of the slot, the scores are
    [H_pad, n * BS] from one product against the whole slot, and one
    max / exp / sum and one rescale of the accumulator serve them all.
    Rows of a slot that no copy of this chunk wrote lie past every
    position the mask lets through.
    A LATENT pool (`pool_v` and `v_blocks` None: `latent_attention_tpu`)
    has one row a token that every query head reads whole, as the key
    over all its columns and as the value over its first
    `acc_scratch.shape[1]`: a block is copied once and used twice, the
    query comes [H_pad, width] as it is multiplied (no block diagonal:
    there is one head of keys), and the answer leaves [H_pad, rank]."""
    count = count_ref[0]
    h_pad, hd = acc_scratch.shape
    latent = pool_v is None
    pools = (((pool_k, k_blocks),) if latent
             else ((pool_k, k_blocks), (pool_v, v_blocks)))

    def own_columns():
        # [h_pad, hd] bool: column c belongs to (the KV head of) head r.
        row = jax.lax.broadcasted_iota(jnp.int32, (h_pad, hd), 0)
        if group > 1:
            row = row // group
        col = jax.lax.broadcasted_iota(jnp.int32, (h_pad, hd), 1)
        return (col >= row * head_dim) & (col < (row + 1) * head_dim)

    def held(at):
        """Blocks of the pair at flat table index `at`."""
        if chunk == 1:
            return 1
        return jnp.minimum(
            walked_ref[at // table_width] - at % table_width, chunk)

    def each_copy(i, at, blocks, do):
        """`do` on the copy of each of the `blocks` that pair i, at
        flat table index `at`, holds, into its place in the pair's
        slot."""
        slot = i % _BUFFERS
        for j in range(chunk):
            def _copies(j=j):
                block = table_ref[at + j]
                rows = pl.ds(j * block_size, block_size)
                for s, (pool, slots) in enumerate(pools):
                    do(pltpu.make_async_copy(
                        pool.at[block], slots.at[slot, rows],
                        sems.at[s, slot]))
            if j == 0:  # every pair holds a block
                _copies()
            else:
                pl.when(j < blocks)(_copies)

    def fetch(i):
        @pl.when(i < count)
        def _start():
            at = pairs_ref[i]
            each_copy(i, at, held(at), lambda copy: copy.start())

    def pair(i, _):
        fetch(i + _BUFFERS - 1)  # into the slot pair i - 1 has left
        at = pairs_ref[i]
        row, column = at // table_width, at % table_width
        row_len = len_ref[row]
        slot = i % _BUFFERS

        @pl.when(column == 0)
        def _init():
            if latent:
                q_scratch[...] = q_ref[row].astype(q_scratch.dtype)
            else:
                # Select in float32 and cast: Mosaic refuses the relayout
                # of a 16-bit select here.
                q = q_ref[row].astype(jnp.float32)
                if group == 1:
                    q = jnp.broadcast_to(q, (h_pad, hd))
                else:  # [h_pad, D], repeated under every KV head's columns
                    q = jnp.concatenate([q] * (hd // head_dim), axis=1)
                q_scratch[...] = jnp.where(own_columns(), q,
                                           0.0).astype(q_scratch.dtype)
            m_scratch[...] = jnp.full_like(m_scratch, _NEG_INF)
            l_scratch[...] = jnp.zeros_like(l_scratch)
            acc_scratch[...] = jnp.zeros_like(acc_scratch)

        blocks = held(at)
        each_copy(i, at, blocks, lambda copy: copy.wait())
        k = k_blocks[slot].astype(q_scratch.dtype)        # [n * bs, hd]
        s = jax.lax.dot_general(
            q_scratch[...], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [h_pad, n * bs]
        within = jax.lax.broadcasted_iota(jnp.int32,
                                          (h_pad, block_size), 1)
        pos = []
        for j in range(chunk):
            if window is None:
                first = (column + j) * block_size
            else:  # the ring column's place in the sequence
                first = _ring_positions(row_len, column + j, table_width,
                                        block_size)
            if j:  # a block the pair does not hold: past the sequence
                first = jnp.where(j < blocks, first, row_len)
            pos.append(first + within)
        pos = pos[0] if chunk == 1 else jnp.concatenate(pos, axis=1)
        seen = pos < row_len
        if window is not None:
            # A column wholly before the window adds exp(-1e30 - m) = 0
            # (or is wiped by the first real maximum's alpha = 0).
            seen &= pos >= row_len - window
        s = jnp.where(seen, s, _NEG_INF)
        m_prev = m_scratch[...]                           # [h_pad, 1]
        m_new = jnp.maximum(m_prev,
                            jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                        # [h_pad, n * bs]
        alpha = jnp.exp(m_prev - m_new)                   # [h_pad, 1]
        l_scratch[...] = alpha * l_scratch[...] + jnp.sum(
            p, axis=1, keepdims=True)
        # [n * bs, hd]; a latent row's first hd columns
        v = k_blocks[slot][:, :hd] if latent else v_blocks[slot]
        pv = jnp.dot(p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)  # [h_pad, hd]
        acc_scratch[...] = acc_scratch[...] * alpha + pv
        m_scratch[...] = m_new

        # The row's last pair: the list ends, or the next pair starts
        # a row (every walk starts at column 0).
        after = pairs_ref[jnp.minimum(i + 1, pairs_ref.shape[0] - 1)]

        @pl.when((i + 1 == count) | (after % table_width == 0))
        def _finalize():
            out = acc_scratch[...] / jnp.maximum(l_scratch[...], 1e-30)
            if not latent:
                out = jnp.where(own_columns(), out, 0.0)
                if group == 1:
                    out = jnp.sum(out, axis=0, keepdims=True)
                else:
                    out = sum(out[:, g * head_dim:(g + 1) * head_dim]
                              for g in range(hd // head_dim))
            o_ref[row] = out.astype(o_ref.dtype)

    o_ref[...] = jnp.zeros_like(o_ref)
    if chunk > 1:
        # p is 0 on the rows of a slot that no copy of the pair wrote,
        # and 0 x NaN is NaN in p . V: what lies there must be finite,
        # and what VMEM holds before its first write need not be.
        values = k_blocks if latent else v_blocks
        values[...] = jnp.zeros_like(values)
    for i in range(_BUFFERS - 1):
        fetch(i)
    jax.lax.fori_loop(0, count, pair, None)


@functools.partial(jax.jit, static_argnames=("interpret", "window"))
def paged_attention_tpu(q, pool_k, pool_v, block_table, lengths,
                        interpret: bool = False, window=None):
    """Pallas paged decode attention — same contract as
    `paged_attention_xla` on every row that holds context, without
    materializing the gathered cache view, and reading only blocks that
    hold valid tokens (a short sequence in a long-context pool costs
    its length, not the pool width; a slot nobody decodes in costs
    nothing).  A row that walks no block (`paged_walk`) comes back as
    zeros."""
    b, lq, h, d = q.shape
    nb, bs, hd = pool_k.shape
    group = h * d // hd
    assert lq == 1 and hd * group == h * d, (q.shape, pool_k.shape)
    mb = block_table.shape[1]
    scale = 1.0 / (d ** 0.5)
    lengths = lengths.astype(jnp.int32)
    chunk = blocks_per_iteration(bs, hd, pool_k.dtype, mb)
    pairs, count = paged_walk(block_table, lengths, bs, window, chunk)
    scalars = (pairs, count, block_table.reshape(-1), lengths)
    if chunk > 1:
        scalars = (_blocks_walked(block_table, lengths, bs, window)
                   .astype(jnp.int32),) + scalars

    # The products run in the pool's precision when the query shares
    # it (bfloat16 x bfloat16 with float32 accumulation is what a
    # bfloat16 configuration states); rows pad to a whole sublane tile
    # of that type.
    compute = jnp.promote_types(q.dtype, pool_k.dtype)
    h_pad = -(-h // _sublanes(compute)) * _sublanes(compute)
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    # A row's query and answer: all heads flat, or [heads, D] when a KV
    # head has several, in whole sublane tiles: 20 query heads come as 32
    # in bfloat16, the rows past them zeros that belong to no KV head
    # (`own_columns`), whose answers are cut off below.
    row_shape = (b, 1, hd) if group == 1 else (b, h_pad, d)
    q = q.reshape(b, 1, hd) if group == 1 else q.reshape(b, h, d)
    if group > 1 and h_pad > h:
        q = jnp.pad(q, ((0, 0), (0, h_pad - h), (0, 0)))
    rows = pl.BlockSpec(row_shape, lambda i, *_: (0, 0, 0))
    blocks = pltpu.VMEM((_BUFFERS, chunk * bs, hd), pool_k.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(1,),
        in_specs=[rows, hbm, hbm],
        out_specs=rows,
        scratch_shapes=[
            blocks, blocks, pltpu.SemaphoreType.DMA((2, _BUFFERS)),
            pltpu.VMEM((h_pad, hd), compute),
            pltpu.VMEM((h_pad, 1), jnp.float32),
            pltpu.VMEM((h_pad, 1), jnp.float32),
            pltpu.VMEM((h_pad, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, block_size=bs,
                               table_width=mb, scale=scale, head_dim=d,
                               group=group, chunk=chunk, window=window)
    if chunk == 1:  # every pair is one block: no `walked_ref`
        kernel = functools.partial(kernel, None)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(row_shape, q.dtype),
        interpret=interpret,
    )(*scalars, q, pool_k, pool_v)
    if group > 1:
        out = out[:, :h]
    return out.reshape(b, 1, h, d)


def _latent_kernel(*refs, **static):
    """`_paged_kernel` on a latent pool: the refs it is handed have no
    second pool and no second set of blocks."""
    *before, o_ref, blocks, sems, q_scratch, m, l, acc = refs
    _paged_kernel(*before, None, o_ref, blocks, None, sems, q_scratch, m,
                  l, acc, **static)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def latent_attention_tpu(q, pool, block_table, lengths, *, rank: int,
                         scale: float, interpret: bool = False):
    """Pallas decode attention over a latent pool [NB, BS, W]
    (models/deepseek_v3.py): q [B, 1, H, W] is every head's absorbed
    query, a pool row the key of them all over its W columns and their
    value over its first `rank`; `scale` multiplies the scores (the
    published head's 1/sqrt, not W's).  Returns [B, 1, H, rank]; a row
    that walks no block comes back as zeros.  `paged_attention_tpu`'s
    walk and loop, each block copied once."""
    b, lq, h, w = q.shape
    nb, bs, width = pool.shape
    assert lq == 1 and w == width and rank <= width, (q.shape, pool.shape)
    mb = block_table.shape[1]
    lengths = lengths.astype(jnp.int32)
    chunk = blocks_per_iteration(bs, width, pool.dtype, mb, copies=1)
    pairs, count = paged_walk(block_table, lengths, bs, None, chunk)
    scalars = (pairs, count, block_table.reshape(-1), lengths)
    if chunk > 1:
        scalars = (_blocks_walked(block_table, lengths, bs)
                   .astype(jnp.int32),) + scalars
    compute = jnp.promote_types(q.dtype, pool.dtype)
    h_pad = -(-h // _sublanes(compute)) * _sublanes(compute)
    q = q.reshape(b, h, w)
    if h_pad > h:
        q = jnp.pad(q, ((0, 0), (0, h_pad - h), (0, 0)))

    def rows(width):
        return pl.BlockSpec((b, h_pad, width), lambda i, *_: (0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(1,),
        in_specs=[rows(w), pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=rows(rank),
        scratch_shapes=[
            pltpu.VMEM((_BUFFERS, chunk * bs, width), pool.dtype),
            pltpu.SemaphoreType.DMA((1, _BUFFERS)),
            pltpu.VMEM((h_pad, w), compute),
            pltpu.VMEM((h_pad, 1), jnp.float32),
            pltpu.VMEM((h_pad, 1), jnp.float32),
            pltpu.VMEM((h_pad, rank), jnp.float32),
        ],
    )
    kernel = functools.partial(_latent_kernel, block_size=bs,
                               table_width=mb, scale=scale, head_dim=w,
                               group=h, chunk=chunk)
    if chunk == 1:  # every pair is one block: no `walked_ref`
        kernel = functools.partial(kernel, None)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h_pad, rank), q.dtype),
        interpret=interpret,
    )(*scalars, q, pool)
    return out[:, :h].reshape(b, 1, h, rank)


def _write_kernel(blk_ref, off_ref, *refs, rows: int, sublanes: int):
    """A decode step's rows into the pools, in place (the outputs alias
    the inputs, all in HBM): row r's sublane tile, the `sublanes`
    positions of its block around its offset, comes into VMEM, takes
    the row, and goes back.  A 16-bit pool packs two positions into
    each word, so one position is not something a DMA can address: the
    tile is.  Rows to drop (block -1) move nothing.  One program, every
    copy in flight at once.  `refs`: the step's rows a pool (K and V, or
    a latent pool's one), the pools in (the same buffers as) and out, a
    pool's tiles, the semaphores."""
    n = len(refs) // 4
    steps, pools, tiles_of, sems = (refs[:n], refs[2 * n:3 * n],
                                    refs[3 * n:4 * n], refs[-1])

    def copies(r, to_pool: bool):
        start = pl.multiple_of(
            (off_ref[r] // sublanes) * sublanes, sublanes)
        out = []
        for i, (pool, tiles) in enumerate(zip(pools, tiles_of)):
            hbm = pool.at[blk_ref[r], pl.ds(start, sublanes), :]
            src, dst = (tiles.at[r], hbm) if to_pool else (hbm,
                                                           tiles.at[r])
            out.append(pltpu.make_async_copy(src, dst, sems.at[i, r]))
        return out

    def each_live_row(body):
        def row(r, _):
            pl.when(blk_ref[r] >= 0)(functools.partial(body, r))

        jax.lax.fori_loop(0, rows, row, None)

    def fetch(r):
        for copy in copies(r, to_pool=False):
            copy.start()

    def merge(r):
        for copy in copies(r, to_pool=False):
            copy.wait()
        at = jax.lax.broadcasted_iota(jnp.int32, tiles_of[0].shape[1:],
                                      0) == off_ref[r] % sublanes
        for tiles, step in zip(tiles_of, steps):
            # Select in float32 (a 16-bit select asks Mosaic for a
            # relayout it refuses); the round trip is exact.
            row = jnp.broadcast_to(step[r].astype(jnp.float32),
                                   tiles.shape[1:])
            tiles[r] = jnp.where(at, row, tiles[r].astype(
                jnp.float32)).astype(tiles.dtype)
        for copy in copies(r, to_pool=True):
            copy.start()

    def land(r):
        for copy in copies(r, to_pool=True):
            copy.wait()

    each_live_row(fetch)
    each_live_row(merge)
    each_live_row(land)


def _write_rows(pools, steps, blocks, offsets, interpret: bool):
    """`_write_kernel` on `pools` of one shape and dtype, a [B, width]
    array of `steps` each; returns the written pools."""
    n = len(pools)
    rows, hd = steps[0].shape
    sublanes = _sublanes(pools[0].dtype)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    step = pl.BlockSpec((rows, 1, hd), lambda i, blk, off: (0, 0, 0))
    tiles = pltpu.VMEM((rows, sublanes, hd), pools[0].dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(1,),
        in_specs=[step] * n + [hbm] * n, out_specs=[hbm] * n,
        scratch_shapes=[tiles] * n + [pltpu.SemaphoreType.DMA((n, rows))])
    kernel = functools.partial(_write_kernel, rows=rows,
                               sublanes=sublanes)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype)
                   for pool in pools],
        input_output_aliases={2 + n + i: i for i in range(n)},
        interpret=interpret,
    )(blocks.astype(jnp.int32), offsets.astype(jnp.int32),
      *(x.astype(pool.dtype).reshape(rows, 1, hd)
        for x, pool in zip(steps, pools)), *pools)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_write_tpu(pool_k, pool_v, k_step, v_step, blocks, offsets,
                    interpret: bool = False):
    """Pallas decode-step write: k/v [B, H*D] into the pools at
    (blocks [B], offsets [B]), block -1 dropping its row.  Same result
    as `paged_write`'s scatter; it exists because the pools' only users
    inside the decode program are then Mosaic calls, which read and
    write them where they are.  Left to XLA, a scatter into a pool that
    fits VMEM (gpt2-large's 47 MB) has the whole pool prefetched there
    and copied back every step."""
    return _write_rows((pool_k, pool_v), (k_step, v_step), blocks, offsets,
                       interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def latent_write_tpu(pool, step, blocks, offsets, interpret: bool = False):
    """`paged_write_tpu` for a latent pool: one row [B, W] a step."""
    return _write_rows((pool,), (step,), blocks, offsets, interpret)[0]


def _kernels_serve(block_size: int, heads: int, head_dim: int,
                   group: int = 1) -> bool:
    """Whether the Pallas kernels take these shapes, asked at trace
    time: on a TPU, with block_size and the H*D of one (KV) heads shard
    both lane multiples, so that every block is whole tiles (the XLA
    formulations serve the rest, and the CPU); with `group` > 1 query
    heads a KV head, also a head size of whole lane tiles and no mesh
    (query heads that are not whole sublane tiles are padded to them:
    `paged_attention_tpu`)."""
    mesh = jax.sharding.get_abstract_mesh()
    shards = 1
    if not mesh.empty and attention.mesh_axis(mesh, "tp", heads):
        shards = mesh.shape["tp"]
    if group > 1 and not (mesh.empty and head_dim % 128 == 0):
        return False
    return (attention._tpu_backend() and block_size % 128 == 0
            and (heads // shards * head_dim) % 128 == 0)


def paged_attention(q, pool_k, pool_v, block_table, lengths,
                    window=None):
    """Dispatcher: the Pallas kernel where `_kernels_serve` says so and
    the query is a single token, XLA gather otherwise (CPU tests, odd
    shapes).  Runs at trace time inside the jitted decode function."""
    heads, head_dim = q.shape[2:]
    kv_heads = pool_k.shape[2] // head_dim
    use_kernel = q.shape[1] == 1 and _kernels_serve(
        pool_k.shape[1], kv_heads, head_dim, heads // kv_heads)
    shapes = dict(q=q.shape, pool=pool_k.shape, table=block_table.shape)
    if window is not None:
        shapes["window"] = window
    attention.log_dispatch(
        "pallas_paged" if use_kernel else "xla_paged", **shapes)
    if use_kernel:
        return paged_attention_sharded(q, pool_k, pool_v, block_table,
                                       lengths, window=window)
    return paged_attention_xla(q, pool_k, pool_v, block_table, lengths,
                               window)


def paged_attention_sharded(q, pool_k, pool_v, block_table, lengths,
                            interpret: bool = False, window=None):
    """`paged_attention_tpu`, under `shard_map` when the caller runs
    inside a mesh (`jax.set_mesh`): Mosaic kernels cannot be
    partitioned automatically, and per-head attention needs no
    collective — q splits on heads over ``tp`` and the pools on H*D,
    which is the same head groups and how the engine shards the pool;
    block table and lengths replicate."""
    kernel = functools.partial(paged_attention_tpu, interpret=interpret,
                               window=window)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return kernel(q, pool_k, pool_v, block_table, lengths)
    tp = attention.mesh_axis(mesh, "tp", q.shape[2])
    heads, pool = P(None, None, tp, None), P(None, None, tp)
    return jax.shard_map(
        kernel, in_specs=(heads, pool, pool, P(), P()), out_specs=heads,
        check_vma=False)(q, pool_k, pool_v, block_table, lengths)


def paged_write_sharded(pool_k, pool_v, k_step, v_step, blocks, offsets,
                        interpret: bool = False):
    """`paged_write_tpu` for k/v [B, H, D], per heads shard inside a
    mesh like `paged_attention_sharded`."""
    rows, h = k_step.shape[:2]
    kernel = functools.partial(paged_write_tpu, interpret=interpret)
    k_step, v_step = k_step.reshape(rows, -1), v_step.reshape(rows, -1)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return kernel(pool_k, pool_v, k_step, v_step, blocks, offsets)
    tp = attention.mesh_axis(mesh, "tp", h)
    pool, step = P(None, None, tp), P(None, tp)
    return jax.shard_map(
        kernel, in_specs=(pool, pool, step, step, P(), P()),
        out_specs=(pool, pool), check_vma=False)(
            pool_k, pool_v, k_step, v_step, blocks, offsets)


def _gathered(pool, table, head_dim: int):
    """A batch's blocks as one contiguous [B, MB*BS, H, D] view.
    -1 (unallocated) clamps to block 0: the callers mask it out, and
    XLA's gather clamps anyway — explicit is better than relying on
    OOB behavior."""
    b, mb = table.shape
    bs = pool.shape[1]
    return pool[jnp.maximum(table, 0)].reshape(b, mb * bs, -1, head_dim)


def _masked_attention(q, k, v, mask):
    """softmax(q.k / sqrt(D)) . v in float32 over [B, K, H, D] keys,
    `mask` [B, 1, Lq, K] true where a query may look; q [B, Lq, G*H, D],
    query head j on KV head j // G."""
    b, lq, heads, d = q.shape
    kv_heads = k.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    q = q.reshape(b, lq, kv_heads, heads // kv_heads, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = jnp.where(mask[:, :, None], logits,
                       jnp.finfo(jnp.float32).min)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", weights,
                     v.astype(jnp.float32))
    return out.reshape(b, lq, heads, d).astype(q.dtype)


def _ring_key_positions(table_width: int, block_size: int, lengths):
    """[B, MB*BS] position in its sequence of every row of a window
    layer's gathered ring, for sequences of `lengths` tokens: the latest
    position written to the row (row r of the ring holds the positions
    congruent to r modulo the ring's MB*BS rows; negative where the
    sequence has not reached the row)."""
    rows = table_width * block_size
    last = lengths[:, None] - 1
    return last - (last - jnp.arange(rows)[None, :]) % rows


def paged_attention_xla(q, pool_k, pool_v, block_table, lengths,
                        window=None):
    d = q.shape[3]
    k = _gathered(pool_k, block_table, d)
    v = _gathered(pool_v, block_table, d)
    if window is None:
        positions = jnp.arange(k.shape[1])[None, :]
        mask = positions < lengths[:, None]
    else:
        positions = _ring_key_positions(block_table.shape[1],
                                        pool_k.shape[1], lengths)
        mask = ((positions < lengths[:, None]) & (positions >= 0)
                & (positions >= lengths[:, None] - window))
    return _masked_attention(q, k, v, mask[:, None, None, :])


def _write_targets(block_table, positions, block_size: int, window=None):
    """(block, offset within it, whether the write drops) of `positions`
    [B] or [B, L] through `block_table`, by `paged_write`'s rules."""
    mb = block_table.shape[1]
    block_idx = positions // block_size
    offs = positions % block_size
    rows = jnp.arange(block_table.shape[0])
    if positions.ndim == 2:
        rows = rows[:, None]
    if window is None:
        blocks = block_table[rows, jnp.minimum(block_idx, mb - 1)]
        dropped = (blocks < 0) | (block_idx >= mb)
    else:
        blocks = block_table[rows, block_idx % mb]
        dropped = blocks < 0
    return blocks, offs, dropped


def paged_write(pool_k, pool_v, k_step, v_step, block_table,
                positions, window=None):
    """Scatter a step's k/v into the pools at each slot's positions.

    Two call shapes, distinguished at trace time:
      decode:        k/v [B, H, D],    positions [B]
      chunk prefill: k/v [B, L, H, D], positions [B, L]
    Unallocated targets (-1 in the table) AND positions past the
    table's coverage (the engine parks mid-prefill slots on an
    out-of-range feed-position sentinel so speculative decode waves
    cannot corrupt chunks already written) drop via OOB sentinel —
    never clamp: a clamped OOB write would land inside another
    position's block.

    Speculative verify rides the chunked shape: the K+1-position
    dispatch writes k/v for every PROPOSED position [L, L+K], accepted
    or not.  That needs no rollback — rejected positions hold garbage
    the per-query causal mask keeps unreachable (no committed query
    sits past the first rejection), and the next wave over the slot
    re-writes those very positions before its own attention reads
    them.  Only the drop-never-clamp rule above makes the parked-slot
    and near-max_seq overrun cases of that scheme safe.

    With a `window` the table is the layer's ring: position p goes to
    column (p // BS) % MB, no position is past the table, and a row
    that holds no request is dropped by its table's -1s alone."""
    bs = pool_k.shape[1]
    chunked = positions.ndim == 2
    blocks, offs, dropped = _write_targets(block_table, positions, bs,
                                           window)
    if not chunked and _kernels_serve(bs, *k_step.shape[1:]):
        return paged_write_sharded(pool_k, pool_v, k_step, v_step,
                                   jnp.where(dropped, -1, blocks), offs)
    # -1 (unallocated) or past-the-table positions -> OOB sentinel so
    # mode="drop" discards the write.
    blocks = jnp.where(dropped, pool_k.shape[0], blocks)
    flat = positions.shape + pool_k.shape[2:]   # [B(, L), H*D]
    pool_k = pool_k.at[blocks, offs].set(
        k_step.reshape(flat).astype(pool_k.dtype), mode="drop")
    pool_v = pool_v.at[blocks, offs].set(
        v_step.reshape(flat).astype(pool_v.dtype), mode="drop")
    return pool_k, pool_v


def paged_prefill_attention_xla(q, pool_k, pool_v, block_table,
                                q_positions, window=None):
    """Chunk-prefill attention: multi-token queries over the paged
    pool with PER-QUERY causal masking (query at absolute position p
    attends keys at positions <= p).  The single-length mask of
    `paged_attention_xla` cannot express this — a chunk's later
    queries see more of the pool than its earlier ones.

    q           [B, L, H, D]   the chunk's queries (L > 1)
    q_positions [B, L] int32   absolute position per query; the
                               engine parks padding queries of a
                               partial final chunk on an out-of-range
                               sentinel (their output is discarded,
                               the mask keeps them finite)
    With a `window` the table is the layer's ring as the chunk's own
    writes left it (every query position real, the row's last the
    latest): a chunk of at most MB*BS - W + 1 tokens (BS + 1 or more)
    has overwritten no key that its first query still sees.
    Returns [B, L, H, D]."""
    d = q.shape[3]
    k = _gathered(pool_k, block_table, d)
    v = _gathered(pool_v, block_table, d)
    if window is None:
        key_pos = jnp.arange(k.shape[1])[None, None, :]   # [1, 1, K]
        mask = key_pos <= q_positions[:, :, None]         # [B, L, K]
    else:
        key_pos = _ring_key_positions(
            block_table.shape[1], pool_k.shape[1],
            jnp.max(q_positions, axis=1) + 1)[:, None, :]
        mask = ((key_pos <= q_positions[:, :, None]) & (key_pos >= 0)
                & (key_pos > q_positions[:, :, None] - window))
    return _masked_attention(q, k, v, mask[:, None])


def paged_insert(pool_k, pool_v, k_new, v_new, dest_blocks, lengths):
    """Insert a prefill batch's k/v ([B, L, H, D]) into pool blocks.

    dest_blocks [B, ceil(L/BS)] int32: destination block id per
    L-chunk of each row; -1 chunks drop (bucket padding rows, or
    prefix-cache hits whose blocks already hold the data).  Positions
    beyond lengths[i] within a written block are harmless garbage —
    reads mask by length."""
    b, l = k_new.shape[:2]
    bs = pool_k.shape[1]
    chunks = l // bs
    assert chunks * bs == l, "prefill bucket must be block-aligned"
    dest = jnp.where(dest_blocks < 0, pool_k.shape[0], dest_blocks)
    blocks = (b * chunks,) + pool_k.shape[1:]             # [.., BS, H*D]
    flat_dest = dest.reshape(b * chunks)
    pool_k = pool_k.at[flat_dest].set(
        k_new.reshape(blocks).astype(pool_k.dtype), mode="drop")
    pool_v = pool_v.at[flat_dest].set(
        v_new.reshape(blocks).astype(pool_v.dtype), mode="drop")
    return pool_k, pool_v


# -- a latent pool: one row a token, key and value at once -------------------
# Latent attention (models/deepseek_v3.py) keeps, a token a layer, the
# normed compression c [rank] and the rotated shared key k_pe side by side,
# W = rank + rope columns: pool [NB, BS, W], tabled, walked, written and
# inserted by position as a K/V pool is.  Every query head reads the whole
# row as its key (the absorbed query is W wide) and the row's first `rank`
# columns as its value.  The pool's rows are whole lane tiles, the columns
# past W zeros (576 stored as 640): the chip's own layout of a [.., 576]
# bfloat16 array is 640 wide in HBM whatever is declared, and a Mosaic
# copy takes whole tiles of it alone ("Slice shape along dimension 2 must
# be aligned to tiling (128), but is 576"), so the padding costs no byte
# that a pool declared 576 wide would not.


def latent_pool_shape(num_blocks: int, block_size: int, width: int):
    """Shape of one latent layer's pool for rows of `width` columns."""
    return (num_blocks, block_size, -(-width // 128) * 128)


def _pool_wide(x, pool):
    """x [.., W] with zeros up to the pool's columns."""
    pad = pool.shape[-1] - x.shape[-1]
    return x if pad == 0 else jnp.pad(
        x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _latent_kernels_serve(block_size: int) -> bool:
    """The Pallas kernels on a latent pool: a TPU, no mesh (one row is
    all heads', so there is nothing of it to shard on heads), blocks of
    whole lane tiles."""
    return (attention._tpu_backend() and block_size % 128 == 0
            and jax.sharding.get_abstract_mesh().empty)


def latent_write(pool, rows, block_table, positions):
    """`paged_write` for a latent pool: rows [B, W] at positions [B] (a
    decode step) or [B, L, W] at [B, L] (a chunk, a verify)."""
    blocks, offs, dropped = _write_targets(block_table, positions,
                                           pool.shape[1])
    rows = _pool_wide(rows, pool)
    if positions.ndim == 1 and _latent_kernels_serve(pool.shape[1]):
        return latent_write_tpu(pool, rows, jnp.where(dropped, -1, blocks),
                                offs)
    blocks = jnp.where(dropped, pool.shape[0], blocks)
    return pool.at[blocks, offs].set(rows.astype(pool.dtype), mode="drop")


def latent_insert(pool, new, dest_blocks):
    """`paged_insert` for a latent pool: a prefill's rows [B, L, W]."""
    b, l = new.shape[:2]
    chunks = l // pool.shape[1]
    assert chunks * pool.shape[1] == l, "prefill bucket must be block-aligned"
    new = _pool_wide(new, pool)
    dest = jnp.where(dest_blocks < 0, pool.shape[0], dest_blocks)
    return pool.at[dest.reshape(b * chunks)].set(
        new.reshape((b * chunks,) + pool.shape[1:]).astype(pool.dtype),
        mode="drop")


def latent_attention_xla(q, pool, block_table, q_positions, rank: int,
                         scale: float):
    """q [B, Lq, H, W] at absolute `q_positions` [B, Lq] over the
    gathered rows of each sequence, the query at p seeing the rows at
    positions <= p (its own is written): float32, [B, Lq, H, rank]."""
    b, mb = block_table.shape
    rows = pool[jnp.maximum(block_table, 0)].reshape(
        b, mb * pool.shape[1], -1).astype(jnp.float32)
    scores = jnp.einsum("bqhw,bkw->bhqk", q.astype(jnp.float32),
                        rows) * scale
    seen = (jnp.arange(rows.shape[1])[None, None, :]
            <= q_positions[:, :, None])                     # [B, Lq, K]
    scores = jnp.where(seen[:, None], scores, jnp.finfo(jnp.float32).min)
    out = jnp.einsum("bhqk,bkr->bqhr", jax.nn.softmax(scores, axis=-1),
                     rows[..., :rank])
    return out.astype(q.dtype)


def latent_attention(q, pool, block_table, q_positions, *, rank: int,
                     scale: float):
    """Dispatcher, at trace time: the Pallas kernel for a decode step
    where `_latent_kernels_serve`, XLA's gather otherwise (the CPU; Lq >
    1: a chunk or a verify against the pool)."""
    use_kernel = q.shape[1] == 1 and _latent_kernels_serve(pool.shape[1])
    q = _pool_wide(q, pool)
    attention.log_dispatch(
        "pallas_latent" if use_kernel else "xla_latent", q=q.shape,
        pool=pool.shape, table=block_table.shape)
    if use_kernel:
        return latent_attention_tpu(q, pool, block_table,
                                    q_positions[:, 0] + 1, rank=rank,
                                    scale=scale)
    return latent_attention_xla(q, pool, block_table, q_positions, rank,
                                scale)
