"""Routed expert layer: top-k routing (two routers, one of them with or
without renormalised weights) and three ways through
the experts, chosen from shapes (and where the program runs) at trace time.

A mixture-of-experts MLP holds E experts and sends every token through the
k of them its router scores highest, weighted by the router's weights.  No
token is dropped and there is no capacity limit: every path computes the
(token, expert) pairs the router chose.  Two things vary by model and are
arguments of every path, not copies of it:

- the expert's form: gated, `down_e(silu(x·gate_e) ⊙ x·up_e)` (OLMoE,
  models/olmoe.py: three matrices), or plain, `down_e(relu(x·up_e)²)`
  (Nemotron-H, models/nemotron_h.py: two; `gate=None`);
- the experts held: all E (`first=None`), or the `up.shape[0]` of them that
  start at expert `first` (one chip's share under expert parallelism).  The
  router's ids run over all E; a pair whose expert is not held is computed
  by no path here (it is another chip's), like a pair of a token that is
  not `valid`.

- `experts_grouped`: routed work only, and of the routed pairs only the
  real ones: those of a `valid` token and a held expert.  The pairs are
  sorted by expert, the rest past the last group, the tokens gathered in
  that order, and a Pallas TPU kernel walks the (row tile, expert) pairs
  in which a tile of 256 sorted rows holds a row of the expert: the
  expert's matrices whole in VMEM and the next one's on their way (the
  walk of `experts_touched`, over rows), the tile through them, and the
  walk ends at the last real row, so padding and other chips' pairs cost
  a sort and a gathered row and no arithmetic.  Each token then sums its
  own k rows, times the router's weights, in float32.  Where the kernel
  does not serve (off the TPU, under a mesh, and at any shape it has no
  record at on the chip: `_grouped_kernel_serves`), two or three
  `jax.lax.ragged_dot`s take its place (on a v5e XLA's kernel takes 0.2
  ms for every group that has a row, however few: PERF.md, PR 32; on the
  CPU a masked dense form, which the tests use at toy size).  An expert
  no row chose is not read.  That list of shapes is a workaround for a
  hang of the chip that is not understood (PERF.md §7 N6), not a design:
  it goes, and the kernel serves every dispatch on a TPU, when the hang
  is.
- `experts_touched`: a Pallas TPU kernel for few tokens (a decode wave,
  a speculative verify).  It walks the list of experts some token chose
  and streams each one's three matrices through VMEM once, whole, the
  next expert's on their way while the MXU multiplies every token by
  the resident one (a token that did not choose it at weight zero).  It
  reads what the routing touched and nothing twice, so its time follows
  the touched bytes alone.
- `experts_streamed`: the same without a kernel, for every expert: one
  batched matmul over the expert axis streams all E experts' matrices
  once (1.17-1.25 ms a layer of 64 x 3 x 2048 x 1024 bfloat16 on a v5e
  for 8 to 256 tokens, against 0.98 ms at 819 GB/s), whatever the
  routing.  Where the kernel does not serve (no TPU, under a mesh).

`routed_experts` chooses at trace time (readings: PERF.md, PR 26).  Many
tokens (prefill, chunks): grouped, the only one that does routed work
alone.  Up to 256 tokens the weights' stream outlasts the arithmetic of
all tokens through every touched expert (T <= peak FLOP/s over peak
bytes/s, 240 on a v5e), so the kernel where it serves; elsewhere grouped
while routing can leave experts untouched (at most 3 pairs an expert: a
24-row wave of 8 choices over 64) and streamed in between, where XLA's
grouped kernel would read an expert once per row tile that holds its
rows (1.4-2.8 ms).

Scopes (`jax.named_scope`, so device operations in the profiler's trace
carry them): `moe.router`, `moe.dispatch`, `moe.experts`, `moe.combine`.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Above this many tokens the FLOPs of every token through every expert
# read (the kernel's and the streamed path's way) would outlast the
# stream of the experts' weights on the chips this serves (v5e: 197
# TFLOP/s over 819 GB/s = 240 tokens), so routed work is grouped.
STREAMED_MAX_TOKENS = 256
# Up to this many (token, expert) pairs an expert, routing leaves experts
# untouched (3 a piece: 5% of them if it is even), and the grouped kernel
# reads no more than the streamed path does.
GROUPED_MAX_PAIRS_PER_EXPERT = 3
# The grouped path's kernel multiplies tiles of this many sorted rows by
# the one expert they belong to: 256 rows of arithmetic last as long as
# the stream of the next expert's matrices (2 x 10.3 MB and 27 us for
# Nemotron's, 3 x 4.2 MB and 16 us for OLMoE's, on a v5e).
GROUPED_TILE_ROWS = 256
# What a kernel here may ask of a core's 128 MiB of VMEM (v5e): OLMoE's
# 3 x 4 MiB an expert, twice over, need 33 MiB.
KERNEL_MAX_VMEM_BYTES = 96 << 20
# The shapes at which the grouped kernel serves, in bfloat16: (sorted rows
# T x k, H, F, matrices an expert).  With the kernel at every row count
# the Nemotron-H prefill stopped the chip (4 of 20 benchmark runs, 1 in 27
# and 1 in 49 ramps of the server from idle; the oldest unfinished program
# a (2, 1024) prefill both times it was looked at, where XLA keeps the
# kernel's sorted rows in VMEM above the kernel's own 66 MB).  The cause
# is not known (PERF.md, PR 32, N6), and a hang takes the chip and raises
# nothing, so a shape is listed once it has run 100 such ramps on a v5e
# without a stall, and the kernel serves no other.  OLMoE's 4 rows of
# 1024, (4 * 1024 * 8, 2048, 1024, 3), has run 49 and waits for the rest.
GROUPED_KERNEL_PROVEN = frozenset({
    (4 * 1024 * 6, 2688, 1920, 2),  # Nemotron-H, 4 and 8 rows of 1024
    (8 * 1024 * 6, 2688, 1920, 2),
})


def route(logits: jax.Array, k: int, renormalise: bool = False):
    """Router probabilities.  logits [T, E] in any dtype; the softmax runs
    over all E in float32 and the k largest are kept with their
    probabilities as they are (OLMoE), or, with `renormalise`, divided by
    their sum so that a token's weights add up to 1 (`norm_topk_prob`:
    Mellum, the Qwen3-MoE family); ties go to the lowest index, as
    `lax.top_k`.  Returns (probs [T, k] float32, experts [T, k] int32)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top, experts = jax.lax.top_k(probs, k)
    if renormalise:
        top = top / top.sum(axis=-1, keepdims=True)
    return top, experts.astype(jnp.int32)


def route_sigmoid(logits: jax.Array, bias: jax.Array, k: int,
                  scale: float):
    """The DeepSeek-V3 router as Nemotron-H uses it (one group, so no
    group limit).  Scores s = sigmoid(logits) in float32 over all E; the k
    largest of s + bias are chosen (the bias steers the choice alone);
    their weights are scale · s_i / (Σ_chosen s + 1e-20).  Returns
    (weights [T, k] float32, experts [T, k] int32)."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    weights = scale * chosen / (chosen.sum(axis=-1, keepdims=True) + 1e-20)
    return weights, experts.astype(jnp.int32)


def _held(experts: jax.Array, count: int, first: Optional[int],
          valid: Optional[jax.Array]) -> jax.Array:
    """The router's ids as indices into the `count` experts held, and
    `count` (one past them) for a pair no path computes: its token is not
    valid, or its expert is not among those that start at `first`."""
    if first is None and valid is None:
        return experts
    keep = None if valid is None else valid[:, None]
    if first is not None:
        experts = experts - first
        mine = (experts >= 0) & (experts < count)
        keep = mine if keep is None else keep & mine
    return jnp.where(keep, experts, count)


def routed_pairs(experts: jax.Array, num_experts: int,
                 valid: Optional[jax.Array] = None,
                 first: Optional[int] = None) -> jax.Array:
    """[num_experts] int32: how many (token, expert) pairs each expert
    held was given."""
    experts = _held(experts, num_experts, first, valid)
    return jnp.zeros(num_experts, jnp.int32).at[experts.reshape(-1)].add(
        1, mode="drop")


def _activation(g: Optional[jax.Array], u: jax.Array) -> jax.Array:
    """float32: silu(g) ⊙ u of a gated expert, relu(u)² of a plain one."""
    u = u.astype(jnp.float32)
    if g is None:
        return jnp.square(jax.nn.relu(u))
    return jax.nn.silu(g.astype(jnp.float32)) * u


def experts_streamed(x, gate, up, down, probs, experts, valid=None,
                     first=None):
    """x [T, H]; gate (or None), up [E, H, F]; down [E, F, H]; probs,
    experts [T, k].  Every held expert on every token, weighted by the
    router's weight where the expert was chosen and by zero elsewhere."""
    t, e = x.shape[0], up.shape[0]
    with jax.named_scope("moe.dispatch"):
        weights = jnp.zeros((t, e), jnp.float32).at[
            jnp.arange(t)[:, None], _held(experts, e, first, valid)].add(
                probs, mode="drop")
    with jax.named_scope("moe.experts"):
        g = None if gate is None else jnp.einsum("th,ehf->etf", x, gate)
        u = jnp.einsum("th,ehf->etf", x, up)
        act = (_activation(g, u) * weights.T[:, :, None]).astype(x.dtype)
        return jnp.einsum("etf,efh->th", act, down)


def grouped_rows_offered(tokens: int, per_token: int) -> int:
    """Host side, for the counters: the (token, expert) rows a dispatch of
    `tokens` tokens (padding and all) offers the grouped path in every
    expert layer; 0 for so few tokens that `routed_experts` may take
    another path."""
    return tokens * per_token if tokens > STREAMED_MAX_TOKENS else 0


def grouped_rows_real(real):
    """Host side: `real` pairs (an int, or an array of them) in whole
    tiles of the kernel's: the sorted rows up to the last real one, which
    is the least a grouped matmul can visit and where either way here
    stops (XLA's grouped matmul ends at the last group too: PERF.md, PR
    32).  A count of what the traffic offered, not of what a kernel did:
    the kernel's walk visits a tile once for every expert with a row in
    it, up to tiles + experts - 1 visits."""
    return -(-real // GROUPED_TILE_ROWS) * GROUPED_TILE_ROWS


def experts_grouped(x, gate, up, down, probs, experts, valid=None,
                    first=None, interpret: bool = False):
    """The same sum over the real routed pairs only, n of the T x k: those
    of a valid token (valid: optional [T] bool) and a held expert.  The
    pairs are sorted by expert, the rest past the last group, and the
    experts' matmuls stop at row n, a value on the device
    (`_grouped_tiles`, the kernel, where `_grouped_kernel_serves` or
    `interpret` asks for its interpreter; else `_grouped_ragged`).  Each
    token sums its k rows, times the router's weights, in float32,
    rounded once; a token with no real pair gets a zero row.  With no
    `valid` and every expert held n = T x k."""
    t, k = experts.shape
    e = up.shape[0]
    matrices = [up, down] if gate is None else [gate, up, down]
    tiles = interpret or _grouped_kernel_serves(x, k, up, len(matrices))
    with jax.named_scope("moe.dispatch"):
        experts = _held(experts, e, first, valid)
        sizes = routed_pairs(experts, e)
        order = jnp.argsort(experts.reshape(-1), stable=True)
        # Whole tiles for the kernel; the rows added are past the last group.
        pad = -(t * k) % GROUPED_TILE_ROWS if tiles else 0
        rows = x[jnp.pad(order // k, (0, pad))]
    with jax.named_scope("moe.experts"):
        if tiles:
            out = _grouped_tiles(rows, matrices, sizes, interpret)
        else:
            out = _grouped_ragged(rows, matrices, sizes)
    with jax.named_scope("moe.combine"):
        # Where each token's k pairs lie in the sorted order; choice by
        # choice, a gather of [T, H] added to the sum.  A pair that is
        # not real lies past the last group; whatever a kernel left there
        # is replaced, not scaled.
        at = jnp.argsort(order).reshape(t, k)
        weights = jnp.where(experts < e, probs, 0.0)
        total = jnp.zeros(x.shape, jnp.float32)
        for j in range(k):
            w = weights[:, j, None]
            total += jnp.where(w != 0.0,
                               out[at[:, j]].astype(jnp.float32) * w, 0.0)
        return total.astype(x.dtype)


def _grouped_ragged(rows, matrices, sizes):
    """[R, H]: rows [R, H] sorted by expert, the first `sizes[0]` of them
    expert 0's and so on, each through its own expert, by XLA's grouped
    matmul.  Rows past the last group come back as its kernel leaves
    them."""
    gate, up, down = ([None] + matrices)[-3:]
    g = None if gate is None else jax.lax.ragged_dot(rows, gate, sizes)
    u = jax.lax.ragged_dot(rows, up, sizes)
    return jax.lax.ragged_dot(_activation(g, u).astype(rows.dtype), down,
                              sizes)


def _grouped_kernel(tile_ref, group_ref, lo_ref, hi_ref, count_ref, rows_ref,
                    *refs, gated: bool):
    """One grid step a (row tile, expert) pair of the walk: the expert's
    matrices are in VMEM (the next pair's on their way, unless it is the
    same expert), the tile's rows go through it, and those that are not
    this expert's add nothing to the tile's float32 sum, which is
    rounded and written when the walk leaves the tile."""
    gate_ref = refs[0] if gated else None
    up_ref, down_ref, o_ref, acc_ref = refs[-4:]
    i = pl.program_id(0)
    rows_per_tile = rows_ref.shape[0]

    @pl.when(i < count_ref[0])
    def _pair():
        tile, group = tile_ref[i], group_ref[i]

        @pl.when((i == 0) | (tile != tile_ref[jnp.maximum(i - 1, 0)]))
        def _enter():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = rows_ref[...]
        g = None if gate_ref is None else jnp.dot(
            x, gate_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, up_ref[0], preferred_element_type=jnp.float32)
        row = tile * rows_per_tile + jax.lax.broadcasted_iota(
            jnp.int32, (rows_per_tile, 1), 0)
        mine = (row >= lo_ref[group]) & (row < hi_ref[group])
        act = jnp.where(mine, _activation(g, u), 0.0).astype(x.dtype)
        acc_ref[...] += jnp.dot(act, down_ref[0],
                                preferred_element_type=jnp.float32)

        # Entries past the walk's end repeat its last pair.
        @pl.when((i == count_ref[0] - 1) | (tile != tile_ref[i + 1]))
        def _leave():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    @pl.when((i == 0) & (count_ref[0] == 0))
    def _no_pair_at_all():  # the first tile is written back all the same
        o_ref[...] = rows_ref[...]


def _grouped_tiles(rows, matrices, sizes, interpret: bool = False):
    """[R, H]: rows [R, H] sorted by expert (R whole tiles), the first
    `sizes[0]` of them expert 0's and so on, each through its own
    expert, as a Pallas TPU kernel.  Its grid walks the (row tile,
    expert) pairs in which a tile holds a row of the expert, by expert
    and so by tile (scalar prefetch: the walk picks the blocks): a tile
    that holds three experts' rows is visited three times, an expert
    whose rows lie in two tiles twice with its matrices read once.
    Entries past the walk's end re-address its last pair (no copy) and
    skip the arithmetic; the result takes the rows' place in memory, and
    a tile past the last group's last row is not visited: its rows come
    back as they went in."""
    r, h = rows.shape
    e, _, f = matrices[-2].shape
    rows_per_tile = GROUPED_TILE_ROWS
    tiles = r // rows_per_tile
    # From one pair to the next the walk moves a tile on, or an expert on.
    steps = tiles + e - 1
    hi = jnp.cumsum(sizes.astype(jnp.int32))
    lo = hi - sizes
    first_tile = lo // rows_per_tile
    visits = jnp.where(hi > lo, (hi - 1) // rows_per_tile - first_tile + 1, 0)
    stops = jnp.cumsum(visits)
    count = stops[-1]
    i = jnp.minimum(jnp.arange(steps + 1), jnp.maximum(count - 1, 0))
    group = jnp.minimum(jnp.sum(stops[None, :] <= i[:, None], axis=1),
                        e - 1).astype(jnp.int32)
    tile = jnp.clip(first_tile[group] + i - (stops[group] - visits[group]),
                    0, tiles - 1).astype(jnp.int32)

    def tile_block(i, tile, group, lo, hi, count):
        return (tile[i], 0)

    def expert_block(i, tile, group, lo, hi, count):
        return (group[i], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(steps,),
        in_specs=[pl.BlockSpec((rows_per_tile, h), tile_block)]
        + [pl.BlockSpec((1,) + m.shape[1:], expert_block) for m in matrices],
        out_specs=pl.BlockSpec((rows_per_tile, h), tile_block),
        scratch_shapes=[pltpu.VMEM((rows_per_tile, h), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_grouped_kernel, gated=len(matrices) == 3),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, h), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_kernel_vmem_bytes(
                rows_per_tile, h, f, rows.dtype, len(matrices))),
        # A tile's rows are in VMEM before its result is written.
        input_output_aliases={5: 0},
        name="moe_experts_grouped", interpret=interpret,
    )(tile, group, lo, hi, count[None], rows, *matrices)


def _touched_kernel(ids_ref, count_ref, x_ref, w_ref, *refs, gated: bool):
    """One grid step an entry of the touched list: this expert's
    matrices (gate, up, down; or up, down of a plain expert) are in VMEM
    (the next entry's are on their way), every token goes through it,
    and the router's weight (zero for a token that did not choose it)
    scales what it adds."""
    gate_ref = refs[0] if gated else None
    up_ref, down_ref, o_ref, acc_ref = refs[-4:]
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < count_ref[0])
    def _expert():
        x = x_ref[...]
        g = None if gate_ref is None else jnp.dot(
            x, gate_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, up_ref[0], preferred_element_type=jnp.float32)
        act = (_activation(g, u) * w_ref[0]).astype(x.dtype)
        acc_ref[...] += jnp.dot(act, down_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(0) - 1)
    def _out():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def experts_touched(x, gate, up, down, probs, experts, valid=None,
                    first=None, interpret: bool = False):
    """The same sum for few tokens, as a Pallas TPU kernel that reads
    each *touched* held expert's matrices once and no other's: the
    grid walks the list of experts some token chose (scalar prefetch:
    the list picks the blocks), whole matrices are double-buffered
    through VMEM while the MXU multiplies all T tokens by the resident
    expert, and entries past the list's end re-address the last block
    (no copy) and skip the arithmetic.  Time follows the touched bytes
    alone, whatever the routing; T x touched experts of arithmetic hides
    under the stream as `experts_streamed`'s does."""
    t, k = experts.shape
    e, h, f = up.shape
    matrices = [up, down] if gate is None else [gate, up, down]
    with jax.named_scope("moe.dispatch"):
        experts = _held(experts, e, first, valid)
        weights = jnp.zeros((t, e), jnp.float32).at[
            jnp.arange(t)[:, None], experts].add(probs, mode="drop")
        touched = jnp.zeros((e,), jnp.bool_).at[experts.reshape(-1)].set(
            True, mode="drop")
        count = jnp.sum(touched, dtype=jnp.int32)
        first = jnp.argsort(~touched, stable=True).astype(jnp.int32)
        ids = jnp.where(jnp.arange(e) < count, first,
                        first[jnp.maximum(count - 1, 0)])
        # Whole sublane tiles of tokens (bfloat16: 16 rows).
        rows = -(-t // 16) * 16
        x_rows = jnp.pad(x, ((0, rows - t), (0, 0)))
        w_rows = jnp.pad(weights, ((0, rows - t), (0, 0))).T[:, :, None]

    def expert_block(j, ids, count):
        return (ids[j], 0, 0)

    def whole(j, ids, count):
        return (0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(e,),
        in_specs=[
            pl.BlockSpec((rows, h), whole),
            pl.BlockSpec((1, rows, 1), expert_block),
        ] + [pl.BlockSpec((1,) + m.shape[1:], expert_block)
             for m in matrices],
        out_specs=pl.BlockSpec((rows, h), whole),
        scratch_shapes=[pltpu.VMEM((rows, h), jnp.float32)],
    )
    with jax.named_scope("moe.experts"):
        out = pl.pallas_call(
            functools.partial(_touched_kernel, gated=gate is not None),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((rows, h), x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_kernel_vmem_bytes(
                    rows, h, f, up.dtype, len(matrices))),
            name="moe_experts_touched", interpret=interpret,
        )(ids, count[None], x_rows, w_rows, *matrices)
    return out[:t]


def _kernel_vmem_bytes(rows: int, h: int, f: int, dtype,
                       matrices: int = 3) -> int:
    """An expert's matrices, two buffers each, the tokens in and out,
    the float32 sum and the activations, and room to spare."""
    item = jnp.dtype(dtype).itemsize
    return (2 * matrices * h * f * item + 4 * rows * h * item
            + 4 * rows * h + 16 * rows * f + (8 << 20))


def _kernel_serves(rows: int, up, matrices: int = 3) -> bool:
    """The gate of both Pallas kernels, read at trace time: a TPU, no
    ambient mesh (a Mosaic kernel is not partitioned automatically; under
    `tp` the XLA paths split the expert width), lane-aligned widths (a
    model whose expert width is not one stores its matrices padded with
    zeros: models/nemotron_h.py, 1856 as 1920), and an expert's matrices
    twice over beside `rows` rows within the chip's VMEM."""
    from kfserving_tpu.ops.attention import _tpu_backend

    _, h, f = up.shape
    return (_tpu_backend() and jax.sharding.get_abstract_mesh().empty
            and h % 128 == 0 and f % 128 == 0
            and _kernel_vmem_bytes(rows, h, f, up.dtype, matrices)
            <= KERNEL_MAX_VMEM_BYTES)


def _grouped_kernel_serves(x, k: int, up, matrices: int) -> bool:
    """The grouped kernel's gate: `_kernel_serves`, and a shape that has
    run on the chip under traffic (`GROUPED_KERNEL_PROVEN`: a workaround
    for a hang that is not understood, not a design).  Any other
    dispatch goes by `_grouped_ragged`, which has no stall on record."""
    _, h, f = up.shape
    return (_kernel_serves(GROUPED_TILE_ROWS, up, matrices)
            and x.dtype == jnp.bfloat16
            and (x.shape[0] * k, h, f, matrices) in GROUPED_KERNEL_PROVEN)


def routed_experts(x, gate, up, down, probs, experts, valid=None,
                   first=None):
    """Σ_k p_k · expert_k(x) over the held experts for x [T, H], by the
    path that fits T (a static shape) and where it runs."""
    pairs = x.shape[0] * experts.shape[1]
    if x.shape[0] <= STREAMED_MAX_TOKENS:
        if _kernel_serves(x.shape[0] + 16, up, 2 if gate is None else 3):
            return experts_touched(x, gate, up, down, probs, experts, valid,
                                   first)
        # Under a share the pairs that land here are not known at trace
        # time; what is, is that the held experts are all read anyway.
        if first is not None \
                or pairs > GROUPED_MAX_PAIRS_PER_EXPERT * up.shape[0]:
            return experts_streamed(x, gate, up, down, probs, experts,
                                    valid, first)
    return experts_grouped(x, gate, up, down, probs, experts, valid, first)
