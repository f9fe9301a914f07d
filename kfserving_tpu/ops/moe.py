"""Routed expert layer: top-k routing (two routers) and three ways through
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

- `experts_grouped`: routed work only.  The pairs are sorted by expert,
  the tokens gathered in that order, and three `jax.lax.ragged_dot`s
  multiply each group of rows by its own expert's matrix.  XLA lowers
  `ragged_dot` on TPU to its own grouped-matmul kernel (a
  `tpu_custom_call` over row tiles and the groups in them) and on the
  CPU to a masked dense form, which the tests use at toy size.  An
  expert no row chose is not read; tokens marked not `valid` (bucket
  padding) are given to no expert: they sort past the last group and
  their rows come back zero.
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
# What `experts_touched` may ask of VMEM (a v5e core has 128 MiB): OLMoE's
# 3 x 4 MiB an expert, twice over, need 33 MiB.
TOUCHED_MAX_VMEM_BYTES = 96 << 20


def route(logits: jax.Array, k: int):
    """Router probabilities.  logits [T, E] in any dtype; the softmax runs
    over all E in float32 and the k largest are kept with their
    probabilities as they are (not renormalised; ties go to the lowest
    index, as `lax.top_k`).  Returns (probs [T, k] float32, experts
    [T, k] int32)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top, experts = jax.lax.top_k(probs, k)
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


def experts_grouped(x, gate, up, down, probs, experts, valid=None,
                    first=None):
    """The same sum over routed pairs only: rows sorted by expert, one
    grouped matmul per matrix.  valid: optional [T] bool; a token that
    is not valid is routed to no expert and gets a zero row.  Pairs of
    experts not held sort past the last group beside theirs."""
    t, k = experts.shape
    e = up.shape[0]
    with jax.named_scope("moe.dispatch"):
        experts = _held(experts, e, first, valid)
        sizes = routed_pairs(experts, e)
        order = jnp.argsort(experts.reshape(-1), stable=True)
        rows = x[order // k]
    with jax.named_scope("moe.experts"):
        g = None if gate is None else jax.lax.ragged_dot(rows, gate, sizes)
        u = jax.lax.ragged_dot(rows, up, sizes)
        out = jax.lax.ragged_dot(_activation(g, u).astype(x.dtype), down,
                                 sizes)
    with jax.named_scope("moe.combine"):
        # Rows past the last group belong to no expert; whatever the
        # kernel left there is replaced, not scaled.
        routed = jnp.arange(t * k) < jnp.sum(sizes)
        out = jnp.where(routed[:, None],
                        out.astype(jnp.float32)
                        * probs.reshape(-1)[order][:, None],
                        0.0).astype(x.dtype)
        back = jnp.argsort(order)
        return out[back].reshape(t, k, -1).sum(
            axis=1, dtype=jnp.float32).astype(x.dtype)


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
                vmem_limit_bytes=_touched_vmem_bytes(
                    rows, h, f, up.dtype, len(matrices))),
            name="moe_experts_touched", interpret=interpret,
        )(ids, count[None], x_rows, w_rows, *matrices)
    return out[:t]


def _touched_vmem_bytes(rows: int, h: int, f: int, dtype,
                        matrices: int = 3) -> int:
    """An expert's matrices, two buffers each, the tokens in and out,
    the float32 sum and the activations, and room to spare."""
    item = jnp.dtype(dtype).itemsize
    return (2 * matrices * h * f * item + 4 * rows * h * item
            + 4 * rows * h + 16 * rows * f + (8 << 20))


def _touched_kernel_serves(x, up, matrices: int = 3) -> bool:
    """The Pallas kernel's gate, read at trace time: a TPU, no ambient
    mesh (a Mosaic kernel is not partitioned automatically; under `tp`
    the XLA paths split the expert width), lane-aligned widths (a model
    whose expert width is not one stores its matrices padded with zeros:
    models/nemotron_h.py, 1856 as 1920), and an expert's matrices twice
    over within the chip's VMEM."""
    from kfserving_tpu.ops.attention import _tpu_backend

    _, h, f = up.shape
    return (_tpu_backend() and jax.sharding.get_abstract_mesh().empty
            and h % 128 == 0 and f % 128 == 0
            and _touched_vmem_bytes(x.shape[0] + 16, h, f, up.dtype,
                                    matrices) <= TOUCHED_MAX_VMEM_BYTES)


def routed_experts(x, gate, up, down, probs, experts, valid=None,
                   first=None):
    """Σ_k p_k · expert_k(x) over the held experts for x [T, H], by the
    path that fits T (a static shape) and where it runs."""
    pairs = x.shape[0] * experts.shape[1]
    if x.shape[0] <= STREAMED_MAX_TOKENS:
        if _touched_kernel_serves(x, up, 2 if gate is None else 3):
            return experts_touched(x, gate, up, down, probs, experts, valid,
                                   first)
        # Under a share the pairs that land here are not known at trace
        # time; what is, is that the held experts are all read anyway.
        if first is not None \
                or pairs > GROUPED_MAX_PAIRS_PER_EXPERT * up.shape[0]:
            return experts_streamed(x, gate, up, down, probs, experts,
                                    valid, first)
    return experts_grouped(x, gate, up, down, probs, experts, valid, first)
