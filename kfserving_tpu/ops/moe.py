"""Routed expert layer: softmax top-k routing and three ways through the
experts, chosen from shapes (and where the program runs) at trace time.

A mixture-of-experts MLP (OLMoE, models/olmoe.py) holds E gated MLPs
`down_e(silu(x·gate_e) ⊙ x·up_e)` and sends every token through the k of
them its router scores highest, weighted by the router's probabilities.
No token is dropped and there is no capacity limit: every path computes
the k·T (token, expert) pairs the router chose.

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


def routed_pairs(experts: jax.Array, num_experts: int,
                 valid: Optional[jax.Array] = None) -> jax.Array:
    """[E] int32: how many (token, expert) pairs each expert was given."""
    if valid is not None:
        experts = jnp.where(valid[:, None], experts, num_experts)
    return jnp.zeros(num_experts, jnp.int32).at[experts.reshape(-1)].add(
        1, mode="drop")


def _gated(g: jax.Array, u: jax.Array) -> jax.Array:
    return jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)


def experts_streamed(x, gate, up, down, probs, experts):
    """x [T, H]; gate, up [E, H, F]; down [E, F, H]; probs, experts
    [T, k].  Every expert on every token, weighted by the router's
    probability where the expert was chosen and by zero elsewhere."""
    t, e = x.shape[0], gate.shape[0]
    with jax.named_scope("moe.dispatch"):
        weights = jnp.zeros((t, e), jnp.float32).at[
            jnp.arange(t)[:, None], experts].add(probs)
    with jax.named_scope("moe.experts"):
        g = jnp.einsum("th,ehf->etf", x, gate)
        u = jnp.einsum("th,ehf->etf", x, up)
        act = (_gated(g, u) * weights.T[:, :, None]).astype(x.dtype)
        return jnp.einsum("etf,efh->th", act, down)


def experts_grouped(x, gate, up, down, probs, experts, valid=None):
    """The same sum over routed pairs only: rows sorted by expert, one
    grouped matmul per matrix.  valid: optional [T] bool; a token that
    is not valid is routed to no expert and gets a zero row."""
    t, k = experts.shape
    e = gate.shape[0]
    with jax.named_scope("moe.dispatch"):
        sizes = routed_pairs(experts, e, valid)
        if valid is not None:
            experts = jnp.where(valid[:, None], experts, e)
        order = jnp.argsort(experts.reshape(-1), stable=True)
        rows = x[order // k]
    with jax.named_scope("moe.experts"):
        g = jax.lax.ragged_dot(rows, gate, sizes)
        u = jax.lax.ragged_dot(rows, up, sizes)
        out = jax.lax.ragged_dot(_gated(g, u).astype(x.dtype), down, sizes)
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


def _touched_kernel(ids_ref, count_ref, x_ref, w_ref, gate_ref, up_ref,
                    down_ref, o_ref, acc_ref):
    """One grid step an entry of the touched list: this expert's three
    matrices are in VMEM (the next entry's are on their way), every
    token goes through it, and the router's weight (zero for a token
    that did not choose it) scales what it adds."""
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < count_ref[0])
    def _expert():
        x = x_ref[...]
        g = jnp.dot(x, gate_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, up_ref[0], preferred_element_type=jnp.float32)
        act = (jax.nn.silu(g) * u * w_ref[0]).astype(x.dtype)
        acc_ref[...] += jnp.dot(act, down_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(0) - 1)
    def _out():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def experts_touched(x, gate, up, down, probs, experts, valid=None,
                    interpret: bool = False):
    """The same sum for few tokens, as a Pallas TPU kernel that reads
    each *touched* expert's three matrices once and no other's: the
    grid walks the list of experts some token chose (scalar prefetch:
    the list picks the blocks), whole matrices are double-buffered
    through VMEM while the MXU multiplies all T tokens by the resident
    expert, and entries past the list's end re-address the last block
    (no copy) and skip the arithmetic.  Time follows the touched bytes
    alone, whatever the routing; T x touched experts of arithmetic hides
    under the stream as `experts_streamed`'s does."""
    t, k = experts.shape
    e, h, f = gate.shape
    with jax.named_scope("moe.dispatch"):
        if valid is not None:
            experts = jnp.where(valid[:, None], experts, e)
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
            pl.BlockSpec((1, h, f), expert_block),
            pl.BlockSpec((1, h, f), expert_block),
            pl.BlockSpec((1, f, h), expert_block),
        ],
        out_specs=pl.BlockSpec((rows, h), whole),
        scratch_shapes=[pltpu.VMEM((rows, h), jnp.float32)],
    )
    with jax.named_scope("moe.experts"):
        out = pl.pallas_call(
            _touched_kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((rows, h), x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_touched_vmem_bytes(rows, h, f,
                                                     gate.dtype)),
            name="moe_experts_touched", interpret=interpret,
        )(ids, count[None], x_rows, w_rows, gate, up, down)
    return out[:t]


def _touched_vmem_bytes(rows: int, h: int, f: int, dtype) -> int:
    """Three matrices an expert, two buffers each, the tokens in and
    out, the float32 sum and the gated activations, and room to spare."""
    item = jnp.dtype(dtype).itemsize
    return (6 * h * f * item + 4 * rows * h * item + 4 * rows * h
            + 16 * rows * f + (8 << 20))


def _touched_kernel_serves(x, gate) -> bool:
    """The Pallas kernel's gate, read at trace time: a TPU, no ambient
    mesh (a Mosaic kernel is not partitioned automatically; under `tp`
    the XLA paths split the expert width), lane-aligned widths, and an
    expert's matrices twice over within the chip's VMEM."""
    from kfserving_tpu.ops.attention import _tpu_backend

    _, h, f = gate.shape
    return (_tpu_backend() and jax.sharding.get_abstract_mesh().empty
            and h % 128 == 0 and f % 128 == 0
            and _touched_vmem_bytes(x.shape[0] + 16, h, f, gate.dtype)
            <= TOUCHED_MAX_VMEM_BYTES)


def routed_experts(x, gate, up, down, probs, experts, valid=None):
    """Σ_k p_k · down_k(silu(x·gate_k) ⊙ x·up_k) for x [T, H], by the
    path that fits T (a static shape) and where it runs."""
    pairs = x.shape[0] * experts.shape[1]
    if x.shape[0] <= STREAMED_MAX_TOKENS:
        if _touched_kernel_serves(x, gate):
            return experts_touched(x, gate, up, down, probs, experts, valid)
        if pairs > GROUPED_MAX_PAIRS_PER_EXPERT * gate.shape[0]:
            if valid is not None:
                probs = jnp.where(valid[:, None], probs, 0.0)
            return experts_streamed(x, gate, up, down, probs, experts)
    return experts_grouped(x, gate, up, down, probs, experts, valid)
