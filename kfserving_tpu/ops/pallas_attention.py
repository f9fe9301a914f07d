"""Flash attention as a Pallas TPU kernel.

Canonical online-softmax formulation (Dao et al.) tiled for the TPU memory
hierarchy: the grid walks (batch*heads, q_blocks, k_blocks) with the k axis
innermost and sequential, keeping the running max / normalizer / output
accumulator for one q tile resident in VMEM scratch.  The [L, L] score
matrix never exists in HBM, which is the whole point — at the serving
sequence lengths BASELINE.json config #3 targets the score tensor is what
turns attention HBM-bandwidth-bound.

Layout contract matches kfserving_tpu.ops.attention: [B, L, H, D] in, same
out; the values may have a width of their own, [B, L, H, Dv], which is then
the output's (latent attention's expanded prefill: keys of 192, values of
128, scores scaled by the keys' width).  D must be a multiple of 64 (64
pads the 128-lane width but measured 34 TF/s on v5e; attention.py gates
eligibility); L needs a power-of-two
block divisor >= 8 — block sizes adapt downward (512/256/.../8) to divide
any such L, so every legal seq bucket keeps the flash path (128-multiples
get full-width blocks; smaller divisors trade MXU efficiency for
coverage).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512
_NEG_INF = -1e30


def _fit_block(block: int, length: int) -> Optional[int]:
    """Largest candidate block (<= requested) dividing `length`, or
    None when no power-of-two >= 8 divides it — the caller raises the
    documented error rather than launching the kernel with an unaligned
    block (Mosaic mis-lowers those)."""
    for b in (block, 512, 256, 128, 64, 32, 16, 8):
        if b <= block and length % b == 0:
            return b
    return None


def _first_block(q_idx, block_q: int, block_k: int, window: Optional[int]):
    """The first key block that holds a key the query block's first query
    sees: 0 without a window."""
    if window is None:
        return 0
    return jnp.maximum(q_idx * block_q - window + 1, 0) // block_k


def _flash_kernel(*refs,
                  causal: bool, scale: float, block_q: int, block_k: int,
                  has_lengths: bool, window: Optional[int] = None):
    if has_lengths:
        # Scalar-prefetch layout: the lengths vector precedes the
        # tensor refs (PrefetchScalarGridSpec).
        len_ref, q_ref, k_ref, v_ref, o_ref, \
            m_scratch, l_scratch, acc_scratch = refs
    else:
        len_ref = None
        q_ref, k_ref, v_ref, o_ref, \
            m_scratch, l_scratch, acc_scratch = refs
    bh_idx = pl.program_id(0)
    q_idx = pl.program_id(1)
    k_step = pl.program_id(2)
    num_k = pl.num_programs(2)
    # With a window the k axis walks the band of key blocks this query
    # block can see, from `_first_block` on; without one it walks them all.
    k_idx = k_step + _first_block(q_idx, block_q, block_k, window)
    row_len = len_ref[bh_idx] if has_lengths else None

    @pl.when(k_step == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, _NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    def _run_block():
        # Dots take the inputs' native (bf16) dtype — the MXU multiplies
        # bf16 at full rate with fp32 accumulation; upcasting first
        # halves throughput.  Stats/accumulator stay fp32.
        q = q_ref[0]                                      # [bq, d]
        k = k_ref[0]                                      # [bk, d]
        s = jax.lax.dot_general(                          # [bq, bk] fp32
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal or has_lengths:
            k_pos = k_idx * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
        if causal:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
            if window is not None:
                # Sliding window: a query sees the `window` latest keys,
                # its own among them.
                s = jnp.where(k_pos > q_pos - window, s, _NEG_INF)
        if has_lengths:
            # Key-padding: keys at positions >= this batch row's real
            # length never contribute (suffix padding from the serving
            # batcher's seq buckets).
            s = jnp.where(k_pos < row_len, s, _NEG_INF)

        m_prev = m_scratch[:]                             # [bq, 1]
        l_prev = l_scratch[:]
        m_cur = jnp.max(s, axis=-1, keepdims=True)        # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                            # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)                   # [bq, 1]
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)

        v = v_ref[0]                                      # [bk, d]
        pv = jax.lax.dot_general(                         # p rides bf16
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scratch[:] = acc_scratch[:] * alpha + pv
        m_scratch[:] = m_new
        l_scratch[:] = l_new

    pred = None
    if causal:
        # Skip fully-masked k blocks above the diagonal.
        pred = k_idx * block_k <= q_idx * block_q + (block_q - 1)
        if window is not None:
            # ... and those wholly before the first query's window.
            pred &= (k_idx + 1) * block_k - 1 > q_idx * block_q - window
    if has_lengths:
        # Skip k blocks entirely beyond this row's length (dynamic
        # predicate — pl.when accepts traced conditions).
        beyond = k_idx * block_k < row_len
        pred = beyond if pred is None else (pred & beyond)
    if pred is None:
        _run_block()
    else:
        pl.when(pred)(_run_block)

    @pl.when(k_step == num_k - 1)
    def _finalize():
        # max() guards rows with length 0 (batch-dim padding): 0/eps
        # instead of 0/0 NaN; those rows are sliced away by the caller.
        o_ref[0] = (acc_scratch[:]
                    / jnp.maximum(l_scratch[:], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "window"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    kv_lengths: "jax.Array | None" = None,
                    window: Optional[int] = None) -> jax.Array:
    """Fused attention over [B, L, H, D]; returns [B, L, H, D].

    kv_lengths: optional int32 [B] — per-row count of real keys (suffix
    padding beyond is masked inside the kernel, and fully-padded k
    blocks are skipped).  This is what lets the serving path's
    seq-bucket padding ride the flash kernel instead of falling back
    to XLA with a materialized mask.

    window: with `causal`, a query at t sees keys s with t - window < s
    <= t; the grid's k axis is then the band of key blocks a query block
    can see (block_q + window - 1 keys), not the sequence's.
    """
    assert window is None or causal, "a window is a causal band"
    B, Lq, H, D = q.shape
    Lk, Dv = k.shape[1], v.shape[3]
    # Blocks shrink to the largest power-of-two divisor <= the requested
    # size, so L=640 runs with 128-blocks instead of losing the kernel.
    block_q = _fit_block(block_q, Lq)
    block_k = _fit_block(block_k, Lk)
    if block_q is None or block_k is None:
        raise ValueError(
            f"seq lens ({Lq}, {Lk}) need a power-of-two block divisor "
            ">= 8; pad sequences to a multiple of 8")
    scale = 1.0 / D ** 0.5

    # Fold heads into the grid's first axis: BHLD views with one (b,h) slab
    # per program keeps BlockSpecs 3-D and index maps trivial.
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, Lq, D)
    kt = k.transpose(0, 2, 1, 3).reshape(B * H, Lk, D)
    vt = v.transpose(0, 2, 1, 3).reshape(B * H, Lk, Dv)

    num_k = Lk // block_k
    if window is not None:
        # A query block sees block_q + window - 1 keys: that many blocks
        # at most, however long the sequence.
        num_k = min(num_k, (block_q + window - 2) // block_k + 2)
    grid = (B * H, Lq // block_q, num_k)
    has_lengths = kv_lengths is not None

    def seen(i, j, last):
        """The key block of grid step (i, j), held inside what query block
        i sees: a step outside it asks for a block it already has, which
        the pipeline does not copy again (the kernel skips its work)."""
        if causal:
            last = jnp.minimum(last, (i * block_q + block_q - 1) // block_k)
        return jnp.clip(j + _first_block(i, block_q, block_k, window),
                        0, last)

    kernel = functools.partial(
        _flash_kernel, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, has_lengths=has_lengths,
        window=window)
    scratch_shapes = [
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, Dv), jnp.float32),
    ]
    out_shape = jax.ShapeDtypeStruct((B * H, Lq, Dv), q.dtype)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    if has_lengths:
        # Lengths ride as a prefetched scalar vector so the k/v index
        # maps can CLAMP their block index: grid steps beyond a row's
        # last real block re-request the same block, which Mosaic's
        # pipeline elides — short rows in long buckets skip the HBM
        # traffic, not just the FLOPs (the pl.when below only skips
        # compute).
        lengths_bh = jnp.repeat(kv_lengths.astype(jnp.int32), H)

        def kv_index(bh, i, j, lens):
            # index_map signature: (*grid_indices, *scalar_refs)
            last = jnp.maximum(
                (lens[bh] + block_k - 1) // block_k - 1, 0)
            return (bh, seen(i, j, last), 0)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, D),
                             lambda bh, i, j, lens: (bh, i, 0)),
                pl.BlockSpec((1, block_k, D), kv_index),
                pl.BlockSpec((1, block_k, Dv), kv_index),
            ],
            out_specs=pl.BlockSpec(
                (1, block_q, Dv), lambda bh, i, j, lens: (bh, i, 0)),
            scratch_shapes=scratch_shapes,
        )
        out = pl.pallas_call(
            kernel, grid_spec=grid_spec, out_shape=out_shape,
            compiler_params=params,
        )(lengths_bh, qt, kt, vt)
    else:
        def kv_block(bh, i, j):
            return (bh, seen(i, j, Lk // block_k - 1) if causal else j, 0)

        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda bh, i, j: (bh, i, 0)),
                pl.BlockSpec((1, block_k, D), kv_block),
                pl.BlockSpec((1, block_k, Dv), kv_block),
            ],
            out_specs=pl.BlockSpec((1, block_q, Dv),
                                   lambda bh, i, j: (bh, i, 0)),
            out_shape=out_shape,
            scratch_shapes=scratch_shapes,
            compiler_params=params,
        )(qt, kt, vt)
    return out.reshape(B, H, Lq, Dv).transpose(0, 2, 1, 3)
