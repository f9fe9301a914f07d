"""Ring attention: sequence-parallel attention over an ICI ring.

For contexts too long for one chip's HBM, the sequence axis shards over the
mesh's ``sp`` axis.  Each device holds its local Q/K/V block; K/V blocks
rotate around the ring with `lax.ppermute` while every device folds each
visiting block into a running online-softmax accumulator (same math as the
Pallas flash kernel, lifted to the mesh level).  After sp steps every query
has attended to the full sequence; communication overlaps compute because
each ppermute is issued before the block is consumed.

No reference counterpart exists (SURVEY.md §5.7 audits its absence); this is
the long-context requirement built TPU-first: collectives ride ICI, the
sequence never materializes on one device, and the whole thing jits inside
the engine's pjit program.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_NEG_INF = -1e30


def _ring_attention_local(q, k, v, kv_mask, axis_name: str, causal: bool):
    """Per-device body under shard_map.

    q, k, v: [B, L_local, H, D] local sequence blocks.
    kv_mask: [B, L_local] bool (True = real token) — rotates around the
        ring alongside its K/V block so padding never attends.
    The sp axis index orders blocks: device i holds positions
    [i*L_local, (i+1)*L_local).
    """
    sp = jax.lax.axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    B, Lq, H, D = q.shape
    scale = 1.0 / D ** 0.5
    qf = q.astype(jnp.float32) * scale

    def fold(carry, kv_block, block_idx):
        acc, m_prev, l_prev = carry
        kf, vf, mask_blk = kv_block
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf.astype(jnp.float32))
        Lk = kf.shape[1]
        if causal:
            q_pos = (my_idx * Lq
                     + jax.lax.broadcasted_iota(jnp.int32, (Lq, Lk), 0))
            k_pos = (block_idx * Lk
                     + jax.lax.broadcasted_iota(jnp.int32, (Lq, Lk), 1))
            s = jnp.where((q_pos >= k_pos)[None, None], s, _NEG_INF)
        # [B, Lk] -> [B, 1, 1, Lk]: mask padded keys in this block.
        s = jnp.where(mask_blk[:, None, None, :], s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)          # [B,H,Lq,1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, vf.astype(jnp.float32))
        acc_new = acc * alpha.transpose(0, 2, 1, 3) + pv
        return acc_new, m_new, l_new

    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def step(i, state):
        k_blk, v_blk, m_blk, acc, m, l = state
        # Block owner index walks backwards around the ring from my_idx.
        block_idx = (my_idx - i) % sp
        acc, m, l = fold((acc, m, l), (k_blk, v_blk, m_blk), block_idx)
        # Rotate for the next step (skipped result on the last iteration —
        # lax.fori_loop still issues it; cheap relative to the folds and
        # keeps the loop body uniform).
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        m_blk = jax.lax.ppermute(m_blk, axis_name, perm)
        return k_blk, v_blk, m_blk, acc, m, l

    acc0 = jnp.zeros((B, Lq, H, D), jnp.float32)
    m0 = jnp.full((B, H, Lq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Lq, 1), jnp.float32)
    _, _, _, acc, m, l = jax.lax.fori_loop(
        0, sp, step, (k, v, kv_mask, acc0, m0, l0))
    # Fully-masked query rows (padding) would divide by zero; clamp — their
    # outputs are sliced off / ignored downstream anyway.
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1, 3)
    return out.astype(q.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   mesh: Mesh, axis_name: str = "sp",
                   causal: bool = False,
                   kv_mask: Optional[jax.Array] = None,
                   batch_axis: Optional[str] = "dp") -> jax.Array:
    """Sequence-parallel attention over [B, L, H, D] with L sharded on
    `axis_name` (and optionally B on `batch_axis`).

    kv_mask: optional [B, L] bool/int padding mask (True = attend to that
    key position); it shards and rotates with the K/V blocks.

    Call inside or outside jit; inputs need not be pre-sharded (shard_map
    constraints will move them), but pre-sharded inputs avoid the reshard.
    """
    if q.shape[1] % mesh.shape[axis_name]:
        raise ValueError(
            f"sequence length {q.shape[1]} not divisible by mesh axis "
            f"{axis_name}={mesh.shape[axis_name]}")
    # Batch sharding is best-effort: module init traces with batch=1, which
    # can't split over dp — replicate batch in that case, shard otherwise.
    if batch_axis is not None and q.shape[0] % mesh.shape[batch_axis]:
        batch_axis = None
    if kv_mask is None:
        kv_mask = jnp.ones(q.shape[:2], jnp.bool_)
    else:
        kv_mask = kv_mask.astype(jnp.bool_)
    spec = P(batch_axis, axis_name, None, None)
    mask_spec = P(batch_axis, axis_name)
    fn = functools.partial(
        _ring_attention_local, axis_name=axis_name, causal=causal)
    sharded = jax.shard_map(fn, mesh=mesh,
                            in_specs=(spec, spec, spec, mask_spec),
                            out_specs=spec, check_vma=False)
    return sharded(q, k, v, kv_mask)


def ring_attention_sharded(mesh: Mesh, axis_name: str = "sp",
                           batch_axis: Optional[str] = "dp",
                           causal: bool = False):
    """Returns a jit-ready closure over the mesh in the model zoo's
    pluggable-attention calling convention (q, k, v, mask) where mask is a
    broadcastable [B, 1, 1, L] or [B, L] key-padding mask."""
    def attn(q, k, v, mask=None):
        if mask is not None and mask.ndim == 4:
            # [B, 1, 1, L] (BERT-style broadcast mask) -> [B, L]
            mask = mask[:, 0, 0, :]
        return ring_attention(q, k, v, mesh, axis_name=axis_name,
                              causal=causal, kv_mask=mask,
                              batch_axis=batch_axis)
    return attn
