"""Multi-head attention dispatch: Pallas flash kernel on TPU, XLA otherwise.

One public entry point, `dot_product_attention(q, k, v, mask=None)`, with
shape [batch, len, heads, head_dim] (BLHD — flax linen convention).  On TPU
backends with seq-len and head_dim meeting the kernel's tiling constraints it
runs the fused Pallas kernel (kfserving_tpu/ops/pallas_attention.py);
otherwise it lowers to the standard einsum formulation, which XLA fuses well
on its own for short sequences.  The choice is made from shapes and the
backend at trace time and logged once per distinct program; a kernel the
dispatcher chose either runs or the request fails.

The kernel exists for the long-sequence serving configs (BERT seq-bucketed
batching, BASELINE.json config #3): at seq >= 1024 the materialized
[B, H, L, L] score tensor becomes HBM-bandwidth-bound; the flash formulation
keeps the running softmax in VMEM.
"""

import functools
import logging
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

logger = logging.getLogger("kfserving_tpu.ops")

# Pallas TPU kernels need the lane dimension (head_dim) to be a multiple of
# 128 and benefit only past a sequence length that depends on lane fill.
# Measured on v5e (fori-chain device timing, B=8 H=12 D=64, 90%-full
# suffix padding): at L=512 XLA is 3.1x FASTER than the kernel (0.13 vs
# 0.42 ms/step — a half-lane head dim wastes the MXU and XLA's fused
# softmax is excellent while the score tensor is small); the kernel wins
# from L~1024 (1.5x) and dominates at long context (57x at L=8192 where
# XLA materializes [B,H,L,L] scores).
_FLASH_MIN_SEQ = 512        # full-lane head dims (D % 128 == 0)
_FLASH_MIN_SEQ_HALF_LANE = 1024  # D % 128 != 0 pads the lane width
# Head dims in multiples of 64 are flash-eligible: D=64 pads the
# 128-lane width but measured 34 TF/s on v5e; smaller head dims waste
# more than half the array and fall back to XLA.
_FLASH_HEAD_DIM_MULTIPLE = 64
# A causal prefill over a padded bucket (kv_lengths) or with a sliding
# window goes to XLA below this length, as it always has, and to the
# kernel from it on: the [B, H, L, L] float32 scores XLA materializes
# are 0.5 GB a row of 32 heads at 2048 and 8.6 GB at 8192, which no
# chip holds.  Under a causal mask a real query never sees the padding
# behind it, so the kernel's causal and length masks together say what
# the derived mask says on every row that is read.
_FLASH_CAUSAL_MASKED_MIN_SEQ = 2048


def masked_prefill_takes_xla(length: int) -> bool:
    """A causal prefill of `length` positions under a padding mask is
    served by XLA's attention on every backend and for every head size:
    what a prefill that needs another mask than the kernel's two (one
    row carrying several prompts, engine/programs.py) may count on."""
    return length < _FLASH_CAUSAL_MASKED_MIN_SEQ


def _xla_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   mask: Optional[jax.Array]) -> jax.Array:
    """Reference einsum attention in BLHD layout; XLA fuses scale+bias+softmax
    into the two MXU matmuls for short sequences."""
    depth = q.shape[-1]
    scale = jnp.asarray(1.0 / depth ** 0.5, q.dtype)
    # [B, H, Lq, Lk]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
    if mask is not None:
        big_neg = jnp.finfo(scores.dtype).min
        scores = jnp.where(mask, scores, big_neg)
    weights = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    weights = weights.astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


@functools.lru_cache(maxsize=1)
def _tpu_backend() -> bool:
    return jax.default_backend() == "tpu"


@functools.lru_cache(maxsize=None)
def log_dispatch(path: str, **shapes) -> None:
    """One INFO line per distinct (path, shapes) program: which
    attention formulation a traced program contains.  The dispatchers
    run at trace time, so this is the record of what was compiled."""
    logger.info("attention path=%s %s", path,
                " ".join(f"{k}={v}" for k, v in shapes.items()))


def mesh_axis(mesh, name: str, dim: int) -> Optional[str]:
    """`name` when the ambient mesh can split `dim` over it."""
    if name in mesh.axis_names and dim % mesh.shape[name] == 0:
        return name
    return None


def _flash(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
           lengths: Optional[jax.Array],
           window: Optional[int] = None) -> jax.Array:
    """The Pallas flash kernel, under `shard_map` when the caller runs
    inside a mesh (`jax.set_mesh`): Mosaic kernels cannot be
    partitioned automatically, and per-(batch, head) attention needs
    no collective — heads split over ``tp`` like the q/k/v projections
    that feed it, batch over ``dp``, lengths follow the batch."""
    from kfserving_tpu.ops.pallas_attention import flash_attention

    def kernel(q, k, v, *lens):
        return flash_attention(q, k, v, causal=causal,
                               kv_lengths=lens[0] if lens else None,
                               window=window)

    args = (q, k, v) if lengths is None else (q, k, v, lengths)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return kernel(*args)
    batch = mesh_axis(mesh, "dp", q.shape[0])
    spec = P(batch, None, mesh_axis(mesh, "tp", q.shape[2]), None)
    in_specs = (spec, spec, spec) + ((P(batch),) if lengths is not None
                                     else ())
    return jax.shard_map(kernel, in_specs=in_specs, out_specs=spec,
                         check_vma=False)(*args)


def _flash_eligible(q: jax.Array) -> bool:
    """Shape/backend gate for the fused kernel.  Mask handling is the
    dispatcher's job: suffix key padding rides the kernel as kv_lengths
    (non-causal only); every other mask pattern serves via XLA.
    KFS_DISABLE_FLASH=1 forces the XLA path (A/B benchmarking)."""
    if os.getenv("KFS_DISABLE_FLASH", "") not in ("", "0", "false"):
        return False
    if not _tpu_backend():
        return False
    _, L, _, D = q.shape
    if D % _FLASH_HEAD_DIM_MULTIPLE != 0:
        return False
    min_seq = (_FLASH_MIN_SEQ if D % 128 == 0
               else _FLASH_MIN_SEQ_HALF_LANE)
    return L >= min_seq


def dot_product_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          mask: Optional[jax.Array] = None,
                          causal: bool = False,
                          kv_lengths: Optional[jax.Array] = None,
                          prefix_padding: bool = False,
                          window: Optional[int] = None
                          ) -> jax.Array:
    """Attention over [batch, len, heads, head_dim] tensors.

    mask: optional broadcastable boolean [B, H, Lq, Lk] (True = attend).
    causal: apply a causal mask (decoder serving).  Composes with an
        explicit mask (logical AND); the flash kernel path requires the
        causal-only case.
    kv_lengths: optional int32 [B] declaring suffix key padding (real
        keys then padding) — the flash kernel masks it natively, so
        padded seq buckets keep the fused path.  When flash is
        ineligible, the equivalent suffix mask is derived and served via
        XLA.  Mutually exclusive with `mask`: lengths fully determine
        the suffix mask, and an inconsistent explicit mask would be
        silently ignored on the kernel path (callers with arbitrary mask
        patterns pass `mask` alone; the serving path enforces
        suffix-ness host-side in jax_model._check_prefix_mask).
    prefix_padding: declares `mask` to be suffix key padding.  The
        flash path then consumes it as per-row lengths (sum over the
        key axis) while the XLA fallback still applies the mask
        itself — so a contract-violating (non-suffix) mask stays
        correct on XLA and is wrong only where the declaration was
        load-bearing (the kernel), unlike kv_lengths which bakes the
        suffix form into both paths.
    window: with `causal`, a sliding window: the query at position t
        sees keys s with t - window < s <= t (`window` keys, its own
        among them).  A window no shorter than the sequence is plain
        causal attention.
    """
    if window is not None and not causal:
        raise ValueError("a sliding window is a causal band: pass "
                         "causal=True")
    if kv_lengths is not None and mask is not None:
        raise ValueError(
            "mask and kv_lengths are mutually exclusive: kv_lengths "
            "asserts suffix padding and the flash path would silently "
            "ignore a disagreeing mask; pass the mask alone for "
            "arbitrary patterns (optionally with prefix_padding=True)")
    Lq, Lk = q.shape[1], k.shape[1]
    derived_lengths = None
    if prefix_padding and mask is not None and not causal:
        # mask broadcasts over [B, H, Lq, Lk]; any one query row's key
        # mask gives the row's real-key count for a suffix mask.
        flat = jnp.reshape(mask, (mask.shape[0], -1, mask.shape[-1]))
        derived_lengths = flat[:, 0, :].astype(jnp.int32).sum(-1)
    if kv_lengths is not None and mask is None:
        mask = (jnp.arange(Lk)[None, :]
                < kv_lengths[:, None])[:, None, None, :]
    if causal:
        # KV-cache decode has Lq < Lk: query i sits at absolute position
        # (Lk - Lq + i), so the allowed region is a shifted triangle.
        causal_mask = jnp.tril(
            jnp.ones((Lq, Lk), jnp.bool_), k=Lk - Lq)[None, None, :, :]
        plain = mask is None or kv_lengths is not None
        mask = causal_mask if mask is None else (mask & causal_mask)
        if window is not None:
            mask = mask & jnp.triu(jnp.ones((Lq, Lk), jnp.bool_),
                                   k=Lk - Lq - window + 1)[None, None]
        # The Pallas kernel's causal mask assumes query i sits at absolute
        # position i, which only holds when Lq == Lk; KV-cache decode
        # (Lq < Lk, shifted triangle) must take the XLA path.  Causal +
        # key-padding composition and a window stay on XLA too while
        # XLA can hold their scores.
        flash_ok = plain and Lq == Lk and (
            (kv_lengths is None and window is None)
            or Lq >= _FLASH_CAUSAL_MASKED_MIN_SEQ)
        lengths = kv_lengths
    else:
        # Non-causal flash handles rectangular (Lq != Lk) grids and
        # key-padding lengths natively.
        flash_ok = (mask is None or kv_lengths is not None
                    or derived_lengths is not None)
        lengths = kv_lengths if kv_lengths is not None else derived_lengths
    use_flash = flash_ok and _flash_eligible(q)
    shapes = dict(q=q.shape, k=k.shape, causal=causal,
                  kv_lengths=kv_lengths is not None)
    if window is not None:
        shapes["window"] = window
    log_dispatch("pallas_flash" if use_flash else "xla", **shapes)
    if use_flash:
        return _flash(q, k, v, causal, lengths, window)
    return _xla_attention(q, k, v, mask)
