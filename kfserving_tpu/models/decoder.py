"""Decoder-only transformer (GPT-class) with KV-cache serving modes.

The reference treats generative models as opaque request/response
artifacts behind the same predict route as everything else (reference
pkg/apis/serving/v1beta1/predictor.go:33-59 — no decoder-aware serving
exists anywhere in it).  A TPU-native serving framework needs the
decoder to be a first-class citizen: incremental decoding with a KV
cache is what makes generation O(L) instead of O(L^2), and the cache
layout decides whether the decode step maps onto the MXU.

One Flax module, three executions (all static-shape, jit-friendly):

- **full**: `input_ids [B, L] -> logits [B, L, V]` — causal attention
  over the whole sequence.  Teacher-forcing / parity baseline.
- **prefill**: same forward pass with `return_cache=True` — also
  returns every layer's (k, v) [B, L, H, D] so the serving engine can
  scatter them into its block pool.  Suffix padding is masked via
  `kv_lengths` and rides the padding-aware flash kernel at long L.
- **decode**: `input_ids [B, 1]` with `kv_cache` — writes the step's
  k/v through each row's block table at per-row `positions` and
  attends over the valid prefix.  B here is the engine's slot count:
  one compiled program serves continuous batching forever.

TPU notes:
- pre-LN blocks (GPT-2 style): the residual stream stays bf16; logits
  come back float32 for stable sampling.
- the LM head ties the embedding matrix (one [V, H] tensor in HBM).
- the one cache layout is the engine's block pool, [NB, BS, H*D] per
  layer with a [B, MB] block table per dispatch
  (ops/paged_attention.py owns it): lane-dense whatever H and D are,
  and its last axis is shardable for tensor parallelism on heads.
- full/prefill attention dispatches through
  ops.dot_product_attention (the flash kernel when eligible); decode
  and chunk prefill through ops/paged_attention.py (the Pallas
  kernels when eligible, XLA gathers elsewhere).
"""

from typing import Any, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp

from kfserving_tpu.ops import dot_product_attention


# What a layer keeps between steps, as a model's config declares it
# (`config.cache_layers()`, one entry a layer) and the engine builds it:
# K/V rows in the block pool, arrays of a slot's own (a recurrence's
# state), both (a layer whose attention and recurrence run side by side),
# latent rows in a block pool of their own kind, or None.
class KVCache(NamedTuple):
    heads: int       # KV heads: fewer than the query's under GQA
    head_dim: int
    # Sliding-window attention: a query sees this many latest keys, its
    # own among them, and the layer keeps a ring of blocks that long a
    # sequence (ops/paged_attention.py) in a pool of its own kind.
    # None: the whole context.
    window: Optional[int] = None


class LatentCache(NamedTuple):
    """Latent attention (models/deepseek_v3.py): ONE row a token, the
    normed compression (`rank`) beside the rotated key all heads share
    (`rope_dim`), which every query head reads as its key and, over its
    first `rank` columns, as its value.  Addressed by position as K/V rows
    are: the engine tables, shares and frees its blocks the same way, and
    wherever a layer's cache is handed over it is the one-tuple of what a
    `KVCache` layer hands over as a pair.  To the code that asks a K/V
    layer for its geometry it is one head as wide as the row that keeps
    its whole context."""
    rank: int
    rope_dim: int

    heads = 1
    window = None

    @property
    def head_dim(self):
        return self.rank + self.rope_dim


class StateCache(NamedTuple):
    # ((shape without the slot axis, dtype), ...), one per array
    arrays: Tuple[Tuple[Tuple[int, ...], Any], ...]
    # The tokens a chunk of the recurrence's prefill holds: a prompt that
    # starts at a multiple of it starts from a zero state (ops/ssm.py).
    chunk: int


class BothCaches(NamedTuple):
    """One layer's K/V rows and its state (models/falcon_h1.py).  Wherever
    a layer's cache is handed over (the engine's arrays, what prefill
    returns, what decode takes and returns) such a layer's is the pair
    (what a `KVCache` layer's would be, what a `StateCache` layer's would
    be)."""
    kv: KVCache
    state: StateCache


# The modules of DecoderLM that read their parameters in `config.dtype`.
_READ_IN_DTYPE = frozenset({"wte", "wpe", "query", "key", "value", "out",
                            "mlp_in", "mlp_out"})


class DecoderConfig:
    def __init__(self, vocab_size=32000, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=3072, max_seq=1024,
                 layer_norm_eps=1e-5, dtype=jnp.bfloat16,
                 attn_fn=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size
        self.max_seq = max_seq
        self.layer_norm_eps = layer_norm_eps
        self.dtype = dtype
        # Pluggable full/prefill attention (q, k, v, mask) -> out for
        # sequence-parallel serving (ring attention), mirroring
        # models/bert.py.  Decode-mode cache attention is not pluggable:
        # its Lq=1 reads are latency-bound, not sequence-shardable.
        self.attn_fn = attn_fn

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    def cache_layers(self):
        return [KVCache(self.num_heads, self.head_dim)] * self.num_layers

    def resident_dtypes(self, variables):
        """A tree like `variables` whose leaves are the dtype the
        programs read that leaf in: what the engine may keep resident
        in its place (engine/param_cache.place_on_device).  Every Dense
        and Embed below casts its kernel, bias or table to `dtype`
        before it multiplies, adds or gathers (Flax's `promote_dtype`),
        so those rest in `dtype`; LayerNorm multiplies by `scale` and
        adds `bias` in float32 whatever `dtype` is, so they, and any
        leaf this class does not know, rest as stored."""
        import jax

        read = jnp.dtype(self.dtype)

        def of(path, leaf):
            names = {getattr(key, "key", None) for key in path}
            return read if names & _READ_IN_DTYPE else jnp.dtype(leaf.dtype)

        return jax.tree_util.tree_map_with_path(of, variables)


def cached_attention(q, k, v, *, cache=None, positions=None,
                     kv_lengths=None, attn_fn=None, window=None,
                     segments=None):
    """Attention of one block, shared by every decoder block of the zoo
    (GPT-2's here, OLMoE's in models/olmoe.py, Nemotron-H's in
    models/nemotron_h.py): q, k, v are [B, L, H, D] as projected (and,
    for rotary models, rotated — the pool stores what attention reads);
    k and v may have fewer heads than q (grouped-query attention: query
    head j reads KV head j // (Hq / Hkv)), and the pool holds theirs.
    `cache` is None (full forward
    and prefill: causal attention over q, k, v themselves) or
    (pool_k, pool_v, block_table): the engine's block pools
    [NB, BS, H*D] (ops/paged_attention.py owns the layout and reshapes
    q, k, v at its edge) plus this batch's [B, MB] table.  The table
    flows in per dispatch and is not returned — only the written pools
    are.  Lq == 1 is the decode step; Lq > 1 is a CHUNK PREFILL: the
    chunk's tokens write through the table, then attend over the pool
    with per-query causal masking (earlier chunks are already resident
    — cross-chunk attention comes from the pool, exactly like decode).
    `window` (static; None for the whole context) makes the layer a
    sliding-window one on every branch: a query at t sees keys s with
    t - window < s <= t, and `cache` is then the layer's ring pools and
    ring table (ops/paged_attention.py).
    `segments` [B, L] int32 (a prefill whose rows each carry several
    prompts, engine/programs.py `prefill_fn`; in place of `kv_lengths`)
    numbers the prompt a position belongs to, -1 for padding: a query
    sees the keys of its own prompt that are not behind it, so a key of
    another prompt has weight exactly 0, as a padded one has.
    Returns (out [B, L, H, D], new_cache)."""
    lq = q.shape[1]
    group = q.shape[2] // k.shape[2]
    new_cache = (k, v)
    if cache is None and group > 1:
        # Full forward and prefill: every query head its own copy.
        k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    if cache is not None:
        from kfserving_tpu.ops.paged_attention import (
            paged_attention,
            paged_prefill_attention_xla,
            paged_write,
        )

        pool_k, pool_v, table = cache
        if lq == 1:
            pool_k, pool_v = paged_write(pool_k, pool_v, k[:, 0],
                                         v[:, 0], table,
                                         positions[:, 0], window)
            out = paged_attention(q, pool_k, pool_v, table,
                                  positions[:, 0] + 1, window)
        else:
            pool_k, pool_v = paged_write(pool_k, pool_v, k, v,
                                         table, positions, window)
            out = paged_prefill_attention_xla(q, pool_k, pool_v,
                                              table, positions, window)
        new_cache = (pool_k, pool_v)
    elif attn_fn is not None:
        causal = jnp.tril(jnp.ones((lq, lq), jnp.bool_))[None, None]
        if window is not None:
            causal &= jnp.triu(jnp.ones((lq, lq), jnp.bool_),
                               k=1 - window)[None, None]
        if kv_lengths is not None:
            pad = (jnp.arange(lq)[None, :]
                   < kv_lengths[:, None])[:, None, None, :]
            attn_mask = causal & pad
        elif segments is not None:
            attn_mask = causal & same_segment(segments)
        else:
            attn_mask = causal
        out = attn_fn(q, k, v, attn_mask)
        # The k/v projections are already materialized; without
        # this a prefill with return_cache=True under a pluggable
        # attn_fn returned caches=[None, ...] and crashed deep in
        # the engine's insert scatter instead of working.
    elif segments is not None:
        # An explicit mask takes XLA's attention whatever the length:
        # the caller gives segments only to the lengths whose padded
        # prefill takes it too (`attention.masked_prefill_takes_xla`).
        out = dot_product_attention(q, k, v, mask=same_segment(segments),
                                    causal=True, window=window)
    else:
        out = dot_product_attention(q, k, v, causal=True,
                                    kv_lengths=kv_lengths, window=window)
    return out, new_cache


def same_segment(segments):
    """[B, 1, Lq, Lk] bool of `segments` [B, L]: the key is of the
    query's prompt, and of a prompt at all."""
    keys = segments[:, None, None, :]
    return (segments[:, None, :, None] == keys) & (keys >= 0)


class DecoderBlock(nn.Module):
    config: DecoderConfig

    @nn.compact
    def __call__(self, hidden, *, mask=None, kv_lengths=None,
                 cache=None, positions=None, segments=None):
        """cache: optional (pool_k, pool_v, block_table) — decode and
        chunk prefill.  positions: [B, L] absolute positions of the
        fed tokens — where the cache write lands.  segments: [B, L],
        the prompt each position of a packed prefill belongs to."""
        cfg = self.config
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="attn_norm")(hidden)

        def proj(name):
            return nn.DenseGeneral((cfg.num_heads, cfg.head_dim),
                                   dtype=cfg.dtype, name=name)

        q = proj("query")(x)
        k = proj("key")(x)
        v = proj("value")(x)
        out, new_cache = cached_attention(
            q, k, v, cache=cache, positions=positions,
            kv_lengths=kv_lengths, attn_fn=cfg.attn_fn, segments=segments)
        out = nn.DenseGeneral(cfg.hidden_size, axis=(-2, -1),
                              dtype=cfg.dtype, name="out")(out)
        hidden = hidden + out
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="mlp_norm")(hidden)
        x = nn.Dense(cfg.intermediate_size, dtype=cfg.dtype,
                     name="mlp_in")(x)
        x = nn.gelu(x, approximate=True)
        x = nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="mlp_out")(x)
        return hidden + x, new_cache


class DecoderLM(nn.Module):
    """Token ids -> next-token logits, with optional KV-cache modes.

    full/prefill: input_ids [B, L]; kv_lengths optional [B] (suffix
        real-token counts — bucket padding).  Returns logits [B, L, V]
        (float32), plus per-layer (k, v) [B, L, H, D] when
        return_cache=True.
    decode: input_ids [B, 1] + kv_cache (list of per-layer
        (pool_k, pool_v, block_table): pools [NB, BS, H*D], table
        [B, MB]) + positions [B].  Returns logits [B, 1, V] and the
        written pools.
    chunk prefill: input_ids [B, L>1] + kv_cache + positions [B, L] —
        the chunk's tokens write into the cache at their absolute
        positions and attend per-query-causally over the cache
        (earlier chunks included), so a long prompt lands in
        block-aligned pieces between decode waves.
    logit_positions: optional [B] or [B, P] int32 — compute logits
        ONLY at those positions per row (hidden gathered before the
        final norm + LM head).  The sampled-token path never needs
        the [B, L, V] logits cube; skipping it drops the LM-head
        matmul from O(L·H·V) to O(P·H·V) per row, the dominant
        prefill FLOP at long L.  [B] returns logits [B, 1, V]
        (chunked prefill's last-token slice); [B, P] returns
        [B, P, V] — speculative decoding's verify dispatch reads all
        K+1 positions of a draft run from the one Lq>1 forward.
    packed prefill: `segments` [B, L] int32 in place of `kv_lengths`,
        with `positions` [B, L] — a row carries several prompts, each
        numbered in `segments` (-1: padding) and counted from 0 again
        in `positions`; each attends to itself alone, and
        `logit_positions` [B, P] names each one's last token.
    """

    config: DecoderConfig

    @nn.compact
    def __call__(self, input_ids, positions: Optional[Any] = None,
                 kv_cache: Optional[Any] = None,
                 kv_lengths: Optional[Any] = None,
                 return_cache: bool = False,
                 logit_positions: Optional[Any] = None,
                 segments: Optional[Any] = None):
        cfg = self.config
        b, l = input_ids.shape
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                         dtype=cfg.dtype, name="wte")
        if positions is None:
            pos = jnp.arange(l)[None, :]
        else:
            pos = positions.reshape(b, -1)
        hidden = embed(input_ids)
        # Clamp for the position table: cache-mode callers park
        # padding/sentinel rows on max_seq (their cache writes drop;
        # an unclamped index would still be gather-clamped inside jit,
        # this just makes the contract explicit).
        hidden += nn.Embed(cfg.max_seq, cfg.hidden_size, dtype=cfg.dtype,
                           name="wpe")(jnp.minimum(pos, cfg.max_seq - 1))
        caches = []
        for i in range(cfg.num_layers):
            layer_cache = None if kv_cache is None else kv_cache[i]
            layer_pos = (None if kv_cache is None
                         else pos.reshape(b, -1))
            hidden, new_cache = DecoderBlock(cfg, name=f"layer_{i}")(
                hidden, kv_lengths=kv_lengths, cache=layer_cache,
                positions=layer_pos, segments=segments)
            caches.append(new_cache)
        if logit_positions is not None:
            # Per-row gather BEFORE the norm + LM head: LayerNorm and
            # the tied-embedding matmul are per-position, so the
            # sliced path is numerically identical to slicing the
            # full logits cube at the same indices.  reshape(b, -1, 1)
            # accepts both the [B] single-slice form and the [B, P]
            # multi-position form (speculative verify).
            hidden = jnp.take_along_axis(
                hidden, logit_positions.reshape(b, -1, 1), axis=1)
        hidden = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                              name="final_norm")(hidden)
        logits = embed.attend(hidden.astype(embed.embedding.dtype))
        logits = logits.astype(jnp.float32)
        if kv_cache is not None:
            return logits, caches
        if return_cache:
            return logits, caches
        return logits


def decoder_small(**overrides):
    """GPT-2-small-class config (124M at vocab 50257)."""
    defaults = dict(vocab_size=50257, hidden_size=768, num_layers=12,
                    num_heads=12, intermediate_size=3072, max_seq=1024)
    defaults.update(overrides)
    return DecoderConfig(**defaults)


def decoder_tiny(**overrides):
    """4-layer/128-wide config for hermetic CPU tests.  vocab 384
    covers the byte tokenizer (258 ids) rounded up to a lane-friendly
    multiple of 128."""
    defaults = dict(vocab_size=384, hidden_size=128, num_layers=4,
                    num_heads=4, intermediate_size=512, max_seq=256,
                    dtype=jnp.float32)
    defaults.update(overrides)
    return DecoderConfig(**defaults)


def create_decoder(config: Optional[DecoderConfig] = None,
                   seq_len: int = 64):
    cfg = config or decoder_small()
    module = DecoderLM(cfg)
    example = jnp.zeros((1, seq_len), jnp.int32)
    return module, example


def _create_decoder_small(**kw):
    """Registry factory: 'decoder'."""
    seq_len = kw.pop("seq_len", 64)
    return create_decoder(decoder_small(**kw) if kw else None,
                          seq_len=seq_len)


def _create_decoder_tiny(seq_len=32, **kw):
    """Registry factory: 'decoder_tiny'."""
    return create_decoder(decoder_tiny(**kw), seq_len=seq_len)
