"""OLMoE (Muennighoff et al. 2024; `allenai/OLMoE-1B-7B-*`): a decoder
whose every layer is a modern block — RMSNorm, rotary positions, QK-norm,
SwiGLU — with a routed expert layer of 64 experts, 8 per token, in place
of the MLP.

The serving contract is `models/decoder.py`'s, mode for mode (full,
prefill, decode, chunk prefill, `logit_positions`), and the attention and
cache dispatch is the same function (`decoder.cached_attention`), so the
engine, the paged pool and the decode kernel serve both.  What differs
per layer:

    h = RMSNorm(x)
    q = RMSNorm_q(h·Wq), k = RMSNorm_k(h·Wk)   over the whole projection,
                                               before the split into heads
    v = h·Wv;  q, k = rope(q), rope(k)         rotate-half, absolute positions
    x = x + attention(q, k, v)·Wo              K is stored rotated
    h = RMSNorm(x)
    p = softmax(h·Wr) in float32;  top 8, probabilities not renormalised
    x = x + Σ_k p_k · down_k(silu(h·gate_k) ⊙ h·up_k)

then a final RMSNorm and an untied head.  No biases, no position table.
Which way the experts are computed is `ops/moe.py`'s choice from the
number of tokens.  Every expert layer sows its routing counts ([E] pairs
per expert) into the `moe` collection; a caller that wants them applies
with `mutable=["moe"]` and reads `module.routed_pairs(state)`.
"""

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from kfserving_tpu.models.decoder import KVCache, cached_attention
from kfserving_tpu.ops import moe


class OlmoeConfig:
    def __init__(self, vocab_size=50304, hidden_size=2048, num_layers=16,
                 num_heads=16, intermediate_size=1024, num_experts=64,
                 experts_per_token=8, max_seq=4096, rope_theta=10000.0,
                 rms_norm_eps=1e-5, dtype=jnp.bfloat16,
                 param_dtype=jnp.bfloat16, attn_fn=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size  # one expert's width
        self.num_experts = num_experts
        self.experts_per_token = experts_per_token
        self.max_seq = max_seq
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.dtype = jnp.dtype(dtype)
        # The checkpoint's own dtype: parameters are stored, cached and
        # placed in HBM as this, never widened.
        self.param_dtype = jnp.dtype(param_dtype)
        self.attn_fn = attn_fn

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    def cache_layers(self):
        return [KVCache(self.num_heads, self.head_dim)] * self.num_layers

    def param_counts(self):
        """Parameters by how a served token meets them: `per_expert`
        (one expert's three matrices), `always_read` (what every step
        streams whatever the routing: attention, router, norms, head;
        not the embedding table, of which a step gathers a row a
        token), `active` (what a token is multiplied by: `always_read`
        and its own experts) and `total`."""
        h, layers = self.hidden_size, self.num_layers
        per_expert = 3 * h * self.intermediate_size
        per_layer = 4 * h * h + h * self.num_experts + 4 * h
        always = layers * per_layer + h + h * self.vocab_size
        return {
            "per_expert": per_expert,
            "always_read": always,
            "active": always + layers * self.experts_per_token * per_expert,
            "total": (always + h * self.vocab_size
                      + layers * self.num_experts * per_expert),
        }


class RMSNorm(nn.Module):
    epsilon: float
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           self.param_dtype)
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.epsilon)
        return (x32 * scale.astype(jnp.float32)).astype(self.dtype)


def rope_tables(positions, head_dim: int, theta: float):
    """(cos, sin) [B, L, 1, D/2] of absolute positions [B, L]; the same
    for every layer, so made once a forward pass."""
    half = head_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]


def rope(x, tables):
    """Rotary embedding, rotate-half convention, over the whole head:
    x [B, L, H, D]."""
    cos, sin = tables
    half = x.shape[-1] // 2
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


class ExpertLayer(nn.Module):
    config: OlmoeConfig

    @nn.compact
    def __call__(self, hidden, valid=None):
        """hidden [B, L, H]; valid optional [B, L] bool (False: bucket
        padding, routed to no expert)."""
        cfg = self.config
        e, h, f = cfg.num_experts, cfg.hidden_size, cfg.intermediate_size
        fan_in = nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1,
            batch_axis=(0,))
        gate = self.param("gate", fan_in, (e, h, f), cfg.param_dtype)
        up = self.param("up", fan_in, (e, h, f), cfg.param_dtype)
        down = self.param("down", fan_in, (e, f, h), cfg.param_dtype)
        x = hidden.reshape(-1, h)
        if valid is not None:
            valid = valid.reshape(-1)
        with jax.named_scope("moe.router"):
            logits = nn.Dense(e, use_bias=False, dtype=jnp.float32,
                              param_dtype=cfg.param_dtype,
                              name="router")(x.astype(jnp.float32))
            # Mellum's config (models/mellum.py) renormalises the kept
            # probabilities; OLMoE's has no such key and does not.
            probs, experts = moe.route(
                logits, cfg.experts_per_token,
                getattr(cfg, "norm_topk_prob", False))
            if not self.is_initializing():
                self.sow("moe", "pairs",
                         moe.routed_pairs(experts, e, valid),
                         reduce_fn=lambda _, new: new,
                         init_fn=lambda: None)
        out = moe.routed_experts(
            x, gate.astype(cfg.dtype), up.astype(cfg.dtype),
            down.astype(cfg.dtype), probs, experts, valid)
        return out.reshape(hidden.shape)


class OlmoeBlock(nn.Module):
    config: OlmoeConfig

    @nn.compact
    def __call__(self, hidden, positions, rotary, *, kv_lengths=None,
                 cache=None, valid=None, segments=None):
        cfg = self.config

        def norm(name):
            return RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype,
                           name=name)

        def proj(name):
            # Kernels [hidden, heads, head_dim], as the GPT-2 block's:
            # the sharding rules split the heads of both.
            return nn.DenseGeneral((cfg.num_heads, cfg.head_dim),
                                   use_bias=False, dtype=cfg.dtype,
                                   param_dtype=cfg.param_dtype, name=name)

        def qk_norm(name, x):
            # Over the whole projection, all heads together.
            return norm(name)(x.reshape(hidden.shape)).reshape(x.shape)

        with jax.named_scope("attn"):
            x = norm("attn_norm")(hidden)
            q = qk_norm("q_norm", proj("query")(x))
            k = qk_norm("k_norm", proj("key")(x))
            v = proj("value")(x)
            q, k = rope(q, rotary), rope(k, rotary)
            out, new_cache = cached_attention(
                q, k, v, cache=cache,
                positions=None if cache is None else positions,
                kv_lengths=kv_lengths, attn_fn=cfg.attn_fn,
                segments=segments)
            hidden = hidden + nn.DenseGeneral(
                cfg.hidden_size, axis=(-2, -1), use_bias=False,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name="out")(out)
        x = norm("mlp_norm")(hidden)
        x = ExpertLayer(cfg, name="experts")(x, valid)
        with jax.named_scope("moe.combine"):
            hidden = hidden + x
        return hidden, new_cache


class _Head(nn.Module):
    """Untied output head; logits accumulate and come back in float32."""
    config: OlmoeConfig

    @nn.compact
    def __call__(self, hidden):
        cfg = self.config
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (cfg.hidden_size, cfg.vocab_size),
                            cfg.param_dtype)
        return jnp.dot(hidden, kernel.astype(cfg.dtype),
                       preferred_element_type=jnp.float32)


class OlmoeLM(nn.Module):
    """Token ids -> next-token logits; arguments and returns as
    `decoder.DecoderLM` (which documents the modes)."""

    config: OlmoeConfig

    def routed_pairs(self, state):
        """[layers, experts] int32 (token, expert) pairs, from the
        `moe` collection an apply with `mutable=["moe"]` returned."""
        return jnp.stack([
            state["moe"][f"layer_{i}"]["experts"]["pairs"]
            for i in range(self.config.num_layers)])

    @nn.compact
    def __call__(self, input_ids, positions: Optional[Any] = None,
                 kv_cache: Optional[Any] = None,
                 kv_lengths: Optional[Any] = None,
                 return_cache: bool = False,
                 logit_positions: Optional[Any] = None,
                 valid: Optional[Any] = None,
                 segments: Optional[Any] = None):
        cfg = self.config
        b, l = input_ids.shape
        if positions is None:
            pos = jnp.broadcast_to(jnp.arange(l)[None, :], (b, l))
        else:
            pos = positions.reshape(b, -1)
        # Tokens no request owns are given to no expert: a prefill
        # bucket's padding (past kv_lengths, or in no segment of a
        # packed prefill), a chunk's padding (parked on the out-of-range
        # sentinel), and the rows of a decode step that the engine says
        # are not `valid` ([B, 1] bool: past their token budget).
        if kv_lengths is not None:
            valid = jnp.arange(l)[None, :] < kv_lengths[:, None]
        elif segments is not None:
            valid = segments >= 0
        elif kv_cache is not None and l > 1:
            # What a row's table can hold: the engine parks padding
            # past it, where cache writes drop.
            pool_k, _, table = kv_cache[0]
            valid = pos < table.shape[1] * pool_k.shape[1]
        hidden = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                          param_dtype=cfg.param_dtype,
                          name="wte")(input_ids)
        rotary = rope_tables(pos, cfg.head_dim, cfg.rope_theta)
        caches = []
        for i in range(cfg.num_layers):
            hidden, new_cache = OlmoeBlock(cfg, name=f"layer_{i}")(
                hidden, pos, rotary, kv_lengths=kv_lengths,
                cache=None if kv_cache is None else kv_cache[i],
                valid=valid, segments=segments)
            caches.append(new_cache)
        if logit_positions is not None:
            hidden = jnp.take_along_axis(
                hidden, logit_positions.reshape(b, -1, 1), axis=1)
        hidden = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype,
                         name="final_norm")(hidden)
        logits = _Head(cfg, name="lm_head")(hidden)
        if kv_cache is not None or return_cache:
            return logits, caches
        return logits


def olmoe_tiny(**overrides):
    """2 layers, 8 experts of 64, 2 per token, float32: hermetic CPU
    tests.  vocab 384 covers the byte tokenizer, as `decoder_tiny`."""
    defaults = dict(vocab_size=384, hidden_size=128, num_layers=2,
                    num_heads=4, intermediate_size=64, num_experts=8,
                    experts_per_token=2, max_seq=256, dtype=jnp.float32,
                    param_dtype=jnp.float32)
    defaults.update(overrides)
    return OlmoeConfig(**defaults)


def _create_olmoe(seq_len=64, **kw):
    """Registry factory: 'olmoe' (the defaults are OLMoE-1B-7B's published
    sizes, 6.92 B parameters)."""
    return OlmoeLM(OlmoeConfig(**kw)), jnp.zeros((1, seq_len), jnp.int32)


def _create_olmoe_tiny(seq_len=32, **kw):
    """Registry factory: 'olmoe_tiny'."""
    return OlmoeLM(olmoe_tiny(**kw)), jnp.zeros((1, seq_len), jnp.int32)
