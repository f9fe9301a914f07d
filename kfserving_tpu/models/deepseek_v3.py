"""DeepSeek-V3-style decoder (`model_type` `deepseek_v3`; the published
sizes below are `moonshotai/Moonlight-16B-A3B`'s): multi-head LATENT
attention, whose cache is one compressed row a token a layer, beside a
dense SwiGLU layer first and then routed SwiGLU experts with a shared one.

With `h = RMSNorm(x)` (eps 1e-5), 16 heads, d_nope 128, d_rope 64, d_v 128,
rank r = 512 (`q_lora_rank` null: the query has no low-rank step;
`rope_scaling` absent: no YaRN, no `mscale`):

    q = W_q·h  (16 x 192)            split q_nope (128) | q_pe (64)
    (c, k_pe) = W_kva·h  (512 + 64)  c <- RMSNorm_kva(c)
    q_pe, k_pe <- rope(·)            theta 50000 over 64 dims, the pairs
                                     (2i, 2i+1) rotated by pos·theta^(-2i/64):
                                     de-interleaved, then rotate-half, as the
                                     modelling code; k_pe is ONE vector a
                                     token, shared by all 16 heads
  expanded (the published form; a whole-prompt prefill, `attn.latent.expand`):
    (k_nope, v)_j = W_kvb·c_j  (16 x (128 + 128))
    score_{t,j,head} = (q_nope_t·k_nope_j + q_pe_t·k_pe_j) / √192
    o_t = Σ_j softmax_j(score)·v_j;  out = W_o·concat(o)  (16 x 128 -> 2048)
  absorbed (decode, and any Lq > 1 call against the pool; the same numbers,
  `attn.latent.absorb`): W_kvb a head is W_uk [128, 512] over W_uv [128, 512];
    q̃ = q_nopeᵀ·W_uk  (512);  score = (q̃·c_j + q_pe·k_pe_j) / √192
    õ = Σ_j p·c_j  (512);     o = W_uv·õ
  so the cache is the row (c_j normed, k_pe_j rotated): 576 numbers a token
  a layer, read once as the key (all 576) and as the value (its first 512)
  by the 16 query heads.  The absorption is computed from W_kvb as stored,
  a call: no second copy of the weights is resident.
    x <- x + attn
  layer 0:        x <- x + down(silu(gate(h2)) ⊙ up(h2))      width 11264
  layers 1..:     s = sigmoid(W_g·h2) over 64, float32; the 6 largest of
                  s + b chosen (`e_score_correction_bias`; `n_group` =
                  `topk_group` = 1: no group limit); weights
                  2.446·s_i / (Σ_chosen s + 1e-20); routed SwiGLU experts of
                  1408, plus one shared SwiGLU of 2 x 1408 on every token
                  x <- x + routed + shared
    logits = RMSNorm(x_L)·W_head   untied, float32

Cache (`decoder.LatentCache`): a layer's pool [NB, BS, 576 in whole lane
tiles] (ops/paged_attention.py owns it), tabled by position as K/V blocks
are.  Prefill returns a layer's `(rows [B, L, 576],)`; decode, a chunk and a
verify take `(pool, table)` and return `(pool,)`.

The routed experts go through ops/moe.py's paths (`route_sigmoid`,
`routed_experts`) as the other expert models' do, and every expert layer
sows its routing counts as models/olmoe.py's.  What no key of the published
config fixes is listed under `assumed` in
chipbench/configs/moonlight-16b-a3b-7l.json.

Scopes (`jax.named_scope`): `attn.latent`, under it `attn.latent.absorb`
and `attn.latent.expand` (the two forms of the up-projection), `mlp`,
`moe.router`, `moe.shared`, `moe.combine` (and ops/moe.py's own), `head`.
"""

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from kfserving_tpu.models.decoder import LatentCache
from kfserving_tpu.models.olmoe import RMSNorm, _Head, rope, rope_tables
from kfserving_tpu.ops import dot_product_attention, moe


class DeepseekV3Config:
    def __init__(self, vocab_size=163840, hidden_size=2048, num_layers=27,
                 num_heads=16, qk_nope_head_dim=128, qk_rope_head_dim=64,
                 v_head_dim=128, kv_lora_rank=512, intermediate_size=11264,
                 moe_intermediate_size=1408, num_experts=64,
                 experts_per_token=6, shared_experts=2, first_dense_layers=1,
                 routed_scaling_factor=2.446, max_seq=8192,
                 rope_theta=50000.0, rms_norm_eps=1e-5, dtype=jnp.bfloat16,
                 param_dtype=jnp.bfloat16):
        if not 0 <= first_dense_layers <= num_layers:
            raise ValueError(f"{first_dense_layers} dense layers of "
                             f"{num_layers}")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.kv_lora_rank = kv_lora_rank
        self.intermediate_size = intermediate_size      # the dense layers'
        self.moe_intermediate_size = moe_intermediate_size  # one expert's
        self.num_experts = num_experts
        self.experts_per_token = experts_per_token
        self.shared_experts = shared_experts
        self.first_dense_layers = first_dense_layers
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.max_seq = max_seq
        self.rope_theta = float(rope_theta)
        self.rms_norm_eps = rms_norm_eps
        self.dtype = jnp.dtype(dtype)
        self.param_dtype = jnp.dtype(param_dtype)

    @property
    def expert_layers(self):
        return self.num_layers - self.first_dense_layers

    def cache_layers(self):
        """Every layer keeps latent rows: `kv_lora_rank` +
        `qk_rope_head_dim` numbers a token."""
        return [LatentCache(self.kv_lora_rank,
                            self.qk_rope_head_dim)] * self.num_layers

    def param_counts(self):
        """As `OlmoeConfig.param_counts`."""
        h, heads = self.hidden_size, self.num_heads
        rank, rope_dim = self.kv_lora_rank, self.qk_rope_head_dim
        attention = (h * heads * (self.qk_nope_head_dim + rope_dim)
                     + h * (rank + rope_dim) + rank
                     + rank * heads * (self.qk_nope_head_dim
                                       + self.v_head_dim)
                     + heads * self.v_head_dim * h + 2 * h)
        per_expert = 3 * h * self.moe_intermediate_size
        dense = 3 * h * self.intermediate_size
        expert_layer = (h * self.num_experts + self.num_experts
                        + self.shared_experts * per_expert)
        always = (self.num_layers * attention
                  + self.first_dense_layers * dense
                  + self.expert_layers * expert_layer
                  + h + h * self.vocab_size)
        return {
            "per_expert": per_expert,
            "always_read": always,
            "active": always + (self.expert_layers * self.experts_per_token
                                * per_expert),
            "total": (always + h * self.vocab_size
                      + self.expert_layers * self.num_experts * per_expert),
        }


def _paired(x):
    """[.., 2n] with the even columns first and then the odd: the pairs
    (2i, 2i+1) that the checkpoint rotates become rotate-half's (i, n+i)."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


class LatentAttention(nn.Module):
    config: DeepseekV3Config

    @nn.compact
    def __call__(self, hidden, positions, rotary, *, kv_lengths=None,
                 cache=None):
        """hidden [B, L, H] normed; cache None (a full forward, a
        prefill) or (pool, table); returns (out, (rows,) or (pool,))."""
        from kfserving_tpu.ops import paged_attention

        cfg = self.config
        heads, rank = cfg.num_heads, cfg.kv_lora_rank
        nope, value = cfg.qk_nope_head_dim, cfg.v_head_dim
        scale = (nope + cfg.qk_rope_head_dim) ** -0.5
        fan_in = nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", in_axis=0, out_axis=(1, 2))
        with jax.named_scope("attn.latent"):
            q = nn.DenseGeneral(
                (heads, nope + cfg.qk_rope_head_dim), use_bias=False,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name="query")(hidden)
            down = nn.Dense(rank + cfg.qk_rope_head_dim, use_bias=False,
                            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                            name="kv_a")(hidden)
            c = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype,
                        name="kv_norm")(down[..., :rank])
            q_pe = rope(_paired(q[..., nope:]), rotary)
            k_pe = rope(_paired(down[..., None, rank:]), rotary)[:, :, 0]
            rows = jnp.concatenate([c, k_pe], axis=-1)       # [B, L, 576]
            # W_kvb [rank, heads, nope + value], as the checkpoint's
            # `kv_b_proj` is: a head's W_uk over its W_uv.
            w_kvb = self.param("kv_b", fan_in, (rank, heads, nope + value),
                               cfg.param_dtype).astype(cfg.dtype)
            if cache is None:
                with jax.named_scope("attn.latent.expand"):
                    kv = jnp.einsum("blr,rhd->blhd", c, w_kvb)
                k = jnp.concatenate(
                    [kv[..., :nope], jnp.broadcast_to(
                        k_pe[:, :, None], kv.shape[:3] + k_pe.shape[-1:])],
                    axis=-1)
                q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
                # the scale is the query's width's, 1/√192, on both paths
                out = dot_product_attention(q, k, kv[..., nope:],
                                            causal=True,
                                            kv_lengths=kv_lengths)
                new_cache = (rows,)
            else:
                pool, table = cache
                step = hidden.shape[1] == 1  # a decode step writes [B, W]
                pool = paged_attention.latent_write(
                    pool, rows[:, 0] if step else rows, table,
                    positions[:, 0] if step else positions)
                with jax.named_scope("attn.latent.absorb"):
                    absorbed = jnp.einsum("blhd,rhd->blhr", q[..., :nope],
                                          w_kvb[..., :nope])
                latent = paged_attention.latent_attention(
                    jnp.concatenate([absorbed, q_pe], axis=-1), pool, table,
                    positions, rank=rank, scale=scale)
                with jax.named_scope("attn.latent.absorb"):
                    out = jnp.einsum("blhr,rhd->blhd", latent,
                                     w_kvb[..., nope:])
                new_cache = (pool,)
            out = nn.DenseGeneral(cfg.hidden_size, axis=(-2, -1),
                                  use_bias=False, dtype=cfg.dtype,
                                  param_dtype=cfg.param_dtype,
                                  name="out")(out)
        return out, new_cache


class GatedMLP(nn.Module):
    """down(silu(gate(h)) ⊙ up(h)) of `width`."""
    config: DeepseekV3Config
    width: int

    @nn.compact
    def __call__(self, hidden):
        cfg = self.config

        def dense(name, width):
            return nn.Dense(width, use_bias=False, dtype=cfg.dtype,
                            param_dtype=cfg.param_dtype, name=name)

        gate = jax.nn.silu(dense("gate", self.width)(hidden)
                           .astype(jnp.float32))
        up = dense("up", self.width)(hidden)
        wide = (up.astype(jnp.float32) * gate).astype(cfg.dtype)
        return dense("down", cfg.hidden_size)(wide)


class ExpertLayer(nn.Module):
    config: DeepseekV3Config

    @nn.compact
    def __call__(self, hidden, valid=None):
        """hidden [B, L, H]; valid optional [B, L] bool (False: routed to
        no expert)."""
        cfg = self.config
        e, h, f = cfg.num_experts, cfg.hidden_size, cfg.moe_intermediate_size
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
            bias = self.param("router_bias", nn.initializers.zeros, (e,),
                              jnp.float32)
            weights, experts = moe.route_sigmoid(
                logits, bias, cfg.experts_per_token,
                cfg.routed_scaling_factor)
            if not self.is_initializing():
                self.sow("moe", "pairs",
                         moe.routed_pairs(experts, e, valid),
                         reduce_fn=lambda _, new: new,
                         init_fn=lambda: None)
        out = moe.routed_experts(
            x, gate.astype(cfg.dtype), up.astype(cfg.dtype),
            down.astype(cfg.dtype), weights, experts, valid)
        with jax.named_scope("moe.shared"):
            shared = GatedMLP(cfg, cfg.shared_experts * f, name="shared")(x)
        with jax.named_scope("moe.combine"):
            return (out + shared).reshape(hidden.shape)


class DeepseekV3Block(nn.Module):
    config: DeepseekV3Config
    dense: bool

    @nn.compact
    def __call__(self, hidden, positions, rotary, *, kv_lengths=None,
                 cache=None, valid=None):
        cfg = self.config

        def norm(name):
            return RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype,
                           name=name)

        attended, new_cache = LatentAttention(cfg, name="attention")(
            norm("attn_norm")(hidden), positions, rotary,
            kv_lengths=kv_lengths, cache=cache)
        hidden = hidden + attended
        x = norm("mlp_norm")(hidden)
        if self.dense:
            with jax.named_scope("mlp"):
                x = GatedMLP(cfg, cfg.intermediate_size, name="mlp")(x)
        else:
            x = ExpertLayer(cfg, name="experts")(x, valid)
        return hidden + x, new_cache


class DeepseekV3LM(nn.Module):
    """Token ids -> next-token logits; arguments and returns as
    `decoder.DecoderLM` (which documents the modes), a layer's cache as
    the module's docstring says."""

    config: DeepseekV3Config

    def routed_pairs(self, state):
        """[expert layers, experts] int32 (token, expert) pairs, from the
        `moe` collection an apply with `mutable=["moe"]` returned."""
        cfg = self.config
        return jnp.stack([
            state["moe"][f"layer_{i}"]["experts"]["pairs"]
            for i in range(cfg.first_dense_layers, cfg.num_layers)])

    @nn.compact
    def __call__(self, input_ids, positions: Optional[Any] = None,
                 kv_cache: Optional[Any] = None,
                 kv_lengths: Optional[Any] = None,
                 return_cache: bool = False,
                 logit_positions: Optional[Any] = None,
                 valid: Optional[Any] = None):
        cfg = self.config
        b, l = input_ids.shape
        if positions is None:
            pos = jnp.broadcast_to(jnp.arange(l)[None, :], (b, l))
        else:
            pos = positions.reshape(b, -1)
        # Tokens no request owns are given to no expert (models/olmoe.py
        # says which those are).
        if kv_lengths is not None:
            valid = jnp.arange(l)[None, :] < kv_lengths[:, None]
        elif kv_cache is not None and l > 1:
            pool, table = kv_cache[0]
            valid = pos < table.shape[1] * pool.shape[1]
        hidden = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                          param_dtype=cfg.param_dtype,
                          name="wte")(input_ids)
        rotary = rope_tables(pos, cfg.qk_rope_head_dim, cfg.rope_theta)
        caches = []
        for i in range(cfg.num_layers):
            hidden, new_cache = DeepseekV3Block(
                cfg, i < cfg.first_dense_layers, name=f"layer_{i}")(
                    hidden, pos, rotary, kv_lengths=kv_lengths,
                    cache=None if kv_cache is None else kv_cache[i],
                    valid=valid)
            caches.append(new_cache)
        if logit_positions is not None:
            hidden = jnp.take_along_axis(
                hidden, logit_positions.reshape(b, -1, 1), axis=1)
        with jax.named_scope("head"):
            hidden = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype,
                             name="final_norm")(hidden)
            logits = _Head(cfg, name="lm_head")(hidden)
        if kv_cache is not None or return_cache:
            return logits, caches
        return logits


def deepseek_v3_tiny(**overrides):
    """3 layers (1 dense + 2 of experts), 4 heads of 16 + 8 over a rank of
    24, values of 12, 8 experts of 24 (3 a token) and a shared one of 48,
    float32: hermetic CPU tests.  No width is a lane multiple."""
    defaults = dict(vocab_size=384, hidden_size=96, num_layers=3,
                    num_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
                    v_head_dim=12, kv_lora_rank=24, intermediate_size=160,
                    moe_intermediate_size=24, num_experts=8,
                    experts_per_token=3, shared_experts=2,
                    first_dense_layers=1, routed_scaling_factor=2.446,
                    max_seq=256, rope_theta=1e4, dtype=jnp.float32,
                    param_dtype=jnp.float32)
    defaults.update(overrides)
    return DeepseekV3Config(**defaults)


def _create_deepseek_v3(seq_len=64, **kw):
    """Registry factory: 'deepseek_v3' (the defaults are
    Moonlight-16B-A3B's published sizes, all 27 layers: 15.96 B)."""
    return DeepseekV3LM(DeepseekV3Config(**kw)), jnp.zeros((1, seq_len),
                                                          jnp.int32)


def _create_deepseek_v3_tiny(seq_len=32, **kw):
    """Registry factory: 'deepseek_v3_tiny'."""
    return DeepseekV3LM(deepseek_v3_tiny(**kw)), jnp.zeros((1, seq_len),
                                                           jnp.int32)
