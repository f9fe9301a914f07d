"""Falcon-H1 (TII 2025; `tiiuae/Falcon-H1-34B-Instruct`, `model_type`
`falcon_h1`): a decoder whose every layer runs an attention mixer and a
Mamba-2 mixer side by side on the same normed input and adds them, then a
SwiGLU MLP, with muP-style scalar multipliers on the embedding, on both
mixers' inputs and outputs, on the keys, on the five segments of the SSM's
in-projection, on the MLP's gate and output, and on the logits.

With `h = RMSNorm(x)` (eps 1e-5), at the 34B model's sizes:

    x0   = embed(ids) · embedding_multiplier
    attn:  h' = h · attention_in_multiplier
           q = h'·W_q (20 x 128),  k = (h'·W_k) · key_multiplier (4 x 128),
           v = h'·W_v (4 x 128)                              no biases
           q, k <- rotary(q, k), rotate-half over the whole head, theta 1e11
           causal softmax(q·kᵀ/√128)·v, query head j on KV head j // 5
           a = (o·W_o) · attention_out_multiplier
    ssm:   [z | xBC | dt] = ((h · ssm_in_multiplier)·W_in) ⊙ mup_vector
                            5120 -> 4096 + 5120 + 32, no bias
           mup_vector = ssm_multipliers[0..4] on the segments z (4096),
                        x (4096), B (512), C (512), dt (32)
           xBC_t <- silu(Σ_{j<4} w[:, j]·xBC_{t-3+j} + b)   depthwise, causal
           xBC -> x [32, 128], B [2, 256], C [2, 256];  head h reads group
                  h // 16
           Δ = softplus(dt + dt_bias),  A = -exp(A_log)
           S_t[h] = exp(Δ_t[h]·A[h])·S_{t-1}[h] + Δ_t[h]·x_t[h] ⊗ B_t[g]
           y_t[h] = S_t[h]·C_t[g] + D[h]·x_t[h]        S[h] 128 x 256, float32
           y <- RMSNorm_grouped(y ⊙ silu(z))   gate first
                  (`mamba_norm_before_gate` false), 2 groups of 2048, one
                  scale
           m = (y·W_out) · ssm_out_multiplier          4096 -> 5120
                  (`mamba_d_ssm` 4096; `mamba_expand` is not read)
    x  <- x + (m + a)
    h2 = RMSNorm(x)
    x  <- x + ((up(h2) ⊙ silu(gate(h2) · mlp_multipliers[0]))·W_down)
              · mlp_multipliers[1]                           width 21504
    logits = (RMSNorm(x_L)·W_head) · lm_head_multiplier   untied, float32

Every multiplier is applied where it stands above, in float32 on a value
that is then rounded to the activations' dtype once (a Python scalar times
a bfloat16 array would round the multiplier itself to 8 bits first); none
is folded into a weight.

Cache, per layer and BOTH of them (`decoder.BothCaches`): K/V rows of 4 KV
heads of 128 in the block pool (2 KiB a token in bfloat16), and per slot
the state S [32, 128, 256] float32 (4 MiB) with the last 3 pre-activation
xBC rows [3, 5120].  Prefill returns a layer's ((k, v), (S, conv)) at each
row's own length; decode takes ((pool_k, pool_v, table), (S, conv)) and
returns the same without the table.  The Mamba mixer is
models/nemotron_h.py's (given this model's three kinds of multiplier) over
ops/ssm.py's conv and recurrence, the attention `decoder.cached_attention`:
the pool and the decode kernel serve this model as they do the others.  A
chunk prefill is not served (models/nemotron_h.py says why; the engine
refuses the settings that would ask for one).

Parameters are stored in `param_dtype` (bfloat16 as served) but `A_log`,
`D`, `dt_bias`, which stay float32 as the recurrence and its state do.
Seeded random weights are flax's fan-in initialisations, and Mamba-2's own
for the recurrence (models/nemotron_h.py draws them).  What no key of
the published config fixes is listed under `assumed` in
chipbench/configs/falcon-h1-34b-6l.json.

Scopes (`jax.named_scope`): `attn`, `ssm.in_proj`, `ssm.conv`, `ssm.scan`,
`ssm.out` as the other hybrid's, and `mlp`, `head`.
"""

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from kfserving_tpu.models.decoder import (
    BothCaches,
    KVCache,
    StateCache,
    cached_attention,
)
from kfserving_tpu.models.nemotron_h import MambaMixer, scaled
from kfserving_tpu.models.olmoe import RMSNorm, _Head, rope, rope_tables


class FalconH1Config:
    def __init__(self, vocab_size=261120, hidden_size=5120, num_layers=72,
                 num_heads=20, num_kv_heads=4, head_dim=128,
                 intermediate_size=21504, mamba_heads=32, mamba_head_dim=128,
                 mamba_d_ssm=4096, ssm_groups=2, ssm_state=256,
                 conv_kernel=4, chunk_size=128, rope_theta=1e11,
                 rms_norm_eps=1e-5, embedding_multiplier=5.656854249492381,
                 attention_in_multiplier=1.0,
                 attention_out_multiplier=0.0375,
                 key_multiplier=0.011048543456039804,
                 ssm_in_multiplier=0.25,
                 ssm_multipliers=(0.3535533905932738, 0.25,
                                  0.1767766952966369, 0.5,
                                  0.3535533905932738),
                 ssm_out_multiplier=0.08838834764831845,
                 mlp_multipliers=(0.1767766952966369,
                                  0.011160714285714284),
                 lm_head_multiplier=0.0078125, max_seq=262144,
                 dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                 attn_fn=None):
        if mamba_heads * mamba_head_dim != mamba_d_ssm:
            raise ValueError(
                f"{mamba_heads} Mamba heads of {mamba_head_dim} are not "
                f"mamba_d_ssm {mamba_d_ssm}")
        if num_heads % num_kv_heads or mamba_heads % ssm_groups:
            raise ValueError(
                f"{num_heads} query heads on {num_kv_heads} KV heads, "
                f"{mamba_heads} Mamba heads in {ssm_groups} groups: each "
                "must divide")
        if len(ssm_multipliers) != 5 or len(mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers are 5 (z, x, B, C, dt) and "
                             "mlp_multipliers 2 (gate, down)")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.intermediate_size = intermediate_size
        self.mamba_heads = mamba_heads
        self.mamba_head_dim = mamba_head_dim
        self.ssm_groups = ssm_groups
        self.ssm_state = ssm_state
        self.conv_kernel = conv_kernel
        self.chunk_size = chunk_size
        self.rope_theta = float(rope_theta)  # 1e11 as an int is no int32
        self.rms_norm_eps = rms_norm_eps
        self.embedding_multiplier = float(embedding_multiplier)
        self.attention_in_multiplier = float(attention_in_multiplier)
        self.attention_out_multiplier = float(attention_out_multiplier)
        self.key_multiplier = float(key_multiplier)
        self.ssm_in_multiplier = float(ssm_in_multiplier)
        self.ssm_multipliers = tuple(float(m) for m in ssm_multipliers)
        self.ssm_out_multiplier = float(ssm_out_multiplier)
        self.mlp_multipliers = tuple(float(m) for m in mlp_multipliers)
        self.lm_head_multiplier = float(lm_head_multiplier)
        self.max_seq = max_seq
        self.dtype = jnp.dtype(dtype)
        self.param_dtype = jnp.dtype(param_dtype)
        self.attn_fn = attn_fn

    @property
    def mamba_inner(self):
        return self.mamba_heads * self.mamba_head_dim  # `mamba_d_ssm`

    @property
    def conv_width(self):
        return self.mamba_inner + 2 * self.ssm_groups * self.ssm_state

    def cache_layers(self):
        """Every layer keeps both: K/V rows of the block pool and a
        per-slot state (shape and dtype of each array, without the slot
        axis)."""
        both = BothCaches(
            KVCache(self.num_kv_heads, self.head_dim),
            StateCache((
                ((self.mamba_heads, self.mamba_head_dim, self.ssm_state),
                 jnp.dtype(jnp.float32)),
                ((self.conv_kernel - 1, self.conv_width), self.dtype)),
                self.chunk_size))
        return [both] * self.num_layers


class AttentionMixer(nn.Module):
    config: FalconH1Config

    @nn.compact
    def __call__(self, hidden, positions, rotary, *, kv_lengths=None,
                 cache=None, segments=None):
        cfg = self.config

        def proj(name, heads):
            return nn.DenseGeneral((heads, cfg.head_dim), use_bias=False,
                                   dtype=cfg.dtype,
                                   param_dtype=cfg.param_dtype, name=name)

        with jax.named_scope("attn"):
            x = scaled(hidden, cfg.attention_in_multiplier)
            q = proj("query", cfg.num_heads)(x)
            k = scaled(proj("key", cfg.num_kv_heads)(x), cfg.key_multiplier)
            v = proj("value", cfg.num_kv_heads)(x)
            q, k = rope(q, rotary), rope(k, rotary)
            out, new_cache = cached_attention(
                q, k, v, cache=cache,
                positions=None if cache is None else positions,
                kv_lengths=kv_lengths, attn_fn=cfg.attn_fn,
                segments=segments)
            out = nn.DenseGeneral(cfg.hidden_size, axis=(-2, -1),
                                  use_bias=False, dtype=cfg.dtype,
                                  param_dtype=cfg.param_dtype,
                                  name="out")(out)
            out = scaled(out, cfg.attention_out_multiplier)
        return out, new_cache


class GatedMLP(nn.Module):
    config: FalconH1Config

    @nn.compact
    def __call__(self, hidden):
        cfg = self.config

        def dense(name, width):
            return nn.Dense(width, use_bias=False, dtype=cfg.dtype,
                            param_dtype=cfg.param_dtype, name=name)

        gate_multiplier, down_multiplier = cfg.mlp_multipliers
        with jax.named_scope("mlp"):
            gate = jax.nn.silu(scaled(
                dense("gate", cfg.intermediate_size)(hidden),
                gate_multiplier).astype(jnp.float32))
            up = dense("up", cfg.intermediate_size)(hidden)
            wide = (up.astype(jnp.float32) * gate).astype(cfg.dtype)
            return scaled(dense("down", cfg.hidden_size)(wide),
                          down_multiplier)


class FalconH1Block(nn.Module):
    config: FalconH1Config

    @nn.compact
    def __call__(self, hidden, positions, rotary, *, kv_lengths=None,
                 cache=None, packed=None):
        """cache None, or the layer's pair ((pool_k, pool_v, table),
        (S, conv)); returns its pair without the table (prefill:
        ((k, v), (S, conv)); a packed one, `packed` as
        `nemotron_h.MambaMixer` takes it: (S, conv) a prompt)."""
        cfg = self.config

        def norm(name):
            return RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype,
                           name=name)

        x = norm("norm")(hidden)
        rows, state = (None, None) if cache is None else cache
        attended, rows = AttentionMixer(cfg, name="attention")(
            x, positions, rotary, kv_lengths=kv_lengths, cache=rows,
            segments=None if packed is None else packed[0])
        mixed, state = MambaMixer(
            cfg, in_scale=cfg.ssm_in_multiplier,
            segment_scales=cfg.ssm_multipliers,
            out_scale=cfg.ssm_out_multiplier, name="mamba")(
                x, kv_lengths=kv_lengths, cache=state, packed=packed)
        hidden = hidden + (mixed + attended)
        hidden = hidden + GatedMLP(cfg, name="mlp")(norm("mlp_norm")(hidden))
        return hidden, (rows, state)


class FalconH1LM(nn.Module):
    """Token ids -> next-token logits; arguments and returns as
    `decoder.DecoderLM` (which documents the modes), every layer's cache
    the pair the module's docstring describes.  A packed prefill
    (`segments`, restarted `positions`, which the rotary reads,
    `logit_positions` [B, P]) returns a layer's K/V by row and its
    (S, conv) a prompt, as `nemotron_h.NemotronHLM`'s does."""

    config: FalconH1Config

    @nn.compact
    def __call__(self, input_ids, positions: Optional[Any] = None,
                 kv_cache: Optional[Any] = None,
                 kv_lengths: Optional[Any] = None,
                 return_cache: bool = False,
                 logit_positions: Optional[Any] = None,
                 segments: Optional[Any] = None):
        cfg = self.config
        b, l = input_ids.shape
        if positions is None:
            pos = jnp.broadcast_to(jnp.arange(l)[None, :], (b, l))
        else:
            pos = positions.reshape(b, -1)
        packed = (None if segments is None
                  else (segments, pos, logit_positions))
        hidden = scaled(
            nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="wte")(input_ids),
            cfg.embedding_multiplier)
        rotary = rope_tables(pos, cfg.head_dim, cfg.rope_theta)
        caches = []
        for i in range(cfg.num_layers):
            hidden, new_cache = FalconH1Block(cfg, name=f"layer_{i}")(
                hidden, pos, rotary, kv_lengths=kv_lengths,
                cache=None if kv_cache is None else kv_cache[i],
                packed=packed)
            caches.append(new_cache)
        if logit_positions is not None:
            hidden = jnp.take_along_axis(
                hidden, logit_positions.reshape(b, -1, 1), axis=1)
        with jax.named_scope("head"):
            hidden = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype,
                             name="final_norm")(hidden)
            logits = _Head(cfg, name="lm_head")(hidden) \
                * cfg.lm_head_multiplier
        if kv_cache is not None or return_cache:
            return logits, caches
        return logits


def falcon_h1_tiny(**overrides):
    """3 layers, 5 query heads on each of 2 KV heads, 4 Mamba heads in 2
    groups, `mamba_d_ssm` 48 where `mamba_expand` x hidden would be 192,
    every multiplier another value and none 1, float32: hermetic CPU
    tests.  No width is a lane multiple."""
    defaults = dict(vocab_size=384, hidden_size=96, num_layers=3,
                    num_heads=10, num_kv_heads=2, head_dim=16,
                    intermediate_size=160, mamba_heads=4, mamba_head_dim=12,
                    mamba_d_ssm=48, ssm_groups=2, ssm_state=8,
                    chunk_size=16, rope_theta=1e4,
                    embedding_multiplier=2.5, attention_in_multiplier=0.8,
                    attention_out_multiplier=0.6, key_multiplier=1.7,
                    ssm_in_multiplier=1.3,
                    ssm_multipliers=(0.7, 1.4, 0.55, 1.6, 0.9),
                    ssm_out_multiplier=0.45, mlp_multipliers=(1.9, 0.35),
                    lm_head_multiplier=0.3, max_seq=256,
                    dtype=jnp.float32, param_dtype=jnp.float32)
    defaults.update(overrides)
    return FalconH1Config(**defaults)


def _create_falcon_h1(seq_len=64, **kw):
    """Registry factory: 'falcon_h1' (the defaults are Falcon-H1-34B's
    published sizes, all 72 layers: 33.6 B)."""
    return FalconH1LM(FalconH1Config(**kw)), jnp.zeros((1, seq_len),
                                                      jnp.int32)


def _create_falcon_h1_tiny(seq_len=32, **kw):
    """Registry factory: 'falcon_h1_tiny'."""
    return FalconH1LM(falcon_h1_tiny(**kw)), jnp.zeros((1, seq_len),
                                                       jnp.int32)
