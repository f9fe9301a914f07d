"""Mellum 2 (JetBrains `Mellum2-12B-A2.5B-Instruct`, `model_type` `mellum`):
a decoder whose layers alternate three sliding-window layers with one that
sees the whole context, every MLP a routed expert layer of 64 experts, 8 a
token, their weights renormalised.  The published config's keys are the
Qwen3-MoE convention's (`use_sliding_window`, `max_window_layers`,
`norm_topk_prob`, `moe_intermediate_size`); what it leaves to the modelling
code is taken from that family and named below as assumed.

The serving contract is `models/decoder.py`'s, mode for mode, and attention
and cache dispatch is `decoder.cached_attention`, with each layer's window.
One layer:

    h = RMSNorm(x)
    q = h·Wq [heads, D];  k = h·Wk [kv heads, D];  v = h·Wv [kv heads, D]
        D = `head_dim` is the config's own, not hidden / heads
    q, k = RMSNorm over D, per head, one scale for q and one for k
        (assumed: the Qwen3 convention has no key for it and always has it)
    q, k = rope_t(q), rope_t(k)    t = the layer's type; rotate-half over D
        sliding_attention: inv_freq_i = theta^(-2i/D); cos, sin as they are
        full_attention: YaRN (`yarn_inv_freq`), cos and sin both times
        `attention_factor`
    query head j reads KV head j // (heads / kv heads); key s is visible to
        query t iff s <= t and, in a sliding layer, s > t - window
        (assumed: `transformers`' sliding mask; the window holds the query)
    x = x + softmax(q·kᵀ/sqrt(D))·v · Wo
    h = RMSNorm(x);  p = softmax(h·Wr) over all experts in float32;
        top k;  w = p_top / Σ p_top   (`norm_topk_prob`)
    x = x + Σ_k w_k · down_k(silu(h·gate_k) ⊙ h·up_k)

then a final RMSNorm and an untied head.  No biases, no position table, no
shared expert, no dense MLP (`intermediate_size` is the dense width and no
layer uses it).  The "MTP head" of the model card has no key in the config
and is left out.

A sliding layer declares its window in `cache_layers()`, so the engine
keeps it a ring of ceil(window / block) + 1 blocks a sequence in a pool of
its own, beside the full layers' pool (engine/generator.py).  Two rotary
tables are made once a forward pass (`rope.tables`); attention runs under
`attn.window` or `attn.full` inside `attn`; the expert layer is
`models/olmoe.py`'s, scopes and routing counts and all.
"""

import math
from typing import Any, Dict, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from kfserving_tpu.models.decoder import KVCache, cached_attention
from kfserving_tpu.models.olmoe import (
    ExpertLayer,
    RMSNorm,
    _Head,
    rope,
    rope_tables,
)

SLIDING, FULL = "sliding_attention", "full_attention"
# The published rotary sections, one a layer type.
ROPE_PARAMETERS = {
    FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
           "original_max_position_embeddings": 8192, "beta_fast": 32,
           "beta_slow": 1, "attention_factor": 1.2772588722239782},
    SLIDING: {"rope_type": "default", "rope_theta": 500000},
}


def layer_pattern(num_layers: int) -> tuple:
    """The published pattern cut to `num_layers`: three sliding layers,
    then a full one."""
    return tuple(FULL if i % 4 == 3 else SLIDING for i in range(num_layers))


class MellumConfig:
    def __init__(self, vocab_size=98304, hidden_size=2304, num_layers=28,
                 num_heads=32, num_kv_heads=4, head_dim=128,
                 moe_intermediate_size=896, num_experts=64,
                 experts_per_token=8, norm_topk_prob=True,
                 layer_types: Optional[Sequence[str]] = None,
                 sliding_window=1024,
                 rope_parameters: Optional[Dict[str, Dict]] = None,
                 max_seq=131072, rms_norm_eps=1e-6, dtype=jnp.bfloat16,
                 param_dtype=jnp.bfloat16, attn_fn=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts
        self.experts_per_token = experts_per_token
        self.norm_topk_prob = bool(norm_topk_prob)
        self.layer_types = tuple(layer_types or layer_pattern(num_layers))
        if len(self.layer_types) != num_layers or set(
                self.layer_types) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types must name {num_layers} layers, each "
                f"{SLIDING!r} or {FULL!r}: {self.layer_types}")
        self.sliding_window = int(sliding_window)
        self.rope_parameters = dict(rope_parameters or ROPE_PARAMETERS)
        self.max_seq = max_seq
        self.rms_norm_eps = rms_norm_eps
        self.dtype = jnp.dtype(dtype)
        self.param_dtype = jnp.dtype(param_dtype)
        self.attn_fn = attn_fn

    @property
    def intermediate_size(self):
        """One expert's width, under the name `olmoe.ExpertLayer` reads."""
        return self.moe_intermediate_size

    def window_of(self, layer: int) -> Optional[int]:
        return (self.sliding_window
                if self.layer_types[layer] == SLIDING else None)

    def cache_layers(self):
        return [KVCache(self.num_kv_heads, self.head_dim, self.window_of(i))
                for i in range(self.num_layers)]

    def resident_dtypes(self, variables):
        """The dtype the programs read each leaf in (`engine/param_cache.
        place_on_device`): the projections, the experts, the embedding and
        the head are cast to `dtype` before they multiply or gather; the
        norms' scales and the router's kernel are read in float32 whatever
        `dtype` is, so they rest as stored."""
        read = self.dtype

        def of(path, leaf):
            names = {getattr(key, "key", None) for key in path}
            as_stored = names & {"scale", "router"}
            return jnp.dtype(leaf.dtype) if as_stored else read

        return jax.tree_util.tree_map_with_path(of, variables)

    def param_counts(self):
        """As `OlmoeConfig.param_counts`: `per_expert`, `always_read` (not
        the embedding table), `active`, `total`."""
        h, layers = self.hidden_size, self.num_layers
        q, kv = self.num_heads * self.head_dim, \
            self.num_kv_heads * self.head_dim
        per_expert = 3 * h * self.moe_intermediate_size
        per_layer = (2 * h * q + 2 * h * kv + h * self.num_experts
                     + 2 * h + 2 * self.head_dim)
        always = layers * per_layer + h + h * self.vocab_size
        return {
            "per_expert": per_expert,
            "always_read": always,
            "active": always + layers * self.experts_per_token * per_expert,
            "total": (always + h * self.vocab_size
                      + layers * self.num_experts * per_expert),
        }


def yarn_correction_range(head_dim: int, theta: float, original_max: int,
                          beta_fast: float, beta_slow: float):
    """(low, high): the rotary pairs between which YaRN blends, the pair
    that turns `beta_fast` times over the original context rounded down
    and the one that turns `beta_slow` times rounded up (18 and 35 at the
    published sizes)."""
    def pair(turns):
        return head_dim * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    return (max(math.floor(pair(beta_fast)), 0),
            min(math.ceil(pair(beta_slow)), head_dim - 1))


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_max: int, beta_fast: float, beta_slow: float):
    """[D/2] float32: pair i turns at theta^(-2i/D) below `low` (fast
    pairs extrapolate), at that over `factor` above `high` (slow pairs
    interpolate), and at a linear blend of the two between."""
    low, high = yarn_correction_range(head_dim, theta, original_max,
                                      beta_fast, beta_slow)
    half = head_dim // 2
    plain = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return (1.0 - ramp) * plain + ramp * plain / factor


def rotary_tables(positions, head_dim: int, section: Dict[str, Any]):
    """(cos, sin) [B, L, 1, D/2] of absolute positions [B, L] for one
    layer type's section of `rope_parameters`."""
    theta = float(section["rope_theta"])
    if section.get("rope_type", "default") != "yarn":
        return rope_tables(positions, head_dim, theta)
    inv_freq = yarn_inv_freq(
        head_dim, theta, float(section["factor"]),
        int(section["original_max_position_embeddings"]),
        float(section["beta_fast"]), float(section["beta_slow"]))
    scale = section.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(float(section["factor"])) + 1.0
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    return (scale * jnp.cos(angles)[:, :, None, :],
            scale * jnp.sin(angles)[:, :, None, :])


class MellumBlock(nn.Module):
    config: MellumConfig
    window: Optional[int]

    @nn.compact
    def __call__(self, hidden, positions, rotary, *, kv_lengths=None,
                 cache=None, valid=None):
        cfg = self.config

        def norm(name):
            return RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype,
                           name=name)

        def proj(name, heads):
            return nn.DenseGeneral((heads, cfg.head_dim), use_bias=False,
                                   dtype=cfg.dtype,
                                   param_dtype=cfg.param_dtype, name=name)

        with jax.named_scope("attn"):
            x = norm("attn_norm")(hidden)
            # Per head: the norm runs over the last axis, D, one scale [D].
            q = norm("q_norm")(proj("query", cfg.num_heads)(x))
            k = norm("k_norm")(proj("key", cfg.num_kv_heads)(x))
            v = proj("value", cfg.num_kv_heads)(x)
            q, k = rope(q, rotary), rope(k, rotary)
            with jax.named_scope(
                    "attn.full" if self.window is None else "attn.window"):
                out, new_cache = cached_attention(
                    q, k, v, cache=cache,
                    positions=None if cache is None else positions,
                    kv_lengths=kv_lengths, attn_fn=cfg.attn_fn,
                    window=self.window)
            hidden = hidden + nn.DenseGeneral(
                cfg.hidden_size, axis=(-2, -1), use_bias=False,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name="out")(out)
        x = norm("mlp_norm")(hidden)
        x = ExpertLayer(cfg, name="experts")(x, valid)
        with jax.named_scope("moe.combine"):
            hidden = hidden + x
        return hidden, new_cache


class MellumLM(nn.Module):
    """Token ids -> next-token logits; arguments and returns as
    `decoder.DecoderLM` (which documents the modes).  `kv_cache[i]` is
    layer i's (pool_k, pool_v, table): a sliding layer's its ring."""

    config: MellumConfig

    def routed_pairs(self, state):
        """[layers, experts] int32 (token, expert) pairs, as
        `OlmoeLM.routed_pairs`."""
        return jnp.stack([
            state["moe"][f"layer_{i}"]["experts"]["pairs"]
            for i in range(self.config.num_layers)])

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
        # Padding is given to no expert, as in `OlmoeLM`: past kv_lengths
        # in a prefill bucket, past what a full layer's table can hold in
        # a chunk (a ring's table holds every position), and a decode
        # step's rows that the engine says are not `valid`.
        if kv_lengths is not None:
            valid = jnp.arange(l)[None, :] < kv_lengths[:, None]
        elif kv_cache is not None and l > 1:
            pool_k, _, table = kv_cache[cfg.layer_types.index(FULL)]
            valid = pos < table.shape[1] * pool_k.shape[1]
        hidden = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                          param_dtype=cfg.param_dtype,
                          name="wte")(input_ids)
        with jax.named_scope("rope.tables"):
            rotary = {kind: rotary_tables(pos, cfg.head_dim,
                                          cfg.rope_parameters[kind])
                      for kind in sorted(set(cfg.layer_types))}
        caches = []
        for i, kind in enumerate(cfg.layer_types):
            hidden, new_cache = MellumBlock(
                cfg, cfg.window_of(i), name=f"layer_{i}")(
                hidden, pos, rotary[kind], kv_lengths=kv_lengths,
                cache=None if kv_cache is None else kv_cache[i],
                valid=valid)
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


def mellum_tiny(**overrides):
    """4 layers in the published pattern, window 16, 8 query heads on 2 KV
    heads of 32 (not hidden / heads), 8 experts of 64, 2 a token, float32:
    hermetic CPU tests whose sequences are several windows long.  The
    YaRN section is the published one on an original context of 32, so
    that its blend falls inside the 16 rotary pairs."""
    rope = {FULL: dict(ROPE_PARAMETERS[FULL],
                       original_max_position_embeddings=32),
            SLIDING: dict(ROPE_PARAMETERS[SLIDING])}
    defaults = dict(vocab_size=384, hidden_size=128, num_layers=4,
                    num_heads=8, num_kv_heads=2, head_dim=32,
                    moe_intermediate_size=64, num_experts=8,
                    experts_per_token=2, sliding_window=16,
                    rope_parameters=rope, max_seq=512, dtype=jnp.float32,
                    param_dtype=jnp.float32)
    defaults.update(overrides)
    return MellumConfig(**defaults)


def _create_mellum(seq_len=64, **kw):
    """Registry factory: 'mellum' (the defaults are the published sizes of
    Mellum2-12B-A2.5B-Instruct, 12.15 B parameters)."""
    return MellumLM(MellumConfig(**kw)), jnp.zeros((1, seq_len), jnp.int32)


def _create_mellum_tiny(seq_len=32, **kw):
    """Registry factory: 'mellum_tiny'."""
    return MellumLM(mellum_tiny(**kw)), jnp.zeros((1, seq_len), jnp.int32)
