"""Nemotron-H (NVIDIA 2025; `nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`,
`model_type` `nemotron_h`): a hybrid decoder whose layers are of three
kinds, drawn by `hybrid_override_pattern` (`M` Mamba-2, `*` attention, `E`
routed experts), each wrapped the same way:

    x <- x + Mixer_c(RMSNorm(x))        eps 1e-5, residual in the model's
                                        dtype (`residual_in_fp32` false)

after a token embedding with no position table, and before a final RMSNorm
and an untied head whose logits are float32.

`M`, Mamba-2 (H = 64 heads of P = 64 -> inner 4096; G = 8 groups; state
N = 128; conv kernel 4; `expand` is not read):
    [z | xBC | dt] = h·W_in                  2688 -> 4096 + 6144 + 64, no bias
    xBC_t <- silu(Σ_{j<4} w[:, j]·xBC_{t-3+j} + b)   depthwise, causal,
                                             zeros before the sequence
    xBC -> x [64, 64], B [8, 128], C [8, 128];  head h reads group h // 8
    Δ = softplus(dt + dt_bias),  A = -exp(A_log)     one scalar a head
    S_t[h] = exp(Δ_t[h]·A[h])·S_{t-1}[h] + Δ_t[h]·x_t[h] ⊗ B_t[g]
    y_t[h] = S_t[h]·C_t[g] + D[h]·x_t[h]             S[h] 64 x 128, float32
    y <- RMSNorm_grouped(y ⊙ silu(z))        gate, then norm: over groups
                                             of 4096/8 = 512, one scale
    out = y·W_out                            4096 -> 2688
  Cache, per slot: S [64, 64, 128] float32 and the last 3 pre-activation
  xBC rows [3, 6144].  (ops/ssm.py holds the conv and the recurrence.)

`*`, attention: q = h·W_q (32 x 128), k, v = h·W_k, h·W_v (2 x 128 each),
no bias and NO rotary or other position encoding (the `nemotron_h`
modelling code applies none; `rope_theta` and `partial_rotary_factor` are
not read), causal softmax(q·kᵀ/√128)·v with query head j on KV head
j // 16, then ·W_o (4096 -> 2688).  `decoder.cached_attention`, the pool
and the decode kernel serve it as they do the other decoders.

`E`, experts: s = sigmoid(h·W_r) in float32 over all 128; the 6 largest of
s + b are chosen (b = `e_score_correction_bias`: for the choice alone;
`n_group` = `topk_group` = 1, so group-limited routing is the identity);
w_i = 2.5·s_i / (Σ_chosen s + 1e-20); an expert is not gated:
down_i(relu(up_i·h)²), two matrices of width 1856;
out = Σ_chosen w_i·expert_i(h) + shared(h), the shared expert the same form
at width 3712.  No token dropped, no capacity.

**The share.**  `experts_held = (first, count)` tells every expert layer
which of the routed experts live here (expert parallelism: one chip's).
It routes over all of them and normalises over all 6 chosen, computes the
pairs whose expert it holds, adds the whole shared expert, and that partial
sum goes on.  What the other experts would add is left out; nothing stands
in for the other chips or their exchange.

Departures, of layout and none of mathematics: projections are kept
[hidden, heads, head size] and experts stacked [held, in, out]; an expert
width that is no lane multiple is stored padded with zeros to the next one
(1856 as 1920: `relu(0)² = 0` and zero rows of `down` add nothing; a TPU
array tiles its minor dimension to 128 lanes anyway, and the decode kernel
takes a matrix as one whole block).  Seeded random weights are flax's
usual fan-in initialisations but for the recurrence, which gets Mamba-2's
own so that it runs in its real regime: `A_log = log U(1, 16)`, `dt_bias`
the inverse softplus of a log-uniform draw in [0.001, 0.1] floored at 1e-4,
`D = 1`, `b = 0`.  Stored in `param_dtype` (bfloat16 as served) but for
`A_log`, `D`, `dt_bias` and `b`, which stay float32.

The serving contract is `models/decoder.py`'s (full, prefill, decode,
`logit_positions`), with one difference the engine reads from
`config.cache_layers()`: a layer's cache is K/V rows in the block pool
(`*`), a per-slot state (`M`) or nothing (`E`).  Prefill returns, per
layer, (k, v), (S, conv) at each row's own length, or (); decode takes
(pool_k, pool_v, table), (S, conv) or () and returns the same without the
table.  A chunk prefill (several tokens onto a cache) is not served: the
engine refuses the settings that would ask for one.
"""

import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from kfserving_tpu.models.decoder import KVCache, StateCache, cached_attention
from kfserving_tpu.models.olmoe import RMSNorm, _Head
from kfserving_tpu.ops import moe, ssm

PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


class NemotronHConfig:
    def __init__(self, vocab_size=131072, hidden_size=2688,
                 pattern=PUBLISHED_PATTERN, num_heads=32, num_kv_heads=2,
                 head_dim=128, mamba_heads=64, mamba_head_dim=64,
                 ssm_groups=8, ssm_state=128, conv_kernel=4, chunk_size=128,
                 intermediate_size=1856, shared_intermediate_size=3712,
                 routed_experts=128, experts_held=None, experts_per_token=6,
                 routed_scaling_factor=2.5, expert_width_multiple=128,
                 max_seq=262144, rms_norm_eps=1e-5, dtype=jnp.bfloat16,
                 param_dtype=jnp.bfloat16, attn_fn=None):
        if set(pattern) - set("M*E"):
            raise ValueError(f"layer pattern {pattern!r}: M, * and E only")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.pattern = pattern
        self.num_layers = len(pattern)
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.mamba_heads = mamba_heads
        self.mamba_head_dim = mamba_head_dim
        self.ssm_groups = ssm_groups
        self.ssm_state = ssm_state
        self.conv_kernel = conv_kernel
        self.chunk_size = chunk_size
        self.intermediate_size = intermediate_size  # one expert's width
        self.shared_intermediate_size = shared_intermediate_size
        self.routed_experts = routed_experts        # the router's width
        # (first, count) of the routed experts this chip holds.
        first, count = experts_held or (0, routed_experts)
        if not 0 <= first < first + count <= routed_experts:
            raise ValueError(f"experts_held {experts_held} of "
                             f"{routed_experts}")
        self.experts_first, self.num_experts = int(first), int(count)
        self.experts_per_token = experts_per_token
        self.routed_scaling_factor = routed_scaling_factor
        self.expert_width_multiple = expert_width_multiple
        self.max_seq = max_seq
        self.rms_norm_eps = rms_norm_eps
        self.dtype = jnp.dtype(dtype)
        self.param_dtype = jnp.dtype(param_dtype)
        self.attn_fn = attn_fn

    @property
    def mamba_inner(self):
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_width(self):
        return self.mamba_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def expert_width_stored(self):
        m = self.expert_width_multiple
        return -(-self.intermediate_size // m) * m

    def cache_layers(self):
        """What each layer keeps between steps (the engine builds its
        caches from this): K/V rows of the block pool, a per-slot state
        (shape and dtype of each array, without the slot axis), or
        None."""
        state = StateCache((
            ((self.mamba_heads, self.mamba_head_dim, self.ssm_state),
             jnp.dtype(jnp.float32)),
            ((self.conv_kernel - 1, self.conv_width), self.dtype)),
            self.chunk_size)
        kinds = {"M": state, "E": None,
                 "*": KVCache(self.num_kv_heads, self.head_dim)}
        return [kinds[c] for c in self.pattern]

    def param_counts(self):
        """As `OlmoeConfig.param_counts`, at the published expert width
        and for the experts held here: a token's `active` share of them
        is `experts_per_token` x held / routed."""
        h = self.hidden_size
        per_expert = 2 * h * self.intermediate_size
        mamba = (h * (self.mamba_inner + self.conv_width + self.mamba_heads)
                 + self.conv_width * (self.conv_kernel + 1)
                 + 3 * self.mamba_heads + self.mamba_inner
                 + self.mamba_inner * h + h)
        attn = (2 * h * self.head_dim * (self.num_heads + self.num_kv_heads)
                + h)
        expert = (h * self.routed_experts + self.routed_experts
                  + 2 * h * self.shared_intermediate_size + h)
        n = {c: self.pattern.count(c) for c in "M*E"}
        always = (n["M"] * mamba + n["*"] * attn + n["E"] * expert
                  + h + h * self.vocab_size)
        held = self.num_experts / self.routed_experts
        return {
            "per_expert": per_expert,
            "always_read": always,
            "active": int(always + n["E"] * self.experts_per_token * held
                          * per_expert),
            "total": (always + h * self.vocab_size
                      + n["E"] * self.num_experts * per_expert),
        }


def _log_uniform(lo: float, hi: float):
    def init(key, shape, dtype):
        return jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(lo), math.log(hi))
        ).astype(dtype)
    return init


def _a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                      16.0)).astype(dtype)


def _dt_bias_init(key, shape, dtype):
    dt = jnp.maximum(_log_uniform(0.001, 0.1)(key, shape, jnp.float32),
                     1e-4)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)  # softplus⁻¹


def _uniform(bound: float):
    def init(key, shape, dtype):
        return jax.random.uniform(key, shape, jnp.float32, -bound,
                                  bound).astype(dtype)
    return init


def _padded(init, axis: int, width: int):
    """`init` at `width` along `axis`, zeros up to the shape asked for."""
    def padded(key, shape, dtype):
        real = tuple(width if i == axis else n for i, n in enumerate(shape))
        return jnp.pad(init(key, real, dtype), [
            (0, n - m) for n, m in zip(shape, real)])
    return padded


def scaled(x, multiplier):
    """x · multiplier, the product made in float32 and rounded to x's
    dtype once (a Python scalar times a bfloat16 array would round the
    multiplier itself to 8 bits first).  `multiplier` is a scalar or an
    array over x's last axis."""
    return (x.astype(jnp.float32) * multiplier).astype(x.dtype)


class MambaMixer(nn.Module):
    """Mamba-2 between a block's norm and its residual.  `config` gives the
    sizes (any config with this one's Mamba attributes: models/falcon_h1.py
    brings its own).  The three scales are scalar multipliers that some
    models put around the projections, None (this model) for none: on the
    input, on the in-projection's segments (z, x, B, C, dt), on the
    output."""
    config: Any
    in_scale: Optional[float] = None
    segment_scales: Optional[Tuple[float, ...]] = None
    out_scale: Optional[float] = None

    @nn.compact
    def __call__(self, hidden, *, kv_lengths=None, cache=None, packed=None):
        """hidden [B, L, H].  cache None: the sequences start here, and
        the returned cache is each row's (S, conv) after `kv_lengths`
        tokens; or, with `packed` (`segments` [B, L], `positions` [B, L],
        `last` [B, P]: a row carries several prompts, as
        `NemotronHLM.__call__` is told them), each prompt's, [B * P, ...],
        an entry a place a prompt could start.  cache (S, conv): L == 1,
        one step of every row."""
        cfg = self.config
        b, l, _ = hidden.shape
        heads, p = cfg.mamba_heads, cfg.mamba_head_dim
        g, n = cfg.ssm_groups, cfg.ssm_state
        inner, conv = cfg.mamba_inner, cfg.conv_width
        with jax.named_scope("ssm.in_proj"):
            if self.in_scale is not None:
                hidden = scaled(hidden, self.in_scale)
            zxbcdt = nn.Dense(inner + conv + heads, use_bias=False,
                              dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                              name="in_proj")(hidden)
            if self.segment_scales is not None:
                zxbcdt = scaled(zxbcdt, jnp.concatenate([
                    jnp.full((width,), m, jnp.float32)
                    for width, m in zip((inner, inner, g * n, g * n, heads),
                                        self.segment_scales)]))
            z, xbc, dt = jnp.split(zxbcdt, [inner, inner + conv], axis=-1)
            dt_bias = self.param("dt_bias", _dt_bias_init, (heads,),
                                 jnp.float32)
            delta = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
        weight = self.param("conv_kernel",
                            _uniform(cfg.conv_kernel ** -0.5),
                            (conv, cfg.conv_kernel), cfg.param_dtype)
        bias = self.param("conv_bias", nn.initializers.zeros, (conv,),
                          cfg.param_dtype)
        a = -jnp.exp(self.param("A_log", _a_log_init, (heads,),
                                jnp.float32))
        d = self.param("D", nn.initializers.ones, (heads,), jnp.float32)

        def parts(act):
            x, bb, cc = jnp.split(act, [inner, inner + g * n], axis=-1)
            lead = act.shape[:-1]
            return (x.reshape(lead + (heads, p)), bb.reshape(lead + (g, n)),
                    cc.reshape(lead + (g, n)))

        if cache is None and packed is not None:
            segments, positions, last = packed
            act, conv_state = ssm.causal_conv(xbc, weight, bias,
                                              packed=(positions, last))
            x, bb, cc = parts(act)
            y, states = ssm.ssd_prefill(x, delta, a, bb, cc, d,
                                        chunk=cfg.chunk_size,
                                        packed=(segments, positions))
            # An entry a place a prompt could start: a block's first
            # chunk.
            every = states.shape[1] // last.shape[1]
            state = states[:, ::every].reshape((-1,) + states.shape[2:])
        elif cache is None:
            act, conv_state = ssm.causal_conv(xbc, weight, bias, kv_lengths)
            x, bb, cc = parts(act)
            y, state = ssm.ssd_prefill(x, delta, a, bb, cc, d, kv_lengths,
                                       cfg.chunk_size)
        else:
            if l != 1:
                raise ValueError(
                    "a Mamba layer steps one token a row onto its state; "
                    f"{l} were given (chunked prefill and speculative "
                    "verify are not served for this model)")
            state, conv_state = cache
            act, conv_state = ssm.conv_step(xbc[:, 0], conv_state, weight,
                                            bias)
            x, bb, cc = parts(act)
            y, state = ssm.ssd_step(state, x, delta[:, 0], a, bb, cc, d)
            y = y[:, None]
        with jax.named_scope("ssm.out"):
            y = y.reshape(b, l, g, inner // g) * jax.nn.silu(
                z.astype(jnp.float32)).reshape(b, l, g, inner // g)
            y = y * jax.lax.rsqrt(
                jnp.mean(y * y, axis=-1, keepdims=True) + cfg.rms_norm_eps)
            scale = self.param("norm_scale", nn.initializers.ones,
                               (inner,), cfg.param_dtype)
            y = (y.reshape(b, l, inner)
                 * scale.astype(jnp.float32)).astype(cfg.dtype)
            out = nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                           param_dtype=cfg.param_dtype, name="out_proj")(y)
            if self.out_scale is not None:
                out = scaled(out, self.out_scale)
        return out, (state, conv_state)


class AttentionMixer(nn.Module):
    config: NemotronHConfig

    @nn.compact
    def __call__(self, hidden, positions, *, kv_lengths=None, cache=None,
                 segments=None):
        cfg = self.config

        def proj(name, heads):
            return nn.DenseGeneral((heads, cfg.head_dim), use_bias=False,
                                   dtype=cfg.dtype,
                                   param_dtype=cfg.param_dtype, name=name)

        with jax.named_scope("attn"):
            q = proj("query", cfg.num_heads)(hidden)
            k = proj("key", cfg.num_kv_heads)(hidden)
            v = proj("value", cfg.num_kv_heads)(hidden)
            out, new_cache = cached_attention(
                q, k, v, cache=cache,
                positions=None if cache is None else positions,
                kv_lengths=kv_lengths, attn_fn=cfg.attn_fn,
                segments=segments)
            out = nn.DenseGeneral(cfg.hidden_size, axis=(-2, -1),
                                  use_bias=False, dtype=cfg.dtype,
                                  param_dtype=cfg.param_dtype,
                                  name="out")(out)
        return out, new_cache


class ExpertMixer(nn.Module):
    config: NemotronHConfig

    @nn.compact
    def __call__(self, hidden, valid=None):
        """hidden [B, L, H]; valid optional [B, L] bool (False: bucket
        padding, routed to no expert)."""
        cfg = self.config
        h, f = cfg.hidden_size, cfg.expert_width_stored
        first, held = cfg.experts_first, cfg.num_experts
        fan_in = nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1,
            batch_axis=(0,))
        up = self.param("up", _padded(fan_in, 2, cfg.intermediate_size),
                        (held, h, f), cfg.param_dtype)
        down = self.param("down", _padded(fan_in, 1, cfg.intermediate_size),
                          (held, f, h), cfg.param_dtype)
        x = hidden.reshape(-1, h)
        if valid is not None:
            valid = valid.reshape(-1)
        with jax.named_scope("moe.router"):
            logits = nn.Dense(cfg.routed_experts, use_bias=False,
                              dtype=jnp.float32,
                              param_dtype=cfg.param_dtype,
                              name="router")(x.astype(jnp.float32))
            bias = self.param("router_bias", nn.initializers.zeros,
                              (cfg.routed_experts,), jnp.float32)
            weights, experts = moe.route_sigmoid(
                logits, bias, cfg.experts_per_token,
                cfg.routed_scaling_factor)
            if not self.is_initializing():
                pairs = moe.routed_pairs(experts, held, valid, first)
                everywhere = experts.size if valid is None else (
                    jnp.sum(valid, dtype=jnp.int32) * experts.shape[1])
                for name, value in (("pairs", pairs), (
                        "elsewhere", everywhere - jnp.sum(pairs))):
                    self.sow("moe", name, value,
                             reduce_fn=lambda _, new: new,
                             init_fn=lambda: None)
        out = moe.routed_experts(x, None, up.astype(cfg.dtype),
                                 down.astype(cfg.dtype), weights, experts,
                                 valid, first)
        with jax.named_scope("moe.shared"):
            wide = nn.Dense(cfg.shared_intermediate_size, use_bias=False,
                            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                            name="shared_up")(x)
            wide = jnp.square(jax.nn.relu(wide.astype(jnp.float32)))
            shared = nn.Dense(h, use_bias=False, dtype=cfg.dtype,
                              param_dtype=cfg.param_dtype,
                              name="shared_down")(wide.astype(cfg.dtype))
        with jax.named_scope("moe.combine"):
            return (out + shared).reshape(hidden.shape)


class NemotronHBlock(nn.Module):
    config: NemotronHConfig
    kind: str

    @nn.compact
    def __call__(self, hidden, positions, *, kv_lengths=None, cache=None,
                 valid=None, packed=None):
        cfg = self.config
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype,
                    name="norm")(hidden)
        if self.kind == "M":
            out, new_cache = MambaMixer(cfg, name="mixer")(
                x, kv_lengths=kv_lengths, cache=cache, packed=packed)
        elif self.kind == "*":
            out, new_cache = AttentionMixer(cfg, name="mixer")(
                x, positions, kv_lengths=kv_lengths, cache=cache,
                segments=None if packed is None else packed[0])
        else:
            out, new_cache = ExpertMixer(cfg, name="mixer")(x, valid), ()
        return hidden + out, new_cache


class NemotronHLM(nn.Module):
    """Token ids -> next-token logits; arguments and returns as
    `decoder.DecoderLM` (which documents the modes), the caches by layer
    kind as the module's docstring says.  A packed prefill (`segments`,
    restarted `positions`, `logit_positions` [B, P]) returns a Mamba
    layer's (S, conv) a prompt, [B * P, ...], entry p of a row the prompt
    that starts at its block p and ends at `logit_positions[:, p]`: it
    needs every prompt to start at a multiple of `chunk_size`, which the
    engine sees to (`programs.packs_prompts`)."""

    config: NemotronHConfig

    def _sown(self, state, name: str):
        return jnp.stack([
            state["moe"][f"layer_{i}"]["mixer"][name]
            for i, c in enumerate(self.config.pattern) if c == "E"])

    def routed_pairs(self, state):
        """[expert layers, experts held] int32 (token, expert) pairs, from
        the `moe` collection an apply with `mutable=["moe"]` returned."""
        return self._sown(state, "pairs")

    def routed_elsewhere(self, state):
        """[expert layers] int32: pairs routed to experts not held."""
        return self._sown(state, "elsewhere")

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
        # A prefill bucket's padding is given to no expert (and, by
        # `kv_lengths` or `segments`, leaves no mark on a state), nor are
        # the rows of a decode step that the engine says are not `valid`
        # ([B, 1] bool: past their token budget; their state goes on
        # stepping, and the insert that admits the slot's next request
        # overwrites it).
        packed = None
        if kv_lengths is not None:
            valid = jnp.arange(l)[None, :] < kv_lengths[:, None]
        elif segments is not None:
            valid = segments >= 0
            packed = (segments, pos, logit_positions)
        hidden = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                          param_dtype=cfg.param_dtype,
                          name="wte")(input_ids)
        caches = []
        for i, kind in enumerate(cfg.pattern):
            hidden, new_cache = NemotronHBlock(cfg, kind, name=f"layer_{i}")(
                hidden, pos, kv_lengths=kv_lengths,
                cache=None if kv_cache is None else kv_cache[i],
                valid=valid, packed=packed)
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


def nemotron_h_tiny(**overrides):
    """6 layers of every kind (`MEM*EM`), 8 experts of 24 of which the
    first 4 are held, 2 per token, 4 query heads on each of 2 KV heads,
    float32: hermetic CPU tests.  No width is a lane multiple."""
    defaults = dict(vocab_size=384, hidden_size=96, pattern="MEM*EM",
                    num_heads=8, num_kv_heads=2, head_dim=16, mamba_heads=4,
                    mamba_head_dim=12, ssm_groups=2, ssm_state=8,
                    chunk_size=16, intermediate_size=24,
                    shared_intermediate_size=40, routed_experts=8,
                    experts_held=(0, 4), experts_per_token=2,
                    expert_width_multiple=8, max_seq=256,
                    dtype=jnp.float32, param_dtype=jnp.float32)
    defaults.update(overrides)
    return NemotronHConfig(**defaults)


def _create_nemotron_h(seq_len=64, **kw):
    """Registry factory: 'nemotron_h' (the defaults are Nemotron-3-Nano-
    30B-A3B's published sizes, all 52 layers and 128 experts: 31.6 B)."""
    return NemotronHLM(NemotronHConfig(**kw)), jnp.zeros((1, seq_len),
                                                        jnp.int32)


def _create_nemotron_h_tiny(seq_len=32, **kw):
    """Registry factory: 'nemotron_h_tiny'."""
    return NemotronHLM(nemotron_h_tiny(**kw)), jnp.zeros((1, seq_len),
                                                         jnp.int32)
