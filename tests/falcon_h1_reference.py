"""The repo's own copy of the Falcon-H1 reference
(chipbench/references/falcon_h1.py is the benchmark's; the benchmark imports
nothing from here and the tests nothing from there but for the one test that
the two agree): the forward pass of `tiiuae/Falcon-H1-34B-Instruct`
(`model_type` `falcon_h1`) in plain float32 jax.numpy, written from the
`falcon_h1` modelling code's equations.  One sequence, eagerly: no cache, no
batching, no kernel, no chunking (the recurrence is a loop over the tokens).

    x = embed(ids)·embedding_multiplier
    per layer, h = RMSNorm(x):
      attention  q = h'·W_q, k = (h'·W_k)·key_multiplier, v = h'·W_v with
                 h' = h·attention_in_multiplier; rotate-half rotary over the
                 whole head (theta `rope_theta`) on q and k; causal
                 softmax(q·kᵀ/√D)·v, query head j on KV head
                 j // (heads / KV heads); a = (o·W_o)·attention_out_multiplier
      Mamba-2    [z | xBC | dt] = ((h·ssm_in_multiplier)·W_in) ⊙ mup_vector,
                 mup_vector = ssm_multipliers[0..4] over z, x, B, C, dt;
                 xBC <- silu(causal depthwise conv(xBC) + b);
                 Δ = softplus(dt + dt_bias), A = -exp(A_log);
                 S_t = exp(Δ_t·A)·S_{t-1} + Δ_t·x_t ⊗ B_t;  y_t = S_t·C_t + D·x_t
                 (head h on group h // (H/G));  y <- RMSNorm over groups of
                 (y ⊙ silu(z)), one learned scale (gate before norm);
                 m = (y·W_out)·ssm_out_multiplier
      x <- x + m + a
      x <- x + ((up(h2) ⊙ silu(gate(h2)·mlp_multipliers[0]))·W_down)
               ·mlp_multipliers[1],   h2 = RMSNorm(x)
    logits = (RMSNorm(x)·W_head)·lm_head_multiplier

`params` is {"params/a/b": array}; `model` the published config's keys (the
multipliers, `rope_theta`, `rms_norm_eps`, `mamba_n_heads`, `mamba_n_groups`,
`mamba_d_state`); every other size is a parameter's own shape.

Departures from the published checkpoint's layout, none from its
mathematics: the attention projections are kept [hidden, heads, head size]
and the out-projection [heads, head size, hidden] (the served model's
layout); the depthwise conv's weight is [channels, kernel].
"""

import math


def rotary(x, theta: float):
    """x [L, heads, D] at positions 0..L-1; rotate-half over the whole head."""
    import jax.numpy as jnp

    length, _, d = x.shape
    inv_freq = float(theta) ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rotated * sin


def attention(h, w, model: dict):
    """h [L, hidden], normed; w: the attention mixer's parameters."""
    import jax
    import jax.numpy as jnp

    length = h.shape[0]
    wq, wk, wv = (w[name + "/kernel"] for name in ("query", "key", "value"))
    (heads, d), kv_heads = wq.shape[1:], wk.shape[1]
    x = h * model["attention_in_multiplier"]
    q = (x @ wq.reshape(wq.shape[0], -1)).reshape(length, heads, d)
    k = (x @ wk.reshape(wk.shape[0], -1)).reshape(length, kv_heads, d) \
        * model["key_multiplier"]
    v = (x @ wv.reshape(wv.shape[0], -1)).reshape(length, kv_heads, d)
    q, k = rotary(q, model["rope_theta"]), rotary(k, model["rope_theta"])
    k, v = (jnp.repeat(t, heads // kv_heads, axis=1) for t in (k, v))
    scores = jnp.einsum("qnd,knd->nqk", q, k) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((length, length), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    context = jnp.einsum("nqk,knd->qnd", jax.nn.softmax(scores, axis=-1), v)
    return jnp.einsum("qnd,ndh->qh", context, w["out/kernel"]) \
        * model["attention_out_multiplier"]


def mamba(h, w, model: dict, state_round_to=None):
    """h [L, hidden], normed; w: the Mamba mixer's parameters.
    state_round_to: a dtype's name the state is rounded through after each
    token (a control: the configuration states a float32 state)."""
    import jax
    import jax.numpy as jnp

    length = h.shape[0]
    heads, groups = model["mamba_n_heads"], model["mamba_n_groups"]
    n = model["mamba_d_state"]
    inner = w["out_proj/kernel"].shape[0]
    p = inner // heads
    mz, mx, mb, mc, mdt = model["ssm_multipliers"]
    mup_vector = jnp.concatenate([
        jnp.full((inner,), mz), jnp.full((inner,), mx),
        jnp.full((groups * n,), mb), jnp.full((groups * n,), mc),
        jnp.full((heads,), mdt)])
    zxbcdt = ((h * model["ssm_in_multiplier"]) @ w["in_proj/kernel"]) \
        * mup_vector
    conv = inner + 2 * groups * n
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:inner + conv],
                  zxbcdt[:, inner + conv:])
    k = w["conv_kernel"].shape[1]
    padded = jnp.concatenate([jnp.zeros((k - 1, conv)), xbc])
    xbc = jax.nn.silu(sum(padded[j:j + length] * w["conv_kernel"][:, j]
                          for j in range(k)) + w["conv_bias"])
    xs = xbc[:, :inner].reshape(length, heads, p)
    b = xbc[:, inner:inner + groups * n].reshape(length, groups, n)
    c = xbc[:, inner + groups * n:].reshape(length, groups, n)
    b, c = (jnp.repeat(t, heads // groups, axis=1) for t in (b, c))
    delta = jax.nn.softplus(dt + w["dt_bias"])            # [L, heads]
    a = -jnp.exp(w["A_log"])

    def step(s, t):
        x_t, b_t, c_t, d_t = t
        s = jnp.exp(d_t * a)[:, None, None] * s \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        if state_round_to is not None:
            s = s.astype(state_round_to).astype(jnp.float32)
        return s, jnp.einsum("hpn,hn->hp", s, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((heads, p, n)), (xs, b, c, delta))
    y = (y + w["D"][:, None] * xs).reshape(length, inner) * jax.nn.silu(z)
    y = y.reshape(length, groups, inner // groups)
    y = y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True)
                          + model["rms_norm_eps"])
    return ((y.reshape(length, inner) * w["norm_scale"])
            @ w["out_proj/kernel"]) * model["ssm_out_multiplier"]


def mlp(h, w, model: dict):
    import jax

    gate_multiplier, down_multiplier = model["mlp_multipliers"]
    wide = (h @ w["up/kernel"]) * jax.nn.silu(
        (h @ w["gate/kernel"]) * gate_multiplier)
    return (wide @ w["down/kernel"]) * down_multiplier


def logits(params: dict, ids, model: dict, round_to=None,
           state_round_to=None):
    """Logits [len(ids), vocab] of the next token after each position of the
    sequence `ids`, over `model["num_hidden_layers"]` layers.  round_to: a
    dtype's name; every weight and each layer's output is rounded through
    it, which is how a computation in a lower precision than the
    configuration states is told from one in it.  state_round_to: the same
    for the recurrent state alone."""
    import jax
    import jax.numpy as jnp

    eps = model["rms_norm_eps"]

    def rounded(x):
        # Widened by XLA, not by numpy on the way in: numpy takes seconds
        # for each stored bfloat16 matrix.  Exact either way.
        x = jnp.asarray(x).astype(jnp.float32)
        return x if round_to is None else x.astype(round_to).astype(
            jnp.float32)

    def weights(at: str) -> dict:
        return {k[len(at):]: rounded(v) for k, v in params.items()
                if k.startswith(at)}

    def rms_norm(x, scale):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
            * scale

    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        hidden = rounded(params["params/wte/embedding"])[ids] \
            * model["embedding_multiplier"]
        for i in range(model["num_hidden_layers"]):
            at = f"params/layer_{i}/"
            h = rms_norm(hidden, rounded(params[at + "norm/scale"]))
            hidden = rounded(
                hidden + mamba(h, weights(at + "mamba/"), model,
                               state_round_to)
                + attention(h, weights(at + "attention/"), model))
            h = rms_norm(hidden, rounded(params[at + "mlp_norm/scale"]))
            hidden = rounded(hidden + mlp(h, weights(at + "mlp/"), model))
        return (rms_norm(hidden, rounded(params["params/final_norm/scale"]))
                @ rounded(params["params/lm_head/kernel"])) \
            * model["lm_head_multiplier"]
