"""The repo's own copy of the DeepSeek-V3 / Moonlight reference
(chipbench/references/deepseek_v3.py is the benchmark's; the benchmark imports
nothing from here and the tests nothing from there but for the one test that
the two agree): the forward pass of `moonshotai/Moonlight-16B-A3B`
(`model_type` `deepseek_v3`) in plain float32 jax.numpy, written from the
`deepseek_v3` modelling code's equations in their EXPANDED (published) form
only, so that the served absorbed decode is held against the published
mathematics and not against itself.  One sequence, eagerly: no cache, no
batching, no kernel, every expert computed densely and weighted.

    x = embed(ids)
    per layer, h = RMSNorm(x):
      q = W_q·h (heads x (d_nope + d_rope)), split q_nope | q_pe
      (c, k_pe) = W_kva·h (rank + d_rope);  c <- RMSNorm_kva(c)
      q_pe, k_pe <- rotary: the pair (2i, 2i+1) rotated by
        pos·theta^(-2i/d_rope), the results laid out evens first and then
        odds (what the modelling code's de-interleave and rotate-half give);
        k_pe is one vector a token for all heads
      (k_nope, v)_j = W_kvb·c_j (heads x (d_nope + d_v))
      score = (q_nope·k_nope + q_pe·k_pe) / √(d_nope + d_rope), causal
      softmax; o = Σ p·v; x <- x + W_o·concat(o)
      h2 = RMSNorm(x)
      the first `first_k_dense_replace` layers: x <- x + down(silu(gate h2) ⊙ up h2)
      the others: s = sigmoid(W_g·h2); the `num_experts_per_tok` largest of
        s + b chosen (b: `e_score_correction_bias`, for the choice alone);
        w_i = routed_scaling_factor·s_i / (Σ_chosen s + 1e-20), 0 for the
        rest; x <- x + Σ_e w_e·expert_e(h2) + shared(h2), every expert a
        SwiGLU, the shared one of `n_shared_experts` experts' width
    logits = RMSNorm(x)·W_head

`params` is {"params/a/b": array}; `model` the published config's keys
(`num_hidden_layers`, `rms_norm_eps`, `rope_theta`, `qk_nope_head_dim`,
`kv_lora_rank`, `num_experts_per_tok`, `routed_scaling_factor`,
`first_k_dense_replace`); every other size is a parameter's own shape.

Departures from the published checkpoint's layout, none from its
mathematics: the projections are kept [in, heads, head size] and the
out-projection [heads, head size, in] (the served model's layout), `kv_b` is
[rank, heads, d_nope + d_v], the experts stacked [experts, in, out].
"""

import math


def rotary(x, theta: float):
    """x [L, heads, D] at positions 0..L-1: pairs (2i, 2i+1) rotated, the
    results evens first and then odds."""
    import jax.numpy as jnp

    length, _, d = x.shape
    inv_freq = float(theta) ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([even * cos - odd * sin, odd * cos + even * sin],
                           axis=-1)


def latent_rows(h, w, model: dict):
    """(c normed [L, rank], k_pe rotated [L, d_rope]) of h [L, hidden],
    normed: what a latent cache keeps of a token."""
    import jax

    rank = model["kv_lora_rank"]
    down = h @ w["kv_a/kernel"]
    c = down[:, :rank]
    c = c * jax.lax.rsqrt((c * c).mean(-1, keepdims=True)
                          + model["rms_norm_eps"]) * w["kv_norm/scale"]
    return c, rotary(down[:, None, rank:], model["rope_theta"])[:, 0]


def attention(h, w, model: dict, drop_k_pe: bool = False):
    """h [L, hidden], normed; w: the attention's parameters.  drop_k_pe (a
    control): the score without its rotary term."""
    import jax
    import jax.numpy as jnp

    length = h.shape[0]
    nope = model["qk_nope_head_dim"]
    wq = w["query/kernel"]
    heads, width = wq.shape[1:]
    q = (h @ wq.reshape(wq.shape[0], -1)).reshape(length, heads, width)
    q_nope, q_pe = q[..., :nope], rotary(q[..., nope:], model["rope_theta"])
    c, k_pe = latent_rows(h, w, model)
    kv = jnp.einsum("lr,rhd->lhd", c, w["kv_b"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = jnp.einsum("qnd,knd->nqk", q_nope, k_nope)
    if not drop_k_pe:
        scores = scores + jnp.einsum("qnd,kd->nqk", q_pe, k_pe)
    scores = scores / math.sqrt(width)
    causal = jnp.tril(jnp.ones((length, length), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    context = jnp.einsum("nqk,knd->qnd", jax.nn.softmax(scores, axis=-1), v)
    return jnp.einsum("qnd,ndh->qh", context, w["out/kernel"])


def swiglu(h, gate, up, down):
    import jax

    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def route(h, w, model: dict):
    """[L, experts] float32: an expert's weight on a token, 0 where it is
    not chosen."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(h @ w["router/kernel"])
    _, chosen = jax.lax.top_k(scores + w["router_bias"],
                              model["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = model["routed_scaling_factor"] * picked / (
        picked.sum(-1, keepdims=True) + 1e-20)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(weights)


def experts(h, w, model: dict):
    """Every expert on every token, weighted, plus the shared expert."""
    import jax.numpy as jnp

    weights = route(h, w, model)
    out = swiglu(h, w["shared/gate/kernel"], w["shared/up/kernel"],
                 w["shared/down/kernel"])
    for e in range(w["gate"].shape[0]):
        out = out + weights[:, e:e + 1] * swiglu(h, w["gate"][e], w["up"][e],
                                                 w["down"][e])
    return jnp.asarray(out)


def logits(params: dict, ids, model: dict, round_to=None,
           drop_k_pe: bool = False):
    """Logits [len(ids), vocab] of the next token after each position of the
    sequence `ids`.  round_to: a dtype's name; every weight and each layer's
    output is rounded through it, which is how a computation in a lower
    precision than the configuration states is told from one in it.
    drop_k_pe: the other control (`attention`)."""
    import jax
    import jax.numpy as jnp

    def rounded(x):
        x = jnp.asarray(x).astype(jnp.float32)
        return x if round_to is None else x.astype(round_to).astype(
            jnp.float32)

    def weights(at: str) -> dict:
        return {k[len(at):]: rounded(v) for k, v in params.items()
                if k.startswith(at)}

    def rms_norm(x, scale):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True)
                                 + model["rms_norm_eps"]) * scale

    with jax.default_matmul_precision("highest"):
        x = rounded(params["params/wte/embedding"])[jnp.asarray(ids,
                                                                jnp.int32)]
        for i in range(model["num_hidden_layers"]):
            at = f"params/layer_{i}/"
            x = rounded(x + attention(
                rms_norm(x, rounded(params[at + "attn_norm/scale"])),
                weights(at + "attention/"), model, drop_k_pe))
            h = rms_norm(x, rounded(params[at + "mlp_norm/scale"]))
            if i < model["first_k_dense_replace"]:
                w = weights(at + "mlp/")
                x = rounded(x + swiglu(h, w["gate/kernel"], w["up/kernel"],
                                       w["down/kernel"]))
            else:
                x = rounded(x + experts(h, weights(at + "experts/"), model))
        return rms_norm(x, rounded(params["params/final_norm/scale"])) \
            @ rounded(params["params/lm_head/kernel"])
