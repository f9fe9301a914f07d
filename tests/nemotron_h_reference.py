"""The repo's own copy of the Nemotron-H reference
(chipbench/references/nemotron_h.py is the benchmark's; the benchmark imports
nothing from here and the tests nothing from there but for the one test that
the two agree): the forward pass of `nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-
BF16` (`nemotron_h`) in plain float32 jax.numpy, as models/nemotron_h.py's
docstring writes it.  Per layer `x <- x + Mixer(RMSNorm(x))` with the mixer
drawn by the pattern: `M` Mamba-2 (in-projection, depthwise causal conv and
silu, the selective recurrence S_t = exp(Δ·A)·S_{t-1} + Δ·x ⊗ B,
y = S·C + D·x as a sequential loop over tokens, gate then grouped RMSNorm,
out-projection); `*` causal grouped-query attention with no position
encoding; `E` a sigmoid router (choice by s + b, weights
scaling·s / Σ_chosen s), plain relu² experts of which this chip holds
`experts_held = (first, count)` (the others' part is left out) and a shared
expert, whole.  One sequence, eagerly, no cache, no kernel, no chunking.
`params` is {"params/a/b": array}; a stored expert width padded with zeros
is multiplied as it is (relu(0)² = 0).
"""

import math


def mamba(x, w, *, heads, groups, state_size, eps):
    """x [L, hidden] (normed); w: the mixer's parameters, float32."""
    import jax
    import jax.numpy as jnp

    length = x.shape[0]
    inner = w["out_proj/kernel"].shape[0]
    p = inner // heads
    zxbcdt = x @ w["in_proj/kernel"]
    conv = zxbcdt.shape[1] - inner - heads
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:inner + conv],
                  zxbcdt[:, inner + conv:])
    k = w["conv_kernel"].shape[1]
    padded = jnp.concatenate([jnp.zeros((k - 1, conv)), xbc])
    xbc = jax.nn.silu(sum(padded[j:j + length] * w["conv_kernel"][:, j]
                          for j in range(k)) + w["conv_bias"])
    xs = xbc[:, :inner].reshape(length, heads, p)
    b = xbc[:, inner:inner + groups * state_size].reshape(
        length, groups, state_size)
    c = xbc[:, inner + groups * state_size:].reshape(
        length, groups, state_size)
    b, c = (jnp.repeat(t, heads // groups, axis=1) for t in (b, c))
    delta = jax.nn.softplus(dt + w["dt_bias"])            # [L, heads]
    a = -jnp.exp(w["A_log"])

    def step(s, t):
        x_t, b_t, c_t, d_t = t
        s = jnp.exp(d_t * a)[:, None, None] * s \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((heads, p, state_size)),
                        (xs, b, c, delta))
    y = (y + w["D"][:, None] * xs).reshape(length, inner) * jax.nn.silu(z)
    y = y.reshape(length, groups, inner // groups)
    y = y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True) + eps)
    return (y.reshape(length, inner) * w["norm_scale"]) @ w["out_proj/kernel"]


def attention(x, w):
    import jax
    import jax.numpy as jnp

    length = x.shape[0]
    wq, wk, wv = (w[name + "/kernel"] for name in ("query", "key", "value"))
    (heads, d), kv_heads = wq.shape[1:], wk.shape[1]
    q = (x @ wq.reshape(wq.shape[0], -1)).reshape(length, heads, d)
    k = (x @ wk.reshape(wk.shape[0], -1)).reshape(length, kv_heads, d)
    v = (x @ wv.reshape(wv.shape[0], -1)).reshape(length, kv_heads, d)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=1) for t in (k, v))
    scores = jnp.einsum("qnd,knd->nqk", q, k) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((length, length), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    context = jnp.einsum("nqk,knd->qnd", jax.nn.softmax(scores, axis=-1), v)
    return jnp.einsum("qnd,ndh->qh", context, w["out/kernel"])


def experts(x, w, *, experts_per_token, scaling, experts_held, routing=None,
            shared=True):
    """The routed sum over the held experts (+ the shared expert)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    first, count = experts_held
    scores = jax.nn.sigmoid(x @ w["router/kernel"])
    _, chosen = jax.lax.top_k(scores + w["router_bias"], experts_per_token)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = np.asarray(scaling * picked
                         / (picked.sum(-1, keepdims=True) + 1e-20))
    chosen = np.asarray(chosen)
    if routing is not None:
        routing.append(chosen)
    # Eager jax.numpy compiles every operation once per shape, and each
    # expert is given another number of rows: rows are picked and put back
    # with numpy, and the arithmetic runs on whole tiles of 128 rows (zero
    # rows give zero and are dropped).
    up, down = np.asarray(w["up"]), np.asarray(w["down"])
    x_rows = np.asarray(x)
    mixed = np.zeros_like(x_rows)
    for e in range(first, first + count):
        rows, slot = np.nonzero(chosen == e)
        if rows.size == 0:
            continue
        mine = np.zeros((-(-rows.size // 128) * 128, x_rows.shape[1]),
                        np.float32)
        mine[:rows.size] = x_rows[rows]
        out = jnp.matmul(jnp.square(jax.nn.relu(
            jnp.matmul(mine, up[e - first]))), down[e - first])
        mixed[rows] += weights[rows, slot][:, None] \
            * np.asarray(out)[:rows.size]
    if shared:
        mixed = mixed + jnp.square(jax.nn.relu(
            x @ w["shared_up/kernel"])) @ w["shared_down/kernel"]
    return jnp.asarray(mixed)


def logits(params: dict, ids, n_layer: int, eps: float, *, pattern: str,
           mamba_heads: int, ssm_groups: int, ssm_state: int,
           experts_per_token: int, scaling: float, experts_held,
           routing=None, round_to=None):
    """Logits [len(ids), vocab] of the next token after each position of the
    sequence `ids`, over the first `n_layer` layers of `pattern`.  routing:
    an optional list that receives each expert layer's chosen experts
    [len(ids), experts_per_token].  round_to: a dtype's name; every weight
    and each layer's output is rounded through it, which is how a
    computation in a lower precision than the configuration states is told
    from one in it (PERF.md: float8 has to come out not correct)."""
    import jax
    import jax.numpy as jnp

    def rounded(x):
        # Widened by XLA, not by numpy on the way in: numpy takes seconds
        # for each stored bfloat16 matrix.  Exact either way.
        x = jnp.asarray(x).astype(jnp.float32)
        return x if round_to is None else x.astype(round_to).astype(
            jnp.float32)

    def layer_weights(at: str) -> dict:
        return {k[len(at):]: rounded(v) for k, v in params.items()
                if k.startswith(at)}

    def rms_norm(x, scale):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
            * scale

    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        hidden = rounded(params["params/wte/embedding"])[ids]
        for i, kind in enumerate(pattern[:n_layer]):
            x = rms_norm(hidden, rounded(
                params[f"params/layer_{i}/norm/scale"]))
            w = layer_weights(f"params/layer_{i}/mixer/")
            if kind == "M":
                out = mamba(x, w, heads=mamba_heads, groups=ssm_groups,
                            state_size=ssm_state, eps=eps)
            elif kind == "*":
                out = attention(x, w)
            else:
                out = experts(x, w, experts_per_token=experts_per_token,
                              scaling=scaling, experts_held=experts_held,
                              routing=routing)
            hidden = rounded(hidden + out)
        return rms_norm(hidden, rounded(
            params["params/final_norm/scale"])) \
            @ rounded(params["params/lm_head/kernel"])
