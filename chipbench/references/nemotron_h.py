"""Nemotron-H (the forward pass of `nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`,
`model_type` `nemotron_h`) in plain float32 jax.numpy.  Token embedding with
no position table; layer `l` of kind `c_l` from `hybrid_override_pattern`:
`x <- x + Mixer_c(RMSNorm(x))`; a final RMSNorm and an untied head.

`M`, Mamba-2 (H heads of P, G groups, state N, conv kernel K):
    [z | xBC | dt] = h·W_in;   xBC_t <- silu(Σ_j w[:, j]·xBC_{t-K+1+j} + b)
    (depthwise, causal, zeros before the sequence);  xBC -> x [H, P],
    B [G, N], C [G, N], head h on group h // (H/G);  Δ = softplus(dt + dt_bias),
    A = -exp(A_log);  S_t[h] = exp(Δ_t[h]·A[h])·S_{t-1}[h] + Δ_t[h]·x_t[h] ⊗ B_t[g];
    y_t[h] = S_t[h]·C_t[g] + D[h]·x_t[h];  y <- RMSNorm over groups of H·P/G
    of (y ⊙ silu(z)) with one learned scale;  out = y·W_out.
    The recurrence is a sequential loop over the tokens, from S = 0.
`*`, attention: q (heads x D), k, v (KV heads x D) = h·W, no bias and no
    position encoding of any kind (the `nemotron_h` modelling code applies
    none), causal softmax(q·kᵀ/√D)·v with query head j on KV head
    j // (heads / KV heads), then ·W_o.
`E`, experts: s = sigmoid(h·W_r) over all routed experts; the
    `num_experts_per_tok` largest of s + b are chosen (b, the score
    correction bias, for the choice alone; one group, so no group limit);
    w_i = `routed_scaling_factor`·s_i / (Σ_chosen s + 1e-20);
    out = Σ_chosen w_i·down_i(relu(up_i·h)²) + shared(h), the shared expert
    the same form.  No token dropped.

**The share**: this chip holds the routed experts `experts_held = [first,
count]`.  Routing and the normalisation run over all experts; the sum runs
over the chosen experts that are held (a plain loop over the held ones, each
on the tokens routed to it); the shared expert is added whole.  What the
other experts would add is left out, here as in the served model.

No cache, no batching, no kernel, no chunking: one sequence, eagerly.

    python -m chipbench.references.nemotron_h <job.json> <out.json>   (CPU child)
    python -m chipbench.references.nemotron_h <job.json> <out.json> float8_e4m3fn
        (by hand: the same job with weights and layer outputs rounded through
        that dtype, to show that the configuration's tolerance refuses it)
    python -m chipbench.references.nemotron_h <job.json> <out.json> state:bfloat16
        (by hand: the recurrent state alone kept in that dtype between
        tokens; PERF.md says what the tolerance makes of it)

The job gives depth and the norm's epsilon; the pattern, the Mamba heads,
groups and state, experts per token, the scaling and the share are read from
this reference's own configuration file
(chipbench/configs/nemotron-3-nano-16l-ep2.json); every other size is the
served parameters' own shape.

Departures from the published checkpoint's layout, none from its
mathematics: the served model keeps attention projections as [hidden, heads,
head size], the held experts stacked as [held, in, out], and an expert's
width padded with zeros to a lane multiple (1856 stored as 1920:
relu(0)² = 0 and the zero rows of `down` add nothing, so the reference
multiplies by the stored matrices as they are).  The weights are the served
bytes (bfloat16 as stored; `A_log`, `D`, `dt_bias` and the router's bias
float32), widened to float32 exactly.
"""

import json
import math
import os
import sys

CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                      "configs", "nemotron-3-nano-16l-ep2.json")


def settings() -> dict:
    with open(CONFIG) as f:
        config = json.load(f)
    return {"pattern": config["hybrid_override_pattern"],
            "mamba_heads": config["mamba_num_heads"],
            "ssm_groups": config["n_groups"],
            "ssm_state": config["ssm_state_size"],
            "experts_per_token": config["num_experts_per_tok"],
            "scaling": float(config["routed_scaling_factor"]),
            "experts_held": tuple(config["experts_held"])}


def mamba(x, w, *, heads, groups, state_size, eps, state_round_to=None):
    """x [L, hidden] (normed); w: the mixer's parameters, float32.
    state_round_to: a dtype's name the state is rounded through after each
    token (a control: the configuration states a float32 state)."""
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
        if state_round_to is not None:
            s = s.astype(state_round_to).astype(jnp.float32)
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
           routing=None, round_to=None, state_round_to=None):
    """Logits [len(ids), vocab] of the next token after each position of the
    sequence `ids`, over the first `n_layer` layers of `pattern`.  routing:
    an optional list that receives each expert layer's chosen experts
    [len(ids), experts_per_token].  round_to: a dtype's name; every weight
    and each layer's output is rounded through it, which is how a
    computation in a lower precision than the configuration states is told
    from one in it (PERF.md: float8 has to come out not correct).
    state_round_to: the same for the recurrent state alone."""
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
                            state_size=ssm_state, eps=eps,
                            state_round_to=state_round_to)
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


def log_probs(params: dict, ids, n_layer: int, eps: float, **model):
    import jax

    return jax.nn.log_softmax(logits(params, ids, n_layer, eps, **model),
                              axis=-1)


def main(argv) -> int:
    import jax
    import numpy as np

    from chipbench.references import params as served_params

    with open(argv[1]) as f:
        job = json.load(f)
    if jax.devices()[0].platform != "cpu":
        raise SystemExit(f"the reference runs on the CPU, not {jax.devices()}")
    # `jax` is imported: the stored bfloat16 leaves now resolve by name.
    params = served_params.load(job["params_dir"])
    control = argv[3] if len(argv) > 3 else None
    rounding = {} if control is None else (
        {"state_round_to": control[len("state:"):]}
        if control.startswith("state:") else {"round_to": control})
    answers = []
    for case in job["cases"]:
        prompt, generated = case["prompt_ids"], case["generated_ids"]
        # Teacher forcing: the row after the prompt's last token scores the
        # first generated token, the row after that token the second, ...
        rows = np.asarray(log_probs(
            params, prompt + generated[:-1], job["n_layer"],
            job["layer_norm_epsilon"], **settings(),
            **rounding))[len(prompt) - 1:]
        answers.append({
            "chosen": [float(rows[j, t]) for j, t in enumerate(generated)],
            "top": [float(rows[0, t]) for t in case["top_ids"]],
        })
    with open(argv[2], "w") as f:
        json.dump({"cases": answers}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
