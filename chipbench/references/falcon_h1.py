"""Falcon-H1 (the forward pass of `tiiuae/Falcon-H1-34B-Instruct`,
`model_type` `falcon_h1`) in plain float32 jax.numpy, written from the
`falcon_h1` modelling code's equations.  Every layer runs an attention mixer
and a Mamba-2 mixer on the same normed input and adds both to the residual,
then a SwiGLU MLP; scalar multipliers sit where the equations put them:

    x = embed(ids)·embedding_multiplier
    per layer, h = RMSNorm(x):
      attention  h' = h·attention_in_multiplier;  q = h'·W_q,
                 k = (h'·W_k)·key_multiplier, v = h'·W_v, no bias;
                 rotate-half rotary over the whole head (theta `rope_theta`);
                 causal softmax(q·kᵀ/√D)·v, query head j on KV head
                 j // (heads / KV heads);  a = (o·W_o)·attention_out_multiplier
      Mamba-2    [z | xBC | dt] = ((h·ssm_in_multiplier)·W_in) ⊙ mup_vector,
                 mup_vector = ssm_multipliers[0..4] over the segments z, x,
                 B, C, dt;  xBC_t <- silu(Σ_j w[:, j]·xBC_{t-K+1+j} + b)
                 (depthwise, causal, zeros before the sequence);
                 xBC -> x [H, P], B [G, N], C [G, N], head h on group
                 h // (H/G);  Δ = softplus(dt + dt_bias), A = -exp(A_log);
                 S_t[h] = exp(Δ_t[h]·A[h])·S_{t-1}[h] + Δ_t[h]·x_t[h] ⊗ B_t[g];
                 y_t[h] = S_t[h]·C_t[g] + D[h]·x_t[h];
                 y <- RMSNorm over groups of H·P/G of (y ⊙ silu(z)) with one
                 learned scale (the gate before the norm);
                 m = (y·W_out)·ssm_out_multiplier
                 The recurrence is a sequential loop over the tokens, from
                 S = 0.
      x <- x + m + a
      x <- x + ((up(h2) ⊙ silu(gate(h2)·mlp_multipliers[0]))·W_down)
               ·mlp_multipliers[1],   h2 = RMSNorm(x)
    logits = (RMSNorm(x)·W_head)·lm_head_multiplier

No cache, no batching, no kernel, no chunking of the mathematics: one
sequence at a time, eagerly.

    python -m chipbench.references.falcon_h1 <job.json> <out.json>   (CPU child)
    python -m chipbench.references.falcon_h1 <job.json> <out.json> float8_e4m3fn
        (by hand: the same job with weights and layer outputs rounded through
        that dtype, to show that the configuration's tolerance refuses it)
    python -m chipbench.references.falcon_h1 <job.json> <out.json> state:bfloat16
        (by hand: the recurrent state alone kept in that dtype between tokens)

The job gives depth and the norm's epsilon; the multipliers, `rope_theta`,
the Mamba heads, groups and state are read from this reference's own
configuration file (chipbench/configs/falcon-h1-34b-6l.json); every other
size is the served parameters' own shape.

Departures from the published checkpoint's layout, none from its mathematics:
attention projections are [hidden, heads, head size] and the out-projection
[heads, head size, hidden] (the served model's layout), the depthwise conv's
weight [channels, kernel].  The weights are the served bytes (bfloat16 as
stored; `A_log`, `D`, `dt_bias` float32), widened to float32 exactly.  What
is blocked is memory and not mathematics: a layer's weights are widened
once and every sequence of the job goes through the layer before the next
is widened (5.25 B parameters in float32 would be 21 GB at once); of the
embedding only the rows the sequences name are widened; and the head is
multiplied in column blocks, on the rows that are scored.
"""

import json
import math
import os
import sys

CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                      "configs", "falcon-h1-34b-6l.json")
HEAD_COLUMNS = 32768  # of the head widened at once: 0.67 GB at hidden 5120


def settings() -> dict:
    with open(CONFIG) as f:
        config = json.load(f)
    return {key: config[key] for key in (
        "rope_theta", "embedding_multiplier", "attention_in_multiplier",
        "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
        "ssm_multipliers", "ssm_out_multiplier", "mlp_multipliers",
        "lm_head_multiplier", "mamba_n_heads", "mamba_n_groups",
        "mamba_d_state")}


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


def mamba(h, w, model: dict, eps: float, state_round_to=None):
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
    y = y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True) + eps)
    return ((y.reshape(length, inner) * w["norm_scale"])
            @ w["out_proj/kernel"]) * model["ssm_out_multiplier"]


def mlp(h, w, model: dict):
    import jax

    gate_multiplier, down_multiplier = model["mlp_multipliers"]
    wide = (h @ w["up/kernel"]) * jax.nn.silu(
        (h @ w["gate/kernel"]) * gate_multiplier)
    return (wide @ w["down/kernel"]) * down_multiplier


def logits(params: dict, sequences, n_layer: int, eps: float, model: dict,
           first_rows=None, round_to=None, state_round_to=None) -> list:
    """For each sequence of ids, the logits [rows, vocab] of the next token
    after each of its positions from `first_rows`' own on (all of them by
    default).  round_to: a dtype's name; every weight and each layer's
    output is rounded through it, which is how a computation in a lower
    precision than the configuration states is told from one in it (PERF.md:
    float8 has to come out not correct).  state_round_to: the same for the
    recurrent state alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np

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

    first_rows = first_rows or [0] * len(sequences)
    with jax.default_matmul_precision("highest"):
        table = params["params/wte/embedding"]
        hidden = [rounded(np.asarray(table[np.asarray(ids, np.int64)]))
                  * model["embedding_multiplier"] for ids in sequences]
        for i in range(n_layer):
            at = f"params/layer_{i}/"
            norm, mlp_norm = (rounded(params[at + name + "/scale"])
                              for name in ("norm", "mlp_norm"))
            w_mamba, w_attention, w_mlp = (
                weights(at + name + "/")
                for name in ("mamba", "attention", "mlp"))
            for j, x in enumerate(hidden):
                h = rms_norm(x, norm)
                x = rounded(x + mamba(h, w_mamba, model, eps, state_round_to)
                            + attention(h, w_attention, model))
                hidden[j] = rounded(
                    x + mlp(rms_norm(x, mlp_norm), w_mlp, model))
            del w_mamba, w_attention, w_mlp
        final = rounded(params["params/final_norm/scale"])
        scored = [rms_norm(x[first:], final)
                  for x, first in zip(hidden, first_rows)]
        head = params["params/lm_head/kernel"]
        out = [[] for _ in scored]
        for start in range(0, head.shape[1], HEAD_COLUMNS):
            block = rounded(head[:, start:start + HEAD_COLUMNS])
            for rows, x in zip(out, scored):
                rows.append(np.asarray(x @ block)
                            * model["lm_head_multiplier"])
        return [np.concatenate(rows, axis=1) for rows in out]


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
    # Teacher forcing: the row after the prompt's last token scores the
    # first generated token, the row after that token the second, ...
    cases = job["cases"]
    scored = logits(
        params, [c["prompt_ids"] + c["generated_ids"][:-1] for c in cases],
        job["n_layer"], job["layer_norm_epsilon"], settings(),
        first_rows=[len(c["prompt_ids"]) - 1 for c in cases], **rounding)
    answers = []
    for case, rows in zip(cases, scored):
        rows = np.asarray(jax.nn.log_softmax(rows, axis=-1))
        answers.append({
            "chosen": [float(rows[j, t])
                       for j, t in enumerate(case["generated_ids"])],
            "top": [float(rows[0, t]) for t in case["top_ids"]],
        })
    with open(argv[2], "w") as f:
        json.dump({"cases": answers}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
