"""DeepSeek-V3 / Moonlight (the forward pass of `moonshotai/Moonlight-16B-A3B`,
`model_type` `deepseek_v3`) in plain float32 jax.numpy, in the EXPANDED
(published) form of its latent attention only: the served model decodes in
the absorbed form over a cache of compressed rows, and is held against this.

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
        s + b chosen (b: `e_score_correction_bias`, for the choice alone; one
        group, so no group limit); w_i = routed_scaling_factor·s_i /
        (Σ_chosen s + 1e-20); x <- x + Σ_chosen w_i·expert_i(h2) + shared(h2),
        every expert a SwiGLU, the shared one of `n_shared_experts` experts'
        width.  No token dropped.
    logits = RMSNorm(x)·W_head

No cache, no batching, no kernel, no chunking: a sequence at a time, eagerly.
Where tests/deepseek_v3_reference.py computes every expert on every token and
weights it (0 for the unchosen), this copy gives each expert the tokens that
chose it (a plain loop over the experts; the same sum, a tenth of the
arithmetic at 6 of 64), widens a layer's weights at a time, reads only the
embedding rows the prompts name and takes the head in column blocks: the
float32 twin of 4.26 B parameters would not fit beside the served bytes.

    python -m chipbench.references.deepseek_v3 <job.json> <out.json>   (CPU child)
    python -m chipbench.references.deepseek_v3 <job.json> <out.json> float8_e4m3fn
        (by hand: the same job with weights and layer outputs rounded through
        that dtype, to show that the configuration's limits refuse it)
    python -m chipbench.references.deepseek_v3 <job.json> <out.json> drop_k_pe
        (by hand: the score without its rotary term q_pe·k_pe, which a
        cache that lost or mis-rotated the row's last 64 columns would give)

The job gives depth and the norm's epsilon; the head sizes, the rank, experts
per token, the scaling and the dense layers are read from this reference's
own configuration file (chipbench/configs/moonlight-16b-a3b-7l.json); every
other size is the served parameters' own shape.

Departures from the published checkpoint's layout, none from its
mathematics: the projections are kept [in, heads, head size] and the
out-projection [heads, head size, in] (the served model's layout), `kv_b` is
[rank, heads, d_nope + d_v], the experts stacked [experts, in, out].  The
weights are the served bytes (bfloat16 as stored, the router's bias
float32), widened to float32 exactly.
"""

import json
import math
import os
import sys

CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                      "configs", "moonlight-16b-a3b-7l.json")
HEAD_COLUMNS = 32768  # of the head widened at once: 0.27 GB at hidden 2048


def settings() -> dict:
    with open(CONFIG) as f:
        config = json.load(f)
    return {key: config[key] for key in (
        "rope_theta", "qk_nope_head_dim", "kv_lora_rank",
        "num_experts_per_tok", "routed_scaling_factor",
        "first_k_dense_replace")}


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


def attention(h, w, model: dict, eps: float, drop_k_pe: bool = False):
    """h [L, hidden], normed; w: the attention's parameters."""
    import jax
    import jax.numpy as jnp

    length = h.shape[0]
    nope, rank = model["qk_nope_head_dim"], model["kv_lora_rank"]
    wq = w["query/kernel"]
    heads, width = wq.shape[1:]
    q = (h @ wq.reshape(wq.shape[0], -1)).reshape(length, heads, width)
    q_nope, q_pe = q[..., :nope], rotary(q[..., nope:], model["rope_theta"])
    down = h @ w["kv_a/kernel"]
    c = down[:, :rank]
    c = c * jax.lax.rsqrt((c * c).mean(-1, keepdims=True) + eps) \
        * w["kv_norm/scale"]
    k_pe = rotary(down[:, None, rank:], model["rope_theta"])[:, 0]
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
    import jax.numpy as jnp

    return jnp.matmul(jax.nn.silu(jnp.matmul(h, gate)) * jnp.matmul(h, up),
                      down)


def experts(h, w, model: dict):
    """The routed sum (each expert on the tokens that chose it) + the shared
    expert."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    scores = jax.nn.sigmoid(h @ w["router/kernel"])
    _, chosen = jax.lax.top_k(scores + w["router_bias"],
                              model["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = np.asarray(model["routed_scaling_factor"] * picked
                         / (picked.sum(-1, keepdims=True) + 1e-20))
    chosen = np.asarray(chosen)
    # Eager jax.numpy compiles every operation once per shape, and each
    # expert is given another number of rows: rows are picked and put back
    # with numpy, and the arithmetic runs on whole tiles of 128 rows (zero
    # rows give zero and are dropped).
    x_rows = np.asarray(h)
    mixed = np.zeros_like(x_rows)
    for e in range(w["gate"].shape[0]):
        rows, slot = np.nonzero(chosen == e)
        if rows.size == 0:
            continue
        mine = np.zeros((-(-rows.size // 128) * 128, x_rows.shape[1]),
                        np.float32)
        mine[:rows.size] = x_rows[rows]
        out = swiglu(mine, w["gate"][e], w["up"][e], w["down"][e])
        mixed[rows] += weights[rows, slot][:, None] \
            * np.asarray(out)[:rows.size]
    return jnp.asarray(mixed) + swiglu(
        h, w["shared/gate/kernel"], w["shared/up/kernel"],
        w["shared/down/kernel"])


def logits(params: dict, sequences, model: dict, first_rows=None,
           round_to=None, drop_k_pe: bool = False) -> list:
    """For each sequence of ids, the logits [rows, vocab] of the next token
    after each of its positions from `first_rows`' own on (all of them by
    default).  `model`: `settings()` with `num_hidden_layers` and
    `rms_norm_eps`.  round_to: a dtype's name; every weight and each
    layer's output is rounded through it, which is how a computation in a
    lower precision than the configuration states is told from one in it
    (PERF.md: float8 has to come out not correct).  drop_k_pe: the other
    control."""
    import jax
    import jax.numpy as jnp
    import numpy as np

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

    first_rows = first_rows or [0] * len(sequences)
    with jax.default_matmul_precision("highest"):
        table = params["params/wte/embedding"]
        hidden = [rounded(np.asarray(table[np.asarray(ids, np.int64)]))
                  for ids in sequences]
        for i in range(model["num_hidden_layers"]):
            at = f"params/layer_{i}/"
            norm, mlp_norm = (rounded(params[at + name + "/scale"])
                              for name in ("attn_norm", "mlp_norm"))
            dense = i < model["first_k_dense_replace"]
            w_attention = weights(at + "attention/")
            w_mlp = weights(at + ("mlp/" if dense else "experts/"))
            for j, x in enumerate(hidden):
                x = rounded(x + attention(rms_norm(x, norm), w_attention,
                                          model, eps, drop_k_pe))
                h = rms_norm(x, mlp_norm)
                hidden[j] = rounded(x + (
                    swiglu(h, w_mlp["gate/kernel"], w_mlp["up/kernel"],
                           w_mlp["down/kernel"]) if dense
                    else experts(h, w_mlp, model)))
            del w_attention, w_mlp
        final = rounded(params["params/final_norm/scale"])
        scored = [rms_norm(x[first:], final)
                  for x, first in zip(hidden, first_rows)]
        head = params["params/lm_head/kernel"]
        out = [[] for _ in scored]
        for start in range(0, head.shape[1], HEAD_COLUMNS):
            block = rounded(head[:, start:start + HEAD_COLUMNS])
            for rows, x in zip(out, scored):
                rows.append(np.asarray(x @ block))
        return [np.concatenate(rows, axis=1) for rows in out]


def end_with_parent() -> None:
    """This child is sent SIGTERM when the run that started it ends, however
    that run ended (Linux's PR_SET_PDEATHSIG): minutes of float32 on every
    core must not outlive a run that was ended from outside."""
    import ctypes
    import signal

    try:
        ctypes.CDLL("libc.so.6").prctl(1, signal.SIGTERM)
    except OSError:
        return
    if os.getppid() == 1:
        os.kill(os.getpid(), signal.SIGTERM)


def main(argv) -> int:
    end_with_parent()
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
    controls = {} if control is None else (
        {"drop_k_pe": True} if control == "drop_k_pe"
        else {"round_to": control})
    model = dict(settings(), num_hidden_layers=job["n_layer"],
                 rms_norm_eps=job["layer_norm_epsilon"])
    # Teacher forcing: the row after the prompt's last token scores the
    # first generated token, the row after that token the second, ...
    cases = job["cases"]
    scored = logits(
        params, [c["prompt_ids"] + c["generated_ids"][:-1] for c in cases],
        model, first_rows=[len(c["prompt_ids"]) - 1 for c in cases],
        **controls)
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
