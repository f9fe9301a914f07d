"""OLMoE (Muennighoff et al. 2024; the forward pass of
`allenai/OLMoE-1B-7B-0125-Instruct`) in plain float32 jax.numpy: token
embedding with no position table; per layer RMSNorm, query and key each
RMS-normalised over the whole projection (all heads together, own scale)
before the split into heads, rotary embedding (rotate-half, absolute
positions, over the whole head), causal softmax attention scaled by
1/sqrt(head size), then RMSNorm, a router whose softmax runs over all
experts in float32, the `num_experts_per_tok` largest kept with their
probabilities not renormalised (`norm_topk_prob` false), and the sum of
those experts' SwiGLU MLPs weighted by them; a final RMSNorm and an untied
head.  No biases, no `clip_qkv`, no shared expert, no token dropped.
No cache, no batching, no kernel: one sequence, eagerly, the experts by a
plain loop over all of them, each on the tokens routed to it.

    python -m chipbench.references.olmoe <job.json> <out.json>   (CPU child)
    python -m chipbench.references.olmoe <job.json> <out.json> float8_e4m3fn
        (by hand: the same job with weights and layer outputs rounded through
        that dtype, to show that the configuration's tolerance refuses it)

The job gives depth and the norm's epsilon; experts per token and the
rotary base are read from this reference's own configuration file
(chipbench/configs/olmoe-1b-7b-8l.json); every other size is the served
parameters' own shape.

Departures from the published checkpoint's layout, none from its
mathematics: the served model keeps each projection as [hidden, heads,
head size] and the experts stacked as [experts, in, out], where the
checkpoint has one [out, in] matrix per expert; the reference reads the
served parameters, so it multiplies by those.  The weights are the served
bytes (bfloat16 as stored), widened to float32 exactly.
"""

import json
import math
import os
import sys

CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                      "configs", "olmoe-1b-7b-8l.json")


def settings() -> dict:
    with open(CONFIG) as f:
        config = json.load(f)
    return {"experts_per_token": config["num_experts_per_tok"],
            "rope_theta": float(config["rope_theta"])}


def logits(params: dict, ids, n_layer: int, eps: float,
           experts_per_token: int, rope_theta: float, routing=None,
           round_to=None):
    """Logits [len(ids), vocab] of the next token after each position of the
    sequence `ids`.  routing: an optional list that receives each layer's
    chosen experts [len(ids), experts_per_token].  round_to: a dtype's name;
    every weight and each layer's output is rounded through it, which is
    how a computation in a lower precision than the configuration states
    is told from one in it (PERF.md: float8 has to come out not correct)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def rounded(x):
        # Widened by XLA, not by numpy on the way in: numpy takes seconds
        # for each stored bfloat16 matrix.  Exact either way.
        x = jnp.asarray(x).astype(jnp.float32)
        return x if round_to is None else x.astype(round_to).astype(
            jnp.float32)

    def p(path):
        return rounded(params["params/" + path])

    def rms_norm(x, name):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
            * p(name + "/scale")

    def rope(x, positions):
        half = x.shape[-1] // 2
        inv_freq = rope_theta ** (-jnp.arange(half, dtype=jnp.float32)
                                  / half)
        angles = positions[:, None] * inv_freq[None, :]
        cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None, :]
        sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None, :]
        rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
        return x * cos + rotated * sin

    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        length = ids.shape[0]
        positions = jnp.arange(length, dtype=jnp.float32)
        hidden = p("wte/embedding")[ids]
        causal = jnp.tril(jnp.ones((length, length), bool))
        for i in range(n_layer):
            at = f"layer_{i}/"
            x = rms_norm(hidden, at + "attn_norm")
            wq, wk, wv = (p(at + name + "/kernel")
                          for name in ("query", "key", "value"))
            heads = wq.shape[1:]
            q = rms_norm(x @ wq.reshape(wq.shape[0], -1), at + "q_norm")
            k = rms_norm(x @ wk.reshape(wk.shape[0], -1), at + "k_norm")
            v = x @ wv.reshape(wv.shape[0], -1)
            q = rope(q.reshape((length,) + heads), positions)
            k = rope(k.reshape((length,) + heads), positions)
            v = v.reshape((length,) + heads)
            scores = jnp.einsum("qnd,knd->nqk", q, k) / math.sqrt(heads[1])
            scores = jnp.where(causal[None], scores, -jnp.inf)
            context = jnp.einsum("nqk,knd->qnd",
                                 jax.nn.softmax(scores, axis=-1), v)
            hidden = hidden + jnp.einsum("qnd,ndh->qh", context,
                                         p(at + "out/kernel"))
            x = rms_norm(hidden, at + "mlp_norm")
            probs = jax.nn.softmax(x @ p(at + "experts/router/kernel"),
                                   axis=-1)
            top, chosen = jax.lax.top_k(probs, experts_per_token)
            top, chosen = np.asarray(top), np.asarray(chosen)
            if routing is not None:
                routing.append(chosen)
            # Eager jax.numpy compiles every operation once per shape, and
            # each expert is given another number of rows: rows are picked
            # and put back with numpy, and the arithmetic runs on whole
            # tiles of 128 rows (zero rows give zero and are dropped).
            gate, up, down = (np.asarray(p(at + "experts/" + name))
                              for name in ("gate", "up", "down"))
            x_rows = np.asarray(x)
            mixed = np.zeros_like(x_rows)
            for e in range(probs.shape[-1]):
                rows, slot = np.nonzero(chosen == e)
                if rows.size == 0:
                    continue
                mine = np.zeros((-(-rows.size // 128) * 128, x_rows.shape[1]),
                                np.float32)
                mine[:rows.size] = x_rows[rows]
                out = jnp.matmul(jax.nn.silu(jnp.matmul(mine, gate[e]))
                                 * jnp.matmul(mine, up[e]), down[e])
                mixed[rows] += top[rows, slot][:, None] \
                    * np.asarray(out)[:rows.size]
            hidden = rounded(hidden + mixed)
        return rms_norm(hidden, "final_norm") @ p("lm_head/kernel")


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
    answers = []
    for case in job["cases"]:
        prompt, generated = case["prompt_ids"], case["generated_ids"]
        # Teacher forcing: the row after the prompt's last token scores the
        # first generated token, the row after that token the second, ...
        rows = np.asarray(log_probs(
            params, prompt + generated[:-1], job["n_layer"],
            job["layer_norm_epsilon"], **settings(),
            round_to=argv[3] if len(argv) > 3 else None))[len(prompt) - 1:]
        answers.append({
            "chosen": [float(rows[j, t]) for j, t in enumerate(generated)],
            "top": [float(rows[0, t]) for t in case["top_ids"]],
        })
    with open(argv[2], "w") as f:
        json.dump({"cases": answers}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
