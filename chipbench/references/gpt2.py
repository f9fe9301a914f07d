"""GPT-2 (Radford et al. 2019; the forward pass of `openai-community/gpt2-*`)
in plain float32 jax.numpy: learned position embeddings, pre-LayerNorm blocks
(eps from the config), causal multi-head attention scaled by 1/sqrt(head
size), a `gelu_new` (tanh) MLP, a final LayerNorm and the output head tied to
the token embedding.  No cache, no batching, no kernel: one sequence, eagerly.

    python -m chipbench.references.gpt2 <job.json> <out.json>   (CPU child)

Departure from the published checkpoint's layout, none from its mathematics:
the served model keeps query, key and value as three matrices where GPT-2
packs them into one `c_attn`; the reference reads the served parameters, so
it multiplies by the three.
"""

import json
import math
import sys


def log_probs(params: dict, ids, n_layer: int, eps: float):
    """Log-probabilities [len(ids), vocab] of the next token after each
    position of the sequence `ids`."""
    import jax
    import jax.numpy as jnp

    def p(path):
        return jnp.asarray(params["params/" + path], jnp.float32)

    def layer_norm(x, name):
        mean = x.mean(-1, keepdims=True)
        var = ((x - mean) ** 2).mean(-1, keepdims=True)
        return ((x - mean) / jnp.sqrt(var + eps) * p(name + "/scale")
                + p(name + "/bias"))

    def gelu_new(x):
        return 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))

    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        length = ids.shape[0]
        wte = p("wte/embedding")
        hidden = wte[ids] + p("wpe/embedding")[:length]
        causal = jnp.tril(jnp.ones((length, length), bool))
        for i in range(n_layer):
            at = f"layer_{i}/"
            x = layer_norm(hidden, at + "attn_norm")
            q, k, v = (jnp.einsum("lh,hnd->lnd", x, p(at + name + "/kernel"))
                       + p(at + name + "/bias")
                       for name in ("query", "key", "value"))
            scores = jnp.einsum("qnd,knd->nqk", q, k) / math.sqrt(q.shape[-1])
            scores = jnp.where(causal[None], scores, -jnp.inf)
            context = jnp.einsum("nqk,knd->qnd",
                                 jax.nn.softmax(scores, axis=-1), v)
            hidden = hidden + jnp.einsum(
                "qnd,ndh->qh", context, p(at + "out/kernel")) \
                + p(at + "out/bias")
            x = layer_norm(hidden, at + "mlp_norm")
            x = gelu_new(x @ p(at + "mlp_in/kernel") + p(at + "mlp_in/bias"))
            hidden = hidden + x @ p(at + "mlp_out/kernel") \
                + p(at + "mlp_out/bias")
        logits = layer_norm(hidden, "final_norm") @ wte.T
        return jax.nn.log_softmax(logits, axis=-1)


def main(argv) -> int:
    import jax
    import numpy as np

    from chipbench.references import params as served_params

    with open(argv[1]) as f:
        job = json.load(f)
    if jax.devices()[0].platform != "cpu":
        raise SystemExit(f"the reference runs on the CPU, not {jax.devices()}")
    params = served_params.load(job["params_dir"])
    answers = []
    for case in job["cases"]:
        prompt, generated = case["prompt_ids"], case["generated_ids"]
        # Teacher forcing: the row after the prompt's last token scores the
        # first generated token, the row after that token the second, ...
        rows = np.asarray(log_probs(
            params, prompt + generated[:-1], job["n_layer"],
            job["layer_norm_epsilon"]))[len(prompt) - 1:]
        answers.append({
            "chosen": [float(rows[j, t]) for j, t in enumerate(generated)],
            "top": [float(rows[0, t]) for t in case["top_ids"]],
        })
    with open(argv[2], "w") as f:
        json.dump({"cases": answers}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
