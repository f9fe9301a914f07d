"""Mellum 2 (the forward pass of JetBrains `Mellum2-12B-A2.5B-Instruct` as
its published config.json determines it) in plain float32 jax.numpy under
`jax.default_matmul_precision("highest")`.  One layer:

    h = RMSNorm(x)
    q = h·Wq [heads, D];  k = h·Wk [kv heads, D];  v = h·Wv [kv heads, D]
    q, k = RMSNorm over D, per head, own scale
    q, k = rope_t(q), rope_t(k)      t = the layer's type; rotate-half over D
      sliding_attention: inv_freq_i = theta^(-2i/D); cos, sin unscaled
      full_attention (YaRN): low = floor(c(beta_fast)), high = ceil(c(beta_slow)),
        c(n) = D·ln(original / (2πn)) / (2·ln theta);
        ramp_i = clip((i - low) / (high - low), 0, 1);
        inv_freq_i = (1 - ramp_i)·theta^(-2i/D) + ramp_i·theta^(-2i/D)/factor;
        cos and sin both times attention_factor, at every position
    scores = q·kᵀ/sqrt(D); query head j reads KV head j // (heads / kv heads);
      key s visible to query t iff s <= t and, in a sliding layer,
      s > t - window
    x = x + softmax(scores)·v · Wo
    h = RMSNorm(x);  p = softmax(h·Wr) over all experts;  top k;
      w = p_top / Σ p_top  (norm_topk_prob)
    x = x + Σ_k w_k · down_k(silu(h·gate_k) ⊙ h·up_k)

then a final RMSNorm and an untied head.  One sequence, eagerly, no cache,
no kernel, no batching; the masks are whole [t, s] booleans; the experts by
a plain loop over all of them, each on the tokens routed to it.

    python -m chipbench.references.mellum <job.json> <out.json>   (CPU child)
    python -m chipbench.references.mellum <job.json> <out.json> float8_e4m3fn
        (by hand: the same job with weights and layer outputs rounded through
        that dtype, to show that the configuration's limits refuse it)

Everything the model's config.json says and this reads (`layer_types`,
`sliding_window`, `rope_parameters`, `num_experts_per_tok`,
`norm_topk_prob`, `rms_norm_eps`) comes from this reference's own
configuration file (chipbench/configs/mellum2-12b-a2.5b-8l.json); every
size is the served parameters' own shape.

Departures from the published checkpoint's layout, none from its
mathematics: the served model keeps each projection as [hidden, heads, D]
and the experts stacked [experts, in, out]; the reference reads the served
parameters (the served bytes, bfloat16 as stored, widened to float32
exactly), so it multiplies by those.  Attention is computed a block of
queries at a time, against the keys that some query of the block can see
(the same numbers as all at once: a row of a softmax needs its own scores
alone, and a key that the mask hides from every row adds exactly 0), so that
a prompt of 7,000 tokens fits and takes minutes, not more; the head runs on
the rows that are scored alone.

Two things the config does not settle are assumed, as in the served model
(kfserving_tpu/models/mellum.py): the per-head RMSNorm of q and k (the
config's keys are the Qwen3-MoE convention's, which has no key for that
norm and always has it), and the window's edge (s > t - window:
`transformers`' sliding mask; the window's `window` keys hold the query's
own).  The "MTP head" of the model card has no key in the config and is
left out.
"""

import json
import math
import os
import sys

CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                      "configs", "mellum2-12b-a2.5b-8l.json")
QUERY_BLOCK = 512


def settings() -> dict:
    """The published keys this reads, from its own configuration file."""
    with open(CONFIG) as f:
        config = json.load(f)
    return {key: config[key] for key in (
        "layer_types", "sliding_window", "rope_parameters",
        "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps")}


def yarn_inv_freq(np, head_dim: int, section: dict):
    """[D/2] float32 and (low, high) of a `rope_type` `yarn` section."""
    theta = float(section["rope_theta"])
    original = float(section["original_max_position_embeddings"])

    def pair(turns):
        return head_dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair(float(section["beta_fast"]))), 0)
    high = min(math.ceil(pair(float(section["beta_slow"]))), head_dim - 1)
    i = np.arange(head_dim // 2, dtype=np.float32)
    plain = theta ** (-2.0 * i / head_dim)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    blend = (1.0 - ramp) * plain + ramp * plain / float(section["factor"])
    return blend.astype(np.float32), (low, high)


def rotary(np, positions, head_dim: int, section: dict):
    """(cos, sin) [len, 1, D], each half repeated, for one layer type."""
    if section.get("rope_type", "default") == "yarn":
        inv_freq, _ = yarn_inv_freq(np, head_dim, section)
        scale = section.get("attention_factor")
        if scale is None:
            scale = 0.1 * math.log(float(section["factor"])) + 1.0
    else:
        i = np.arange(head_dim // 2, dtype=np.float32)
        inv_freq = float(section["rope_theta"]) ** (-2.0 * i / head_dim)
        scale = 1.0
    angles = positions[:, None] * inv_freq[None, :]
    cos = np.concatenate([np.cos(angles)] * 2, -1)[:, None, :]
    sin = np.concatenate([np.sin(angles)] * 2, -1)[:, None, :]
    return float(scale) * cos, float(scale) * sin


def logits(params: dict, ids, config: dict, from_row: int = 0,
           routing=None, round_to=None, leave_out=()):
    """Logits [len(ids) - from_row, vocab] of the next token after each
    position of the sequence `ids` from `from_row` on.  routing: an optional
    list that receives each layer's chosen experts.  round_to: a dtype's
    name; every weight and each layer's output is rounded through it (the
    control that a lower precision has to come out not correct).
    leave_out: names among "window", "yarn_blend", "attention_factor",
    "qk_norm", "renormalise": the tests' proof that each is load-bearing."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    eps = float(config["rms_norm_eps"])
    per_token = int(config["num_experts_per_tok"])
    window = int(config["sliding_window"])

    def rounded(x):
        x = jnp.asarray(x).astype(jnp.float32)
        return x if round_to is None else x.astype(round_to).astype(
            jnp.float32)

    def p(path):
        return rounded(params["params/" + path])

    def rms_norm(x, name):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
            * p(name + "/scale")

    def rope(x, table):
        cos, sin = table
        half = x.shape[-1] // 2
        rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
        return x * cos + rotated * sin

    def section(kind):
        out = dict(config["rope_parameters"][kind])
        if "yarn_blend" in leave_out:
            out["rope_type"] = "default"
        if "attention_factor" in leave_out:
            out["attention_factor"] = 1.0
        return out

    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        length = ids.shape[0]
        positions = jnp.arange(length, dtype=jnp.float32)
        at_t, at_s = jnp.arange(length)[:, None], jnp.arange(length)[None, :]
        masks = {"full_attention": at_s <= at_t,
                 "sliding_attention": (at_s <= at_t) & (
                     (at_s > at_t - window) | ("window" in leave_out))}
        hidden = p("wte/embedding")[ids]
        tables = {}
        for i, kind in enumerate(config["layer_types"]):
            at = f"layer_{i}/"
            x = rms_norm(hidden, at + "attn_norm")
            wq, wk, wv = (p(at + name + "/kernel")
                          for name in ("query", "key", "value"))
            heads, dim = wq.shape[1:]
            kv_heads = wk.shape[1]
            q = jnp.einsum("th,hnd->tnd", x, wq)
            k = jnp.einsum("th,hnd->tnd", x, wk)
            v = jnp.einsum("th,hnd->tnd", x, wv)
            if "qk_norm" not in leave_out:
                q, k = rms_norm(q, at + "q_norm"), rms_norm(k, at + "k_norm")
            if kind not in tables:
                tables[kind] = rotary(jnp, positions, dim, section(kind))
            q, k = rope(q, tables[kind]), rope(k, tables[kind])
            # Query head j reads KV head j // (heads / kv heads).
            k = jnp.repeat(k, heads // kv_heads, axis=1)
            v = jnp.repeat(v, heads // kv_heads, axis=1)
            context = []
            for start in range(0, length, QUERY_BLOCK):
                rows = slice(start, start + QUERY_BLOCK)
                # The keys some query of the block can see; the mask is
                # the whole one's rows and columns for them.
                first = 0 if kind == "full_attention" \
                    or "window" in leave_out else max(0, start - window + 1)
                keys = slice(first, start + QUERY_BLOCK)
                scores = jnp.einsum("qnd,knd->nqk", q[rows], k[keys]) \
                    / math.sqrt(dim)
                scores = jnp.where(masks[kind][rows, keys][None], scores,
                                   -jnp.inf)
                context.append(jnp.einsum(
                    "nqk,knd->qnd", jax.nn.softmax(scores, axis=-1),
                    v[keys]))
            hidden = hidden + jnp.einsum("qnd,ndh->qh",
                                         jnp.concatenate(context),
                                         p(at + "out/kernel"))
            x = rms_norm(hidden, at + "mlp_norm")
            probs = jax.nn.softmax(x @ p(at + "experts/router/kernel"),
                                   axis=-1)
            top, chosen = jax.lax.top_k(probs, per_token)
            if config["norm_topk_prob"] and "renormalise" not in leave_out:
                top = top / top.sum(-1, keepdims=True)
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
        return rms_norm(hidden[from_row:], "final_norm") \
            @ p("lm_head/kernel")


def log_probs(params: dict, ids, config: dict, **kw):
    import jax

    return jax.nn.log_softmax(logits(params, ids, config, **kw), axis=-1)


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
    config = settings()
    assert len(config["layer_types"]) == job["n_layer"]
    answers = []
    for case in job["cases"]:
        prompt, generated = case["prompt_ids"], case["generated_ids"]
        # Teacher forcing: the row after the prompt's last token scores the
        # first generated token, the row after that token the second, ...
        rows = np.asarray(log_probs(
            params, prompt + generated[:-1], config,
            from_row=len(prompt) - 1,
            round_to=argv[3] if len(argv) > 3 else None))
        answers.append({
            "chosen": [float(rows[j, t]) for j, t in enumerate(generated)],
            "top": [float(rows[0, t]) for t in case["top_ids"]],
        })
    with open(argv[2], "w") as f:
        json.dump({"cases": answers}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
