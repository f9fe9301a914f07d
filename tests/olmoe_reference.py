"""The repo's own copy of the OLMoE reference (chipbench/references/olmoe.py
is the benchmark's; the benchmark imports nothing from here and the tests
nothing from there but for the one test that the two agree): the forward
pass of `allenai/OLMoE-1B-7B-*` in plain float32 jax.numpy.  RMSNorm;
query and key RMS-normalised over the whole projection before the split
into heads; rotary embedding (rotate-half, absolute positions); causal
softmax attention; a router whose softmax runs over all experts in float32,
the largest `experts_per_token` kept with their probabilities not
renormalised; the chosen experts' SwiGLU MLPs summed with those weights; a
final RMSNorm and an untied head.  One sequence, eagerly, no cache, no
kernel, the experts by a plain loop.  `params` is {"params/a/b": array}.
"""

import math


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
