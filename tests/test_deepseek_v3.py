"""DeepSeek-V3 / Moonlight (models/deepseek_v3.py: latent attention, whose
cache is one row a token a layer in a pool of its own kind, absorbed against
the pool and expanded in a whole-prompt prefill; a dense layer, then sigmoid-
routed SwiGLU experts with a shared one) against the plain reference
(tests/deepseek_v3_reference.py, the expanded form only) at
`deepseek_v3_tiny` size on seeded weights: 3 layers, 4 heads of 16 + 8 over
a rank of 24, values of 12, 8 experts (3 a token).

Everything compares logits or log-probabilities, never sampled tokens alone.
Both sides compute in float32 on the CPU, so they differ by the order of
their sums only (the absorbed form sums over the rank where the expanded
sums over the head): 1e-5 on logits of magnitude 1.  The tolerance, 1e-4, is
a twentieth of what dropping `k_pe`'s term from the score moves and under a
hundredth of what float8 weights move (`test_the_controls_fail`).
"""

import asyncio
import logging
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.traverse_util import flatten_dict, unflatten_dict

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import deepseek_v3_reference as reference  # noqa: E402

from kfserving_tpu.engine import programs  # noqa: E402
from kfserving_tpu.engine.generator import GenerationEngine  # noqa: E402
from kfserving_tpu.models import create_model, init_params  # noqa: E402
from kfserving_tpu.models.decoder import LatentCache  # noqa: E402
from kfserving_tpu.models.deepseek_v3 import (  # noqa: E402
    DeepseekV3Config,
    ExpertLayer,
    LatentAttention,
)
from kfserving_tpu.models.olmoe import rope_tables  # noqa: E402
from kfserving_tpu.ops import moe, paged_attention  # noqa: E402
from kfserving_tpu.protocol.errors import InvalidInput  # noqa: E402

TOL = 1e-4
MAX_SEQ = 128
BS = 16


def model_of(cfg) -> dict:
    """The published config's keys that the reference reads."""
    return dict(
        num_hidden_layers=cfg.num_layers, rms_norm_eps=cfg.rms_norm_eps,
        rope_theta=cfg.rope_theta, qk_nope_head_dim=cfg.qk_nope_head_dim,
        kv_lora_rank=cfg.kv_lora_rank,
        num_experts_per_tok=cfg.experts_per_token,
        routed_scaling_factor=cfg.routed_scaling_factor,
        first_k_dense_replace=cfg.first_dense_layers)


def flat_of(variables) -> dict:
    return {"/".join(k): np.asarray(v)
            for k, v in flatten_dict(variables).items()}


@pytest.fixture(scope="module")
def tiny():
    spec = create_model("deepseek_v3_tiny", max_seq=MAX_SEQ)
    variables = init_params(spec, seed=5)
    # A bias that matters: the seeded one is zeros, as the served one.
    flat = flatten_dict(variables)
    for key in flat:
        if key[-1] == "router_bias":
            flat[key] = jnp.asarray(
                np.random.default_rng(7).normal(0, 0.05, flat[key].shape),
                jnp.float32)
    variables = unflatten_dict(flat)
    return spec.module, variables, flat_of(variables)


def prompt_of(n, stride=7):
    return [(i * stride) % 250 + 1 for i in range(n)]


_compiled = {}


def ref_logits(tiny, ids, flat=None, **controls):
    """The reference's logits, its plain operations compiled as one program
    a length (eagerly, each compiles alone: seconds a length)."""
    module, _, served_flat = tiny
    model = model_of(module.config)
    key = tuple(sorted(controls.items()))
    if key not in _compiled:
        _compiled[key] = jax.jit(lambda params, tokens: reference.logits(
            params, tokens, model, **controls))
    return np.asarray(_compiled[key](flat or served_flat,
                                     jnp.asarray(ids, jnp.int32)))


def ref_log_probs(tiny, ids):
    return np.asarray(jax.nn.log_softmax(ref_logits(tiny, ids), axis=-1))


async def served(engine, prompt, steps):
    req = engine.submit(prompt, steps, logprobs=5)
    tokens = [t async for t, _ in engine.stream(req) if t is not None]
    return tokens, req.lp_chosen, req.lp_top


def assert_matches_reference(tiny, prompt, tokens, chosen, top):
    """Teacher forcing: the reference's row after the prompt's last token
    scores the first served token, the next row the second, ..."""
    rows = ref_log_probs(tiny, prompt + tokens[:-1])[len(prompt) - 1:]
    assert len(tokens) == len(chosen) == len(top) == len(rows)
    for row, token, lp, record in zip(rows, tokens, chosen, top):
        assert token == int(np.argmax(row))
        assert abs(lp - row[token]) < TOL
        for tid, tlp in record:
            assert abs(tlp - row[tid]) < TOL


def engine_of(tiny, **kw):
    module, variables, _ = tiny
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("prefill_buckets", [64])
    kw.setdefault("block_size", BS)
    kw.setdefault("steps_per_call", 4)
    return GenerationEngine(module, variables, name="moonlight-test", **kw)


# -- (a) the model against the reference -------------------------------------
@pytest.mark.parametrize("length", [1, 16, 37])
def test_full_forward_logits(tiny, length):
    module, variables, _ = tiny
    ids = prompt_of(length)
    got = np.asarray(module.apply(variables, jnp.asarray([ids])))[0]
    np.testing.assert_allclose(got, ref_logits(tiny, ids), atol=TOL, rtol=0)


def test_the_tiny_model_is_the_shape_the_tests_say(tiny):
    module, variables, flat = tiny
    cfg = module.config
    assert (cfg.num_layers, cfg.first_dense_layers, cfg.expert_layers) == (
        3, 1, 2)
    assert flat["params/layer_0/attention/kv_b"].shape == (24, 4, 16 + 12)
    assert flat["params/layer_0/attention/kv_a/kernel"].shape == (96, 24 + 8)
    assert "params/layer_0/mlp/gate/kernel" in flat
    assert flat["params/layer_1/experts/gate"].shape == (8, 96, 24)
    assert flat["params/layer_1/experts/shared/up/kernel"].shape == (96, 48)
    n = sum(int(np.prod(x.shape)) for x in flat.values())
    assert n == cfg.param_counts()["total"]


def test_the_controls_fail(tiny):
    """What the tolerance has to refuse: the reference in float8, and the
    score without `k_pe`'s term."""
    ids = prompt_of(48)
    want = ref_logits(tiny, ids)
    for control in ({"round_to": "float8_e4m3fn"}, {"drop_k_pe": True}):
        assert np.abs(ref_logits(tiny, ids, **control) - want).max() \
            > 20 * TOL, control


def test_the_two_copies_of_the_reference_agree(tiny):
    from chipbench.references import deepseek_v3 as benchmarks_copy

    module, _, flat = tiny
    ids = prompt_of(40)
    model = model_of(module.config)
    (theirs,) = benchmarks_copy.logits(flat, [ids], model)
    np.testing.assert_allclose(theirs, ref_logits(tiny, ids), atol=1e-5,
                               rtol=0)
    for control in ({"round_to": "float8_e4m3fn"}, {"drop_k_pe": True}):
        (theirs,) = benchmarks_copy.logits(flat, [ids], model, **control)
        np.testing.assert_allclose(theirs, ref_logits(tiny, ids, **control),
                                   atol=1e-5, rtol=0)


# -- (b) one layer's attention: absorbed against expanded ----------------------
def test_absorbed_attention_is_the_expanded_and_inserts_the_latent_row(tiny):
    """One layer's attention over 40 tokens, expanded (a prefill) and then
    absorbed over the pool the prefill's rows were inserted into (a chunk
    of all 40 queries, and each position as a decode step): the same
    numbers to 1e-5.  The inserted row is (c normed, k_pe rotated), the
    reference's, in the pool's first 32 columns, zeros after."""
    module, variables, flat = tiny
    cfg = module.config
    attention = LatentAttention(cfg)
    params = {"params": variables["params"]["layer_1"]["attention"]}
    length, bucket = 40, 48
    hidden = jnp.asarray(np.random.default_rng(2).normal(
        0, 1, (1, bucket, cfg.hidden_size)), jnp.float32)
    pos = jnp.arange(bucket)[None, :]
    rotary = rope_tables(pos, cfg.qk_rope_head_dim, cfg.rope_theta)
    expanded, (rows,) = attention.apply(
        params, hidden, pos, rotary, kv_lengths=jnp.asarray([length]))
    w = {k[len("params/layer_1/attention/"):]: jnp.asarray(v)
         for k, v in flat.items()
         if k.startswith("params/layer_1/attention/")}
    c, k_pe = reference.latent_rows(hidden[0], w, model_of(cfg))
    np.testing.assert_allclose(rows[0], np.concatenate([c, k_pe], axis=1),
                               atol=1e-5, rtol=0)
    pool = jnp.zeros(paged_attention.latent_pool_shape(8, BS, 32))
    assert pool.shape == (8, BS, 128)
    table = jnp.asarray([[5, 2, 7, -1]], jnp.int32)
    pool = paged_attention.latent_insert(pool, rows, table[:, :3])
    np.testing.assert_array_equal(pool[2, :, :32], rows[0, BS:2 * BS])
    assert not np.asarray(pool[:, :, 32:]).any()
    chunk, (again,) = attention.apply(params, hidden, pos, rotary,
                                      cache=(pool, table))
    np.testing.assert_allclose(chunk[0, :length], expanded[0, :length],
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(again, pool, atol=1e-6, rtol=0)
    for t in (0, 15, 16, 39):
        step, _ = attention.apply(
            params, hidden[:, t:t + 1], pos[:, t:t + 1],
            rope_tables(pos[:, t:t + 1], cfg.qk_rope_head_dim,
                        cfg.rope_theta), cache=(pool, table))
        np.testing.assert_allclose(step[0, 0], expanded[0, t], atol=1e-5,
                                   rtol=0)


# -- (c) the router and the shared expert --------------------------------------
def test_the_router_against_a_hand_computed_case():
    """4 experts, 2 a token, scale 2.446.  Scores sigmoid(logits) =
    (0.6, 0.5, 0.4, 0.2); without a bias experts 0 and 1 are chosen; a
    bias of +0.3 on expert 3 makes it 0 and 3 (0.6, 0.5 against 0.5 + 0 =
    0.5 ties go to the lower index: 0.5001 settles it), and the weights
    are of the SCORES, not of the biased ones: 2.446·0.6/0.8 and
    2.446·0.2/0.8."""
    scores = np.asarray([[0.6, 0.5, 0.4, 0.2]])
    logits = jnp.asarray(np.log(scores / (1 - scores)), jnp.float32)
    weights, experts = moe.route_sigmoid(logits, jnp.zeros(4), 2, 2.446)
    assert experts.tolist() == [[0, 1]]
    np.testing.assert_allclose(weights, [[2.446 * 0.6 / 1.1,
                                          2.446 * 0.5 / 1.1]], rtol=1e-6)
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.3001])
    weights, experts = moe.route_sigmoid(logits, bias, 2, 2.446)
    assert experts.tolist() == [[0, 3]]
    np.testing.assert_allclose(weights, [[2.446 * 0.6 / 0.8,
                                          2.446 * 0.2 / 0.8]], rtol=1e-6)
    # the reference's dense form of the same
    dense = reference.route(
        jnp.eye(4), {"router/kernel": jnp.broadcast_to(logits, (4, 4)),
                     "router_bias": bias},
        {"num_experts_per_tok": 2, "routed_scaling_factor": 2.446})
    np.testing.assert_allclose(
        dense[0], [2.446 * 0.6 / 0.8, 0, 0, 2.446 * 0.2 / 0.8], rtol=1e-6)


def test_the_shared_expert_is_counted_once(tiny):
    """With every routed expert's `down` zero the layer IS the shared
    SwiGLU of 2 x 24, once."""
    module, variables, _ = tiny
    cfg = module.config
    params = jax.tree.map(lambda x: x,
                          variables["params"]["layer_1"]["experts"])
    params["down"] = jnp.zeros_like(params["down"])
    x = jnp.asarray(np.random.default_rng(4).normal(0, 1, (1, 9, 96)),
                    jnp.float32)
    got, state = ExpertLayer(cfg).apply({"params": params}, x,
                                        mutable=["moe"])
    shared = {k: np.asarray(v["kernel"]) for k, v in params["shared"].items()}
    h = np.asarray(x[0])
    gate = h @ shared["gate"]
    want = (gate / (1 + np.exp(-gate)) * (h @ shared["up"])) @ shared["down"]
    np.testing.assert_allclose(got[0], want, atol=1e-5, rtol=0)
    assert int(state["moe"]["pairs"].sum()) == 9 * cfg.experts_per_token


# -- (d) through the engine: the latent pool -----------------------------------
async def test_prefill_then_decode_through_the_latent_pool(tiny, caplog):
    """Rows of different lengths admitted together (one expanded prefill
    whose rows are inserted into every layer's pool), decoded absorbed over
    several calls, and a fourth request that takes a finished request's
    slot: each on the reference's full forward pass."""
    prompts = [prompt_of(n, stride)
               for n, stride in ((5, 3), (33, 5), (60, 11), (18, 13))]
    paged_attention.attention.log_dispatch.cache_clear()
    with caplog.at_level(logging.INFO, logger="kfserving_tpu.ops"):
        engine = engine_of(tiny)
        try:
            results = await asyncio.wait_for(asyncio.gather(*[
                served(engine, p, steps)
                for p, steps in zip(prompts, (6, 14, 9, 12))]), timeout=300)
            stats = engine.stats()
        finally:
            await engine.close()
    for prompt, (tokens, chosen, top) in zip(prompts, results):
        assert_matches_reference(tiny, prompt, tokens, chosen, top)
    assert stats["requests_finished"] == 4
    # 3 layers of 24 + 8 float32 a token; the pool holds them 128 wide
    assert stats["kv_bytes_per_token"] == 3 * 32 * 4
    pools = stats["paged"]["pools"]
    assert list(pools) == ["latent"]
    assert pools["latent"]["blocks"] == 3 * MAX_SEQ // BS
    assert pools["latent"]["bytes"] == stats["cache_bytes"] \
        == 3 * 24 * BS * 128 * 4
    assert 0 < pools["latent"]["block_fill"] <= 1
    assert stats["moe_experts_touched_mean"] > 1
    assert "attention path=xla_latent" in caplog.text
    from kfserving_tpu.observability import metrics as obs

    text = obs.REGISTRY.render()
    for family in ("kv_pool_blocks", "kv_pool_bytes",
                   "decode_kv_pool_blocks_walked_total"):
        assert (f'kfserving_tpu_generator_{family}{{model="moonlight-test",'
                f'pool="latent"}}') in text.replace(", ", ","), family


async def test_a_preempted_request_resumes_on_the_references_logits(tiny):
    prompts = [prompt_of(42, stride) for stride in (3, 5, 11)]
    engine = engine_of(tiny, cache_blocks=10)  # 3 x (42 + 20) needs 12
    try:
        results = await asyncio.wait_for(asyncio.gather(*[
            served(engine, p, 20) for p in prompts]), timeout=300)
        assert engine.stats()["paged"]["preemptions"] >= 1
    finally:
        await engine.close()
    for prompt, (tokens, chosen, top) in zip(prompts, results):
        assert_matches_reference(tiny, prompt, tokens, chosen, top)


# -- (e) what rests on rows addressed by position ------------------------------
@pytest.mark.parametrize("setting, prompt, stat", [
    ({"prefill_chunk_tokens": 32}, prompt_of(75),
     lambda s: s["chunked_prefill"]["chunks_dispatched"] >= 3),
    ({"speculative": {"tokens": 3}}, [5, 9, 2, 7] * 6,
     lambda s: s["speculative"]["waves"] >= 1),
], ids=["chunked-prefill", "speculative-verify"])
async def test_a_latent_row_is_addressed_by_position(tiny, setting, prompt,
                                                     stat):
    """Chunks of a prompt and a verify wave write their rows through the
    table and attend absorbed over the pool (XLA, Lq > 1), as OLMoE's do
    over K/V."""
    engine = engine_of(tiny, **setting)
    try:
        tokens, chosen, top = await served(engine, prompt, 12)
        assert stat(engine.stats())
    finally:
        await engine.close()
    assert_matches_reference(tiny, prompt, tokens, chosen, top)


async def test_a_shared_prefix_is_reused(tiny):
    """The second prompt's first 48 tokens are the first's: their three
    blocks are found in the index, and only the rest is prefilled (a chunk
    against the pool)."""
    first = prompt_of(50)
    second = first[:48] + prompt_of(9, 11)
    engine = engine_of(tiny)
    try:
        one = await served(engine, first, 6)
        two = await served(engine, second, 6)
        paged = engine.stats()["paged"]
    finally:
        await engine.close()
    assert paged["prefix_hits"] >= 1 and paged["prefill_tokens_saved"] >= 48
    assert_matches_reference(tiny, first, *one)
    assert_matches_reference(tiny, second, *two)


def test_the_host_tier_is_refused_at_load(tiny):
    with pytest.raises(InvalidInput, match="latent rows.*one array"):
        engine_of(tiny, host_tier_blocks=8)
    assert set(programs.UNSERVED["latent rows"]) == {"host_tier_blocks"}


# -- (f) the layout at the published shapes ------------------------------------
def test_the_cache_layout_at_the_published_shapes():
    """Seven published layers under the cell's serving sizes, by
    `jax.eval_shape` (nothing is allocated): one pool a layer, 576 numbers
    a token in rows of 640, 8,064 bytes a token over the layers."""
    cfg = DeepseekV3Config(num_layers=7)
    assert cfg.cache_layers() == [LatentCache(512, 64)] * 7
    layouts = []

    def build():
        layouts.append(programs.CacheLayout(
            cfg, "moonlight", max_slots=128, max_seq=8192,
            prefill_buckets=[1024, 6144], block_size=128, cache_blocks=4096,
            window_cache_blocks=None, mesh=None))
        return layouts[0].caches

    shapes = jax.eval_shape(build)
    layout = layouts[0]
    assert layout.limits == ("latent rows",) and layout.shares_prefixes
    assert layout.kv_bytes_per_token == 8064 == 7 * 576 * 2
    assert layout.pool_shape == (4096, 128, 640) and layout.kv_layers == 7
    assert layout.cache_bytes == layout.pool_bytes["latent"] \
        == 7 * 4096 * 128 * 640 * 2
    assert layout.walk_chunks[0] == 4 and layout.blocks_per_slot == 64
    assert not any(programs.packs_prompts(layout.kinds, b, layout.block_size)
                   for b in (1024, 6144))
    for (pool,) in shapes:
        assert pool.shape == (4096, 128, 640) and pool.dtype == jnp.bfloat16
    counts = cfg.param_counts()
    assert counts["per_expert"] == 8_650_752
    assert round(counts["total"] / 1e9, 3) == 4.263


def test_latent_and_kv_layers_do_not_mix():
    from kfserving_tpu.models.decoder import KVCache

    class Mixed:
        dtype = jnp.float32

        def cache_layers(self):
            return [KVCache(1, 32), LatentCache(24, 8)]

    with pytest.raises(InvalidInput, match="all of them latent"):
        programs.CacheLayout(Mixed(), "mixed", max_slots=2, max_seq=64,
                             prefill_buckets=[64], block_size=16,
                             cache_blocks=8, window_cache_blocks=None,
                             mesh=None)


# -- (g) the kernels, interpreted ---------------------------------------------
@pytest.mark.parametrize("heads, columns, dtype", [
    (16, 8, jnp.bfloat16),    # 4 blocks an iteration, one tile of queries
    (4, 2, jnp.float32),      # rows padded to a tile, 2 blocks an iteration
    (16, 1, jnp.bfloat16),    # one block an iteration: no `walked_ref`
])
def test_latent_kernels_in_interpret_mode_against_xla(heads, columns, dtype):
    """`latent_attention_tpu` and `latent_write_tpu` (Pallas, interpreted)
    against the gather and the scatter, at the served row (512 + 64 in
    640): rows of 0, 1 and several blocks, a parked row, one whose table
    ends early."""
    rng = np.random.default_rng(11)
    rank, width, bs, nb, slots = 512, 576, 128, 20, 5
    pool = jnp.zeros(paged_attention.latent_pool_shape(nb, bs, width), dtype)
    filled = jnp.asarray(rng.normal(0, 1, (nb, bs, width)), dtype)
    pool = pool.at[:, :, :width].set(filled)
    table = np.full((slots, columns), -1, np.int32)
    lengths = np.asarray([1, bs * columns - 3, 0, 70, bs * columns + 9],
                         np.int32)[:slots]
    free = iter(rng.permutation(nb))
    for row, n in enumerate(lengths):
        for column in range(min(-(-int(n) // bs), columns)):
            table[row, column] = next(free)
    table, lengths = jnp.asarray(table), jnp.asarray(lengths)
    q = jnp.asarray(rng.normal(0, 0.3, (slots, 1, heads, width)), dtype)
    scale = 192 ** -0.5
    assert paged_attention.blocks_per_iteration(
        bs, pool.shape[2], dtype, columns, copies=1) == min(
            columns, 4 if dtype == jnp.bfloat16 else 3)
    got = paged_attention.latent_attention_tpu(
        paged_attention._pool_wide(q, pool), pool, table, lengths,
        rank=rank, scale=scale, interpret=True)
    want = paged_attention.latent_attention_xla(
        paged_attention._pool_wide(q, pool), pool, table,
        lengths[:, None] - 1, rank, scale)
    live = np.asarray((lengths > 0) & (lengths <= bs * columns))
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32)[live],
                               np.asarray(want, np.float32)[live],
                               atol=tol, rtol=tol)
    assert not np.asarray(got, np.float32)[~live].any()
    # the write: a row a slot at its position, dropped where -1
    step = jnp.asarray(rng.normal(0, 1, (slots, width)), dtype)
    positions = jnp.maximum(lengths - 1, 0)
    blocks, offs, dropped = paged_attention._write_targets(table, positions,
                                                           bs)
    wrote = paged_attention.latent_write_tpu(
        pool, paged_attention._pool_wide(step, pool),
        jnp.where(dropped, -1, blocks), offs, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(wrote, np.float32),
        np.asarray(paged_attention.latent_write(pool, step, table,
                                                positions), np.float32))
    assert np.asarray(dropped).tolist() == [False, False, True, False, True]


def test_flash_attention_takes_values_of_their_own_width(monkeypatch):
    """The expanded prefill's attention: keys 192 wide, values 128, scores
    scaled by the keys' width, under a padded causal bucket."""
    import functools

    from jax.experimental import pallas as pl

    from kfserving_tpu.ops import attention, pallas_attention

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    rng = np.random.default_rng(3)
    q, k = (jnp.asarray(rng.normal(0, 1, (2, 256, 2, 192)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(0, 1, (2, 256, 2, 128)), jnp.float32)
    lengths = jnp.asarray([256, 150], jnp.int32)
    got = pallas_attention.flash_attention.__wrapped__(
        q, k, v, causal=True, kv_lengths=lengths, block_q=128, block_k=128)
    mask = jnp.tril(jnp.ones((256, 256), bool))[None, None] & (
        jnp.arange(256)[None, :] < lengths[:, None])[:, None, None, :]
    want = attention._xla_attention(q, k, v, mask)
    assert got.shape == (2, 256, 2, 128)
    np.testing.assert_allclose(np.asarray(got)[1, :150],
                               np.asarray(want)[1, :150], atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_allclose(got[0], want[0], atol=2e-5, rtol=2e-5)
