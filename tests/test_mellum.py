"""Mellum 2 (models/mellum.py): sliding-window layers beside whole-context
ones, two rotary tables, renormalised experts, through the engine's two
pools, against the plain reference (tests/mellum_reference.py) at
`mellum_tiny` size on seeded weights: window 16, blocks of 8 (a ring of 3),
8 query heads on 2 KV heads of 32 (hidden 128: `head_dim` is not hidden /
heads), 4 layers in the published 3:1 pattern, 8 experts 2 a token.

Logits and log-probabilities are compared, never sampled tokens alone: with
random weights the largest logit changes on rounding.  Both sides compute
in float32 on the CPU, so they differ by the order of their sums only: 5e-6
on logits of magnitude 4 here.  The tolerance, 1e-4, is twenty times that
and under a thousandth of what leaving out any term moves: the window 5.3,
the YaRN blend 0.9, its attention factor 0.7, the QK-norm 3.1, the
renormalisation 2.8 (`test_the_reference_needs_each_term`).
"""

import asyncio
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.traverse_util import flatten_dict

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import mellum_reference as reference  # noqa: E402

from kfserving_tpu.engine.generator import GenerationEngine  # noqa: E402
from kfserving_tpu.models import create_model, init_params, mellum  # noqa: E402
from kfserving_tpu.models.decoder import KVCache, cached_attention  # noqa: E402
from kfserving_tpu.observability import metrics as obs  # noqa: E402
from kfserving_tpu.ops import dot_product_attention, moe  # noqa: E402
from kfserving_tpu.ops import paged_attention as pa  # noqa: E402
from kfserving_tpu.protocol.errors import InvalidInput  # noqa: E402

TOL = 1e-4
MAX_SEQ = 256
BS = 8
WINDOW = 16
RING = 3  # ceil(16 / 8) + 1


def config_of(cfg) -> dict:
    """The published keys the reference reads, from a MellumConfig."""
    return {"rms_norm_eps": cfg.rms_norm_eps,
            "num_experts_per_tok": cfg.experts_per_token,
            "norm_topk_prob": cfg.norm_topk_prob,
            "sliding_window": cfg.sliding_window,
            "layer_types": list(cfg.layer_types),
            "rope_parameters": cfg.rope_parameters}


@pytest.fixture(scope="module")
def tiny():
    spec = create_model("mellum_tiny", max_seq=MAX_SEQ)
    variables = init_params(spec, seed=3)
    flat = {"/".join(k): np.asarray(v)
            for k, v in flatten_dict(variables).items()}
    return spec.module, variables, flat, config_of(spec.module.config)


def prompt_of(n, stride=7):
    return [(i * stride) % 250 + 1 for i in range(n)]


async def served(engine, prompt, steps, watch=None):
    """(tokens, chosen log-probabilities, top-5 records) of one greedy
    request; `watch(engine)` is called after every token."""
    req = engine.submit(prompt, steps, logprobs=5)
    tokens = []
    async for t, _ in engine.stream(req):
        if t is not None:
            tokens.append(t)
            if watch is not None:
                watch(engine)
    return tokens, req.lp_chosen, req.lp_top


def assert_matches_reference(tiny, prompt, tokens, chosen, top):
    """Teacher forcing: the reference's row after the prompt's last token
    scores the first served token, the next row the second, ..."""
    _, _, flat, config = tiny
    rows = np.asarray(reference.log_probs(
        flat, prompt + tokens[:-1], config, from_row=len(prompt) - 1))
    assert len(tokens) == len(chosen) == len(top) == len(rows)
    for row, token, lp, record in zip(rows, tokens, chosen, top):
        assert token == int(np.argmax(row))
        assert abs(lp - row[token]) < TOL
        for tid, tlp in record:
            assert abs(tlp - row[tid]) < TOL


def engine_of(tiny, **kw):
    module, variables = tiny[:2]
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("prefill_buckets", [32, 64, 128])
    kw.setdefault("block_size", BS)
    kw.setdefault("steps_per_call", 4)
    return GenerationEngine(module, variables, name="mellum-test", **kw)


# -- the model against the reference -----------------------------------------
def test_full_forward_logits_over_several_windows(tiny):
    module, variables, flat, config = tiny
    ids = prompt_of(100)  # six windows, twelve blocks
    want = np.asarray(reference.logits(flat, ids, config))
    got = np.asarray(module.apply(variables, jnp.asarray([ids])))[0]
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    # ... and a padded bucket beside another row changes nothing
    padded = jnp.asarray([ids + [0] * 28, prompt_of(128, 5)])
    got, _ = module.apply(variables, padded,
                          kv_lengths=jnp.asarray([100, 128]),
                          return_cache=True)
    np.testing.assert_allclose(np.asarray(got)[0, :100], want, atol=TOL,
                               rtol=0)


@pytest.mark.parametrize("term", ["window", "yarn_blend", "attention_factor",
                                  "qk_norm", "renormalise"])
def test_the_reference_needs_each_term(tiny, term):
    """The reference with one term of the layer left out is far from the
    model: each is load-bearing in the comparison above."""
    module, variables, flat, config = tiny
    ids = prompt_of(100)
    got = np.asarray(module.apply(variables, jnp.asarray([ids])))[0]
    without = np.asarray(reference.logits(flat, ids, config,
                                          leave_out=(term,)))
    assert np.abs(got - without).max() > 1000 * TOL


def test_the_two_copies_of_the_reference_agree(tiny):
    from chipbench.references import mellum as benchmarks_copy

    _, _, flat, config = tiny
    ids = prompt_of(60)
    ours = np.asarray(reference.logits(flat, ids, config, from_row=50))
    theirs = np.asarray(benchmarks_copy.logits(flat, ids, config,
                                               from_row=50))
    np.testing.assert_array_equal(ours, theirs)
    # the published sections come from its own configuration file
    published = benchmarks_copy.settings()
    assert published["sliding_window"] == 1024
    assert published["layer_types"] == list(mellum.layer_pattern(8))
    assert published["rope_parameters"] == mellum.ROPE_PARAMETERS


def test_head_dim_is_the_configs_own_and_the_cache_declares_windows(tiny):
    module, variables = tiny[:2]
    cfg = module.config
    assert cfg.head_dim * cfg.num_heads != cfg.hidden_size
    layer = variables["params"]["layer_0"]
    assert layer["query"]["kernel"].shape == (128, 8, 32)
    assert layer["key"]["kernel"].shape == (128, 2, 32)
    assert layer["q_norm"]["scale"].shape == (32,)  # per head, one scale
    assert cfg.cache_layers() == [KVCache(2, 32, WINDOW)] * 3 \
        + [KVCache(2, 32, None)]
    published = mellum.MellumConfig()
    counts = published.param_counts()
    assert counts["total"] == 28 * 417_747_712 + 452_984_832 + 2304
    assert 2.4e9 < counts["active"] + 2304 * 98304 < 2.5e9  # "A2.5B"
    assert published.layer_types == mellum.layer_pattern(28)
    assert published.layer_types.count(mellum.FULL) == 7


# -- rotary tables -----------------------------------------------------------
def test_yarn_table_against_the_closed_form():
    """At the published sizes: the blend runs between pairs 18 and 35, the
    fast pairs keep their frequency, the slow ones turn 16 times slower,
    and cos and sin carry 0.1 ln 16 + 1 at every position."""
    section = mellum.ROPE_PARAMETERS[mellum.FULL]
    assert mellum.yarn_correction_range(128, 500000, 8192, 32, 1) == (18, 35)
    inv_freq = np.asarray(mellum.yarn_inv_freq(128, 500000, 16, 8192, 32, 1))
    i = np.arange(64)
    plain = 500000.0 ** (-2.0 * i / 128)
    ramp = np.clip((i - 18) / (35 - 18), 0, 1)
    want = (1 - ramp) * plain + ramp * plain / 16
    np.testing.assert_allclose(inv_freq, want, rtol=1e-6)
    np.testing.assert_allclose(inv_freq[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(inv_freq[35:], plain[35:] / 16, rtol=1e-6)
    ours, (low, high) = reference.yarn_inv_freq(np, 128, section)
    assert (low, high) == (18, 35)
    np.testing.assert_allclose(ours, want, rtol=1e-6)
    assert section["attention_factor"] == pytest.approx(
        0.1 * math.log(16) + 1, rel=1e-12)
    positions = jnp.asarray([[0, 1, 1500, 9000]])
    cos, sin = mellum.rotary_tables(positions, 128, section)
    assert cos.shape == (1, 4, 1, 64)
    np.testing.assert_allclose(np.asarray(cos)[0, 0, 0],
                               1.2772588722239782, rtol=1e-6)
    angles = np.asarray(positions)[0, :, None] * want[None, :]
    np.testing.assert_allclose(
        np.asarray(cos)[0, :, 0] ** 2 + np.asarray(sin)[0, :, 0] ** 2,
        1.2772588722239782 ** 2, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(sin)[0, :3, 0],
                               1.2772588722239782 * np.sin(angles[:3]),
                               atol=2e-4)
    # the sliding layers' table is the plain one, unscaled
    cos, _ = mellum.rotary_tables(positions, 128,
                                  mellum.ROPE_PARAMETERS[mellum.SLIDING])
    np.testing.assert_allclose(
        np.asarray(cos)[0, 1, 0], np.cos(plain), rtol=1e-5, atol=1e-6)


# -- the router --------------------------------------------------------------
def test_renormalised_weights_sum_to_one_and_olmoes_do_not():
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(32, 8)),
                         jnp.float32)
    plain, experts = moe.route(logits, 2)
    renormalised, same = moe.route(logits, 2, renormalise=True)
    np.testing.assert_array_equal(np.asarray(experts), np.asarray(same))
    np.testing.assert_allclose(np.asarray(renormalised).sum(-1), 1.0,
                               rtol=1e-6)
    assert (np.asarray(plain).sum(-1) < 0.99).all()
    np.testing.assert_allclose(
        np.asarray(renormalised),
        np.asarray(plain) / np.asarray(plain).sum(-1, keepdims=True),
        rtol=1e-6)
    # ... and each model asks for its own: OLMoE's config has no such key
    from kfserving_tpu.models.olmoe import olmoe_tiny

    assert not hasattr(olmoe_tiny(), "norm_topk_prob")
    assert mellum.mellum_tiny().norm_topk_prob


# -- a window on every branch of the attention -------------------------------
def _qkv(rng, b, l, heads, kv_heads, d):
    return (jnp.asarray(rng.normal(size=(b, l, heads, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, l, kv_heads, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, l, kv_heads, d)), jnp.float32))


def _plain_attention(q, k, v, window=None, lengths=None):
    """[B, L, H, D] by the definition: query t sees key s iff s <= t and
    s > t - window, query head j on KV head j // group."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    b, l, heads, d = q.shape
    group = heads // k.shape[2]
    out = np.zeros_like(q)
    for t in range(l):
        lo = 0 if window is None else max(0, t - window + 1)
        for h in range(heads):
            s = np.einsum("bd,bkd->bk", q[:, t, h],
                          k[:, lo:t + 1, h // group]) / math.sqrt(d)
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[:, t, h] = np.einsum("bk,bkd->bd", p,
                                     v[:, lo:t + 1, h // group])
    return out


def test_full_forward_attention_with_a_window_and_grouped_heads():
    q, k, v = _qkv(np.random.default_rng(1), 2, 40, 8, 2, 32)
    got, _ = cached_attention(q, k, v, window=WINDOW)
    np.testing.assert_allclose(np.asarray(got),
                               _plain_attention(q, k, v, WINDOW),
                               atol=2e-5, rtol=0)
    # the pluggable attention is handed the same band
    seen = {}

    def attn_fn(q, k, v, mask):
        seen["mask"] = np.asarray(mask)
        return dot_product_attention(q, k, v, mask=mask)

    cached_attention(q, k, v, window=WINDOW, attn_fn=attn_fn)
    t, s = np.arange(40)[:, None], np.arange(40)[None, :]
    np.testing.assert_array_equal(seen["mask"][0, 0],
                                  (s <= t) & (s > t - WINDOW))


def test_a_window_as_long_as_the_sequence_is_full_attention_bit_for_bit(
        tiny):
    q, k, v = _qkv(np.random.default_rng(2), 2, 40, 8, 2, 32)
    full, _ = cached_attention(q, k, v)
    for window in (40, 41, 4096):
        windowed, _ = cached_attention(q, k, v, window=window)
        np.testing.assert_array_equal(np.asarray(windowed),
                                      np.asarray(full))
    shorter, _ = cached_attention(q, k, v, window=39)
    assert np.abs(np.asarray(shorter) - np.asarray(full)).max() > 0
    # ... and the whole model's logits with it
    module, variables = tiny[:2]
    ids = jnp.asarray([prompt_of(48)])
    logits = [np.asarray(mellum.MellumLM(mellum.mellum_tiny(
        max_seq=MAX_SEQ, sliding_window=w)).apply(variables, ids))
        for w in (48, 1024)]
    np.testing.assert_array_equal(*logits)
    assert np.abs(logits[0] - np.asarray(
        module.apply(variables, ids))).max() > 1000 * TOL


@pytest.mark.parametrize("length, block_q, block_k, window, short", [
    (64, 16, 16, 24, 37),     # as many key blocks in the band as there are
    (128, 16, 16, 24, 70),    # a band of 4 of the 8 key blocks
    (128, 8, 32, 40, 9),      # key blocks longer than query blocks
    (128, 32, 8, 16, 128),    # and shorter; a window of two key blocks
    (128, 16, 16, 1, 50),     # each query sees itself alone
])
def test_flash_kernel_with_a_window_and_padding_in_interpret_mode(
        monkeypatch, length, block_q, block_k, window, short):
    """The kernel's causal, window and length masks together against the
    definition, with blocks so small that whole key blocks fall before a
    query block's windows: the k axis walks the band a query block sees
    (block_q + window - 1 keys), not the sequence."""
    from jax.experimental import pallas as pl

    from kfserving_tpu.ops import pallas_attention

    # unjitted, so that every call here traces and its grid is seen
    flash_attention = pallas_attention.flash_attention.__wrapped__
    grids, compiled = [], pl.pallas_call

    def interpreted(*args, **kwargs):
        spec = kwargs.get("grid_spec")
        grids.append(spec.grid if spec is not None else kwargs["grid"])
        return compiled(*args, interpret=True, **kwargs)

    monkeypatch.setattr(pl, "pallas_call", interpreted)
    q, k, v = _qkv(np.random.default_rng(3), 2, length, 2, 2, 64)
    lengths = jnp.asarray([length, short], jnp.int32)
    want = _plain_attention(q, k, v, window)
    got = np.asarray(flash_attention(q, k, v, causal=True, block_q=block_q,
                                     block_k=block_k, kv_lengths=lengths,
                                     window=window))
    np.testing.assert_allclose(got[0], want[0], atol=2e-5, rtol=0)
    np.testing.assert_allclose(got[1, :short], want[1, :short], atol=2e-5,
                               rtol=0)
    assert np.isfinite(got).all()  # padding rows: garbage, not NaN
    band = min(length // block_k, (block_q + window - 2) // block_k + 2)
    assert grids[-1][2] == band
    # no lengths: the same band; no window: every key block, as it was
    unpadded = np.asarray(flash_attention(q, k, v, causal=True,
                                          block_q=block_q, block_k=block_k,
                                          window=window))
    np.testing.assert_allclose(unpadded, want, atol=2e-5, rtol=0)
    no_window = np.asarray(flash_attention(q, k, v, causal=True,
                                           block_q=block_q, block_k=block_k))
    np.testing.assert_allclose(no_window, _plain_attention(q, k, v),
                               atol=2e-5, rtol=0)
    assert grids[-1][2] == length // block_k


def _ring_case(rng, lengths, heads=2, d=32, group=4, ring=RING, bs=BS,
               window=WINDOW):
    """Pools whose rings hold the last blocks of sequences of `lengths`
    tokens (block j in column j % ring), the table, and the K/V each
    sequence would have unrolled."""
    b = len(lengths)
    longest = max(lengths)
    k_seq = rng.normal(size=(b, longest, heads, d)).astype(np.float32)
    v_seq = rng.normal(size=(b, longest, heads, d)).astype(np.float32)
    nb = b * ring + 2
    pool_k = rng.normal(size=(nb, bs, heads * d)).astype(np.float32)
    pool_v = rng.normal(size=(nb, bs, heads * d)).astype(np.float32)
    table = np.full((b, ring), -1, np.int32)
    free = list(rng.permutation(nb))
    for r, n in enumerate(lengths):
        total = -(-n // bs)
        for j in range(max(0, total - ring), total):
            blk = table[r, j % ring] = free.pop()
            rows = slice(j * bs, min((j + 1) * bs, n))
            count = rows.stop - rows.start
            pool_k[blk, :count] = k_seq[r, rows].reshape(count, -1)
            pool_v[blk, :count] = v_seq[r, rows].reshape(count, -1)
    q = rng.normal(size=(b, 1, heads * group, d)).astype(np.float32)
    want = np.zeros_like(q)
    for r, n in enumerate(lengths):
        if n == 0:
            continue
        lo = max(0, n - window)
        want[r] = _plain_attention(
            np.broadcast_to(q[r:r + 1], (1, n - lo, heads * group, d)),
            k_seq[r:r + 1, lo:n], v_seq[r:r + 1, lo:n])[:, -1:]
    return q, pool_k, pool_v, table, want


@pytest.mark.parametrize("window, lengths", [
    (WINDOW, (5, 16, 17, 24)),   # under the window, at it, past it, at a ring
    (WINDOW, (25, 33, 160, 7)),  # recycled once, twice, ten windows long
    (WINDOW, (48, 0, 41, 8)),    # a row never fed walks nothing
    # A ring of 9, wider than the 4 blocks a loop iteration takes and no
    # multiple of them (4 + 4 + 1, as the 1024-token window's in blocks
    # of 128): under the window, at it, a block past it, ten windows long.
    (64, (5, 30, 63, 64)),
    (64, (65, 72, 640, 0)),
    (64, (100, 33, 577, 8)),
])
def test_paged_kernel_with_a_window_in_interpret_mode(window, lengths):
    """The Pallas decode kernel over rings (interpret mode) against the
    XLA path and the definition: 8 query heads on 2 KV heads."""
    ring = pa.ring_blocks(window, BS)
    chunk = pa.blocks_per_iteration(BS, 2 * 32, jnp.float32, ring)
    assert (ring, chunk) in ((RING, RING), (9, 4))
    rng = np.random.default_rng(sum(lengths))
    q, pool_k, pool_v, table, want = _ring_case(rng, lengths, ring=ring,
                                                window=window)
    lens = jnp.asarray(lengths, jnp.int32)
    args = [jnp.asarray(x) for x in (q, pool_k, pool_v, table)]
    xla = np.asarray(pa.paged_attention_xla(*args, lens, window))
    got = np.asarray(pa.paged_attention_tpu(*args, lens, interpret=True,
                                            window=window))
    live = [r for r, n in enumerate(lengths) if n > 0]
    np.testing.assert_allclose(xla[live], want[live], atol=2e-5, rtol=0)
    np.testing.assert_allclose(got[live], want[live], atol=2e-5, rtol=0)
    for r, n in enumerate(lengths):
        if n == 0:
            np.testing.assert_array_equal(got[r], 0.0)
    # the walk reads the ring's columns the sequence has reached, and no
    # more; the kernel's own list has an entry for every `chunk` of them
    reached = [min(-(-n // BS), ring) for n in lengths]
    pairs, count = pa.paged_walk(jnp.asarray(table), lens, BS, window)
    assert int(count[0]) == sum(reached) <= ring * len(lengths)
    pairs, count = pa.paged_walk(jnp.asarray(table), lens, BS, window,
                                 chunk)
    assert int(count[0]) == sum(-(-c // chunk) for c in reached)
    assert np.asarray(pairs)[:int(count[0])].tolist() == [
        r * ring + column for r, c in enumerate(reached)
        for column in range(0, c, chunk)]


def test_ring_write_and_chunk_attention_follow_the_ring():
    """`paged_write` puts position p in column (p // BS) % ring whatever
    p is, and a chunk of a block's length attends over the ring as its
    own writes left it (`cached_attention`'s Lq > 1 branch)."""
    rng = np.random.default_rng(9)
    heads, d, start, chunk = 2, 32, 43, BS
    q, pool_k, pool_v, table, _ = _ring_case(rng, (start,), group=1)
    k_seq = rng.normal(size=(1, start + chunk, heads, d)).astype(np.float32)
    v_seq = rng.normal(size=(1, start + chunk, heads, d)).astype(np.float32)
    # rebuild the ring from these sequences, one decode write at a time
    pools = (jnp.zeros_like(pool_k), jnp.zeros_like(pool_v))
    full_table = table.copy()
    full_table[full_table < 0] = [b for b in range(pool_k.shape[0])
                                  if b not in table][:(table < 0).sum()]
    for p in range(start):
        pools = pa.paged_write(*pools, jnp.asarray(k_seq[:, p]),
                               jnp.asarray(v_seq[:, p]),
                               jnp.asarray(full_table),
                               jnp.asarray([p]), WINDOW)
    blk = full_table[0, (start - 1) // BS % RING]
    np.testing.assert_array_equal(
        np.asarray(pools[0])[blk, (start - 1) % BS],
        k_seq[0, start - 1].reshape(-1))
    qs = jnp.asarray(rng.normal(size=(1, chunk, heads, d)), jnp.float32)
    positions = jnp.asarray([np.arange(start, start + chunk)])
    got, _ = cached_attention(
        qs, jnp.asarray(k_seq[:, start:]), jnp.asarray(v_seq[:, start:]),
        cache=pools + (jnp.asarray(full_table),), positions=positions,
        window=WINDOW)
    q_all = np.zeros((1, start + chunk, heads, d), np.float32)
    q_all[:, start:] = np.asarray(qs)
    want = _plain_attention(q_all, k_seq, v_seq, WINDOW)[:, start:]
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=0)


# -- through the engine's two pools ------------------------------------------
@pytest.mark.parametrize("prompt_len,steps", [
    (10, 12),    # under the window: the ring is not full yet
    (16, 24),    # at the window, then a recycled block
    (41, 40),    # over the window and over the ring at insert
    (100, 60),   # ten windows by the end: the ring recycled many times
])
async def test_prefill_then_decode_through_both_pools(tiny, prompt_len,
                                                      steps):
    prompt = prompt_of(prompt_len)
    engine = engine_of(tiny)
    try:
        tokens, chosen, top = await served(engine, prompt, steps)
        stats = engine.stats()
    finally:
        await engine.close()
    assert len(tokens) == steps
    assert_matches_reference(tiny, prompt, tokens, chosen, top)
    pools = stats["paged"]["pools"]
    assert pools["window"]["blocks_per_slot"] == RING
    if prompt_len + steps > RING * BS + 16:
        assert pools["window"]["recycled"] > 0


async def test_a_window_layer_holds_its_ring_and_a_global_layer_its_context(
        tiny):
    """A sequence of ten windows: a window layer never holds more than
    ceil(W / BS) + 1 blocks and its walk reads no more, a global layer
    holds ceil(len / BS); the counters say which pool they count."""
    held = []

    def watch(engine):
        slot = next((i for i, s in enumerate(engine._slots)
                     if s is not None), None)
        if slot is None:  # the last token: the slot is released already
            return
        held.append((engine._slots[slot].length,
                     int((engine._ring.table[slot] >= 0).sum()),
                     int((engine._pool.table[slot] >= 0).sum())))

    engine = engine_of(tiny)
    try:
        tokens, _, _ = await served(engine, prompt_of(100), 60, watch)
        stats = engine.stats()
    finally:
        await engine.close()
    assert len(tokens) == 60 and len(held) >= 50
    assert max(ring for _, ring, _ in held) == RING
    for length, _, blocks in held:
        assert blocks >= -(-length // BS)
    assert held[-1][0] >= 10 * WINDOW - 4
    assert held[-1][2] >= 10 * WINDOW // BS - 1
    window, whole = stats["paged"]["pools"]["window"], \
        stats["paged"]["pools"]["global"]
    # The prefill answers the first token and 59 steps from context 100
    # the rest: min(len, 16) rows in min(blocks, 3) columns each.  The
    # fifteenth wave's fourth step is past the budget: parked, it walks
    # nothing.
    assert engine._walked["window"][0] == 59 * WINDOW
    assert engine._walked["window"][1] == 59 * RING
    assert engine._walked["global"][0] == sum(range(101, 160))
    # The kernel's loop iterations, by each pool's own table: the whole
    # ring of 3 at once, the global layer's columns 4 at a time.
    assert engine._walk_chunks == (4, RING)
    assert engine._walked["window"][2] == 59
    columns = [-(-n // BS) for n in range(101, 160)]
    assert engine._walked["global"][1] == sum(columns)
    assert engine._walked["global"][2] == sum(-(-c // 4) for c in columns)
    assert stats["kv_blocks_per_iteration"] == round(
        (sum(columns) + 59 * RING)
        / (engine._walked["global"][2] + 59), 4)
    for pool, iterations in (("global", engine._walked["global"][2]),
                             ("window", 59)):
        assert obs.generator_decode_kv_pool_walk_iterations_total(
            ).labels(model="mellum-test", pool=pool).value >= iterations
    assert window["block_fill"] == pytest.approx(WINDOW / (RING * BS), abs=1e-3)
    assert whole["block_fill"] > 0.9
    assert window["recycled"] >= (160 - 100) // BS
    assert window["blocks"] == 4 * RING and whole["blocks"] == 4 * MAX_SEQ // BS
    assert window["fill"] == 0.0  # all released


async def test_released_window_blocks_are_reused_and_leak_nothing(tiny):
    """A window pool of one ring: the second request can only run in the
    blocks the first released, over whatever it left there."""
    engine = engine_of(tiny, max_slots=1, window_cache_blocks=RING)
    try:
        first = await served(engine, prompt_of(41), 20)
        assert len(engine._ring.free) == RING  # released
        second = await served(engine, prompt_of(50, 11), 20)
        again = await served(engine, prompt_of(12, 3), 8)
    finally:
        await engine.close()
    assert_matches_reference(tiny, prompt_of(41), *first)
    assert_matches_reference(tiny, prompt_of(50, 11), *second)
    assert_matches_reference(tiny, prompt_of(12, 3), *again)


async def test_streams_side_by_side_keep_their_own_rings(tiny):
    engine = engine_of(tiny)
    prompts = [prompt_of(10), prompt_of(41, 5), prompt_of(100, 3),
               prompt_of(33, 11), prompt_of(64, 13)]
    try:
        results = await asyncio.gather(*(served(engine, p, 30)
                                         for p in prompts))
        stats = engine.stats()
    finally:
        await engine.close()
    for prompt, result in zip(prompts, results):
        assert_matches_reference(tiny, prompt, *result)
    # five requests over four slots, no shared prefix looked up
    assert stats["paged"]["prefix_hits"] == 0
    assert engine.prefix_reuse_refused == 5


async def test_a_split_group_fills_every_rows_ring(tiny):
    """Three arrivals once the 1-, 2- and 4-row programs are warm go as
    2 + 1, each piece an insert of its own: every row's last blocks land
    in its own slot's ring (prompts of two to four windows), so twenty
    served tokens each match the reference."""
    prompts = [prompt_of(41, 5), prompt_of(64, 13), prompt_of(33, 11)]
    engine = engine_of(tiny, prefill_buckets=[64, 128])
    rows, prefill = [], engine._prefill

    def watched(variables, ids, *rest):
        rows.append(ids.shape[0])
        return prefill(variables, ids, *rest)

    engine._prefill = watched
    # every dispatched program timed at its rows: the pieces take less
    engine._note_prefill_took = lambda rows, bucket, seconds: \
        engine._prefill_took_s.__setitem__((rows, bucket), [float(rows)])
    try:
        for n in (1, 2, 4):
            await asyncio.gather(*(served(engine, prompt_of(40 + i), 2)
                                   for i in range(n)))
        assert rows == [1, 2, 4]
        results = await asyncio.gather(*(served(engine, p, 20)
                                         for p in prompts))
        stats = engine.stats()
    finally:
        await engine.close()
    assert rows[3:] == [2, 1]
    assert stats["prefill_rows_padded"] == 0
    for prompt, result in zip(prompts, results):
        assert_matches_reference(tiny, prompt, *result)


async def test_a_repeated_prompt_shares_no_blocks_and_is_counted(tiny):
    engine = engine_of(tiny)
    prompt = prompt_of(40)
    try:
        first = await served(engine, prompt, 6)
        second = await served(engine, prompt, 6)
        stats = engine.stats()
    finally:
        await engine.close()
    assert first[0] == second[0]
    assert stats["paged"]["prefix_hits"] == 0
    assert stats["paged"]["index_entries"] == 0
    assert engine.prefix_reuse_refused == 2


@pytest.mark.parametrize("setting,kw,why", [
    ("speculative", {"speculative": {"tokens": 2}}, "position sentinel"),
    ("prefill_chunk_tokens", {"prefill_chunk_tokens": 32},
     "position sentinel"),
    ("host_tier_blocks", {"host_tier_blocks": 8}, "kept nowhere"),
])
def test_what_a_window_model_is_refused_at_load(tiny, setting, kw, why):
    with pytest.raises(InvalidInput) as refused:
        engine_of(tiny, **kw)
    message = str(refused.value)
    assert setting in message and "sliding-window layers" in message
    assert why in message


def test_the_engine_refuses_what_it_cannot_page(tiny):
    module, variables = tiny[:2]

    def declared(layers):
        cfg = mellum.mellum_tiny(max_seq=MAX_SEQ)
        cfg.cache_layers = lambda: layers
        return mellum.MellumLM(cfg)

    for layers, why in (
            ([KVCache(2, 32, 16)] * 4, "whole context"),
            ([KVCache(2, 32, 16), KVCache(2, 32, 8), KVCache(2, 32)] * 2,
             "one window"),
            ([KVCache(2, 32), KVCache(4, 32)] * 2, "one geometry")):
        with pytest.raises(InvalidInput, match=why):
            GenerationEngine(declared(layers), variables, max_slots=2,
                             max_seq=MAX_SEQ, block_size=BS, name="m")
    with pytest.raises(InvalidInput, match="one sequence's ring"):
        engine_of(tiny, window_cache_blocks=RING - 1)


def test_resident_dtypes_keep_norms_and_router_as_stored(tiny):
    module, variables = tiny[:2]
    cfg = mellum.mellum_tiny(dtype=jnp.bfloat16)
    read = flatten_dict(cfg.resident_dtypes(variables))
    for path, dtype in read.items():
        as_stored = "scale" in path or "router" in path
        assert dtype == (jnp.float32 if as_stored else jnp.bfloat16), path
