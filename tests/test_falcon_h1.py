"""Falcon-H1 (models/falcon_h1.py: attention and Mamba-2 side by side in
every layer, so every layer keeps K/V rows in the pool AND a per-slot state;
ops/ssm.py; 5 query heads a KV head through ops/paged_attention.py) against
the plain reference (tests/falcon_h1_reference.py) at `falcon_h1_tiny` size
on seeded weights: 3 layers, 10 query heads on 2 KV heads, 4 Mamba heads in
2 groups, `mamba_d_ssm` 48 where `mamba_expand` x hidden is 192, and every
multiplier another value, none of them 1.

Everything compares logits or log-probabilities, never sampled tokens alone.
Both sides compute in float32 on the CPU, so they differ by the order of
their sums only: 1e-6 on logits of magnitude 1 here.  The tolerance, 1e-4,
is under a tenth of what any single multiplier set to 1 moves
(`test_every_multiplier_matters`) and a fifth of what a bfloat16 state
moves over 48 tokens (5e-4).
"""

import asyncio
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.traverse_util import flatten_dict

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import falcon_h1_reference as reference  # noqa: E402

from kfserving_tpu.engine import programs  # noqa: E402
from kfserving_tpu.engine.generator import GenerationEngine  # noqa: E402
from kfserving_tpu.models import create_model, init_params  # noqa: E402
from kfserving_tpu.models.decoder import (  # noqa: E402
    BothCaches,
    KVCache,
    StateCache,
)
from kfserving_tpu.models.falcon_h1 import FalconH1Config  # noqa: E402
from kfserving_tpu.ops import paged_attention  # noqa: E402
from kfserving_tpu.protocol.errors import InvalidInput  # noqa: E402

TOL = 1e-4
MAX_SEQ = 128
BS = 16
MULTIPLIERS = ["embedding_multiplier", "attention_in_multiplier",
               "attention_out_multiplier", "key_multiplier",
               "ssm_in_multiplier", "ssm_multipliers.0", "ssm_multipliers.1",
               "ssm_multipliers.2", "ssm_multipliers.3", "ssm_multipliers.4",
               "ssm_out_multiplier", "mlp_multipliers.0",
               "mlp_multipliers.1", "lm_head_multiplier"]


def model_of(cfg) -> dict:
    """The published config's keys that the reference reads."""
    return dict(
        num_hidden_layers=cfg.num_layers, rms_norm_eps=cfg.rms_norm_eps,
        rope_theta=cfg.rope_theta, mamba_n_heads=cfg.mamba_heads,
        mamba_n_groups=cfg.ssm_groups, mamba_d_state=cfg.ssm_state,
        embedding_multiplier=cfg.embedding_multiplier,
        attention_in_multiplier=cfg.attention_in_multiplier,
        attention_out_multiplier=cfg.attention_out_multiplier,
        key_multiplier=cfg.key_multiplier,
        ssm_in_multiplier=cfg.ssm_in_multiplier,
        ssm_multipliers=list(cfg.ssm_multipliers),
        ssm_out_multiplier=cfg.ssm_out_multiplier,
        mlp_multipliers=list(cfg.mlp_multipliers),
        lm_head_multiplier=cfg.lm_head_multiplier)


@pytest.fixture(scope="module")
def tiny():
    spec = create_model("falcon_h1_tiny", max_seq=MAX_SEQ)
    variables = init_params(spec, seed=3)
    flat = {"/".join(k): np.asarray(v)
            for k, v in flatten_dict(variables).items()}
    return spec.module, variables, flat


def prompt_of(n, stride=7):
    return [(i * stride) % 250 + 1 for i in range(n)]


_SHAPES = ("num_hidden_layers", "mamba_n_heads", "mamba_n_groups",
           "mamba_d_state", "rope_theta")
_compiled = {}


def ref_logits(tiny, ids, state_round_to=None, **changed):
    """The reference's logits, its plain operations compiled as one
    program a length (eagerly, each compiles alone: seconds a length),
    the multipliers its arguments."""
    module, _, flat = tiny
    model = {**model_of(module.config), **changed}
    shapes = {k: model.pop(k) for k in _SHAPES}
    key = (tuple(shapes.values()), state_round_to)
    if key not in _compiled:
        _compiled[key] = jax.jit(
            lambda params, tokens, numbers: reference.logits(
                params, tokens, {**shapes, **numbers},
                state_round_to=state_round_to))
    return np.asarray(_compiled[key](flat, jnp.asarray(ids, jnp.int32),
                                     model))


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
    return GenerationEngine(module, variables, name="falcon-test", **kw)


# -- (a) the model against the reference -------------------------------------
@pytest.mark.parametrize("length", [1, 16, 37])
def test_full_forward_logits(tiny, length):
    """Lengths under, at and over the scan's chunk (16)."""
    module, variables, _ = tiny
    ids = prompt_of(length)
    got = np.asarray(module.apply(variables, jnp.asarray([ids])))[0]
    np.testing.assert_allclose(got, ref_logits(tiny, ids), atol=TOL, rtol=0)


def test_the_tiny_model_is_the_shape_the_tests_say(tiny):
    cfg = tiny[0].config
    assert cfg.num_heads // cfg.num_kv_heads == 5 and cfg.ssm_groups == 2
    assert cfg.mamba_inner == 48 != 2 * cfg.hidden_size
    values = [getattr(cfg, m.split(".")[0]) for m in MULTIPLIERS]
    values = [v[int(m.split(".")[1])] if "." in m else v
              for m, v in zip(MULTIPLIERS, values)]
    assert len(set(values)) == len(values) and 1.0 not in values
    with pytest.raises(ValueError, match="mamba_d_ssm"):
        FalconH1Config(mamba_heads=32, mamba_head_dim=128, mamba_d_ssm=10240)


# -- (c) no multiplier can be left out ----------------------------------------
@pytest.mark.parametrize("multiplier", MULTIPLIERS)
def test_every_multiplier_matters(tiny, multiplier):
    """Set to 1, each moves the reference's logits by more than the
    tolerance the model is held to: a model that dropped it would fail
    `test_full_forward_logits`."""
    module = tiny[0]
    name, _, index = multiplier.partition(".")
    value = getattr(module.config, name)
    if index:
        value = list(value)
        value[int(index)] = 1.0
    else:
        value = 1.0
    ids = prompt_of(24)
    moved = np.abs(ref_logits(tiny, ids, **{name: value})
                   - ref_logits(tiny, ids)).max()
    assert moved > 10 * TOL, (multiplier, moved)


def test_a_bfloat16_state_is_outside_the_tolerance(tiny):
    ids = prompt_of(48)
    moved = np.abs(ref_logits(tiny, ids, state_round_to="bfloat16")
                   - ref_logits(tiny, ids)).max()
    assert moved > 3 * TOL, moved


# -- (b) through the engine: pool and state for the same layer ----------------
async def test_prefill_then_decode_through_pool_and_state(tiny):
    """Rows of different lengths admitted together (one prefill dispatch
    writes every layer's pool blocks AND its slot's state), decoded through
    both over several calls, and a fourth request that takes a finished
    request's slot: each on the reference's full forward pass."""
    prompts = [prompt_of(n, stride)
               for n, stride in ((5, 3), (33, 5), (60, 11), (18, 13))]
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
    assert stats["requests_finished"] == 4 and stats["max_slots"] == 3
    # 3 slots x 3 layers of (4 x 12 x 8 state + 3 x 80 conv) float32, and
    # 2 KV heads of 16 on 3 layers a token
    assert stats["state_bytes_per_slot"] == 3 * (4 * 12 * 8 + 3 * 80) * 4
    assert stats["recurrent_state_bytes"] == 3 * stats["state_bytes_per_slot"]
    assert stats["kv_bytes_per_token"] == 2 * 3 * 2 * 16 * 4
    assert stats["cache_bytes"] == stats["recurrent_state_bytes"] + (
        3 * MAX_SEQ * stats["kv_bytes_per_token"])


# -- (d) the layout at the published shapes -----------------------------------
def test_the_cache_layout_at_the_published_shapes():
    """Six published layers under the cell's serving sizes, by
    `jax.eval_shape` (nothing is allocated): both kinds on every layer,
    24.2 MiB of state a slot, 12 KiB of K/V a token."""
    cfg = FalconH1Config(num_layers=6, max_seq=1536)
    kinds = cfg.cache_layers()
    assert len(kinds) == 6 and all(isinstance(k, BothCaches) for k in kinds)
    assert kinds[0].kv == KVCache(4, 128)
    assert kinds[0].state == StateCache((
        ((32, 128, 256), jnp.dtype(jnp.float32)),
        ((3, 5120), jnp.dtype(jnp.bfloat16))), 128)
    assert programs.packs_prompts(kinds, 512, 128)
    layouts = []

    def build():
        layouts.append(programs.CacheLayout(
            cfg, "falcon", max_slots=64, max_seq=1536,
            prefill_buckets=[512], block_size=128, cache_blocks=768,
            window_cache_blocks=None, mesh=None))
        return layouts[0].caches

    shapes = jax.eval_shape(build)
    layout = layouts[0]
    assert layout.limits == ("recurrent state",)
    assert layout.kv_bytes_per_token == 12 * 1024
    assert layout.state_bytes_per_slot == 6 * (4 * 2**20 + 30 * 1024)
    assert round(layout.state_bytes_per_slot / 2**20, 1) == 24.2
    assert layout.state_bytes == 64 * layout.state_bytes_per_slot
    assert layout.cache_bytes == layout.state_bytes + 768 * 128 * 12 * 1024
    assert layout.pool_shape == (768, 128, 512) and layout.kv_layers == 6
    assert layout.walk_chunks[0] == 4
    for (pool_k, pool_v), (state, conv) in shapes:
        assert pool_k.shape == pool_v.shape == (768, 128, 512)
        assert state.shape == (64, 32, 128, 256) and state.dtype == jnp.float32
        assert conv.shape == (64, 3, 5120) and conv.dtype == jnp.bfloat16


# -- (e) what a recurrence's state cannot serve -------------------------------
@pytest.mark.parametrize("setting", [
    {"speculative": {"tokens": 3}},
    {"prefill_chunk_tokens": 32},
    {"host_tier_blocks": 8},
])
def test_what_rests_on_rows_addressed_by_position_is_refused_at_load(
        tiny, setting):
    with pytest.raises(InvalidInput, match="recurrent state"):
        engine_of(tiny, **setting)


# -- (f) the paged kernel at 5 query heads a KV head --------------------------
@pytest.mark.parametrize("heads, kv_heads, dtype", [
    (20, 4, jnp.bfloat16),   # this model: 20 rows padded to 32
    (20, 4, jnp.float32),    # 20 rows padded to 24
    (32, 4, jnp.bfloat16),   # 8 a KV head: whole tiles, as it was served
    (4, 4, jnp.bfloat16),    # one a KV head: the flat path
])
def test_paged_kernel_in_interpret_mode_against_xla(heads, kv_heads, dtype):
    """Rows of 1 token, part of a block, whole blocks and none: the kernel
    walks each row's own blocks, the padded query rows' answers are cut
    off, and a row that walks nothing is zeros."""
    b, d, nb, bs, mb = 5, 128, 24, 128, 4
    ks = jax.random.split(jax.random.PRNGKey(heads), 3)
    q = jax.random.normal(ks[0], (b, 1, heads, d), dtype)
    pool_k = jax.random.normal(ks[1], (nb, bs, kv_heads * d), dtype)
    pool_v = jax.random.normal(ks[2], (nb, bs, kv_heads * d), dtype)
    lengths = np.asarray([1, 130, 512, 0, 300], np.int32)
    table = np.random.default_rng(0).permutation(nb)[:b * mb].reshape(
        b, mb).astype(np.int32)
    for row, n in enumerate(lengths):
        table[row, -(-int(n) // bs):] = -1
    got = paged_attention.paged_attention_tpu(
        q, pool_k, pool_v, jnp.asarray(table), jnp.asarray(lengths),
        interpret=True)
    want = paged_attention.paged_attention_xla(
        q, pool_k, pool_v, jnp.asarray(table), jnp.asarray(lengths))
    assert got.shape == want.shape == (b, 1, heads, d)
    live = lengths > 0
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live], np.asarray(want, np.float32)[live],
        atol=1e-5 if dtype == jnp.float32 else 2e-2, rtol=0)
    assert not np.asarray(got, np.float32)[~live].any()


@pytest.mark.parametrize("heads, group", [
    (4, 5), (4, 8), (2, 16), (20, 1), (4, 3), (3, 5)])
def test_the_kernels_serve_any_group_of_whole_lane_heads(monkeypatch, heads,
                                                         group):
    from kfserving_tpu.ops import attention

    monkeypatch.setattr(attention, "_tpu_backend", lambda: True)
    assert paged_attention._kernels_serve(128, heads, 128, group)
    assert not paged_attention._kernels_serve(128, heads, 64, 5)


# -- a prefill row that carries several prompts ------------------------------
# name -> the prompts of one row as (tokens, the block of 16 it starts at)
LAID = {
    "alone": [(37, 0)],
    "first": [(20, 0), (9, 2), (16, 3)],
    "last": [(23, 2), (32, 0)],
    "between": [(21, 1), (7, 0), (33, 3)],
    "one-block-and-shorter-than-the-taps": [(16, 2), (32, 0), (2, 3)],
    "the-bucket": [(64, 0)],
}


@pytest.mark.parametrize("case", sorted(LAID))
def test_a_packed_row_gives_each_prompt_the_references_logits(tiny, case):
    """`segments`, restarted `positions` and each prompt's last column:
    a prompt's logits are the reference's over the prompt alone, and a
    state layer returns a state and conv rows for every place a prompt
    could start (tests/test_programs.py holds each to the prompt's own
    alone in a row)."""
    module, variables, _ = tiny
    laid = LAID[case]
    bucket = BS * max(block - (-n // BS) for n, block in laid)
    ids = np.zeros((1, bucket), np.int32)
    segments = np.full((1, bucket), -1, np.int32)
    positions = np.zeros((1, bucket), np.int32)
    last = np.zeros((1, bucket // BS), np.int32)
    prompts = [prompt_of(n, 3 + 2 * i) for i, (n, _) in enumerate(laid)]
    for prompt, (n, block) in zip(prompts, laid):
        at = slice(block * BS, block * BS + n)
        ids[0, at], segments[0, at] = prompt, block
        positions[0, at], last[0, block] = np.arange(n), at.stop - 1
    logits, caches = module.apply(
        variables, jnp.asarray(ids), positions=jnp.asarray(positions),
        segments=jnp.asarray(segments), logit_positions=jnp.asarray(last),
        return_cache=True)
    for kind, layer in zip(module.config.cache_layers(), caches):
        _, state = programs.parts(kind)
        if state is not None:
            arrays = layer[1] if isinstance(kind, BothCaches) else layer
            assert [(x.shape, x.dtype) for x in arrays] == [
                ((bucket // BS,) + shape, dtype)
                for shape, dtype in state.arrays]
    for prompt, (n, block) in zip(prompts, laid):
        np.testing.assert_allclose(np.asarray(logits[0, block]),
                                   ref_logits(tiny, prompt)[-1], atol=TOL,
                                   rtol=0)


async def test_decode_continues_from_each_packed_prompts_own_state(tiny):
    """Seven arrivals of one to four blocks lie in rows of four blocks;
    each then decodes 32 tokens from the state, the conv rows and the K/V
    its prompt left, on the reference's logits throughout."""
    prompts = [prompt_of(n, stride) for n, stride in (
        (16, 3), (5, 5), (33, 7), (64, 11), (21, 13), (1, 17), (40, 19))]
    engine = engine_of(tiny, max_slots=8, prefill_buckets=[64],
                       steps_per_call=8)
    try:
        results = await asyncio.wait_for(asyncio.gather(*[
            served(engine, p, 32) for p in prompts]), timeout=600)
        stats = engine.stats()
    finally:
        await engine.close()
    assert stats["prefill_requests"] == 7
    assert stats["prefill_prompts_per_row"] > 1.0
    assert engine._prefill_refusals == 0
    for prompt, (tokens, chosen, top) in zip(prompts, results):
        assert len(tokens) == 32
        assert_matches_reference(tiny, prompt, tokens, chosen, top)


async def test_a_preempted_request_resumes_on_the_references_logits(tiny):
    """A pool too small for three streams: one is preempted and its
    prompt and tokens are prefilled again, in a row beside whatever
    waits; every stream stays on the reference's logits."""
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
