"""Nemotron-H (models/nemotron_h.py, ops/ssm.py, ops/moe.py's sigmoid
router, plain experts and held share, grouped-query attention through
ops/paged_attention.py) against the plain reference
(tests/nemotron_h_reference.py) at `nemotron_h_tiny` size on seeded weights:
pattern `MEM*EM`, 8 routed experts of width 24 of which the first 4 are
held, 4 query heads on each of 2 KV heads; no width is a lane multiple.

Everything compares logits or log-probabilities, never sampled tokens
alone: with random weights the largest logit changes on rounding.  Both
sides compute in float32 on the CPU, so they differ by the order of their
sums only: a few 1e-6 on logits of magnitude 4 here.  The tolerance, 1e-4,
is a hundredth of what leaving out any term would move (the conv's bias,
the D skip, the gate before the norm, the router's bias in the choice, the
shared expert: each moves logits by 1e-2 and more).
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

import nemotron_h_reference as reference  # noqa: E402

from kfserving_tpu.engine import programs  # noqa: E402
from kfserving_tpu.engine.generator import GenerationEngine  # noqa: E402
from kfserving_tpu.models import create_model, init_params  # noqa: E402
from kfserving_tpu.models.decoder import (  # noqa: E402
    BothCaches,
    KVCache,
    StateCache,
)
from kfserving_tpu.models.nemotron_h import (  # noqa: E402
    NemotronHConfig,
    NemotronHLM,
    nemotron_h_tiny,
)
from kfserving_tpu.protocol.errors import InvalidInput  # noqa: E402

TOL = 1e-4
MAX_SEQ = 128
BS = 16
EPS = 1e-5


def model_of(cfg):
    return dict(pattern=cfg.pattern, mamba_heads=cfg.mamba_heads,
                ssm_groups=cfg.ssm_groups, ssm_state=cfg.ssm_state,
                experts_per_token=cfg.experts_per_token,
                scaling=cfg.routed_scaling_factor,
                experts_held=(cfg.experts_first, cfg.num_experts))


@pytest.fixture(scope="module")
def tiny():
    spec = create_model("nemotron_h_tiny", max_seq=MAX_SEQ)
    variables = init_params(spec, seed=3)
    # A router bias that is not zero, so that choosing by s + b and
    # weighting by s differ.
    params = jax.tree.map(lambda x: x, variables["params"])
    for i, kind in enumerate(spec.module.config.pattern):
        if kind == "E":
            bias = params[f"layer_{i}"]["mixer"]["router_bias"]
            params[f"layer_{i}"]["mixer"]["router_bias"] = 0.3 * jnp.cos(
                jnp.arange(bias.size, dtype=jnp.float32) + i)
    variables = {"params": params}
    flat = {"/".join(k): np.asarray(v)
            for k, v in flatten_dict(variables).items()}
    return spec.module, variables, flat


def prompt_of(n, stride=7):
    return [(i * stride) % 250 + 1 for i in range(n)]


def ref_logits(tiny, ids, **kw):
    module, _, flat = tiny
    cfg = module.config
    return np.asarray(reference.logits(flat, ids, cfg.num_layers, EPS,
                                       **{**model_of(cfg), **kw}))


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
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("prefill_buckets", [32, 64, MAX_SEQ])
    kw.setdefault("block_size", BS)
    return GenerationEngine(module, variables, name="nemotron-test", **kw)


# -- the model against the reference -----------------------------------------
@pytest.mark.parametrize("length", [1, 15, 16, 48, 77])
def test_full_forward_logits(tiny, length):
    """Lengths under, at and over the scan's chunk (16)."""
    module, variables, _ = tiny
    ids = prompt_of(length)
    got = np.asarray(module.apply(variables, jnp.asarray([ids])))[0]
    np.testing.assert_allclose(got, ref_logits(tiny, ids), atol=TOL, rtol=0)


def test_at_the_published_widths_the_program_is_the_reference():
    """One layer of each kind at Nemotron-3-Nano's own widths (hidden 2688,
    64 Mamba heads of 64 over 8 groups of state 128, 32 query heads on 2 KV
    heads of 128, experts of 1856 stored as 1920, a shared expert of 3712;
    8 experts of which 4 are held, and a small vocabulary, so that it is
    125 M parameters), in float32, over 200 tokens (a chunk and a part):
    what separates the served model from the reference on the chip is
    then precision, not a width the tiny model does not have."""
    spec = create_model(
        "nemotron_h_tiny", max_seq=256, hidden_size=2688, pattern="ME*",
        num_heads=32, num_kv_heads=2, head_dim=128, mamba_heads=64,
        mamba_head_dim=64, ssm_groups=8, ssm_state=128, chunk_size=128,
        intermediate_size=1856, shared_intermediate_size=3712,
        experts_per_token=6, expert_width_multiple=128)
    variables = init_params(spec, seed=5)
    cfg = spec.module.config
    flat = {"/".join(k): np.asarray(v)
            for k, v in flatten_dict(variables).items()}
    assert flat["params/layer_1/mixer/up"].shape == (4, 2688, 1920)
    ids = prompt_of(200)
    got = np.asarray(spec.module.apply(variables, jnp.asarray([ids])))[0]
    want = np.asarray(reference.logits(flat, ids, cfg.num_layers, EPS,
                                       **model_of(cfg)))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_the_layers_are_of_every_kind_and_no_width_is_a_lane_multiple(tiny):
    cfg = tiny[0].config
    assert cfg.pattern == "MEM*EM"
    kinds = cfg.cache_layers()
    assert [type(k) for k in kinds] == [StateCache, type(None), StateCache,
                                        KVCache, type(None), StateCache]
    assert kinds[3] == KVCache(heads=2, head_dim=16)
    assert kinds[0].arrays == (((4, 12, 8), jnp.dtype("float32")),
                               ((3, 80), jnp.dtype("float32")))
    for width in (cfg.hidden_size, cfg.intermediate_size,
                  cfg.expert_width_stored, cfg.shared_intermediate_size,
                  cfg.mamba_inner, cfg.conv_width,
                  cfg.num_kv_heads * cfg.head_dim):
        assert width % 128


def test_bucket_padding_changes_nothing_and_is_routed_nowhere(tiny):
    module, variables, _ = tiny
    ids = prompt_of(40)
    padded = jnp.asarray([ids + [0] * 24, prompt_of(64, 5)])
    (got, caches), state = module.apply(
        variables, padded, kv_lengths=jnp.asarray([40, 64]),
        return_cache=True, mutable=["moe"])
    np.testing.assert_allclose(np.asarray(got)[0, :40], ref_logits(tiny, ids),
                               atol=TOL, rtol=0)
    pairs = np.asarray(module.routed_pairs(state))
    elsewhere = np.asarray(module.routed_elsewhere(state))
    assert pairs.shape == (2, 4) and elsewhere.shape == (2,)
    # every real token's 2 choices are counted once, held or not
    assert (pairs.sum(axis=1) + elsewhere == (40 + 64) * 2).all()
    assert (pairs.sum(axis=1) > 0).all() and (elsewhere > 0).all()
    # the state a padded row leaves is its state after 40 tokens
    alone = module.apply(variables, jnp.asarray([ids]), return_cache=True)[1]
    for kind, padded_layer, own in zip(module.config.pattern, caches, alone):
        if kind == "M":
            for a, b in zip(padded_layer, own):
                np.testing.assert_allclose(np.asarray(a)[0], np.asarray(b)[0],
                                           atol=1e-5, rtol=0)


def test_router_chooses_the_references_experts(tiny):
    from kfserving_tpu.ops import moe

    module, variables, _ = tiny
    cfg = module.config
    ids = prompt_of(48)
    routing = []
    ref_logits(tiny, ids, routing=routing)
    _, state = module.apply(
        variables, jnp.asarray([ids]), capture_intermediates=(
            lambda mdl, _: mdl.name == "router"), mutable=["intermediates"])
    layers = [i for i, c in enumerate(cfg.pattern) if c == "E"]
    assert len(routing) == len(layers) == 2
    for i, want in zip(layers, routing):
        logits = state["intermediates"][f"layer_{i}"]["mixer"]["router"][
            "__call__"][0]
        bias = variables["params"][f"layer_{i}"]["mixer"]["router_bias"]
        _, got = moe.route_sigmoid(logits, bias, cfg.experts_per_token,
                                   cfg.routed_scaling_factor)
        np.testing.assert_array_equal(np.sort(np.asarray(got), -1),
                                      np.sort(want, -1))
        # the bias moved some choices: it is not a no-op here
        _, plain = moe.route_sigmoid(logits, jnp.zeros_like(bias),
                                     cfg.experts_per_token, 1.0)
        assert (np.sort(np.asarray(plain), -1) != np.sort(want, -1)).any()


def test_bfloat16_compute_is_inside_a_bound_that_8_bits_are_not(tiny):
    """The served configuration computes in bfloat16.  Where it chooses
    the reference's experts its logits lie within 0.1 of the float32
    reference's; the reference with its weights and layer outputs rounded
    to an 8-bit float is further away on the same rows, so the bound tells
    the two apart.  (Past a token whose 2nd and 3rd expert scores are
    closer than bfloat16 resolves, the choice flips, and the recurrence
    carries that token's difference forward: those rows are not compared.
    The chip's comparison meets the same flips with 6 of 128 experts,
    where one moves far less.)"""
    from kfserving_tpu.ops import moe

    _, variables, _ = tiny
    ids = prompt_of(48)
    routing = []
    want = ref_logits(tiny, ids, routing=routing)
    bf16 = NemotronHLM(nemotron_h_tiny(max_seq=MAX_SEQ, dtype=jnp.bfloat16))
    got, state = bf16.apply(
        variables, jnp.asarray([ids]), capture_intermediates=(
            lambda mdl, _: mdl.name == "router"), mutable=["intermediates"])
    same = len(ids)
    for i, chosen in zip((1, 4), routing):
        at = state["intermediates"][f"layer_{i}"]["mixer"]
        _, mine = moe.route_sigmoid(
            at["router"]["__call__"][0],
            variables["params"][f"layer_{i}"]["mixer"]["router_bias"], 2, 2.5)
        flips = np.nonzero((np.sort(np.asarray(mine), -1)
                            != np.sort(chosen, -1)).any(-1))[0]
        same = min([same] + list(flips))
    assert same >= 16
    got = np.asarray(got)[0, :same]
    coarse = ref_logits(tiny, ids, round_to="float8_e4m3fn")[:same]
    bound = 0.1
    assert np.abs(got - want[:same]).max() < bound \
        < np.abs(coarse - want[:same]).max()


def test_the_two_copies_of_the_reference_agree(tiny):
    from chipbench.references import nemotron_h as benchmarks_copy

    module, _, flat = tiny
    ids = prompt_of(40)
    theirs = np.asarray(benchmarks_copy.logits(
        flat, ids, module.config.num_layers, EPS, **model_of(module.config)))
    np.testing.assert_array_equal(ref_logits(tiny, ids), theirs)
    # the pattern, the Mamba geometry, the router's settings and the share
    # come from its own file
    assert benchmarks_copy.settings() == {
        "pattern": "MEMEM*EMEMEM*EME", "mamba_heads": 64, "ssm_groups": 8,
        "ssm_state": 128, "experts_per_token": 6, "scaling": 2.5,
        "experts_held": (0, 64)}


def test_the_two_shares_and_the_shared_expert_once_make_the_uncut_layer(
        tiny):
    """Experts 0-3 here, 4-7 on the other chip, the shared expert on both:
    the two partial results less one shared expert are what the reference
    gives for the layer with all 8 experts."""
    from kfserving_tpu.models.nemotron_h import ExpertMixer

    module, variables, flat = tiny
    cfg = module.config
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 21, cfg.hidden_size)), jnp.float32)
    own = variables["params"]["layer_1"]["mixer"]
    more = jax.tree.map(lambda t: t, own)
    for name, key in (("up", 5), ("down", 6)):
        more[name] = jnp.roll(own[name], 1, axis=0) * 0.9  # other experts
    parts = []
    for first, params in ((0, own), (4, more)):
        layer = ExpertMixer(nemotron_h_tiny(max_seq=MAX_SEQ,
                                            experts_held=(first, 4)))
        parts.append(np.asarray(layer.apply({"params": params}, x)))
    w = {k: jnp.asarray(v) for k, v in flatten_dict(
        {**own, "up": jnp.concatenate([own["up"], more["up"]]),
         "down": jnp.concatenate([own["down"], more["down"]])},
        sep="/").items()}
    flat_x = x.reshape(-1, cfg.hidden_size)
    settings = dict(experts_per_token=cfg.experts_per_token,
                    scaling=cfg.routed_scaling_factor)
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(reference.experts(flat_x, w, experts_held=(0, 8),
                                             **settings))
        shared = uncut - np.asarray(reference.experts(
            flat_x, w, experts_held=(0, 8), shared=False, **settings))
    total = (parts[0] + parts[1]).reshape(-1, cfg.hidden_size) - shared
    np.testing.assert_allclose(total, uncut, atol=TOL, rtol=0)
    assert np.abs(shared).max() > 0.01
    assert np.abs(parts[0] - parts[1]).max() > 0.01


def test_parameter_counts_of_the_benchmarked_shapes():
    """The first 16 layers with experts 0-63 at the published widths:
    7 x 38.74 M (Mamba) + 2 x 23.40 M (attention) + 7 x (64 x 9.98 M held +
    19.96 M shared + 0.34 M router) + 2 x 352.3 M (embedding, head) =
    5.635 B; stored with the experts' width padded to 1920, 5.789 B."""
    cfg = NemotronHConfig(pattern="MEMEM*EMEMEM*EME", experts_held=(0, 64))
    counts = cfg.param_counts()
    assert counts["per_expert"] == 2 * 2688 * 1856
    assert counts["total"] == 5_634_855_744
    assert counts["active"] == counts["always_read"] \
        + 7 * 3 * counts["per_expert"]
    stored = counts["total"] + 7 * 64 * 2 * 2688 * (1920 - 1856)
    shapes = jax.eval_shape(lambda: NemotronHLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == stored


# -- through the engine ------------------------------------------------------
async def test_prefill_then_decode_through_slots_pool_and_waves(tiny):
    """Prompts that pad to different buckets, 16-step waves, two more
    requests than slots (so two slots are reused)."""
    prompts = [prompt_of(n, stride) for n, stride in (
        (37, 7), (5, 3), (64, 11), (90, 5), (17, 13), (33, 9))]
    engine = engine_of(tiny, steps_per_call=16)
    try:
        results = await asyncio.wait_for(asyncio.gather(*[
            served(engine, p, 40) for p in prompts]), timeout=600)
        stats = engine.stats()
    finally:
        await engine.close()
    for prompt, (tokens, chosen, top) in zip(prompts, results):
        assert_matches_reference(tiny, prompt, tokens, chosen, top)
    cfg = tiny[0].config
    state_bytes = 4 * 3 * (4 * 12 * 8 + 3 * 80) * 4  # slots, layers, float32
    assert stats["recurrent_state_bytes"] == state_bytes
    assert stats["cache_bytes"] == state_bytes + 2 * 32 * BS * 2 * 16 * 4
    assert 0.2 < stats["moe_pairs_held_share"] < 0.8
    assert stats["active_params"] == cfg.param_counts()["active"]
    assert stats["prefill_rows"] == 0


async def test_a_reused_slot_equals_a_fresh_one(tiny):
    """One slot: every request but the first decodes where another's
    state was, and reads the reference's logits all the same."""
    prompts = [prompt_of(29, 7), prompt_of(50, 3), prompt_of(29, 7)]
    engine = engine_of(tiny, max_slots=1, steps_per_call=4)
    try:
        results = [await served(engine, p, 12) for p in prompts]
    finally:
        await engine.close()
    for prompt, (tokens, chosen, top) in zip(prompts, results):
        assert_matches_reference(tiny, prompt, tokens, chosen, top)
    assert results[0][0] == results[2][0]
    np.testing.assert_allclose(results[0][1], results[2][1], atol=1e-6)


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


async def test_a_repeated_prompt_counts_no_prefix_hit(tiny):
    prompt = prompt_of(70)  # four whole blocks of 16
    engine = engine_of(tiny)
    try:
        first = await served(engine, prompt, 6)
        again = await served(engine, prompt, 6)
        paged = engine.stats()["paged"]
        refused = engine.prefix_reuse_refused
    finally:
        await engine.close()
    assert first[0] == again[0]
    assert paged["prefix_hits"] == 0 and paged["index_entries"] == 0
    assert paged["prefill_tokens_saved"] == 0
    assert refused == 2
    assert_matches_reference(tiny, prompt, *again)


@pytest.mark.parametrize("setting", [
    {"speculative": {"tokens": 3}},
    {"prefill_chunk_tokens": 32},
    {"host_tier_blocks": 8},
])
def test_what_rests_on_rows_addressed_by_position_is_refused_at_load(
        tiny, setting):
    with pytest.raises(InvalidInput, match="recurrent state"):
        engine_of(tiny, **setting)


@pytest.mark.parametrize("serving", [
    {},                                       # rows unset: a group of 16-64
    {"prefill_rows": 16, "prefill_buckets": [MAX_SEQ]},
    {"prefill_rows": 8, "prefill_buckets": [64, MAX_SEQ]},
])
def test_on_a_tpu_only_the_prefill_shapes_that_have_run_are_served(
        tiny, monkeypatch, serving):
    """The v5e hung at a (4, 1024) prefill and the cause is not known: on a
    TPU a model with recurrent state loads only at the shapes that have
    run there since (1 to 8 rows of one bucket, the tiny model's longest
    here); elsewhere, as every other test here shows, any
    shape loads."""
    from kfserving_tpu.ops import ssm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ssm, "CHIP_PROVEN", frozenset(
        (rows, MAX_SEQ) for rows in (1, 2, 4, 8)))
    with pytest.raises(InvalidInput, match="run on the chip"):
        engine_of(tiny, **serving)


async def test_prefill_rows_bounds_a_dispatch_without_a_sync(tiny):
    """Six arrivals at once over 4 free slots: with `prefill_rows` 2 no
    prefill dispatch carries more than 2 rows, and none waits for its
    insert (that is the refusal path's)."""
    prompts = [prompt_of(20 + i, 3 + i) for i in range(6)]
    engine = engine_of(tiny, prefill_rows=2)
    try:
        results = await asyncio.wait_for(asyncio.gather(*[
            served(engine, p, 8) for p in prompts]), timeout=300)
        stats = engine.stats()
        programs = {k for k in engine._dispatched_programs
                    if k[0] == "prefill"}
    finally:
        await engine.close()
    assert stats["prefill_rows"] == stats["prefill_rows_cap"] == 2
    assert stats["prefills"] >= 3 and stats["prefill_requests"] == 6
    assert programs and all(rows <= 2 for _, rows, _ in programs)
    assert engine._prefill_refusals == 0
    for prompt, (tokens, chosen, top) in zip(prompts, results):
        assert_matches_reference(tiny, prompt, tokens, chosen, top)


async def test_a_split_group_inserts_every_rows_state(tiny):
    """Seven arrivals of two blocks, a row of the bucket each, once the
    1-, 2-, 4- and 8-row programs are warm go as 4 + 2 + 1, each piece
    an insert of its own into its rows' slots:
    every request's conv and scan state (and K/V) is its own, so eight
    served tokens each match the reference."""
    prompts = [prompt_of(18 + i, 3 + i) for i in range(7)]
    engine = engine_of(tiny, max_slots=8, prefill_buckets=[32, MAX_SEQ])
    rows, prefill = [], engine._prefill

    def watched(variables, ids, *rest):
        rows.append(ids.shape[0])
        return prefill(variables, ids, *rest)

    engine._prefill = watched
    # every dispatched program timed at its rows: the pieces take less
    engine._note_prefill_took = lambda rows, bucket, seconds: \
        engine._prefill_took_s.__setitem__((rows, bucket), [float(rows)])
    try:
        for n in (1, 2, 4, 8):
            await asyncio.wait_for(asyncio.gather(*[
                served(engine, prompt_of(17 + i), 2) for i in range(n)]),
                timeout=300)
        assert rows == [1, 2, 4, 8]
        results = await asyncio.wait_for(asyncio.gather(*[
            served(engine, p, 8) for p in prompts]), timeout=300)
        stats = engine.stats()
    finally:
        await engine.close()
    assert rows[4:] == [4, 2, 1]
    assert stats["prefill_rows_dispatched"] == 15 + 7
    assert stats["prefill_rows_padded"] == 0
    for prompt, (tokens, chosen, top) in zip(prompts, results):
        assert_matches_reference(tiny, prompt, tokens, chosen, top)


def test_the_older_models_declare_the_cache_the_engine_built_before():
    from kfserving_tpu.models.decoder import decoder_tiny
    from kfserving_tpu.models.olmoe import olmoe_tiny

    for cfg in (decoder_tiny(), olmoe_tiny()):
        assert cfg.cache_layers() == [
            KVCache(cfg.num_heads, cfg.head_dim)] * cfg.num_layers
    spec = create_model("decoder_tiny", max_seq=MAX_SEQ)
    engine = GenerationEngine(spec.module, init_params(spec, seed=0),
                              max_slots=2, max_seq=MAX_SEQ, block_size=BS)
    try:
        assert engine._cache_shape == (16, BS, 128)
        assert engine.cache_bytes() == 4 * 2 * 16 * BS * 128 * 4
        assert engine.recurrent_state_bytes == 0
        assert engine._kv_bytes_per_token == 2 * 4 * 128 * 4
        assert engine._attn_flops_coeff == 4.0 * 4 * 128
    finally:
        engine.shutdown_nowait()


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
