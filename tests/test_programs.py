"""engine/programs.py without an engine: the names the benchmark finds
the programs by, the sampler's keying, the cache's layout and what a
kind of cache supports."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kfserving_tpu.engine import programs
from kfserving_tpu.models import create_model
from kfserving_tpu.protocol.errors import InvalidInput

SIZES = dict(max_slots=4, max_seq=64, prefill_buckets=[16, 32],
             block_size=16, cache_blocks=None, window_cache_blocks=None,
             mesh=None)

# chipbench/trace.py keys `jit_decode_fn`, `jit_prefill_fn` and
# `jit_insert_fn`, chipbench/kinds/generate.py reads `jit(prefill_fn)`
# in the compile log and `decode_fn` in operation paths: a renamed
# program fails nothing there, and the per-layer metrics read 0.
NAMES = {"decode": "decode_fn", "prefill": "prefill_fn",
         "chunk_prefill": "chunk_prefill_fn", "insert": "insert_fn",
         "feed_update": "feed_update_fn"}


@pytest.mark.parametrize("architecture,limits", [
    ("decoder_tiny", ()),
    ("olmoe_tiny", ()),
    ("nemotron_h_tiny", ("recurrent state",)),
    ("mellum_tiny", ("sliding-window layers",)),
])
def test_build_gives_the_programs_under_the_names_the_benchmark_reads(
        architecture, limits):
    module = create_model(architecture).module
    layout = programs.CacheLayout(module.config, architecture, **SIZES)
    assert layout.limits == limits
    built = programs.build(module, layout.kinds, 4, 5,
                           jax.random.PRNGKey(0))
    for field, name in NAMES.items():
        program = getattr(built, field)
        assert program.__name__ == name
        assert program.__wrapped__.__name__ == name
    assert built.spec_verify is None and built.gather_blocks is None
    with_all = programs.build(module, layout.kinds, 4, 5,
                              jax.random.PRNGKey(0), spec_tokens=2,
                              host_tier=True)
    assert with_all.spec_verify.__name__ == "spec_verify_fn"
    assert with_all.gather_blocks.__name__ == "gather_blocks_fn"


def test_decode_fn_is_named_in_its_lowered_program_and_scans_its_steps():
    module = create_model("decoder_tiny").module
    layout = programs.CacheLayout(module.config, "m", **SIZES)
    variables = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 16), jnp.int32)))
    built = programs.build(module, layout.kinds, 4, 5,
                           jax.random.PRNGKey(0))

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    i32, f32 = jnp.int32, jnp.float32
    lowered = built.decode.lower(
        variables, layout.caches, arg(i32, 4, layout.blocks_per_slot),
        arg(i32, 4), arg(i32, 4), arg(i32, 4), arg(f32, 4), arg(i32, 4),
        arg(f32, 4), arg(i32, 4), arg(jnp.bool_))
    text = lowered.as_text()
    assert "jit_decode_fn" in text
    assert "stablehlo.while" in text  # the scan of steps_per_call steps


def test_sample_is_a_function_of_seed_and_position_alone():
    key = jax.random.PRNGKey(7)
    rng = np.random.default_rng(0)
    row = jnp.asarray(rng.normal(size=(1, 384)), jnp.float32)
    others = jnp.asarray(rng.normal(size=(5, 384)), jnp.float32)

    def draw(logits, at, seed=11, position=23):
        n = logits.shape[0]
        seeds = jnp.arange(100, 100 + n, dtype=jnp.int32).at[at].set(seed)
        positions = jnp.arange(n, dtype=jnp.int32).at[at].set(position)
        return int(programs.sample(
            key, logits, jnp.full((n,), 0.8, jnp.float32),
            jnp.zeros((n,), jnp.int32), jnp.ones((n,), jnp.float32),
            seeds, positions)[at])

    alone = draw(row, 0)
    # Another slot of another batch: the same token.
    assert draw(jnp.concatenate([others, row]), 5) == alone
    assert draw(jnp.concatenate([others[:2], row, others[2:]]), 2) == alone
    # Another position, seed or base key: other noise.
    drawn = {draw(row, 0, position=p) for p in range(23, 40)}
    assert len(drawn) > 1
    assert len({draw(row, 0, seed=s) for s in range(11, 28)}) > 1
    greedy = programs.sample(
        key, row, jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.int32),
        jnp.ones((1,), jnp.float32), jnp.zeros((1,), jnp.int32),
        jnp.zeros((1,), jnp.int32))
    assert int(greedy[0]) == int(jnp.argmax(row[0]))


def test_mask_to_support_keeps_top_k_and_the_nucleus():
    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05]] * 3, jnp.float32))
    kept = programs.mask_to_support(
        logits, jnp.asarray([2, 0, 0], jnp.int32),
        jnp.asarray([1.0, 0.7, 1.0], jnp.float32)) > -1e30
    assert kept.tolist() == [[True, True, False, False],
                             [True, True, False, False],
                             [True, True, True, True]]


def test_the_layout_books_both_pools_of_a_window_model():
    module = create_model("mellum_tiny").module
    layout = programs.CacheLayout(module.config, "m", **SIZES)
    assert layout.window == 16 and layout.ring_columns == 2
    assert layout.num_window_blocks == 4 * 2
    assert layout.blocks_per_slot == 4 and layout.num_blocks == 16
    assert layout.window_layers == 3 and layout.kv_layers == 4
    pools = [layer[0].shape for layer in layout.caches]
    assert pools.count(layout.pool_shape) == 1
    assert pools.count((8,) + layout.pool_shape[1:]) == 3
    assert layout.cache_bytes == sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(layout.caches))
    assert layout.state_bytes == 0


@pytest.mark.parametrize("kind,setting", [
    (kind, setting) for kind, settings in programs.UNSERVED.items()
    for setting in settings])
def test_refusal_names_the_setting_the_model_and_the_kind(kind, setting):
    architecture = {"recurrent state": "nemotron_h_tiny",
                    "sliding-window layers": "mellum_tiny",
                    "latent rows": "deepseek_v3_tiny"}[kind]
    module = create_model(architecture).module
    layout = programs.CacheLayout(module.config, "m", **SIZES)
    off = dict.fromkeys(programs.UNSERVED[kind], False)
    assert layout.refusal("m", off) is None
    text = layout.refusal("m", {**off, setting: True})
    assert text.startswith(
        f"{setting} is not served for 'm', a model with {kind}: ")
    dense = programs.CacheLayout(
        create_model("decoder_tiny").module.config, "d", **SIZES)
    assert dense.refusal("d", dict.fromkeys(off, True)) is None


def test_the_layout_refuses_lengths_that_are_not_whole_blocks():
    config = create_model("decoder_tiny").module.config
    with pytest.raises(InvalidInput, match="multiple of block_size 16"):
        programs.CacheLayout(config, "m", **{**SIZES, "max_seq": 72})
    with pytest.raises(InvalidInput, match="prefill bucket 24"):
        programs.CacheLayout(
            config, "m", **{**SIZES, "prefill_buckets": [24, 32]})
    assert programs.derive_block_size(64, [16, 32]) == 16
    assert programs.derive_block_size(2048, [128, 1024]) == 128


# -- the sampler's tail ----------------------------------------------------------
# `sample` and `logprob_of` as they stood before the tail was gated on
# what a dispatch's rows ask for: the oracle the gated pair is held to.
def sample_ungated(base_key, logits, temps, top_ks, top_ps, seeds,
                   noise_pos):
    greedy = jnp.argmax(logits, axis=-1)
    need_mask = jnp.any((top_ks > 0) | (top_ps < 1.0))
    masked = jax.lax.cond(
        need_mask,
        lambda l: programs.mask_to_support(l, top_ks, top_ps),
        lambda l: l, logits)

    def row_key(seed, pos):
        return jax.random.fold_in(
            jax.random.fold_in(base_key, seed), pos)

    keys = jax.vmap(row_key)(seeds, noise_pos)
    gumbel = jax.vmap(
        lambda k: jax.random.gumbel(k, (logits.shape[-1],))
    )(keys)
    scaled = masked / jnp.maximum(temps, 1e-6)[:, None]
    sampled = jnp.argmax(scaled + gumbel, axis=-1)
    return jnp.where(temps <= 0.0, greedy,
                     sampled).astype(jnp.int32)


def logprob_of_ungated(logits, chosen, top_n: int, want=None):
    lps = jax.nn.log_softmax(logits, axis=-1)
    chosen_lp = jnp.take_along_axis(
        lps, chosen[:, None].astype(jnp.int32), axis=-1)[:, 0]
    top_lps, top_ids = jax.lax.top_k(lps, top_n)
    return chosen_lp, top_ids.astype(jnp.int32), top_lps


WAVES = {"greedy": [0.0] * 6,
         "sampled": [0.5, 0.8, 1.0, 1.3, 0.8, 0.7],
         "mixed": [0.0, 0.8, 0.0, 1.3, 0.7, 0.0]}
SUPPORTS = {"whole": ([0] * 6, [1.0] * 6),
            "top_k": ([0, 5, 40, 1, 0, 3], [1.0] * 6),
            "top_p": ([0] * 6, [1.0, 0.9, 0.5, 0.95, 0.3, 1.0]),
            "both": ([0, 5, 40, 0, 7, 3], [0.9, 1.0, 0.5, 0.95, 1.0, 0.8])}


def _wave(wave: str, support: str = "whole", vocab: int = 384):
    """(logits [6, vocab] with columns 5 and 7 of row 0 tied at the top,
    temps, top_ks, top_ps, seeds, noise positions)."""
    rng = np.random.default_rng(3)
    logits = (3.0 * rng.normal(size=(6, vocab))).astype(np.float32)
    logits[0, 7] = logits[0, 5] = 20.0
    top_ks, top_ps = SUPPORTS[support]
    return (jnp.asarray(logits), jnp.asarray(WAVES[wave], jnp.float32),
            jnp.asarray(top_ks, jnp.int32), jnp.asarray(top_ps, jnp.float32),
            jnp.arange(40, 46, dtype=jnp.int32),
            jnp.arange(17, 23, dtype=jnp.int32))


@pytest.mark.parametrize("support", sorted(SUPPORTS))
@pytest.mark.parametrize("wave", sorted(WAVES))
def test_a_wave_draws_the_tokens_the_ungated_sampler_drew(wave, support):
    """Greedy rows take the argmax and sampled rows the token of their
    (seed, position), bit for bit, whether the wave around them is all
    greedy (no noise is drawn), all sampled or mixed, and whatever
    support its rows restrict themselves to."""
    key = jax.random.PRNGKey(7)
    args = _wave(wave, support)
    got = jax.jit(programs.sample)(key, *args)
    want = jax.jit(sample_ungated)(key, *args)
    assert got.dtype == jnp.int32 and got.shape == (6,)
    assert got.tolist() == want.tolist()
    greedy = [i for i, t in enumerate(WAVES[wave]) if t == 0.0]
    assert got[jnp.asarray(greedy, jnp.int32)].tolist() == \
        jnp.argmax(args[0], axis=-1)[jnp.asarray(greedy, jnp.int32)].tolist()
    if wave != "greedy" and support == "whole":
        assert got.tolist() != jnp.argmax(args[0], axis=-1).tolist()


def test_a_greedy_wave_draws_no_noise():
    """In the lowered sampler everything that exists for a sampled row
    (the keys' threefry rounds, the Gumbel draw, the mask's sort) sits
    inside one branch, and the branch gives [rows] tokens, never
    [rows, vocab] logits: outside it there is the greedy argmax."""
    text = jax.jit(programs.sample).lower(
        jax.random.PRNGKey(7), *_wave("greedy")).as_text()
    main = text.split("func.func public @main")[1].split(
        "func.func")[0].splitlines()
    opens = [n for n, line in enumerate(main) if "stablehlo.case" in line]
    closes = [n for n, line in enumerate(main)
              if line.startswith("    }) : (tensor<i32>) -> ")]
    assert len(closes) == 1 and opens[0] < closes[0]
    assert main[closes[0]].endswith("-> tensor<6xi32>")
    outside = "\n".join(main[:opens[0]] + main[closes[0] + 1:])
    inside = "\n".join(main[opens[0]:closes[0]])
    for call in ("@_gumbel", "@_threefry_fold_in", "@sort"):
        assert call in inside and call not in outside, call
    assert "call @argmax" in outside


@pytest.mark.parametrize("wave", sorted(WAVES))
def test_logprobs_are_the_ungated_ones_where_a_row_asked(wave):
    """`want` set: the chosen token's and the top 5's log-probabilities to
    float32 rounding and the top 5's ids exactly, tied maxima the lower
    column first, though no [rows, vocab] array of log-probabilities is
    formed."""
    args = _wave(wave)
    logits = args[0]
    chosen = sample_ungated(jax.random.PRNGKey(7), *args)
    got = jax.jit(programs.logprob_of, static_argnums=2)(
        logits, chosen, 5, jnp.asarray(True))
    want = jax.jit(logprob_of_ungated, static_argnums=2)(logits, chosen, 5)
    assert [x.dtype for x in got] == [x.dtype for x in want]
    assert got[1].tolist() == want[1].tolist()
    assert got[1][0, :2].tolist() == [5, 7]     # the tie, in argmax's order
    assert float(got[2][0, 0]) == float(got[2][0, 1])
    np.testing.assert_allclose(got[0], want[0], rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(got[2], want[2], rtol=2e-6, atol=2e-6)
    assert int(chosen[0]) == 5 or WAVES[wave][0] > 0.0
    # a chosen token among the top 5 reads the same number both ways
    at = jnp.argmax(got[1] == chosen[:, None], axis=-1)
    held = jnp.any(got[1] == chosen[:, None], axis=-1)
    np.testing.assert_array_equal(
        np.asarray(got[0])[np.asarray(held)],
        np.asarray(jnp.take_along_axis(got[2], at[:, None], 1)[:, 0])[
            np.asarray(held)])


def test_logprobs_nobody_asked_for_are_zeros_of_the_same_shapes():
    args = _wave("mixed")
    chosen = sample_ungated(jax.random.PRNGKey(7), *args)
    asked, unasked = (jax.jit(programs.logprob_of, static_argnums=2)(
        args[0], chosen, 5, jnp.asarray(want)) for want in (True, False))
    assert [(x.shape, x.dtype) for x in asked] == \
        [(x.shape, x.dtype) for x in unasked]
    assert all(not np.asarray(x).any() for x in unasked)
    assert all(np.asarray(x).any() for x in asked)


def _tail_inputs(program: str, layout, temps):
    """The arguments after (variables[, caches]) of one of `build`'s four
    programs that end in the sampler, over `SIZES`' four slots, rows 1 and
    3 sampling where `temps` says so."""
    rows = 1 if program == "chunk_prefill" else 4
    i32, f32 = jnp.int32, jnp.float32
    sampling = (jnp.asarray(temps[:rows], f32),
                jnp.asarray([0, 5, 0, 0][:rows], i32),
                jnp.asarray([1.0, 1.0, 1.0, 0.9][:rows], f32),
                jnp.arange(70, 70 + rows, dtype=i32))
    table = jnp.arange(4 * layout.blocks_per_slot, dtype=i32).reshape(
        4, layout.blocks_per_slot)
    ids = jnp.asarray(np.random.default_rng(5).integers(
        1, 384, (rows, 16)), i32)
    if program == "decode":
        return (table, jnp.asarray([3, 17, 42, 5], i32),
                jnp.asarray([0, 9, 30, 1], i32), jnp.full((4,), 64, i32),
                *sampling)
    if program == "prefill":
        return (ids, jnp.asarray([16, 5, 11, 1], i32), *sampling)
    if program == "chunk_prefill":
        return (table[:1, :1], ids, jnp.arange(16, dtype=i32)[None],
                jnp.asarray([15], i32), *sampling, jnp.asarray([16], i32))
    positions = jnp.asarray([4, 9, 30, 1], i32)[:, None] + jnp.arange(3)
    return (table, jnp.asarray([3, 17, 42, 5], i32), ids[:, :2], positions,
            *sampling)


@pytest.mark.parametrize("temps", [[0.0] * 4, [0.0, 0.8, 0.0, 1.2]],
                         ids=["greedy", "mixed"])
@pytest.mark.parametrize("program", ["decode", "prefill", "chunk_prefill",
                                     "spec_verify"])
def test_every_program_samples_and_scores_as_with_the_ungated_tail(
        monkeypatch, program, temps):
    """Decode, prefill, chunked prefill and speculative verify, built
    over the gated tail and over the ungated one: the same tokens bit
    for bit, the same log-probabilities where the dispatch asked, zeros
    where it did not."""
    module = create_model("decoder_tiny").module
    variables = module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 16), jnp.int32))

    def run(want):
        layout = programs.CacheLayout(module.config, "m", **SIZES)
        built = programs.build(module, layout.kinds, 3, 5,
                               jax.random.PRNGKey(9), spec_tokens=2)
        caches = () if program == "prefill" else (layout.caches,)
        out = getattr(built, program)(
            variables, *caches, *_tail_inputs(program, layout, temps),
            jnp.asarray(want))
        tokens = out[0]
        lps = out[4:7] if program == "decode" else (
            out[3:6] if program == "spec_verify" else out[2:5])
        return np.asarray(tokens), [np.asarray(x) for x in lps]

    tokens, lps = run(True)
    lean_tokens, zeros = run(False)
    monkeypatch.setattr(programs, "sample", sample_ungated)
    monkeypatch.setattr(programs, "logprob_of", logprob_of_ungated)
    old_tokens, old_lps = run(True)
    np.testing.assert_array_equal(tokens, old_tokens)
    np.testing.assert_array_equal(lean_tokens, old_tokens)
    np.testing.assert_array_equal(lps[1], old_lps[1])
    for new, old in zip(lps[::2], old_lps[::2]):
        np.testing.assert_allclose(new, old, rtol=2e-6, atol=2e-6)
    assert all(not x.any() for x in zeros)
    assert [x.shape for x in zeros] == [x.shape for x in old_lps]


async def test_a_request_that_asks_logprobs_between_greedy_waves_gets_them():
    """Two greedy requests decode, pipelined two calls deep; a third that
    asks for its top 3 is admitted between their waves.  Until then no
    wave's log-probabilities are fetched (`lp_h` None, and the program
    made none); from its first token on the third gets what the ungated
    tail gives over a full forward pass, and the dispatches are counted
    by what they asked."""
    from kfserving_tpu.engine.generator import GenerationEngine
    from kfserving_tpu.observability import metrics as obs

    module = create_model("decoder_tiny").module
    variables = module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 16), jnp.int32))
    eng = GenerationEngine(module, variables, max_slots=4, max_seq=64,
                           prefill_buckets=[16, 32], steps_per_call=2,
                           pipeline_depth=2, name="tail")
    fetched = []                 # (program's tokens shape, lp fetched?)
    fetch_wave = eng._fetch_wave

    def spy(toks_h, lp_h):
        fetched.append((tuple(toks_h.shape), lp_h is not None))
        return fetch_wave(toks_h, lp_h)

    eng._fetch_wave = spy
    calls = obs.engine_sampler_tail_calls_total()

    def counted(program, logprobs):
        return calls.labels(model="tail", program=program, noise="0",
                            logprobs=logprobs).value

    try:
        neighbours = [eng.submit(p, max_new_tokens=40)
                      for p in ([7, 7, 3], [2, 8, 11, 4])]
        streams = [eng.stream(r) for r in neighbours]
        for _ in range(6):       # both are decoding, waves in flight
            for s in streams:
                await anext(s)
        assert fetched and not any(lp for _, lp in fetched)
        assert counted("decode", "1") == counted("prefill", "1") == 0
        lean_before = counted("decode", "0")
        assert lean_before > 0
        prompt = [5, 9, 2, 7, 11]
        asking = eng.submit(prompt, max_new_tokens=6, logprobs=3)
        tokens = [t async for t, _ in eng.stream(asking) if t is not None]
        for r in neighbours:
            eng.cancel(r)
    finally:
        await eng.close()
    assert len(tokens) == len(asking.lp_chosen) == len(asking.lp_top) == 6
    assert counted("prefill", "1") == 1 and counted("decode", "1") >= 3
    assert any(lp for shape, lp in fetched if shape == (4, 2))
    ids = list(prompt)
    for step, tok in enumerate(tokens):
        logits = module.apply(variables, jnp.asarray([ids], jnp.int32))
        chosen_lp, top_ids, top_lps = logprob_of_ungated(
            logits[:, -1], jnp.asarray([tok], jnp.int32), 3)
        assert tok == int(jnp.argmax(logits[0, -1]))
        np.testing.assert_allclose(asking.lp_chosen[step],
                                   float(chosen_lp[0]), rtol=2e-3, atol=2e-3)
        assert [t for t, _ in asking.lp_top[step]] == top_ids[0].tolist()
        np.testing.assert_allclose([v for _, v in asking.lp_top[step]],
                                   np.asarray(top_lps[0]),
                                   rtol=2e-3, atol=2e-3)
        ids.append(tok)


# -- a prefill row that carries several prompts ----------------------------------
PACKED = {**SIZES, "prefill_buckets": [32], "block_size": 8}
BUCKET, BS = 32, 8
PER_ROW = BUCKET // BS


@pytest.mark.parametrize("architecture,bucket,block,packs", [
    ("decoder_tiny", 32, 8, True), ("olmoe_tiny", 32, 8, True),
    ("decoder_tiny", 1024, 128, True),
    ("decoder_tiny", 2048, 128, False),   # the flash kernel's, on a chip
    ("mellum_tiny", 32, 8, False),        # a ring is inserted a prompt
    ("deepseek_v3_tiny", 32, 8, False),   # a latent row's expanded prefill
    # A state starts again where a chunk of its recurrence does (16 in
    # the tiny models): at every block boundary, or not at each.
    ("nemotron_h_tiny", 32, 16, True), ("nemotron_h_tiny", 64, 32, True),
    ("nemotron_h_tiny", 32, 8, False), ("nemotron_h_tiny", 2048, 128, False),
    ("falcon_h1_tiny", 32, 16, True),     # K/V and a state in one layer:
    ("falcon_h1_tiny", 48, 24, False),    # where both halves do
    ("falcon_h1_tiny", 2048, 16, False),
])
def test_which_programs_pack_follows_from_the_layers_and_the_sizes(
        architecture, bucket, block, packs):
    kinds = create_model(architecture).module.config.cache_layers()
    assert programs.packs_prompts(kinds, bucket, block) is packs


@pytest.mark.parametrize("config", ["nemotron-3-nano-16l-ep2",
                                    "falcon-h1-34b-6l"])
def test_the_served_state_models_pack_at_the_sizes_they_are_served_at(
        config):
    """The chunk and the block size as the benchmark's configurations
    state them: every bucket they serve packs."""
    import json
    import os

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "chipbench", "configs",
                           f"{config}.json")) as f:
        served = json.load(f)
    serving = served["serving"]
    chunk = serving["arch_kwargs"]["chunk_size"]
    assert serving["block_size"] % chunk == 0
    arch = create_model(serving["architecture"] + "_tiny",
                        chunk_size=chunk).module.config
    for bucket in serving["prefill_buckets"]:
        assert programs.packs_prompts(arch.cache_layers(), bucket,
                                      serving["block_size"])


def _prompt(n: int, salt: int):
    return [(salt * 31 + 7 * j) % 250 + 1 for j in range(n)]


def _packed_args(placed, rows: int):
    """`prefill_fn`'s arguments after `variables` for prompts `placed`
    as (ids, row, first block, temperature, seed)."""
    ids = np.zeros((rows, BUCKET), np.int32)
    segments = np.full((rows, BUCKET), -1, np.int32)
    positions = np.zeros((rows, BUCKET), np.int32)
    last = np.zeros((rows, PER_ROW), np.int32)
    lengths = np.ones(rows * PER_ROW, np.int32)
    temps = np.zeros(rows * PER_ROW, np.float32)
    seeds = np.zeros(rows * PER_ROW, np.int32)
    for prompt, row, block, temp, seed in placed:
        n, start, at = len(prompt), block * BS, row * PER_ROW + block
        assert (segments[row, start:start + n] == -1).all()
        ids[row, start:start + n] = prompt
        segments[row, start:start + n] = block
        positions[row, start:start + n] = np.arange(n)
        last[row, block] = start + n - 1
        lengths[at], temps[at], seeds[at] = n, temp, seed
    i32 = jnp.int32
    return [jnp.asarray(ids), jnp.asarray(lengths), jnp.asarray(temps),
            jnp.zeros(rows * PER_ROW, i32),
            jnp.ones(rows * PER_ROW, jnp.float32), jnp.asarray(seeds),
            jnp.asarray(True),
            (jnp.asarray(segments), jnp.asarray(positions),
             jnp.asarray(last))]


@pytest.fixture(scope="module", params=[
    ("decoder_tiny", {}), ("olmoe_tiny", {}),
    # The two state models, their recurrence in chunks of a block.
    ("nemotron_h_tiny", {"chunk_size": 8}),
    ("falcon_h1_tiny", {"chunk_size": 8})], ids=lambda p: p[0])
def packing(request):
    """(the prefill program of a model that packs, its variables, what
    its layers keep)."""
    name, sizes = request.param
    module = create_model(name, **sizes).module
    assert module.config.dtype == jnp.float32
    layout = programs.CacheLayout(module.config, "m", **PACKED)
    assert programs.packs_prompts(layout.kinds, BUCKET, BS)
    built = programs.build(module, layout.kinds, 4, 5,
                           jax.random.PRNGKey(9))
    variables = module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 16), jnp.int32))
    return built.prefill, variables, layout.kinds


def _kept(kinds, caches, rows: int, row: int, columns: slice, entry: int):
    """What a prefill of `rows` rows left a prompt, a layer: its K/V over
    the `columns` of its `row` (the positions it owns: what lies behind it
    in its last block is never read), its state at its `entry`."""
    def kv(_, arrays):
        assert all(x.shape[:2] == (rows, BUCKET) for x in arrays)
        return tuple(x[row, columns] for x in arrays)

    def state(kind, arrays):
        assert [x.shape[1:] for x in arrays] == [
            shape for shape, _ in kind.arrays]
        return tuple(x[entry] for x in arrays)

    return [np.asarray(x) for kind, layer in zip(kinds, caches)
            for x in jax.tree.leaves(programs.by_part(kind, kv, state,
                                                      layer))]


def _read(out, kinds, row: int, block: int, n: int):
    """What a prompt of n tokens placed at (row, block) got: its first
    token, its log-probabilities, and what every layer keeps of it."""
    at, start = row * PER_ROW + block, block * BS
    firsts, caches, chosen, top_ids, top_lps = out[:5]
    return (int(firsts[at]), float(chosen[at]), np.asarray(top_ids[at]),
            np.asarray(top_lps[at]),
            _kept(kinds, caches, firsts.shape[0] // PER_ROW, row,
                  slice(start, start + n), at))


def _same(got, want):
    assert got[0] == want[0]
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[2], want[2])
    np.testing.assert_allclose(got[3], want[3], rtol=1e-5, atol=1e-5)
    for ours, theirs in zip(got[4], want[4]):
        np.testing.assert_allclose(ours, theirs, rtol=1e-5, atol=1e-5)


# name -> (the prompt's tokens, its row, its first block, what lies
# beside it as (tokens, row, first block), the program's rows)
BESIDE = {
    "alone": (11, 0, 0, [], 1),
    "first-in-a-row": (11, 0, 0, [(7, 0, 2), (8, 0, 3)], 1),
    "last-in-a-row": (11, 0, 2, [(16, 0, 0)], 1),
    "between-two": (11, 0, 1, [(3, 0, 0), (5, 0, 3)], 1),
    "one-block": (8, 0, 2, [(16, 0, 0), (8, 0, 3)], 1),
    "one-block-alone": (8, 0, 0, [], 1),
    "the-bucket": (32, 1, 0, [(9, 0, 0), (12, 0, 2)], 2),
    "second-row": (11, 1, 2, [(32, 0, 0), (8, 1, 0), (1, 1, 1)], 2),
}


@pytest.mark.parametrize("temp", [0.0, 0.9], ids=["greedy", "sampled"])
@pytest.mark.parametrize("case", sorted(BESIDE))
def test_a_packed_prompt_gets_what_it_gets_alone_in_a_row(packing, case,
                                                          temp):
    """Whatever shares its row: the first token (a sampled one drawn by
    the prompt's own length and seed), the chosen and the top 5
    log-probabilities, K/V and a recurrence's state and conv rows,
    against the same prompt through the one-prompt-a-row form of the
    program (`lengths`, no segments)."""
    prefill, variables, kinds = packing
    n, row, block, others, rows = BESIDE[case]
    prompt = _prompt(n, 1)
    padded = np.zeros((1, BUCKET), np.int32)
    padded[0, :n] = prompt
    alone = prefill(variables, jnp.asarray(padded),
                    jnp.asarray([n], jnp.int32),
                    jnp.asarray([temp], jnp.float32),
                    jnp.zeros(1, jnp.int32), jnp.ones(1, jnp.float32),
                    jnp.asarray([77], jnp.int32), jnp.asarray(True))
    want = (int(alone[0][0]), float(alone[2][0]), np.asarray(alone[3][0]),
            np.asarray(alone[4][0]),
            _kept(kinds, alone[1], 1, 0, slice(0, n), 0))
    placed = [(prompt, row, block, temp, 77)] + [
        (_prompt(m, 2 + i), r, b, 1.3, 5 + i)
        for i, (m, r, b) in enumerate(others)]
    out = prefill(variables, *_packed_args(placed, rows))
    _same(_read(out, kinds, row, block, n), want)


def test_unused_entries_change_nothing(packing):
    """An entry no prompt starts at may name any column as its last
    token: what the prompts of the row get does not move, and neither
    does it for a padding row beside them."""
    prefill, variables, kinds = packing
    placed = [(_prompt(11, 1), 0, 1, 0.0, 3), (_prompt(5, 2), 0, 3, 0.7, 4)]
    args = _packed_args(placed, 2)
    base = prefill(variables, *args)
    segments, positions, last = args[-1]
    moved = last.at[0, 0].set(31).at[0, 2].set(9).at[1].set(
        jnp.asarray([3, 30, 17, 8], jnp.int32))
    other = prefill(variables, *args[:-1], (segments, positions, moved))
    for prompt, row, block, _, _ in placed:
        _same(_read(other, kinds, row, block, len(prompt)),
              _read(base, kinds, row, block, len(prompt)))
