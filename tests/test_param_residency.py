"""Parameters rest on the device in the dtype the programs read them in.

A bfloat16 decoder that stores float32 (`gpt2-large` as benchmarked)
casts every Dense and Embed leaf inside its programs, and XLA rebuilds
the bfloat16 twin of the whole tree on every call.  The model declares
what it reads (`DecoderConfig.resident_dtypes`), the placement narrows
once (`param_cache.place_on_device`), and these tests hold it to the
same work: bit-equal programs, LayerNorm left alone, nothing narrowed
where a model stores what it reads, the stored bytes untouched.
"""

import json
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kfserving_tpu.engine import param_cache
from kfserving_tpu.engine.generator import GenerationEngine
from kfserving_tpu.models import create_model, init_params
from kfserving_tpu.models.decoder import DecoderLM, decoder_tiny

MAX_SEQ = 64
BS = 16
SLOTS = 4
STEPS = 16
SIZES = dict(num_layers=2, hidden_size=64, num_heads=2,
             intermediate_size=128, max_seq=MAX_SEQ, vocab_size=96)
NORMS = ("attn_norm", "mlp_norm", "final_norm")


def _names(path):
    return [key.key for key in path]


def _bytes_of(tree):
    return [np.asarray(x).tobytes() for x in jax.tree.leaves(tree)]


@pytest.fixture(scope="module")
def mixed():
    """(module, a twin whose config declares nothing, float32 variables)
    of a decoder that computes in bfloat16."""
    cfg = decoder_tiny(dtype=jnp.bfloat16, **SIZES)
    silent = decoder_tiny(dtype=jnp.bfloat16, **SIZES)
    silent.resident_dtypes = None
    module = DecoderLM(cfg)
    variables = module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    assert {x.dtype.name for x in jax.tree.leaves(variables)} == {"float32"}
    return module, DecoderLM(silent), variables


def _engine(module, variables, **kw):
    kw.setdefault("max_slots", SLOTS)
    return GenerationEngine(module, variables, max_seq=MAX_SEQ,
                            prefill_buckets=[BS, MAX_SEQ], block_size=BS,
                            steps_per_call=STEPS, **kw)


def _as(kind, variables):
    if kind == "host":
        return jax.tree.map(np.asarray, variables)
    return jax.device_put(jax.tree.map(np.asarray, variables))


# -- what rests where ---------------------------------------------------------
@pytest.mark.parametrize("kind", ["host", "device"])
def test_dense_and_embed_leaves_rest_in_the_compute_dtype(mixed, kind):
    module, _, variables = mixed
    given = _as(kind, variables)
    eng = _engine(module, given)
    try:
        flat = jax.tree_util.tree_leaves_with_path(eng.variables)
        assert len(flat) == len(jax.tree.leaves(variables))
        norms = [leaf for path, leaf in flat
                 if set(_names(path)) & set(NORMS)]
        others = [leaf for path, leaf in flat
                  if not set(_names(path)) & set(NORMS)]
        assert len(norms) == 2 * (2 * SIZES["num_layers"] + 1)
        assert {x.dtype.name for x in norms} == {"float32"}
        # kernels and biases of six projections a layer, wte, wpe
        assert len(others) == 12 * SIZES["num_layers"] + 2
        assert {x.dtype.name for x in others} == {"bfloat16"}
        for leaf in jax.tree.leaves(eng.variables):
            assert isinstance(leaf, jax.Array)
        narrowed = sum(x.size for x in others)
        stats = eng.stats()
        assert stats["params_narrowed_bytes"] == 2 * narrowed
        assert (stats["params_resident_bytes"] == eng.param_bytes()
                == param_cache.device_resident_bytes(eng.variables)
                == 4 * sum(x.size for x in norms) + 2 * narrowed)
        assert eng._param_read_bytes == eng.param_bytes()
        # the caller's tree is what it was
        assert {x.dtype.name for x in jax.tree.leaves(given)} == {"float32"}
    finally:
        eng.shutdown_nowait()


def _stores_what_it_reads(which):
    if which == "decoder_tiny":
        spec = create_model("decoder_tiny", **SIZES)
    elif which == "olmoe":
        spec = create_model("olmoe_tiny", max_seq=MAX_SEQ,
                            dtype="bfloat16", param_dtype="bfloat16")
    else:
        spec = create_model("nemotron_h_tiny", max_seq=MAX_SEQ,
                            dtype="bfloat16", param_dtype="bfloat16")
    return spec, jax.tree.map(np.asarray, init_params(spec, seed=1))


@pytest.mark.parametrize("which", ["decoder_tiny", "olmoe", "nemotron_h"])
def test_a_model_that_stores_what_it_reads_is_placed_as_stored(which):
    """The controls: float32 compute, and the expert models, whose
    float32 leaves (norms, `A_log`, `D`, router biases) are read in
    float32.  Every leaf keeps its dtype and its bytes."""
    spec, host = _stores_what_it_reads(which)
    eng = GenerationEngine(spec.module, host, max_slots=2, max_seq=MAX_SEQ,
                           block_size=BS, name=which)
    try:
        placed = jax.tree.leaves(eng.variables)
        stored = jax.tree.leaves(host)
        assert [x.dtype for x in placed] == [x.dtype for x in stored]
        if which == "nemotron_h":
            assert {x.dtype.name for x in stored} == {"float32", "bfloat16"}
        assert _bytes_of(eng.variables) == _bytes_of(host)
        assert eng.stats()["params_narrowed_bytes"] == 0
        assert (eng.stats()["params_resident_bytes"] == eng.param_bytes()
                == sum(x.nbytes for x in stored))
    finally:
        eng.shutdown_nowait()


def test_a_model_that_declares_nothing_is_placed_as_stored(mixed):
    _, silent, variables = mixed
    eng = _engine(silent, _as("host", variables))
    try:
        assert ({x.dtype.name for x in jax.tree.leaves(eng.variables)}
                == {"float32"})
        assert eng.stats()["params_narrowed_bytes"] == 0
    finally:
        eng.shutdown_nowait()


def test_only_a_wider_float_leaf_is_narrowed():
    """Stored narrower than read, stored as read, an integer leaf: all
    placed as they are.  A shape narrows as a shape."""
    tree = {"wide": np.ones((4, 4), np.float32),
            "same": np.ones(4, jnp.bfloat16),
            "narrow": np.ones(4, jnp.bfloat16),
            "ids": np.arange(4, dtype=np.int32),
            "shape": jax.ShapeDtypeStruct((8, 2), jnp.float32)}
    read = {"wide": jnp.bfloat16, "same": jnp.bfloat16,
            "narrow": jnp.float32, "ids": jnp.bfloat16,
            "shape": jnp.bfloat16}
    placed = param_cache.place_on_device(tree, dtypes=read)
    assert {k: str(v.dtype) for k, v in placed.items()} == {
        "wide": "bfloat16", "same": "bfloat16", "narrow": "bfloat16",
        "ids": "int32", "shape": "bfloat16"}
    assert isinstance(placed["shape"], jax.ShapeDtypeStruct)
    assert placed["shape"].shape == (8, 2)
    assert param_cache.narrowed(tree, placed) == (2, 16 * 2 + 16 * 2)
    assert tree["wide"].dtype == np.float32


def test_the_narrowing_rounds_to_nearest_even():
    """The placement's bytes are the program's own `convert`'s, and
    those are round to nearest, ties to even, as numpy makes them."""
    ties = np.array([0x3F808000, 0x3F818000, 0x3F807FFF, 0x3F808001,
                     0xBF808000, 0x7F7FFFFF, 0x00000000, 0x80000000,
                     0x7F800000], np.uint32).view(np.float32)
    values = np.concatenate([
        ties, np.random.default_rng(0).standard_normal(4096)
        .astype(np.float32) * 0.02])
    placed = param_cache.place_on_device(
        {"w": values}, dtypes={"w": jnp.bfloat16})["w"]
    in_program = jax.jit(lambda x: x.astype(jnp.bfloat16))(values)
    assert np.asarray(placed).tobytes() == np.asarray(in_program).tobytes()
    assert (np.asarray(placed).tobytes()
            == values.astype(jnp.bfloat16).tobytes())
    assert (np.asarray(placed)[:2].view(np.uint16).tolist()
            == [0x3F80, 0x3F82])


def test_the_wide_copy_the_placement_made_is_freed(mixed):
    """No float32 twin of a narrowed leaf stays on the device: the
    placement frees the copy it put there, and leaves a caller's own
    device arrays alone."""
    _, _, variables = mixed
    cfg = decoder_tiny(dtype=jnp.bfloat16, **SIZES)
    host = _as("host", variables)
    before = {id(x) for x in jax.live_arrays()}
    placed = param_cache.place_on_device(
        host, dtypes=cfg.resident_dtypes(host))
    new = [x for x in jax.live_arrays() if id(x) not in before]
    assert len(new) == len(jax.tree.leaves(placed))
    assert (sum(x.nbytes for x in new)
            == param_cache.device_resident_bytes(placed))
    given = _as("device", variables)
    param_cache.place_on_device(given, dtypes=cfg.resident_dtypes(given))
    assert not any(x.is_deleted() for x in jax.tree.leaves(given))


# -- the same work -------------------------------------------------------------
def _prefill_args(rows):
    rng = np.random.default_rng(7)
    ids = rng.integers(1, SIZES["vocab_size"], (rows, BS)).astype(np.int32)
    lengths = np.array([BS, 5, 11, 1][:rows], np.int32)
    return (jnp.asarray(ids), jnp.asarray(lengths),
            jnp.zeros(rows, jnp.float32), jnp.zeros(rows, jnp.int32),
            jnp.ones(rows, jnp.float32), jnp.arange(rows, dtype=jnp.int32),
            jnp.asarray(True))  # log-probabilities asked for


def _decode_call(eng):
    """One 16-step call of the engine's decode program over its own
    (empty) pool: four rows at different positions, one of them
    sampling."""
    table = np.arange(SLOTS * eng.blocks_per_slot, dtype=np.int32).reshape(
        SLOTS, eng.blocks_per_slot)
    out = eng._decode(
        eng.variables, eng._caches, jnp.asarray(table),
        jnp.asarray([3, 17, 42, 5], jnp.int32),
        jnp.asarray([0, 9, 30, 1], jnp.int32),
        jnp.full(SLOTS, eng.max_seq, jnp.int32),  # no budget ends here
        jnp.asarray([0.0, 0.0, 0.8, 0.0], jnp.float32),
        jnp.zeros(SLOTS, jnp.int32), jnp.ones(SLOTS, jnp.float32),
        jnp.arange(SLOTS, dtype=jnp.int32), jnp.asarray(True))
    toks, caches, _, _, chosen_lp, top_ids, top_lps = out
    assert toks.shape == (SLOTS, STEPS)
    return toks, chosen_lp, top_ids, top_lps, caches


@pytest.mark.parametrize("kind", ["host", "device"])
def test_programs_are_bit_identical_with_and_without_the_narrowing(
        mixed, kind):
    """The prefill program's logits, first tokens, log-probabilities and
    K/V rows, and the tokens, log-probabilities and written pool of a
    16-step decode call: the narrowed tree against the float32 tree
    that the programs convert themselves."""
    module, silent, variables = mixed
    outs = []
    for model in (module, silent):
        eng = _engine(model, _as(kind, variables))
        try:
            args = _prefill_args(SLOTS)
            logits = jax.jit(lambda v, ids, n, m=model: m.apply(
                v, ids, kv_lengths=n, logit_positions=n - 1))(
                    eng.variables, args[0], args[1])
            assert logits.dtype == jnp.float32
            outs.append(_bytes_of((logits, eng._prefill(eng.variables, *args),
                                   _decode_call(eng))))
        finally:
            eng.shutdown_nowait()
    assert outs[0] == outs[1]


async def test_served_streams_are_bit_identical(mixed):
    """Through the scheduler: prefill, insert, and 16-step decode calls
    over the paged pool give the same tokens and log-probabilities."""
    module, silent, variables = mixed
    outs = []
    for model in (module, silent):
        eng = _engine(model, _as("host", variables))
        try:
            reqs = [eng.submit(prompt, max_new_tokens=2 * STEPS + 3,
                               logprobs=3, temperature=t, seed=11)
                    for prompt, t in (([5, 9, 2, 7, 11], 0.0),
                                      (list(range(1, 20)), 0.7))]
            got = []
            for req in reqs:
                tokens = [t async for t, _ in eng.stream(req)
                          if t is not None]
                got.append((tokens, list(req.lp_chosen),
                            [list(top) for top in req.lp_top]))
        finally:
            await eng.close()
        outs.append(got)
    assert len(outs[0][0][0]) == 2 * STEPS + 3
    assert outs[0] == outs[1]


def test_the_draft_tree_follows_the_draft_models_rule(mixed):
    """A float32 target with a bfloat16 draft: the one placement narrows
    the draft's leaves by the draft module's declaration and leaves the
    target's alone."""
    draft, _, draft_vars = mixed
    target = DecoderLM(decoder_tiny(**SIZES))
    variables = target.init(jax.random.PRNGKey(1),
                            jnp.zeros((1, 8), jnp.int32))
    eng = _engine(target, _as("host", variables), speculative={
        "tokens": 2, "draft_module": draft,
        "draft_variables": _as("host", draft_vars), "draft_window": 8})
    try:
        assert ({x.dtype.name for x in jax.tree.leaves(eng.variables)}
                == {"float32"})
        assert ({x.dtype.name for x in jax.tree.leaves(eng.draft_variables)}
                == {"float32", "bfloat16"})
        stats = eng.stats()
        assert 0 < stats["params_narrowed_bytes"] < eng.draft_param_bytes() * 2
        assert (stats["params_resident_bytes"]
                == eng.param_bytes() + eng.draft_param_bytes())
        assert eng.draft_param_bytes() < eng.param_bytes()
    finally:
        eng.shutdown_nowait()


# -- shardings and shapes -------------------------------------------------------
@pytest.mark.parametrize("arrives", ["sharded", "host"])
def test_under_a_mesh_a_leaf_is_narrowed_where_it_lies(mixed, arrives):
    """A `shard_params` leaf keeps its sharding, a host leaf the
    replicated one it is placed with."""
    from jax.sharding import NamedSharding, PartitionSpec

    from kfserving_tpu.parallel import build_mesh, shard_params
    from kfserving_tpu.parallel.mesh import MeshConfig

    module, _, variables = mixed
    mesh = build_mesh(MeshConfig(tp=2))
    given = _as("host", variables)["params"]
    if arrives == "sharded":
        given = shard_params(given, mesh)
        want = [leaf.sharding for leaf in jax.tree.leaves(given)]
        assert any(any(axis is not None for axis in sharding.spec)
                   for sharding in want), "nothing partitioned: vacuous test"
    else:
        want = [NamedSharding(mesh, PartitionSpec())] * len(
            jax.tree.leaves(given))
    eng = _engine(module, {"params": given}, mesh=mesh, max_slots=2)
    try:
        kept = jax.tree.leaves(eng.variables["params"])
        assert {x.dtype.name for x in kept} == {"float32", "bfloat16"}
        for got, stored, sharding in zip(kept, jax.tree.leaves(given), want):
            assert got.sharding == sharding
            assert got.shape == stored.shape
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(stored).astype(got.dtype))
        assert eng.stats()["params_resident_bytes"] == eng.param_bytes()
    finally:
        eng.shutdown_nowait()


def test_a_tree_of_shapes_is_narrowed_as_shapes(mixed):
    """`tests/test_chip_compile.py` builds engines from shapes and
    compiles from `engine.variables`."""
    from jax.sharding import SingleDeviceSharding

    module, _, variables = mixed
    one = SingleDeviceSharding(jax.devices()[0])
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one), variables)
    eng = _engine(module, shapes)
    try:
        for (path, got), want in zip(
                jax.tree_util.tree_leaves_with_path(eng.variables),
                jax.tree.leaves(shapes)):
            assert isinstance(got, jax.ShapeDtypeStruct)
            assert got.shape == want.shape and got.sharding == one
            assert got.dtype == (jnp.float32 if set(_names(path)) & set(NORMS)
                                 else jnp.bfloat16)
        assert eng.stats()["params_resident_bytes"] == 0  # nothing placed
        assert eng.stats()["params_narrowed_bytes"] > 0
    finally:
        eng.shutdown_nowait()


# -- the stored bytes ------------------------------------------------------------
def test_the_stored_entry_stays_float32(tmp_path):
    """The cache entry, its key and the mapped tree an engine was built
    from are what they were: the reference reads those bytes."""
    kwargs = dict(SIZES)  # "decoder": bfloat16 compute, float32 stored
    spec = create_model("decoder", **kwargs)
    key = param_cache.content_key("decoder", kwargs)
    first, source = param_cache.load_or_materialize(
        "decoder", kwargs, spec, str(tmp_path))
    assert source == "init"
    before = _bytes_of(param_cache.load(key))
    eng = _engine(spec.module, first)
    try:
        assert eng.stats()["params_narrowed_bytes"] > 0
        again, source = param_cache.load_or_materialize(
            "decoder", kwargs, spec, str(tmp_path))
        assert source == "mmap"
        for tree in (first, again, param_cache.load(key)):
            leaves = jax.tree.leaves(tree)
            assert {x.dtype.name for x in leaves} == {"float32"}
            assert all(isinstance(x, np.ndarray) for x in leaves)
            assert _bytes_of(tree) == before
        assert param_cache.content_key("decoder", kwargs) == key
    finally:
        eng.shutdown_nowait()


# -- the counter that says it engaged ---------------------------------------------
@pytest.mark.parametrize("architecture, narrows",
                         [("decoder", True), ("decoder_tiny", False)])
async def test_narrowed_bytes_on_metrics_and_in_the_log(
        tmp_path, caplog, architecture, narrows):
    import aiohttp

    from kfserving_tpu.predictors.llm import GenerativeModel
    from kfserving_tpu.server.app import ModelServer

    (tmp_path / "config.json").write_text(json.dumps({
        "architecture": architecture, "arch_kwargs": SIZES,
        "max_slots": 2, "max_seq": MAX_SEQ, "prefill_buckets": [16, 64],
        "max_new_tokens": 4, "tokenizer": "byte"}))
    model = GenerativeModel("gen", str(tmp_path))
    with caplog.at_level(logging.INFO, "kfserving_tpu.engine.generator"):
        model.load()
    server = ModelServer(http_port=0)
    await server.start_async([model], host="127.0.0.1")
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(
                    f"http://127.0.0.1:{server.http_port}/metrics") as r:
                text = await r.text()
        stats = model.engine.stats()
        leaves = jax.tree.leaves(model.engine.variables)
        small = [x for x in leaves if x.dtype == jnp.bfloat16]
        assert bool(small) == narrows
        assert stats["params_narrowed_bytes"] == 2 * sum(
            x.size for x in small)
        lines = [ln for ln in text.splitlines()
                 if "params_narrowed_bytes" in ln
                 and not ln.startswith("#")]
        assert len(lines) == 1, lines  # not again as kfserving_tpu_engine_*
        name, value = lines[0].rsplit(" ", 1)
        assert name == ('kfserving_tpu_generator_params_narrowed_bytes'
                        '{model="gen"}')
        assert float(value) == stats["params_narrowed_bytes"]
        said = [r.getMessage() for r in caplog.records
                if "leaves narrowed" in r.getMessage()]
        assert said == [
            f"gen: parameters resident, {stats['params_resident_bytes']} "
            f"bytes; {len(small)} leaves narrowed to the dtype they are "
            f"read in, {stats['params_narrowed_bytes']} bytes saved"]
        out = await model.predict({"instances": ["resident"]})
        assert out["predictions"][0]["token_count"] > 0
    finally:
        await server.stop_async()
