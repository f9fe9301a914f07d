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
        arg(f32, 4), arg(i32, 4))
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
                    "sliding-window layers": "mellum_tiny"}[kind]
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
