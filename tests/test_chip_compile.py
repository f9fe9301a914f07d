"""What can be asked about the chip without the chip.

- The Pallas kernels of the serving path compile for a *described* v5e at
  the shapes `chip_smoke.py` serves — alone, and under a four-device mesh
  through the dispatchers' `shard_map` wrappers.  A compile is not a chip
  run; it catches what interpret mode cannot (tiling, VMEM, partitioning).
- `chip_smoke.py`'s phases pass at toy size on the CPU when handed toy
  configs, and its command line — fixed to "tpu" — fails here.
- The pieces the smoke leans on: no XLA fallback behind a chosen kernel,
  the sharded wrappers' numerics, one set of chips per replica.
"""

import functools
import json
import math
import os
import re
import subprocess
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY_PREDICT = {
    "architecture": "mlp",
    "arch_kwargs": {"input_dim": 64, "features": [128], "num_classes": 10},
    "max_batch_size": 16, "batch_buckets": [4, 16], "pipeline_depth": 3,
    "max_latency_ms": 15.0, "warmup": True, "input_dtype": "uint8",
    "scale": 1.0 / 255.0, "output": "logits",
}
TOY_DECODER = {
    "architecture": "decoder_tiny",
    "arch_kwargs": {"num_layers": 2, "hidden_size": 64, "num_heads": 4,
                    "intermediate_size": 128, "max_seq": 256},
    "max_slots": 4, "max_seq": 256, "prefill_buckets": [64, 256],
    "block_size": 32, "cache_blocks": 24, "steps_per_call": 2,
    "tokenizer": "byte",
}
TOY_BATCH = np.random.default_rng(0).integers(
    0, 256, size=(4, 64)).astype(np.uint8)


# -- compiles for the described chip -------------------------------------------
@pytest.fixture(scope="module")
def v5e():
    """A described (not attached) v5e 2x2, with the persistent compile
    cache off: such a compile is written to it but cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {exc}")
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", saved)
    compilation_cache.reset_cache()


def _tp4(devices) -> Mesh:
    return Mesh(np.array(devices[:4]).reshape(1, 1, 4), ("dp", "sp", "tp"))


def _paged_args(slots, heads, head_dim, blocks, block_size, blocks_per_slot):
    """[(shape, dtype, spec under a tp mesh), ...] of the paged decode
    kernel's operands: q on heads, the flat pools on H*D."""
    from kfserving_tpu.ops.paged_attention import pool_shape

    bf16, i32 = jnp.bfloat16, jnp.int32
    pool = (pool_shape(blocks, block_size, heads, head_dim), bf16,
            P(None, None, "tp"))
    return [((slots, 1, heads, head_dim), bf16, P(None, None, "tp", None)),
            pool, pool, ((slots, blocks_per_slot), i32, P()),
            ((slots,), i32, P())]


def _compile(fn, args, v5e, sharded: bool, donate=()):
    """`fn` compiled for one described chip, or for four under a tp mesh."""
    from jax.sharding import SingleDeviceSharding

    fn = jax.jit(fn, donate_argnums=donate)
    if not sharded:
        one = SingleDeviceSharding(v5e.devices[0])
        return fn.lower(*[
            jax.ShapeDtypeStruct(shape, dtype, sharding=one)
            for shape, dtype, _ in args]).compile()
    mesh = _tp4(v5e.devices)
    structs = [jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))
               for shape, dtype, spec in args]
    with jax.set_mesh(mesh):
        return fn.lower(*structs).compile()


def _pool_copies(compiled, pool_dims) -> list:
    """The copies a compiled program makes of an array of the pool's
    dimensions: a `copy` into the layout a kernel asked for, or XLA's
    memory-space assignment moving a whole pool through VMEM and back
    (`copy-start`) around an operation of its own on it."""
    dims = ",".join(str(n) for n in pool_dims)
    return [line.strip()[:160] for line in compiled.as_text().splitlines()
            if f"bf16[{dims}]" in line
            and any(op in line for op in (" copy(", " copy-start("))]


def _mosaic_calls(compiled, kernel: str) -> list:
    """Names of the compiled program's Mosaic calls that carry `kernel`,
    the jitted function's name: the profiler names a device operation by
    its instruction, and `chipbench` finds the kernel's time by it."""
    return [line.split("=")[0].strip().removeprefix("ROOT ")
            for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            and kernel in line.split("=")[0]]


def _walk_operations(compiled) -> list:
    """The XLA operations a compiled program runs for `paged_walk`: what
    is traced under `paged_attention_tpu` and is not the kernel."""
    return [line.strip()[:120] for line in compiled.as_text().splitlines()
            if "jit(paged_attention_tpu)/" in line
            and "/pallas_call" not in line
            and any(op in line for op in (" fusion(", " reduce("))]


def _kernel_case(kernel: str, sharded: bool = False):
    """(function, [(shape, dtype, spec under a tp mesh), ...]) of one kernel
    at the shapes the smoke's generate phase serves.  The functions are
    the ones the dispatchers call: bare kernel without a mesh, `shard_map`
    over heads inside one."""
    from kfserving_tpu.ops import attention, paged_attention

    s = chip_smoke.kernel_shapes(chip_smoke.DECODER)
    heads = P(None, None, "tp", None)
    bf16, i32 = jnp.bfloat16, jnp.int32
    if kernel == "paged":
        # The smoke's 12 heads of 64 leave a tp=4 shard 192 lanes: the
        # dispatcher gives those to XLA (`_kernels_serve`), and a copy of
        # a block that is not whole lanes is refused.  Under the mesh
        # the kernel is compiled at 16 heads, 256 lanes a shard.
        return paged_attention.paged_attention_sharded, _paged_args(
            s["slots"], 16 if sharded else s["heads"], s["head_dim"],
            s["blocks"], s["block_size"], s["blocks_per_slot"])
    qkv = ((1, s["prefill"], s["heads"], s["head_dim"]), bf16, heads)
    if kernel == "flash_causal":
        return (lambda q, k, v: attention._flash(q, k, v, True, None),
                [qkv, qkv, qkv])
    return (lambda q, k, v, n: attention._flash(q, k, v, False, n),
            [qkv, qkv, qkv, ((1,), i32, P())])


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["one-chip", "tp4-mesh"])
@pytest.mark.parametrize("kernel",
                         ["paged", "flash_causal", "flash_kv_lengths"])
def test_kernel_compiles_for_described_v5e(v5e, kernel, sharded):
    fn, args = _kernel_case(kernel, sharded)
    assert "tpu_custom_call" in _compile(fn, args, v5e, sharded).as_text()


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["one-chip", "tp4-mesh"])
@pytest.mark.parametrize("heads, blocks, blocks_per_slot", [
    ((20, 64), 144, 8),     # gpt2-large: padded a [.., H, D] tile 3.2 times
    ((16, 128), 288, 16),   # OLMoE: whole tiles either way
    ((4, 128), 288, 16),    # GQA-like: a quarter of a [.., H, D] tile
], ids=["20x64", "16x128", "4x128"])
def test_paged_kernel_reads_the_flat_pool_in_place(
        v5e, monkeypatch, heads, blocks, blocks_per_slot, sharded):
    """Whatever the head geometry, the kernel takes the pool as it is
    stored: no `copy` of a pool-shaped operand in front of it and no
    temporaries.  Where one heads shard's H*D is not whole lanes
    (20 x 64 over four chips: 320) the dispatcher keeps the kernel out
    and the XLA formulation serves."""
    from kfserving_tpu.ops import attention, paged_attention

    args = _paged_args(24, *heads, blocks, 128, blocks_per_slot)
    nb, bs, hd = args[1][0]
    shard = hd // 4 if sharded else hd
    monkeypatch.setattr(attention, "_tpu_backend", lambda: True)
    compiled = _compile(paged_attention.paged_attention, args, v5e, sharded)
    if shard % 128:
        assert "tpu_custom_call" not in compiled.as_text()
        return
    assert len(_mosaic_calls(compiled, "paged_attention_tpu")) == 1
    assert _pool_copies(compiled, (nb, bs, shard)) == []
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["one-chip", "tp4-mesh"])
def test_paged_write_kernel_updates_the_pool_in_place(v5e, sharded):
    """A decode step's write at OLMoE's 16 x 128 heads (whole lanes on
    one chip and on four): a Mosaic call whose outputs are its pool
    operands, no copy of a pool, no temporaries."""
    from kfserving_tpu.ops import paged_attention

    _, pool, _, _, rows = _paged_args(24, 16, 128, 288, 128, 16)
    step = ((24, 16, 128), jnp.bfloat16, P(None, "tp", None))
    compiled = _compile(paged_attention.paged_write_sharded,
                        [pool, pool, step, step, rows, rows], v5e, sharded,
                        donate=(0, 1))  # as the engine's programs do
    assert "tpu_custom_call" in compiled.as_text()
    nb, bs, hd = pool[0]
    assert _pool_copies(compiled, (nb, bs, hd // 4 if sharded else hd)) == []
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes == 0
    assert memory.alias_size_in_bytes >= 2 * nb * bs * hd * 2 // (
        4 if sharded else 1)


# -- OLMoE at the benchmarked configuration's shapes ----------------------------
def _olmoe_serving() -> dict:
    with open(os.path.join(REPO, "chipbench", "configs",
                           "olmoe-1b-7b-8l.json")) as f:
        return json.load(f)["serving"]


def _wide_operations(compiled, scope: str, rows: int):
    """The program's operations traced under `scope` whose result is a
    matrix of `rows` rows (a vector of as many indices is not one)."""
    wide = re.compile(r"= \(?\w+\[%d,\d" % rows)
    return [line.strip() for line in compiled.as_text().splitlines()
            if f"/{scope}/" in line and wide.search(line)]


def _assert_grouped_work_is_the_kernels(compiled, layers: int, pairs: int):
    """Every expert layer of a prefill program goes through the grouped
    kernel, whose walk ends at the last real row; nothing else under
    `moe.experts` or `moe.combine` runs over the `pairs` offered rows (no
    grouped matmul of XLA's, no activation, no un-sort), and no loop is
    left to XLA (PERF.md, PR 32: a `while` in this program keeps every
    Mamba layer's projection alive to the end, 0.6 GB)."""
    text = compiled.as_text()
    assert "ragged-dot" not in text
    assert " while(" not in text
    kernels = _wide_operations(compiled, "moe.experts", pairs)
    assert len(kernels) == layers
    assert all("moe_experts_grouped" in line and "tpu_custom_call" in line
               for line in kernels)
    # the sorted rows, which the result overwrites, are in HBM (`S(1)` on
    # a shape is VMEM), as they were when this shape earned its place in
    # `moe.GROUPED_KERNEL_PROVEN`
    assert not any("S(1)}" in line.split(" custom-call(")[0]
                   for line in kernels)
    assert _wide_operations(compiled, "moe.combine", pairs) == []


OLMOE_4_ROWS = (4 * 1024 * 8, 2048, 1024, 3)


@pytest.mark.parametrize("way", ["served", "kernel"])
def test_grouped_expert_layer_compiles_for_described_v5e(v5e, monkeypatch,
                                                         way):
    """The prefill path of ops/moe.py at 4 rows x 1024 tokens of OLMoE's
    widths.  As served, XLA's own grouped matmuls, three a layer, routed
    rows only (the shape waits for its record on the chip:
    `moe.GROUPED_KERNEL_PROVEN`), and the sum back with no operation over
    the T x k rows.  With the shape listed, what it would run then: one
    Mosaic call over the sorted rows, three matrices an expert whole in
    VMEM, and XLA left with no arithmetic to speak of."""
    from jax.sharding import SingleDeviceSharding

    from kfserving_tpu.ops import attention, moe

    monkeypatch.setattr(attention, "_tpu_backend", lambda: True)
    if way == "kernel":
        monkeypatch.setattr(moe, "GROUPED_KERNEL_PROVEN",
                            moe.GROUPED_KERNEL_PROVEN | {OLMOE_4_ROWS})
    kw = _olmoe_serving()["arch_kwargs"]
    e, h, f = kw["num_experts"], kw["hidden_size"], kw["intermediate_size"]
    k, tokens = kw["experts_per_token"], 4 * 1024
    one = SingleDeviceSharding(v5e.devices[0])

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    # a function of its own: the gate is read when a trace is made
    compiled = jax.jit(lambda *args: moe.routed_experts(*args)).lower(
        arg((tokens, h), jnp.bfloat16), arg((e, h, f), jnp.bfloat16),
        arg((e, h, f), jnp.bfloat16), arg((e, f, h), jnp.bfloat16),
        arg((tokens, k), jnp.float32), arg((tokens, k), jnp.int32),
        arg((tokens,), jnp.bool_)).compile()
    routed = 2 * 3 * tokens * k * h * f
    flops = compiled.cost_analysis()["flops"]
    assert _wide_operations(compiled, "moe.combine", tokens * k) == []
    if way == "served":
        assert compiled.as_text().count("ragged-dot") >= 3
        assert not _mosaic_calls(compiled, "moe_experts_grouped")
        assert routed <= flops < 1.5 * routed
        return
    assert len(_mosaic_calls(compiled, "moe_experts_grouped")) == 1
    assert "ragged-dot" not in compiled.as_text()
    assert flops < 0.01 * routed
    # the sorted rows (the result takes their place) and k slabs of [T, H]
    assert compiled.memory_analysis().temp_size_in_bytes < 2.2 * (
        tokens * k * h * 2)


@pytest.mark.parametrize("tokens", [24, 256])
def test_touched_experts_kernel_compiles_for_described_v5e(v5e, tokens):
    """The decode path of ops/moe.py at the configuration's widths: the
    Pallas kernel that streams each touched expert's three 4-MiB
    matrices through VMEM, at a decode wave's 24 rows and at the most
    tokens it serves."""
    from jax.sharding import SingleDeviceSharding

    from kfserving_tpu.ops import moe

    kw = _olmoe_serving()["arch_kwargs"]
    e, h, f = kw["num_experts"], kw["hidden_size"], kw["intermediate_size"]
    k = kw["experts_per_token"]
    one = SingleDeviceSharding(v5e.devices[0])

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = jax.jit(moe.experts_touched).lower(
        arg((tokens, h), jnp.bfloat16), arg((e, h, f), jnp.bfloat16),
        arg((e, h, f), jnp.bfloat16), arg((e, f, h), jnp.bfloat16),
        arg((tokens, k), jnp.float32), arg((tokens, k), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert "ragged-dot" not in compiled.as_text()


def test_held_plain_experts_kernel_compiles_for_described_v5e(v5e):
    """`nemotron-3-nano-16l-ep2`'s expert layer at a decode wave's 64 rows:
    two matrices an expert of width 1856 stored as 1920 (15 lane tiles),
    64 of the router's 128 experts held, through the same Pallas kernel;
    the matrices are read where they are, in no other layout."""
    from jax.sharding import SingleDeviceSharding

    from kfserving_tpu.models.nemotron_h import NemotronHConfig
    from kfserving_tpu.ops import moe

    cfg = NemotronHConfig(**_nemotron_serving()["arch_kwargs"])
    e, h, f = cfg.num_experts, cfg.hidden_size, cfg.expert_width_stored
    assert (e, h, f, cfg.intermediate_size) == (64, 2688, 1920, 1856)
    one = SingleDeviceSharding(v5e.devices[0])

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = jax.jit(
        lambda x, up, down, w, chosen: moe.experts_touched(
            x, None, up, down, w, chosen, None, cfg.experts_first)).lower(
        arg((64, h), jnp.bfloat16), arg((e, h, f), jnp.bfloat16),
        arg((e, f, h), jnp.bfloat16), arg((64, 6), jnp.float32),
        arg((64, 6), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("cell, window", [
    ("nemotron", None), ("mellum", None), ("mellum", 1024)],
    ids=["2x128-16q", "4x128-8q", "4x128-8q-ring"])
def test_grouped_query_paged_kernel_compiles_for_described_v5e(v5e, cell,
                                                               window):
    """The paged decode kernel on the two narrow pools, 2 x 128 K/V
    lanes with 16 query heads on each KV head and 4 x 128 with 8, 64
    rows over the configuration's pool and table (a ring of 9 columns
    for a window layer): one Mosaic call, the pools not copied.  A loop
    iteration takes 4 blocks of a row here, and the slots that hold them
    (3 x 4 blocks of K and of V, 1.5 and 3 MiB) are the kernel's VMEM:
    the program's temporaries in HBM are the walk's lists and no more."""
    from kfserving_tpu.ops import paged_attention

    serving = _nemotron_serving() if cell == "nemotron" else \
        _mellum_serving()
    kw = serving["arch_kwargs"]
    columns = serving["max_seq"] // serving["block_size"]
    blocks = serving["cache_blocks"]
    if window is not None:
        columns = paged_attention.ring_blocks(window, serving["block_size"])
        blocks = serving["window_cache_blocks"]
    args = _paged_args(serving["max_slots"], kw["num_kv_heads"],
                       kw["head_dim"], blocks, serving["block_size"],
                       columns)
    args[0] = ((serving["max_slots"], 1, kw["num_heads"], kw["head_dim"]),
               jnp.bfloat16, P())
    assert paged_attention.blocks_per_iteration(
        serving["block_size"], args[1][0][2], jnp.bfloat16, columns) == 4
    compiled = _compile(
        functools.partial(paged_attention.paged_attention_sharded,
                          window=window), args, v5e, sharded=False)
    assert len(_mosaic_calls(compiled, "paged_attention_tpu")) == 1
    assert _pool_copies(compiled, args[1][0]) == []
    memory = compiled.memory_analysis()
    print(f"{cell} paged kernel, window {window}: {memory}")
    assert memory.temp_size_in_bytes < 2**18, memory


def _decode_program(v5e, monkeypatch, serving: dict):
    """(the 16-step decode program of a benchmarked configuration's
    serving settings with the Pallas paged kernel, as the chip's compiler
    sees it, compiled from the parameters as the engine keeps them
    resident; the engine's pool shape; the parameter shapes as stored)."""
    from jax.sharding import SingleDeviceSharding

    from kfserving_tpu.engine.generator import GenerationEngine
    from kfserving_tpu.models import create_model
    from kfserving_tpu.ops import attention

    # The dispatchers ask the attached backend, which is the CPU here.
    monkeypatch.setattr(attention, "_tpu_backend", lambda: True)
    spec = create_model(serving["architecture"], **serving["arch_kwargs"])
    one = SingleDeviceSharding(v5e.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    shapes = jax.eval_shape(
        lambda: spec.module.init(jax.random.PRNGKey(0), spec.example))
    engine = GenerationEngine(
        spec.module, shapes, max_slots=serving["max_slots"],
        max_seq=serving["max_seq"],
        prefill_buckets=serving["prefill_buckets"],
        block_size=serving["block_size"],
        cache_blocks=serving["cache_blocks"],
        window_cache_blocks=serving.get("window_cache_blocks"),
        steps_per_call=serving["steps_per_call"])
    try:
        s = serving["max_slots"]

        def arg(dtype, *shape):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

        i32, f32 = jnp.int32, jnp.float32
        table = arg(i32, s, engine.blocks_per_slot)
        if engine.window_blocks_per_slot:  # and the rings' beside it
            table = (table, arg(i32, s, engine.window_blocks_per_slot))
        compiled = engine._decode.lower(
            on_chip(engine.variables), on_chip(engine._caches),
            table, arg(i32, s), arg(i32, s), arg(i32, s),
            arg(f32, s), arg(i32, s), arg(f32, s), arg(i32, s),
            arg(jnp.bool_)).compile()
        return compiled, engine._cache_shape, shapes
    finally:
        engine.shutdown_nowait()


_PREFILL_PROGRAMS = {}


def _prefill_program(v5e, monkeypatch, serving: dict, rows: int,
                     a_prompt_a_row: bool = False):
    """The (rows, largest bucket) prefill program of a configuration's
    serving settings, as the chip's compiler sees it: in the form the
    engine dispatches it (a row carrying as many prompts as its blocks
    hold where `programs.packs_prompts` says so), or with
    `a_prompt_a_row` in the form that lays one prompt in a row whatever
    the model: the program of the engine before rows were packed."""
    from jax.sharding import SingleDeviceSharding

    from kfserving_tpu.engine.generator import GenerationEngine
    from kfserving_tpu.models import create_model
    from kfserving_tpu.ops import attention, moe

    # One compile a program and a run of this file: the packed form is
    # asked for by the case that sizes it and by the one that holds it
    # against a prompt a row.
    key = (json.dumps(serving, sort_keys=True), rows, a_prompt_a_row,
           moe.GROUPED_KERNEL_PROVEN)
    if key in _PREFILL_PROGRAMS:
        return _PREFILL_PROGRAMS[key]
    monkeypatch.setattr(attention, "_tpu_backend", lambda: True)
    spec = create_model(serving["architecture"], **serving["arch_kwargs"])
    one = SingleDeviceSharding(v5e.devices[0])
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        jax.eval_shape(
            lambda: spec.module.init(jax.random.PRNGKey(0), spec.example)))
    engine = GenerationEngine(
        spec.module, shapes, max_slots=serving["max_slots"],
        max_seq=serving["max_seq"],
        prefill_buckets=serving["prefill_buckets"],
        block_size=serving["block_size"],
        cache_blocks=serving["cache_blocks"],
        window_cache_blocks=serving.get("window_cache_blocks"),
        steps_per_call=serving["steps_per_call"])
    try:
        def arg(dtype, *shape):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

        i32, f32 = jnp.int32, jnp.float32
        bucket = max(serving["prefill_buckets"])
        per_row = 1 if a_prompt_a_row else engine._row_entries[bucket]
        entries = rows * per_row
        packed = () if per_row == 1 else ((
            arg(i32, rows, bucket), arg(i32, rows, bucket),
            arg(i32, rows, per_row)),)
        compiled = _PREFILL_PROGRAMS[key] = engine._prefill.lower(
            engine.variables, arg(i32, rows, bucket),
            arg(i32, entries), arg(f32, entries), arg(i32, entries),
            arg(f32, entries), arg(i32, entries), arg(jnp.bool_),
            *packed).compile()
        return compiled
    finally:
        engine.shutdown_nowait()


def _program_bytes(memory) -> int:
    return (memory.argument_size_in_bytes + memory.output_size_in_bytes
            - memory.alias_size_in_bytes + memory.temp_size_in_bytes)


def test_olmoe_decode_program_fits_the_described_v5e(v5e, monkeypatch):
    """The 16-step decode program of `olmoe-1b-7b-8l` (24 slots, 288
    blocks of 128, bfloat16 parameters) with the Pallas paged kernel, as
    the chip's compiler sees it: what it needs beside its arguments, and
    that arguments, outputs and temporaries fit 15.75 GiB."""
    compiled, _, shapes = _decode_program(v5e, monkeypatch, _olmoe_serving())
    assert {x.dtype.name for x in jax.tree.leaves(shapes)} == {"bfloat16"}
    assert len(_mosaic_calls(compiled, "paged_attention_tpu")) == 8
    assert len(_walk_operations(compiled)) < 8  # a step's, not a layer's
    memory = compiled.memory_analysis()
    print(f"olmoe-1b-7b-8l decode program: {memory}")
    assert 9.5e9 < memory.argument_size_in_bytes < 9.6e9  # 7.13 + 2.42
    assert memory.temp_size_in_bytes < 0.03e9, memory
    assert _program_bytes(memory) < 15.75 * 2**30, memory


def _nemotron_serving() -> dict:
    with open(os.path.join(REPO, "chipbench", "configs",
                           "nemotron-3-nano-16l-ep2.json")) as f:
        return json.load(f)["serving"]


def test_nemotron_decode_program_fits_the_described_v5e(v5e, monkeypatch):
    """The 16-step decode program of `nemotron-3-nano-16l-ep2` (64 slots;
    7 Mamba, 7 expert and 2 attention layers; experts 0-63 of 128): its
    arguments are the parameters, the per-slot state and the K/V pool;
    the two attention layers read the pool through the Pallas kernel
    (16 query heads on each of 2 KV heads), the seven expert layers read
    their touched experts through `moe_experts_touched`, and no expert
    matrix is copied into another layout on the way."""
    compiled, pool, shapes = _decode_program(v5e, monkeypatch,
                                             _nemotron_serving())
    assert pool == (768, 128, 256)
    assert len(_mosaic_calls(compiled, "paged_attention_tpu")) == 2
    assert len(_mosaic_calls(compiled, "paged_write_tpu")) == 2
    # one walk a step for both attention layers (7 operations; 14 if
    # each layer listed its own chunks)
    assert len(_walk_operations(compiled)) < 10
    assert compiled.as_text().count("moe_experts_touched") >= 7
    assert "ragged-dot" not in compiled.as_text()
    assert [line for line in compiled.as_text().splitlines()
            if " copy(" in line and "bf16[64,2688,1920]" in line] == []
    memory = compiled.memory_analysis()
    print(f"nemotron-3-nano-16l-ep2 decode program: {memory}")
    stored = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert 11.5e9 < stored < 11.7e9                 # 5.79 B, bfloat16
    # ... + 0.96 GB of state + 0.20 GB of pool
    assert 12.7e9 < memory.argument_size_in_bytes < 12.9e9
    assert memory.temp_size_in_bytes < 0.3e9, memory
    assert _program_bytes(memory) < 15.75 * 2**30, memory


def test_nemotron_prefill_program_fits_the_described_v5e(v5e, monkeypatch):
    """Its (8, 1024) prefill, the most one dispatch carries
    (`prefill_rows` 8): parameters, temporaries (the 10304-wide Mamba
    projection, the chunked scan's float32 blocks, 49152 sorted rows) and
    outputs fit beside the 1.16 GB of state and pool that the program
    does not see; the seven expert layers go through the grouped
    kernel."""
    serving = _nemotron_serving()
    compiled = _prefill_program(v5e, monkeypatch, serving,
                                serving["prefill_rows"])
    _assert_grouped_work_is_the_kernels(compiled, layers=7, pairs=8192 * 6)
    memory = compiled.memory_analysis()
    print(f"nemotron-3-nano-16l-ep2 (8, 1024) prefill program: {memory}")
    # 1.88 GB at PR 31 and now: the attention layers' scores
    assert memory.temp_size_in_bytes < 1.9e9, memory
    assert _program_bytes(memory) + 1.16e9 < 15.75 * 2**30, memory


def test_a_dispatch_with_no_record_on_the_chip_takes_no_kernel(v5e,
                                                               monkeypatch):
    """The (2, 1024) Nemotron prefill is not in
    `moe.GROUPED_KERNEL_PROVEN`: with the kernel in it (the chip's
    compiler keeps its 66 MB of sorted rows in VMEM above the kernel's
    own 66 MB) it stopped the chip once in some hundreds of dispatches
    (PERF.md, PR 32).  It goes by XLA's grouped matmuls, two a layer; the
    sum back is the k gathers all the same, and no loop."""
    compiled = _prefill_program(v5e, monkeypatch, _nemotron_serving(), 2)
    text = compiled.as_text()
    assert "moe_experts_grouped" not in text
    assert text.count("ragged-dot") >= 14
    assert " while(" not in text
    assert _wide_operations(compiled, "moe.combine", 2048 * 6) == []


@pytest.mark.parametrize("way", ["served", "kernel"])
def test_olmoe_prefill_program_fits_the_described_v5e(v5e, monkeypatch,
                                                      way):
    """`olmoe-1b-7b-8l`'s (4, 1024) prefill, the most rows its one bucket
    was warmed for that the experts' temporaries decide.  As served (the
    shape is not in `moe.GROUPED_KERNEL_PROVEN` yet) eight expert layers
    of XLA's grouped matmuls with the sum back that has no operation
    over the 32768 rows; with the shape listed, eight grouped kernels and
    nothing else over them, and less beside the parameters than the
    0.373 GB of PR 31."""
    from kfserving_tpu.ops import moe

    if way == "kernel":
        monkeypatch.setattr(moe, "GROUPED_KERNEL_PROVEN",
                            moe.GROUPED_KERNEL_PROVEN | {OLMOE_4_ROWS})
    compiled = _prefill_program(v5e, monkeypatch, _olmoe_serving(), 4)
    if way == "kernel":
        _assert_grouped_work_is_the_kernels(compiled, layers=8,
                                            pairs=4096 * 8)
    else:
        text = compiled.as_text()
        assert "moe_experts_grouped" not in text
        assert text.count("ragged-dot") >= 24
        assert " while(" not in text
        assert _wide_operations(compiled, "moe.combine", 4096 * 8) == []
    memory = compiled.memory_analysis()
    print(f"olmoe-1b-7b-8l (4, 1024) prefill program, {way}: {memory}")
    # PR 31: 0.373 GB.  The sum back's k gathers of [T, H] are alive
    # together and XLA's grouped matmuls keep their float32 activations:
    # 0.442; the kernel keeps neither
    assert memory.temp_size_in_bytes < (
        0.373e9 if way == "kernel" else 0.45e9), memory


def _mellum_serving() -> dict:
    with open(os.path.join(REPO, "chipbench", "configs",
                           "mellum2-12b-a2.5b-8l.json")) as f:
        return json.load(f)["serving"]


def test_mellum_decode_program_fits_the_described_v5e(v5e, monkeypatch):
    """The 16-step decode program of `mellum2-12b-a2.5b-8l` (64 slots; 6
    sliding-window and 2 whole-context layers, 32 query heads on 4 KV
    heads of 128; 64 experts of 896): its arguments are the parameters
    and both pools, 3,584 blocks of whole contexts and 648 ring blocks;
    all eight layers read and write their pool through the Pallas
    kernels, six of them over rings of 9 columns, with one walk a pool
    and not one a layer; the experts go through `moe_experts_touched`."""
    serving = _mellum_serving()
    compiled, pool, shapes = _decode_program(v5e, monkeypatch, serving)
    assert pool == (3584, 128, 512)
    assert {x.dtype.name for x in jax.tree.leaves(shapes)} == {"bfloat16"}
    assert len(_mosaic_calls(compiled, "paged_attention_tpu")) == 8
    assert len(_mosaic_calls(compiled, "paged_write_tpu")) == 8
    assert _pool_copies(compiled, pool) == []
    assert _pool_copies(compiled, (648, 128, 512)) == []
    # a walk a pool (over 64 x 81 and 64 x 9 table entries), not a layer
    walks = [line for line in _walk_operations(compiled) if "s32[" in line]
    assert len(walks) < 16
    assert sum("s32[576]" in line for line in walks) \
        == sum("s32[5184]" in line for line in walks) < 8
    assert compiled.as_text().count("moe_experts_touched") >= 8
    assert "ragged-dot" not in compiled.as_text()
    memory = compiled.memory_analysis()
    print(f"mellum2-12b-a2.5b-8l decode program: {memory}")
    stored = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert 7.58e9 < stored < 7.60e9                  # 3.795 B, bfloat16
    # ... + 1.88 GB of whole contexts (2 layers) + 1.02 GB of rings (6)
    assert 10.4e9 < memory.argument_size_in_bytes < 10.6e9
    assert memory.temp_size_in_bytes < 0.3e9, memory
    assert _program_bytes(memory) < 15.75e9, memory


def test_mellum_prefill_program_fits_the_described_v5e(v5e, monkeypatch):
    """Its (1, 8192) prefill, the largest (`prefill_rows` 1): all eight
    layers' attention is the flash kernel, causal and padded, six of them
    with the window (XLA's scores, 32 x 8192 x 8192 float32 = 8.6 GB,
    would not fit); the experts go by XLA's grouped matmuls, since the
    grouped kernel serves no shape without a record on the chip; and
    parameters, temporaries and outputs fit beside the 2.90 GB of pools
    that the program does not see."""
    serving = _mellum_serving()
    assert serving["prefill_rows"] == 1
    compiled = _prefill_program(v5e, monkeypatch, serving, 1)
    text = compiled.as_text()
    assert len(_mosaic_calls(compiled, "flash_attention")) == 8
    assert "f32[1,32,8192,8192]" not in text
    assert "moe_experts_grouped" not in text
    assert text.count("ragged-dot") >= 24
    memory = compiled.memory_analysis()
    print(f"mellum2-12b-a2.5b-8l (1, 8192) prefill program: {memory}")
    assert memory.temp_size_in_bytes < 2.5e9, memory
    assert _program_bytes(memory) + 2.90e9 < 15.75e9, memory


@pytest.mark.parametrize("bucket", [2560, 3584, 7168])
def test_mellum_prefill_buckets_between_the_powers_of_two_compile(
        v5e, monkeypatch, bucket):
    """The configuration's buckets lie every 512 tokens from 2048 to 4096
    and every 1024 to 8192, so that a prompt pays for 1.15 times its
    length and not 1.44: one that is no power of two keeps eight flash
    kernels (blocks of 256 queries and 512 keys divide it), whose k axis
    is the band a window layer's query block sees, 4 key blocks, and
    every key block of a whole-context layer.  (1536 is not among them:
    the chip's compiler refuses that program, out of scoped VMEM in the
    experts' gather over 12,288 rows.)"""
    serving = _mellum_serving()
    assert serving["prefill_buckets"] == [1024, 2048, 2560, 3072, 3584, 4096,
                                          5120, 6144, 7168, 8192]
    serving["prefill_buckets"] = [b for b in serving["prefill_buckets"]
                                  if b <= bucket]
    compiled = _prefill_program(v5e, monkeypatch, serving, 1)
    assert len(_mosaic_calls(compiled, "flash_attention")) == 8
    assert f"f32[1,32,{bucket},{bucket}]" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9


def _gpt2_large_serving() -> dict:
    with open(os.path.join(REPO, "chipbench", "configs",
                           "gpt2-large.json")) as f:
        return json.load(f)["serving"]


def _parameter_converts(compiled) -> list:
    """The `convert`s of a compiled `gpt2-large` program whose result has
    the shape of a parameter matrix or table: the per-call bfloat16 twin
    of float32 parameters, where a program is handed those."""
    matrix = re.compile(
        r"bf16\[(1280,20,64|20,64,1280|1280,5120|5120,1280|50257,1280"
        r"|1024,1280)\]")
    return [line.strip()[:160] for line in compiled.as_text().splitlines()
            if " convert(" in line and matrix.search(line.split(" convert(")[0])]


@pytest.fixture(scope="module")
def gpt2_large_decode(v5e):
    """`_decode_program` of `gpt2-large` as benchmarked, 144 blocks,
    compiled once for the tests that read it."""
    from kfserving_tpu.observability import REGISTRY

    with pytest.MonkeyPatch.context() as patch:
        program = _decode_program(v5e, patch, _gpt2_large_serving())
    REGISTRY.reset()  # the engine's gauges: no test may start with any
    return program


def test_gpt2_large_decode_program_takes_each_rows_stop(gpt2_large_decode):
    """The decode program is told where each row's token budget ends by
    one more `s32[24]` beside the feed and the sampling arrays (and
    whether a row asked for log-probabilities by one `pred[]`), and parks
    a row through the table its kernels already take: the same 72 Mosaic
    calls, no pool copied, the list of blocks to walk still made once a
    step, and 96 bytes of arguments more."""
    compiled, pool, _ = gpt2_large_decode
    entry = compiled.as_text().split("\nENTRY ")[1].split("\n}")[0]
    per_slot = {
        name: shape for name, shape in re.findall(
            r"%(\w+?)\.\d+ = (\w+\[[\d,]*\])\S* parameter\(", entry)
        if not name.startswith(("variables__", "caches_"))}
    assert per_slot == {
        "table": "s32[24,8]", "tokens": "s32[24]", "positions": "s32[24]",
        "stops": "s32[24]", "temps": "f32[24]", "top_ks": "s32[24]",
        "top_ps": "f32[24]", "seeds": "s32[24]", "want_lp": "pred[]"}
    assert len(_mosaic_calls(compiled, "paged_write_tpu")) == 36
    assert len(_mosaic_calls(compiled, "paged_attention_tpu")) == 36
    assert 0 < len(_walk_operations(compiled)) < 12
    assert _pool_copies(compiled, pool) == []
    stored = sum(
        int(np.prod([int(n) for n in dims.split(",") if n]))
        * {"bf16": 2, "f32": 4, "s32": 4, "pred": 1}[dtype]
        for dtype, dims in re.findall(
            r" = (\w+)\[([\d,]*)\]\S* parameter\(", entry))
    memory = compiled.memory_analysis()
    # What the chip keeps for its arguments is the arrays' own bytes and
    # tiling's rounding of the small ones.
    assert 0 <= memory.argument_size_in_bytes - stored < 1 << 20, memory


@pytest.mark.parametrize("cache_blocks", [144, 192])
def test_gpt2_large_decode_program_fits_the_described_v5e(
        v5e, monkeypatch, cache_blocks, request):
    """The 16-step decode program of `gpt2-large` (24 slots, 20 heads of
    64) at the benchmarked 144 blocks and at the 192 the chip refused
    while the kernel read a padded twin of every layer's pool (9.16 GiB
    of temporaries): no pool is copied, neither into another layout nor
    through VMEM, and no parameter is converted: the float32 matrices
    as stored rest in bfloat16 (1.44 GiB fewer arguments), so the twin
    that was 1.44 of the program's 1.55 GiB of temporaries is gone."""
    serving = {**_gpt2_large_serving(), "cache_blocks": cache_blocks}
    if serving == _gpt2_large_serving():
        compiled, pool, shapes = request.getfixturevalue("gpt2_large_decode")
    else:
        compiled, pool, shapes = _decode_program(v5e, monkeypatch, serving)
    assert pool[0] == cache_blocks
    # A layer's two Mosaic calls: the step's write, then attention.
    assert len(_mosaic_calls(compiled, "paged_write_tpu")) == 36
    assert len(_mosaic_calls(compiled, "paged_attention_tpu")) == 36
    # The list of blocks to walk depends on the table and the lengths
    # alone: XLA computes it once a step, not once a layer.
    assert 0 < len(_walk_operations(compiled)) < 12
    assert _pool_copies(compiled, pool) == []
    memory = compiled.memory_analysis()
    print(f"gpt2-large decode program, {cache_blocks} blocks: {memory}")
    assert {x.dtype.name for x in jax.tree.leaves(shapes)} == {"float32"}
    assert sum(x.size for x in jax.tree.leaves(shapes)) > 7.7e8  # 3.1 GB
    assert _parameter_converts(compiled) == []
    assert memory.temp_size_in_bytes < 0.2 * 2**30, memory  # 1.55 at PR 27
    if cache_blocks == 144:
        # 1.44 GiB of parameters + 3.16 of pool; 6.05 with float32
        assert abs(memory.argument_size_in_bytes / 2**30 - 4.61) < 0.02
    assert _program_bytes(memory) < 15.75 * 2**30, memory


def test_gpt2_large_prefill_program_fits_the_described_v5e(v5e, monkeypatch):
    """Its (16, 512) prefill, the widest `warm_rows` brings in: a
    dispatch converts no parameter either, so its temporaries are the
    activations' alone."""
    compiled = _prefill_program(v5e, monkeypatch, _gpt2_large_serving(), 16)
    assert _parameter_converts(compiled) == []
    memory = compiled.memory_analysis()
    print(f"gpt2-large (16, 512) prefill program: {memory}")
    assert 1.4 < memory.argument_size_in_bytes / 2**30 < 1.5, memory
    assert memory.temp_size_in_bytes < 0.3 * 2**30, memory  # 0.27
    assert _program_bytes(memory) + 3.4e9 < 15.75 * 2**30, memory


@pytest.mark.parametrize("config, rows", [("olmoe-1b-7b-8l", 4),
                                          ("gpt2-large", 16)])
def test_packed_prefill_program_keeps_the_temporaries_of_a_prompt_a_row(
        v5e, monkeypatch, config, rows):
    """A whole-context attention model's (rows, bucket) program, with its
    rows carrying a prompt a block: no larger beside its arguments than
    the program that lays one prompt in a row by more than the head's
    and the sampler's [rows * blocks, vocabulary] float32 logits, where
    there were [rows, vocabulary]; the same K/V out, and no operation
    over scores that the other does not have."""
    serving = {"olmoe-1b-7b-8l": _olmoe_serving,
               "gpt2-large": _gpt2_large_serving}[config]()
    bucket = max(serving["prefill_buckets"])
    blocks = bucket // serving["block_size"]
    packed = _prefill_program(v5e, monkeypatch, serving, rows)
    alone = _prefill_program(v5e, monkeypatch, serving, rows,
                             a_prompt_a_row=True)
    memory, before = packed.memory_analysis(), alone.memory_analysis()
    print(f"{config} ({rows}, {bucket}) prefill program, packed: {memory}\n"
          f"a prompt a row: {before}")
    vocabulary = serving["arch_kwargs"]["vocab_size"]
    logits = 4 * rows * blocks * vocabulary
    assert memory.temp_size_in_bytes <= before.temp_size_in_bytes + logits
    # int32 segments, positions and last columns in; an entry a block out
    assert 0 < (memory.argument_size_in_bytes
                - before.argument_size_in_bytes) < 3 * 4 * rows * bucket
    assert 0 <= (memory.output_size_in_bytes
                - before.output_size_in_bytes) < 2**14
    assert _parameter_converts(packed) == []


@pytest.mark.parametrize("config, rows, beside", [
    ("nemotron-3-nano-16l-ep2", 8, 1.16e9), ("falcon-h1-34b-6l", 8, 2.83e9)])
def test_packed_state_model_prefill_program_beside_a_prompt_a_row(
        v5e, monkeypatch, config, rows, beside):
    """A state model's widest program with its rows carrying a prompt a
    block, against the program that lays one prompt in a row (the
    parent's): it returns a state and its conv rows for every block of
    every row where that one returned them a row, the same K/V, and with
    them it fits beside the state and the pools it does not see."""
    from kfserving_tpu.engine import programs
    from kfserving_tpu.models import create_model

    with open(os.path.join(REPO, "chipbench", "configs",
                           f"{config}.json")) as f:
        serving = json.load(f)["serving"]
    bucket = max(serving["prefill_buckets"])
    blocks = bucket // serving["block_size"]
    packed = _prefill_program(v5e, monkeypatch, serving, rows)
    alone = _prefill_program(v5e, monkeypatch, serving, rows,
                             a_prompt_a_row=True)
    memory, before = packed.memory_analysis(), alone.memory_analysis()
    print(f"{config} ({rows}, {bucket}) prefill program, packed: {memory}\n"
          f"a prompt a row: {before}")
    kinds = create_model(serving["architecture"],
                         **serving["arch_kwargs"]).module.config.cache_layers()
    assert programs.packs_prompts(kinds, bucket, serving["block_size"])
    state = sum(math.prod(shape) * jnp.dtype(dtype).itemsize
                for kind in kinds if programs.parts(kind)[1] is not None
                for shape, dtype in programs.parts(kind)[1].arrays)
    more = rows * (blocks - 1) * state
    grown = memory.output_size_in_bytes - before.output_size_in_bytes
    # the conv rows leave in tiles of 8 or 16 sublanes where they are 3
    assert more <= grown < 1.1 * more + 2**20, (grown, more)
    vocabulary = serving["arch_kwargs"]["vocab_size"]
    logits = 4 * rows * blocks * vocabulary
    assert memory.temp_size_in_bytes \
        <= before.temp_size_in_bytes + logits + more, (memory, before)
    assert _parameter_converts(packed) == []
    assert _program_bytes(memory) + beside < 15.75 * 2**30, memory


def _falcon_serving() -> dict:
    with open(os.path.join(REPO, "chipbench", "configs",
                           "falcon-h1-34b-6l.json")) as f:
        return json.load(f)["serving"]


def test_five_query_heads_a_kv_head_compile_for_described_v5e(v5e):
    """The paged decode kernel at `falcon-h1-34b-6l`'s shapes: 20 query
    heads on 4 KV heads of 128, which are no whole sublane tiles (16 rows
    of bfloat16): the rows come padded to 32, and the kernel is the one
    Mosaic call it was at 32 query heads, the pools not copied, 4 blocks an
    iteration."""
    from kfserving_tpu.ops import paged_attention

    serving = _falcon_serving()
    kw = serving["arch_kwargs"]
    columns = serving["max_seq"] // serving["block_size"]
    args = _paged_args(serving["max_slots"], kw["num_kv_heads"],
                       kw["head_dim"], serving["cache_blocks"],
                       serving["block_size"], columns)
    args[0] = ((serving["max_slots"], 1, kw["num_heads"], kw["head_dim"]),
               jnp.bfloat16, P())
    assert (kw["num_heads"], kw["num_kv_heads"]) == (20, 4)
    assert paged_attention.blocks_per_iteration(
        serving["block_size"], args[1][0][2], jnp.bfloat16, columns) == 4
    compiled = _compile(paged_attention.paged_attention_sharded, args, v5e,
                        sharded=False)
    assert len(_mosaic_calls(compiled, "paged_attention_tpu")) == 1
    assert _pool_copies(compiled, args[1][0]) == []
    text = compiled.as_text()
    assert "bf16[64,32,128]" in text  # the padded rows, in and out
    memory = compiled.memory_analysis()
    print(f"falcon paged kernel: {memory}")
    assert memory.temp_size_in_bytes < 2**20, memory


def test_falcon_decode_program_fits_the_described_v5e(v5e, monkeypatch):
    """The 16-step decode program of `falcon-h1-34b-6l` (64 slots, six
    layers that each keep K/V rows AND a state): its arguments are the
    parameters (10.51 GB), every layer's state (1.62 GB) and pools
    (1.21 GB); all six layers read the pool through the Pallas kernel and
    write it through the other, and no pool is copied."""
    compiled, pool, shapes = _decode_program(v5e, monkeypatch,
                                             _falcon_serving())
    assert pool == (768, 128, 512)
    assert len(_mosaic_calls(compiled, "paged_attention_tpu")) == 6
    assert len(_mosaic_calls(compiled, "paged_write_tpu")) == 6
    assert _pool_copies(compiled, pool) == []
    # the walk's int32 lists are a step's, not a layer's; a layer's own
    # are the pad of its 20 query rows and the cut of its answer
    walk = _walk_operations(compiled)
    assert len([op for op in walk if "= s32[" in op]) < 10, walk
    assert len(walk) < 10 + 2 * 6, walk
    stored = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert 10.50e9 < stored < 10.52e9               # 5.255 B, bfloat16
    memory = compiled.memory_analysis()
    print(f"falcon-h1-34b-6l decode program: {memory}")
    assert 13.33e9 < memory.argument_size_in_bytes < 13.36e9
    assert memory.temp_size_in_bytes < 1.0e9, memory
    assert _program_bytes(memory) < 15.75 * 2**30, memory
    _assert_the_tail_is_lean(compiled, 64, 261120, "lm_head/dot_general")


def test_falcon_prefill_program_fits_the_described_v5e(v5e, monkeypatch):
    """Its (8, 512) prefill, the most one dispatch carries (`prefill_rows`
    8): parameters, temporaries (the 9248-wide in-projection, the chunked
    scan's float32 blocks at a state of 128 x 256 a head, the 21504-wide
    MLP) and outputs (six layers' K/V rows and states) fit beside the
    2.83 GB of state and pool that the program does not see."""
    serving = _falcon_serving()
    assert serving["prefill_rows"] == 8
    compiled = _prefill_program(v5e, monkeypatch, serving, 8)
    memory = compiled.memory_analysis()
    print(f"falcon-h1-34b-6l (8, 512) prefill program: {memory}")
    assert 10.50e9 < memory.argument_size_in_bytes < 10.52e9
    assert _program_bytes(memory) + 2.83e9 < 15.75 * 2**30, memory


def _moonlight_serving() -> dict:
    with open(os.path.join(REPO, "chipbench", "configs",
                           "moonlight-16b-a3b-7l.json")) as f:
        return json.load(f)["serving"]


def test_moonlight_decode_program_fits_the_described_v5e(v5e, monkeypatch):
    """The 16-step decode program of `moonlight-16b-a3b-7l` (128 slots,
    4,096 blocks of 128 latent rows): its arguments are the parameters
    (8.53 GB) and seven latent pools, one array a layer, 576 numbers a
    row held 640 wide (4.70 GB); every layer reads its pool through the
    latent kernel, which copies a block once (one pool operand, one set
    of blocks), and writes it through the one-row write; no pool is
    copied."""
    compiled, pool, shapes = _decode_program(v5e, monkeypatch,
                                             _moonlight_serving())
    assert pool == (4096, 128, 640)
    assert len(_mosaic_calls(compiled, "latent_attention_tpu")) == 7
    assert len(_mosaic_calls(compiled, "latent_write_tpu")) == 7
    assert _mosaic_calls(compiled, "paged_attention_tpu") == []
    assert _pool_copies(compiled, pool) == []
    kernel = next(line for line in compiled.as_text().splitlines()
                  if 'custom_call_target="tpu_custom_call"' in line
                  and "latent_attention_tpu" in line.split("=")[0])
    assert kernel.count("bf16[4096,128,640]") == 1, kernel[:400]
    stored = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert 8.52e9 < stored < 8.54e9                 # 4.263 B, bfloat16
    memory = compiled.memory_analysis()
    print(f"moonlight-16b-a3b-7l decode program: {memory}")
    assert 13.2e9 < memory.argument_size_in_bytes < 13.3e9
    assert memory.temp_size_in_bytes < 0.5e9, memory
    assert _program_bytes(memory) < 15.75 * 2**30, memory
    _assert_the_tail_is_lean(compiled, 128, 163840, "lm_head/dot_general")


def test_moonlight_prefill_program_fits_the_described_v5e(v5e, monkeypatch):
    """Its (1, 6144) prefill, the largest (`prefill_rows` 1): expanded
    attention with keys of 192 and values of 128 through the flash
    kernel, the grouped experts; parameters, temporaries and outputs
    (seven layers' latent rows) fit beside the 4.70 GB of pools that the
    program does not see."""
    serving = _moonlight_serving()
    assert serving["prefill_rows"] == 1
    assert max(serving["prefill_buckets"]) == 6144
    compiled = _prefill_program(v5e, monkeypatch, serving, 1)
    assert len(_mosaic_calls(compiled, "flash_attention")) == 7
    memory = compiled.memory_analysis()
    print(f"moonlight-16b-a3b-7l (1, 6144) prefill program: {memory}")
    assert 8.52e9 < memory.argument_size_in_bytes < 8.54e9
    assert _program_bytes(memory) + 4.70e9 < 15.75 * 2**30, memory


# -- the sampler's tail, as the chip's compiler leaves it -----------------------
_CALLED = re.compile(
    r"(?:calls|to_apply|body|condition)=%([\w.\-]+)"
    r"|(?:branch|called)_computations=\{([^}]*)\}")


def _computations(compiled) -> dict:
    """{computation: its instruction lines} of a compiled program."""
    computations, name = {}, None
    for line in compiled.as_text().splitlines():
        opened = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\) -> .*\{$", line)
        if opened:
            name = opened.group(1)
            computations[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None and " = " in line:
            computations[name].append(line.strip())
    return computations


def _callees(line: str) -> list:
    return [name.strip().lstrip("%")
            for one, many in _CALLED.findall(line)
            for name in (one + "," + many).split(",") if name.strip()]


def _under_branches(computations: dict) -> set:
    """The computations that run only where a `conditional` takes the
    branch that calls them."""
    todo = [name for lines in computations.values() for line in lines
            if " conditional(" in line for name in _callees(line)]
    seen = set()
    while todo:
        name = todo.pop()
        if name not in seen and name in computations:
            seen.add(name)
            todo += [c for line in computations[name]
                     for c in _callees(line)]
    return seen


def _made(computations: dict, names, shape: str) -> list:
    """The instructions of `names`, fusions' bodies left out, that compute
    or copy a result of `shape` in any layout (an element of a tuple
    counts): what exists in memory at that size.  A `tuple`, a
    `get-tuple-element`, a `parameter` or a `bitcast` makes nothing."""
    bodies = {c for lines in computations.values() for line in lines
              if " fusion(" in line for c in _callees(line)}
    made = []
    for name in names:
        for line in () if name in bodies else computations[name]:
            result = re.match(r"(\(.*?\)|\S+) ([\w\-]+)\(",
                              line.split(" = ", 1)[1])
            if result and shape in result.group(1) and result.group(2) not in (
                    "tuple", "get-tuple-element", "parameter", "bitcast"):
                made.append(line)
    return made


def _assert_the_tail_is_lean(compiled, rows: int, vocab: int, head: str):
    """Outside the branches of a `conditional` the [rows, vocab] float32
    logits exist once, as the head's result (and as the compiler's own
    move of it to where the branches read it), and nothing there draws
    noise; the branch that answers a request for log-probabilities forms
    no second array of that size: no log-softmax written out, no
    transposed twin, no sort."""
    logits = f"f32[{rows},{vocab}]"
    computations = _computations(compiled)
    inside = _under_branches(computations)
    outside = [name for name in computations if name not in inside]
    made = _made(computations, outside, logits)
    heads = [line for line in made if head in line]
    moves = [line for line in made
             if re.search(r"\) copy-start\(|\} copy-done\(", line)]
    assert len(heads) == 1, made
    assert sorted(made) == sorted(heads + moves), made
    assert not [line for name in outside for line in computations[name]
                if "threefry" in line or "gumbel" in line]
    assert [line for name in inside for line in computations[name]
            if "threefry" in line]               # where a row samples
    scored = [name for name in inside
              if any(f"s32[1,{rows},5]" in line and line.startswith("ROOT")
                     for line in computations[name])
              and any(logits in line for line in computations[name])]
    assert len(scored) == 1, scored             # `logprob_of`'s `asked`
    reach = [scored[0]] + [c for line in computations[scored[0]]
                           for c in _callees(line)]
    made = _made(computations, reach, logits)
    assert all(re.search(r"\) copy-start\(|\} copy-done\(", line)
               for line in made), made
    assert not [line for name in reach for line in computations[name]
                if f"[{rows},{vocab}]{{0,1" in line or " sort(" in line]


def test_toy_decoder_with_a_real_vocabulary_keeps_its_tail_lean(v5e,
                                                                monkeypatch):
    """The decode program of a one-layer toy decoder with
    `falcon-h1-34b-6l`'s 64 slots and 261120 columns, compiled for the
    described v5e."""
    rows, vocab = 64, 261120
    compiled, _, _ = _decode_program(v5e, monkeypatch, {
        "architecture": "decoder_tiny",
        "arch_kwargs": {"vocab_size": vocab, "num_layers": 1,
                        "max_seq": 256},
        "max_slots": rows, "max_seq": 256, "prefill_buckets": [128],
        "block_size": 128, "cache_blocks": None, "steps_per_call": 4})
    _assert_the_tail_is_lean(compiled, rows, vocab, "wte.attend/dot_general")


def test_bare_mosaic_kernel_is_refused_under_a_mesh(v5e):
    """Why the wrappers exist: the pool sharded on heads, as the engine
    shards it under tp, and the kernel called bare."""
    from kfserving_tpu.ops.paged_attention import paged_attention_tpu

    _, args = _kernel_case("paged", sharded=True)
    with pytest.raises(NotImplementedError, match="shard_map"):
        _compile(paged_attention_tpu, args, v5e, sharded=True)


# -- the wrappers' numerics, on virtual CPU devices ----------------------------
@pytest.fixture
def cpu_mesh():
    return _tp4(jax.devices())


def test_paged_kernel_under_mesh_matches_xla(cpu_mesh):
    from kfserving_tpu.ops.paged_attention import (
        paged_attention_sharded,
        paged_attention_xla,
    )

    rng = np.random.default_rng(0)
    b, h, d, nb, bs = 3, 8, 64, 10, 128
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    pool_k = jnp.asarray(rng.standard_normal((nb, bs, h * d)), jnp.float32)
    pool_v = jnp.asarray(rng.standard_normal((nb, bs, h * d)), jnp.float32)
    table = jnp.asarray([[0, 1], [2, -1], [3, 4]], jnp.int32)
    lengths = jnp.asarray([200, 7, 256], jnp.int32)
    want = paged_attention_xla(q, pool_k, pool_v, table, lengths)
    heads = NamedSharding(cpu_mesh, P(None, None, "tp", None))
    pool = NamedSharding(cpu_mesh, P(None, None, "tp"))
    with jax.set_mesh(cpu_mesh):
        got = jax.jit(functools.partial(paged_attention_sharded,
                                        interpret=True))(
            jax.device_put(q, heads), jax.device_put(pool_k, pool),
            jax.device_put(pool_v, pool), table, lengths)
    assert len(got.sharding.device_set) == 4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_kernel_under_mesh_matches_xla(cpu_mesh, monkeypatch):
    from jax.experimental import pallas as pl

    from kfserving_tpu.ops import attention

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 64, 4, 64)), jnp.float32)
               for _ in range(3))
    lengths = jnp.asarray([64, 40], jnp.int32)
    pad = (jnp.arange(64)[None, :] < lengths[:, None])[:, None, None, :]
    with jax.set_mesh(cpu_mesh):
        got = jax.jit(lambda q, k, v, n: attention._flash(q, k, v, False, n))(
            q, k, v, lengths)
    want = attention._xla_attention(q, k, v, pad)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_dispatcher_propagates_kernel_errors(monkeypatch):
    """A kernel the dispatcher chose runs or the call fails: no XLA
    fallback behind it."""
    from kfserving_tpu.ops import attention, pallas_attention

    def broken(*args, **kwargs):
        raise RuntimeError("mosaic refused this kernel")

    monkeypatch.setattr(attention, "_tpu_backend", lambda: True)
    monkeypatch.setattr(pallas_attention, "flash_attention", broken)
    q = jnp.ones((1, 1024, 2, 64), jnp.bfloat16)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        attention.dot_product_attention(q, q, q, causal=True)


# -- chip_smoke.py: fails off the chip, phases pass at toy size ----------------
def test_chip_smoke_fails_without_a_chip():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=60)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "expected 'tpu'" in proc.stderr


def test_chip_smoke_fails_outside_a_checkout(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    alone.write_bytes(open(os.path.join(REPO, "chip_smoke.py"), "rb").read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, str(alone)], cwd=tmp_path, env=env,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_chip_smoke_parent_stays_off_jax():
    """A parent that has touched JAX holds the chip, and its children
    then fail: nothing the phases import in the parent may import jax."""
    code = (
        "import sys, chip_smoke\n"
        "from kfserving_tpu.protocol import native, v2\n"
        "from kfserving_tpu.control.controller import Controller\n"
        "from kfserving_tpu.control.router import IngressRouter\n"
        "from kfserving_tpu.control.spec import InferenceService\n"
        "from kfserving_tpu.control.subprocess_orchestrator import (\n"
        "    SubprocessOrchestrator)\n"
        "assert 'jax' not in sys.modules, 'the smoke parent imported jax'\n")
    subprocess.run([sys.executable, "-c", code], cwd=REPO, check=True,
                   timeout=60)


def test_predict_phase_at_toy_size():
    device = chip_smoke.phase_predict(TOY_PREDICT, "cpu", TOY_BATCH)
    assert device["platform"] == "cpu"


def test_generate_phase_at_toy_size():
    out = chip_smoke.phase_generate(TOY_DECODER, "cpu")
    assert out["device"]["platform"] == "cpu"
    assert len(out["short"]["ids"]) == len(out["long"]["ids"]) == 7
    # Off the chip the dispatchers choose the XLA formulations, and say so.
    assert {path for path, _, _ in out["paths"]} == {"xla", "xla_paged"}


def test_kernels_phase_at_toy_size():
    device = chip_smoke.phase_kernels(TOY_DECODER, "cpu")
    assert device["platform"] == "cpu"


def test_phase_refuses_the_wrong_platform():
    with pytest.raises(chip_smoke.SmokeFailure, match="expected 'tpu'"):
        chip_smoke.phase_generate(TOY_DECODER, "tpu")


# -- one set of chips per replica ----------------------------------------------
def test_replicas_claim_their_own_chips():
    from kfserving_tpu.control.spec import PredictorSpec
    from kfserving_tpu.control.subprocess_orchestrator import (
        SubprocessOrchestrator,
    )
    from kfserving_tpu.control.topology import select_topology

    class _Exited:
        returncode = -9

    orch = SubprocessOrchestrator()
    first = orch._claim_chips(1)
    assert first.chips == [0]
    assert orch._claim_chips(2).chips == [1, 2]
    first.process = _Exited()  # its process exited: the claim lapses
    assert orch._claim_chips(1).chips == [0]

    placement = select_topology(PredictorSpec(framework="jax",
                                              storage_uri="file:///m"))
    assert "TPU_VISIBLE_CHIPS" not in placement.env()
    env = placement.env([3])
    assert env["TPU_VISIBLE_CHIPS"] == "3"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"


@pytest.mark.slow
def test_four_chip_phases_at_toy_size():
    """The --four-chips phases on virtual CPU devices: the decoder under
    tp=4 against one device, and four replicas behind the router, each
    handed its own chip index."""
    device = chip_smoke.phase_sharded(TOY_DECODER, "cpu", tp=4)
    assert device["count"] >= 4
    records = chip_smoke.phase_replicas(TOY_PREDICT, "cpu", replicas=4,
                                        instance=TOY_BATCH[0])
    assert sorted(r["device"]["visible_chips"] for r in records) \
        == ["0", "1", "2", "3"]
    assert json.dumps(records)  # plain data, printable as observations
