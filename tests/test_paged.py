"""The KV cache: block-pool + block-table serving (VERDICT r4 #4).

Parity bar: the engine must reproduce the no-cache full recompute
token-for-token — block boundaries, prefix sharing, pool pressure,
and eviction change WHERE bytes live, never results.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kfserving_tpu.engine.generator import GenerationEngine
from kfserving_tpu.models.decoder import DecoderLM, decoder_tiny
from kfserving_tpu.protocol.errors import InvalidInput

MAX_SEQ = 64
BS = 16


@pytest.fixture(scope="module")
def tiny():
    cfg = decoder_tiny(num_layers=2, hidden_size=64, num_heads=2,
                       intermediate_size=128, max_seq=MAX_SEQ,
                       vocab_size=96)
    module = DecoderLM(cfg)
    variables = module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    return module, variables, cfg


def ref_greedy(module, variables, prompt, steps):
    """Greedy continuation by full recompute, no cache.  Every step
    runs the one shape [1, MAX_SEQ]: attention is causal, so what pads
    the row after its last token reaches no logit that is read, and a
    shape a step would compile the model anew 60 times a test."""
    apply = jax.jit(module.apply)
    ids = [int(t) for t in prompt]
    out = []
    for _ in range(steps):
        row = np.zeros((1, MAX_SEQ), np.int32)
        row[0, :len(ids)] = ids
        logits = apply(variables, jnp.asarray(row))
        nxt = int(jnp.argmax(logits[0, len(ids) - 1]))
        out.append(nxt)
        ids.append(nxt)
    return out


def make_paged(tiny, **kw):
    module, variables, _ = tiny
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("prefill_buckets", [16, 32, MAX_SEQ])
    kw.setdefault("block_size", BS)
    return GenerationEngine(module, variables, **kw)


# ------------------------------------------------------------- parity


async def test_paged_greedy_matches_full_recompute(tiny):
    module, variables, _ = tiny
    prompt = [5, 9, 2, 7, 11]
    want = ref_greedy(module, variables, prompt, 12)
    eng = make_paged(tiny, max_slots=1)
    try:
        got, reason = await eng.complete(prompt, max_new_tokens=12)
    finally:
        await eng.close()
    assert got == want
    assert reason == "length"


@pytest.mark.slow
async def test_paged_block_boundary_cases(tiny):
    """Prompts AT a block boundary and budgets that cross one: the
    scatter/gather seams must be invisible."""
    module, variables, _ = tiny
    cases = [
        (list(range(1, BS + 1)), 5),        # prompt exactly one block
        ([3, 1, 4], BS + 3),                # budget crosses a boundary
        (list(range(1, BS + 2)), 2 * BS),   # prompt just past a block
    ]
    eng = make_paged(tiny, max_slots=4)
    try:
        for prompt, budget in cases:
            want = ref_greedy(module, variables, prompt,
                              min(budget, MAX_SEQ - len(prompt)))
            got, _ = await eng.complete(prompt,
                                        max_new_tokens=budget)
            assert got == want, (prompt, budget)
    finally:
        await eng.close()


@pytest.mark.slow
async def test_paged_concurrent_requests_isolated(tiny):
    module, variables, _ = tiny
    prompts = [[3, 1, 4], [1, 5, 9, 2, 6, 5],
               [35, 8, 90, 9, 3, 2, 38, 4, 6]]
    want = [ref_greedy(module, variables, p, 8) for p in prompts]
    eng = make_paged(tiny, max_slots=4)
    try:
        got = await asyncio.gather(*[
            eng.complete(p, max_new_tokens=8) for p in prompts])
    finally:
        await eng.close()
    assert [t for t, _ in got] == want


async def test_paged_seeded_sampling_reproduces(tiny):
    eng = make_paged(tiny, max_slots=2)
    prompt = [5, 9, 2]
    try:
        a, _ = await eng.complete(prompt, max_new_tokens=8,
                                  temperature=1.1, seed=42)
        b, _ = await eng.complete(prompt, max_new_tokens=8,
                                  temperature=1.1, seed=42)
    finally:
        await eng.close()
    assert a == b


# ------------------------------------------------------- prefix reuse


async def test_prefix_reuse_shares_blocks_and_preserves_output(tiny):
    """Two prompts sharing >= one full block of prefix: the second
    admission hits the prefix index (no new storage for the shared
    part) and still generates exactly its isolated-baseline tokens."""
    module, variables, _ = tiny
    shared = list(range(1, 2 * BS + 1))       # two full shared blocks
    p1 = shared + [7, 7]
    p2 = shared + [9]
    want1 = ref_greedy(module, variables, p1, 6)
    want2 = ref_greedy(module, variables, p2, 6)
    eng = make_paged(tiny, max_slots=2)
    try:
        got1, _ = await eng.complete(p1, max_new_tokens=6)
        hits_before = eng.stats()["paged"]["prefix_hits"]
        got2, _ = await eng.complete(p2, max_new_tokens=6)
        hits_after = eng.stats()["paged"]["prefix_hits"]
    finally:
        await eng.close()
    assert got1 == want1
    assert got2 == want2
    assert hits_after - hits_before == 2  # both shared blocks hit


@pytest.mark.slow
async def test_prefix_blocks_linger_and_get_evicted_under_pressure(
        tiny):
    """Zero-ref registered blocks stay reclaimable (future requests
    can still hit them) until allocation pressure evicts LRU — the
    pool never deadlocks on lingering prefixes."""
    eng = make_paged(tiny, max_slots=2, cache_blocks=8)
    prompt_a = list(range(1, BS + 1))
    try:
        await eng.complete(prompt_a, max_new_tokens=2)
        # Idle engine: deferred frees force-process; the registered
        # block lingers as reclaimable.
        for _ in range(30):
            await asyncio.sleep(0.1)
            st = eng.stats()["paged"]
            if st["reclaimable_blocks"] >= 1:
                break
        assert st["reclaimable_blocks"] >= 1
        # A re-run of the same prompt hits the lingering block.
        hits0 = st["prefix_hits"]
        await eng.complete(prompt_a, max_new_tokens=2)
        assert eng.stats()["paged"]["prefix_hits"] > hits0
        # Pressure: distinct prompts wanting more blocks than free —
        # eviction reclaims the lingering registrations, everything
        # completes.
        outs = await asyncio.gather(*[
            eng.complete([100 + i] + list(range(1, BS + 1)),
                         max_new_tokens=2)
            for i in range(4)])
        assert all(len(t) == 2 for t, _ in outs)
    finally:
        await eng.close()


# ------------------------------------------------------ pool sizing


@pytest.mark.parametrize("max_seq, buckets, block_size, want", [
    (32, None, None, 16),        # default pow-2 buckets from 16
    (1024, [512], None, 128),    # gpt2-large's lengths: the kernels'
    (2048, [1024], None, 128),   # OLMoE's
    (48, [24, 48], None, 8),
    (64, [16, 64], 8, 8),        # explicit: taken as given
    (64, [16, 64], 24, None),    # ... and still validated
], ids=["pow2-32", "1024-512", "2048-1024", "24-48", "explicit",
        "explicit-invalid"])
def test_block_size_unset_is_derived_from_the_lengths(
        max_seq, buckets, block_size, want):
    """block_size=None means gcd(128, max_seq, every prefill bucket);
    cache_blocks=None then holds every position of every slot."""
    module = DecoderLM(decoder_tiny(
        num_layers=1, hidden_size=32, num_heads=2,
        intermediate_size=64, max_seq=max_seq, vocab_size=96))
    variables = module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))

    def build():
        return GenerationEngine(module, variables, max_slots=2,
                                max_seq=max_seq, prefill_buckets=buckets,
                                block_size=block_size)

    if want is None:
        with pytest.raises(InvalidInput, match="multiple of block_size"):
            build()
        return
    eng = build()
    try:
        assert eng.block_size == want
        assert eng.stats()["paged"]["block_size"] == want
        assert eng.num_blocks * want == 2 * max_seq
    finally:
        eng.shutdown_nowait()


async def test_engine_built_with_slots_and_max_seq_alone_shares_prefixes(
        tiny):
    """The engine a caller gets who set nothing of the cache: a block
    pool, reported under stats()["paged"], that shares the blocks of a
    repeated prompt."""
    module, variables, _ = tiny
    prompt = list(range(1, 2 * BS + 4))
    want = ref_greedy(module, variables, prompt, 4)
    eng = GenerationEngine(module, variables, max_slots=2,
                           max_seq=MAX_SEQ)
    try:
        pool = eng.stats()["paged"]
        assert pool["block_size"] == BS
        assert pool["pool_blocks"] == 2 * MAX_SEQ // BS
        assert eng.cache_debug()["paged"] is True
        first, _ = await eng.complete(prompt, max_new_tokens=4)
        again, _ = await eng.complete(prompt, max_new_tokens=4)
        pool = eng.stats()["paged"]
    finally:
        await eng.close()
    assert first == again == want
    assert pool["prefix_hits"] == 2       # both full blocks, shared
    assert pool["prefill_tokens_saved"] == 2 * BS


def test_paged_cache_bytes_scale_with_pool(tiny):
    """cache_blocks unset holds every position of every slot; half the
    blocks are half the bytes."""
    _, _, cfg = tiny
    # layers * k+v * S * max_seq * H * D * itemsize (float32)
    every_position = (cfg.num_layers * 2 * 4 * MAX_SEQ * cfg.num_heads
                      * cfg.head_dim * 4)
    parity = make_paged(tiny, max_slots=4)
    half = make_paged(tiny, max_slots=4,
                      cache_blocks=2 * (MAX_SEQ // BS))
    try:
        assert parity.cache_bytes() == every_position
        assert half.cache_bytes() == every_position // 2
    finally:
        parity.shutdown_nowait()
        half.shutdown_nowait()


@pytest.mark.slow
async def test_paged_pool_pressure_queues_not_fails(tiny):
    """A pool smaller than the offered load: requests WAIT for block
    releases and all complete (progress guarantee), matching their
    baselines."""
    module, variables, _ = tiny
    prompts = [[i + 1, i + 2, i + 3] for i in range(5)]
    want = [ref_greedy(module, variables, p, 6) for p in prompts]
    # 3 blocks: roughly one active request at a time (prompt block +
    # growth headroom).
    eng = make_paged(tiny, max_slots=4, cache_blocks=3,
                     steps_per_call=1, pipeline_depth=1)
    try:
        got = await asyncio.wait_for(asyncio.gather(*[
            eng.complete(p, max_new_tokens=6) for p in prompts]),
            timeout=120)
    finally:
        await eng.close()
    assert [t for t, _ in got] == want


def test_paged_validation(tiny):
    with pytest.raises(InvalidInput):
        make_paged(tiny, block_size=13)  # doesn't divide buckets
    eng = make_paged(tiny, cache_blocks=2)
    try:
        with pytest.raises(InvalidInput):
            # Needs 3 blocks, pool holds 2: permanent — reject at
            # submit, don't queue forever.
            eng.submit(list(range(1, 40)), max_new_tokens=1)
    finally:
        eng.shutdown_nowait()


async def test_paged_cancel_releases_blocks(tiny):
    eng = make_paged(tiny, max_slots=2)
    try:
        req = eng.submit([1, 2, 3], max_new_tokens=10_000)
        stream = eng.stream(req)
        await asyncio.wait_for(stream.__anext__(), timeout=30)
        eng.cancel(req)
        # After the deferral window drains, the blocks come back.
        total = eng.stats()["paged"]["pool_blocks"]
        for _ in range(100):
            await asyncio.sleep(0.1)
            st = eng.stats()["paged"]
            if st["free_blocks"] + st["reclaimable_blocks"] == total:
                break
        assert st["free_blocks"] + st["reclaimable_blocks"] == total
    finally:
        await eng.close()


# -------------------------------------------------- serving integration


async def test_paged_model_serves_over_http(tmp_path):
    """A served model with no block_size (the engine derives 16 from
    these buckets, a block for every position of every slot) beside
    one with an explicit small pool: equal tokens, fewer bytes, and
    /metrics exports the prefix-cache stats."""
    import json as _json

    import aiohttp

    from kfserving_tpu.predictors.llm import GenerativeModel
    from kfserving_tpu.server.app import ModelServer

    def write_dir(name, extra):
        d = tmp_path / name
        d.mkdir()
        cfg = {
            "architecture": "decoder_tiny",
            "arch_kwargs": {"num_layers": 2, "hidden_size": 64,
                            "num_heads": 2, "intermediate_size": 128,
                            "max_seq": 64},
            "max_slots": 2, "max_seq": 64,
            "prefill_buckets": [16, 32, 64],
            "max_new_tokens": 8, "tokenizer": "byte",
        }
        cfg.update(extra)
        (d / "config.json").write_text(_json.dumps(cfg))
        return str(d)

    derived = GenerativeModel("derived", write_dir("derived", {}))
    derived.load()
    assert derived.engine.block_size == 16
    small = GenerativeModel("small", write_dir(
        "small", {"block_size": 16, "cache_blocks": 6}))
    small.load()
    server = ModelServer(http_port=0)
    await server.start_async([derived, small], host="127.0.0.1")
    base = f"http://127.0.0.1:{server.http_port}"
    try:
        async with aiohttp.ClientSession() as s:
            outs = {}
            for name in ("derived", "small"):
                async with s.post(
                        f"{base}/v2/models/{name}/generate",
                        json={"text_input": "paging!",
                              "parameters": {"max_tokens": 6}}) as r:
                    assert r.status == 200, await r.text()
                    outs[name] = (await r.json())["text_output"]
            assert outs["derived"] == outs["small"]
            async with s.get(f"{base}/metrics") as r:
                metrics = await r.text()
        assert "kfserving_tpu_engine_paged" in metrics
        assert 'bucket="prefix_hits"' in metrics
        assert (small.engine.cache_bytes()
                < derived.engine.cache_bytes())
    finally:
        await server.stop_async()


@pytest.mark.slow
async def test_paged_generation_parity_under_tp_mesh(tmp_path):
    """tp=2 sharded PAGED decode (pool shards on heads like the dense
    layout) produces the same greedy tokens as unsharded paged."""
    import json as _json

    from kfserving_tpu.predictors.llm import GenerativeModel

    def write_dir(name, extra):
        d = tmp_path / name
        d.mkdir()
        cfg = {
            "architecture": "decoder_tiny",
            "arch_kwargs": {"num_layers": 2, "hidden_size": 64,
                            "num_heads": 2, "intermediate_size": 128,
                            "max_seq": 64},
            "max_slots": 2, "max_seq": 64,
            "prefill_buckets": [16, 32, 64],
            "max_new_tokens": 8, "tokenizer": "byte",
            "block_size": 16,
        }
        cfg.update(extra)
        (d / "config.json").write_text(_json.dumps(cfg))
        return str(d)

    plain = GenerativeModel("p", write_dir("p", {}))
    plain.load()
    sharded = GenerativeModel("s", write_dir("s", {"mesh": {"tp": 2}}))
    sharded.load()
    try:
        a = await plain.predict({"instances": ["paged parity"]})
        b = await sharded.predict({"instances": ["paged parity"]})
        assert (a["predictions"][0]["text"]
                == b["predictions"][0]["text"])
    finally:
        await plain.close()
        await sharded.close()


async def test_paged_growth_preemption_resumes_exactly(tiny):
    """The live-drive regression (round 5): concurrent streams whose
    growth exceeds the pool must be PREEMPTED and resumed — never
    killed with 'pool exhausted' — and the resumed stream produces
    exactly the tokens an uninterrupted run would (noise is keyed on
    (seed, position), so re-prefill continuation is bit-exact)."""
    module, variables, _ = tiny
    prompts = [[(i * 7 + j) % 90 + 1 for j in range(42)]
               for i in range(3)]
    budget = 20  # 42 + 20 = 62: every stream wants 4 blocks eventually
    want = [ref_greedy(module, variables, p, budget) for p in prompts]
    eng = make_paged(tiny, max_slots=4, cache_blocks=10)
    try:
        got = await asyncio.wait_for(asyncio.gather(*[
            eng.complete(p, max_new_tokens=budget) for p in prompts]),
            timeout=300)
        stats = eng.stats()["paged"]
    finally:
        await eng.close()
    assert [t for t, _ in got] == want
    assert stats["preemptions"] >= 1  # pressure actually happened


@pytest.mark.slow
async def test_paged_preemption_exact_under_sampling(tiny):
    """Seeded temperature stream preempted mid-flight == the same
    stream run solo with ample blocks."""
    prompt = [(j * 3) % 90 + 1 for j in range(42)]
    ample = make_paged(tiny, max_slots=1)
    try:
        want, _ = await ample.complete(prompt, max_new_tokens=18,
                                       temperature=1.1, seed=9)
    finally:
        await ample.close()
    tight = make_paged(tiny, max_slots=4, cache_blocks=10)
    try:
        results = await asyncio.wait_for(asyncio.gather(
            tight.complete(prompt, max_new_tokens=18,
                           temperature=1.1, seed=9),
            tight.complete([(j * 5) % 90 + 1 for j in range(42)],
                           max_new_tokens=18),
            tight.complete([(j * 11) % 90 + 1 for j in range(42)],
                           max_new_tokens=18)), timeout=300)
    finally:
        await tight.close()
    assert results[0][0] == want


async def test_plan_rollback_deregisters_provisional_chains(tiny):
    """A plan that registers a fresh full block then fails allocation
    must deregister it — a retry hitting the stale chain would share
    a block that was NEVER WRITTEN (all-zero k/v, code-review r5)."""
    import numpy as _np

    from kfserving_tpu.engine.generator import _Request

    module, variables, _ = tiny
    # Pool of 3: the request needs 2 prompt blocks + 1 growth block.
    eng = make_paged(tiny, max_slots=2, cache_blocks=3)
    prompt = list(range(1, 2 * BS + 1))  # needs 2 blocks
    try:
        # Consume two blocks so the 2-block plan fails on chunk 1
        # AFTER registering chunk 0.
        held = []
        with eng._block_lock:
            for _ in range(2):
                b = eng._pool.alloc()
                eng._pool.hold(b)
                held.append(b)
        req = _Request(_np.asarray(prompt, _np.int32), 4, 0.0)
        assert eng._plan_prompt_blocks(req, 0) is None
        assert eng._prefix_index == {}  # no stale registration
        assert eng._pool.chain == {}
        with eng._block_lock:
            for b in held:
                eng._pool.drop(b)
        # And the request now completes CORRECTLY end-to-end.
        want = ref_greedy(module, variables, prompt, 4)
        got, _ = await eng.complete(prompt, max_new_tokens=4)
        assert got == want
    finally:
        await eng.close()


@pytest.mark.slow
async def test_prefill_enqueue_failure_releases_planned_blocks(tiny):
    """An enqueue-time prefill failure must release the planned
    blocks AND deregister provisional chains — leaked refs shrink the
    pool forever and stale chains alias later occupants' k/v
    (code-review r5)."""
    module, variables, _ = tiny
    eng = make_paged(tiny, max_slots=2, cache_blocks=6)
    prompt = list(range(1, BS + 5))  # one full + one partial block
    orig = eng._enqueue_prefill_group
    calls = {"n": 0}

    def flaky(group, slots, bucket, dest_rows=None):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("synthetic bucket OOM")
        return orig(group, slots, bucket, dest_rows)

    eng._enqueue_prefill_group = flaky
    try:
        from kfserving_tpu.protocol.errors import InferenceError

        with pytest.raises(InferenceError, match="prefill failed"):
            await asyncio.wait_for(
                eng.complete(prompt, max_new_tokens=4), timeout=30)
        # Pool fully recovered, no stale registrations.
        for _ in range(100):
            await asyncio.sleep(0.05)
            st = eng.stats()["paged"]
            if st["free_blocks"] == st["pool_blocks"]:
                break
        assert st["free_blocks"] == st["pool_blocks"], st
        assert eng._prefix_index == {}
        # The SAME prefix now serves correctly (previously: the stale
        # chain would hit an unwritten block).
        want = ref_greedy(module, variables, prompt, 4)
        got, _ = await eng.complete(prompt, max_new_tokens=4)
        assert got == want
    finally:
        await eng.close()


# --------------------------------------------- pallas paged kernel

KERNEL_BS, KERNEL_MB, KERNEL_NB = 128, 4, 8
KERNEL_LENGTHS = {
    "inside-a-block": [300, 40, 200],
    "on-a-block-edge": [128, 256, 384],
    "one": [1, 1, 1],
    "full-table": [512, 512, 511],
}
KERNEL_TABLE = [[0, 1, 2, 7], [3, 5, 6, 1], [0, 4, 2, 3]]  # 0 is shared


def _chat_mix():
    """24 rows of an 8-column table as `gpt2-large.chat` fills them:
    20 rows hold contexts of 50-770 tokens, 4 are free and still
    counting."""
    rng = np.random.default_rng(29)
    lengths = np.exp(rng.uniform(np.log(50), np.log(770), 24)).astype(int)
    free = [3, 9, 15, 21]
    lengths[free] = [2900, 1025, 640, 77]
    table = np.full((24, 8), -1, np.int32)
    for row in set(range(24)) - set(free):
        table[row, :-(-lengths[row] // KERNEL_BS)] = rng.integers(
            0, 64, -(-lengths[row] // KERNEL_BS))
    return lengths.tolist(), table.tolist(), free


def _wider_than_a_chunk():
    """8 rows of a 13-column table, wider than the blocks one loop
    iteration of the kernel takes (`blocks_per_iteration`: 2 or 4 of
    the narrow pools' here): rows of 1, 4, 5, 8, 9 and 13 blocks, so a
    row's last chunk is exactly full, one block over and ragged for
    either; a free row and a parked one between them; block 0 shared."""
    mb = 13
    blocks = [1, 4, 5, None, 8, 9, None, 13]
    lengths = [77, 4 * KERNEL_BS, 4 * KERNEL_BS + 1, 5000,
               8 * KERNEL_BS - 3, 8 * KERNEL_BS + 100,
               mb * KERNEL_BS + 1, mb * KERNEL_BS - 50]
    table, fresh = np.full((len(blocks), mb), -1, np.int32), iter(range(1, 99))
    for row, held in enumerate(blocks):
        if held is not None:
            table[row, :held] = [0] + [next(fresh) for _ in range(held - 1)]
    table[6, :3] = [next(fresh) for _ in range(3)]  # parked mid-prefill
    return lengths, table.tolist(), [3, 6]


# name -> (lengths, table, rows that walk nothing).  A table of None is
# KERNEL_TABLE with each row cut to its length, as the engine keeps it.
KERNEL_CASES = {
    **{name: (lengths, None, []) for name, lengths in
       KERNEL_LENGTHS.items()},
    # Freed between two live rows: its table row is all -1 and its
    # length went on counting past what a table covers.
    "free-row-stale-length": (
        [300, 2000, 200], [[0, 1, 2, -1], [-1] * 4, [0, 4, -1, -1]], [1]),
    # Parked on the position sentinel (max_seq + 1 with the step's own
    # token), its prefilled chunks still in its table.
    "parked-row": (
        [130, 513, 40], [[6, 2, -1, -1], [3, 5, -1, -1], [1, -1, -1, -1]],
        [1]),
    "every-row-free": ([700, 513, 90], [[-1] * 4] * 3, [0, 1, 2]),
    "whole-table-beside-one-token": ([1, 512, 1], None, []),
    "chat-mix-24x8": _chat_mix(),
    "wider-than-a-chunk-8x13": _wider_than_a_chunk(),
}


def _pools(rng, nb, bs, h, d, dtype="float32"):
    """One layer's K and V as [NB, BS, H, D] arrays (the layout the
    pool had before it went flat, and the reference's: float32 arrays
    of the values `dtype` holds), and the flat pools the ops take: the
    same bytes."""
    from kfserving_tpu.ops.paged_attention import pool_shape

    k4, v4 = (np.asarray(jnp.asarray(rng.normal(size=(nb, bs, h, d)),
                                     dtype), np.float32)
              for _ in range(2))
    flat = pool_shape(nb, bs, h, d)
    assert flat == (nb, bs, h * d)
    return k4, v4, jnp.asarray(k4.reshape(flat), dtype), \
        jnp.asarray(v4.reshape(flat), dtype)


def _attention_over_blocks(q, k4, v4, table, allowed):
    """Plain numpy attention over a [NB, BS, H, D] pool: q [B, L, H, D],
    allowed [B, L, MB*BS] true where a query may look."""
    b, mb = table.shape
    _, bs, h, d = k4.shape
    blocks = np.maximum(table, 0)
    k = k4[blocks].reshape(b, mb * bs, h, d)
    v = v4[blocks].reshape(b, mb * bs, h, d)
    logits = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    logits = np.where(allowed[:, None], logits, -np.inf)
    weights = np.exp(logits - logits.max(axis=-1, keepdims=True))
    weights /= weights.sum(axis=-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", weights, v)


@pytest.mark.parametrize("case", list(KERNEL_CASES))
@pytest.mark.parametrize("heads", [
    (20, 64, 1, "float32", 1), (16, 128, 1, "float32", 1),
    (4, 64, 1, "float32", 4), (2, 128, 4, "float32", 4),
    (2, 128, 16, "float32", 4), (4, 128, 8, "float32", 2),
    (4, 128, 8, "bfloat16", 4), (2, 128, 16, "bfloat16", 4)],
    ids=["20x64", "16x128", "4x64", "2x128-4q", "2x128-16q", "4x128-8q",
         "4x128-8q-bf16", "2x128-16q-bf16"])
def test_pallas_paged_kernel_matches_xla(heads, case):
    """The Pallas paged-decode kernel (interpret mode on CPU) on the
    flat pool matches the XLA gather reference, and both the plain
    attention over the same bytes as [NB, BS, H, D]: for heads that
    padded a tile (20 x 64), fill it (16 x 128) and are a fraction of
    one (4 x 64), and for 2 or 4 KV heads of 128 that 4, 8 or 16 query
    heads each read (grouped-query attention: query head j on KV head
    j // group); lengths inside a block, on its edge, of one token
    and of the whole table; a shared block and unallocated (-1) table
    tails.  The narrow pools take 2 or 4 blocks of a row in one loop
    iteration (a wide one takes 1), as many as the configurations with
    bfloat16 pools of these widths do.  A row that walks nothing (free,
    parked) comes back as zeros, whatever is beside it."""
    from kfserving_tpu.ops import paged_attention as pa

    h, d, group, dtype, chunk = heads
    lengths, table, idle = KERNEL_CASES[case]
    bs = KERNEL_BS
    tol = 2e-5 if dtype == "float32" else 2e-2
    rng = np.random.default_rng(h * 1000 + lengths[0])
    if table is None:
        table = np.asarray(KERNEL_TABLE, np.int32)
        for row, n in enumerate(lengths):
            table[row, -(-n // bs):] = -1
    table = np.asarray(table, np.int32)
    mb, nb = table.shape[1], max(KERNEL_NB, int(table.max()) + 1)
    assert pa.blocks_per_iteration(bs, h * d, dtype, mb) == min(chunk, mb)
    q = rng.normal(size=(len(lengths), 1, h * group, d)).astype(np.float32)
    k4, v4, pool_k, pool_v = _pools(rng, nb, bs, h, d, dtype)
    q = np.asarray(jnp.asarray(q, dtype), np.float32)  # as the kernel has it
    lens = jnp.asarray(lengths, jnp.int32)
    live = np.setdiff1d(np.arange(len(lengths)), idle)
    allowed = (np.arange(mb * bs)[None, None, :]
               < np.asarray(lengths)[:, None, None])
    want = _attention_over_blocks(q, np.repeat(k4, group, axis=2),
                                  np.repeat(v4, group, axis=2), table,
                                  allowed)
    xla = pa.paged_attention_xla(jnp.asarray(q, dtype), pool_k, pool_v,
                                 jnp.asarray(table), lens)
    got = np.asarray(pa.paged_attention_tpu(
        jnp.asarray(q, dtype), pool_k, pool_v, jnp.asarray(table), lens,
        interpret=True), np.float32)
    assert got.shape == q.shape
    np.testing.assert_allclose(np.asarray(xla, np.float32)[live],
                               want[live], rtol=tol, atol=tol)
    np.testing.assert_allclose(got[live], want[live], rtol=tol, atol=tol)
    np.testing.assert_array_equal(got[idle], 0.0)


def test_a_slots_stale_rows_reach_no_answer():
    """A loop iteration that holds fewer blocks than its VMEM slot has
    room for (a row's ragged last chunk) multiplies by the whole slot:
    what its copies did not write, memory never written (NaN in this
    interpreter) or another row's blocks, is masked out of the weights
    and must not reach the answer through 0 x NaN either.  Blocks that
    no row owns hold infinities and are never read."""
    from jax.experimental.pallas import tpu as pltpu

    from kfserving_tpu.ops import paged_attention as pa

    h, d, group, bs, mb, nb = 4, 128, 8, KERNEL_BS, 13, 24
    assert pa.blocks_per_iteration(bs, h * d, jnp.bfloat16, mb) == 4
    rng = np.random.default_rng(47)
    k4, v4, _, _ = _pools(rng, nb, bs, h, d, "bfloat16")
    table = np.full((4, mb), -1, np.int32)
    fresh = iter(range(1, nb))
    for row, held in enumerate([5, 1, 9, 2]):   # 4 + 1, 1, 4 + 4 + 1, 2
        table[row, :held] = [next(fresh) for _ in range(held)]
    lengths = [4 * bs + 17, 1, 8 * bs + 1, 2 * bs]
    unowned = np.setdiff1d(np.arange(1, nb), table)
    k4[unowned], v4[unowned] = np.inf, -np.inf
    q = np.asarray(jnp.asarray(rng.normal(size=(4, 1, h * group, d)),
                               jnp.bfloat16), np.float32)
    allowed = (np.arange(mb * bs)[None, None, :]
               < np.asarray(lengths)[:, None, None])
    want = _attention_over_blocks(q, np.repeat(k4, group, axis=2),
                                  np.repeat(v4, group, axis=2), table,
                                  allowed)
    flat = pa.pool_shape(nb, bs, h, d)
    got = np.asarray(pa.paged_attention_tpu(
        jnp.asarray(q, jnp.bfloat16),
        jnp.asarray(k4.reshape(flat), jnp.bfloat16),
        jnp.asarray(v4.reshape(flat), jnp.bfloat16), jnp.asarray(table),
        jnp.asarray(lengths, jnp.int32),
        interpret=pltpu.InterpretParams(uninitialized_memory="nan")),
        np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("chunk", [1, 4, 8])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_paged_walk_lists_each_rows_blocks_in_order(seed, chunk):
    """`paged_walk` against a plain loop: row r walks its first
    ceil(len / BS) columns as far as they are allocated, rows in order;
    a length of 0 or past the table's coverage walks nothing.  Listed
    for a kernel that takes `chunk` columns a loop iteration, a row
    appears once for every `chunk` of its columns, at the first of
    them; a chunk of 1 lists every column, and is what the argument's
    default lists."""
    from kfserving_tpu.ops.paged_attention import paged_walk

    rng = np.random.default_rng(seed)
    b, mb, bs = 9, 11, 16
    lengths = rng.integers(0, mb * bs + 1, b)
    lengths[rng.integers(0, b)] = mb * bs + 1      # parked
    lengths[rng.integers(0, b)] = 3 * mb * bs      # free, still counting
    table = rng.integers(0, 50, (b, mb)).astype(np.int32)
    for row in range(b):
        # mostly what the length needs; sometimes fewer, or none
        held = -(-int(lengths[row]) // bs) - int(rng.integers(0, 4) == 0)
        table[row, max(0, held) * int(rng.integers(0, 5) > 0):] = -1
    want = []
    for row in range(b):
        if not 0 < lengths[row] <= mb * bs:
            continue
        for column in range(-(-int(lengths[row]) // bs)):
            if table[row, column] < 0:
                break
            want.append(row * mb + column)
    each, total = paged_walk(jnp.asarray(table),
                             jnp.asarray(lengths, jnp.int32), bs)
    assert np.asarray(each)[:int(total[0])].tolist() == want
    want = [at for at in want if at % mb % chunk == 0]
    pairs, count = paged_walk(jnp.asarray(table),
                              jnp.asarray(lengths, jnp.int32), bs,
                              chunk=chunk)
    pairs, count = np.asarray(pairs), np.asarray(count)
    assert pairs.shape == (b * mb,) and count.shape == (1,)
    assert pairs.dtype == count.dtype == np.int32
    assert pairs[:count[0]].tolist() == want
    # what follows the count is never read, and still inside the table
    assert ((0 <= pairs) & (pairs < b * mb)).all()


@pytest.mark.parametrize("chunk", [None, 1, 2], ids=["rule", "1", "2"])
async def test_decode_waves_count_the_blocks_and_tokens_they_read(
        tiny, monkeypatch, chunk):
    """`kv_block_fill`: over the rows that hold a request when a wave is
    delivered and over its steps as far as the request's budget runs,
    context tokens (the step's own included) against the blocks that
    hold them.  `kv_blocks_per_iteration`: those blocks against the
    paged kernel's loop iterations, ceil(blocks / n) a row and step for
    the n blocks an iteration takes (the kernel's rule on this pool, or
    here 1, where they are the blocks, and 2)."""
    from kfserving_tpu.observability import metrics as obs
    from kfserving_tpu.ops import paged_attention as pa

    prompt, new, k = [5, 9, 2, 7, 11, 3, 8], 46, 4
    rule = pa.blocks_per_iteration(BS, 64, tiny[2].dtype, MAX_SEQ // BS)
    assert rule == 4    # the tiny pool is narrow: 2 heads of 32
    if chunk is not None:
        monkeypatch.setattr(pa, "blocks_per_iteration", lambda *_: chunk)
    n = chunk or rule
    eng = make_paged(tiny, max_slots=2, steps_per_call=k)
    eng.name = f"kv-counted-{n}"
    try:
        got, _ = await eng.complete(prompt, max_new_tokens=new)
        stats = eng.stats()
    finally:
        await eng.close()
    assert len(got) == new
    # Prefill answers the first token; the other 45 take 12 waves of 4
    # steps, the last of which has 3 steps past the request's end: the
    # row is parked for them and walks nothing.
    context = len(prompt) + 1 + np.arange(new - 1)
    tokens, blocks = int(context.sum()), int((-(-context // BS)).sum())
    iterations = int((-(-(-(-context // BS)) // n)).sum())
    assert eng._walked["global"].tolist() == [tokens, blocks, iterations]
    assert (iterations == blocks) == (n == 1)
    assert stats["kv_block_fill"] == round(tokens / (blocks * BS), 4)
    assert stats["kv_blocks_per_iteration"] == round(blocks / iterations, 4)
    for counter, value in (
            (obs.generator_decode_kv_context_tokens_total, tokens),
            (obs.generator_decode_kv_blocks_walked_total, blocks),
            (obs.generator_decode_kv_walk_iterations_total, iterations)):
        assert counter().labels(model=eng.name).value == value


# ------------------------------ the flat pool against [NB, BS, H, D]


def _written(pool4, values, table, positions):
    """`paged_write`'s contract on a [NB, BS, H, D] array, one position
    at a time: -1 blocks and positions past the table drop."""
    want = pool4.copy()
    bs, mb = pool4.shape[1], table.shape[1]
    for index in np.ndindex(positions.shape):
        pos = int(positions[index])
        blk = table[index[0], pos // bs] if pos // bs < mb else -1
        if blk >= 0:
            want[blk, pos % bs] = values[index]
    return want


@pytest.mark.parametrize("group", [1, 4], ids=["1q", "4q"])
@pytest.mark.parametrize("op", ["write-decode", "write-chunk", "insert",
                                "prefill-attention"])
def test_flat_pool_ops_match_the_4d_pool(op, group):
    """`paged_write` (both call shapes), `paged_insert` and
    `paged_prefill_attention_xla` take [.., H, D] activations and the
    flat pool; reshaped to [NB, BS, H, D] their results are what the
    same operations give on such an array.  The pool holds the KV
    heads (2 with `group` 4: what is written is theirs), and the query
    brings `group` heads for each."""
    from kfserving_tpu.ops import paged_attention as pa

    h, d, bs, nb, mb, b = (5 if group == 1 else 2), 8, 4, 7, 3, 3
    rng = np.random.default_rng(7)
    k4, v4, pool_k, pool_v = _pools(rng, nb, bs, h, d)
    table = np.asarray([[0, 1, -1], [2, 3, 4], [5, -1, -1]], np.int32)

    def unflat(pool):
        return np.asarray(pool).reshape(nb, bs, h, d)

    if op in ("write-decode", "write-chunk"):
        # Row 0 writes into its -1 block, row 2 past the table: drop.
        positions = np.asarray([9, 6, mb * bs + 2], np.int32)
        if op == "write-chunk":
            positions = positions[:, None] + np.arange(3, dtype=np.int32)
        k_step, v_step = (rng.normal(
            size=positions.shape + (h, d)).astype(np.float32)
            for _ in range(2))
        got_k, got_v = pa.paged_write(
            pool_k, pool_v, jnp.asarray(k_step), jnp.asarray(v_step),
            jnp.asarray(table), jnp.asarray(positions))
        np.testing.assert_array_equal(
            unflat(got_k), _written(k4, k_step, table, positions))
        np.testing.assert_array_equal(
            unflat(got_v), _written(v4, v_step, table, positions))
        assert not np.array_equal(unflat(got_k), k4)
    elif op == "insert":
        k_new, v_new = (rng.normal(
            size=(b, 2 * bs, h, d)).astype(np.float32) for _ in range(2))
        dest = np.asarray([[6, -1], [1, 0], [-1, -1]], np.int32)
        got_k, got_v = pa.paged_insert(
            pool_k, pool_v, jnp.asarray(k_new), jnp.asarray(v_new),
            jnp.asarray(dest), None)
        want_k, want_v = k4.copy(), v4.copy()
        for row, chunk in np.ndindex(dest.shape):
            if dest[row, chunk] >= 0:
                rows = slice(chunk * bs, (chunk + 1) * bs)
                want_k[dest[row, chunk]] = k_new[row, rows]
                want_v[dest[row, chunk]] = v_new[row, rows]
        np.testing.assert_array_equal(unflat(got_k), want_k)
        np.testing.assert_array_equal(unflat(got_v), want_v)
    else:
        q = rng.normal(size=(b, 3, h * group, d)).astype(np.float32)
        q_positions = np.asarray([[3, 4, 5], [9, 10, 11], [0, 1, 2]],
                                 np.int32)
        allowed = (np.arange(mb * bs)[None, None, :]
                   <= q_positions[:, :, None])
        got = pa.paged_prefill_attention_xla(
            jnp.asarray(q), pool_k, pool_v, jnp.asarray(table),
            jnp.asarray(q_positions))
        np.testing.assert_allclose(
            np.asarray(got),
            _attention_over_blocks(q, np.repeat(k4, group, axis=2),
                                   np.repeat(v4, group, axis=2), table,
                                   allowed),
            rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_pallas_paged_write_matches_the_scatter(dtype):
    """The decode step's write kernel (interpret mode on CPU) against
    `paged_write`'s scatter: rows at a tile's first and last position
    and at a block's, a row whose block is unallocated and one parked
    past the table (both move nothing), and every other position of
    the pools untouched."""
    from kfserving_tpu.ops import paged_attention as pa

    h, d, bs, nb = 4, 64, 128, 8
    rng = np.random.default_rng(3)
    k4, v4, pool_k, pool_v = _pools(rng, nb, bs, h, d)
    pool_k, pool_v = pool_k.astype(dtype), pool_v.astype(dtype)
    table = jnp.asarray([[0, 1], [2, -1], [3, 4], [5, -1], [6, 7]],
                        jnp.int32)
    positions = jnp.asarray([bs + 15, bs + 5, 2 * bs - 1, 1000, 0],
                            jnp.int32)
    k_step, v_step = (jnp.asarray(rng.normal(size=(5, h, d)), dtype)
                      for _ in range(2))
    want_k, want_v = pa.paged_write(pool_k, pool_v, k_step, v_step,
                                    table, positions)
    got_k, got_v = pa.paged_write_sharded(
        pool_k, pool_v, k_step, v_step,
        jnp.asarray([1, -1, 4, -1, 6], jnp.int32), positions % bs,
        interpret=True)
    assert np.asarray(want_k != pool_k).any(axis=-1).sum() == 3
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want_k))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))


def test_flat_pool_block_bytes_are_the_4d_blocks_bytes():
    """What leaves the device a block at a time (host tier, /kv/chains,
    the hand-off export) is `pool[idx][row].tobytes()`: out of the flat
    pool those are the bytes a [BS, H, D] block gave, so a payload
    written before the pool went flat reads back, and one written now
    lands (`_land_faultbacks`: frombuffer -> [BS, H*D] -> insert)."""
    from kfserving_tpu.ops import paged_attention as pa

    h, d, bs, nb = 5, 8, 4, 6
    rng = np.random.default_rng(11)
    k_new, v_new = (jnp.asarray(rng.normal(size=(2, 2 * bs, h, d)),
                                jnp.bfloat16) for _ in range(2))
    zeros = jnp.zeros(pa.pool_shape(nb, bs, h, d), jnp.bfloat16)
    dest = jnp.asarray([[4, 1], [0, -1]], jnp.int32)
    pool_k, pool_v = pa.paged_insert(zeros, zeros, k_new, v_new, dest,
                                     None)
    gathered = np.asarray(pool_k[jnp.asarray([4, 1, 0])])
    blocks_4d = [np.asarray(k_new[0, :bs]), np.asarray(k_new[0, bs:]),
                 np.asarray(k_new[1, :bs])]
    for row, block in enumerate(blocks_4d):
        assert block.shape == (bs, h, d)
        assert gathered[row].tobytes() == block.tobytes()
    # And back: a payload's bytes, inserted as [1, BS, H*D], are the
    # block again.
    payload = blocks_4d[1].tobytes()
    landed = np.frombuffer(payload, gathered.dtype).reshape(1, bs, h * d)
    again, _ = pa.paged_insert(zeros, zeros, jnp.asarray(landed),
                               jnp.asarray(landed),
                               jnp.asarray([[3]], jnp.int32), None)
    assert np.asarray(again[3]).tobytes() == payload
