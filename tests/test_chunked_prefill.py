"""Chunked prefill + adaptive pipeline depth (ISSUE 5 tentpole).

Parity bar: splitting a cold prompt's prefill into block-aligned
chunks that interleave with decode waves changes WHEN compute happens,
never WHAT comes out — token-for-token vs the monolithic prefill under
greedy AND seeded temperature, including a mid-prefill preemption that
restarts the chunked prefill from scratch.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kfserving_tpu.engine.generator import GenerationEngine, _Active
from kfserving_tpu.models.decoder import DecoderLM, decoder_tiny
from kfserving_tpu.protocol.errors import InvalidInput

MAX_SEQ = 128
BS = 16
CHUNK = 32


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
    ids = [int(t) for t in prompt]
    out = []
    for _ in range(steps):
        logits = module.apply(variables,
                              jnp.asarray([ids], jnp.int32))
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        ids.append(nxt)
    return out


def make_engine(tiny, chunk=CHUNK, **kw):
    module, variables, _ = tiny
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("prefill_buckets", [16, 32, 64, MAX_SEQ])
    kw.setdefault("block_size", BS)
    return GenerationEngine(module, variables,
                            prefill_chunk_tokens=chunk, **kw)


def prompt_of(n, stride=7):
    return [(i * stride) % 90 + 1 for i in range(n)]


# ------------------------------------------------------------- parity


async def test_chunked_greedy_matches_full_recompute(tiny):
    """THE parity criterion: a cold prompt prefilled in chunks (with a
    partial final chunk) decodes token-for-token like the no-cache
    full recompute."""
    module, variables, _ = tiny
    prompt = prompt_of(50)        # partial final chunk (50 = 32 + 18)
    eng = make_engine(tiny)
    try:
        want = ref_greedy(module, variables, prompt, 8)
        got, reason = await eng.complete(prompt, max_new_tokens=8)
        assert got == want
        assert reason == "length"
        assert eng.stats()["chunked_prefill"]["admissions"] == 1
    finally:
        await eng.close()


@pytest.mark.slow
async def test_chunked_boundary_cases(tiny):
    """Chunk/block boundary seams are invisible: exact-boundary
    prompt, one-past-boundary, final chunk exactly one block."""
    module, variables, _ = tiny
    cases = [
        prompt_of(2 * CHUNK),     # prompt exactly on a chunk boundary
        prompt_of(2 * CHUNK + 1),  # one past a boundary
        prompt_of(CHUNK + BS),    # final chunk exactly one block
    ]
    eng = make_engine(tiny)
    try:
        for prompt in cases:
            want = ref_greedy(module, variables, prompt, 8)
            got, reason = await eng.complete(prompt, max_new_tokens=8)
            assert got == want, len(prompt)
            assert reason == "length"
    finally:
        await eng.close()


async def test_chunked_seeded_temperature_matches_monolithic(tiny):
    """Seeded sampling: the chunked path must reproduce the monolithic
    engine's stream exactly (noise is keyed on (seed, position); the
    final chunk samples the first token with the same key AND the same
    sliced-head logits as monolithic prefill)."""
    prompt = prompt_of(50, stride=3)
    mono = make_engine(tiny, chunk=None)
    try:
        want, _ = await mono.complete(prompt, max_new_tokens=10,
                                      temperature=1.1, seed=42,
                                      top_k=20, top_p=0.9)
    finally:
        await mono.close()
    eng = make_engine(tiny)
    try:
        got, _ = await eng.complete(prompt, max_new_tokens=10,
                                    temperature=1.1, seed=42,
                                    top_k=20, top_p=0.9)
        assert eng.stats()["chunked_prefill"]["chunks_dispatched"] >= 2
    finally:
        await eng.close()
    assert got == want


@pytest.mark.slow
async def test_cold_prompt_beyond_largest_bucket(tiny):
    """Chunked prompts never ride a prefill bucket: a cold prompt
    longer than the largest bucket serves fine (monolithic engines
    still reject it)."""
    module, variables, _ = tiny
    prompt = prompt_of(90)
    eng = make_engine(tiny, prefill_buckets=[16, 32])
    try:
        want = ref_greedy(module, variables, prompt, 6)
        got, _ = await eng.complete(prompt, max_new_tokens=6)
        assert got == want
    finally:
        await eng.close()
    mono = make_engine(tiny, chunk=None, prefill_buckets=[16, 32])
    try:
        with pytest.raises(InvalidInput, match="largest prefill"):
            mono.submit(prompt, max_new_tokens=6)
    finally:
        mono.shutdown_nowait()


# --------------------------------------------------- decode interleave


@pytest.mark.slow
async def test_decode_waves_interleave_with_chunks(tiny):
    """The tentpole scheduling property: while a cold prompt's chunks
    land, decode waves for live streams keep dispatching BETWEEN them
    (the in-flight FIFO alternates kinds), and the live stream's
    output is unaffected."""
    module, variables, _ = tiny
    dispatch_log = []
    eng = make_engine(tiny, steps_per_call=1)
    orig_wave, orig_chunk = eng._enqueue_wave, eng._enqueue_chunk

    def wave_spy(*a, **kw):
        dispatch_log.append("wave")
        return orig_wave(*a, **kw)

    def chunk_spy(*a, **kw):
        dispatch_log.append("chunk")
        return orig_chunk(*a, **kw)

    eng._enqueue_wave, eng._enqueue_chunk = wave_spy, chunk_spy
    p_live = prompt_of(10, stride=5)
    want_live = ref_greedy(module, variables, p_live, 20)
    p_cold = prompt_of(3 * CHUNK + 5)
    want_cold = ref_greedy(module, variables, p_cold, 4)
    try:
        live = eng.generate(p_live, max_new_tokens=20)
        got_live = []
        async for token, fin in live:
            got_live.append(token)
            if len(got_live) == 3:
                break
        cold_task = asyncio.ensure_future(
            eng.complete(p_cold, max_new_tokens=4))
        async for token, fin in live:
            got_live.append(token)
        got_cold, _ = await cold_task
    finally:
        await eng.close()
    assert got_live == want_live
    assert got_cold == want_cold
    # Between the first and last chunk dispatch there was at least one
    # decode wave — the cold prefill did NOT land monolithically while
    # the live stream waited.
    chunk_idx = [i for i, k in enumerate(dispatch_log) if k == "chunk"]
    assert len(chunk_idx) >= 3
    interleaved = any(k == "wave" for k in
                      dispatch_log[chunk_idx[0]:chunk_idx[-1]])
    assert interleaved, dispatch_log


async def test_chunk_stall_bounded_vs_prompt(tiny):
    """Chunk accounting: a cold admission dispatches ceil(n/C) chunks
    (minus whole-chunk prefix hits), each a separate FIFO item."""
    eng = make_engine(tiny)
    try:
        await eng.complete(prompt_of(3 * CHUNK + 5), max_new_tokens=2)
        st = eng.stats()["chunked_prefill"]
        assert st["chunks_dispatched"] == 4
        assert st["chunk_tokens"] == CHUNK
        # Engine-level prefill counters: the request was admitted
        # through the chunked path, not a bucket prefill.
        assert eng.stats()["prefills"] == 0
        assert eng.stats()["prefill_requests"] == 1
    finally:
        await eng.close()


# ------------------------------------------------- prefix-cache reuse


@pytest.mark.slow
async def test_shared_chunks_skip_dispatch(tiny):
    """A re-run of the same cold prompt hits the chain-hash prefix
    index chunk-by-chunk: fully-shared non-final chunks skip their
    dispatch outright (the monolithic path recomputes and drops the
    writes) and the output is unchanged."""
    module, variables, _ = tiny
    prompt = prompt_of(3 * CHUNK)
    want = ref_greedy(module, variables, prompt, 6)
    eng = make_engine(tiny)
    try:
        got1, _ = await eng.complete(prompt, max_new_tokens=6)
        st1 = eng.stats()["chunked_prefill"]
        assert st1["chunks_skipped_shared"] == 0
        got2, _ = await eng.complete(prompt, max_new_tokens=6)
        st2 = eng.stats()["chunked_prefill"]
    finally:
        await eng.close()
    assert got1 == want
    assert got2 == want
    # 3 chunks; the final one always dispatches (it samples the first
    # token), the two earlier fully-shared ones skip.
    assert st2["chunks_skipped_shared"] == 2
    assert eng.prefix_hits >= 3


async def test_deferred_registration_no_premature_sharing(tiny):
    """Prefix registrations of a chunked prompt publish ONLY as each
    chunk dispatches — mid-prefill, later chunks' chains must not be
    visible (a sharer would read unwritten blocks)."""
    eng = make_engine(tiny)
    prompt = prompt_of(3 * CHUNK)
    try:
        req = eng.submit(prompt, max_new_tokens=4)
        # Poll until the first chunk has dispatched but the prefill
        # has not finished.
        for _ in range(200):
            await asyncio.sleep(0.005)
            if eng.prefill_chunks >= 1:
                break
        with eng._block_lock:
            mid_regs = len(eng._prefix_index)
        # At most the chunks dispatched so far may be registered
        # (2 blocks per 32-token chunk at BS=16).
        assert mid_regs <= 2 * eng.prefill_chunks
        tokens = []
        async for token, fin in eng.stream(req):
            if token is not None:
                tokens.append(token)
        with eng._block_lock:
            final_regs = len(eng._prefix_index)
        assert final_regs == 6  # all full blocks registered by the end
    finally:
        await eng.close()


async def test_duplicate_deferred_registration_survives_eviction(tiny):
    """Two identical cold prompts planned concurrently (both before
    either's chunks dispatch) allocate duplicate fresh blocks for the
    same chains.  Registration must keep ONE canonical index entry:
    the loser stays private, and evicting it must not delete the
    survivor's mapping (regression: the overwrite + unconditional
    eviction pop silently killed prefix reuse)."""
    from kfserving_tpu.engine.generator import _Request

    # Pool sized exactly for the two plans: post-registration there is
    # no free block left, so the re-allocation below MUST evict.
    eng = make_engine(tiny, cache_blocks=8)
    prompt = np.asarray(prompt_of(2 * CHUNK), np.int32)
    try:
        acts = []
        for slot in (0, 1):   # BOTH plan before EITHER registers —
            req = _Request(prompt_ids=prompt, max_new_tokens=1,
                           temperature=0.0)
            reg: dict = {}
            dest = eng._plan_prompt_blocks(req, slot, chunk_regs=reg)
            assert dest is not None
            assert len(reg) == 4   # all fresh: nothing published yet
            acts.append(_Active(req=req, length=prompt.size,
                                last_token=-1, generated=0,
                                prefilling=True, chunk_total=2,
                                chunk_dest=dest, chunk_regs=reg))
        for act in acts:          # — the deferred-registration race.
            eng._register_chunk_blocks(act, 0)
            eng._register_chunk_blocks(act, 1)
        with eng._block_lock:
            canonical = dict(eng._prefix_index)
            # The duplicate (slot 1) blocks are unregistered privates.
            assert len(canonical) == 4  # 2 chunks * 2 blocks, one set
        # Free both slots' blocks, then force eviction pressure: every
        # canonical entry must either survive or be popped WITH its
        # own block — never orphaned by a duplicate's eviction.
        for slot in (0, 1):
            with eng._block_lock:
                for c in range(prompt.size // BS):
                    eng._pool.drop(int(eng._pool.table[slot, c]))
                eng._pool.table[slot, :] = -1
        n_blocks = prompt.size // BS
        with eng._block_lock:
            taken = [eng._pool.alloc() for _ in range(n_blocks)]
            assert all(b is not None for b in taken)
            # One full set of canonical entries survives, each backed
            # by a block that still maps its chain.  (Pre-fix: the
            # duplicate's registration overwrote the index, and this
            # allocation evicted the LRU originals — unconditionally
            # popping the survivor's entries, leaving the index empty
            # with the duplicate blocks still resident.)
            assert len(eng._prefix_index) == n_blocks
            for chain, blk in eng._prefix_index.items():
                assert eng._pool.chain.get(blk) == chain
    finally:
        await eng.close()


# ------------------------------------------------ mid-prefill preempt


async def test_mid_prefill_preemption_resumes_exactly(tiny):
    """Pool pressure hitting while a cold prompt is mid-chunked-
    prefill: the prefilling slot yields its blocks (it has produced
    nothing), the live stream resumes first, and the cold request
    restarts its chunked prefill later — producing exactly the tokens
    an unpressured run would, greedy AND seeded."""
    module, variables, _ = tiny
    p_live = prompt_of(46, stride=5)   # 3 blocks, boundary-close
    p_cold = prompt_of(96, stride=3)   # 6 blocks, 3 chunks
    want_live = ref_greedy(module, variables, p_live, 10)
    ample = make_engine(tiny, max_slots=1)
    try:
        want_cold, _ = await ample.complete(
            p_cold, max_new_tokens=8, temperature=1.1, seed=9)
    finally:
        await ample.close()
    # 9 blocks: live (3 + growth) + cold (6) collide immediately.
    eng = make_engine(tiny, max_slots=4, cache_blocks=9,
                      steps_per_call=1, pipeline_depth=1)
    try:
        live_task = asyncio.ensure_future(
            eng.complete(p_live, max_new_tokens=10))
        # Let the live stream occupy its slot first.
        for _ in range(100):
            await asyncio.sleep(0.005)
            if any(s is not None for s in eng._slots):
                break
        cold_task = asyncio.ensure_future(
            eng.complete(p_cold, max_new_tokens=8, temperature=1.1,
                         seed=9))
        got_live, _ = await asyncio.wait_for(live_task, timeout=120)
        got_cold, _ = await asyncio.wait_for(cold_task, timeout=120)
        stats = eng.stats()
    finally:
        await eng.close()
    assert got_live == want_live
    assert got_cold == want_cold
    assert stats["paged"]["preemptions"] >= 1
    # The cold request was admitted (at least) twice: once before the
    # preemption, once to resume.
    assert stats["chunked_prefill"]["admissions"] >= 2


async def test_stale_growth_hold_clears_on_drained_pipeline(tiny):
    """Regression: the growth-starvation HOLD could outlive its
    reason — pool pressure preempts a mid-prefill slot, then the
    held streams finish from their in-flight waves and the slot table
    drains.  The idle branch `continue`d above the only reset, so the
    scheduler spun admission-gated with zero awaits: the preempted
    request sat in pending forever and the starved event loop took
    the whole server with it.  A drained pipeline must clear the
    hold.  (Pre-fix this test HANGS rather than fails — the spin
    starves the wait_for timer too.)"""
    eng = make_engine(tiny, max_slots=2, steps_per_call=1,
                      pipeline_depth=1)
    try:
        eng._growth_starved = True   # the stale HOLD a drain leaves
        got, reason = await asyncio.wait_for(
            eng.complete(prompt_of(40), max_new_tokens=4), timeout=60)
        assert reason == "length"
        assert len(got) == 4
        assert eng._growth_starved is False
    finally:
        await eng.close()


async def test_cancel_mid_prefill_releases_blocks(tiny):
    eng = make_engine(tiny, max_slots=2)
    try:
        req = eng.submit(prompt_of(3 * CHUNK + 5), max_new_tokens=50)
        # Cancel while chunks are (likely) still landing.
        for _ in range(100):
            await asyncio.sleep(0.002)
            if eng.prefill_chunks >= 1:
                break
        eng.cancel(req)
        token, reason = await asyncio.wait_for(req.out.get(),
                                               timeout=30)
        assert reason in ("cancelled",)
        total = eng.stats()["paged"]["pool_blocks"]
        for _ in range(200):
            await asyncio.sleep(0.05)
            st = eng.stats()["paged"]
            if st["free_blocks"] + st["reclaimable_blocks"] == total:
                break
        assert st["free_blocks"] + st["reclaimable_blocks"] == total
    finally:
        await eng.close()


# ---------------------------------------------------- adaptive depth


async def test_adaptive_depth_suppresses_garbage_tail_waves(tiny):
    """Uniform traffic whose finishes cluster: the adaptive governor
    must suppress the speculative wave that could only decode garbage
    — strictly less waste than fixed depth, identical output."""
    module, variables, _ = tiny
    prompts = [prompt_of(8, stride=s) for s in (3, 5, 7)]
    want = [ref_greedy(module, variables, p, 8) for p in prompts]
    results = {}
    for adaptive in (False, True):
        eng = make_engine(tiny, chunk=None, steps_per_call=2,
                          pipeline_depth=2, adaptive_depth=adaptive)
        try:
            outs = await asyncio.gather(*[
                eng.complete(p, max_new_tokens=8) for p in prompts])
            results[adaptive] = ([t for t, _ in outs], eng.stats())
        finally:
            await eng.close()
    assert results[True][0] == results[False][0] == want
    fixed, adapt = results[False][1], results[True][1]
    assert adapt["suppressed_waves"] >= 1
    assert fixed["suppressed_waves"] == 0
    assert adapt["wasted_token_steps"] <= fixed["wasted_token_steps"]
    assert adapt["adaptive_depth"] is True


async def test_adaptive_depth_keeps_pipelining_for_long_streams(tiny):
    """A stream with work far beyond the in-flight horizon still gets
    the configured depth — adaptive only trims the tail."""
    eng = make_engine(tiny, chunk=None, steps_per_call=1,
                      pipeline_depth=2, adaptive_depth=True)
    try:
        await eng.complete(prompt_of(6), max_new_tokens=24)
        stats = eng.stats()
    finally:
        await eng.close()
    # The governor trimmed ONLY the tail: a correct run suppresses the
    # couple of top-ups where the remaining budget already fits the
    # in-flight wave, while a governor wrongly pinning a long stream
    # at depth 1 suppresses one top-up per decode step (~20 here).
    # (stats["pipeline_depth"] is the CONFIGURED depth and can never
    # change — the effective depth rides "depth_effective".)
    assert 1 <= stats["suppressed_waves"] <= 4
    assert stats["depth_effective"] >= 1


# -------------------------------------------------------- validation


def test_chunked_validation(tiny):
    module, variables, _ = tiny
    # No block_size: 16 is derived from these buckets, and chunks
    # are held to it like to an explicit one.
    derived = GenerationEngine(module, variables, max_slots=2,
                               max_seq=MAX_SEQ,
                               prefill_buckets=[16, MAX_SEQ],
                               prefill_chunk_tokens=32)
    try:
        assert derived.block_size == 16
    finally:
        derived.shutdown_nowait()
    with pytest.raises(InvalidInput, match="multiple of block_size"):
        make_engine(tiny, chunk=24)  # 24 % 16 != 0
    with pytest.raises(InvalidInput, match="exceeds max_seq"):
        make_engine(tiny, chunk=MAX_SEQ * 2)


def test_new_metric_families_lint(tiny):
    """The PR's metric families obey the house naming rules."""
    from kfserving_tpu.observability import metrics as obs
    from kfserving_tpu.observability.registry import REGISTRY
    from kfserving_tpu.tools.check_metrics import lint_families

    obs.generator_prefill_chunks_total()
    obs.generator_prefill_chunk_stall_ms()
    obs.generator_pipeline_depth()
    obs.generator_suppressed_waves_total()
    fams = {n: k for n, k in REGISTRY.families().items()
            if "generator" in n}
    assert len(fams) >= 4
    assert lint_families(fams) == []


# ------------------------------------------------ served-model plumb


def _write_gen_dir(tmp_path, name, extra):
    import json as _json

    d = tmp_path / name
    d.mkdir()
    cfg = {
        "architecture": "decoder_tiny",
        "arch_kwargs": {"num_layers": 2, "hidden_size": 64,
                        "num_heads": 2, "intermediate_size": 128,
                        "max_seq": 128},
        "max_slots": 2, "max_seq": 128,
        "prefill_buckets": [16, 32, 64, 128],
        "max_new_tokens": 6, "tokenizer": "byte",
        "block_size": 16,
    }
    cfg.update(extra)
    (d / "config.json").write_text(_json.dumps(cfg))
    return str(d)


def test_chunked_config_reaches_engine(tmp_path):
    """prefill_chunk_tokens / adaptive_depth in config.json plumb
    through GenerativeConfig into the engine."""
    from kfserving_tpu.predictors.llm import GenerativeModel

    m = GenerativeModel("plumb", _write_gen_dir(
        tmp_path, "plumb", {"prefill_chunk_tokens": 32,
                            "adaptive_depth": False}))
    m.load()
    try:
        assert m.engine.prefill_chunk_tokens == 32
        assert m.engine.adaptive_depth is False
        assert m.engine_stats()["chunked_prefill"][
            "chunk_tokens"] == 32
    finally:
        m.unload()


@pytest.mark.slow
async def test_chunked_config_serves_over_http(tmp_path):
    """prefill_chunk_tokens in config.json reaches the engine and the
    served output matches the monolithic config's."""
    import aiohttp

    from kfserving_tpu.predictors.llm import GenerativeModel
    from kfserving_tpu.server.app import ModelServer

    chunked = GenerativeModel("chunked", _write_gen_dir(
        tmp_path, "chunked", {"prefill_chunk_tokens": 32}))
    chunked.load()
    assert chunked.engine.prefill_chunk_tokens == 32
    assert chunked.engine.adaptive_depth is True
    mono = GenerativeModel("mono", _write_gen_dir(tmp_path, "mono",
                                                  {}))
    mono.load()
    server = ModelServer(http_port=0)
    await server.start_async([chunked, mono], host="127.0.0.1")
    base = f"http://127.0.0.1:{server.http_port}"
    # > 32 byte-tokens: cold on the chunked model.
    prompt = "a cold prompt long enough to be chunked into pieces"
    try:
        async with aiohttp.ClientSession() as s:
            outs = {}
            for name in ("chunked", "mono"):
                async with s.post(
                        f"{base}/v2/models/{name}/generate",
                        json={"text_input": prompt}) as r:
                    assert r.status == 200, await r.text()
                    outs[name] = (await r.json())["text_output"]
        assert outs["chunked"] == outs["mono"]
        assert chunked.engine_stats()[
            "chunked_prefill"]["admissions"] >= 1
    finally:
        await server.stop_async()
