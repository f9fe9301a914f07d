"""Decoder model + GenerationEngine tests (VERDICT r3 item 1).

Done-criteria from the verdict: CPU-mesh tests for cache correctness
(prefix parity with full recompute) and scheduler invariants.  The
reference has no generative serving; the contract extended here is the
predictor plugin boundary (reference pkg/apis/serving/v1beta1/
predictor.go:33-59) and the batcher response shape
(pkg/batcher/handler.go:129-150).
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
    """Teacher-forcing baseline: recompute the FULL forward pass for
    every generated token (no cache).  The engine's cached path must
    reproduce this exactly."""
    ids = [int(t) for t in prompt]
    out = []
    for _ in range(steps):
        logits = module.apply(variables,
                              jnp.asarray([ids], jnp.int32))
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        ids.append(nxt)
    return out


def make_engine(tiny, **kw):
    module, variables, _ = tiny
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("prefill_buckets", [8, 16, 32, MAX_SEQ])
    return GenerationEngine(module, variables, **kw)


# ------------------------------------------------------ cache parity


def test_prefill_logits_match_full_forward(tiny):
    """Suffix-padded prefill (bucketed) must produce the same logits at
    real positions as the unpadded full forward — bucket padding never
    leaks into the cache or the sampled token."""
    module, variables, _ = tiny
    prompt = jnp.asarray([[5, 9, 2, 7, 11]], jnp.int32)
    full = module.apply(variables, prompt)
    padded = jnp.zeros((1, 16), jnp.int32).at[:, :5].set(prompt)
    logits, caches = module.apply(variables, padded,
                                  kv_lengths=jnp.asarray([5]),
                                  return_cache=True)
    np.testing.assert_allclose(np.asarray(full),
                               np.asarray(logits[:, :5]),
                               rtol=2e-4, atol=2e-4)
    assert len(caches) == 2  # per layer
    assert caches[0][0].shape == (1, 16, 2, 32)


@pytest.mark.slow
async def test_engine_greedy_matches_full_recompute(tiny):
    """THE cache-correctness criterion: incremental decode through the
    slot cache reproduces full-recompute greedy token-for-token."""
    module, variables, _ = tiny
    prompt = [5, 9, 2, 7, 11]
    want = ref_greedy(module, variables, prompt, 12)
    eng = make_engine(tiny, max_slots=1)
    try:
        got, reason = await eng.complete(prompt, max_new_tokens=12)
    finally:
        await eng.close()
    assert got == want
    assert reason == "length"


@pytest.mark.slow
async def test_concurrent_requests_match_isolated(tiny):
    """Slots sharing one decode batch must not influence each other:
    every concurrent result equals its isolated baseline."""
    module, variables, _ = tiny
    prompts = [[3, 1, 4], [1, 5, 9, 2, 6, 5], [35, 8, 97, 9, 3, 2, 38,
                                               4, 6]]
    want = [ref_greedy(module, variables, p, 8) for p in prompts]
    eng = make_engine(tiny, max_slots=4)
    try:
        got = await asyncio.gather(*[
            eng.complete(p, max_new_tokens=8) for p in prompts])
    finally:
        await eng.close()
    for (tokens, reason), expected in zip(got, want):
        assert tokens == expected
        assert reason == "length"


async def test_mid_flight_admission(tiny):
    """Continuous batching: a request arriving while another is decoding
    joins at a step boundary; neither result changes."""
    module, variables, _ = tiny
    p_a, p_b = [7, 7, 3], [2, 8]
    want_a = ref_greedy(module, variables, p_a, 16)
    want_b = ref_greedy(module, variables, p_b, 6)
    eng = make_engine(tiny, max_slots=2)
    try:
        got_a = []
        gen_a = eng.generate(p_a, max_new_tokens=16)
        # Consume a few of A's tokens so A is provably mid-flight...
        async for token, fin in gen_a:
            got_a.append(token)
            if len(got_a) == 3:
                break
        # ...then admit B and drain both.
        task_b = asyncio.ensure_future(
            eng.complete(p_b, max_new_tokens=6))
        async for token, fin in gen_a:
            got_a.append(token)
        tokens_b, _ = await task_b
    finally:
        await eng.close()
    assert got_a == want_a
    assert tokens_b == want_b
    stats = eng.stats()
    assert stats["prefills"] == 2
    assert stats["requests_finished"] == 2
    assert 0.0 < stats["slot_occupancy"] <= 1.0


async def test_more_requests_than_slots(tiny):
    """Queueing invariant: with 2 slots and 5 requests, everything
    completes and matches its baseline (admission order irrelevant for
    greedy)."""
    module, variables, _ = tiny
    prompts = [[i + 1, i + 2] for i in range(5)]
    want = [ref_greedy(module, variables, p, 5) for p in prompts]
    eng = make_engine(tiny, max_slots=2)
    try:
        got = await asyncio.gather(*[
            eng.complete(p, max_new_tokens=5) for p in prompts])
    finally:
        await eng.close()
    assert [t for t, _ in got] == want


async def test_multistep_decode_matches_single_step(tiny):
    """steps_per_call=4 (K decode steps per device dispatch, lax.scan)
    reproduces K=1 greedy token-for-token — the RTT-amortization knob
    changes dispatch granularity, never results."""
    module, variables, _ = tiny
    prompts = [[5, 9, 2], [7, 1, 4, 4, 2]]
    want = [ref_greedy(module, variables, p, 11) for p in prompts]
    eng = make_engine(tiny, max_slots=2, steps_per_call=4)
    try:
        got = await asyncio.gather(*[
            eng.complete(p, max_new_tokens=11) for p in prompts])
        stats = eng.stats()
    finally:
        await eng.close()
    for (tokens, reason), expected in zip(got, want):
        assert tokens == expected  # 11 tokens though 11 % 4 != 0
        assert reason == "length"
    # Far fewer dispatches than token steps.
    assert stats["decode_steps"] < stats["token_steps"]
    assert stats["steps_per_call"] == 4


async def test_multistep_eos_truncates_chunk(tiny):
    """An EOS mid-chunk stops that stream at the EOS — the chunk's
    remaining tokens are never delivered."""
    module, variables, _ = tiny
    prompt = [5, 9, 2, 7, 11]
    ref = ref_greedy(module, variables, prompt, 12)
    eos = ref[5]  # lands mid-chunk for K=4
    first_eos = ref.index(eos)
    eng = make_engine(tiny, max_slots=1, eos_id=eos, steps_per_call=4)
    try:
        tokens, reason = await eng.complete(prompt, max_new_tokens=12)
    finally:
        await eng.close()
    assert reason == "eos"
    assert tokens == ref[:first_eos]


async def test_multistep_budget_capacity_clamp(tiny):
    """A budget ending mid-chunk delivers exactly the budget, and the
    cache-capacity clamp holds under K>1 (device steps may overrun a
    freed slot's tail; delivered tokens never do)."""
    module, variables, _ = tiny
    prompt = list(range(1, 31))  # 30 tokens; capacity 64-30=34
    eng = make_engine(tiny, max_slots=1, steps_per_call=8)
    try:
        tokens, reason = await eng.complete(prompt,
                                            max_new_tokens=10_000)
    finally:
        await eng.close()
    assert len(tokens) == MAX_SEQ - 30
    assert reason == "length"


# ----------------------------------------------------- stop conditions


async def test_eos_stops_generation(tiny):
    module, variables, _ = tiny
    prompt = [5, 9, 2, 7, 11]
    ref = ref_greedy(module, variables, prompt, 12)
    # Make the 4th generated token the EOS: generation must stop there
    # and NOT emit it as content.
    eos = ref[3]
    first_eos = ref.index(eos)
    eng = make_engine(tiny, max_slots=1, eos_id=eos)
    try:
        tokens, reason = await eng.complete(prompt, max_new_tokens=12)
    finally:
        await eng.close()
    assert reason == "eos"
    assert tokens == ref[:first_eos]
    assert eos not in tokens


async def test_budget_clamped_to_cache_capacity(tiny):
    """max_new_tokens past max_seq is clamped, not an error — the slot
    cache is the capacity contract."""
    module, variables, _ = tiny
    prompt = list(range(1, 31))  # 30 tokens, max_seq 64
    eng = make_engine(tiny, max_slots=1)
    try:
        tokens, reason = await eng.complete(prompt,
                                            max_new_tokens=10_000)
    finally:
        await eng.close()
    assert len(tokens) == MAX_SEQ - 30
    assert reason == "length"


async def test_temperature_sampling_varies_and_greedy_does_not(tiny):
    module, variables, _ = tiny
    prompt = [4, 2]
    eng = make_engine(tiny, max_slots=2, rng_seed=0)
    try:
        g1, _ = await eng.complete(prompt, max_new_tokens=8,
                                   temperature=0.0)
        g2, _ = await eng.complete(prompt, max_new_tokens=8,
                                   temperature=0.0)
        hot = [await eng.complete(prompt, max_new_tokens=8,
                                  temperature=5.0) for _ in range(4)]
    finally:
        await eng.close()
    assert g1 == g2  # greedy is deterministic
    # At high temperature some draw differs from greedy with
    # overwhelming probability across 4 runs of 8 tokens.
    assert any(t != g1 for t, _ in hot)


# ------------------------------------------------------- validation


async def test_request_validation(tiny):
    eng = make_engine(tiny, max_slots=1)
    try:
        with pytest.raises(InvalidInput, match="empty"):
            await eng.complete([], max_new_tokens=4)
        with pytest.raises(InvalidInput, match="exceeds"):
            await eng.complete(list(range(MAX_SEQ + 1)),
                               max_new_tokens=4)
        with pytest.raises(InvalidInput, match="max_new_tokens"):
            await eng.complete([1], max_new_tokens=0)
    finally:
        await eng.close()


async def test_streaming_yields_incrementally(tiny):
    """generate() is a live stream: tokens arrive one by one, in order,
    and concatenate to the complete() result."""
    module, variables, _ = tiny
    prompt = [9, 9, 1]
    eng = make_engine(tiny, max_slots=1)
    try:
        seen = []
        async for token, fin in eng.generate(prompt, max_new_tokens=6):
            if token is not None:
                seen.append(token)
        want = ref_greedy(module, variables, prompt, 6)
    finally:
        await eng.close()
    assert seen == want


def test_cache_bytes_accounting(tiny):
    module, variables, cfg = tiny
    eng = GenerationEngine(module, variables, max_slots=4,
                           max_seq=MAX_SEQ)
    # layers * k+v * S * max_seq * H * D * itemsize
    want = 2 * 2 * 4 * MAX_SEQ * 2 * 32 * 4  # float32 tiny config
    assert eng.cache_bytes() == want
    assert eng.param_bytes() > 0


# ------------------------------------------------ parameter residency


def _host_tree(variables, kind, tmp_path):
    """`variables` as host arrays: plain np arrays, or read-only
    memmap views like a param_cache hit serves."""
    if kind == "ndarray":
        return jax.tree.map(np.asarray, variables)
    leaves, treedef = jax.tree.flatten(variables)
    views = []
    for i, leaf in enumerate(leaves):
        path = tmp_path / f"leaf{i}.bin"
        np.asarray(leaf).tofile(path)
        views.append(np.memmap(path, dtype=leaf.dtype, mode="r",
                               shape=leaf.shape))
    return jax.tree.unflatten(treedef, views)


@pytest.mark.parametrize("kind", ["ndarray", "memmap"])
def test_host_parameters_are_placed_at_construction(tiny, kind,
                                                    tmp_path):
    """A host tree handed to the engine is on the engine's device
    when the constructor returns: a jitted call would otherwise
    transfer every host leaf again with every launch."""
    module, variables, _ = tiny
    host = _host_tree(variables, kind, tmp_path)
    eng = GenerationEngine(module, host, max_slots=4, max_seq=MAX_SEQ)
    pool_devices = eng._caches[0][0].devices()
    leaves = jax.tree.leaves(eng.variables)
    assert len(leaves) == len(jax.tree.leaves(host))
    for leaf in leaves:
        assert isinstance(leaf, jax.Array), type(leaf)
        assert not isinstance(leaf, np.ndarray)
        assert leaf.devices() == pool_devices
    for placed, given in zip(leaves, jax.tree.leaves(host)):
        assert placed.dtype == given.dtype  # stored precision kept
        np.testing.assert_array_equal(np.asarray(placed), given)
    assert eng.stats()["params_resident_bytes"] == eng.param_bytes()


def test_device_parameters_are_kept_as_given(tiny):
    """Leaves that are already device arrays pass through the
    constructor untouched (the very same arrays: no copy)."""
    module, variables, _ = tiny
    eng = make_engine(tiny)
    for kept, given in zip(jax.tree.leaves(eng.variables),
                           jax.tree.leaves(variables)):
        assert kept is given
    assert eng.stats()["params_resident_bytes"] == eng.param_bytes()


def test_draft_parameters_are_placed_with_the_target(tiny):
    """The draft tree goes through the same placement, and the
    resident-bytes figure counts both models."""
    module, variables, _ = tiny
    host = jax.tree.map(np.asarray, variables)
    eng = make_engine(tiny, block_size=16,
                      prefill_buckets=[16, MAX_SEQ], speculative={
        "tokens": 2, "draft_module": module,
        "draft_variables": host, "draft_window": 8})
    pool_devices = eng._caches[0][0].devices()
    for leaf in jax.tree.leaves(eng.draft_variables):
        assert isinstance(leaf, jax.Array), type(leaf)
        assert leaf.devices() == pool_devices
    assert eng.draft_param_bytes() == eng.param_bytes() > 0
    assert (eng.stats()["params_resident_bytes"]
            == eng.param_bytes() + eng.draft_param_bytes())
    assert (eng.stats()["speculative"]["draft_param_bytes"]
            == eng.draft_param_bytes())


@pytest.mark.parametrize("explicit", [False, True],
                         ids=["derived", "explicit"])
async def test_host_and_placed_trees_generate_identically(tiny,
                                                          explicit):
    """Placement moves bytes, not mathematics: an engine built from
    the host tree and one built from a pre-placed tree give bit-equal
    greedy tokens and log-probabilities."""
    module, variables, _ = tiny
    # Unset, buckets [16, 64] derive blocks of 16: two different pools.
    kw = {"block_size": 8} if explicit else {}
    host = jax.tree.map(np.asarray, variables)
    placed = jax.device_put(host)
    outs = []
    for tree in (host, placed):
        eng = GenerationEngine(module, tree, max_slots=2,
                               max_seq=MAX_SEQ,
                               prefill_buckets=[16, MAX_SEQ], **kw)
        try:
            req = eng.submit([5, 9, 2, 7, 11], max_new_tokens=8,
                             logprobs=3)
            tokens = [t async for t, _ in eng.stream(req)
                      if t is not None]
        finally:
            await eng.close()
        outs.append((tokens, list(req.lp_chosen),
                     [list(top) for top in req.lp_top]))
    assert len(outs[0][0]) == 8
    assert outs[0] == outs[1]


async def test_decode_failure_fails_all_inflight(tiny):
    """A device failure mid-decode must surface as InferenceError on
    every in-flight request — never a hung awaiter (code-review r4)."""
    from kfserving_tpu.protocol.errors import InferenceError

    eng = make_engine(tiny, max_slots=2)
    try:
        orig = eng._fetch_wave
        calls = []

        def boom(toks_h, lp_h):
            # Let the prefill item's fetch through (a prefill failure
            # is group-scoped, tested separately); fail the DECODE
            # wave fetch — that one is global.
            if not calls:
                calls.append(1)
                return orig(toks_h, lp_h)
            raise RuntimeError("synthetic XLA failure")

        eng._fetch_wave = boom
        with pytest.raises(InferenceError, match="generation failed"):
            # Generous bound: this is a hang guard, not the assertion —
            # first-call compiles under full-suite load have blown 10s.
            await asyncio.wait_for(
                eng.complete([1, 2, 3], max_new_tokens=8), timeout=60)
        # The engine recovers for new work once the fault clears.
        eng._fetch_wave = orig
        tokens, reason = await asyncio.wait_for(
            eng.complete([1, 2, 3], max_new_tokens=4), timeout=30)
        assert len(tokens) == 4
    finally:
        await eng.close()


async def test_prefill_failure_fails_only_that_group(tiny):
    from kfserving_tpu.protocol.errors import InferenceError

    module, variables, _ = tiny
    want = ref_greedy(module, variables, [5, 5], 4)
    eng = make_engine(tiny, max_slots=2)
    try:
        orig = eng._enqueue_prefill_group
        calls = {"n": 0}

        def flaky(group, slots, bucket, dest_rows=None):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("synthetic prefill OOM")
            return orig(group, slots, bucket, dest_rows)

        eng._enqueue_prefill_group = flaky
        with pytest.raises(InferenceError, match="prefill failed"):
            await asyncio.wait_for(
                eng.complete([9, 9], max_new_tokens=4), timeout=10)
        tokens, _ = await asyncio.wait_for(
            eng.complete([5, 5], max_new_tokens=4), timeout=30)
        assert tokens == want
    finally:
        await eng.close()


_REFUSAL = ("RESOURCE_EXHAUSTED: Error allocating device buffer: "
            "Attempting to allocate 10.00M. That was not possible. "
            "There are 3.29M free.; (0x0x0_HBM0)")


@pytest.mark.parametrize("explicit", [False, True],
                         ids=["derived", "explicit"])
async def test_prefill_refused_for_memory_is_taken_again_smaller(
        tiny, explicit):
    """The runtime refuses a prefill launch's output buffers when the
    chip is full (seen on the v5e once launches stopped waiting on a
    parameter transfer).  Nothing ran, so no request fails: the group
    goes back to the queue and rides dispatches of half the refused
    row count from then on."""
    module, variables, _ = tiny
    # Each fills its row of the 16 bucket whatever the block size.
    prompts = [[5, 5] * 6, [7, 1, 3] * 4, [2] * 9, [9, 9, 4] * 5]
    wants = [ref_greedy(module, variables, p, 4) for p in prompts]
    kw = {"block_size": 8} if explicit else {}
    eng = make_engine(tiny, max_slots=4,
                      prefill_buckets=[16, MAX_SEQ], **kw)
    try:
        real = eng._prefill
        rows_seen = []

        def refusing(variables, ids, *rest):
            rows_seen.append(ids.shape[0])
            if ids.shape[0] > 2:
                raise ValueError(_REFUSAL)
            return real(variables, ids, *rest)

        eng._prefill = refusing
        reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
        outs = await asyncio.wait_for(
            asyncio.gather(*(_drain(eng, r) for r in reqs)), timeout=60)
        assert outs == wants
        assert rows_seen[0] == 4 and set(rows_seen[1:]) <= {1, 2}
        stats = eng.stats()
        assert stats["prefill_rows_cap"] == 2
        assert stats["requests_finished"] == 4
    finally:
        await eng.close()


async def test_prefill_of_one_row_refused_fails_that_request(tiny):
    """One row cannot be made smaller: its refusal is that request's
    failure, as any other enqueue failure, and the engine goes on."""
    from kfserving_tpu.protocol.errors import InferenceError

    module, variables, _ = tiny
    want = ref_greedy(module, variables, [5, 5], 4)
    eng = make_engine(tiny, max_slots=2)
    try:
        real = eng._prefill
        left = {"n": 1}

        def refusing(*args):
            if left["n"]:
                left["n"] -= 1
                raise ValueError(_REFUSAL)
            return real(*args)

        eng._prefill = refusing
        with pytest.raises(InferenceError, match="prefill failed"):
            await asyncio.wait_for(
                eng.complete([9, 9], max_new_tokens=4), timeout=10)
        tokens, _ = await asyncio.wait_for(
            eng.complete([5, 5], max_new_tokens=4), timeout=30)
        assert tokens == want
        assert eng.stats()["prefill_rows_cap"] == 0
    finally:
        await eng.close()


async def test_burst_prefills_share_one_dispatch(tiny):
    """A burst of same-bucket arrivals rides ONE prefill dispatch (the
    padded batch scatters into all their slots at once); results still
    match isolated baselines.  Mixed buckets split, FIFO preserved."""
    module, variables, _ = tiny
    prompts = [[3, 1], [4, 1], [5, 9]]  # all in the 8-bucket
    want = [ref_greedy(module, variables, p, 6) for p in prompts]
    eng = make_engine(tiny, max_slots=4)
    try:
        # Submit the burst before the scheduler wakes: one group.
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        results = await asyncio.gather(*[
            _drain(eng, r) for r in reqs])
        stats = eng.stats()
    finally:
        await eng.close()
    assert results == want
    assert stats["prefills"] == 1  # one dispatch for the whole burst
    assert stats["prefill_requests"] == 3

    # Mixed buckets: front-run grouping splits at the bucket change.
    eng2 = make_engine(tiny, max_slots=4)
    try:
        mixed = [[3, 1], list(range(1, 13)), [5, 9]]  # 8, 16, 8
        want2 = [ref_greedy(module, variables, p, 4) for p in mixed]
        reqs2 = [eng2.submit(p, max_new_tokens=4) for p in mixed]
        results2 = await asyncio.gather(*[
            _drain(eng2, r) for r in reqs2])
        stats2 = eng2.stats()
    finally:
        await eng2.close()
    assert results2 == want2
    assert stats2["prefills"] == 3  # 8 | 16 | 8 — FIFO, no jumping
    assert stats2["prefill_requests"] == 3


async def _drain(eng, req):
    tokens = []
    async for token, fin in eng.stream(req):
        if token is not None:
            tokens.append(token)
    return tokens


async def test_close_drains_inflight_awaiters(tiny):
    """close() with a request mid-flight must not strand its awaiter:
    the stream either finishes normally (close raced completion) or
    raises InferenceError — it NEVER hangs."""
    from kfserving_tpu.protocol.errors import InferenceError

    eng = make_engine(tiny, max_slots=1)
    gen = eng.generate([1, 2, 3], max_new_tokens=10_000)
    token, _ = await asyncio.wait_for(gen.__anext__(), timeout=30)
    assert token is not None

    async def drain_all():
        try:
            async for _ in gen:
                pass
        except InferenceError:
            return "error"
        return "done"

    task = asyncio.ensure_future(asyncio.wait_for(drain_all(), 15))
    await eng.close()
    assert await task in ("error", "done")


async def test_engine_idle_loop_restarts(tiny):
    """The scheduler task dies when idle and restarts on the next
    request — no busy loop between requests."""
    module, variables, _ = tiny
    prompt = [3, 2, 1]
    want = ref_greedy(module, variables, prompt, 4)
    eng = make_engine(tiny, max_slots=1)
    try:
        got1, _ = await eng.complete(prompt, max_new_tokens=4)
        # Wait past the idle timeout so the loop task exits.
        for _ in range(25):
            await asyncio.sleep(0.1)
            if eng._loop_task.done():
                break
        assert eng._loop_task.done()
        got2, _ = await eng.complete(prompt, max_new_tokens=4)
    finally:
        await eng.close()
    assert got1 == want and got2 == want


# ------------------------------------------------------ cancellation


async def test_cancel_active_request_frees_slot(tiny):
    """cancel() on an in-flight request frees its slot so a waiting
    request gets admitted — the client-disconnect path must not decode
    to the budget for nobody."""
    eng = make_engine(tiny, max_slots=1)
    try:
        req = eng.submit([1, 2, 3], max_new_tokens=10_000)
        stream = eng.stream(req)
        token, _ = await asyncio.wait_for(stream.__anext__(), timeout=30)
        assert token is not None
        eng.cancel(req)
        # The slot is free: a second request completes.
        got, reason = await asyncio.wait_for(
            eng.complete([4, 5], max_new_tokens=3), timeout=30)
        assert len(got) == 3 and reason == "length"
        # The cancelled stream sees a terminal event.
        async for _, fin in stream:
            if fin is not None:
                assert fin == "cancelled"
                break
    finally:
        await eng.close()


async def test_cancel_pending_request(tiny):
    """cancel() removes a queued (not yet prefilled) request."""
    eng = make_engine(tiny, max_slots=1)
    try:
        # Fill the one slot so the second submit stays pending.
        hog = eng.submit([9, 8, 7], max_new_tokens=10_000)
        hog_stream = eng.stream(hog)
        await asyncio.wait_for(hog_stream.__anext__(), timeout=30)
        victim = eng.submit([1, 2], max_new_tokens=8)
        assert victim in eng._pending
        eng.cancel(victim)
        assert victim not in eng._pending
        eng.cancel(hog)
    finally:
        await eng.close()


async def test_cancel_finished_request_is_noop(tiny):
    eng = make_engine(tiny, max_slots=1)
    try:
        req = eng.submit([1, 2, 3], max_new_tokens=2)
        tokens = []
        async for t, fin in eng.stream(req):
            if t is not None:
                tokens.append(t)
        eng.cancel(req)  # must not raise or corrupt slots
        got, _ = await eng.complete([1, 2, 3], max_new_tokens=2)
        assert got == tokens
    finally:
        await eng.close()


def test_attn_fn_prefill_returns_cache(tiny):
    """A pluggable attn_fn (sequence-parallel serving) must still
    produce per-layer k/v for return_cache=True — the generation
    engine's insert scatter needs real tensors, not Nones."""
    from kfserving_tpu.models.decoder import decoder_tiny
    from kfserving_tpu.ops import dot_product_attention

    cfg = decoder_tiny(num_layers=2, hidden_size=64, num_heads=2,
                       intermediate_size=128, max_seq=MAX_SEQ,
                       vocab_size=96,
                       attn_fn=lambda q, k, v, m:
                           dot_product_attention(q, k, v, mask=m))
    module = DecoderLM(cfg)
    variables = module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    _, caches = module.apply(variables,
                             jnp.zeros((1, 8), jnp.int32),
                             kv_lengths=jnp.asarray([5]),
                             return_cache=True)
    assert len(caches) == 2
    for k, v in caches:
        assert k.shape == (1, 8, 2, 32) and v.shape == (1, 8, 2, 32)


async def test_cancel_during_prefill_delivers_terminal_event(tiny):
    """cancel() landing while the request's prefill dispatch is on the
    executor (neither pending nor active) must still end the stream
    with a terminal event — a draining consumer must never hang
    (code-review r5)."""
    eng = make_engine(tiny, max_slots=1)
    orig = eng._enqueue_prefill_group

    def cancel_mid_prefill(group, slots, bucket, dest_rows=None):
        for r in group:
            eng.cancel(r)
        return orig(group, slots, bucket, dest_rows)

    eng._enqueue_prefill_group = cancel_mid_prefill
    try:
        req = eng.submit([1, 2, 3], max_new_tokens=5)
        token, fin = await asyncio.wait_for(
            eng.stream(req).__anext__(), timeout=30)
        assert token is None and fin == "cancelled"
        # The slot never got occupied; a follow-up request works.
        eng._enqueue_prefill_group = orig
        got, reason = await eng.complete([4, 5], max_new_tokens=2)
        assert len(got) == 2 and reason == "length"
    finally:
        await eng.close()


# ------------------------------------------------------ sampling surface


async def test_top_k_1_equals_greedy(tiny):
    """top_k=1 collapses sampling to argmax regardless of temperature."""
    module, variables, _ = tiny
    prompt = [5, 9, 2, 7]
    want = ref_greedy(module, variables, prompt, 8)
    eng = make_engine(tiny, max_slots=1)
    try:
        got, _ = await eng.complete(prompt, max_new_tokens=8,
                                    temperature=1.0, top_k=1)
    finally:
        await eng.close()
    assert got == want


async def test_top_p_tiny_equals_greedy(tiny):
    """top_p -> 0 keeps only the most-likely token (n_keep clamps to
    1), so sampling equals greedy."""
    module, variables, _ = tiny
    prompt = [3, 1, 4, 1, 5]
    want = ref_greedy(module, variables, prompt, 6)
    eng = make_engine(tiny, max_slots=1)
    try:
        got, _ = await eng.complete(prompt, max_new_tokens=6,
                                    temperature=1.5, top_p=1e-6)
    finally:
        await eng.close()
    assert got == want


@pytest.mark.slow
async def test_top_k_and_top_p_restrict_support(tiny):
    """Every sampled token lies inside the declared support: top-k's
    k best ids, and top-p's nucleus (smallest prefix of the sorted
    distribution reaching mass p) — membership implies the
    monotonicity of nested supports."""
    import jax.nn

    module, variables, _ = tiny
    prompt = [7, 2, 9]
    logits = np.asarray(module.apply(
        variables, jnp.asarray([prompt], jnp.int32))[0, -1],
        np.float32)
    order = np.argsort(-logits)
    top2 = set(int(t) for t in order[:2])
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits)))
    cum = np.cumsum(probs[order])
    n_keep = int(np.searchsorted(cum, 0.6) + 1)
    nucleus = set(int(t) for t in order[:n_keep])

    eng = make_engine(tiny, max_slots=4)
    try:
        for seed in range(16):
            got_k, _ = await eng.complete(prompt, max_new_tokens=1,
                                          temperature=2.0, top_k=2,
                                          seed=seed)
            assert got_k[0] in top2, (got_k, top2)
            got_p, _ = await eng.complete(prompt, max_new_tokens=1,
                                          temperature=2.0, top_p=0.6,
                                          seed=seed)
            assert got_p[0] in nucleus, (got_p, nucleus)
    finally:
        await eng.close()


@pytest.mark.slow
async def test_seed_reproduces_regardless_of_scheduling(tiny):
    """A seeded temperature request reproduces exactly — solo or
    sharing decode waves with other requests (noise is keyed on
    (seed, absolute position), never on slot or wave identity)."""
    module, variables, _ = tiny
    prompt = [5, 9, 2, 7, 1]
    eng = make_engine(tiny, max_slots=4)
    try:
        solo, _ = await eng.complete(prompt, max_new_tokens=10,
                                     temperature=1.0, seed=42)
        # Same seed, this time racing two other requests.
        results = await asyncio.gather(
            eng.complete(prompt, max_new_tokens=10,
                         temperature=1.0, seed=42),
            eng.complete([1, 2, 3], max_new_tokens=10,
                         temperature=0.9, seed=7),
            eng.complete([9, 9], max_new_tokens=10,
                         temperature=1.3))
        other, _ = await eng.complete(prompt, max_new_tokens=10,
                                      temperature=1.0, seed=43)
    finally:
        await eng.close()
    assert results[0][0] == solo
    assert other != solo  # different seed diverges (overwhelmingly)


async def test_default_seeds_vary_across_requests(tiny):
    """Unseeded temperature requests must differ from each other (the
    old per-dispatch rng gave every slot different noise; the
    per-request counter must preserve that)."""
    eng = make_engine(tiny, max_slots=2, rng_seed=0)
    prompt = [5, 9, 2]
    try:
        a, _ = await eng.complete(prompt, max_new_tokens=12,
                                  temperature=1.2)
        b, _ = await eng.complete(prompt, max_new_tokens=12,
                                  temperature=1.2)
    finally:
        await eng.close()
    assert a != b


@pytest.mark.slow
async def test_logprobs_match_full_forward(tiny):
    """Chosen-token logprobs come from the unmasked log-softmax; top-N
    ids/values match the reference full forward at every step."""
    import jax.nn

    module, variables, _ = tiny
    prompt = [5, 9, 2, 7, 11]
    eng = make_engine(tiny, max_slots=1)
    try:
        req = eng.submit(prompt, max_new_tokens=6, logprobs=3)
        tokens = []
        async for t, fin in eng.stream(req):
            if t is not None:
                tokens.append(t)
    finally:
        await eng.close()
    assert len(req.lp_chosen) == len(tokens) == 6
    ids = [int(t) for t in prompt]
    for step, tok in enumerate(tokens):
        logits = module.apply(variables, jnp.asarray([ids], jnp.int32))
        lps = np.asarray(jax.nn.log_softmax(logits[0, -1]), np.float32)
        assert tok == int(np.argmax(lps))  # greedy
        np.testing.assert_allclose(req.lp_chosen[step], lps[tok],
                                   rtol=2e-3, atol=2e-3)
        want_top = np.argsort(-lps)[:3]
        got_top = [t for t, _ in req.lp_top[step]]
        assert got_top == [int(x) for x in want_top]
        ids.append(tok)


async def test_sampling_validation(tiny):
    eng = make_engine(tiny, max_slots=1)
    try:
        with pytest.raises(InvalidInput):
            eng.submit([1], top_p=0.0)
        with pytest.raises(InvalidInput):
            eng.submit([1], top_p=1.5)
        with pytest.raises(InvalidInput):
            eng.submit([1], top_k=-1)
        with pytest.raises(InvalidInput):
            eng.submit([1], logprobs=99)
    finally:
        await eng.close()


# ------------------------------------------------------ pipelined decode


@pytest.mark.slow
async def test_pipeline_depth_parity(tiny):
    """Token-for-token parity across pipeline depths: the device-
    resident feed chain (depth>=2, fetch of wave N overlapping wave
    N+1) must produce exactly the blocking path's output — greedy AND
    seeded temperature."""
    module, variables, _ = tiny
    prompts = [[5, 9, 2, 7], [1, 3], [8, 8, 8, 1, 2]]
    results = {}
    for depth in (1, 3):
        eng = make_engine(tiny, max_slots=4, pipeline_depth=depth,
                          steps_per_call=2)
        try:
            outs = await asyncio.gather(*[
                eng.complete(p, max_new_tokens=9) for p in prompts])
            seeded, _ = await eng.complete([4, 2], max_new_tokens=9,
                                           temperature=1.1, seed=77)
        finally:
            await eng.close()
        results[depth] = ([t for t, _ in outs], seeded)
    assert results[1] == results[3]
    # and the greedy outputs equal the no-cache recompute
    for p, got in zip(prompts, results[1][0]):
        assert got == ref_greedy(module, variables, p, 9)


async def test_pipeline_waste_accounting(tiny):
    """A finishing slot wastes at most (depth-1)*K + K-1 garbage steps
    per request; the engine must count them honestly."""
    eng = make_engine(tiny, max_slots=1, pipeline_depth=2,
                      steps_per_call=4)
    try:
        await eng.complete([1, 2, 3], max_new_tokens=2)
        # Budget 2 with K=4: >=2 wasted in the finishing wave, plus
        # the in-flight next wave's 4.
        stats = eng.stats()
        assert stats["wasted_token_steps"] >= 2
        assert stats["pipeline_depth"] == 2
        # Correctness after waste: a second request still matches.
        module, variables, _ = (eng.module, eng.variables, None)
        want = ref_greedy(module, variables, [7, 7], 5)
        got, _ = await eng.complete([7, 7], max_new_tokens=5)
        assert got == want
    finally:
        await eng.close()


async def test_pipeline_decode_wait_tracked(tiny):
    eng = make_engine(tiny, max_slots=1, pipeline_depth=2)
    try:
        await eng.complete([1, 2], max_new_tokens=4)
        stats = eng.stats()
        assert stats["decode_wait_s"] >= 0.0
        # Budget 4 = 1 prefill token + 3 decode steps.  The adaptive
        # governor suppresses the old 4th (speculative, provably
        # garbage) dispatch — exactly 3 useful steps remain.
        assert stats["decode_steps"] >= 3
        assert stats["suppressed_waves"] >= 1
    finally:
        await eng.close()


# ----------------------------------- rows parked at their token budget


PARKED_SEQ, PARKED_BS = 128, 16
FAMILIES = {
    "decoder": ("decoder_tiny", dict(num_layers=2, hidden_size=64,
                                     num_heads=2, intermediate_size=128,
                                     vocab_size=96)),
    "olmoe": ("olmoe_tiny", {}),
    "nemotron_h": ("nemotron_h_tiny", {}),
    "mellum": ("mellum_tiny", {}),
}


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def family(request):
    from kfserving_tpu.models import create_model, init_params

    name, sizes = FAMILIES[request.param]
    spec = create_model(name, max_seq=PARKED_SEQ, **sizes)
    return spec.module, init_params(spec, seed=3)


def family_engine(family, **kw):
    module, variables = family
    kw.setdefault("max_slots", 4)
    return GenerationEngine(module, variables, max_seq=PARKED_SEQ,
                            block_size=PARKED_BS,
                            prefill_buckets=[16, 32, 64], **kw)


def _prompt(length: int, stride: int):
    return [(j * stride) % 90 + 1 for j in range(length)]


async def _served(eng, requests):
    """Every request's (tokens, log-probabilities), all submitted at
    once: (prompt, budget, sampling keywords) each."""
    async def one(prompt, budget, sampling):
        req = eng.submit(prompt, max_new_tokens=budget, logprobs=1,
                         **sampling)
        tokens = [t async for t, _ in eng.stream(req) if t is not None]
        return tokens, list(req.lp_chosen)

    return await asyncio.wait_for(asyncio.gather(*[
        one(*r) for r in requests]), timeout=600)


async def _settled(eng):
    """Until every wave the engine launched has been fetched and
    accounted: its loop leaves once nothing is active or in flight."""
    await asyncio.wait_for(eng._loop_task, timeout=60)


async def test_a_row_parked_at_its_budget_changes_no_stream(family):
    """Four steps a call and two waves in flight, so that the device
    runs up to eight steps past what the host has seen and parks each
    row at its budget's end by itself: budgets that end at every step
    of a wave (and at the prefill), prompts that end on a block's
    boundary and off it, more requests than slots, some sampling; then
    three long streams in a pool too small for them, so that one is
    preempted and resumed.  Every stream is what an engine that steps
    once a call, one wave at a time, over an ample pool gives it."""
    def batch(*rows):
        return [(_prompt(length, stride), budget, sampling)
                for length, stride, budget, sampling in rows]

    short = batch((16, 3, 1, {}), (13, 5, 2, {}), (32, 7, 3, {}),
                  (21, 11, 4, {"temperature": 0.9, "seed": 5}),
                  (16, 13, 5, {}), (29, 17, 6, {}),
                  (32, 19, 7, {"temperature": 1.1, "seed": 11}),
                  (5, 23, 8, {}), (16, 29, 9, {}))
    long = batch((42, 3, 20, {}), (42, 5, 19, {}),
                 (42, 11, 18, {"temperature": 0.8, "seed": 2}))
    results = {}
    for label, kw in (
            ("stepwise", dict(steps_per_call=1, pipeline_depth=1)),
            ("parked", dict(steps_per_call=4, pipeline_depth=2,
                            cache_blocks=10))):
        eng = family_engine(family, **kw)
        try:
            results[label] = (await _served(eng, short)
                              + await _served(eng, long))
            stats = eng.stats()
        finally:
            await eng.close()
    assert stats["paged"]["preemptions"] >= 1
    assert stats["parked_token_steps"] > 0
    for (_, budget, _), want, got in zip(short + long, results["stepwise"],
                                         results["parked"]):
        assert len(got[0]) == budget
        assert got[0] == want[0]
        # float32's last digits: the two engines group their prefills
        # differently, and a resumed stream's next token is a prefill's.
        np.testing.assert_allclose(got[1], want[1], rtol=0, atol=1e-5)


async def test_no_step_past_a_budget_writes_or_routes(family):
    """What the device did, read off the device: a request of 16 prompt
    tokens (one whole block) and a budget of 6 owes five decode steps,
    which write rows 0-4 of its second block; of the other 11 steps of
    its four waves (fixed depth: none is suppressed) none writes a row,
    and none is given an expert."""
    from kfserving_tpu.models.decoder import KVCache

    eng = family_engine(family, max_slots=2, steps_per_call=4,
                        pipeline_depth=2, adaptive_depth=False)
    try:
        tokens, reason = await eng.complete(_prompt(16, 7),
                                            max_new_tokens=6)
        await _settled(eng)
        stats = eng.stats()
        whole = next(i for i, kind in enumerate(eng._cache_layers)
                     if isinstance(kind, KVCache) and kind.window is None)
        pool_k = np.asarray(eng._caches[whole][0], np.float32)
        if eng._moe is not None:
            eng._moe.drain()
            routed = sum(eng._moe.pairs.values()) + eng._moe.elsewhere
            expert_layers = eng._moe.layer_steps // stats["token_steps"]
    finally:
        await eng.close()
    assert (len(tokens), reason) == (6, "length")
    assert stats["token_steps"] >= 8
    assert stats["wasted_token_steps"] == stats["token_steps"] - 5
    assert stats["parked_token_steps"] == stats["wasted_token_steps"]
    written = np.flatnonzero(np.abs(pool_k).sum(axis=(1, 2)))
    assert len(written) == 2
    rows = np.abs(pool_k[written[1]]).sum(axis=1) > 0
    assert rows.tolist() == [True] * 5 + [False] * (PARKED_BS - 5)
    if eng._moe is not None:
        assert expert_layers >= 2
        assert routed == (16 + 5) * eng._moe.per_token * expert_layers


@pytest.mark.parametrize("eos", [False, True], ids=["budget", "eos"])
async def test_parked_steps_are_the_wasted_steps_of_a_budgets_end(tiny,
                                                                  eos):
    """Every dead step past a budget's end is one the device was told
    of; one past an EOS is not."""
    module, variables, _ = tiny
    prompt = [5, 9, 2, 7, 11]
    ref = ref_greedy(module, variables, prompt, 12)
    eng = make_engine(tiny, max_slots=2, steps_per_call=4,
                      pipeline_depth=2, adaptive_depth=False,
                      eos_id=ref[5] if eos else None)
    try:
        got = await asyncio.gather(
            eng.complete(prompt, max_new_tokens=12),
            eng.complete([7, 1, 4], max_new_tokens=7))
        await _settled(eng)
        stats = eng.stats()
    finally:
        await eng.close()
    assert got[0][1] == ("eos" if eos else "length")
    assert stats["wasted_token_steps"] > 0
    if eos:
        assert (0 < stats["parked_token_steps"]
                < stats["wasted_token_steps"])
    else:
        assert stats["parked_token_steps"] == stats["wasted_token_steps"]


@pytest.mark.parametrize("window", [None, 40], ids=["whole", "ring"])
def test_a_masked_table_walks_the_live_rows_alone(window):
    """What `decode_fn` does to a step's table, against `paged_walk`: with
    the rows past their stop masked to -1, the walk lists exactly the
    blocks that the live rows' own tables list, in a whole-context table
    and in a ring, and a decode write through the masked table leaves a
    parked row's blocks as they were."""
    from kfserving_tpu.ops import paged_attention as pa

    rng = np.random.default_rng(4)
    b, bs = 8, 16
    mb = 6 if window is None else pa.ring_blocks(window, bs)
    positions = rng.integers(1, (6 if window is None else 12) * bs - 1, b)
    stops = positions + rng.integers(-3, 4, b)
    stops[0], stops[1] = 0, positions[1]       # free; at its stop
    live = positions < stops
    assert live.any() and not live.all()
    table = rng.permutation(b * mb).astype(np.int32).reshape(b, mb)
    masked = jnp.where(jnp.asarray(live)[:, None], table, -1)
    lengths = jnp.asarray(positions + 1, jnp.int32)

    def walked(tbl, rows):
        pairs, count = pa.paged_walk(jnp.asarray(tbl), lengths, bs, window)
        pairs = np.asarray(pairs)[:int(count[0])]
        assert set(pairs // mb) <= set(rows)
        return [int(np.asarray(tbl).reshape(-1)[p]) for p in pairs]

    everyone = np.arange(b)
    want = [blk for row in everyone[live]
            for blk in walked(np.where((everyone == row)[:, None],
                                       table, -1), [row])]
    assert walked(masked, everyone[live]) == want
    assert len(want) > 0

    pool = jnp.zeros(pa.pool_shape(b * mb, bs, 2, 8), jnp.float32)
    step = jnp.ones((b, 2, 8), jnp.float32)
    pool_k, pool_v = pa.paged_write(pool, pool, step, step, masked,
                                    jnp.asarray(positions, jnp.int32),
                                    window)
    written = np.flatnonzero(np.asarray(pool_k).sum(axis=(1, 2)))
    column = positions // bs % mb if window else positions // bs
    assert sorted(written) == sorted(
        table[row, column[row]] for row in everyone[live])
    np.testing.assert_array_equal(pool_k, pool_v)


# ------------------------------- a group goes as the row counts it fills
# A group of b same-bucket requests pads to the next power of two with
# dummy rows, or is taken as the binary pieces of b, one prefill program
# of exactly its rows a piece: where the engine has timed the padded
# program and every piece's, and the pieces took less.

SPLIT_BS = 8
_ROWS_COUNTED = ("prefills", "prefill_requests", "prefill_rows_dispatched",
                 "prefill_rows_padded")


def _pieces(b: int):
    """The powers of two in b, largest first: 13 is 8 + 4 + 1."""
    return [1 << i for i in reversed(range(b.bit_length())) if b >> i & 1]


def _split_prompt(i: int):
    """Request i of a burst: every third one opens with one whole block
    that the others of its kind share (a later piece's prefix hit on an
    earlier piece's block), all within the 16-token bucket and all of
    more than one of its two blocks, so a prompt fills a row of the
    program and what is said of rows here is said of requests."""
    if i % 3 == 0:
        return list(range(1, SPLIT_BS + 1)) + [20 + i, 50 + i][:1 + i % 2]
    return [(7 * i + j) % 90 + 1 for j in range(SPLIT_BS + 1 + i % 8)]


def split_engine(tiny, **kw):
    kw.setdefault("max_slots", 8)
    return make_engine(tiny, prefill_buckets=[16, MAX_SEQ],
                       block_size=SPLIT_BS, **kw)


def _price(eng, cost=float):
    """Every prefill dispatch from now on is timed at cost(rows)
    seconds, a program's first too: what the rule decides is then the
    test's to say, not this CPU's clock."""
    def note(rows, bucket, seconds):
        eng._prefill_took_s[rows, bucket] = [cost(rows)]

    eng._note_prefill_took = note


def _watch(eng, monkeypatch=None):
    """Record the rows of every prefill program launched, the requests
    in the order of their first token, and every program shape that is
    noted for the first time (a compile, on a chip)."""
    from kfserving_tpu.engine import compile_cache

    seen = {"rows": [], "first": [], "noted": []}
    prefill, emit = eng._prefill, eng._emit
    note = compile_cache.note_compilation

    def watched_prefill(variables, ids, *rest):
        seen["rows"].append(ids.shape[0])
        return prefill(variables, ids, *rest)

    def watched_emit(slot, token, lp_rec=None):
        req = eng._slots[slot].req
        if req.last_emit_t is None:
            seen["first"].append(req)
        return emit(slot, token, lp_rec)

    def watched_note(source, key):
        seen["noted"].append(key)
        return note(source, key)

    eng._prefill, eng._emit = watched_prefill, watched_emit
    if monkeypatch is not None:
        monkeypatch.setattr(compile_cache, "note_compilation", watched_note)
    return seen


def _order(reqs, firsts):
    """The burst's indices of the requests in `firsts`."""
    return [reqs.index(r) for r in firsts]


async def _burst(eng, seen, n, prompt=_split_prompt):
    """n requests submitted before the scheduler wakes: one run at the
    front of the queue."""
    before = eng.stats()
    for record in seen.values():
        del record[:]
    reqs = [eng.submit(prompt(i), max_new_tokens=3) for i in range(n)]
    tokens = await asyncio.wait_for(
        asyncio.gather(*(_drain(eng, r) for r in reqs)), timeout=120)
    after = eng.stats()
    return {"tokens": tokens, "rows": list(seen["rows"]),
            "noted": list(seen["noted"]),
            "first_in_order": _order(reqs, seen["first"]),
            "counted": {k: after[k] - before[k] for k in _ROWS_COUNTED}}


def _prefill_programs(eng):
    return {rows for kind, rows, *_ in eng._dispatched_programs
            if kind == "prefill"}


@pytest.fixture(scope="module")
def warm_bursts(tiny):
    """(each request's tokens sent alone, {b: what a burst of b gave})
    on one engine whose every power-of-two prefill program has been
    dispatched, and timed at its rows, before the bursts."""
    from kfserving_tpu.observability import REGISTRY

    async def collect():
        eng = split_engine(tiny, max_slots=16)
        _price(eng)  # a program costs its rows: every split pays
        patch = pytest.MonkeyPatch()
        seen = _watch(eng, patch)
        try:
            alone = [(await eng.complete(_split_prompt(i),
                                         max_new_tokens=3))[0]
                     for i in range(16)]
            for rows in (2, 4, 8, 16):
                await _burst(eng, seen, rows)
            assert _prefill_programs(eng) == {1, 2, 4, 8, 16}
            return alone, {b: await _burst(eng, seen, b)
                           for b in range(1, 17)}
        finally:
            patch.undo()
            await eng.close()

    try:
        return asyncio.run(collect())
    finally:
        # This engine ran outside any test: its series are not the next
        # test's.
        REGISTRY.reset()


@pytest.mark.parametrize("b", range(1, 17))
def test_warm_group_goes_as_its_binary_pieces(warm_bursts, b):
    alone, bursts = warm_bursts
    assert bursts[b]["rows"] == _pieces(b)  # largest first, no dummy row
    assert bursts[b]["tokens"] == alone[:b]
    assert all(len(t) == 3 for t in bursts[b]["tokens"])


@pytest.mark.parametrize("b", range(1, 17))
def test_split_keeps_arrival_order_and_notes_no_program(warm_bursts, b):
    """Every piece rides a program dispatched before: nothing reaches
    compile_cache.note_compilation (KFS_SANITIZE's recompile check)."""
    _, bursts = warm_bursts
    assert bursts[b]["first_in_order"] == list(range(b))
    assert bursts[b]["noted"] == []


@pytest.mark.parametrize("b", range(1, 17))
def test_prefill_row_counters_count_what_was_dispatched(warm_bursts, b):
    _, bursts = warm_bursts
    assert bursts[b]["counted"] == {
        "prefills": len(_pieces(b)), "prefill_requests": b,
        "prefill_rows_dispatched": b, "prefill_rows_padded": 0}


@pytest.mark.parametrize("warm, b, then", [
    ([2, 1], 3, [2, 1]),
    ([4, 1], 5, [4, 1]),
    ([4, 2], 6, [4, 2]),
    ([4, 2, 1], 7, [4, 2, 1]),
    ([8, 4], 5, [8]),     # the 1-row program is still cold: pads again
], ids=["3", "5", "6", "7", "5-piece-cold"])
async def test_group_pads_until_its_padded_program_is_timed(
        tiny, monkeypatch, warm, b, then):
    """The pieces' programs alone do not split a group: it pads as it
    always did until the padded program has been dispatched, and so
    timed, too (a harness that warms the 8-row program with a burst
    admitted as 5 must still get it), and the next group of that size
    splits."""
    eng = split_engine(tiny)
    _price(eng)
    seen = _watch(eng, monkeypatch)
    try:
        for rows in warm:
            await _burst(eng, seen, rows)
        padded = 1 << (b - 1).bit_length()
        first = await _burst(eng, seen, b)
        assert first["rows"] == [padded]
        assert first["noted"] == ([] if padded in warm
                                  else [("prefill", padded, 16)])
        assert first["counted"]["prefill_rows_padded"] == padded - b
        assert _prefill_programs(eng) == set(warm) | {padded}
        second = await _burst(eng, seen, b)
        assert second["rows"] == then and second["noted"] == []
        assert second["tokens"] == first["tokens"]
        assert _prefill_programs(eng) == set(warm) | {padded}
    finally:
        await eng.close()


# What a program of r rows took, by name: a dense model's (its rows and
# little else), one whose every dispatch streams gigabytes whatever its
# rows, and Nemotron-H's at 1024 tokens as timed on the v5e (PERF.md,
# PR 49: the 1- and 2-row programs leave the grouped kernel).
_COSTS = {"by-row": lambda r: 0.25 + r,
          "by-dispatch": lambda r: 100.0 + r,
          "small-ones-dear": {1: 60.0, 2: 80.0, 4: 72.0, 8: 147.0}.get}
_WENT = {"by-row": {3: [2, 1], 5: [4, 1], 6: [4, 2], 7: [4, 2, 1]},
         "by-dispatch": {3: [4], 5: [8], 6: [8], 7: [8]},
         "small-ones-dear": {3: [4], 5: [4, 1], 6: [8], 7: [8]}}


@pytest.fixture(scope="module")
def priced_bursts(tiny):
    """{cost: {b: the rows a burst of b went as}} on one engine with its
    1-, 2-, 4- and 8-row programs dispatched, under each table of what
    they took."""
    from kfserving_tpu.observability import REGISTRY

    async def collect():
        eng = split_engine(tiny)
        seen = _watch(eng)
        try:
            for rows in (1, 2, 4, 8):
                await _burst(eng, seen, rows)
            eng._note_prefill_took = lambda rows, bucket, seconds: None
            went = {}
            for name, cost in _COSTS.items():
                eng._prefill_took_s = {(r, 16): [cost(r)]
                                       for r in (1, 2, 4, 8)}
                went[name] = {b: (await _burst(eng, seen, b))["rows"]
                              for b in (3, 5, 6, 7)}
            return went
        finally:
            await eng.close()

    try:
        return asyncio.run(collect())
    finally:
        REGISTRY.reset()


@pytest.mark.parametrize("b", [3, 5, 6, 7])
@pytest.mark.parametrize("cost", sorted(_COSTS))
def test_group_splits_only_where_its_pieces_took_less(priced_bursts,
                                                      cost, b):
    assert priced_bursts[cost][b] == _WENT[cost][b]


async def test_a_programs_first_dispatch_is_not_a_timing(tiny):
    """The first dispatch of a program compiles inside its launch: it
    opens the program's record and leaves it empty, so nothing is
    decided on it.  From the second on a dispatch is timed by the fetch
    workers' clock, and the last eight are kept."""
    eng = split_engine(tiny)
    try:
        assert eng._prefill_rows_to_take(3, 16) == 3
        for n in range(11):
            await eng.complete([1 + n, 2, 3], max_new_tokens=2)
            took = eng._prefill_took_s[1, 16]
            assert len(took) == min(n, 8)
        assert all(0.0 < s < 60.0 for s in took)
        assert set(eng._prefill_took_s) == {(1, 16)}
        assert eng._prefill_rows_to_take(3, 16) == 3  # 2 and 4 untimed
        assert eng.stats()["prefill_program_ms"] == {
            "1x16": round(1e3 * float(np.median(took)), 3)}
    finally:
        await eng.close()


async def test_prefill_rows_bounds_a_group_before_it_is_cut(tiny):
    """`prefill_rows` 3: no take sees more than 3 requests, and a take
    of 3 goes as 2 (then what is left is taken under the same bound)."""
    eng = split_engine(tiny, prefill_rows=3)
    _price(eng)
    seen = _watch(eng)
    try:
        for rows in (1, 2, 3):
            await _burst(eng, seen, rows)
        assert _prefill_programs(eng) == {1, 2, 4}
        alone = [(await eng.complete(_split_prompt(i),
                                     max_new_tokens=3))[0]
                 for i in range(7)]
        got = await _burst(eng, seen, 7)
        assert got["rows"] == [2, 2, 2, 1]
        assert got["tokens"] == alone
        assert got["first_in_order"] == list(range(7))
    finally:
        await eng.close()


@pytest.mark.parametrize("n, refused_rows, rows_after", [
    (6, 2, [4, 2, 1, 1]),           # 4 + 2: the 2 goes back, cap 1
    (6, 4, [4, 2, 2, 2]),           # the 4 goes back, cap 2
    (7, 2, [4, 2, 1, 1, 1]),        # 4 + 2 + 1: the 2 goes back, cap 1
])
async def test_refusal_of_a_piece_halves_from_that_piece(
        tiny, n, refused_rows, rows_after):
    """The runtime refuses one piece's launch for memory: the pieces
    before it are in flight and stay so, the refused one goes back to
    the front of the queue, and dispatches are held to half ITS rows
    from then on (not half the whole run's).  No request fails."""
    eng = split_engine(tiny)
    _price(eng)
    seen = _watch(eng)
    try:
        for rows in (2, 4, 8):
            await _burst(eng, seen, rows)
        alone = [(await eng.complete(_split_prompt(i),
                                     max_new_tokens=3))[0]
                 for i in range(n)]
        watched = eng._prefill
        left = {"n": 1}

        def refusing(variables, ids, *rest):
            if ids.shape[0] == refused_rows and left["n"]:
                left["n"] -= 1
                seen["rows"].append(ids.shape[0])
                raise ValueError(_REFUSAL)
            return watched(variables, ids, *rest)

        eng._prefill = refusing
        got = await _burst(eng, seen, n)
        assert got["tokens"] == alone
        assert got["rows"] == rows_after
        assert got["first_in_order"] == list(range(n))
        assert eng.stats()["prefill_rows_cap"] == refused_rows // 2
        # The refused launch ran nothing and is not counted.
        assert got["counted"]["prefill_rows_dispatched"] == n
        assert got["counted"]["prefill_rows_padded"] == 0
    finally:
        await eng.close()


async def test_cancels_inside_a_split_run_leave_its_order(tiny):
    """Seven arrivals, taken 4 first.  While that piece is on the
    enqueue executor one of its requests is cancelled (it never holds a
    slot) and one still queued behind it is (it leaves the queue): the
    next take has what is left, in arrival order."""
    eng = split_engine(tiny)
    _price(eng)
    seen = _watch(eng)
    try:
        for rows in (2, 4, 8):
            await _burst(eng, seen, rows)
        alone = [(await eng.complete(_split_prompt(i),
                                     max_new_tokens=3))[0]
                 for i in range(7)]
        orig = eng._enqueue_prefill_group
        reqs = []

        def cancel_two(group, slots, bucket, dest_rows=None):
            for r in (reqs[2], reqs[5]):
                eng.cancel(r)
            return orig(group, slots, bucket, dest_rows)

        eng._enqueue_prefill_group = cancel_two
        del seen["rows"][:], seen["first"][:]
        reqs.extend(eng.submit(_split_prompt(i), max_new_tokens=3)
                    for i in range(7))
        outs = await asyncio.wait_for(
            asyncio.gather(*(_drain(eng, r) for r in reqs)), timeout=60)
        assert seen["rows"] == [4, 2]
        assert outs == [[] if i in (2, 5) else alone[i] for i in range(7)]
        assert _order(reqs, seen["first"]) == [0, 1, 3, 4, 6]
        assert all(s is None for s in eng._slots)
    finally:
        await eng.close()


async def test_pool_starved_take_rolls_back_to_a_power_of_two(tiny):
    """Four arrivals of two blocks each and a pool that holds three of
    them: the take plans three, keeps the two a warm program carries,
    and undoes the third's plan (its provisional prefix registration
    with it).  Everyone is answered in arrival order as alone, and the
    pool ends as it began."""
    def two_blocks(i):
        return [(11 * i + j) % 90 + 1 for j in range(9)]

    roomy = split_engine(tiny)
    try:
        alone = [(await roomy.complete(two_blocks(i), max_new_tokens=3))[0]
                 for i in range(4)]
    finally:
        await roomy.close()
    eng = split_engine(tiny, cache_blocks=7)
    _price(eng)
    seen = _watch(eng)
    try:
        # The pool holds no four prompts that fill a row each: the
        # programs a take of three is weighed by are priced unrun.
        eng._prefill_took_s.update({(r, 16): [float(r)] for r in (1, 2, 4)})
        invalidated = eng.block_evictions["index_invalidation"]
        requeue, undone = eng._requeue_group, []

        def watched_requeue(group, slots):
            undone.append([r.prompt_ids.tolist() for r in group])
            return requeue(group, slots)

        eng._requeue_group = watched_requeue
        got = await _burst(eng, seen, 4, prompt=two_blocks)
        assert got["rows"][0] == 2 and sum(got["rows"]) == 4
        assert got["counted"]["prefill_rows_padded"] == 0
        assert got["tokens"] == alone
        assert got["first_in_order"] == [0, 1, 2, 3]
        # The third request's plan was undone once, by the take's cut
        # (a plan the pool cannot finish undoes itself and counts too).
        assert undone == [[two_blocks(2)]]
        assert eng.block_evictions["index_invalidation"] > invalidated
        assert not eng._plan_regs
        await _settled(eng)
        assert eng.stats()["paged"]["free_blocks"] \
            + eng.stats()["paged"]["reclaimable_blocks"] == 7
    finally:
        await eng.close()


async def test_padded_rows_share_is_read_from_the_scrapes(tiny):
    """The two counters are on /metrics from the engine's start, and the
    benchmark's reader makes the share of them: a cold group of 3 pads
    to 4, one row in four a dummy."""
    from chipbench import run as bench
    from kfserving_tpu.server.metrics import Metrics
    from kfserving_tpu.tools.check_metrics import lint_exposition

    eng = split_engine(tiny, name="m")
    try:
        scrapes = {"open": {"metrics": Metrics().render()}}
        for series in ("rows_total", "rows_padded_total"):
            assert (f'kfserving_tpu_engine_prefill_{series}{{model="m"}} 0\n'
                    in scrapes["open"]["metrics"])
        await _burst(eng, _watch(eng), 3)
        scrapes["close"] = {"metrics": Metrics().render()}
        stats = eng.stats()
        assert (stats["prefill_rows_dispatched"],
                stats["prefill_rows_padded"]) == (4, 1)
        assert ('kfserving_tpu_engine_prefill_rows_total{model="m"} 4\n'
                in scrapes["close"]["metrics"])
        assert lint_exposition(scrapes["close"]["metrics"]) == []
        reader = bench.load_by_path("layer_metrics",
                                    "prefill_padded_rows_share")
        assert reader.read({"config": {"name": "m"},
                            "scrapes": scrapes}) == 25.0
    finally:
        await eng.close()


# ------------------------- a prefill row that carries several prompts
# One bucket of four blocks: a prompt takes the blocks it fills, and the
# next one of the run starts at the row's next block where the model's
# programs pack (`programs.packs_prompts`: the dense decoder, OLMoE and,
# its recurrence in chunks of a block, Nemotron-H of `FAMILIES`;
# Mellum's rings keep a prompt a row).
PACK_BUCKET = 64
PACKS = {"decoder": True, "olmoe": True, "nemotron_h": True,
         "mellum": False}
# Falcon-H1 (K/V and a state in every layer) beside them, for the tests
# that build their own engine of a family that packs.
PACK_FAMILIES = {**FAMILIES, "falcon_h1": ("falcon_h1_tiny", {})}


def packing_engine(family, **kw):
    module, variables = family
    kw.setdefault("max_slots", 8)
    return GenerationEngine(module, variables, max_seq=PARKED_SEQ,
                            block_size=PARKED_BS,
                            prefill_buckets=[PACK_BUCKET], **kw)


def _packs(eng) -> bool:
    return eng._row_entries[PACK_BUCKET] > 1


def _same_streams(got, want):
    for (tokens, lps), (alone, alone_lps) in zip(got, want):
        assert tokens == alone
        np.testing.assert_allclose(lps, alone_lps, rtol=0, atol=1e-5)


# (tokens, stride, budget, sampling): one block, off a boundary, two
# blocks exactly, the whole bucket, and two that sample.
MIXED = [(16, 3, 4, {}), (5, 5, 6, {}), (32, 7, 3, {}), (64, 11, 2, {}),
         (21, 13, 5, {"temperature": 0.9, "seed": 5}), (1, 17, 4, {}),
         (40, 19, 3, {"temperature": 1.1, "seed": 11}), (17, 23, 5, {})]


def _requests(rows):
    return [(_prompt(length, stride), budget, sampling)
            for length, stride, budget, sampling in rows]


async def _alone(family, requests, **kw):
    """Every request's stream from an engine that serves it alone."""
    eng = packing_engine(family, **kw)
    try:
        return [(await _served(eng, [r]))[0] for r in requests]
    finally:
        await eng.close()


@pytest.fixture(scope="module")
def mixed_alone(family):
    from kfserving_tpu.observability import REGISTRY

    try:
        return asyncio.run(_alone(family, _requests(MIXED)))
    finally:
        REGISTRY.reset()  # this engine ran outside any test


@pytest.fixture
def packs(request):
    """Whether the `family` of this test lays several prompts in a row."""
    return PACKS[request.node.callspec.params["family"]]


async def test_a_burst_of_mixed_lengths_is_served_as_each_alone(
        family, mixed_alone, packs):
    """Eight arrivals of 1 to 64 tokens before the scheduler wakes: one
    take, one dispatch.  Where the programs pack they lie in 4 rows (the
    blocks 4, 3+1, 2+2, 2+1+1) where they filled 8; a ring model
    dispatches a prompt a row as before.  Every stream, sampled ones too,
    is what the request gets served alone."""
    eng = packing_engine(family)
    asked = []
    rule = eng._prefill_rows_to_take
    eng._prefill_rows_to_take = lambda rows, bucket: (
        asked.append(rows), rule(rows, bucket))[1]
    try:
        assert _packs(eng) is packs
        got = await _served(eng, _requests(MIXED))
        stats = eng.stats()
    finally:
        await eng.close()
    _same_streams(got, mixed_alone)
    rows = 4 if packs else 8
    assert (stats["prefills"], stats["prefill_requests"]) == (1, 8)
    assert (stats["prefill_rows_dispatched"],
            stats["prefill_rows_padded"]) == (rows, 0)
    assert stats["prefill_prompts_per_row"] == 8 / rows
    # PR 49's rule is asked about the program's rows, not the prompts.
    assert set(asked) == {rows}
    assert {key for key in eng._dispatched_programs
            if key[0] == "prefill"} == {("prefill", rows, PACK_BUCKET)}


@pytest.mark.parametrize("sizes, per_row, rows", [
    ([1], 4, 1), ([4], 4, 1), ([1, 1, 1, 1], 4, 1), ([1, 1, 1, 1, 1], 4, 2),
    ([4, 3, 1, 2, 2, 2, 1, 1], 4, 4),      # the burst above
    ([2, 3, 2, 3], 4, 3),                  # 3 + 2 fits no row, 2 + 2 does
    ([3, 1, 3, 1, 2], 4, 3), ([1, 4, 1, 4, 1], 4, 3),
    ([5, 3, 8, 2, 2, 4], 8, 3), ([1, 2, 3, 4, 5, 6, 7, 8], 8, 5),
    ([1, 1, 1], 1, 3), ([1] * 7, 1, 7),    # one prompt a row, in order
    ([], 4, 0),
], ids=str)
def test_lay_rows_gives_each_prompt_consecutive_entries_of_one_row(
        sizes, per_row, rows):
    from kfserving_tpu.engine.generator import lay_rows

    at = lay_rows(sizes, per_row)
    owned = [e for first, size in zip(at, sizes)
             for e in range(first, first + size)]
    assert len(set(owned)) == len(owned) == sum(sizes)  # none owned twice
    for first, size in zip(at, sizes):
        assert first % per_row + size <= per_row        # within one row
    used = {e // per_row for e in owned}
    assert used == set(range(rows))                     # no row skipped
    # a row is filled from its first entry, with no hole before its end
    for row in used:
        mine = sorted(e % per_row for e in owned if e // per_row == row)
        assert mine == list(range(len(mine)))
    if per_row == 1:
        assert at == list(range(len(sizes)))


@pytest.mark.parametrize("member", ["decoder", "olmoe", "nemotron_h",
                                    "falcon_h1"])
async def test_a_packed_group_with_a_prefix_hit_a_cancel_and_a_short_pool(
        member):
    """Through one engine of a family that packs: (1) a prompt whose first
    block another request left in the prefix index rides a row with two
    others, its first chunk a -1 the insert drops (a model with a state
    shares no prefix: its rows are laid the same); (2) one request of a
    packed row is cancelled between dispatch and fetch, and its row's
    others get their streams; (3) the pool stops a take of five short at
    five prompts of two blocks, which lie in 3 rows: the 4 that fill 2
    rows go (the programs priced by their rows), the fifth's plan is
    undone and it waits with the rest.  Every stream is what the request
    gets alone."""
    from kfserving_tpu.models import create_model, init_params
    from kfserving_tpu.observability import REGISTRY

    name, sizes = PACK_FAMILIES[member]
    spec = create_model(name, max_seq=PARKED_SEQ, **sizes)
    family = spec.module, init_params(spec, seed=3)
    shared = _prompt(PARKED_BS, 3)
    hit = [(shared + _prompt(9, 29), 4, {}), (_prompt(12, 7), 4, {}),
           (_prompt(20, 11), 3, {"temperature": 0.8, "seed": 9})]
    cancelled = _requests([(18, 5, 4, {}), (14, 13, 5, {}), (7, 17, 3, {})])
    # 2 blocks each and a pool of 11: the sixth plan finds 1 free.
    short = _requests([(18 + 2 * i, 3 + 2 * i, 3, {}) for i in range(7)])
    alone = await _alone(family, hit + cancelled + short)
    REGISTRY.reset()
    eng = packing_engine(family, cache_blocks=11)
    try:
        # (1)
        await eng.complete(shared + [1, 2], max_new_tokens=2)
        before = eng.stats()
        got = await _served(eng, hit)
        after = eng.stats()
        _same_streams(got, alone[:3])
        assert after["prefill_rows_dispatched"] \
            - before["prefill_rows_dispatched"] == 2   # blocks 2 + 1, 2
        assert after["paged"]["prefix_hits"] \
            - before["paged"]["prefix_hits"] == int(eng._shares_prefixes)
        # (2)
        orig, reqs = eng._enqueue_prefill_group, []

        def cancel_one(group, slots, bucket, dest_rows=None):
            assert len({r.prefill_entry // 4 for r in group}) == 1
            eng.cancel(reqs[1])
            return orig(group, slots, bucket, dest_rows)

        eng._enqueue_prefill_group = cancel_one
        reqs.extend(eng.submit(p, max_new_tokens=budget, logprobs=1, **kw)
                    for p, budget, kw in cancelled)
        outs = await asyncio.wait_for(
            asyncio.gather(*(_drain(eng, r) for r in reqs)), timeout=120)
        eng._enqueue_prefill_group = orig
        assert outs == [alone[3][0], [], alone[5][0]]
        # (3)
        await _settled(eng)
        _price(eng)
        eng._prefill_took_s.update(
            {(r, PACK_BUCKET): [float(r)] for r in (1, 2, 4)})
        seen = _watch(eng)
        requeue, undone = eng._requeue_group, []

        def watched_requeue(group, slots):
            undone.append([r.prompt_ids.tolist() for r in group])
            return requeue(group, slots)

        eng._requeue_group = watched_requeue
        got = await _served(eng, short)
        _same_streams(got, alone[6:])
        assert seen["rows"][0] == 2 and sum(seen["rows"]) == 4
        assert undone[0] == [short[4][0]]
        assert all(s is None for s in eng._slots)
    finally:
        await eng.close()


async def test_a_stream_resumed_after_preemption_rides_a_packed_row(
        family, packs):
    """Three long streams in a pool too small for them: some are
    preempted, and their prompts and tokens are prefilled again.  A
    short request arrives at the first preemption and waits behind
    them: where the programs pack it rides a resumed stream's row.
    Every stream is what the request gets alone over an ample pool."""
    rows = _requests([(33, 3, 24, {}), (35, 5, 23, {}),
                      (34, 11, 22, {"temperature": 0.8, "seed": 2}),
                      (9, 7, 6, {})])
    want = await _alone(family, rows)
    eng = packing_engine(family, cache_blocks=10, steps_per_call=4)
    orig, groups = eng._enqueue_prefill_group, []

    def watched(group, slots, bucket, dest_rows=None):
        groups.append([(int(r.prompt_ids.size),
                        r.prefill_entry // eng._row_entries[bucket])
                       for r in group])
        return orig(group, slots, bucket, dest_rows)

    eng._enqueue_prefill_group = watched

    async def late():
        while eng.stats()["paged"]["preemptions"] < 1:
            await asyncio.sleep(0)
        return (await _served(eng, rows[3:]))[0]

    try:
        got = await asyncio.wait_for(asyncio.gather(
            _served(eng, rows[:3]), late()), timeout=300)
        stats = eng.stats()
    finally:
        await eng.close()
    assert stats["paged"]["preemptions"] >= 1
    _same_streams(got[0] + [got[1]], want)
    # The take the short request rode: with streams that were resumed
    # (their prompts have grown), one of them in its row where rows pack.
    ridden, = [group for group in groups if 9 in dict(group)]
    beside = [size for size, row in ridden
              if size > 35 and row == dict(ridden)[9]]
    assert any(size > 35 for size, _ in ridden) and bool(beside) is packs
