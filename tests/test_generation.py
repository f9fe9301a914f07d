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
    prompts = [[5, 5], [7, 1, 3], [2], [9, 9, 4]]
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
