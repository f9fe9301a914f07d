"""Cache & cost attribution (ISSUE 13): prefix/block-pool/HBM
telemetry, per-request cost records, and the federated /debug/cache
surface.

The discriminating bar: each eviction cause counts exactly its own
events, the federated snapshot matches engine stats, and pinned
flight-recorder entries carry the request's cost record.
"""

import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kfserving_tpu.engine.generator import GenerationEngine, _Request
from kfserving_tpu.models.decoder import DecoderLM, decoder_tiny
from kfserving_tpu.observability import REGISTRY, attribution

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


@pytest.fixture(autouse=True)
def _clear_attribution():
    attribution.clear()
    yield
    attribution.clear()


def make_paged(tiny, **kw):
    module, variables, _ = tiny
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("prefill_buckets", [16, 32, MAX_SEQ])
    kw.setdefault("block_size", BS)
    return GenerationEngine(module, variables, name=kw.pop(
        "name", "cachetest"), **kw)


def _counter_value(family_name, **labels):
    fam = REGISTRY.family(family_name)
    if fam is None:
        return 0
    want = {(k, str(v)) for k, v in labels.items()}
    total = 0
    for sample_labels, child in fam.samples():
        if want <= set(sample_labels.items()):
            total += child.value
    return total


async def _settle_pool(eng, timeout_s=10.0):
    """Wait until every block is back (free or reclaimable) — the
    deferred frees force-process once the pipeline idles."""
    total = eng.stats()["paged"]["pool_blocks"]
    for _ in range(int(timeout_s / 0.05)):
        await asyncio.sleep(0.05)
        st = eng.stats()["paged"]
        if st["free_blocks"] + st["reclaimable_blocks"] == total:
            return st
    raise AssertionError(f"pool never settled: {eng.stats()['paged']}")


# ------------------------------------------------- stats key hygiene


async def test_stats_keys_unified_with_pool_counter_sample(tiny):
    """Satellite (ISSUE 13, finished in ISSUE 15): stats() and the
    timeline counter-sample path agree on ONE canonical name
    (free_blocks/reclaimable_blocks); the deprecated blocks_*
    aliases served their one-release grace and are GONE from both."""
    from kfserving_tpu.observability.profiling import TIMELINE

    eng = make_paged(tiny)
    try:
        await eng.complete([5, 9, 2], max_new_tokens=2)
        st = eng.stats()["paged"]
        assert "free_blocks" in st and "reclaimable_blocks" in st
        assert "blocks_free" not in st
        assert "blocks_reclaimable" not in st
        TIMELINE.clear()
        eng._record_pool_sample()
        samples = [e for e in TIMELINE.snapshot()
                   if e[2] == "counter" and e[3] == "pool"]
        assert samples, "pool counter sample missing"
        attrs = samples[-1][6]
        # The counter sample uses EXACTLY the canonical spellings.
        assert "free_blocks" in attrs and "reclaimable_blocks" in attrs
        assert "blocks_free" not in attrs
    finally:
        await eng.close()


# --------------------------------------------- lookup promotion


async def test_prefix_lookups_promoted_to_registry(tiny):
    """Satellite: the dict-only prefix_hits/misses counters now have
    registry twins (visible to the router via /metrics federation),
    plus tokens-saved and the reuse-depth histogram."""
    eng = make_paged(tiny, max_slots=2)
    shared = list(range(1, 2 * BS + 1))  # two full shared blocks
    try:
        await eng.complete(shared + [7], max_new_tokens=2)
        await eng.complete(shared + [9], max_new_tokens=2)
        st = eng.stats()["paged"]
        assert st["prefix_hits"] == 2
        assert st["prefill_tokens_saved"] == 2 * BS
        assert _counter_value(
            "kfserving_tpu_generator_prefix_lookups_total",
            model="cachetest", outcome="hit") == st["prefix_hits"]
        assert _counter_value(
            "kfserving_tpu_generator_prefix_lookups_total",
            model="cachetest", outcome="miss") == st["prefix_misses"]
        assert _counter_value(
            "kfserving_tpu_generator_prefill_tokens_saved_total",
            model="cachetest") == st["prefill_tokens_saved"]
        depth = REGISTRY.family(
            "kfserving_tpu_generator_prefix_reuse_depth_hits")
        assert depth is not None
        assert sum(h.total for _, h in depth.samples()) == 2
    finally:
        await eng.close()


# ------------------------------------------- eviction-cause counters


async def test_eviction_causes_discriminating_sequence(tiny):
    """One sequence, each cause exactly once (satellite): a completed
    request's blocks release through the zombie-deferral window, a
    pressure alloc evicts the lingering cached block (capacity), and
    a failed plan deregisters its provisional chain
    (index_invalidation)."""
    eng = make_paged(tiny, max_slots=2, cache_blocks=3,
                     steps_per_call=1, pipeline_depth=1)
    prompt = list(range(1, BS + 1))  # exactly one full block
    try:
        # Phase 1 — zombie_deferral: the slot held its prompt block +
        # one growth block (horizon 2 tokens past length 16 needs a
        # second block); both mature through the deferral window.
        await eng.complete(prompt, max_new_tokens=1)
        st = await _settle_pool(eng)
        ev = st["evictions"]
        assert ev["zombie_deferral"] == 2, ev
        assert ev["capacity_dropped"] == 0
        assert ev["index_invalidation"] == 0
        assert st["reclaimable_blocks"] == 1  # the registered block

        # Phase 2 — capacity: drain the free list, then one more
        # alloc must reclaim the LRU cached block and drop its index
        # entry.
        with eng._block_lock:
            held = []
            # kfslint: disable=spin-loop — bounded drain of the
            # free-block deque under the lock; nothing refills it.
            while eng._pool.free:
                held.append(eng._pool.free.popleft())
            victim = eng._pool.alloc()
            assert victim is not None
            assert eng._prefix_index == {}  # entry evicted with it
            eng._pool.free.extend(held + [victim])
        ev = eng.stats()["paged"]["evictions"]
        # No host tier wired: a capacity eviction IS a drop (the
        # baseline the ISSUE 16 split makes explicit).
        assert ev["capacity_dropped"] == 1
        assert ev["capacity_spilled"] == 0
        assert ev["index_invalidation"] == 0

        # Phase 3 — index_invalidation: a 2-block plan that registers
        # chunk 0 then fails allocation on chunk 1 rolls back and
        # deregisters exactly one provisional chain.
        with eng._block_lock:
            held = [eng._pool.alloc()
                    for _ in range(2)]
            for b in held:
                eng._pool.hold(b)
        req = _Request(np.asarray(list(range(1, 2 * BS + 1)),
                                  np.int32), 4, 0.0)
        assert eng._plan_prompt_blocks(req, 0) is None
        with eng._block_lock:
            for b in held:
                eng._pool.drop(b)
        ev = eng.stats()["paged"]["evictions"]
        assert ev == {"capacity_dropped": 1, "capacity_spilled": 0,
                      "index_invalidation": 1, "zombie_deferral": 2}
        # Registry twins agree cause-for-cause.
        for cause, want in ev.items():
            assert _counter_value(
                "kfserving_tpu_generator_block_evictions_total",
                model="cachetest", cause=cause) == want, cause
    finally:
        await eng.close()


# --------------------------------------------------- census + ratios


async def test_cache_debug_census_and_ratio_gauges(tiny):
    eng = make_paged(tiny, max_slots=2)
    shared = list(range(1, 2 * BS + 1))
    try:
        await eng.complete(shared + [7], max_new_tokens=2)
        await eng.complete(shared + [9], max_new_tokens=2)
        dbg = eng.cache_debug(top_k=1)
        assert dbg["paged"] is True
        st = eng.stats()["paged"]
        assert dbg["index_entries"] == st["index_entries"] >= 2
        assert dbg["reuse_depth"]["max"] >= 1
        assert len(dbg["hot_chains"]) == 1  # top_k respected
        assert dbg["hot_chains"][0]["hits"] == dbg["reuse_depth"]["max"]
        assert dbg["pool"]["pool_blocks"] == st["pool_blocks"]
        # Ratio stats stay inside the unit their suffix declares.
        assert 0.0 <= st["pool_occupancy_ratio"] <= 1.0
        assert 0.0 <= st["fragmentation_ratio"] <= 1.0
    finally:
        await eng.close()


# --------------------------------------------- per-request attribution


async def test_attribution_record_fields_and_histograms(tiny):
    from kfserving_tpu.tracing import current_request_id

    eng = make_paged(tiny, max_slots=2)
    shared = list(range(1, 2 * BS + 1))
    try:
        await eng.complete(shared + [7], max_new_tokens=3)
        token = current_request_id.set("trace-cache-1")
        try:
            tokens, _ = await eng.complete(shared + [9],
                                           max_new_tokens=3)
        finally:
            current_request_id.reset(token)
        rec = attribution.lookup("trace-cache-1")
        assert rec is not None
        assert rec["model"] == "cachetest"
        assert rec["decode_tokens"] == len(tokens)
        assert rec["prefill_tokens"] == len(shared) + 1
        assert rec["cache_hit_blocks"] == 2
        assert rec["cache_saved_tokens"] == 2 * BS
        assert rec["blocks_held"] >= 3
        assert rec["device_ms"]["decode"] > 0
        assert rec["device_ms"]["prefill"] > 0
        # Per-model aggregate histograms landed.
        fam = REGISTRY.family("kfserving_tpu_request_device_ms")
        assert fam is not None
        phases = {labels["phase"] for labels, _ in fam.samples()}
        assert {"prefill", "decode"} <= phases
        saved = REGISTRY.family(
            "kfserving_tpu_request_cache_saved_tokens")
        assert sum(h.total for _, h in saved.samples()) == 2
    finally:
        await eng.close()


async def test_attribution_sums_match_engine_device_time(tiny):
    """Additivity: the even-split attribution must decompose the
    engine's decode device seconds (not multiply-count shared
    waves)."""
    eng = make_paged(tiny, max_slots=2)
    try:
        from kfserving_tpu.tracing import current_request_id

        async def one(tag, prompt):
            token = current_request_id.set(tag)
            try:
                await eng.complete(prompt, max_new_tokens=4)
            finally:
                current_request_id.reset(token)

        await asyncio.gather(one("t-a", [3, 1, 4]),
                             one("t-b", [1, 5, 9, 2]))
        total_ms = sum(
            attribution.lookup(t)["device_ms"]["decode"]
            for t in ("t-a", "t-b"))
        stats = eng.stats()
        # Slack: stats() rounds device seconds to 4 dp (a 0.1 ms
        # quantum) and each record rounds its ms to 3 dp.
        assert total_ms <= stats["decode_device_s"] * 1000.0 + 0.25
        assert total_ms > 0
    finally:
        await eng.close()


# ---------------------------------------------------- chaos (fault)


@pytest.mark.chaos
async def test_prefix_lookup_fault_forces_miss_storm(tiny):
    """The generator.prefix_lookup site: an injected error makes
    identical prompts MISS the whole index, and the lookup telemetry
    counts the storm instead of hiding it."""
    from kfserving_tpu.reliability.faults import faults

    eng = make_paged(tiny, max_slots=2)
    shared = list(range(1, 2 * BS + 1))
    faults.configure({"generator.prefix_lookup": {"error_rate": 1.0}})
    try:
        await eng.complete(shared + [7], max_new_tokens=2)
        await eng.complete(shared + [9], max_new_tokens=2)
        st = eng.stats()["paged"]
        assert st["prefix_hits"] == 0
        assert st["prefix_misses"] >= 4  # both prompts fully cold
        assert st["prefill_tokens_saved"] == 0
        assert _counter_value(
            "kfserving_tpu_generator_prefix_lookups_total",
            model="cachetest", outcome="miss") == st["prefix_misses"]
    finally:
        faults.reset()
        await eng.close()


# ------------------------------------------------------- HBM families


def test_hbm_manager_registry_and_debug():
    from kfserving_tpu.engine.hbm import HBMManager

    evicted = []
    mgr = HBMManager(budget_bytes=100,
                     evict_cb=lambda name: evicted.append(name))
    mgr.admit("a", 60)
    mgr.admit("b", 30)
    victims = mgr.admit("c", 50)  # must evict LRU "a"
    assert victims == ["a"] == evicted
    assert _counter_value("kfserving_tpu_hbm_evictions_total",
                          model="a") == 1
    fam = REGISTRY.family("kfserving_tpu_hbm_resident_bytes")
    resident = {labels["model"]: child.value
                for labels, child in fam.samples()}
    assert resident == {"b": 30.0, "c": 50.0}  # "a" pruned, not zeroed
    budget = REGISTRY.family("kfserving_tpu_hbm_budget_bytes")
    assert [child.value for _, child in budget.samples()] == [100.0]
    dbg = mgr.debug()
    assert dbg["budget_bytes"] == 100
    assert dbg["used_bytes"] == 80
    assert [r["model"] for r in dbg["resident"]] == ["b", "c"]
    mgr.release("b")
    resident = {labels["model"]: child.value
                for labels, child in fam.samples()}
    assert "b" not in resident


# ----------------------------------------------- replica HTTP surface


def _write_gen_dir(tmp_path, name, extra=None):
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
    cfg.update(extra or {})
    (d / "config.json").write_text(json.dumps(cfg))
    return str(d)


SHARED_PROMPT = "a shared system prompt spanning blocks! "  # 40 chars


async def test_debug_cache_endpoint_matches_engine(tmp_path):
    import aiohttp

    from kfserving_tpu.predictors.llm import GenerativeModel
    from kfserving_tpu.server.app import ModelServer

    model = GenerativeModel("gen", _write_gen_dir(tmp_path, "gen"))
    model.load()
    server = ModelServer(http_port=0)
    await server.start_async([model], host="127.0.0.1")
    base = f"http://127.0.0.1:{server.http_port}"
    try:
        async with aiohttp.ClientSession() as s:
            for tail in ("first", "second"):
                async with s.post(
                        f"{base}/v2/models/gen/generate",
                        json={"text_input": SHARED_PROMPT + tail,
                              "parameters": {"max_tokens": 4}}) as r:
                    assert r.status == 200, await r.text()
            # A finished request's blocks are released a few waves
            # late (the zombie-wave deferral): read both views of an
            # engine at rest, not of one still releasing.
            for _ in range(200):
                st = model.engine.stats()["paged"]
                if (st["free_blocks"] + st["reclaimable_blocks"]
                        == st["pool_blocks"]):
                    break
                await asyncio.sleep(0.05)
            async with s.get(f"{base}/debug/cache?top_k=3") as r:
                assert r.status == 200
                body = await r.json()
        snap = body["models"]["gen"]
        st = model.engine.stats()["paged"]
        assert snap["paged"] is True
        assert snap["index_entries"] == st["index_entries"]
        # Acceptance: the snapshot's pool view is the engine's.
        for key in ("free_blocks", "reclaimable_blocks"):
            assert snap["pool"][key] == st[key], key
        assert snap["pool"]["prefix_hits"] == st["prefix_hits"] >= 2
        assert len(snap["hot_chains"]) <= 3
        assert body["hbm"] is None  # no manager wired in this server
    finally:
        await server.stop_async()


async def test_metrics_scrape_exports_cache_families(tmp_path):
    """/metrics exports the promoted lookup counters and the bounded
    `_ratio` pool gauges, and the exposition passes the house lint."""
    import aiohttp

    from kfserving_tpu.predictors.llm import GenerativeModel
    from kfserving_tpu.server.app import ModelServer
    from kfserving_tpu.tools.check_metrics import lint_exposition

    model = GenerativeModel("gen", _write_gen_dir(tmp_path, "gen"))
    model.load()
    server = ModelServer(http_port=0)
    await server.start_async([model], host="127.0.0.1")
    base = f"http://127.0.0.1:{server.http_port}"
    try:
        async with aiohttp.ClientSession() as s:
            for tail in ("first", "second"):
                async with s.post(
                        f"{base}/v2/models/gen/generate",
                        json={"text_input": SHARED_PROMPT + tail,
                              "parameters": {"max_tokens": 4}}) as r:
                    assert r.status == 200, await r.text()
            async with s.get(f"{base}/metrics") as r:
                text = await r.text()
        assert "kfserving_tpu_generator_prefix_lookups_total{" in text
        assert "kfserving_tpu_generator_pool_occupancy_ratio{" in text
        assert "kfserving_tpu_request_device_ms_bucket{" in text
        assert lint_exposition(text) == []
    finally:
        await server.stop_async()


async def test_pinned_flightrecorder_entry_embeds_cost(tmp_path):
    """Acceptance: pinned entries embed the request's cost-attribution
    record (device ms, tokens, blocks, cache savings)."""
    import aiohttp

    from kfserving_tpu.predictors.llm import GenerativeModel
    from kfserving_tpu.server.app import ModelServer

    model = GenerativeModel("gen", _write_gen_dir(tmp_path, "gen"))
    model.load()
    server = ModelServer(http_port=0)
    await server.start_async([model], host="127.0.0.1")
    base = f"http://127.0.0.1:{server.http_port}"
    rid = "cache-pin-trace"
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(
                    f"{base}/v2/models/gen/generate",
                    headers={"x-request-id": rid},
                    json={"text_input": SHARED_PROMPT + "pin",
                          "parameters": {"max_tokens": 4}}) as r:
                assert r.status == 200, await r.text()
        # Pin an entry for that trace (a 5xx pin — the trigger kind is
        # irrelevant; the embedding is what's under test).
        server.monitoring.record_request("gen", "generate", 500,
                                         123.0, trace_id=rid)
        dump = server.monitoring.dump_flightrecorder()
        pinned = [e for e in dump["pinned"]
                  if e.get("trace_id") == rid]
        assert pinned, dump["pinned"]
        cost = pinned[0].get("cost")
        assert cost is not None
        assert cost["model"] == "gen"
        assert cost["decode_tokens"] == 4
        assert cost["device_ms"]["decode"] >= 0
        assert "cache_saved_tokens" in cost
    finally:
        await server.stop_async()


# -------------------------------------------------- router federation


async def test_router_federates_debug_cache(tmp_path):
    """Acceptance: GET /debug/cache through the router carries the
    per-replica snapshots under their host keys plus the fleet
    rollup, and matches the serving engine's stats within one
    block."""
    import aiohttp

    from kfserving_tpu.control.controller import Controller
    from kfserving_tpu.control.orchestrator import (
        InProcessOrchestrator,
    )
    from kfserving_tpu.control.router import IngressRouter
    from kfserving_tpu.control.spec import (
        InferenceService,
        PredictorSpec,
    )

    model_dir = _write_gen_dir(tmp_path, "writer")
    orch = InProcessOrchestrator()
    controller = Controller(orch)
    router = IngressRouter(controller)
    await router.start_async()
    try:
        isvc = InferenceService(
            name="writer",
            predictor=PredictorSpec(framework="generative",
                                    storage_uri=model_dir))
        status = await controller.apply(isvc)
        assert status.ready
        base = f"http://127.0.0.1:{router.http_port}"
        async with aiohttp.ClientSession() as s:
            for tail in ("one", "two"):
                async with s.post(
                        f"{base}/v1/models/writer:generate",
                        json={"prompt": SHARED_PROMPT + tail,
                              "max_tokens": 4}) as r:
                    assert r.status == 200, await r.text()
            async with s.get(f"{base}/debug/cache") as r:
                assert r.status == 200
                body = await r.json()
        comp = orch.state["default/writer/predictor"].replicas[0]
        host = comp.host
        assert host in body["replicas"], list(body["replicas"])
        snap = body["replicas"][host]["models"]["writer"]
        engine = comp.handle.repository.get_model("writer").engine
        st = engine.stats()["paged"]
        assert snap["paged"] is True
        assert abs(snap["index_entries"] - st["index_entries"]) <= 1
        assert abs(snap["pool"]["free_blocks"]
                   - st["free_blocks"]) <= 1
        assert body["fleet"]["index_entries"] >= 1
        assert body["fleet"]["prefix_hits"] == st["prefix_hits"]
        # ?replica= narrows to one host; an unknown host answers with
        # an empty replica map rather than an error.
        async with aiohttp.ClientSession() as s:
            async with s.get(
                    f"{base}/debug/cache?replica={host}") as r:
                narrowed = await r.json()
        assert list(narrowed["replicas"]) == [host]
    finally:
        await router.stop_async()
        await orch.shutdown()


# -------------------------------------------------- store boundedness


def test_attribution_store_bounded(monkeypatch):
    monkeypatch.setenv("KFS_ATTRIBUTION_RECORDS", "16")
    for i in range(64):
        attribution.observe("m", f"trace-{i}", {"decode_tokens": i})
    assert len(attribution.recent(limit=1000)) == 16
    assert attribution.lookup("trace-0") is None
    assert attribution.lookup("trace-63")["decode_tokens"] == 63
