"""Tiered KV residency (ISSUE 16): host-memory spill for evicted
conversation state, transactional fault-back, chaos-proven graceful
degradation.

The discriminating bar: every arm — healthy, spill-chaos, fault-back-
chaos — produces BIT-EXACT output versus a no-tier baseline.  The tier
only ever changes where KV bytes live, never what the model computes;
a half-spilled chain is never readable, a failed fault-back degrades
to a clean re-prefill, and the books (eviction-cause split, saved-token
attribution, tier telemetry) stay additive throughout.
"""

import asyncio
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kfserving_tpu.engine.generator import GenerationEngine
from kfserving_tpu.engine.kv_tier import HostKVTier
from kfserving_tpu.models.decoder import DecoderLM, decoder_tiny
from kfserving_tpu.observability import REGISTRY, attribution
from kfserving_tpu.reliability import faults

MAX_SEQ = 64
BS = 16

# Three-turn conversation: P1 registers two full chains, P2's three
# blocks (plus growth) overflow a 4-block pool and evict them, the P1
# return turn must then find its state — on device, in the host tier,
# or by re-prefilling — and always produce the same tokens.
P1 = list(range(1, 2 * BS + 1))
P2 = list(range(40, 40 + 3 * BS))


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
def _clean_slate():
    attribution.clear()
    faults.reset()
    yield
    faults.reset()
    attribution.clear()


def make_paged(tiny, **kw):
    module, variables, _ = tiny
    kw.setdefault("max_slots", 1)
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("prefill_buckets", [16, 32, MAX_SEQ])
    kw.setdefault("block_size", BS)
    return GenerationEngine(module, variables, name=kw.pop(
        "name", "kvtier"), **kw)


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
    total = eng.stats()["paged"]["pool_blocks"]
    for _ in range(int(timeout_s / 0.05)):
        await asyncio.sleep(0.05)
        st = eng.stats()["paged"]
        if st["free_blocks"] + st["reclaimable_blocks"] == total:
            return st
    raise AssertionError(f"pool never settled: {eng.stats()['paged']}")


async def _settle_tier(eng, timeout_s=5.0):
    """Spill commits resolve on the fetch executor AFTER the eviction
    returns — wait for the attempt ledger to balance before asserting
    on causes or tier occupancy."""
    for _ in range(int(timeout_s / 0.05)):
        st = eng.stats()
        ev = st["paged"]["evictions"]
        ht = st.get("host_tier") or {}
        attempts = (ht.get("spills", 0) + ht.get("spill_failures", 0)
                    + ht.get("spill_duplicates", 0))
        settled = (ev["capacity_spilled"] + ev["capacity_dropped"])
        if attempts >= settled and not eng._spill_pending:
            return st
        await asyncio.sleep(0.05)
    return eng.stats()


async def _three_turns(eng):
    """The return-visit workload, one list of token lists out."""
    out = []
    for p in (P1, P2, P1):
        toks, reason = await eng.complete(p, max_new_tokens=3)
        assert reason == "length"
        await _settle_pool(eng)
        out.append(toks)
    return out


async def _baseline(tiny):
    eng = make_paged(tiny, cache_blocks=4, name="kvtier-base")
    try:
        return await _three_turns(eng)
    finally:
        await eng.close()


# ===================================================== healthy path


async def test_spill_faultback_bit_exact_parity(tiny):
    """Tentpole acceptance: a conversation whose blocks were
    capacity-evicted to the host tier resumes with a fault-back —
    tokens identical to an engine that kept everything on device."""
    want = await _baseline(tiny)
    eng = make_paged(tiny, cache_blocks=4, host_tier_blocks=8,
                     name="kvtier-hot")
    try:
        got = await _three_turns(eng)
        assert got == want, "tiered KV changed model output"

        st = await _settle_tier(eng)
        ht = st["host_tier"]
        ev = st["paged"]["evictions"]
        # P2's pressure spilled both P1 chains (plus churn): every
        # capacity eviction was a spill, none degraded to a drop.
        assert ev["capacity_spilled"] >= 2
        assert ev["capacity_dropped"] == 0
        assert ht["spills"] == ev["capacity_spilled"]
        assert ht["spill_failures"] == 0
        # The P1 return turn faulted both chains back with real reads.
        assert ht["faulted_blocks"] == 2
        assert ht["fault_failures"] == 0
        assert ht["faultback_ms"]["p50"] >= 0.0
        # Saved-token ledger: every faulted/coalesced block is one
        # block of prefill the device never recomputed.
        saved = st["paged"]["host_tier_tokens_saved"]
        assert saved == (ht["faulted_blocks"]
                         + ht["coalesced_blocks"]) * BS == 2 * BS

        # Registry twins agree with the engine dict.
        assert _counter_value(
            "kfserving_tpu_generator_kv_tier_spills_total",
            model="kvtier-hot", outcome="spilled") == ht["spills"]
        assert _counter_value(
            "kfserving_tpu_generator_kv_tier_faultbacks_total",
            model="kvtier-hot", outcome="faulted") == 2
        assert _counter_value(
            "kfserving_tpu_generator_kv_tier_tokens_saved_total",
            model="kvtier-hot") == saved
        assert _counter_value(
            "kfserving_tpu_generator_block_evictions_total",
            model="kvtier-hot",
            cause="capacity_spilled") == ev["capacity_spilled"]
        # The probe outcome is its own lookup family label.
        assert _counter_value(
            "kfserving_tpu_generator_prefix_lookups_total",
            model="kvtier-hot", outcome="host_hit") >= 1
        # stats() exposes the /debug/cache host_tier block.
        assert ht["capacity_blocks"] == 8
        assert ht["used_blocks"] >= 2
    finally:
        await eng.close()


# ================================================== chaos: spill site


async def test_spill_chaos_degrades_to_drop_on_evict(tiny):
    """engine.kv_spill firing on every gather: the tier admits
    nothing, every capacity eviction degrades to a plain drop, and the
    return turn re-prefills — output still bit-exact."""
    want = await _baseline(tiny)
    faults.configure({"engine.kv_spill": {"error_rate": 1.0}})
    eng = make_paged(tiny, cache_blocks=4, host_tier_blocks=8,
                     name="kvtier-spillchaos")
    try:
        got = await _three_turns(eng)
        assert got == want, "spill chaos changed model output"

        st = await _settle_tier(eng)
        ht = st["host_tier"]
        ev = st["paged"]["evictions"]
        assert ev["capacity_spilled"] == 0
        assert ev["capacity_dropped"] >= 2
        assert ht["spill_failures"] == ev["capacity_dropped"]
        # Nothing half-spilled is ever visible: the tier stayed empty
        # and no fault-back ever found (or served) a chain.
        assert ht["used_blocks"] == 0
        assert ht["spills"] == 0
        assert ht["faulted_blocks"] == 0
        assert st["paged"]["host_tier_tokens_saved"] == 0
        assert _counter_value(
            "kfserving_tpu_generator_kv_tier_spills_total",
            model="kvtier-spillchaos",
            outcome="failed") == ht["spill_failures"]
    finally:
        await eng.close()


# ============================================== chaos: fault-back site


async def test_faultback_chaos_falls_through_to_reprefill(tiny):
    """engine.kv_faultback firing on every read: the planned fault-back
    rolls back transactionally (nothing was dispatched), the suspect
    tier entries are dropped, and the replanned turn re-prefills from
    scratch — output still bit-exact."""
    want = await _baseline(tiny)
    faults.configure({"engine.kv_faultback": {"error_rate": 1.0}})
    eng = make_paged(tiny, cache_blocks=4, host_tier_blocks=8,
                     name="kvtier-fbchaos")
    try:
        got = await _three_turns(eng)
        assert got == want, "fault-back chaos changed model output"

        st = await _settle_tier(eng)
        ht = st["host_tier"]
        # Spills were healthy; the read-back is what failed.
        assert ht["spills"] >= 2
        assert ht["fault_failures"] >= 2
        assert ht["faulted_blocks"] == 0
        assert ht["coalesced_blocks"] == 0
        # Failed fault-backs drop their entries — the replan MUST miss
        # the tier (a suspect payload may never be served).
        assert ht["dropped"] >= 2
        assert st["paged"]["host_tier_tokens_saved"] == 0
        assert _counter_value(
            "kfserving_tpu_generator_kv_tier_evictions_total",
            model="kvtier-fbchaos",
            reason="faultback_failed") == ht["dropped"]
        assert _counter_value(
            "kfserving_tpu_generator_kv_tier_faultbacks_total",
            model="kvtier-fbchaos",
            outcome="failed") == ht["fault_failures"]
    finally:
        await eng.close()


# ==================================== transactional admission (unit)


def test_half_spilled_chain_is_never_readable():
    """put() publishes the index entry only after the complete payload
    landed — a failed admission leaves no trace a reader could find,
    and it reports failure instead of raising into the spill path."""
    tier = HostKVTier(block_bytes=64, capacity_blocks=2,
                      model="kvtier-unit-txn")
    try:
        chain = b"c" * 16
        # Wrong-size payload: the transactional guard rejects it
        # before any index mutation.
        assert tier.put(chain, b"x" * 63) is False
        assert tier.contains(chain) is False
        assert tier.begin_fault(chain) is False
        with pytest.raises(KeyError):
            tier.read(chain)
        assert tier.spill_failures == 1
        assert tier.debug()["used_blocks"] == 0

        # A complete payload round-trips bit-exactly.
        payload = bytes(range(64))
        assert tier.put(chain, payload) is True
        assert tier.read(chain) == payload
        assert tier.debug()["used_blocks"] == 1
    finally:
        tier.close()


def test_tier_lru_bound_and_admission_aware_eviction():
    """The ledger is bounded by its own LRU; an entry mid-fault-in is
    never victimized — admission skips it for the next-oldest."""
    tier = HostKVTier(block_bytes=8, capacity_blocks=2,
                      model="kvtier-unit-lru")
    try:
        a, b, c = b"a" * 16, b"b" * 16, b"c" * 16
        assert tier.put(a, b"A" * 8) and tier.put(b, b"B" * 8)
        # a is LRU; bracket it as in-flight, then force an eviction.
        assert tier.begin_fault(a) is True
        assert tier.put(c, b"C" * 8) is True
        dbg = tier.debug()
        # b (next-oldest) was the victim; a survived its bracket.
        assert tier.contains(a) and tier.contains(c)
        assert not tier.contains(b)
        assert dbg["evictions"] == 1
        assert dbg["eviction_skips"] == 1
        assert dbg["used_blocks"] == 2
        tier.end_fault(a)

        # With the bracket released, a becomes evictable again.
        d = b"d" * 16
        tier.read(c)  # touch: c is now MRU
        assert tier.put(d, b"D" * 8) is True
        assert not tier.contains(a)
        assert tier.contains(c) and tier.contains(d)

        # Single-flight accounting: a rider on an in-flight fault is
        # counted coalesced, not faulted.
        tier.note_coalesced(3)
        assert tier.debug()["coalesced_blocks"] == 3
    finally:
        tier.close()


# ============================================ attribution additivity


async def test_attribution_additivity_and_registry_twin(tiny):
    """Satellite: host_tier_saved_tokens is its own attribution field,
    never double-counted with cache_saved_tokens — on the fault-back
    turn the two ledgers partition the prompt exactly."""
    from kfserving_tpu.tracing import current_request_id

    eng = make_paged(tiny, cache_blocks=4, host_tier_blocks=8,
                     name="kvtier-attr")
    try:
        await eng.complete(P1 + [7], max_new_tokens=2)
        await _settle_pool(eng)
        await eng.complete(P2, max_new_tokens=2)  # evicts P1's chains
        await _settle_pool(eng)
        await _settle_tier(eng)

        token = current_request_id.set("trace-kvtier-1")
        try:
            await eng.complete(P1 + [9], max_new_tokens=2)
        finally:
            current_request_id.reset(token)
        await _settle_pool(eng)

        rec = attribution.lookup("trace-kvtier-1")
        assert rec is not None
        assert rec["prefill_tokens"] == len(P1) + 1
        # Both P1 blocks came back from the host tier; the device
        # prefix index had nothing — the ledgers never overlap.
        assert rec["host_tier_hit_blocks"] == 2
        assert rec["host_tier_saved_tokens"] == 2 * BS
        assert rec["cache_saved_tokens"] == 0
        # Additivity: saved tokens (either tier) + freshly prefilled
        # tokens account for the whole prompt, exactly once.
        fresh = (rec["prefill_tokens"] - rec["cache_saved_tokens"]
                 - rec["host_tier_saved_tokens"])
        assert fresh == 1

        fam = REGISTRY.family(
            "kfserving_tpu_request_host_tier_saved_tokens")
        assert fam is not None
        hits = [h for labels, h in fam.samples()
                if ("model", "kvtier-attr") in labels.items()]
        assert sum(h.total for h in hits) >= 1
        assert sum(h.sum for h in hits) == 2 * BS
    finally:
        await eng.close()


# ========================================== coalesced riders (wave)


async def test_coalesced_riders_share_one_faultback(tiny):
    """Two requests returning to the same spilled conversation in one
    wave: the first faults each block in (primary), the second rides
    the same in-flight insert — one host read per block, both requests
    credited, and the saved-token invariant holds."""
    module, variables, _ = tiny
    base = make_paged(tiny, max_slots=2, cache_blocks=16,
                      name="kvtier-ride-base")
    try:
        await base.complete(P1 + [69], max_new_tokens=2)
        await _settle_pool(base)
        wa = (await base.complete(P1 + [70], max_new_tokens=3))[0]
        wb = (await base.complete(P1 + [71], max_new_tokens=3))[0]
    finally:
        await base.close()

    eng = make_paged(tiny, max_slots=2, cache_blocks=16,
                     host_tier_blocks=8, name="kvtier-ride")
    try:
        await eng.complete(P1 + [69], max_new_tokens=2)
        await _settle_pool(eng)
        # Force-evict P1's two registered chains (the pool is big, so
        # natural pressure won't) — the evictions queue two spills.
        with eng._block_lock:
            held = []
            # kfslint: disable=spin-loop — bounded drain of the
            # free-block deque under the lock; nothing refills it.
            while eng._pool.free:
                held.append(eng._pool.free.popleft())
            victims = [eng._pool.alloc() for _ in range(2)]
            assert all(v is not None for v in victims)
            assert eng._prefix_index == {}
            eng._pool.free.extend(held + victims)
        # Any enqueue drains the spill queue (gather-before-overwrite
        # discipline); wait for both commits.
        await eng.complete([90, 91, 92], max_new_tokens=1)
        await _settle_pool(eng)
        st = await _settle_tier(eng)
        assert st["host_tier"]["used_blocks"] >= 2

        # Submit both return visits with NO await between them: the
        # pipeline wakes to a two-deep queue and plans one wave.
        ra = eng.submit(P1 + [70], max_new_tokens=3)
        rb = eng.submit(P1 + [71], max_new_tokens=3)

        async def collect(req):
            toks = []
            async for tok, fin in eng.stream(req):
                if tok is not None:
                    toks.append(tok)
                if fin is not None:
                    return toks

        ga, gb = await asyncio.gather(collect(ra), collect(rb))
        assert ga == wa and gb == wb, "rider path changed output"
        await _settle_pool(eng)

        st = await _settle_tier(eng)
        ht = st["host_tier"]
        # Two physical reads, two riders on them — one host read per
        # block regardless of how many requests returned.
        assert ht["faulted_blocks"] == 2
        assert ht["coalesced_blocks"] == 2
        assert ht["fault_failures"] == 0
        # Saved-token invariant: every credited block (primary or
        # rider) is one block of prefill nobody recomputed.
        assert st["paged"]["host_tier_tokens_saved"] == \
            (ht["faulted_blocks"] + ht["coalesced_blocks"]) * BS
        assert _counter_value(
            "kfserving_tpu_generator_kv_tier_faultbacks_total",
            model="kvtier-ride", outcome="coalesced") == 2
    finally:
        await eng.close()


# ================================================ fault-back storms


def test_faultback_storm_pins_flight_recorder_once_per_window():
    """A fault-back storm (device pool churning conversations through
    the host tier) pins ONE flight-recorder entry per window, carrying
    the tier's debug block."""
    from kfserving_tpu.observability.monitoring.flight_recorder import (
        FlightRecorder,
    )

    tier = HostKVTier(block_bytes=8, capacity_blocks=4,
                      model="kvtier-storm")
    try:
        tier.storm_threshold = 2
        tier.storm_window_s = 60.0
        rec = FlightRecorder()
        tier.attach_flight_recorder(rec)

        tier.note_faultback(2, 1.0)   # at threshold: no pin yet
        assert rec.dump(10)["pinned"] == []
        tier.note_faultback(1, 1.0)   # crosses it: one pin
        pinned = rec.dump(10)["pinned"]
        assert len(pinned) == 1
        entry = pinned[-1]
        assert entry["pinned"] == "kv_faultback_storm"
        assert entry["kind"] == "kv_tier_faultback_storm"
        assert entry["model"] == "kvtier-storm"
        assert entry["faults_in_window"] >= 3
        assert entry["host_tier"]["faulted_blocks"] == 3
        # Still inside the window: more faults do NOT re-pin.
        tier.note_faultback(4, 1.0)
        assert len(rec.dump(10)["pinned"]) == 1
    finally:
        tier.close()


# ================== durable manifest & predecessor adoption (ISSUE 19)


def _persistent(d, model="handoff", **kw):
    kw.setdefault("block_bytes", 64)
    kw.setdefault("capacity_blocks", 4)
    return HostKVTier(directory=str(d), model=model, **kw)


def _payload(seed, size=64):
    return np.random.default_rng(seed).integers(
        0, 256, size, dtype=np.uint8).tobytes()


def _gen_files(d, suffix):
    return sorted(glob.glob(os.path.join(str(d), f"kv_tier-*{suffix}")))


def test_persistent_reattach_roundtrip(tmp_path):
    """A successor opening the same tier dir adopts the predecessor's
    entries bit-exactly, drains the old generation's files, and the
    adoption is visible in handoff tallies + the registry twin."""
    c1, c2 = b"1" * 16, b"2" * 16
    p1, p2 = _payload(1), _payload(2)
    a = _persistent(tmp_path, model="handoff-rt")
    assert a.persistent and a.put(c1, p1) and a.put(c2, p2)
    a.close()
    # Persistent close keeps the generation on disk for the successor.
    assert len(_gen_files(tmp_path, ".manifest")) == 1
    assert len(_gen_files(tmp_path, ".bin")) == 1

    b = _persistent(tmp_path, model="handoff-rt")
    try:
        assert b.handoff["adopted"] == 2
        assert b.handoff["generations_adopted"] == 1
        assert b.read(c1) == p1 and b.read(c2) == p2
        # The predecessor's files were drained away; only the
        # successor's own generation remains.
        assert len(_gen_files(tmp_path, ".manifest")) == 1
        assert _counter_value(
            "kfserving_tpu_kv_handoff_reattached_blocks_total",
            model="handoff-rt", outcome="adopted") >= 2
        assert b.debug()["handoff"]["adopted"] == 2
    finally:
        b.close()


def test_reattach_truncated_payload_drops_only_that_entry(tmp_path):
    """Satellite: a payload file cut short of a recorded slot drops
    ONLY that entry — the intact one still adopts."""
    c1, c2 = b"1" * 16, b"2" * 16
    p1 = _payload(3)
    a = _persistent(tmp_path, model="handoff-trunc")
    assert a.put(c1, p1) and a.put(c2, _payload(4))
    stride = a.slot_bytes
    a.close()
    bin_path = _gen_files(tmp_path, ".bin")[0]
    # c2 landed in slot 1 (slots issue in order): cut its payload off.
    os.truncate(bin_path, stride)

    b = _persistent(tmp_path, model="handoff-trunc")
    try:
        assert b.handoff["adopted"] == 1
        assert b.handoff["truncated"] == 1
        assert b.read(c1) == p1
        assert not b.contains(c2)
    finally:
        b.close()


def test_reattach_digest_mismatch_drops_only_that_entry(tmp_path):
    """Satellite: a payload whose bytes no longer match the recorded
    digest is counted corrupt and never served — the other entry still
    adopts, and boot never crashes."""
    c1, c2 = b"1" * 16, b"2" * 16
    p2 = _payload(6)
    a = _persistent(tmp_path, model="handoff-corrupt")
    assert a.put(c1, _payload(5)) and a.put(c2, p2)
    a.close()
    bin_path = _gen_files(tmp_path, ".bin")[0]
    with open(bin_path, "r+b") as f:
        f.seek(0)  # c1's slot
        byte = f.read(1)
        f.seek(0)
        f.write(bytes([byte[0] ^ 0xFF]))

    b = _persistent(tmp_path, model="handoff-corrupt")
    try:
        assert b.handoff["adopted"] == 1
        assert b.handoff["corrupt"] == 1
        assert not b.contains(c1)
        assert b.read(c2) == p2
        assert _counter_value(
            "kfserving_tpu_kv_handoff_reattached_blocks_total",
            model="handoff-corrupt", outcome="corrupt") == 1
    finally:
        b.close()


def test_reattach_torn_and_version_skew_records(tmp_path):
    """Satellite: an unparseable manifest line (crash mid-append) and
    a record from a future schema version each drop only themselves;
    the healthy records still adopt."""
    c1, c2 = b"1" * 16, b"2" * 16
    a = _persistent(tmp_path, model="handoff-torn")
    assert a.put(c1, _payload(7)) and a.put(c2, _payload(8))
    a.close()
    mpath = _gen_files(tmp_path, ".manifest")[0]
    with open(mpath, "a") as f:
        f.write('{"op": "put", "v":\n')          # torn mid-append
        f.write(json.dumps({"op": "put", "v": 2,
                            "chain": "ab" * 16, "slot": 2,
                            "digest": "00" * 16}) + "\n")
        f.write(json.dumps({"op": "frobnicate", "v": 1}) + "\n")

    b = _persistent(tmp_path, model="handoff-torn")
    try:
        assert b.handoff["adopted"] == 2
        assert b.handoff["torn"] == 2          # garbage + unknown op
        assert b.handoff["version_skew"] == 1
        assert b.contains(c1) and b.contains(c2)
    finally:
        b.close()


def test_reattach_header_version_skew_discards_generation(tmp_path):
    """A manifest whose HEADER schema version is unknown cannot be
    interpreted at all: every record counts version_skew, the
    generation is discarded, and boot continues clean."""
    a = _persistent(tmp_path, model="handoff-hdr")
    assert a.put(b"1" * 16, _payload(9))
    a.close()
    mpath = _gen_files(tmp_path, ".manifest")[0]
    lines = open(mpath).read().splitlines()
    header = json.loads(lines[0])
    header["v"] = 99
    lines[0] = json.dumps(header)
    with open(mpath, "w") as f:
        f.write("\n".join(lines) + "\n")

    b = _persistent(tmp_path, model="handoff-hdr")
    try:
        assert b.handoff["adopted"] == 0
        assert b.handoff["version_skew"] == 1
        assert b.handoff["generations_rejected"] == 1
        # Discarded: no predecessor files linger to be rescanned.
        assert len(_gen_files(tmp_path, ".manifest")) == 1
    finally:
        b.close()


def test_reattach_eviction_supersede_and_drop_records(tmp_path):
    """Replay semantics: an eviction writes NO drop record — the
    superseding put to the same slot erases the victim on replay; an
    explicit drop() erases its chain.  Only the live entry adopts."""
    ca, cb, cc = b"a" * 16, b"b" * 16, b"c" * 16
    pb = _payload(11)
    a = _persistent(tmp_path, model="handoff-replay",
                    capacity_blocks=1)
    assert a.put(ca, _payload(10))
    assert a.put(cb, pb)       # evicts ca: same-slot supersede
    a.close()
    b = _persistent(tmp_path, model="handoff-replay",
                    capacity_blocks=4)
    try:
        assert b.handoff["adopted"] == 1
        assert not b.contains(ca)
        assert b.read(cb) == pb
        # Explicit drop: the record survives the handoff too.
        assert b.put(cc, _payload(12))
        b.drop(cc)
    finally:
        b.close()
    c = _persistent(tmp_path, model="handoff-replay",
                    capacity_blocks=4)
    try:
        assert c.read(cb) == pb
        assert not c.contains(cc)
    finally:
        c.close()


def test_reattach_live_generation_is_never_stolen(tmp_path):
    """The flock is the liveness authority: a generation whose owner
    still runs (holds the lock) is skipped entirely — no adoption, no
    deletion."""
    live = _persistent(tmp_path, model="handoff-live")
    assert live.put(b"1" * 16, _payload(13))
    try:
        b = _persistent(tmp_path, model="handoff-live")
        try:
            assert b.handoff["adopted"] == 0
            assert b.handoff["generations_live"] == 1
            assert live.contains(b"1" * 16)
        finally:
            b.close()
        # Both generations still on disk: nothing was stolen.
        assert len(_gen_files(tmp_path, ".manifest")) == 2
    finally:
        live.close()


def test_reattach_capacity_never_evicts_own_entries(tmp_path):
    """Adoption takes only FREE slots: the successor's live working
    set outranks the predecessor's cold tail (dropped_capacity counts
    the overflow honestly)."""
    own = b"o" * 16
    po = _payload(14)
    b = _persistent(tmp_path, model="handoff-cap", capacity_blocks=1)
    try:
        assert b.put(own, po)
        a = _persistent(tmp_path, model="handoff-cap",
                        capacity_blocks=4)
        assert a.put(b"1" * 16, _payload(15))
        assert a.put(b"2" * 16, _payload(16))
        a.close()
        res = b.reattach()
        assert res["adopted"] == 0
        assert res["dropped_capacity"] == 2
        assert b.read(own) == po
    finally:
        b.close()


def test_reattach_model_mismatch_leaves_generation_alone(tmp_path):
    """A different model's generation sharing the dir is neither
    adopted nor deleted — its rightful successor still finds it."""
    c1 = b"1" * 16
    p1 = _payload(17)
    a = _persistent(tmp_path, model="handoff-m1")
    assert a.put(c1, p1)
    a.close()
    other = _persistent(tmp_path, model="handoff-m2")
    try:
        assert other.handoff["adopted"] == 0
        assert not other.contains(c1)
    finally:
        other.close()
    heir = _persistent(tmp_path, model="handoff-m1")
    try:
        assert heir.handoff["adopted"] == 1
        assert heir.read(c1) == p1
    finally:
        heir.close()


def test_tier_dir_non_directory_target_fails_clean(tmp_path):
    """Satellite: KFS_KV_TIER_DIR pointing at a FILE is a clear
    startup error, not a traceback from some later mmap call."""
    target = tmp_path / "not-a-dir"
    target.write_text("occupied")
    with pytest.raises(ValueError, match="not a directory"):
        HostKVTier(block_bytes=64, capacity_blocks=2,
                   directory=str(target), model="handoff-baddir")


# ================================================== sanitizer smoke


async def test_sanitizer_smoke_spill_faultback_cycle(monkeypatch,
                                                     tiny):
    """Satellite: KFS_SANITIZE=1 over a spill -> fault-back cycle.
    Post-warmup, the tier's gather/insert dispatches reuse their
    compiled programs and every D2H fetch runs sanctioned off-loop —
    zero violations is the acceptance bar."""
    from kfserving_tpu.reliability import sanitizer

    monkeypatch.setenv("KFS_SANITIZE", "1")
    sanitizer.reset()
    # One-full-block conversations against a 2-block pool: EVERY turn
    # evicts exactly one chain (spill, gather padded to 1) and every
    # return visit faults exactly one back (insert padded to 1), so
    # the warmup cycle compiles the complete steady-state shape set.
    pa = list(range(1, BS + 1))
    pb = list(range(20, 20 + BS))
    eng = make_paged(tiny, cache_blocks=2, host_tier_blocks=8,
                     name="kvtier-sanitize")
    try:
        for p in (pa, pb, pa):  # warmup: spill + fault-back compiled
            await eng.complete(p, max_new_tokens=2)
            await _settle_pool(eng)
        await _settle_tier(eng)
        sanitizer.declare_warmup_complete(eng.sanitize_source)

        for p in (pb, pa):      # steady state: same shapes again
            await eng.complete(p, max_new_tokens=2)
            await _settle_pool(eng)
        st = await _settle_tier(eng)
        assert st["host_tier"]["faulted_blocks"] >= 2
        assert sanitizer.violations() == {}
    finally:
        await eng.close()
        sanitizer.reset()
