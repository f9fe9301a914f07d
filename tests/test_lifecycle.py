"""Replica lifecycle tests (ISSUE 10): mmap param cache, warm-standby
recycles, announced-swap holds, and crash-promoted failover.

Fast-tier by design: the lifecycle smoke (spawn standby -> activate ->
serve) and the crash chaos tests run under `-m 'not slow'` with
JAX_PLATFORMS=cpu, so a swap regression fails the suite — not just the
soak.
"""

import asyncio
import json
import os
import signal

import numpy as np
import pytest

from kfserving_tpu.engine import param_cache
from kfserving_tpu.reliability import faults

pytestmark = pytest.mark.asyncio


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(autouse=True)
def _private_param_cache(tmp_path, monkeypatch):
    """Every test gets its own cache dir: hits must come from THIS
    test's stores, never a prior run's ~/.cache leftovers."""
    monkeypatch.setenv(param_cache.ENV_VAR, str(tmp_path / "pcache"))
    yield


def _write_mlp_dir(tmp_path, **cfg_overrides):
    d = tmp_path / "mlp"
    d.mkdir(exist_ok=True)
    cfg = {"architecture": "mlp",
           "arch_kwargs": {"input_dim": 4, "features": [8],
                           "num_classes": 3},
           "max_latency_ms": 2.0, "output": "argmax", "warmup": False}
    cfg.update(cfg_overrides)
    (d / "config.json").write_text(json.dumps(cfg))
    return str(d)


# ------------------------------------------------------- param cache
def test_param_cache_roundtrip_mixed_dtypes():
    """Nested variable trees round-trip through the mmap layout with
    exact bytes, including the accelerator dtypes numpy can't name
    (bfloat16 via ml_dtypes)."""
    import ml_dtypes

    tree = {
        "params": {
            "Dense_0": {
                "kernel": np.arange(12, dtype=np.float32).reshape(3, 4),
                "bias": np.linspace(0, 1, 4).astype(ml_dtypes.bfloat16),
            }
        },
        "batch_stats": {"mean": np.zeros(3, dtype=np.float64)},
    }
    key = param_cache.content_key("mlp", {"features": [8]})
    assert param_cache.store(key, tree)
    out = param_cache.load(key)
    assert out is not None
    kernel = out["params"]["Dense_0"]["kernel"]
    assert kernel.dtype == np.float32
    np.testing.assert_array_equal(
        kernel, tree["params"]["Dense_0"]["kernel"])
    bias = out["params"]["Dense_0"]["bias"]
    assert bias.dtype == ml_dtypes.bfloat16
    np.testing.assert_array_equal(
        np.asarray(bias, np.float32),
        np.asarray(tree["params"]["Dense_0"]["bias"], np.float32))
    np.testing.assert_array_equal(
        np.asarray(out["batch_stats"]["mean"]),
        tree["batch_stats"]["mean"])


def test_param_cache_miss_corruption_and_disable(monkeypatch):
    tree = {"params": {"w": np.ones(8, np.float32)}}
    key = param_cache.content_key("mlp", {})
    assert param_cache.load(key) is None  # miss
    assert param_cache.store(key, tree)
    # Corrupt the manifest: load must fail CLEAN (None) and delete the
    # entry so the next boot re-stores instead of crashing forever.
    entry = os.path.join(param_cache.cache_dir(), key)
    with open(os.path.join(entry, param_cache.MANIFEST_NAME), "w") as f:
        f.write("{not json")
    assert param_cache.load(key) is None
    assert not os.path.exists(entry)
    # Disabled cache: no store, no load, no crash.
    monkeypatch.setenv(param_cache.ENV_VAR, "0")
    assert param_cache.cache_dir() is None
    assert not param_cache.store(key, tree)
    assert param_cache.load(key) is None


def test_param_cache_key_tracks_checkpoint_digest(tmp_path):
    """Invalidation is by content digest: a new checkpoint (or config)
    MUST miss; identical content must agree on the key."""
    ck = tmp_path / "checkpoint.msgpack"
    ck.write_bytes(b"weights-v1")
    d1 = param_cache.file_digest(str(ck))
    k1 = param_cache.content_key("mlp", {"a": 1}, 0, d1)
    assert k1 == param_cache.content_key("mlp", {"a": 1}, 0, d1)
    ck.write_bytes(b"weights-v2")
    assert param_cache.content_key(
        "mlp", {"a": 1}, 0, param_cache.file_digest(str(ck))) != k1
    assert param_cache.content_key("mlp", {"a": 2}, 0, d1) != k1
    assert param_cache.content_key("mlp", {"a": 1}, 7, d1) != k1
    # The shipped .sha256 sidecar wins over re-hashing the blob.
    (tmp_path / "checkpoint.msgpack.sha256").write_text(
        "cafebabe  checkpoint.msgpack\n")
    assert param_cache.file_digest(str(ck)) == "cafebabe"


async def test_jax_model_mmap_load_parity(tmp_path):
    """Second load of the same artifact maps instead of materializing
    (param_source == "mmap") and serves bit-identical predictions."""
    from kfserving_tpu.predictors.jax_model import JaxModel

    model_dir = _write_mlp_dir(tmp_path)
    first = JaxModel("m", model_dir)
    first.load()
    assert first.param_source == "init"
    second = JaxModel("m", model_dir)
    second.load()
    assert second.param_source == "mmap"
    x = np.random.default_rng(0).normal(size=(3, 4)).astype(np.float32)
    r1 = await first.predict({"instances": x.tolist()})
    r2 = await second.predict({"instances": x.tolist()})
    assert r1 == r2
    # Provenance is visible on the scrape path.
    assert second.engine_stats()["param_source"] == "mmap"


# ------------------------------------------- router swap-window holds
class _StubOrch:
    """Just enough orchestrator for the router's hold path."""

    def __init__(self):
        self.state = {}
        self.swap_announced = {}
        self._replicas = {}

    def replicas(self, cid):
        return self._replicas.get(cid, [])

    def pending_creates(self, cid, rev):
        return 0


class _StubReplica:
    def __init__(self, revision, host):
        self.revision = revision
        self.host = host


def _stub_router(orch):
    import types

    from kfserving_tpu.control.router import IngressRouter

    controller = types.SimpleNamespace(
        reconciler=types.SimpleNamespace(orchestrator=orch))
    return IngressRouter(controller, buffer_deadline_s=2.0)


async def test_swap_hold_serves_when_replica_appears():
    """A request inside an announced swap window HOLDS (no 503) and is
    served the moment the successor registers."""
    from kfserving_tpu.observability import metrics as obs

    orch = _StubOrch()
    router = _stub_router(orch)
    cid = "default/m/predictor"
    orch.swap_announced[cid] = \
        asyncio.get_running_loop().time() + 5.0

    async def register_later():
        await asyncio.sleep(0.15)
        orch._replicas[cid] = [_StubReplica("rev1", "127.0.0.1:9999")]

    task = asyncio.ensure_future(register_later())
    verdict, host = await router._hold_for_swap(cid, "rev1", (), None)
    await task
    assert (verdict, host) == ("host", "127.0.0.1:9999")
    served = obs.router_swap_held_total().labels(outcome="served")
    assert served.value == 1.0
    assert not router._swap_held  # hold accounting drained


async def test_swap_hold_bounded_queue_sheds_at_cap():
    orch = _StubOrch()
    router = _stub_router(orch)
    router.swap_hold_max = 1
    cid = "default/m/predictor"
    orch.swap_announced[cid] = \
        asyncio.get_running_loop().time() + 5.0
    router._swap_held[cid] = 1  # queue already at cap
    verdict, _ = await router._hold_for_swap(cid, "rev1", (), None)
    assert verdict == "shed"


async def test_swap_hold_passes_without_announcement():
    orch = _StubOrch()
    router = _stub_router(orch)
    verdict, _ = await router._hold_for_swap(
        "default/m/predictor", "rev1", (), None)
    assert verdict == "pass"


# ------------------------------------------------- reconciler reaping
async def test_reconciler_reaps_standbys_of_retired_revisions():
    """Scaling a revision to zero must also reap its armed standby —
    a quarantined canary's standby surviving to be promoted later
    would resurrect the rolled-back revision."""
    from kfserving_tpu.control.reconciler import (
        InferenceServiceReconciler,
    )

    reaped = []

    class _Orch:
        def __init__(self):
            self._replicas = [_StubReplica("bad", "h1")]

        def replicas(self, cid):
            return list(self._replicas)

        async def delete_replica(self, replica):
            self._replicas.remove(replica)

        async def create_replica(self, cid, rev, spec, placement=None):
            self._replicas.append(_StubReplica(rev, f"h-{rev}"))

        async def reap_standbys(self, cid, revision=None):
            reaped.append((cid, revision))

    rec = InferenceServiceReconciler(_Orch())
    await rec._scale_revisions("default/m/predictor", {"good": 1},
                               comp=None, specs={"good": None})
    assert ("default/m/predictor", "bad") in reaped
    await rec._scale_revisions("default/m/predictor", {}, comp=None)
    assert ("default/m/predictor", None) in reaped


# ------------------------------------------------- metrics lint
def test_lifecycle_metric_families_lint_clean():
    from kfserving_tpu.observability import REGISTRY
    from kfserving_tpu.observability import metrics as obs
    from kfserving_tpu.tools.check_metrics import lint_exposition

    obs.lifecycle_swaps_total().labels(
        mode="warm_standby", outcome="ok").inc()
    obs.lifecycle_swap_failures_total().labels(
        reason="activate_timeout").inc()
    obs.lifecycle_promotions_total().labels(
        trigger="health_fail", outcome="promoted").inc()
    obs.lifecycle_phase_ms().labels(phase="activate").observe(450.0)
    obs.lifecycle_standby_pool().labels(component="c").set(1.0)
    obs.router_swap_held_total().labels(outcome="expired").inc()
    obs.router_swap_hold_ms().observe(10.0)
    obs.router_stream_failover_total().labels(model="m").inc()
    obs.param_cache_total().labels(outcome="store").inc()
    problems = lint_exposition("\n".join(REGISTRY.render_lines()))
    assert problems == []


# ------------------------------------------- subprocess lifecycle
async def _wait_for(predicate, timeout_s=60.0, interval_s=0.2):
    deadline = asyncio.get_running_loop().time() + timeout_s
    while asyncio.get_running_loop().time() < deadline:
        result = predicate()
        if result:
            return result
        await asyncio.sleep(interval_s)
    raise AssertionError("condition not met within "
                         f"{timeout_s}s: {predicate}")


async def test_lifecycle_smoke_standby_spawn_activate_serve(tmp_path):
    """The tier-1 lifecycle smoke (ISSUE 10 satellite): spawn a
    standby replica (no device-touching load), verify it is alive but
    NOT serving a model, activate it, verify it serves — the whole
    standby contract in one pass, CPU-only."""
    import aiohttp

    from kfserving_tpu.control.spec import PredictorSpec
    from kfserving_tpu.control.subprocess_orchestrator import (
        SubprocessOrchestrator,
    )

    orch = SubprocessOrchestrator(
        env_overrides={"JAX_PLATFORMS": "cpu"})
    spec = PredictorSpec(framework="jax",
                         storage_uri=_write_mlp_dir(tmp_path))
    cid = "default/smoke/predictor"
    standby = await orch.create_replica(cid, "rev1", spec,
                                        standby=True)
    try:
        assert orch.replicas(cid) == []  # armed, NOT in rotation
        async with aiohttp.ClientSession() as session:
            # Alive (liveness answers) but the model is not loaded.
            async with session.get(
                    f"http://{standby.host}/") as resp:
                assert resp.status == 200
            async with session.get(
                    f"http://{standby.host}/v1/models/smoke") as resp:
                assert resp.status != 200
            await orch._activate_standby(standby)
            assert [r.host for r in orch.replicas(cid)] == \
                [standby.host]
            async with session.post(
                    f"http://{standby.host}/v1/models/smoke:predict",
                    json={"instances": [[0, 1, 2, 3]]}) as resp:
                assert resp.status == 200
                assert "predictions" in await resp.json()
            # The activate response/phase marks carry provenance.
            async with session.get(
                    f"http://{standby.host}/startup_phases") as resp:
                phases = await resp.json()
        assert "standby_activate" in phases
    finally:
        await orch.shutdown()


@pytest.mark.chaos
async def test_crash_promotion_within_one_tick(tmp_path):
    """A SIGKILLed replica is replaced by its armed standby in one
    supervisor tick, with the decision trail pinned in the
    supervisor's flight recorder."""
    import aiohttp

    from kfserving_tpu.control.spec import PredictorSpec
    from kfserving_tpu.control.subprocess_orchestrator import (
        RecyclePolicy,
        SubprocessOrchestrator,
    )

    orch = SubprocessOrchestrator(
        env_overrides={"JAX_PLATFORMS": "cpu"},
        recycle=RecyclePolicy(check_interval_s=0.3, min_age_s=0.0))
    spec = PredictorSpec(framework="jax",
                         storage_uri=_write_mlp_dir(tmp_path))
    cid = "default/crash/predictor"
    replica = await orch.create_replica(cid, "rev1", spec)
    try:
        pool = await _wait_for(
            lambda: orch._standbys.get((cid, "rev1")))
        standby = pool[0]
        os.kill(replica.handle.process.pid, signal.SIGKILL)
        await _wait_for(lambda: orch.promotions >= 1, timeout_s=30.0)
        reps = orch.replicas(cid)
        assert [r.host for r in reps] == [standby.host]
        async with aiohttp.ClientSession() as session:
            async with session.post(
                    f"http://{standby.host}/v1/models/crash:predict",
                    json={"instances": [[0, 1, 2, 3]]}) as resp:
                assert resp.status == 200
        pinned = orch.flight_recorder.dump(
            limit=10, pinned_only=True)["pinned"]
        failover = [e for e in pinned
                    if e.get("kind") == "replica_failover"]
        assert failover, pinned
        entry = failover[-1]
        assert entry["trigger"] == "process_exit"
        assert entry["outcome"] == "promoted"
        assert entry["dead_host"] == replica.host
        assert entry["promoted_host"] == standby.host
        assert entry["phases"]["total_s"] >= 0
    finally:
        await orch.shutdown()


@pytest.mark.chaos
async def test_standby_activation_failure_keeps_incumbent(tmp_path):
    """KFS_FAULTS chaos at orchestrator.standby_activate: the swap
    aborts, the INCUMBENT keeps serving untouched, the broken standby
    is torn down, and the failure is counted + pinned.  The next tick
    retries (fail_first=1) and succeeds."""
    import aiohttp

    from kfserving_tpu.control.spec import PredictorSpec
    from kfserving_tpu.control.subprocess_orchestrator import (
        RecyclePolicy,
        SubprocessOrchestrator,
    )

    faults.configure({"orchestrator.standby_activate":
                      {"fail_first": 1}})
    orch = SubprocessOrchestrator(
        env_overrides={"JAX_PLATFORMS": "cpu"},
        recycle=RecyclePolicy(max_requests=3, check_interval_s=0.3,
                              min_age_s=0.0, exclusive_device=False))
    spec = PredictorSpec(framework="jax",
                         storage_uri=_write_mlp_dir(tmp_path))
    cid = "default/chaos/predictor"
    replica = await orch.create_replica(cid, "rev1", spec)
    incumbent_pid = replica.handle.process.pid
    try:
        async with aiohttp.ClientSession() as session:
            url = f"http://{replica.host}/v1/models/chaos:predict"
            for _ in range(4):
                async with session.post(
                        url, json={"instances": [[0, 1, 2, 3]]}) as r:
                    assert r.status == 200
            await _wait_for(lambda: orch.swap_failures >= 1,
                            timeout_s=60.0)
            # Incumbent untouched and still serving.
            assert replica.handle.process.returncode is None
            assert [r.host for r in orch.replicas(cid)] == \
                [replica.host]
            async with session.post(
                    url, json={"instances": [[0, 1, 2, 3]]}) as r:
                assert r.status == 200
            pinned = orch.flight_recorder.dump(
                limit=10, pinned_only=True)["pinned"]
            assert any(e.get("kind") == "swap_failure"
                       for e in pinned), pinned
            from kfserving_tpu.observability import metrics as obs

            failures = obs.lifecycle_swap_failures_total().labels(
                reason="activate_error")
            assert failures.value >= 1.0
            # Retry succeeds once the injected fault is spent: the
            # incumbent is eventually recycled by a clean warm swap.
            await _wait_for(lambda: orch.recycle_count >= 1,
                            timeout_s=90.0)
            assert replica.handle.process.returncode is not None
            reps = orch.replicas(cid)
            assert reps and reps[0].host != replica.host
    finally:
        await orch.shutdown()


@pytest.mark.chaos
async def test_mid_stream_kill_promotes_standby_and_signals(tmp_path):
    """THE crash-failover acceptance flow: a generative replica is
    SIGKILLed mid-token-stream.  The router surfaces an explicit
    retriable failover event on the open stream (never a dead
    socket), the supervisor promotes the armed standby, a retried
    generate lands on the successor, and the failover timeline is
    pinned + federated at /debug/flightrecorder as
    replica="supervisor"."""
    import aiohttp

    from kfserving_tpu.control.controller import Controller
    from kfserving_tpu.control.router import IngressRouter
    from kfserving_tpu.control.spec import (
        InferenceService,
        PredictorSpec,
    )
    from kfserving_tpu.control.subprocess_orchestrator import (
        RecyclePolicy,
        SubprocessOrchestrator,
    )

    d = tmp_path / "llm"
    d.mkdir()
    (d / "config.json").write_text(json.dumps({
        "architecture": "decoder_tiny",
        "arch_kwargs": {"num_layers": 2, "hidden_size": 64,
                        "num_heads": 2, "intermediate_size": 128,
                        "max_seq": 96},
        "max_slots": 2, "max_seq": 96,
        "prefill_buckets": [16],
        "max_new_tokens": 512,
        "tokenizer": "byte",
    }))
    orch = SubprocessOrchestrator(
        env_overrides={"JAX_PLATFORMS": "cpu"},
        recycle=RecyclePolicy(check_interval_s=0.3, min_age_s=0.0))
    controller = Controller(orch)
    router = IngressRouter(controller, buffer_deadline_s=30.0)
    await router.start_async()
    cid = "default/gen/predictor"
    try:
        await controller.apply(InferenceService(
            name="gen",
            predictor=PredictorSpec(framework="generative",
                                    storage_uri=f"file://{d}")))
        replica = (await _wait_for(lambda: orch.replicas(cid)))[0]
        # The standby must be ARMED before the kill: promotion within
        # one tick is the contract under test.
        await _wait_for(lambda: orch._standbys.get((cid,
                                                    replica.revision)))
        base = f"http://127.0.0.1:{router.http_port}"
        events = []
        async with aiohttp.ClientSession() as session:
            async with session.post(
                    f"{base}/v2/models/gen/generate_stream",
                    json={"text_input": "stream then die",
                          "max_tokens": 400}) as resp:
                assert resp.status == 200
                assert resp.headers.get("content-type", "").startswith(
                    "text/event-stream")
                # The SSE response is committed (headers through the
                # router) and the generation has ~80 tokens to go:
                # kill NOW, before the stream can possibly finish —
                # every later event must come from the failover path.
                os.kill(replica.handle.process.pid, signal.SIGKILL)
                buffer = b""
                async for chunk in resp.content.iter_any():
                    buffer += chunk
            for line in buffer.decode().splitlines():
                if line.startswith("data: "):
                    events.append(json.loads(line[6:]))
            # The stream ended with the EXPLICIT retriable failover
            # signal, not a silent close or generic error.
            final = events[-1]
            assert final["finish_reason"] == "failover", events[-3:]
            assert final["retriable"] is True
            # Standby promoted within the supervisor's tick cadence.
            await _wait_for(lambda: orch.promotions >= 1,
                            timeout_s=30.0)
            successor = (await _wait_for(
                lambda: orch.replicas(cid)))[0]
            assert successor.host != replica.host
            # A retried request lands on the promoted successor.
            async with session.post(
                    f"{base}/v1/models/gen:generate",
                    json={"prompt": "retry me",
                          "max_tokens": 4}) as resp:
                assert resp.status == 200
                assert "text_output" in await resp.json()
            # Failover timeline visible through the router federation.
            async with session.get(
                    f"{base}/debug/flightrecorder?pinned=1") as resp:
                body = await resp.json()
        sup = [e for e in body["pinned"]
               if e.get("replica") == "supervisor"
               and e.get("kind") == "replica_failover"]
        assert sup, body["pinned"]
        assert sup[-1]["component"] == cid
        assert sup[-1]["outcome"] == "promoted"
        assert sup[-1]["phases"]["total_s"] < 10.0
    finally:
        await router.stop_async()
        await orch.shutdown()
