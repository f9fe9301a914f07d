"""Reliability layer: RetryPolicy / CircuitBreaker / Deadline units,
plus fault-injected (chaos) integration tests of the wrapped edges —
the batcher queue's deadline 504, the puller's retry-then-succeed, and
the router's open-breaker replica skip."""

import asyncio
import json
import os

import pytest

from kfserving_tpu.reliability import (
    CircuitBreaker,
    Deadline,
    DeadlineExceeded,
    FaultInjected,
    RetryPolicy,
    current_deadline,
    deadline_scope,
    faults,
)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


# ------------------------------------------------------------- retry


async def test_retry_succeeds_after_transient_failures():
    calls = {"n": 0}

    async def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise ConnectionError("flake")
        return "ok"

    policy = RetryPolicy(max_attempts=3, base_delay_s=0.001)
    assert await policy.acall(flaky) == "ok"
    assert calls["n"] == 3
    assert policy.retries == 2


async def test_retry_gives_up_at_max_attempts():
    calls = {"n": 0}

    async def always():
        calls["n"] += 1
        raise ConnectionError("down")

    with pytest.raises(ConnectionError):
        await RetryPolicy(max_attempts=3,
                          base_delay_s=0.001).acall(always)
    assert calls["n"] == 3


async def test_retry_non_retryable_fails_fast():
    calls = {"n": 0}

    async def bad_config():
        calls["n"] += 1
        raise ValueError("not transient")

    with pytest.raises(ValueError):
        await RetryPolicy(max_attempts=5,
                          base_delay_s=0.001).acall(bad_config)
    assert calls["n"] == 1


def test_retry_sync_and_backoff_growth():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 2:
            raise OSError("flake")
        return calls["n"]

    assert RetryPolicy(max_attempts=2, base_delay_s=0.0).call(flaky) == 2
    delays = list(RetryPolicy(max_attempts=4, base_delay_s=0.1,
                              max_delay_s=0.3, jitter=0.0).delays_s())
    assert delays == [0.1, 0.2, 0.3]  # doubling, capped


async def test_retry_never_sleeps_past_the_budget():
    """A backoff that would outlive the remaining budget is not
    slept: the policy re-raises instead of burning the deadline in
    bed and then attempting against a dead client."""
    calls = {"n": 0}

    async def flaky():
        calls["n"] += 1
        raise ConnectionError("flake")

    with deadline_scope(Deadline(0.03)):  # 30ms budget
        with pytest.raises(ConnectionError):
            await RetryPolicy(max_attempts=5, base_delay_s=0.05,
                              jitter=0.0).acall(flaky)  # 50ms backoff
    assert calls["n"] == 1


async def test_retry_stops_when_request_deadline_spent():
    calls = {"n": 0}

    async def flaky():
        calls["n"] += 1
        raise ConnectionError("flake")

    with deadline_scope(Deadline(-1.0)):  # already expired
        with pytest.raises(ConnectionError):
            await RetryPolicy(max_attempts=5,
                              base_delay_s=0.001).acall(flaky)
    assert calls["n"] == 1  # no pointless backoff toward a dead client


def test_retry_http_4xx_is_permanent_5xx_transient():
    """urllib's HTTPError subclasses OSError, but a 404 is the
    server's final answer — only 5xx replays."""
    import urllib.error

    policy = RetryPolicy()
    not_found = urllib.error.HTTPError("http://x", 404, "nf", {}, None)
    flaky_gw = urllib.error.HTTPError("http://x", 503, "bad", {}, None)
    assert not policy.classify(not_found)
    assert policy.classify(flaky_gw)


def test_retry_permanent_os_errors_fail_fast():
    """FileNotFoundError/PermissionError are OSErrors but the
    environment's final answer — never replayed."""
    policy = RetryPolicy()
    assert not policy.classify(FileNotFoundError("gone"))
    assert not policy.classify(PermissionError("wall"))
    assert policy.classify(ConnectionResetError("wire"))


def test_retry_env_knobs(monkeypatch):
    monkeypatch.setenv("KFS_STORAGE_RETRY_MAX_ATTEMPTS", "7")
    monkeypatch.setenv("KFS_RETRY_BASE_MS", "10")
    policy = RetryPolicy.from_env("KFS_STORAGE")
    assert policy.max_attempts == 7          # edge-specific wins
    assert policy.base_delay_s == 0.01       # generic fallback applies


# ----------------------------------------------------------- breaker


def _clock():
    t = {"now": 0.0}

    def now():
        return t["now"]

    return t, now


def test_breaker_opens_on_window_failures_and_recovers():
    t, now = _clock()
    b = CircuitBreaker(failure_threshold=3, window_s=10.0,
                       reset_timeout_s=5.0, clock=now)
    assert b.allow() and b.state == "closed"
    b.record_failure()
    b.record_failure()
    assert b.state == "closed"
    b.record_failure()
    assert b.state == "open" and not b.allow()
    # Reset timeout passes: half-open admits ONE trial.
    t["now"] = 6.0
    assert b.state == "half_open"
    assert b.allow()
    assert not b.allow()  # second trial blocked
    b.record_success()
    assert b.state == "closed" and b.allow()


def test_breaker_half_open_failure_reopens():
    t, now = _clock()
    b = CircuitBreaker(failure_threshold=1, reset_timeout_s=5.0,
                       clock=now)
    b.record_failure()
    t["now"] = 6.0
    assert b.allow()          # the half-open trial
    b.record_failure()        # trial failed
    assert b.state == "open"
    t["now"] = 10.0           # reset clock restarted at t=6
    assert b.state == "open"
    t["now"] = 11.1
    assert b.state == "half_open"


def test_breaker_window_prunes_old_failures():
    t, now = _clock()
    b = CircuitBreaker(failure_threshold=3, window_s=5.0, clock=now)
    b.record_failure()
    t["now"] = 6.0  # first failure ages out of the window
    b.record_failure()
    b.record_failure()
    assert b.state == "closed"  # only 2 inside the window


def test_breaker_external_recovery_mode():
    """half_open_max=0 (the router's mode): no traffic-driven trials;
    only an external health probe (reset/record_success) closes it."""
    t, now = _clock()
    b = CircuitBreaker(failure_threshold=1, reset_timeout_s=0.1,
                       half_open_max=0, clock=now)
    b.record_failure()
    t["now"] = 100.0
    assert not b.allow()  # still blocked long after reset timeout
    b.record_success()
    assert b.allow()


# ---------------------------------------------------------- deadline


def test_deadline_header_parsing():
    assert Deadline.from_headers({}) is None
    assert Deadline.from_headers({"x-request-timeout-ms": "junk"}) is None
    assert Deadline.from_headers({"x-request-timeout-ms": "-5"}) is None
    # float() parses these, but a non-finite budget would poison every
    # downstream comparison — they mean "no deadline".
    assert Deadline.from_headers({"x-request-timeout-ms": "nan"}) is None
    assert Deadline.from_headers({"x-request-timeout-ms": "inf"}) is None
    dl = Deadline.from_headers({"x-request-timeout-ms": "30000"})
    assert dl is not None and not dl.expired
    assert 29.0 < dl.remaining_s() <= 30.0


def test_deadline_expiry_and_scope():
    assert current_deadline() is None
    with deadline_scope(Deadline(60.0)) as dl:
        assert current_deadline() is dl
        dl.raise_if_expired()  # plenty left
        with deadline_scope(Deadline(-0.001)) as inner:
            assert inner.expired
            with pytest.raises(DeadlineExceeded):
                inner.raise_if_expired("test")
        assert current_deadline() is dl  # nesting restores
    assert current_deadline() is None


def test_deadline_exceeded_is_504():
    assert DeadlineExceeded("x").status_code == 504


# ------------------------------------------------------------ faults


def test_faults_fail_first_is_deterministic():
    faults.configure({"storage.download": {"fail_first": 2}})
    for _ in range(2):
        with pytest.raises(FaultInjected):
            faults.inject_sync("storage.download", key="s3://m")
    faults.inject_sync("storage.download", key="s3://m")  # 3rd: clean
    assert faults.stats()["storage.download"]["injected"] == 2


def test_faults_seeded_error_rate_and_match():
    faults.configure({"client.request": {"error_rate": 0.5, "seed": 1,
                                         "match": ":8081"}})

    def outcomes():
        hits = []
        for _ in range(20):
            try:
                faults.inject_sync("client.request",
                                   key="http://h:8081/x")
                hits.append(0)
            except FaultInjected:
                hits.append(1)
        return hits

    first = outcomes()
    assert 1 in first and 0 in first
    faults.configure({"client.request": {"error_rate": 0.5, "seed": 1,
                                         "match": ":8081"}})
    assert outcomes() == first  # seeded: the sequence reproduces
    # Non-matching key: never injected.
    faults.inject_sync("client.request", key="http://h:9000/x")


def test_faults_env_config(monkeypatch):
    monkeypatch.setenv("KFS_FAULTS",
                       json.dumps({"agent.pull": {"fail_first": 1}}))
    faults.reset()
    with pytest.raises(FaultInjected):
        faults.inject_sync("agent.pull", key="m")
    faults.inject_sync("agent.pull", key="m")


def test_faults_configure_rejects_typos_atomically():
    """A typo'd knob raises AND installs nothing — including the
    valid sites in the same config (no half-applied fault plans)."""
    with pytest.raises(TypeError, match="latncy_ms"):
        faults.configure({
            "storage.download": {"error_rate": 0.5},
            "router.dispatch": {"latncy_ms": 50}})
    faults.inject_sync("storage.download", key="x")  # nothing active
    # Internal bookkeeping fields are not config knobs either.
    with pytest.raises(TypeError, match="calls"):
        faults.configure({"agent.pull": {"fail_first": 2, "calls": 2}})


def test_fault_injected_classifies_as_transient():
    assert isinstance(FaultInjected("site"), ConnectionError)
    assert RetryPolicy().classify(FaultInjected("site"))


# ----------------------------------------- chaos: batcher queue 504


@pytest.mark.chaos
async def test_batcher_expired_deadline_504_without_batch_slot():
    """A queued request whose budget dies while the engine is busy is
    failed with DeadlineExceeded (504) and its instances NEVER reach
    the handler — no batch slot is wasted on it."""
    from kfserving_tpu.batching import DynamicBatcher

    release = asyncio.Event()
    seen = []

    async def handler(instances):
        seen.append(list(instances))
        await release.wait()
        return instances

    batcher = DynamicBatcher(handler, max_batch_size=1,
                             max_latency_ms=50, max_inflight=1)
    # A fills the single inflight slot and blocks in the handler.
    a = asyncio.ensure_future(batcher.submit(["a"]))
    await asyncio.sleep(0.01)
    assert seen == [["a"]]
    # B queues behind it with a 30ms budget it cannot meet.
    with deadline_scope(Deadline(0.03)):
        b = asyncio.ensure_future(batcher.submit(["b"]))
        await asyncio.sleep(0)
    with pytest.raises(DeadlineExceeded):
        await asyncio.wait_for(b, timeout=2.0)
    release.set()
    assert (await a).predictions == ["a"]
    await batcher.flush()
    assert seen == [["a"]]  # the expired request never executed


@pytest.mark.chaos
async def test_batcher_expired_request_pruned_at_flush():
    """Even without the expiry timer winning the race, a flush prunes
    over-budget waiters before committing slots (the pre-flush reap)."""
    from kfserving_tpu.batching import DynamicBatcher

    seen = []

    async def handler(instances):
        seen.append(list(instances))
        return instances

    batcher = DynamicBatcher(handler, max_batch_size=8,
                             max_latency_ms=60)
    with deadline_scope(Deadline(0.02)):
        doomed = asyncio.ensure_future(batcher.submit(["doomed"]))
        await asyncio.sleep(0)
    live = asyncio.ensure_future(batcher.submit(["live1", "live2"]))
    await asyncio.sleep(0.03)  # doomed's budget dies pre-flush
    assert (await live).predictions == ["live1", "live2"]
    with pytest.raises(DeadlineExceeded):
        await doomed
    assert all("doomed" not in batch for batch in seen)


@pytest.mark.chaos
async def test_batcher_cancelled_submit_withdraws_instances():
    """Client disconnect: cancelling a queued submit withdraws its
    instances, so siblings batch without it."""
    from kfserving_tpu.batching import DynamicBatcher

    seen = []

    async def handler(instances):
        seen.append(list(instances))
        return instances

    batcher = DynamicBatcher(handler, max_batch_size=8,
                             max_latency_ms=40)
    gone = asyncio.ensure_future(batcher.submit(["gone"]))
    await asyncio.sleep(0)
    kept = asyncio.ensure_future(batcher.submit(["kept"]))
    await asyncio.sleep(0)
    gone.cancel()
    assert (await kept).predictions == ["kept"]
    assert seen == [["kept"]]
    with pytest.raises(asyncio.CancelledError):
        await gone


@pytest.mark.chaos
async def test_server_times_out_queued_request_with_504(tmp_path):
    """End to end over HTTP: x-request-timeout-ms shorter than the
    queue wait yields 504 (ISSUE acceptance #3)."""
    from kfserving_tpu.model.model import Model
    from tests.utils import http_json, running_server

    release = asyncio.Event()

    class SlowModel(Model):
        def __init__(self):
            super().__init__("slow")
            self.ready = True
            self.calls = 0

        async def predict(self, request):
            self.calls += 1
            await release.wait()
            return {"predictions": [1]}

    model = SlowModel()
    async with running_server([model],
                              container_concurrency=1) as server:
        # Occupy the single admission slot.
        hog = asyncio.ensure_future(http_json(
            server.http_port, "POST", "/v1/models/slow:predict",
            {"instances": [[1.0]]}))
        for _ in range(100):
            if model.calls:
                break
            await asyncio.sleep(0.01)
        # This one waits in the admission queue past its 50ms budget.
        status, body = await http_json(
            server.http_port, "POST", "/v1/models/slow:predict",
            {"instances": [[2.0]]},
            headers={"x-request-timeout-ms": "50"})
        assert status == 504
        assert "deadline" in body["error"]
        assert model.calls == 1  # the expired request never ran
        release.set()
        status, _ = await hog
        assert status == 200


@pytest.mark.chaos
async def test_lazy_model_load_is_not_aborted_by_request_deadline():
    """A short-budget request that triggers the lazy load must not
    kill the (shared, multi-second) load mid-warmup: the load runs
    outside the deadline scope and completes; the triggering request
    still gets its own 504 afterwards."""
    from kfserving_tpu.model.model import Model
    from kfserving_tpu.model.repository import ModelRepository
    from kfserving_tpu.reliability.deadline import check_deadline
    from kfserving_tpu.server.dataplane import DataPlane

    class LazyModel(Model):
        def load(self):
            # Stands in for engine warmup's dispatch-time check: must
            # NOT see the request's expired budget during load.
            check_deadline("warmup dispatch")
            self.ready = True
            return True

        async def predict(self, request):
            return {"predictions": [1]}

    repo = ModelRepository()
    model = LazyModel("lazy")
    repo.update(model)
    dp = DataPlane(repo)
    with deadline_scope(Deadline(-1.0)):  # budget already spent
        with pytest.raises(DeadlineExceeded):
            await dp.infer("lazy", {"instances": [[1.0]]})
    assert model.ready  # the load itself survived and is reusable
    result = await dp.infer("lazy", {"instances": [[1.0]]})
    assert result == {"predictions": [1]}


# --------------------------------------- chaos: puller retry edges


@pytest.mark.chaos
async def test_puller_retry_then_succeed(tmp_path):
    """Deterministic fail-twice at the pull edge: the puller's retry
    policy replays and the model loads."""
    from kfserving_tpu.agent.downloader import Downloader
    from kfserving_tpu.agent.puller import Puller

    class _Repo:
        def __init__(self):
            self.loaded = []

        async def load(self, name):
            self.loaded.append(name)
            return True

    src = tmp_path / "artifact"
    src.mkdir()
    (src / "weights").write_text("w")
    faults.configure({"agent.pull": {"fail_first": 2}})
    repo = _Repo()
    puller = Puller(repo, Downloader(str(tmp_path / "models")),
                    retry=RetryPolicy(max_attempts=3,
                                      base_delay_s=0.001))
    await puller.start()
    try:
        await puller.events.put(
            ("load", "m", {"storageUri": f"file://{src}"}))
        for _ in range(300):
            if repo.loaded:
                break
            await asyncio.sleep(0.01)
        assert repo.loaded == ["m"]
        assert puller.ops_failed == 0
        assert faults.stats()["agent.pull"]["injected"] == 2
    finally:
        await puller.stop()


@pytest.mark.chaos
async def test_pulls_survive_ten_percent_error_rate(tmp_path):
    """ISSUE acceptance #1: with a 10% injected error rate on the
    pull edge, every model pull still succeeds via retries."""
    from kfserving_tpu.agent.downloader import Downloader
    from kfserving_tpu.agent.puller import Puller

    class _Repo:
        def __init__(self):
            self.loaded = []

        async def load(self, name):
            self.loaded.append(name)
            return True

    src = tmp_path / "artifact"
    src.mkdir()
    (src / "weights").write_text("w")
    faults.configure({"agent.pull": {"error_rate": 0.1, "seed": 42}})
    repo = _Repo()
    puller = Puller(repo, Downloader(str(tmp_path / "models")),
                    retry=RetryPolicy(max_attempts=5,
                                      base_delay_s=0.001))
    await puller.start()
    try:
        n = 30
        for i in range(n):
            await puller.events.put(
                ("load", f"m{i}", {"storageUri": f"file://{src}"}))
        for _ in range(500):
            if len(repo.loaded) == n:
                break
            await asyncio.sleep(0.01)
        assert sorted(repo.loaded) == sorted(f"m{i}" for i in range(n))
        assert puller.ops_failed == 0
        # The harness really did inject (10% of ~30 calls).
        assert faults.stats()["agent.pull"]["injected"] >= 1
    finally:
        await puller.stop()


@pytest.mark.chaos
def test_storage_download_retries_injected_faults(tmp_path):
    """The storage edge replays transient failures; the marker makes
    the replay idempotent."""
    import http.server
    import threading

    from kfserving_tpu.storage import Storage

    (tmp_path / "src").mkdir()
    (tmp_path / "src" / "artifact.txt").write_text("payload")
    httpd = http.server.HTTPServer(
        ("127.0.0.1", 0), http.server.SimpleHTTPRequestHandler)
    httpd.RequestHandlerClass.directory = None
    cwd = os.getcwd()
    os.chdir(tmp_path / "src")
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    try:
        faults.configure({"storage.download": {"fail_first": 2}})
        os.environ["KFS_STORAGE_RETRY_BASE_MS"] = "1"
        out = tmp_path / "out"
        uri = (f"http://127.0.0.1:{httpd.server_address[1]}"
               f"/artifact.txt")
        Storage.download(uri, str(out))
        assert (out / "artifact.txt").read_text() == "payload"
        assert faults.stats()["storage.download"]["injected"] == 2
    finally:
        os.environ.pop("KFS_STORAGE_RETRY_BASE_MS", None)
        os.chdir(cwd)
        httpd.shutdown()
        thread.join()


# ------------------------------------- chaos: router breaker skip


class _FakeISvc:
    namespace = "default"
    name = "svc"
    transformer = None
    explainer = None


class _FakeTraffic:
    def __init__(self):
        self.percent = 100
        self.revision = "r1"


class _FakeCStatus:
    def __init__(self):
        self.traffic = [_FakeTraffic()]


class _FakeStatus:
    def __init__(self):
        self.components = {"predictor": _FakeCStatus()}


class _FakeReplica:
    def __init__(self, host):
        self.component_id = "default/svc/predictor"
        self.revision = "r1"
        self.host = host


class _FakeOrch:
    def __init__(self, hosts):
        self.state = {"default/svc/predictor": None}
        self._replicas = [_FakeReplica(h) for h in hosts]

    def replicas(self, cid):
        return [r for r in self._replicas if r.component_id == cid]

    async def delete_replica(self, replica):
        self._replicas.remove(replica)


class _FakeReconciler:
    def __init__(self, orch):
        self.orchestrator = orch
        self.status = {"default/svc": _FakeStatus()}
        self.scale_calls = 0

    def component_id(self, isvc, cname):
        return f"{isvc.namespace}/{isvc.name}/{cname}"

    async def scale(self, isvc, cname, n):
        self.scale_calls += 1  # no capacity appears; buffer sheds


class _FakeController:
    def __init__(self, orch):
        self.reconciler = _FakeReconciler(orch)
        self._isvc = _FakeISvc()

    def get(self, name):
        return self._isvc if name == "svc" else None


class _Replica:
    """A minimal controllable HTTP replica: answers 200 JSON, or (in
    hang mode) accepts connections and never responds — including its
    liveness route, like a wedged process."""

    def __init__(self):
        self.hanging = False
        self.server = None
        self.host = None
        self.heads = []  # raw request heads, for header assertions
        self._handlers = set()  # open connection handler tasks

    async def start(self):
        async def handle(reader, writer):
            task = asyncio.current_task()
            self._handlers.add(task)
            task.add_done_callback(self._handlers.discard)
            try:
                self.heads.append(
                    await reader.readuntil(b"\r\n\r\n"))
                while self.hanging:
                    await asyncio.sleep(0.02)
                body = b'{"predictions": [1]}'
                writer.write(
                    b"HTTP/1.1 200 OK\r\ncontent-type: application/"
                    b"json\r\ncontent-length: %d\r\n"
                    b"connection: close\r\n\r\n%s" % (len(body), body))
                await writer.drain()
            finally:
                writer.close()

        self.server = await asyncio.start_server(
            handle, "127.0.0.1", 0)
        port = self.server.sockets[0].getsockname()[1]
        self.host = f"127.0.0.1:{port}"

    async def stop(self):
        # wait_closed() waits for every open connection (Python 3.12),
        # and a hang-mode handler never finishes on its own: release
        # it and cancel whatever is still open before waiting.
        self.hanging = False
        self.server.close()
        for task in list(self._handlers):
            task.cancel()
        await asyncio.wait_for(self.server.wait_closed(), timeout=5.0)


@pytest.mark.chaos
async def test_router_skips_open_breaker_replica():
    """ISSUE acceptance #2: one replica in hang mode — the breaker
    opens after its timeout and every subsequent request completes on
    the healthy replica (no error storm, no eviction of the hung
    one)."""
    import aiohttp

    from kfserving_tpu.control.router import IngressRouter

    hung, healthy = _Replica(), _Replica()
    await hung.start()
    await healthy.start()
    hung.hanging = True
    orch = _FakeOrch([hung.host, healthy.host])
    router = IngressRouter(
        _FakeController(orch), upstream_timeout_s=0.3,
        buffer_deadline_s=0.1,
        breaker_factory=lambda host: CircuitBreaker(
            failure_threshold=1, window_s=10.0, reset_timeout_s=60.0,
            half_open_max=0, name=host))
    await router.start_async()
    try:
        url = (f"http://127.0.0.1:{router.http_port}"
               f"/v1/models/svc:predict")
        statuses = []
        async with aiohttp.ClientSession() as session:
            for _ in range(6):
                async with session.post(
                        url, json={"instances": [[1.0]]}) as resp:
                    statuses.append(resp.status)
        # Round-robin starts at the hung replica: exactly one 504
        # (its breaker opens), then everything lands healthy.
        assert statuses[0] == 504
        assert statuses[1:] == [200] * 5
        assert router._breakers[hung.host].state == "open"
        # The hung replica was skipped, not evicted.
        assert {r.host for r in orch.replicas("default/svc/predictor")} \
            == {hung.host, healthy.host}
    finally:
        await router.stop_async()
        await hung.stop()
        await healthy.stop()


@pytest.mark.chaos
async def test_router_reprobe_recovers_replica():
    """A recovered replica rejoins rotation via the background health
    reprobe (never via a trial request)."""
    import aiohttp

    from kfserving_tpu.control.router import IngressRouter

    replica = _Replica()
    await replica.start()
    replica.hanging = True
    orch = _FakeOrch([replica.host])
    router = IngressRouter(
        _FakeController(orch), upstream_timeout_s=0.3,
        buffer_deadline_s=0.05,
        breaker_factory=lambda host: CircuitBreaker(
            failure_threshold=1, window_s=10.0, reset_timeout_s=0.1,
            half_open_max=0, name=host))
    await router.start_async()
    try:
        url = (f"http://127.0.0.1:{router.http_port}"
               f"/v1/models/svc:predict")
        async with aiohttp.ClientSession() as session:
            async with session.post(
                    url, json={"instances": [[1.0]]}) as resp:
                assert resp.status == 504  # hang -> breaker opens
            async with session.post(
                    url, json={"instances": [[1.0]]}) as resp:
                assert resp.status == 503  # skipped while open
            # Breaker-skipped != scale-from-zero: a replica EXISTS, so
            # the shed is immediate — no activator scale() churn, no
            # buffer-deadline parking.
            assert router.controller.reconciler.scale_calls == 0
            replica.hanging = False       # process recovers
            # Reprobe closes the breaker and drops the entry
            # (absence == closed; the map holds only sick hosts).
            for _ in range(100):
                if replica.host not in router._breakers:
                    break
                await asyncio.sleep(0.05)
            assert replica.host not in router._breakers
            async with session.post(
                    url, json={"instances": [[1.0]]}) as resp:
                assert resp.status == 200  # back in rotation
    finally:
        await router.stop_async()
        await replica.stop()


@pytest.mark.chaos
async def test_router_dispatch_fault_fails_over():
    """An injected pre-dispatch fault at the router edge behaves like
    a refused connection: evict + fail over to the next replica."""
    import aiohttp

    from kfserving_tpu.control.router import IngressRouter

    bad, good = _Replica(), _Replica()
    await bad.start()
    await good.start()
    faults.configure({"router.dispatch": {"fail_first": 1,
                                          "match": bad.host}})
    orch = _FakeOrch([bad.host, good.host])
    router = IngressRouter(_FakeController(orch),
                           upstream_timeout_s=1.0,
                           buffer_deadline_s=0.1)
    await router.start_async()
    try:
        url = (f"http://127.0.0.1:{router.http_port}"
               f"/v1/models/svc:predict")
        async with aiohttp.ClientSession() as session:
            async with session.post(
                    url, json={"instances": [[1.0]]}) as resp:
                assert resp.status == 200  # failover absorbed it
        hosts = {r.host
                 for r in orch.replicas("default/svc/predictor")}
        assert bad.host not in hosts  # evicted like a dead process
    finally:
        await router.stop_async()
        await bad.stop()
        await good.stop()


@pytest.mark.chaos
async def test_router_hang_fault_opens_breaker():
    """hang_s at the router edge rides the upstream timeout envelope:
    it produces the TimeoutError a real hung replica would, feeding
    the breaker — the env-knob soak path of ISSUE acceptance #2."""
    import aiohttp

    from kfserving_tpu.control.router import IngressRouter

    hung, healthy = _Replica(), _Replica()
    await hung.start()
    await healthy.start()
    faults.configure({"router.dispatch": {"hang_s": 30.0,
                                          "match": hung.host}})
    orch = _FakeOrch([hung.host, healthy.host])
    router = IngressRouter(
        _FakeController(orch), upstream_timeout_s=0.2,
        buffer_deadline_s=0.1,
        breaker_factory=lambda host: CircuitBreaker(
            failure_threshold=1, window_s=10.0, reset_timeout_s=60.0,
            half_open_max=0, name=host))
    await router.start_async()
    try:
        url = (f"http://127.0.0.1:{router.http_port}"
               f"/v1/models/svc:predict")
        statuses = []
        async with aiohttp.ClientSession() as session:
            for _ in range(4):
                async with session.post(
                        url, json={"instances": [[1.0]]}) as resp:
                    statuses.append(resp.status)
        assert statuses[0] == 504          # injected hang timed out
        assert statuses[1:] == [200] * 3   # breaker skips, healthy serves
        assert router._breakers[hung.host].state == "open"
    finally:
        await router.stop_async()
        await hung.stop()
        await healthy.stop()


@pytest.mark.chaos
async def test_router_sheds_buffered_request_at_budget():
    """A budgeted request that finds no capacity is shed when ITS
    budget dies, not after the router's full 60s activator buffer."""
    import time as _time

    import aiohttp

    from kfserving_tpu.control.router import IngressRouter

    orch = _FakeOrch([])  # scale-from-zero, and nothing ever comes up
    router = IngressRouter(_FakeController(orch),
                           buffer_deadline_s=30.0)
    await router.start_async()
    try:
        url = (f"http://127.0.0.1:{router.http_port}"
               f"/v1/models/svc:predict")
        t0 = _time.monotonic()
        async with aiohttp.ClientSession() as session:
            async with session.post(
                    url, json={"instances": [[1.0]]},
                    headers={"x-request-timeout-ms": "150"}) as resp:
                # 504, not 503: the budget is spent, so "retry
                # elsewhere" would be a lie — same verdict as every
                # other expiry path.
                assert resp.status == 504
        assert _time.monotonic() - t0 < 2.0  # not the 30s buffer
    finally:
        await router.stop_async()


@pytest.mark.chaos
async def test_router_forwards_decremented_budget():
    """The replica receives the REMAINING budget, not the original —
    router queueing time is never granted twice."""
    import aiohttp

    from kfserving_tpu.control.router import IngressRouter

    replica = _Replica()
    await replica.start()
    orch = _FakeOrch([replica.host])
    router = IngressRouter(_FakeController(orch))
    await router.start_async()
    try:
        url = (f"http://127.0.0.1:{router.http_port}"
               f"/v1/models/svc:predict")
        async with aiohttp.ClientSession() as session:
            async with session.post(
                    url, json={"instances": [[1.0]]},
                    headers={"x-request-timeout-ms": "5000"}) as resp:
                assert resp.status == 200
        head = replica.heads[-1].decode("latin1").lower()
        line = next(ln for ln in head.split("\r\n")
                    if ln.startswith("x-request-timeout-ms:"))
        forwarded = float(line.split(":", 1)[1])
        assert 0 < forwarded < 5000
    finally:
        await router.stop_async()
        await replica.stop()


# ------------------------------------------- chaos: client retries


@pytest.mark.chaos
async def test_client_retries_connection_faults(tmp_path):
    from kfserving_tpu.client import KFServingClient
    from kfserving_tpu.model.model import Model
    from tests.utils import running_server

    class Echo(Model):
        def __init__(self):
            super().__init__("echo")
            self.ready = True

        async def predict(self, request):
            return {"predictions": request["instances"]}

    async with running_server([Echo()]) as server:
        faults.configure({"client.request": {"fail_first": 2}})
        client = KFServingClient(
            "http://127.0.0.1:1",  # control plane unused here
            f"http://127.0.0.1:{server.http_port}",
            retry=None)
        client._retry = RetryPolicy(
            max_attempts=3, base_delay_s=0.001,
            retry_on=(ConnectionError,))
        try:
            result = await client.predict("echo",
                                          {"instances": [[1.0]]})
            assert result == {"predictions": [[1.0]]}
            assert faults.stats()["client.request"]["injected"] == 2
        finally:
            await client.close()


# --------------------------------- generation deadline (decode loop)


@pytest.mark.chaos
async def test_generation_expires_between_decode_steps(tmp_path):
    """A generation whose budget dies mid-decode finishes with reason
    "timeout" at a wave boundary and frees its slot (no decoding to
    the token budget for a dead client)."""
    import numpy as np

    from kfserving_tpu.engine.generator import GenerationEngine
    from kfserving_tpu.models import create_model, init_params

    spec = create_model("decoder_tiny", num_layers=1, hidden_size=32,
                        num_heads=2, intermediate_size=64, max_seq=64)
    engine = GenerationEngine(spec.module, init_params(spec, seed=0),
                              max_slots=2, max_seq=64,
                              prefill_buckets=[16])
    try:
        with deadline_scope(Deadline(0.75)):
            req = engine.submit(np.arange(4), max_new_tokens=500)
        tokens, reason = [], None
        async for token, fin in engine.stream(req):
            if token is not None:
                tokens.append(token)
            if fin is not None:
                reason = fin
        assert reason == "timeout"
        assert len(tokens) < 500
        assert engine.load_gauges()["active_slots"] == 0
    finally:
        await engine.close()
